"""Program names and parts of the model (``orion_tpu.obs.parts``), and their
reader ``benchmarks/trace/scopes.py``: every serve program of every tiny
preset carries its name and books each matmul and kernel to exactly one part;
the reader's arithmetic on hand-made events and on a cut of a chip trace; the
new metrics under the contract's rules. Nothing here is a device time of this
machine."""

import copy
import functools
import json
import re

import jax
import pytest

from benchmarks.harness.cell import Cell, load_benchmark
from benchmarks.trace import scopes
from orion_tpu.obs import parts as vocab
from tests.benchmark.conftest import REPO

PARTS, OUTER = vocab.PARTS, vocab.STEP_SCOPES
MS = 1_000_000

# -- (a) the programs: names, and a part on every matmul and kernel ------------

BASE = ["inference.max_seq_len=128", "inference.page_size=16",
        "inference.num_pages=64", "inference.max_batch_size=4",
        "inference.prefill_chunk=16", "inference.decode_window=2"]
CHUNKED = ["inference.chunked_prefill=true",
           "inference.prefill_chunk_tokens=16"]
SPECULATIVE = ["inference.speculative=true", "inference.speculate_tokens=3"]
# preset -> (overrides, the stems its engine runs on PROMPTS)
ENGINES = {
    "tiny-llama": (BASE, {"prefill", "decode"}),
    "tiny-llama+chunked": (BASE + CHUNKED, {"mixed"}),
    "tiny-llama+speculative": (BASE + SPECULATIVE, {"verify"}),
    "tiny-llama+chunked+speculative": (
        BASE + CHUNKED + SPECULATIVE, {"mixed_verify"}),
    "tiny-mixtral": (BASE, {"prefill", "decode"}),
    "tiny-laguna": ([], {"prefill", "decode"}),
    "tiny-brumby": (["inference.decode_window=4"],
                    {"prefill", "decode", "fold"}),
    "tiny-glm": ([], {"prefill", "decode"}),
}
CASES = [(name, stem) for name, (_, stems) in ENGINES.items()
         for stem in sorted(stems)]
# The first prompt holds 100 of the 256 token ids in order: the n-gram
# proposer drafts from it (verify), it spans seven chunks (mixed) and, at a
# fold chunk of 16, a retention model folds while it decodes. The last is as
# long and comes behind it: while it is still read in chunks, the first
# decodes on drafts (mixed_verify).
PROMPTS = [list(range(1, 101)), [4, 5, 6, 7], [8, 9], list(range(100, 200))]


@functools.lru_cache(maxsize=None)
def programs(name: str) -> dict:
    """{stem: (HLO as lowered, compiled text)} of every dispatch program the
    engine of ``name`` ran on PROMPTS: each program as
    ``executor.jit_program`` built it, lowered again on the shapes it was
    called with."""
    from orion_tpu.config import get_config
    from orion_tpu.infer import InferenceEngine, executor
    from orion_tpu.models import init_params

    preset, overrides = name.split("+")[0], ENGINES[name][0]
    cfg = get_config(preset, list(overrides))
    jitted: dict = {}
    called: dict = {}
    real_jit, real_build = jax.jit, executor.DispatchExecutor.jit_program

    def build(self, stem, mcfg, mesh):
        def jit(fn, **kw):
            jitted[stem] = real_jit(fn, **kw)

            def spy(*args, **kwargs):
                called.setdefault(stem, jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
                    if hasattr(a, "shape") else a, (args, kwargs)))
                return jitted[stem](*args, **kwargs)

            return spy

        executor.jax.jit = jit
        try:
            return real_build(self, stem, mcfg, mesh)
        finally:
            executor.jax.jit = real_jit

    executor.DispatchExecutor.jit_program = build
    try:
        eng = InferenceEngine(
            cfg, init_params(cfg.model, jax.random.key(0)), seed=0)
    finally:
        executor.DispatchExecutor.jit_program = real_build
    for p in PROMPTS:
        eng.submit_request(p, 24)
    while eng.has_work():
        eng.step()
    eng.close()
    out = {}
    for stem, (args, kwargs) in called.items():
        lowered = jitted[stem].lower(*args, **kwargs)
        out[stem.removesuffix("_defaults")] = (
            lowered.as_text(dialect="hlo", debug_info=True),
            lowered.compile().as_text())
    return out


_HEAD = re.compile(r"^(?:ENTRY )?(%?[\w.\-]+) .*\{$")
_INST = re.compile(r"^\s+(?:ROOT )?(%?[\w.\-]+) = (.*)$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=(%?[\w.\-]+)")
_NAME = re.compile(r'op_name="([^"]*)"')
JUDGED = ("dot", "convolution", "custom-call")


def _opcode(rest: str) -> str:
    """The opcode of an instruction's text behind `` = ``: what follows its
    type, which for a tuple is in brackets and holds spaces."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if not depth:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    m = re.match(r"\s*([\w\-]+)\(", rest)
    return m.group(1) if m else ""


def instructions(text: str, inline: bool):
    """(instruction, opcode, op_name, holds a judged opcode) of every
    instruction of a module's text. ``inline``: the text is HLO as lowered,
    where a function that is called (a scan's body) names its instructions
    from its own top, and the compiler's inliner puts the call's ``op_name``
    in front: here that is done by hand, through every ``call`` on the
    way."""
    comps: dict = {}
    comp = None
    for line in text.splitlines():
        head = _HEAD.match(line)
        if head:
            comp = comps.setdefault(head.group(1), [])
            continue
        m = _INST.match(line)
        if m and comp is not None:
            name = _NAME.search(line)
            comp.append((m.group(1), _opcode(m.group(2)),
                         name.group(1) if name else None,
                         _CALLS.findall(line)))
    prefix = {c: "" for c in comps}
    holds = {c: any(op in JUDGED for _, op, _, _ in insts)
             for c, insts in comps.items()}
    if inline:
        # A computation is printed behind the ones it calls: from the entry
        # back, every caller's prefix is known before its callees'.
        for c in reversed(list(comps)):
            for _, op, name, callees in comps[c]:
                for callee in callees:
                    prefix[callee] = prefix[c] + (
                        f"{name}/" if op == "call" and name else "")
    for c, insts in comps.items():
        for inst, op, name, callees in insts:
            yield (inst, op, prefix[c] + name if name else None,
                   op in JUDGED
                   or op == "fusion" and any(holds.get(k) for k in callees))


@pytest.mark.parametrize("name,stem", CASES)
def test_a_program_carries_its_name_and_every_matmul_a_part(name, stem):
    lowered, compiled = programs(name)[stem]
    program = vocab.PROGRAM_NAMES[stem]
    tops = {p.split("/")[0] for p in PARTS}
    # As lowered every instruction has its path. The CPU's compiler then
    # rewrites some dots into ones that carry no metadata at all: those
    # alone are passed over in the compiled text, whose paths the inliner
    # made and the program's name leads.
    for text, is_compiled in ((lowered, False), (compiled, True)):
        assert text.startswith(f"HloModule jit_{program},")
        found = list(instructions(text, inline=not is_compiled))
        judged = [(i, n) for i, _, n, judge in found
                  if judge and not (is_compiled and n is None)]
        assert judged or is_compiled, "no matmul or kernel: nothing is read"
        for inst, op_name in judged:
            assert op_name is not None, inst
            assert scopes.part_of(op_name, PARTS) in PARTS, (inst, op_name)
            assert sum(c in tops for c in op_name.split("/")) == 1, op_name
            assert not is_compiled or op_name.startswith(
                f"jit({program})/"), op_name
        # no scope outside the vocabulary: what follows a parent is one of
        # its children, in every instruction of the module
        for op_name in {n for _, _, n, _ in found if n}:
            assert scopes.part_of(op_name, PARTS) in PARTS + (
                scopes.UNSCOPED,), op_name


def test_only_the_decode_program_is_called_decode_window():
    names = vocab.PROGRAM_NAMES
    assert [s for s, n in names.items() if "decode_window" in n] == ["decode"]
    assert all(n.startswith("orion_") for n in names.values())
    from orion_tpu.infer.executor import DispatchExecutor

    assert set(names) == set(DispatchExecutor.PROGRAM_FNS)


# -- (b) the reader's arithmetic ------------------------------------------------

DECODE = "jit(orion_decode_window)/while/body/closed_call/"
LAYER = DECODE + "while/body/closed_call/"


def hand_made() -> dict:
    """Two runs of a decode window (a ``while`` that holds the body's
    operations, and a tail under no part), one prefill, an operation behind
    every program, and the run spans that launched them."""
    mods = [["jit_orion_decode_window(7)", 10 * MS, 20 * MS],
            ["jit_orion_decode_window(7)", 40 * MS, 20 * MS],
            ["jit_orion_prefill(9)", 70 * MS, 10 * MS],
            ["jit__threefry_split(3)", 39 * MS, MS // 10]]
    window = lambda t: [
        ["while.1_while", t, 18 * MS, "jit(orion_decode_window)/while"],
        ["fusion.1_fusion", t, 2 * MS, LAYER + "attention/qkv/dot_general"],
        ["paged_decode.1_custom-call", t + 2 * MS, 3 * MS,
         LAYER + "attention/kernel/jit(_call)/paged_decode/pallas_call"],
        ["fusion.2_fusion", t + 5 * MS, 9 * MS,
         LAYER + "mlp_moe/experts/ebcd,edf->ebcf/dot_general"],
        ["fusion.3_fusion", t + 14 * MS, 1 * MS,
         LAYER + "attention/mul"],                    # a parent, no child
        ["fusion.4_fusion", t + 15 * MS, 2 * MS,
         DECODE + "unembed/bsd,dv->bsv/dot_general"],
        ["fusion.5_fusion", t + 17 * MS, 1 * MS, DECODE + "sample/argmax"],
        ["copy.1_copy", t + 18 * MS, 1 * MS, None],
    ]
    ops = window(10 * MS) + window(40 * MS) + [
        ["gmm.1_custom-call", 70 * MS, 6 * MS,
         "jit(orion_prefill)/while/body/closed_call/mlp_moe/experts/gmm"],
        ["fusion.9_fusion", 76 * MS, 3 * MS,
         "jit(orion_prefill)/while/body/closed_call/attention/out/dot_general"],
        ["copy.9_copy", 90 * MS, 5 * MS,
         "jit(orion_prefill)/embed/gather"],          # behind every program
        ["fusion.0_fusion", 39 * MS, MS // 10, None],
    ]
    host = [["orion/decode/run", 9 * MS + MS // 2, 21 * MS],
            ["orion/decode/run", 39 * MS + MS // 4, 21 * MS],
            ["orion/prefill/run", 68 * MS, 13 * MS],
            ["orion/decode/build", 38 * MS, MS]]
    return {"devices": {"0": {"XLA Modules": mods, "XLA Ops": ops}},
            "host": host}


@pytest.mark.parametrize("op_name,part", [
    (LAYER + "attention/qkv/bsd,dh->bsh/dot_general", "attention/qkv"),
    (LAYER + "attention/qkv/latent/down/dot_general", "attention/qkv"),
    (LAYER + "attention/kernel/latent/absorb/dot_general", "attention/kernel"),
    (LAYER + "attention/kernel/while/body/closed_call/dot_general",
     "attention/kernel"),
    (LAYER + "mlp_moe/dispatch/jit(take_along_axis)/gather",
     "mlp_moe/dispatch"),
    (LAYER + "attention/mul", "attention"),
    (DECODE + "unembed/rmsnorm/pallas_call", "unembed"),
    (DECODE + "sample/argmax", "sample"),
    ("jit(orion_decode_window)/while/body/dynamic_slice", "unscoped"),
    ("jit(train_step)/fwd_bwd/transpose(jvp())/while/body/closed_call/"
     "checkpoint/attention/kernel/flash_bwd", "attention/kernel"),
    ("jit(train_step)/fwd_bwd/transpose(jvp(unembed))/bsd,dv->bsv/dot_general",
     "unembed"),
    ("jit(train_step)/fwd_bwd/jvp(embed)/convert_element_type", "embed"),
    ("jit(train_step)/fwd_bwd/jvp(jit(log_softmax))/sub", "fwd_bwd"),
    ("jit(train_step)/optimizer/mul", "optimizer"),
    ("jit(train_step)/fwd_bwd/reduce_sum", "fwd_bwd"),
    (None, "unscoped"),
])
def test_an_op_name_lies_under_one_part(op_name, part):
    assert scopes.part_of(op_name, PARTS, OUTER) == part


def test_leaves_only_and_the_parts_add_up_to_the_programs_seconds():
    got = scopes.attribute(hand_made(), PARTS, OUTER)
    dec = got["by"]["orion_decode_window"]
    assert got["module_n"]["orion_decode_window"] == 2
    assert got["module_s"]["orion_decode_window"] == pytest.approx(0.040)
    # the ``while`` holds the body's operations and is counted nowhere
    assert dec["attention/qkv"] == pytest.approx(0.004)
    assert dec["attention/kernel"] == pytest.approx(0.006)
    assert dec["mlp_moe/experts"] == pytest.approx(0.018)
    assert dec["attention"] == pytest.approx(0.002)
    assert dec["unembed"] == pytest.approx(0.004)
    assert dec["sample"] == pytest.approx(0.002)
    # under no part: the copy, and the millisecond a window in which no
    # operation ran
    assert dec["unscoped"] == pytest.approx(0.004)
    assert got["unscoped_ops"]["orion_decode_window"] == pytest.approx(
        {"copy.1_copy": 0.002, scopes.BETWEEN: 0.002})
    for prog, parts in got["by"].items():
        assert sum(parts.values()) == pytest.approx(got["module_s"][prog])
    # the operation behind every program is dropped
    pre = got["by"]["orion_prefill"]
    assert "embed" not in pre
    assert pre["mlp_moe/experts"] == pytest.approx(0.006)
    assert pre["unscoped"] == pytest.approx(0.001)


def test_what_the_metrics_read_adds_up():
    got = scopes.attribute(hand_made(), PARTS, OUTER)
    dec = "orion_decode_window"
    named = [scopes.DECODE_ATTN_KERNEL, scopes.DECODE_ATTN_PROJ,
             scopes.DECODE_FFN, scopes.DECODE_HEAD]
    assert [scopes.seconds(got, dec, p) for p in named] == pytest.approx(
        [0.006, 0.004, 0.018, 0.006])
    # a parent with no child is in "every other second", with the unscoped
    assert scopes.rest(got, dec, scopes.DECODE_PARTS) == pytest.approx(0.006)
    assert scopes.seconds(got, "orion_prefill", scopes.PREFILL_ATTN) \
        == pytest.approx(0.003)


def test_the_clock_offset_is_the_least_lag_of_a_launched_program():
    events = hand_made()
    lag = lambda: scopes.attribute(events, PARTS)["clock_offsets_ns"]
    assert lag() == {"orion/decode/run": [MS // 2, 3 * MS // 4, 2],
                     "orion/prefill/run": [2 * MS, 2 * MS, 1]}
    # the device clock ahead of the host's: a program "starts" before the
    # span that launched it
    events["host"][1][1] = 40 * MS + MS // 4
    assert lag()["orion/decode/run"][0] == -MS // 4
    # the key split that still runs when a span opens is nobody's launch
    events["host"][1][1] = 39 * MS
    assert lag()["orion/decode/run"][:2] == [MS // 2, MS]


def test_a_trace_without_a_device_or_a_program_gives_nothing():
    assert scopes.attribute({"devices": {}, "host": []}, PARTS) is None
    assert scopes.attribute(
        {"devices": {"0": {"XLA Ops": []}}, "host": []}, PARTS) is None
    assert scopes.for_obs({"trace": None}) is None
    assert scopes.stem("jit_orion_prefill(123)") == "orion_prefill"
    assert scopes.program_id("jit_orion_prefill(123)") == 123
    assert scopes.instruction(
        "%fusion.12.clone = bf16[8,32]{1,0:T(8,128)(2,1)} fusion(bf16[8] %p), "
        "kind=kLoop, calls=%fused_computation.3") == "fusion.12.clone"


def _message(*fields) -> bytes:
    """A protocol-buffer message of (number, an int or bytes) fields."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    return b"".join(
        varint(num << 3) + varint(val) if isinstance(val, int)
        else varint(num << 3 | 2) + varint(len(val)) + val
        for num, val in fields)


def test_the_programs_hlo_is_read_from_the_profiles_bytes(tmp_path):
    def inst(name, op_name=None):
        meta = [(7, _message((1, b"type"), (2, op_name)))] if op_name else []
        return (2, _message((1, name), (2, b"fusion"), *meta))

    hlo = _message((1, _message(
        (1, b"jit_orion_prefill"),
        (3, _message((1, b"main"),
                     inst(b"fusion.1", b"jit(orion_prefill)/embed/gather"),
                     inst(b"copy.2"))),
        (3, _message((1, b"body"), inst(
            b"gmm.3", b"jit(orion_prefill)/while/body/mlp_moe/experts/gmm"))),
    )))
    meta = _message((1, 2 ** 63 + 5), (2, b"jit_orion_prefill(x)"),
                    (5, _message((1, 1), (6, hlo))))
    space = _message(
        (1, _message((2, b"/device:TPU:0"), (4, _message((1, 1), (2, b""))))),
        (1, _message((2, b"/host:metadata"),
                     (4, _message((1, 2 ** 63 + 5), (2, meta))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert scopes.op_names(str(path)) == {
        (2 ** 63 + 5, "fusion.1"): "jit(orion_prefill)/embed/gather",
        (2 ** 63 + 5, "gmm.3"):
            "jit(orion_prefill)/while/body/mlp_moe/experts/gmm"}


def recorded() -> dict:
    events = json.loads(
        (REPO / "tests/benchmark/data/trace_scopes_v5e.json").read_text())
    table = events.pop("op_names")
    for op in events["devices"]["0"]["XLA Ops"]:
        op[3] = None if op[3] is None else table[op[3]]
    return events


def test_recorded_v5e_trace_with_the_scopes():
    events = recorded()
    got = scopes.attribute(events, PARTS, OUTER)
    assert {"orion_prefill", "orion_decode_window"} <= set(got["by"])
    for prog in ("orion_prefill", "orion_decode_window"):
        parts = got["by"][prog]
        assert sum(parts.values()) == pytest.approx(got["module_s"][prog])
        assert set(parts) <= set(PARTS) | {scopes.UNSCOPED}
        # Mixtral: the experts are most of either program, and what lies
        # under no part is little
        assert parts["mlp_moe/experts"] > 0.7 * got["module_s"][prog]
        assert 0 < parts[scopes.UNSCOPED] < 0.08 * got["module_s"][prog]
    dec = got["by"]["orion_decode_window"]
    assert dec["attention/kernel"] > dec["attention/qkv"] > 0
    # every matmul and kernel of the trace lies under a part
    for name, _, _, op_name in events["devices"]["0"]["XLA Ops"]:
        if re.match(r"(gmm|paged_decode|flash_fwd|rmsnorm|convolution)", name):
            assert scopes.part_of(op_name, PARTS) in PARTS, (name, op_name)
    assert all(0 < lo <= mid < 5 * MS
               for lo, mid, _ in got["clock_offsets_ns"].values())
    # the program seconds are the ones ``host_spans`` gives the run spans
    # (what ``prefill_device_ms_per_ktoken.batch`` divides)
    from benchmarks.trace import host_spans

    dev = events["devices"]["0"]
    dev["XLA Ops"] = [op[:3] for op in dev["XLA Ops"]]
    run_s = host_spans.attribute(events)["run_module_s"]
    assert run_s["orion/decode/run"] == pytest.approx(
        got["module_s"]["orion_decode_window"], rel=1e-3)
    assert run_s["orion/prefill/run"] == pytest.approx(
        got["module_s"]["orion_prefill"], rel=1e-3)


# -- (c) the new metrics under the contract's rules ------------------------------

UNPINNED = ["laguna-s-2.1.serve-batch-4k", "brumby-14b.serve-longout",
            "glm-4.7-flash.serve-longctx"]
LING = "ling-3.0-flash.serve-reason-128"
BY_PART = ["decode_attn_kernel_ms_per_step.batch",
           "decode_attn_proj_ms_per_step.batch",
           "decode_ffn_ms_per_step.batch", "decode_head_ms_per_step.batch",
           "decode_unscoped_ms_per_step.batch",
           "prefill_attn_ms_per_ktoken.batch",
           "prefill_experts_ms_per_ktoken.batch",
           "prefill_route_ms_per_ktoken.batch",
           "prefill_other_ms_per_ktoken.batch"]
ROUNDING = "paged_decode_page_rounding.batch"
# PR 43's four, Ling's alone: name -> (unit, better, source, layer)
REASON128 = {
    "kda_decode_roofline.reason128": ("%", "higher", "device_trace", "kernels"),
    "kda_prefill_roofline.reason128": ("%", "higher", "device_trace", "kernels"),
    "held_expert_ffn_roofline.reason128": (
        "%", "higher", "device_trace", "kernels"),
    "hybrid_cache_bytes_per_token.reason128": (
        "B", "lower", "program_counter", "scheduler"),
}
# The order in which these were accepted, and the place of the first. The
# driver compares ``per_layer`` place by place and takes a new entry only
# BEHIND the last one, so an accepted entry never moves and what a later PR
# adds stands behind all of these: their places are held, as the driver holds
# them, and not their distance from the end (a ``benchmark`` PR that takes an
# earlier entry away moves FIRST with it).
ACCEPTED = BY_PART + [ROUNDING] + list(REASON128)
FIRST = 36


def reader(name: str):
    return Cell.find(UNPINNED[0]).reader(name)


def serving_cells(bm: dict) -> list:
    """The cells that report the metric the by-part metrics move."""
    e2e = next(m for m in bm["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    return [w["name"] for w in bm["workloads"]
            if "workloads" not in e2e or w["name"] in e2e["workloads"]]


def assert_a_by_part_list_is_kept(workloads: list, serving: list) -> None:
    """A by-part metric's cells: the three it was accepted with, in their
    places, and behind them only further serving cells, each once."""
    assert workloads[:len(UNPINNED)] == UNPINNED
    assert len(set(workloads)) == len(workloads)
    assert set(workloads) <= set(serving)


def assert_a_new_metric_keeps_to_the_contract(bm: dict, name: str) -> None:
    from tests.benchmark.test_contract import NAME, UNIT

    entry = next(m for m in bm["per_layer"] if m["name"] == name)
    assert NAME.match(name) and UNIT.match(entry["unit"])
    assert entry["moves"] == "serve_tokens_per_s"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    if name == ROUNDING:
        assert (entry["source"], entry["layer"], entry["unit"]) == (
            "program_counter", "kernels", "x")
        assert entry["workloads"] == UNPINNED[:1]
    elif name in REASON128:
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"]) == REASON128[name]
        assert entry["workloads"] == [LING]
    else:
        assert (entry["source"], entry["layer"], entry["better"]) == (
            "device_trace", "dispatch programs", "lower")
        assert_a_by_part_list_is_kept(entry["workloads"], serving_cells(bm))
    # the accepted entries keep their order, and their cells find them
    names = [m["name"] for m in bm["per_layer"]]
    assert len(set(names)) == len(names)
    assert names[FIRST:FIRST + len(ACCEPTED)] == ACCEPTED
    for cell in entry["workloads"]:
        found = Cell.find(cell, benchmark=bm)
        assert name in {m["name"] for m in found.per_layer}
        assert hasattr(found.reader(name), "read")


@pytest.mark.parametrize("name", ACCEPTED)
def test_a_new_metric_keeps_to_the_contract(name):
    assert_a_new_metric_keeps_to_the_contract(load_benchmark(), name)
    # a run without a trace, and a program without the counter, read nothing
    assert reader(name).read(
        {"trace": None, "timing": {}, "decode_window": 8,
         "config": {"orion": {"overrides": []}}}) is None


def test_lings_cell_is_split_by_part_and_reads_its_four():
    """Ling's ledger line: the fifteen it had, the nine by-part metrics and
    the four ``.reason128`` entries (28; a later PR may add to them); no
    other cell reads one of the four."""
    bm = load_benchmark()
    mine = [m["name"] for m in Cell.find(LING, benchmark=bm).per_layer]
    assert len(mine) >= 28 and set(BY_PART) | set(REASON128) <= set(mine)
    assert ROUNDING not in mine
    for m in bm["per_layer"]:
        if m["name"] in BY_PART:
            assert m["workloads"][:4] == UNPINNED + [LING]
    for cell in UNPINNED + ["mixtral-8x7b.serve-batch"]:
        theirs = {m["name"] for m in Cell.find(cell, benchmark=bm).per_layer}
        assert not theirs & set(REASON128)


# What the next PR brings: a serving cell that is none of today's. In memory
# it borrows an accepted cell's configuration and another's traffic, so that
# ``Cell.find`` finds files for it.
SIXTH = "in-memory.serve-chat-256"
# in a case below: every serving cell of the file that is not one of the
# three, in the file's order (Ling's, Mixtral's, and what later PRs added)
FURTHER = "<every further serving cell of the file>"


def with_a_sixth_serving_cell(bm: dict, listed: bool = True) -> dict:
    """``bm`` grown as a ``model_config`` PR grows it: one more cell behind
    the last and, if ``listed``, its name behind the last name of
    ``serve_tokens_per_s``'s list."""
    grown = copy.deepcopy(bm)
    cells = {w["name"]: w for w in grown["workloads"]}
    assert SIXTH not in cells
    grown["workloads"].append({
        "name": SIXTH, "config": cells[LING]["config"],
        "traffic": cells[UNPINNED[0]]["traffic"], "chips": 1, "why": "test"})
    if listed:
        next(m for m in grown["end_to_end"]
             if m["name"] == "serve_tokens_per_s")["workloads"].append(SIXTH)
    return grown


@pytest.mark.parametrize("workloads, kept, sixth", [
    (UNPINNED, True, None),                            # as PR 38 had them
    (UNPINNED + [LING], True, None),                   # as PR 43 had them
    (UNPINNED + [FURTHER], True, None),                # a later PR's
    (UNPINNED[:2] + [LING], False, None),              # one of the three lost
    (UNPINNED[1:] + [LING], False, None),
    ([UNPINNED[1], UNPINNED[0], UNPINNED[2], LING], False, None),  # reordered
    ([LING] + UNPINNED, False, None),                  # in front of them
    (UNPINNED + [LING, LING], False, None),            # twice
    (UNPINNED + ["mistral-7b.train-8k"], False, None),  # no serving cell
    (UNPINNED + ["no-such.cell"], False, None),
    # what the next PR does: a list that ends in a serving cell that is none
    # of the file's today; the same name where ``serve_tokens_per_s`` does
    # not list it, and where no cell has it, is no serving cell
    (UNPINNED + [FURTHER, SIXTH], True, "listed"),
    (UNPINNED + [SIXTH], True, "listed"),
    (UNPINNED + [FURTHER, SIXTH], False, "unlisted"),
    (UNPINNED + [FURTHER, SIXTH], False, None),
])
def test_a_by_part_list_grows_by_serving_cells_and_loses_none(
        workloads, kept, sixth):
    """The serving cells are READ from the file, whatever a later PR added
    to them (PR 43 wrote today's five down here, and any sixth failed every
    case)."""
    bm = load_benchmark()
    today = serving_cells(bm)
    assert set(UNPINNED + [LING]) <= set(today)
    further = [c for c in today if c not in UNPINNED]
    workloads = [c for w in workloads
                 for c in (further if w == FURTHER else [w])]
    serving = today if sixth is None else serving_cells(
        with_a_sixth_serving_cell(bm, listed=sixth == "listed"))
    assert (SIXTH in serving) == (sixth == "listed")
    if kept:
        assert_a_by_part_list_is_kept(workloads, serving)
        return
    with pytest.raises(AssertionError):
        assert_a_by_part_list_is_kept(workloads, serving)


def test_a_fifth_entry_behind_the_four_is_taken():
    """What the next PR does: a sixth serving cell, its name behind the last
    name of every by-part list, and one more per-layer entry behind the last
    one that lists THAT cell. Every accepted entry still keeps to the
    contract; with an entry put in front of an accepted one, or an accepted
    one moved, none does."""
    # (a name no PR will bring: the one PR 43 rehearsed here,
    # ``ssm_scan_roofline.chat256``, is the next PR's own, and this test
    # would have found it twice in that PR's list)
    fifth = {"name": "in_memory_roofline.chat256", "unit": "%",
             "better": "higher", "source": "device_trace", "layer": "kernels",
             "moves": "serve_tokens_per_s", "workloads": [SIXTH]}
    bm = load_benchmark()
    grown = with_a_sixth_serving_cell(bm)
    grown["per_layer"].append(fifth)
    for m in grown["per_layer"]:
        if m["name"] in BY_PART:
            m["workloads"].append(SIXTH)
    for name in ACCEPTED:
        assert_a_new_metric_keeps_to_the_contract(grown, name)
    sixth = Cell.find(SIXTH, benchmark=grown)
    assert [m["name"] for m in sixth.per_layer] == BY_PART + [fifth["name"]]
    last = FIRST + len(ACCEPTED) - 1
    for bad in (lambda p: p.insert(last, fifth),
                lambda p: p.insert(FIRST, p.pop(last))):
        moved = copy.deepcopy(grown)
        moved["per_layer"].pop()
        bad(moved["per_layer"])
        for name in ACCEPTED:
            with pytest.raises(AssertionError):
                assert_a_new_metric_keeps_to_the_contract(moved, name)


def test_the_readers_divide_by_what_the_metrics_they_split_divide(monkeypatch):
    from benchmarks.trace import host_spans

    got = scopes.attribute(recorded(), PARTS, OUTER)
    monkeypatch.setattr(scopes, "for_obs", lambda obs: got)
    obs = {"decode_window": 8,
           "trace": {"timing": {"prefill_tokens": 2000},
                     "module_s": {"jit_orion_decode_window(1)":
                                  got["module_s"]["orion_decode_window"]},
                     "module_n": {"jit_orion_decode_window(1)": 1}}}
    read = {name: reader(name).read(obs) for name in BY_PART}
    assert all(v is not None and v >= 0 for v in read.values())
    assert sum(read[n] for n in BY_PART[:5]) == pytest.approx(
        reader("decode_step_ms.batch").read(obs))
    assert sum(read[n] for n in BY_PART[5:]) == pytest.approx(
        1e3 * got["module_s"]["orion_prefill"] / 2.0)
    assert read["decode_ffn_ms_per_step.batch"] > 10 * read[
        "decode_attn_kernel_ms_per_step.batch"]


def test_page_rounding_is_pages_read_over_live_positions():
    obs = {"timing": {"decode_kv_pages_read": 67, "decode_kv_token_layers": 1000},
           "config": {"orion": {"overrides": ["inference.page_size=8",
                                              "inference.page_size=16"]}}}
    assert reader(ROUNDING).read(obs) == pytest.approx(1.072)
