"""The configuration whose layers keep a state row a slot and no page among
layers that keep one compressed row a token in pages, under a grouped router
of which the chip holds a share (Ling-3.0-flash): its cut table from its
file's own keys, the byte and operation functions against hand counts, a tiny
configuration of the same kind through ``run.py`` on the CPU under the traffic
kind it brings (``kinds/serve_rows.py``), its readers on what a parent would
hand them, its planted faults through the harness, and its programs compiled
at their real sizes for a v5e that is described and not attached."""

import json

import pytest

from benchmarks.harness.cell import Cell
from benchmarks.metrics import kda
from tests.benchmark.conftest import (MIXES, REPO, add_configuration,
                                      run_cell, write_root)
from tests.benchmark.test_scopes import ACCEPTED, FIRST, LING as CELL, REASON128

TINY = "tiny.ling"
# the cell's four entries: kda decode, kda prefill, held experts, cache bytes
NEW = tuple(REASON128)


def test_bytes_against_the_cut_table():
    """ISSUE 41's arithmetic, in bf16, from the file's own keys, against the
    reference's tree and the program's: 5.34 B parameters, 10.68 GB; the
    state 1.89 GB, the latent pool 2.01 GB; 14.66 GB held."""
    cell = Cell.find(CELL)
    hf = cell.config
    n = 0
    for shape, _ in cell.reference().param_spec(hf).values():
        size = 1
        for d in shape:
            size *= d
        n += size
    D, H4 = 2560, 4096
    kda_attn = 6 * D * H4 + D * 32 + 4 * 3 * H4 + 32 + H4 + 128
    latent = (D * 6144 + D * 576 + 512 * 8192 + H4 * D + D * 32 + 512
              + 192 + 64)
    assert (kda_attn, latent) == (63_049_888, 31_965_952)
    dense = kda_attn + 3 * D * 6144 + 2 * D
    expert = 3 * D * 768
    ffn = D * 512 + 512 + expert + 128 * expert + 2 * D
    assert expert == 5_898_240 and 128 * expert == 754_974_720
    assert (dense, kda_attn + ffn, latent + ffn) == (
        110_240_928, 825_239_200, 794_155_264)
    assert n == (2 * dense + 5 * (kda_attn + ffn) + latent + ffn
                 + 2 * 39296 * D + D) == 5_342_031_200
    assert 10.68e9 < 2 * n < 10.69e9
    cfg = cell.program_config()
    m, icfg = cfg.model, cfg.inference
    assert (m.n_layers, m.n_experts, m.resolved_router_width, m.expert_offset,
            m.vocab_size) == (8, 128, 512, 0, 39296)
    assert [k.attention for k in m.layer_kinds] == [
        "kda"] * 5 + ["latent"] + ["kda"] * 2
    assert (icfg.max_batch_size, icfg.page_size, icfg.decode_window,
            icfg.prefill_chunk) == (128, 64, 8, 1024)
    assert m.capacity_factor == 512 / 8                     # dropless
    assert icfg.max_seq_len == 8192 + 4096 == 192 * 64
    assert icfg.num_pages == 128 * 192 + 1 == 24_577
    # the program's own tree is the table's
    import jax

    from orion_tpu.infer.kv_cache import init_cache
    from orion_tpu.models.transformer import init_params

    shapes = jax.eval_shape(lambda: init_params(m, jax.random.key(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == n
    cache = jax.eval_shape(lambda: init_cache(m, icfg))
    assert {k: v.shape for k, v in cache.items()} == {
        "latent": (24_577, 1, 64, 640),
        "kda_state": (7, 129, 32, 128, 128), "kda_conv": (7, 129, 3, 12288)}
    size = {k: v.size * v.dtype.itemsize for k, v in cache.items()}
    assert kda.state_row_bytes(hf) == 2_097_152
    assert size["kda_state"] == 129 * 7 * 2_097_152
    assert 1.89e9 < size["kda_state"] < 1.90e9
    assert 2.01e9 < size["latent"] < 2.02e9 and size["kda_conv"] < 0.07e9
    held = 2 * n + sum(size.values())
    assert 14.6e9 < held < 14.7e9 and 0.86 < held / 16.9e9 < 0.87
    # a slot: 14.7 MB of state and 1.28 KB a cached token
    slot = 7 * (kda.state_row_bytes(hf) + 3 * 3 * 4096 * 2)
    assert 14.7e6 < slot < 15.2e6 and 640 * 2 == 1280
    dep = hf["deployment"]
    assert (dep["chips"], dep["chips_sharing_a_layer"]) == (28, 4)
    assert dep["experts_held"] == [0, 128] and 4 * 128 == 512
    assert 4 * 39296 == hf["published"]["vocab_size"] == 157_184


def test_byte_and_operation_functions_against_hand_counts():
    hf = Cell.find(CELL).config
    assert kda.state_row_bytes(hf) == 32 * 128 * 128 * 4
    assert kda.decode_bytes(hf, 10) == 10 * 2 * 2_097_152
    # a token step of 128 live slots moves 3.76 GB of state
    assert 3.75e9 < kda.decode_bytes(hf, 128 * 7) < 3.76e9
    assert kda.prefill_flops(hf, 3) == 3 * 7 * 32 * 128 * 128
    assert kda.sparse_layers(hf) == 6
    assert kda.held_expert_bytes(hf) == 6 * 128 * 5_898_240 * 2
    assert 9.05e9 < kda.held_expert_bytes(hf) < 9.07e9       # "9.06 GB"
    assert kda.prefill_flops(hf, 1) == 7 * 32 * 128 * 128


def test_the_mix_and_its_probes_lie_inside_the_warmed_shapes():
    from benchmarks.kinds import serve
    from benchmarks.traffic.generator import length_table

    cell = Cell.find(CELL)
    icfg = cell.program_config().inference
    table = length_table(cell.mix)
    assert len(table) == 128 == cell.mix["clients"] == icfg.max_batch_size
    assert cell.mix["kind"] == "serve_rows" and cell.mix["pair_seed"] == 4111
    prompts, outputs = [p for p, _ in table], [o for _, o in table]
    assert (min(prompts), max(prompts)) == (128, 8192)
    assert (min(outputs), max(outputs)) == (311, 4096)   # clipped to 256-4096
    assert max(p + o for p, o in table) <= icfg.max_seq_len
    shapes = serve.cell_prefill_shapes(cell, icfg)
    assert len(shapes) == 15
    assert all(nb * s <= 8192 and s % 1024 == 0 for nb, s in shapes)
    assert (1, 8192) in shapes and (8, 1024) in shapes
    for n in cell.mix["probe_prompts"]:
        assert (1, -(-n // 1024) * 1024) in shapes
    assert cell.mix["probe_prompts"] == [500, 2044, 4500, 8192]
    assert hasattr(cell.kind_module(), "run")


def test_every_published_key_is_stated_and_three_meanings_are_reduced():
    import os

    cell = Cell.find(CELL)
    hf, pub = cell.config, cell.published
    assert hf["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in pub.items():
        stated = hf["published"][key] if key in hf["reduced"] else hf[key]
        assert stated == value, key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(l) for l in open(catalog)
            if '"Ling-3.0-flash"' in l] if os.path.exists(catalog) else []
    for row in rows:
        assert row["config"] == pub and row["source_url"] == hf["source"]
    assert set(hf["assumed"]) >= {"kda_gate", "qk_norm", "head_gate"}


def _tiny_configuration():
    published = {
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 12,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 6000000,
        "tie_word_embeddings": False, "q_lora_rank": None,
        "kv_lora_rank": 48, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "qk_head_dim": 24, "v_head_dim": 16, "num_experts": 16,
        "num_shared_experts": 1, "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 32, "num_experts_per_tok": 4,
        "n_group": 4, "topk_group": 2, "first_k_dense_replace": 2,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "layer_group_size": 6, "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "use_qk_norm": True,
        "score_function": "sigmoid", "model_type": "bailing_hybrid",
    }
    real = Cell.find(CELL).config["orion"]["widths"]
    cfg = dict(
        published, num_hidden_layers=8, num_experts=8, source="test",
        reduced=["num_hidden_layers", "num_experts"],
        published={"num_hidden_layers": 12, "num_experts": 16},
        assumed={}, role="serve", reference="ling",
        deployment={"chips_sharing_a_layer": 2, "experts_held": [0, 8]},
        frontend={"prefill_token_budget": 128},
        orion={"preset": "tiny-ling",
               "overrides": ["model.n_experts=8", "model.expert_offset=0",
                             "inference.prefill_chunk=32"],
               "widths": real,
               "unchecked": {"model_type": "the family's name",
                             "norm_topk_prob": "no field"}},
        correct={"router_margin_min": 0.0, "limits": {
            "logit_rel_err_worst_probe_median_clear": 1e-3,
            "window_kv_rel_err_max": 1e-4, "window_token_gap_max": 1e-3}})
    return cfg, published


# What the next PR brings: one more per-layer entry BEHIND the four, with a
# reader file of its own; the tiny root lists it through
# ``write_root(extra_metrics=)`` and ``run.py`` reads it on the CPU.
FIFTH = "kda_slot_layers_in_window.next"
FIFTH_READER = '''"""Live slots x KDA layers over the window's token steps."""


def read(obs):
    return obs["timing"].get("decode_kda_slot_layers") or None
'''


@pytest.fixture(scope="module")
def ling_root(tmp_path_factory):
    """The tests' tiny benchmark root with one more configuration, a mix of
    the kind this configuration brings, and a cell listed under every metric
    the real cell is listed under (the four ``.reason128`` entries and the
    nine by-part metrics among them, since PR 43), and a fifth entry behind
    the four as a later PR would append it."""
    root = write_root(tmp_path_factory.mktemp("tiny_ling"), extra_metrics=[
        {"name": FIFTH, "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "serve_tokens_per_s", "workloads": [TINY]}])
    (root / "benchmarks" / "metrics").mkdir()
    (root / "benchmarks" / "metrics" / f"{FIFTH}.py").write_text(FIFTH_READER)
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bm = json.loads((root / "BENCHMARK.json").read_text())
    # The four stand where they were accepted (the driver holds an entry's
    # place, not its distance from the end) and the fifth is the last of the
    # tiny root's list, whatever a later PR appended between them.
    names = [m["name"] for m in bm["per_layer"]]
    at = FIRST + ACCEPTED.index(NEW[0])
    assert tuple(names[at:at + len(NEW)]) == NEW and names[-1] == FIFTH
    bm["configs"].append(add_configuration(
        root, "tiny-ling-serve", *_tiny_configuration()))
    (root / "benchmarks" / "traffic" / "tiny-rows.json").write_text(
        json.dumps(dict(MIXES["tiny-batch"], kind="serve_rows")))
    bm["workloads"].append({"name": TINY, "config": "tiny-ling-serve",
                            "traffic": "tiny-rows", "chips": 1,
                            "why": "test"})
    mine = {m["name"] for m in real["end_to_end"] + real["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) <= mine and len(mine) >= 1 + 28
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in mine:
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def test_a_tiny_configuration_of_this_kind_runs_end_to_end(
        ling_root, capsys, monkeypatch):
    """``run.py`` itself, traced, on the CPU (counts only), with no edit to
    a file the harness had: the traffic file names the kind the
    configuration brings, whose tap puts a slot's rows back before a
    window's steps are run again; the chip's share (8 of 16 experts under a
    16-wide router) is the reference's; the probes are correct; the one new
    metric that is an exact count is reported."""
    rc, lines = run_cell(ling_root, TINY, capsys, monkeypatch, trace=1)
    assert rc == 0
    out = json.loads(lines[-1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compiles_in_window.batch"] == 0
    assert m["slot_occupancy_pct.batch"] > 0
    # of the four ``.reason128`` metrics the one that is engine counters
    # alone reads here; the three shares of a roofline need a device trace
    assert [n for n in m if n.endswith(".reason128")] == [NEW[3]]
    assert m[NEW[3]] > 0
    # and the entry a later PR appends behind the four is found and read
    assert m[FIFTH] > 0 and list(m)[-2:] == [NEW[3], FIFTH]
    per = {x["name"] for x in Cell.find(TINY, root=ling_root).per_layer}
    assert set(NEW) <= per and "decode_ffn_ms_per_step.batch" in per
    assert "paged_decode_roofline.batch" not in m          # a K/V model's
    assert "latent_cache_bytes_per_token.longctx" not in m  # all-latent's
    checks = dict(line.split(" = ")[0].split("check: ")[1:] + [line]
                  for line in lines if line.startswith("check: "))
    assert set(checks) == {"logit_rel_err_worst_probe_median_clear",
                           "window_kv_rel_err_max", "window_token_gap_max"}
    assert "window_kv_rel_err_max = 0.0 " in checks["window_kv_rel_err_max"]


def test_the_parent_of_this_configuration_reads_nothing():
    """The benchmark as this PR leaves it is laid over the parent too: where
    the program has no such counter, operation or scope, the new readers
    return None and do not raise; nor do they on another configuration's
    keys."""
    cell = Cell.find(CELL)
    empty = {"timing": {}, "config": cell.config, "slots": 128,
             "decode_window": 8, "peaks": {"hbm_bytes_per_s": 819e9,
                                           "bf16_flops": 197e12},
             "trace": {"timing": {}, "op_s": {"fusion.1": 1.0},
                       "module_s": {"jit__unknown(1)": 1.0},
                       "module_n": {"jit__unknown(1)": 2}}}
    # Since PR 43 the four ARE entries of the real ``per_layer``, the last
    # four of that PR's list, and the cell reads them beside the nine by-part
    # metrics (PR 41 could bring them as reader files only: a line of
    # tests/benchmark/test_scopes.py held PR 38's ten to the END of the list);
    # that file holds their places and what each entry says.
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bm["per_layer"]]
    at = names.index("paged_decode_page_rounding.batch")
    assert tuple(names[at + 1:at + 5]) == NEW
    mine = [m["name"] for m in cell.per_layer]
    assert [n for n in mine if n in NEW] == list(NEW) and len(mine) >= 28
    assert {"latent_decode_roofline.longctx", "decode_ffn_ms_per_step.batch",
            "decode_unscoped_ms_per_step.batch",
            "prefill_attn_ms_per_ktoken.batch"} <= set(mine)
    other = Cell.find("mixtral-8x7b.serve-batch").config
    for name in NEW:
        assert cell.reader(name).read(empty) is None
        assert cell.reader(name).read(dict(empty, trace=None)) is None
        assert cell.reader(name).read(dict(empty, config=other)) is None
    # a traced segment in which no prompt was admitted: the counter is there
    # and reads 0, and a share of a roofline is left out, never reported as 0
    quiet = dict(empty, trace=dict(
        empty["trace"], timing={"prefill_kda_token_layers": 0}))
    assert cell.reader(NEW[1]).read(quiet) is None


def test_the_readers_arithmetic():
    """By hand on what a traced segment hands them: 19 windows of 8 steps
    over 128 slots."""
    cell = Cell.find(CELL)
    hf = cell.config
    t = {"decode_kda_slot_layers": 19 * 8 * 128 * 7,
         "prefill_kda_token_layers": 7 * 5000,
         "kda_live_state_bytes": 19 * 128 * 7 * (2_097_152 + 73_728),
         "latent_live_page_bytes": 19 * 128 * 40 * 81_920,
         "latent_live_tokens": 19 * 128 * 2500}
    obs = {"timing": t, "config": hf, "slots": 128, "decode_window": 8,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "trace": {"timing": t, "op_s": {"kda_decode.3": 0.5,
                                           "kda_decode.7": 0.5,
                                           "kda_prefill.1": 0.01},
                     "module_s": {}, "module_n": {}}}
    got = cell.reader(NEW[0]).read(obs)
    assert got == pytest.approx(
        100 * 19 * 8 * 128 * 7 * 2 * 2_097_152 / 819e9 / 1.0)
    assert 0 < got < 100
    got = cell.reader(NEW[1]).read(obs)
    assert got == pytest.approx(
        100 * 7 * 5000 * 7 * 32 * 128 * 128 / 197e12 / 0.01)
    got = cell.reader(NEW[3]).read(obs)
    assert got == pytest.approx(
        (7 * (2_097_152 + 73_728) + 40 * 81_920) / 2500)
    assert 7000 < got < 7500


def test_the_planted_faults_run_through_the_harness(
        ling_root, capsys, monkeypatch):
    """``tools/kda_fault_probe.py`` on the tiny cell (CPU): the benchmark's
    own ``probe_numbers`` and ``decide`` under the cell's tap, on an engine
    whose decode drops the erase term, then whose router ignores its
    groups, then whose chunked prefill starts every chunk of 64 from a zero
    state. Unplanted the check passes; float32 on the CPU under a limit of
    1e-3 sees the first two (on the chip, in bfloat16 under the cell's
    limit: PERF.md section 6, PR 41); the tiny probes (5 and 40 tokens) lie
    inside one chunk, so the third runs through the harness and changes
    nothing here (what it does to longer rows: ``tests/test_kda.py``)."""
    import runpy

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(ling_root / ".c"))
    monkeypatch.setattr("sys.argv", [
        "kda_fault_probe.py", "--workload", TINY, "--seed", "77",
        "--root", str(ling_root), "--allow-cpu"])
    with pytest.raises(SystemExit) as done:
        runpy.run_path(str(REPO / "tools/kda_fault_probe.py"),
                       run_name="__main__")
    assert done.value.code == 0
    lines = capsys.readouterr().out.splitlines()
    assert [l for l in lines if l.startswith("-- fault planted")] == [
        "-- fault planted: none", "-- fault planted: erase",
        "-- fault planted: groups", "-- fault planted: carry"]
    assert [l for l in lines if l.startswith("correct: ")] == [
        "correct: True", "correct: False", "correct: False", "correct: True"]
    assert lines[-1].endswith("the check sees ['erase', 'groups']")


# -- the output check's numbers, on readings of the chip (PR 43) -----------------

READINGS = json.loads(
    (REPO / "tests/benchmark/data/calibrate_ling_reason128_v5e.json")
    .read_text())
OLD = "logit_rel_err_worst_probe_median_clear"
OCTILE = "logit_rel_err_worst_probe_octile_clear"
POOLED = "logit_rel_err_all_probes_median_clear"


def _numbers(reading: dict, errs: str = "err") -> dict:
    """What ``probe_numbers`` handed ``decide`` on the chip for one seed;
    with ``errs='control_err'`` the int8 control in the program's place."""
    return {"probe": READINGS["probe"], "err": reading[errs],
            "margin": reading["margin"],
            "window_kv_rel_err": reading["window_kv_rel_err"],
            "window_token_gap": reading["window_token_gap"]}


@pytest.mark.parametrize("reading", READINGS["seeds"],
                         ids=lambda r: str(r["seed"]))
def test_the_check_passes_the_program_and_fails_the_control(reading):
    """Twelve seeds of the cell on a TPU v5e, position by position, through
    the benchmark's own ``decide`` under the cell's own limits: the program
    is correct on every one, the int8 control in its place on none, nor a
    one-step body fed another token."""
    from benchmarks.kinds import serve

    correct = Cell.find(CELL).config["correct"]
    assert set(correct["limits"]) == {OCTILE, POOLED, "window_kv_rel_err_max",
                                      "window_token_gap_max"}
    ok, checks = serve.decide(_numbers(reading), correct)
    assert ok and len(checks) == 4
    ok, checks = serve.decide(_numbers(reading, "control_err"), correct)
    assert not ok
    assert all(v > lim for name, v, lim in checks if name.startswith("logit"))
    broken = dict(_numbers(reading), window_kv_rel_err=[
        reading["link_broken"]["window_kv_rel_err_max"]])
    assert not serve.decide(broken, correct)[0]


def test_the_worst_probes_median_failed_a_sound_run_and_these_do_not():
    """Why the cell is not held to the worst probe's median any more: on
    seed 1789062289 twelve of the longest probe's 17 positions flipped a
    last expert together and its median read 0.0448, inside the control's
    range; the lower octile and the median over all probes keep the two
    readings 3.5x apart, and each limit has more room above the program's
    largest reading than under the control's smallest."""
    from benchmarks.kinds import serve

    limits = Cell.find(CELL).config["correct"]["limits"]
    read = lambda errs, name: [
        serve.judged(_numbers(r, errs), 0.0)[name] for r in READINGS["seeds"]]
    first = READINGS["seeds"][0]
    assert first["seed"] == 1789062289
    assert sum(e > 0.02 for e in first["err"][-17:]) == 12
    assert serve.judged(_numbers(first), 0.0)[OLD] == pytest.approx(0.044813)
    assert max(read("err", OLD)) > min(read("control_err", OLD)) > 0.021
    for name in (OCTILE, POOLED):
        lower, upper = max(read("err", name)), min(read("control_err", name))
        assert upper > 3 * lower
        assert lower < limits[name] < upper
        assert limits[name] / lower > upper / limits[name] > 1.5
    # every position reads as unflipped or as flipped, nothing between
    errs = [e for r in READINGS["seeds"] for e in r["err"]]
    assert not [e for e in errs if 0.012 < e < 0.03]
    assert 0.15 < sum(e > 0.03 for e in errs) / len(errs) < 0.25


@pytest.mark.parametrize("at, seen", [
    pytest.param(range(18, 34), True, id="every decode position of one probe"),
    pytest.param([17 * p + i for p in range(4) for i in range(4, 17)], True,
                 id="the last thirteen positions of every probe"),
    pytest.param(range(51, 68), True, id="every position of one probe"),
    pytest.param([0, 17, 34, 51], False,
                 id="the last prompt position of every probe"),
    pytest.param(range(5, 17), False,
                 id="the last twelve positions of one probe"),
])
def test_what_the_two_numbers_hold_and_give_up(at, seen):
    """Faults planted in the readings of a sound seed (a position at fault
    reads ten times what it read): what moves the lower octile of a probe or
    the median over all of them, and what neither sees (nor did the worst
    probe's median see a single position; a run of twelve it did see, and
    that is what a sound run can show)."""
    from benchmarks.kinds import serve

    reading = READINGS["seeds"][1]
    err = [e * 10 if i in at else e for i, e in enumerate(reading["err"])]
    ok, _ = serve.decide(dict(_numbers(reading), err=err),
                         Cell.find(CELL).config["correct"])
    assert ok is not seen


def test_decode_and_the_widest_burst_fit_the_chip():
    """The cell's decode window and its widest burst of prompts compiled
    for a described v5e (``test_aot_v5e.py`` finds cells of kind ``serve``
    alone): the KDA decode kernel is in the program, and both fit beside
    the weights, the pool and the state."""
    import importlib
    import pkgutil
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import orion_tpu.ops.pallas as pallas_pkg
    from benchmarks.reference import weights
    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache, pages_per_seq

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:     # no compiler for the chip on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    patched = []
    for m in pkgutil.iter_modules(pallas_pkg.__path__):
        mod = importlib.import_module(f"orion_tpu.ops.pallas.{m.name}")
        if hasattr(mod, "resolve_interpret"):
            patched.append((mod, mod.resolve_interpret))
            mod.resolve_interpret = bool
    try:
        cell = Cell.find(CELL)
        cfg = cell.program_config()
        mcfg, icfg = cfg.model, cfg.inference
        ab = lambda tree: jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)
        spec = cell.reference().param_spec(cell.config)
        params = ab(jax.eval_shape(lambda: weights._draw(
            spec, mcfg.n_layers, jnp.dtype(mcfg.param_dtype),
            jax.random.key(0))))
        cache = ab(jax.eval_shape(lambda: init_cache(mcfg, icfg)))
        i32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32, sharding=one)

        def total(c):
            m = c.memory_analysis()
            return (m.temp_size_in_bytes + m.argument_size_in_bytes
                    + m.output_size_in_bytes - m.alias_size_in_bytes)

        B, W = icfg.max_batch_size, icfg.decode_window
        decode = jax.jit(partial(
            runner.decode_window, cfg=mcfg, max_seq_len=icfg.max_seq_len,
            mesh=None, nan_guard=False, temperature=icfg.temperature,
            top_k=icfg.top_k, top_p=icfg.top_p), donate_argnums=(1,))
        keys = jax.ShapeDtypeStruct((W,), jax.random.key(0).dtype,
                                    sharding=one)
        compiled = decode.lower(
            params, cache, i32(B), i32(B), i32(B, pages_per_seq(icfg)),
            jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one), keys,
        ).compile()
        text = compiled.as_text()
        assert "kda_decode" in text and "latent_paged_decode" in text
        assert total(compiled) < 15.75 * 2 ** 30
        prefill = jax.jit(partial(
            runner.prefill_step, cfg=mcfg, mesh=None,
            paged_prefill=icfg.paged_prefill), donate_argnums=(1,))
        compiled = prefill.lower(
            params, cache, i32(8, 1024), i32(8), i32(8, 16), i32(8),
            i32(8, 0)).compile()
        assert total(compiled) < 15.75 * 2 ** 30
    finally:
        for mod, fn in patched:
            mod.resolve_interpret = fn
