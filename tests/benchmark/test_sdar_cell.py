"""The configuration that generates by diffusion over blocks with all 128
softmax-routed experts held (SDAR-30B-A3B-Chat): its cut table and pool from
its file's own keys, its published keys against the catalog's row, its mix's
table and shapes, the byte and operation functions against hand counts, its
readers on what a traced segment hands them and on what a parent would, the
lists that name the cell, a tiny configuration of the same kind through
``run.py`` on the CPU, the planted faults through the harness, and its block
program and a prefill compiled at their real sizes for a v5e that is
described and not attached."""

import json
import math
import pathlib

import pytest

from benchmarks.harness.cell import Cell
from benchmarks.metrics import sdar
from benchmarks.traffic import generator
from tests.benchmark.conftest import (MIXES, REPO, add_configuration,
                                      run_cell, write_root)

CELL = "sdar-30b-a3b.serve-blocks-1k"
CONFIG = "sdar-30b-a3b-serve-1chip"
TINY = "tiny.sdar"
NEW = ("denoise_forward_ms.blocks", "tokens_per_slot_forward.blocks",
       "block_paged_attn_roofline.blocks", "all_expert_ffn_roofline.blocks",
       "block_flash_prefill_roofline.blocks")
# Accepted lists whose readers, unedited, read this cell (engine step, admit,
# gap, idle, prefill and compile); every reader that divides by runs of
# ``orion_decode_window`` x ``decode_window`` stays off.
JOINED = (
    "serve_tokens_per_s", "compile_s", "engine_host_ms_per_step.batch",
    "device_idle_pct.batch", "prefill_share_pct.batch", "ttft_p50_ms.batch",
    "itl_p50_ms.batch", "compiles_in_window.batch", "admit_ms_per_step.batch",
    "engine_gap_ms_per_step.batch", "prefill_pad_pct.batch",
    "prefill_device_ms_per_ktoken.batch", "idle_unattributed_pct.batch",
    "prefill_attn_ms_per_ktoken.batch", "prefill_experts_ms_per_ktoken.batch",
    "prefill_route_ms_per_ktoken.batch", "prefill_other_ms_per_ktoken.batch")
OFF = ("decode_step_ms.batch", "slot_occupancy_pct.batch",
       "decode_attn_kernel_ms_per_step.batch",
       "decode_attn_proj_ms_per_step.batch", "decode_ffn_ms_per_step.batch",
       "decode_head_ms_per_step.batch", "decode_unscoped_ms_per_step.batch")
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


def _replay(mix: dict, dispatches: int) -> tuple[int, float, int]:
    """(peak, mean) of the live positions and the peak of the pages held
    (whole pages, out to the end of the prompt's bucket of 1024) of a closed
    loop over the mix's own order, one block a slot and dispatch, prefill
    taken as instant."""
    import random

    table = generator.length_table(mix)

    def stream():
        b = 0
        while True:
            order = list(range(len(table)))
            random.Random(mix["pair_seed"] * 1_000_003 + b).shuffle(order)
            yield from (table[k] for k in order)
            b += 1

    def pages(p, d):
        return max(-(-p // 1024) * 1024 // 64, -(-(p + d + 4) // 64))

    s = stream()
    slots = [[*next(s), 0] for _ in range(mix["clients"])]
    live, held = [], []
    for _ in range(dispatches):
        live.append(sum(p + d for p, _, d in slots))
        held.append(sum(pages(p, d) for p, _, d in slots))
        for slot in slots:
            slot[2] += 4
            if slot[2] >= slot[1]:
                slot[:] = [*next(s), 0]
    live = live[500:]
    return max(live), sum(live) / len(live), max(held)


def test_bytes_against_the_cut_table():
    """ISSUE 54's arithmetic, in bf16, from the file's own keys, against the
    reference's tree and the program's: 4.361 B parameters, 8.72 GB; 12,288 B
    a position; 5120 pages, 4.03 GB; 12.75 GB held."""
    cell = Cell.find(CELL)
    hf = cell.config
    n = sum(math.prod(shape)
            for shape, _ in cell.reference().param_spec(hf).values())
    D, N, K, H, E, F, V = 2048, 32, 4, 128, 128, 768, 151936
    attn = D * N * H + 2 * D * K * H + N * H * D + 2 * H
    expert = 3 * D * F
    layer = attn + 2 * D + D * E + E * expert
    assert (D * N * H, D * K * H, expert, E * expert) == (
        8_388_608, 1_048_576, 4_718_592, 603_979_776)
    assert layer == 623_120_640 and 2 * V * D == 622_329_856
    assert n == 6 * layer + 2 * V * D + D == 4_361_055_744
    assert 8.72e9 < 2 * n < 8.73e9
    assert 48 * layer + 2 * V * D + D == 30_532_122_624      # the whole model
    cfg = cell.program_config()
    m, icfg = cfg.model, cfg.inference
    assert (m.n_layers, m.n_experts, m.resolved_router_width, m.vocab_size,
            m.block_length, m.mask_token_id) == (6, 128, 128, V, 4, 151669)
    assert not m.holds_expert_share and m.capacity_factor == 128 / 8
    assert (icfg.max_batch_size, icfg.page_size, icfg.prefill_chunk,
            icfg.max_seq_len, icfg.denoising_steps, icfg.remasking) == (
        128, 64, 1024, 4096 + 1024, 2, "low_confidence_static")
    g = hf["generation"]
    assert (g["block_length"], g["mask_token_id"], g["denoising_steps"],
            g["remasking"]) == (m.block_length, m.mask_token_id,
                                icfg.denoising_steps, icfg.remasking)
    # the program's own tree and cache are the table's
    import jax

    from orion_tpu.infer.kv_cache import init_cache
    from orion_tpu.models.transformer import init_params

    shapes = jax.eval_shape(lambda: init_params(m, jax.random.key(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == n
    cache = jax.eval_shape(lambda: init_cache(m, icfg))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (6 * 5120, 4, 64, 128), "v": (6 * 5120, 4, 64, 128)}
    pool = sum(v.size * v.dtype.itemsize for v in cache.values())
    assert sdar.position_bytes(hf) == 6 * 2 * 4 * 128 * 2 == 12_288
    assert pool == 5120 * 64 * 12_288 == 4_026_531_840
    held = 2 * n + pool
    assert 12.74e9 < held < 12.76e9 and 11.86 < held / 2 ** 30 < 11.88
    assert held / 2 ** 30 >= 11.0                # ISSUE 54: at least 11 GiB
    # the pool against the mix: 1.3 x the peak of live positions fits, and
    # the peak of whole pages held with a fifth to spare
    peak, mean, pages = _replay(cell.mix, dispatches=20_000)
    assert (peak, round(mean), pages) == (239_296, 216_340, 4228)
    assert icfg.num_pages * 64 == 327_680 >= 1.3 * peak
    assert icfg.num_pages >= 1.2 * pages
    assert 128 * icfg.max_seq_len * 12_288 > 8.0e9      # every slot's longest
    dep = hf["deployment"]
    assert dep["chips_sharing_a_layer"] == 1
    assert 8 * 6 == hf["published"]["num_hidden_layers"] == 48
    table = " ".join(dep["cut_table_bf16"].values())
    for said in ("623.1 M parameters, 1.246 GB", "4.361 B, 8.722 GB",
                 "12,288 B", "4.027 GB", "12.75 GB = 11.87 GiB"):
        assert said in table, said
    # each expert's rows a forward: the deployment's at this batch
    assert 128 * 4 * 8 / 128 == 32


def test_the_mix_and_its_probes_lie_inside_the_warmed_shapes():
    """The traffic file, letter for letter ISSUE 54's: 128 clients = 128
    slots, lognormal prompts (median 1024, sigma 0.8, 128-4096) and outputs
    (median 512, sigma 0.5, 128-1024); every prompt, probe and the longest
    request inside the engine's limits; 11 prefill shapes."""
    from benchmarks.kinds import serve, shapes

    cell = Cell.find(CELL)
    mix, icfg = cell.mix, cell.program_config().inference
    assert (mix["kind"], mix["clients"], mix["block"], mix["warm_requests"],
            mix["trace_seconds"], mix["probe_blocks"]) == (
        "serve_blocks", 128, 128, 128, 6.0, 4)
    assert mix["prompt"] == {"median": 1024, "sigma": 0.8, "min": 128,
                             "max": 4096}
    assert mix["output"] == {"median": 512, "sigma": 0.5, "min": 128,
                             "max": 1024}
    table = generator.length_table(mix)
    prompts, outputs = [p for p, _ in table], [o for _, o in table]
    assert (min(prompts), max(prompts)) == (128, 4096)
    assert (min(outputs), max(outputs)) == (135, 1024)
    assert 1330 < sum(prompts) / 128 < 1336 and 555 < sum(outputs) / 128 < 558
    assert max(p + o for p, o in table) <= icfg.max_seq_len
    assert mix["probe_prompts"] == [126, 1021, 2048, 4096]
    assert [n % 4 for n in mix["probe_prompts"]] == [2, 1, 0, 0]
    assert max(mix["probe_prompts"]) + 4 * mix["probe_blocks"] <= (
        icfg.max_seq_len)
    got = serve.cell_prefill_shapes(cell, icfg)
    assert got == [(1, 1024), (1, 2048), (1, 3072), (1, 4096), (2, 1024),
                   (2, 2048), (2, 3072), (2, 4096), (4, 1024), (4, 2048),
                   (8, 1024)]
    budget = cell.config["frontend"]["prefill_token_budget"]
    assert budget == 8192 and all(nb * s <= budget for nb, s in got)
    for n in mix["probe_prompts"]:
        assert (1, shapes.bucket_len(n, icfg.prefill_chunk,
                                     icfg.max_seq_len)) in got


def test_every_published_key_is_stated_and_depth_alone_is_reduced():
    """The configuration file against the source's own keys, and those
    against the catalog's row where this machine has it: every number under
    the same key, ``reduced`` = the depth alone, no width changed."""
    cell = Cell.find(CELL)
    hf, pub = cell.config, cell.published
    assert hf["reduced"] == ["num_hidden_layers"]
    assert hf["published"] == {"num_hidden_layers": 48}
    for key, value in pub.items():
        assert (hf["published"][key] if key in hf["reduced"]
                else hf[key]) == value, key
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["reduced"] == hf["reduced"]
    assert entry[0]["source"] == hf["source"]
    mine = [w for w in bm["workloads"] if w["name"] == CELL]
    assert mine == [{"name": CELL, "config": CONFIG,
                     "traffic": "serve-blocks-1k", "chips": 1,
                     "why": mine[0]["why"]}]
    assert [w["name"] for w in bm["workloads"] if w["config"] == CONFIG] == [
        CELL]
    if CATALOG.exists():
        rows = [json.loads(l) for l in CATALOG.read_text().splitlines()]
        row = [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"][0]
        assert row["config"] == pub and row["source_url"] == hf["source"]
        assert set(row["not_given"]) == {"block length", "noise schedule"}
    for letter in ("(a)", "(b)", "(c)", "(d)"):
        assert any(letter in v for v in hf["assumed"].values()), letter
    cell.program_config()          # every key checked or listed as unchecked


def test_byte_and_operation_functions_against_hand_counts():
    hf = Cell.find(CELL).config
    assert sdar.forwards_a_block(hf) == 3
    assert sdar.expert_bytes(hf) == 6 * 128 * 4_718_592 * 2 == 7_247_757_312
    assert sdar.position_bytes(hf) == 12_288
    # a prompt of n whole-block tokens: a row sees to its block's end
    pairs = lambda n: sum((i // 4 + 1) * 4 for i in range(n))
    assert pairs(1024) == 1024 * 1028 // 2
    assert sdar.prefill_attn_flops(hf, 6 * pairs(1024)) == (
        6 * 526_336 * 32 * 4 * 128)
    # ISSUE 54's reckoning: a forward reads 7.25 GB of experts (8.85 ms) and
    # about 2.7 GB of K/V; three forwards yield 512 tokens
    assert 8.8 < 1e3 * sdar.expert_bytes(hf) / 819e9 < 8.9
    assert 3.2 < 1e3 * 216_340 * sdar.position_bytes(hf) / 819e9 < 3.3


def _traced(hf, timing=None, **by):
    """An ``obs`` as ``serve.run`` hands a traced run's readers."""
    return {"config": hf, "peaks": {"bf16_flops": 197e12,
                                    "hbm_bytes_per_s": 819e9},
            "timing": timing or {}, "trace": {"timing": timing or {}},
            "_got": {"by": by, "module_s": {k: sum(v.values())
                                            for k, v in by.items()},
                     "module_n": {k: 10 for k in by}}}


def test_the_readers_arithmetic(monkeypatch):
    """Each new reader on a traced segment's numbers, by hand: 10 runs of the
    block program, 3 forwards each."""
    from benchmarks.trace import scopes

    cell = Cell.find(CELL)
    hf = cell.config
    monkeypatch.setattr(scopes, "for_obs", lambda obs: obs.get("_got"))
    timing = {"block_kv_positions_read": 30 * 200_000,
              "prefill_attn_pairs": 6 * 526_336,
              "tokens_committed": 1280, "block_slot_forwards": 1000}
    obs = _traced(hf, timing, orion_denoise_block={
        "attention/kernel": 0.15, "mlp_moe/experts": 0.36, "unembed": 0.09},
        orion_prefill={"attention/kernel": 0.004})
    read = {name: cell.reader(name).read(obs) for name in NEW}
    assert read[NEW[0]] == pytest.approx(1e3 * 0.60 / 30)
    assert read[NEW[1]] == 1.28
    assert read[NEW[2]] == pytest.approx(
        100 * (30 * 200_000 * 12_288 / 819e9) / 0.15)
    assert read[NEW[3]] == pytest.approx(
        100 * (30 * 7_247_757_312 / 819e9) / 0.36)
    assert read[NEW[4]] == pytest.approx(
        100 * (6 * 526_336 * 32 * 512 / 197e12) / 0.004)
    assert all(0 < read[n] <= 100 for n in NEW[2:])


def test_a_parent_and_another_configuration_read_nothing(monkeypatch):
    """No trace, a trace without named programs, a trace without the block
    program, a configuration of another kind, counters that are not there:
    every new reader returns None and raises nothing."""
    from benchmarks.trace import scopes

    cell = Cell.find(CELL)
    hf = cell.config
    monkeypatch.setattr(scopes, "for_obs", lambda obs: obs.get("_got"))
    other = Cell.find("mixtral-8x7b.serve-batch").config
    full = {"attention/kernel": 0.1, "mlp_moe/experts": 0.1}
    cases = [
        {"config": hf, "timing": {}, "trace": None, "peaks": None},
        dict(_traced(hf, {}), _got=None),
        _traced(hf, {}, orion_decode_window=full, orion_prefill=full),
        _traced(other, {"block_kv_positions_read": 5,
                        "prefill_attn_pairs": 5},
                orion_denoise_block=full, orion_prefill=full),
    ]
    for obs in cases:
        for name in NEW:
            assert cell.reader(name).read(obs) is None, (name, obs["config"]
                                                         is hf)


def test_the_lists_that_name_the_cell():
    """PR 50's rule: every list that names the cell is one whose accepted
    reader gives this cell a number. The cell joins the engine, admit, gap,
    idle, prefill and compile lists and its own five; it stays off every
    list whose reader divides by runs of the decode-window program, and off
    every other configuration's kernel shares."""
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    metrics = bm["end_to_end"] + bm["per_layer"]
    mine = {m["name"] for m in metrics if CELL in m.get("workloads", ())}
    assert mine == set(JOINED) | set(NEW)
    assert not mine & set(OFF)
    for m in metrics:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
        elif m["name"] in mine:
            assert m["workloads"].count(CELL) == 1
    assert [m["name"] for m in bm["per_layer"] if m["name"] in NEW] == list(
        NEW)
    cell = Cell.find(CELL)
    assert {m["name"] for m in cell.per_layer} == mine - {"serve_tokens_per_s"}
    for name in mine - {"serve_tokens_per_s"}:
        assert hasattr(cell.reader(name), "read")
    # what the stayed-off readers divide by does not exist in this cell
    from benchmarks.metrics import lib

    assert lib.decode_step_ms({"trace": {
        "module_n": {"jit_orion_denoise_block(1)": 3, "jit_orion_prefill": 1},
        "module_s": {}}, "decode_window": 4}) is None


def test_what_mimos_pinned_test_held_besides_its_pins():
    """``test_mimo_cell.py::test_the_parent_of_this_configuration_reads_
    nothing`` pins MiMo's cell as the LAST workload and the last name of
    every shared list, which this PR's appended cell makes false (a file the
    benchmark had is a ``benchmark`` PR's to edit: ``tests/conftest.py``
    expects that test to fail at that one statement). What it held besides,
    by name and membership so that a later cell breaks nothing here: MiMo's
    cell is under every list a layer-plan serving cell is under, right
    behind Ling's, its four metrics are its own, and its readers read
    nothing from a parent or from another configuration."""
    from tests.benchmark import test_mimo_cell as mimo_test

    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    ling, mimo_cell = "ling-3.0-flash.serve-reason-128", mimo_test.CELL
    for m in bm["end_to_end"] + bm["per_layer"]:
        shared = m["name"].endswith(".batch") or m["name"] in (
            "compile_s", "serve_tokens_per_s")
        lists = m.get("workloads", ())
        if ling in lists and shared:
            assert lists[lists.index(ling) + 1] == mimo_cell, m["name"]
        if mimo_cell in lists:
            assert shared or m["name"] in mimo_test.NEW, m["name"]
    chips = {w["name"]: w["chips"] for w in bm["workloads"]}
    assert chips[mimo_cell] == 1
    cell = Cell.find(mimo_cell)
    mine = [m["name"] for m in cell.per_layer]
    assert [n for n in mine if n in mimo_test.NEW] == list(mimo_test.NEW)
    assert len(mine) == 23 + 4
    empty = {"timing": {}, "config": cell.config, "slots": 64,
             "decode_window": 8, "peaks": {"hbm_bytes_per_s": 819e9,
                                           "bf16_flops": 197e12},
             "trace": {"timing": {}, "op_s": {"fusion.1": 1.0},
                       "module_s": {"jit__unknown(1)": 1.0},
                       "module_n": {"jit__unknown(1)": 2}}}
    other = Cell.find("laguna-s-2.1.serve-batch-4k").config
    for name in mimo_test.NEW:
        assert cell.reader(name).read(empty) is None
        assert cell.reader(name).read(dict(empty, trace=None)) is None
        assert cell.reader(name).read(dict(empty, config=other)) is None
    quiet = dict(empty, trace=dict(empty["trace"], timing={
        "prefill_attn_pairs": 0}, op_s={
            "attention.1_custom-call_bf16_1_64_2048_128_": 0.1}))
    assert cell.reader(mimo_test.NEW[1]).read(quiet) is None


def _tiny_configuration():
    published = {
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
        "tie_word_embeddings": False, "sliding_window": None,
        "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "attention_bias": False,
        "model_type": "sdar_moe", "decoder_sparse_step": 1,
        "mlp_only_layers": [], "norm_topk_prob": True,
    }
    real = Cell.find(CELL).config
    cfg = dict(
        published, source="test", reduced=[], assumed={}, role="serve",
        reference="sdar", frontend={"prefill_token_budget": 128},
        generation={"block_length": 4, "mask_token_id": 255,
                    "denoising_steps": 2,
                    "remasking": "low_confidence_static",
                    "confidence_threshold": 0.9},
        orion={"preset": "tiny-sdar",
               "overrides": ["inference.prefill_chunk=32",
                             "inference.remasking=low_confidence_static"],
               "widths": real["orion"]["widths"],
               "unchecked": {k: real["orion"]["unchecked"][k]
                             for k in published
                             if k in real["orion"]["unchecked"]}},
        correct={"router_margin_min": 0.0, "limits": {
            "logit_rel_err_worst_probe_median_clear": 1e-3,
            "logit_rel_err_all_probes_median_clear": 1e-3,
            "block_kv_rel_err_max": 1e-4, "block_token_gap_max": 1e-3,
            "block_order_excess_max": 1e-3,
            "block_mask_side_median": 1e-2}})
    return cfg, published


@pytest.fixture(scope="module")
def sdar_root(tmp_path_factory):
    """The tests' tiny benchmark root with one more configuration, a mix of
    the kind this cell's traffic names, and a cell listed under every metric
    the real cell is listed under."""
    root = write_root(tmp_path_factory.mktemp("tiny_sdar"))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append(add_configuration(
        root, "tiny-sdar-serve", *_tiny_configuration()))
    mix = {k: v for k, v in MIXES["tiny-batch"].items()
           if k != "probe_windows"}
    (root / "benchmarks" / "traffic" / "tiny-blocks.json").write_text(
        json.dumps(dict(mix, kind="serve_blocks", probe_prompts=[6, 41],
                        probe_blocks=3)))
    bm["workloads"].append({"name": TINY, "config": "tiny-sdar-serve",
                            "traffic": "tiny-blocks", "chips": 1,
                            "why": "test"})
    mine = {m["name"] for m in real["end_to_end"] + real["per_layer"]
            if CELL in m.get("workloads", ())}
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in mine:
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def test_a_tiny_configuration_of_this_kind_runs_end_to_end(
        sdar_root, capsys, monkeypatch):
    """``run.py`` itself, traced, on the CPU (counts only), with no edit to
    a file the harness had: the traffic names the kind whose tap runs every
    forward of a probe's block programs again; the probes (6 and 41 tokens:
    tails of 2 and 1) are correct under all five limits; every request
    generates exactly its ``max_new``; of the five new metrics the one that
    is engine counters alone is reported, and none of another cell's."""
    rc, lines = run_cell(sdar_root, TINY, capsys, monkeypatch, trace=1)
    assert rc == 0
    out = json.loads(lines[-1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compiles_in_window.batch"] == 0
    assert [n for n in m if n.endswith(".blocks")] == [NEW[1]]
    assert 0.8 < m[NEW[1]] <= 4 / 3       # 4 / 3 less tails and cut blocks
    assert "slot_occupancy_pct.batch" not in m
    assert "prefill_pad_pct.batch" in m
    checks = dict(line.split(" = ")[0].split("check: ")[1:] + [line]
                  for line in lines if line.startswith("check: "))
    assert set(checks) == {
        "logit_rel_err_worst_probe_median_clear",
        "logit_rel_err_all_probes_median_clear", "block_kv_rel_err_max",
        "block_token_gap_max", "block_order_excess_max",
        "block_mask_side_median"}
    for name in ("block_kv_rel_err_max", "block_token_gap_max",
                 "block_order_excess_max"):
        assert f"{name} = 0.0 " in checks[name]
    said = [l for l in lines if l.startswith("compared positions")]
    assert said == ["compared positions clear of a router tie, per probe: "
                    "[25, 25] of 25"]           # 1 + 3 blocks x 2 x 4


def test_the_parent_fails_at_the_preset_lookup_before_any_device_work():
    """With this PR's benchmark files laid over a program that lacks the
    preset (as the driver runs the parent), the cell stops in
    ``harness/cell.program_config`` with a message, before any weights are
    drawn or any program is built."""
    import orion_tpu.config as config

    cell = Cell.find(CELL)
    kept = config._PRESETS.pop("sdar-30b-a3b")
    try:
        with pytest.raises((KeyError, ValueError, SystemExit),
                           match="sdar-30b-a3b"):
            cell.program_config()
    finally:
        config._PRESETS["sdar-30b-a3b"] = kept


def test_the_planted_faults_run_through_the_harness(
        sdar_root, capsys, monkeypatch):
    """``tools/sdar_fault_probe.py`` on the tiny cell (CPU): the benchmark's
    own ``probe_numbers`` and ``decide`` on an engine whose block program
    masks causally inside the block, then whose commit forward is left out,
    then whose static rule decides the least confident positions, each read
    by the number that is there for it. Unplanted the check passes (the
    test above); float32 on the CPU under limits of 1e-3 sees all three (the
    other three run on the chip, where all six are read: PERF.md section 6,
    PR 54)."""
    import runpy

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(sdar_root / ".c"))
    monkeypatch.setattr("sys.argv", [
        "sdar_fault_probe.py", "--workload", TINY, "--seed", "77",
        "--root", str(sdar_root), "--allow-cpu",
        "--faults", "causal,commit,least"])
    with pytest.raises(SystemExit) as done:
        runpy.run_path(str(REPO / "tools/sdar_fault_probe.py"),
                       run_name="__main__")
    assert done.value.code == 0
    lines = capsys.readouterr().out.splitlines()
    assert [l for l in lines if l.startswith("correct: ")] == [
        "correct: False"] * 3
    assert lines[-1].endswith("the check sees ['causal', 'commit', 'least']")
    # each by its own number: the mask's side, the commit's rows, the order
    over = [[l.split()[1] for l in part.splitlines()
             if l.startswith("check: ") and float(l.split(" = ")[1].split()[0])
             > float(l.split("limit ")[1].rstrip(")"))]
            for part in "\n".join(lines).split("-- fault planted: ")[1:]]
    assert "block_mask_side_median" in over[0]
    assert over[1] == ["block_kv_rel_err_max"]
    assert over[2] == ["block_order_excess_max"]


@pytest.mark.slow       # 9 s of compiling; the chip's own runs hold it too
def test_the_block_program_and_a_prefill_fit_the_chip():
    """The cell's block program and a prefill compiled for
    a described v5e (``test_aot_v5e.py`` finds cells of kind ``serve``
    alone): the W-query paged kernel under full ancestor words is in the
    block program (Mosaic takes it), the flash forward under the block mask
    in prefill, and both fit beside the weights and the pool."""
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
    keep_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
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
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)

        def total(c):
            m = c.memory_analysis()
            return (m.temp_size_in_bytes + m.argument_size_in_bytes
                    + m.output_size_in_bytes - m.alias_size_in_bytes)

        B, L = icfg.max_batch_size, mcfg.block_length
        block = jax.jit(partial(
            runner.denoise_block, cfg=mcfg, max_seq_len=icfg.max_seq_len,
            mesh=None, nan_guard=False, steps=icfg.denoising_steps,
            remasking=icfg.remasking, threshold=icfg.confidence_threshold,
            temperature=icfg.temperature, top_k=icfg.top_k,
            top_p=icfg.top_p), donate_argnums=(1,))
        compiled = block.lower(
            params, cache, i32(B, L), i32(B), i32(B),
            i32(B, pages_per_seq(icfg)),
            jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one), key,
        ).compile()
        text = compiled.as_text()
        assert "block_paged" in text and "paged_decode" not in text
        assert total(compiled) < 13.5 * 2 ** 30
        prefill = jax.jit(partial(
            runner.prefill_step, cfg=mcfg, mesh=None,
            paged_prefill=icfg.paged_prefill), donate_argnums=(1,))
        # (the narrowest shape: what Mosaic takes of the mask does not
        # depend on the length; the widest bursts, 8192 tokens, read 12.4
        # GiB when compiled by hand, CHANGES.md PR 54)
        compiled = prefill.lower(
            params, cache, i32(1, 1024), i32(1), i32(1, 16), i32(1),
            i32(1, 0), None, i32(1), i32(B), key).compile()
        assert "flash_fwd" in compiled.as_text()
        assert total(compiled) < 13.5 * 2 ** 30
    finally:
        jax.config.update("jax_enable_compilation_cache", keep_cache)
        for mod, fn in patched:
            mod.resolve_interpret = fn
