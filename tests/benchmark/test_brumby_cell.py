"""The configuration whose per-request state is a fixed-size matrix beside a
short paged tail (Brumby-14B-Base): its cut table from its file's own keys,
the byte and unit functions against hand counts, a tiny configuration of the
same kind through ``run.py`` on the CPU with no edit to the harness, and its
readers on a small recorded segment."""

import json

import pytest

from benchmarks.harness.cell import Cell
from benchmarks.metrics import retention
from tests.benchmark.conftest import (REPO, add_configuration, run_cell,
                                      write_root)

CELL = "brumby-14b.serve-longout"
TINY = "tiny.brumby"
NEW = ("retention_decode_roofline.longout",
       "retention_prefill_roofline.longout",
       "retention_fold_ms_per_ktoken.longout",
       "state_empty_read_pct.longout")


def test_bytes_against_the_cut_table():
    """ISSUE 33's arithmetic, in bf16, from the file's own keys: a layer is
    330.3 M parameters, eight of them and the whole vocabulary 8.40 GB; a
    state row is 8 heads x 8320 x 128 as the program lays it out (8256 as
    the model needs it); a tail token 4096 B a layer."""
    cell = Cell.find(CELL)
    hf = cell.config
    n = 0
    for shape, _ in cell.reference().param_spec(hf).values():
        size = 1
        for d in shape:
            size *= d
        n += size
    layer = (2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408
             + 5120 * 8 + 2 * 5120 + 2 * 128)
    assert layer == 330_352_896
    assert n == 8 * layer + 2 * 151936 * 5120 + 5120 == 4_198_652_928
    assert 8.39e9 < 2 * n < 8.40e9                          # "8.40 GB"
    cfg = cell.program_config()
    m, icfg = cfg.model, cfg.inference
    from orion_tpu.ops.retention import fold_chunk

    assert (m.n_layers, fold_chunk(m.max_seq_len), icfg.max_batch_size) == (
        8, 512, 32)
    # the state as held: 33 rows x 8 layers x 8 heads x 65 slabs of 128 x 128
    held = 33 * 8 * 8 * 65 * 128 * 128 * 2
    assert 4.49e9 < held < 4.51e9                           # "4.50 GB"
    # the tail pool: 4096 B a token and layer, 2.1 MB a page, 640 pages
    page = 8 * 64 * 2 * 8 * 128 * 2
    assert page == 2_097_152 and icfg.num_pages * page == 1_342_177_280
    # a slot's tail: a chunk and a window, 9 pages, and a row of a prefill
    # burst its whole bucket (16) until its first window; 32 of those fit
    assert (512 + 8 + 63) // 64 == 9 and 32 * 16 < icfg.num_pages
    assert icfg.max_seq_len == 8192 + 4096
    assert 2 * n + held + icfg.num_pages * page < 0.9 * 15.75 * 2 ** 30


def test_byte_and_unit_functions_against_hand_counts():
    hf = Cell.find(CELL).config
    assert retention.state_entries(hf) == 128 * 129 // 2 == 8256
    assert retention.state_row_bytes(hf) == 8 * 8256 * 128 * 2 + 8 * 8256 * 4
    assert retention.tail_token_bytes(hf) == 4096 + 32
    assert retention.decode_bytes(hf, 3, 10) == (
        3 * retention.state_row_bytes(hf) + 10 * 4128)
    # the units are the engine's count (tests/test_brumby.py has it by hand:
    # a 700-token prompt is 700 x 701 a layer); a unit is 128 multiply-adds
    # of each of 40 query heads
    assert retention.prefill_flops(hf, 5) == 5 * 2 * 128 * 40


def test_the_mix_and_its_probes_lie_inside_the_warmed_shapes():
    from benchmarks.kinds import serve
    from benchmarks.traffic.generator import length_table

    cell = Cell.find(CELL)
    icfg = cell.program_config().inference
    table = length_table(cell.mix)
    assert len(table) == 32 == cell.mix["clients"] == icfg.max_batch_size
    assert min(p for p, _ in table) >= 128 and max(p for p, _ in table) <= 8192
    assert min(o for _, o in table) >= 256 and max(o for _, o in table) <= 4096
    assert max(p + o for p, o in table) <= icfg.max_seq_len
    assert sum(o for _, o in table) > sum(p for p, _ in table)   # long outputs
    shapes = serve.cell_prefill_shapes(cell, icfg)
    assert len(shapes) == 14
    assert all(nb * s <= 8192 and s % 1024 == 0 for nb, s in shapes)
    for n in cell.mix["probe_prompts"]:
        assert (1, -(-n // 1024) * 1024) in shapes
    # 2044 + 8 crosses 2048 inside the first window: a fold before the
    # second; 500 is under one chunk (tail only), 8192 a whole number of
    # them (an empty tail), 4500 state and a tail of 404
    from orion_tpu.ops.retention import CHUNK

    assert 2044 // CHUNK == 3 and (2044 + 8) // CHUNK == 4
    assert cell.mix["probe_prompts"] == [500, 2044, 4500, 8192]
    assert 8192 % CHUNK == 0 and 500 < CHUNK and 4500 % CHUNK == 404


def test_the_layout_the_reference_describes_is_the_programs():
    import jax

    from orion_tpu.models.transformer import init_params

    cell = Cell.find(CELL)
    m = cell.program_config().model
    shapes = jax.eval_shape(lambda: init_params(m, jax.random.key(0)))
    got = {tuple(k.key for k in path): leaf.shape for path, leaf
           in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    want = {path: shape for path, (shape, _)
            in cell.reference().param_spec(cell.config).items()}
    assert got == want


def _tiny_configuration():
    published = {
        "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 5, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000,
        "tie_word_embeddings": False, "sliding_window": None,
        "attention_bias": False, "model_type": "brumby",
    }
    cfg = dict(
        published, num_hidden_layers=3, source="test",
        reduced=["num_hidden_layers"], published={"num_hidden_layers": 5},
        assumed={}, role="serve", reference="brumby",
        deployment={"chips_sharing_a_layer": 1},
        frontend={"prefill_token_budget": 128},
        orion={"preset": "tiny-brumby",
               "overrides": ["inference.decode_window=4",
                             "inference.prefill_chunk=32"],
               "widths": {"attention_bias": "attn_bias"},
               "unchecked": {"model_type": "the family's name"}},
        correct={"router_margin_min": 0.0, "limits": {
            "logit_rel_err_worst_probe_median_clear": 1e-3,
            "window_kv_rel_err_max": 1e-4, "window_token_gap_max": 1e-3}})
    return cfg, published


@pytest.fixture(scope="module")
def brumby_root(tmp_path_factory):
    """The tests' tiny benchmark root with one more configuration and cell,
    listed under the metrics the real cell is listed under."""
    root = write_root(tmp_path_factory.mktemp("tiny_brumby"))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append(add_configuration(
        root, "tiny-brumby-serve", *_tiny_configuration()))
    bm["workloads"].append({"name": TINY, "config": "tiny-brumby-serve",
                            "traffic": "tiny-batch", "chips": 1,
                            "why": "test"})
    mine = {m["name"] for m in real["end_to_end"] + real["per_layer"]
            if CELL in m.get("workloads", ())}
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in mine:
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def test_a_tiny_configuration_of_this_kind_runs_end_to_end(
        brumby_root, capsys, monkeypatch):
    """``run.py`` itself, traced, on the CPU (counts only), with no edit to
    the harness: the warm-up's seven prefill arguments compile the programs
    the window runs, the probes (5 and 40 tokens over chunks of 16: tail
    only, and state with a tail that folds at the second window) are correct
    against the quadratic reference with the window link at 0, and the one
    new metric that is an exact count is reported."""
    rc, lines = run_cell(brumby_root, TINY, capsys, monkeypatch, trace=1)
    assert rc == 0
    out = json.loads(lines[-1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compiles_in_window.batch"] == 0
    # prompts of 4-60 over chunks of 16: some slots hold nothing yet
    assert 0 < m["state_empty_read_pct.longout"] < 100
    assert "paged_decode_roofline.batch" not in m          # a K/V model's
    checks = dict(line.split(" = ")[0].split("check: ")[1:] + [line]
                  for line in lines if line.startswith("check: "))
    assert set(checks) == {"logit_rel_err_worst_probe_median_clear",
                           "window_kv_rel_err_max", "window_token_gap_max"}
    assert "window_kv_rel_err_max = 0.0 " in checks["window_kv_rel_err_max"]


def test_the_planted_state_faults_run_through_the_harness(
        brumby_root, capsys, monkeypatch):
    """``tools/state_fault_probe.py`` on the tiny cell (CPU): the
    benchmark's own ``probe_numbers`` and ``decide`` on an engine whose
    decode reads state rows of zeros, then whose fold writes nothing. The
    tiny mix's probes (5 and 40 tokens, two windows of 4, gates of about a
    half) put two of nine positions behind a fold, so here too a probe's
    median does not see what the worst position sees: what PR 33 found of
    the real cell on the chip (PERF.md section 7)."""
    import runpy

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(brumby_root / ".c"))
    monkeypatch.setattr("sys.argv", [
        "state_fault_probe.py", "--workload", TINY, "--seed", "77",
        "--root", str(brumby_root), "--allow-cpu"])
    with pytest.raises(SystemExit) as done:
        runpy.run_path(str(REPO / "tools/state_fault_probe.py"),
                       run_name="__main__")
    assert done.value.code == 0
    lines = capsys.readouterr().out.splitlines()
    assert [l for l in lines if l.startswith("-- fault planted")] == [
        "-- fault planted: none", "-- fault planted: zeros",
        "-- fault planted: fold"]
    assert [l for l in lines if l.startswith("correct: ")][0] == (
        "correct: True")
    worst = [max(float(x) for x in l.split("positions ")[1].split())
             for l in lines if l.startswith("probe 40")]
    assert worst[0] < 1e-3 < worst[1]       # zeros: seen at some position
    assert lines[-1].startswith("verdicts ")


def test_the_parent_of_this_configuration_reads_nothing():
    """The benchmark as this PR leaves it is laid over the parent too: where
    the program has no such counter or operation, the new readers return
    None and do not raise."""
    cell = Cell.find(CELL)
    empty = {"timing": {}, "config": cell.config, "slots": 32,
             "decode_window": 8, "peaks": {"hbm_bytes_per_s": 819e9,
                                           "bf16_flops": 197e12},
             "trace": {"timing": {}, "op_s": {"fusion.1": 1.0},
                       "module_s": {"jit__unknown(1)": 1.0},
                       "module_n": {"jit__unknown(1)": 2}}}
    assert {m["name"] for m in cell.per_layer} >= set(NEW)
    for name in NEW:
        assert cell.reader(name).read(empty) is None
        assert cell.reader(name).read(dict(empty, trace=None)) is None


def test_the_readers_on_a_recorded_segment():
    """The arithmetic by hand on a small segment in the recorded form: two
    decode windows of 8 steps over 32 slots (eight ``retention_decode``
    calls a step), one prefill dispatch of a 700-token prompt, one fold."""
    from benchmarks.harness.device import PEAKS
    from benchmarks.trace import reduce

    cell = Cell.find(CELL)
    hf = cell.config
    rec = json.loads((REPO / "tests/benchmark/data/"
                      "trace_brumby_longout_small.json").read_text())
    tr = reduce.reduce(rec, rec["window_s"])
    tr["timing"] = rec["timing"]
    obs = {"trace": tr, "timing": rec["timing"], "config": hf,
           "peaks": PEAKS["TPU v5 lite"], "slots": rec["slots"],
           "decode_window": rec["decode_window"]}
    ops = rec["devices"]["0"]["XLA Ops"]

    def seconds(prefix):
        return sum(d for n, _, d in ops if n.startswith(prefix)) / 1e9

    t = rec["timing"]
    assert t["decode_state_slot_layers"] == 2 * 8 * 32 * 8
    assert t["prefill_retention_units"] == 8 * 700 * 701
    row = 8 * 8256 * 128 * 2 + 8 * 8256 * 4
    want = {
        NEW[0]: 100 * (t["decode_state_slot_layers"] * row
                       + t["decode_tail_token_layers"] * 4128) / 819e9
        / seconds("retention_decode"),
        NEW[1]: 100 * t["prefill_retention_units"] * 2 * 128 * 40 / 197e12
        / seconds("retention_prefill"),
        NEW[2]: 1e3 * seconds("retention_fold")
        / ((t["slot_steps"] - t["wasted_steps"]) / 1000),
        NEW[3]: 100 * t["decode_state_empty_slot_layers"]
        / t["decode_state_slot_layers"],
    }
    for name in NEW:
        got = cell.reader(name).read(obs)
        assert got == pytest.approx(want[name], rel=1e-9), name
        if "roofline" in name:
            assert 0 < got < 100, name
