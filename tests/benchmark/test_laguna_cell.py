"""The configuration that holds a share of its experts under two kinds of
attention layer (Laguna-S-2.1): its byte and FLOP functions against the cut
table of its file, a tiny configuration of the same kind through ``run.py``
on the CPU with no edit to the harness, and its readers on what a v5e
recorded."""

import json

import pytest

from benchmarks.harness.cell import Cell
from benchmarks.metrics import held_share
from tests.benchmark.conftest import (REPO, add_configuration, run_cell,
                                      write_root)

CELL = "laguna-s-2.1.serve-batch-4k"
TINY = "tiny.laguna"


def test_bytes_and_flops_against_the_cut_table():
    """ISSUE 28's table, in bf16: 128 experts x 9.44 M parameters a sparse
    layer (2.42 GB) and a shared one of 9.4 M, four sparse layers; 4096 B of
    K and V a token and layer; a routed row costs 6 x 3072 x 1024."""
    cell = Cell.find(CELL)
    hf = cell.config
    assert held_share.sparse_layers(hf) == 4
    expert = 3 * 3072 * 1024
    assert expert == 9_437_184
    assert held_share.held_expert_bytes(hf) == 4 * 128 * expert * 2
    assert 4 * 2.41e9 < held_share.held_expert_bytes(hf) < 4 * 2.42e9   # "2.42 GB"
    assert held_share.kv_bytes(hf, 1) == 4096
    assert held_share.kv_bytes(hf, 5 * 64) == 1_310_720       # a page
    assert held_share.held_expert_matmul_flops(hf, 7) == 7 * 6 * 3072 * 1024
    # the weights of the cut: 5.57 B parameters, 11.14 GB
    spec = cell.reference().param_spec(hf)
    n = 0
    for shape, _ in spec.values():
        size = 1
        for d in shape:
            size *= d
        n += size
    assert n == 5_572_076_544
    # and the pool: 2432 pages of 1.31 MB
    icfg = cell.program_config().inference
    assert icfg.num_pages * held_share.kv_bytes(hf, 5 * 64) == 3_187_671_040
    assert icfg.num_pages * icfg.page_size >= 32 * (4096 + 768)


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
    """The tiny preset under the published key names: the second share (8
    of 16 experts from expert 8) of a depth cut."""
    layers = ["full_attention"] + ["sliding_attention"] * 3
    rope = {"full_attention": {
        "rope_type": "yarn", "rope_theta": 10000.0, "factor": 8.0,
        "original_max_position_embeddings": 16, "beta_fast": 4.0,
        "beta_slow": 1.0, "attention_factor": 1.2,
        "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0,
                              "partial_rotary_factor": 1}}
    published = {
        "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "sliding_window": 8, "num_experts": 16, "num_experts_per_tok": 4,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "moe_routed_scaling_factor": 2.5, "gating": "per-head",
        "layer_types": layers * 2,
        "num_attention_heads_per_layer": [4, 6, 6, 6] * 2,
        "mlp_layer_types": ["dense"] + ["sparse"] * 7,
        "rope_parameters": rope,
    }
    lists = "a list or a nested group; tests/test_laguna.py holds the preset to it"
    cfg = dict(
        published, num_hidden_layers=6, num_experts=8, source="test",
        reduced=["num_hidden_layers", "num_experts"],
        published={"num_hidden_layers": 8, "num_experts": 16},
        assumed={}, role="serve", reference="laguna",
        deployment={"chips_sharing_a_layer": 2, "experts_held": [8, 16]},
        frontend={"prefill_token_budget": 128},
        orion={"preset": "tiny-laguna",
               "overrides": ["model.n_experts=8", "model.expert_offset=8",
                             "inference.decode_window=4"],
               "widths": {
                   "num_experts": "n_experts",
                   "moe_intermediate_size": "moe_d_ff",
                   "shared_expert_intermediate_size": "shared_expert_d_ff",
                   "moe_routed_scaling_factor": "router_scale",
                   "gating": "attn_gate"},
               "unchecked": {k: lists for k in (
                   "layer_types", "num_attention_heads_per_layer",
                   "mlp_layer_types", "rope_parameters")}},
        correct={"router_margin_min": 0.0, "limits": {
            "logit_rel_err_worst_probe_median_clear": 1e-3,
            "window_kv_rel_err_max": 1e-4, "window_token_gap_max": 1e-3}})
    return cfg, published


@pytest.fixture(scope="module")
def laguna_root(tmp_path_factory):
    """The tests' tiny benchmark root with one more configuration and cell,
    listed under the metrics the real cell is listed under."""
    root = write_root(tmp_path_factory.mktemp("tiny_laguna"))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append(add_configuration(
        root, "tiny-laguna-serve", *_tiny_configuration()))
    bm["workloads"].append({"name": TINY, "config": "tiny-laguna-serve",
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
        laguna_root, capsys, monkeypatch):
    """``run.py`` itself, traced, on the CPU (counts only): correct against
    the reference of ITS share, nothing compiled in the window, and the two
    metrics that are exact counts."""
    rc, lines = run_cell(laguna_root, TINY, capsys, monkeypatch, trace=1)
    assert rc == 0
    out = json.loads(lines[-1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compiles_in_window.batch"] == 0
    # top-4 of 16 with 8 held: 2 rows a position and sparse layer, and what
    # the padded rows of a burst route (one position each)
    assert 1.7 < m["prefill_held_rows_per_token.batch4k"] < 2.6
    # prompts to 60 and outputs to 12 over pages of 8 under a window of 8
    assert 5 < m["window_kv_dead_pct.batch4k"] < 60
    assert "moe_ffn_roofline.batch" not in m          # Mixtral's, not ours


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
    for m in cell.per_layer:
        if m["name"].endswith(".batch4k"):
            assert cell.reader(m["name"]).read(empty) is None
            assert cell.reader(m["name"]).read(dict(empty, trace=None)) is None


def test_the_readers_on_what_a_v5e_recorded():
    """The arithmetic by hand on the recorded segment: 224 token steps of
    the decode program, whose twelve routed expert operations a step read 4
    x 2.416 GB; the paged kernel's five calls a step against the bytes two
    layer kinds had to read; the grouped matmuls of 19 prefill dispatches
    against the rows the prefill programs counted on held experts."""
    from benchmarks.harness.device import PEAKS
    from benchmarks.trace import reduce

    cell = Cell.find(CELL)
    rec = json.loads((REPO / "tests/benchmark/data/"
                      "trace_laguna_batch4k_v5e.json").read_text())
    tr = reduce.reduce(rec, rec["window_s"])
    tr["timing"] = rec["timing"]
    obs = {"trace": tr, "timing": rec["timing"], "config": cell.config,
           "peaks": PEAKS["TPU v5 lite"], "slots": rec["slots"],
           "decode_window": rec["decode_window"]}
    ops = rec["devices"]["0"]["XLA Ops"]

    def seconds(pred):
        return sum(d for n, _, d in ops if pred(n)) / 1e9

    got = {m["name"]: cell.reader(m["name"]).read(obs)
           for m in cell.per_layer if m["name"].endswith(".batch4k")}
    steps = rec["timing"]["windows"] * rec["decode_window"]
    expert_s = seconds(lambda n: n.startswith("fusion."))
    assert len([n for n, _, _ in ops if n.startswith("fusion.")]) == 12 * steps
    want = {
        "held_expert_ffn_roofline.batch4k":
            100 * steps * 4 * 128 * 3 * 3072 * 1024 * 2 / 819e9 / expert_s,
        "mixed_paged_decode_roofline.batch4k":
            100 * rec["timing"]["decode_kv_token_layers"] * 4096 / 819e9
            / seconds(lambda n: n.startswith("paged_decode")),
        "prefill_held_expert_roofline.batch4k":
            100 * rec["timing"]["prefill_held_expert_rows"] * 6 * 3072 * 1024
            / 197e12 / seconds(lambda n: n.startswith("gmm")),
        "prefill_held_rows_per_token.batch4k":
            rec["timing"]["prefill_held_expert_rows"]
            / rec["timing"]["prefill_tokens"] / 4,
        "window_kv_dead_pct.batch4k":
            100 * rec["timing"]["kv_dead_window_page_layers"]
            / rec["timing"]["kv_live_page_layers"],
    }
    assert set(got) == set(want)
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-9), name
        if "roofline" in name:      # the two counts were read off the
            # 40 s window's counters, the record holds the traced segment's
            assert got[name] == pytest.approx(rec["read"][name], rel=1e-6)
        assert 0 < got[name] < 100, name
    assert 4.9 < got["prefill_held_rows_per_token.batch4k"] < 5.2
    assert got["held_expert_ffn_roofline.batch4k"] > 80     # memory-bound
