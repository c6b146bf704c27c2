"""The configuration whose cache is one compressed row a token and layer
(GLM-4.7-Flash): its cut table from its file's own keys, the byte and
operation functions against hand counts, a tiny configuration of the same
kind through ``run.py`` on the CPU with no edit to the harness, and its
readers on a small recorded segment."""

import json

import pytest

from benchmarks.harness.cell import Cell
from benchmarks.metrics import latent
from tests.benchmark.conftest import (REPO, add_configuration, run_cell,
                                      write_root)

CELL = "glm-4.7-flash.serve-longctx"
TINY = "tiny.glm"
NEW = ("latent_decode_roofline.longctx",
       "prefill_attn_roofline.longctx",
       "routed_expert_ffn_roofline.longctx",
       "latent_cache_bytes_per_token.longctx")


def test_bytes_against_the_cut_table():
    """ISSUE 36's arithmetic, in bf16, from the file's own keys: a sparse
    layer is 635.3 M parameters, five of them, the dense one and the whole
    vocabulary 7.79 GB; a cached row is 1152 B as the model needs it and
    1280 B as the pool lays it out; 10,753 pages hold every slot at its
    longest."""
    cell = Cell.find(CELL)
    hf = cell.config
    n = 0
    for shape, _ in cell.reference().param_spec(hf).values():
        size = 1
        for d in shape:
            size *= d
        n += size
    attn = (2048 * 768 + 768 + 768 * 5120 + 2048 * 576 + 512
            + 512 * 8960 + 5120 * 2048 + 2 * 2048)
    assert attn == 21_763_328
    dense = attn + 3 * 2048 * 10240
    sparse = attn + 2048 * 64 + 64 + 65 * 3 * 2048 * 1536
    assert (dense, sparse) == (84_677_888, 635_311_424)
    assert n == dense + 5 * sparse + 2 * 154880 * 2048 + 2048 == 3_895_625_536
    assert 7.79e9 < 2 * n < 7.80e9                          # "7.79 GB"
    cfg = cell.program_config()
    m, icfg = cfg.model, cfg.inference
    assert (m.n_layers, icfg.max_batch_size, icfg.page_size,
            icfg.decode_window) == (6, 32, 64, 8)
    assert m.capacity_factor == 64 / 4                      # dropless
    assert icfg.max_seq_len == 20480 + 1024 == 336 * 64
    assert icfg.num_pages == 32 * 336 + 1 == 10_753
    assert latent.latent_row_bytes(hf) == 1152
    assert 20 * (256 + 256) * 2 == 20480 and 20480 / 1152 > 17.7
    from orion_tpu.infer.kv_cache import latent_width

    page = latent_width(m) * 2 * 6 * 64
    assert latent_width(m) == 640 and page == 491_520
    pool = icfg.num_pages * page
    assert 5.28e9 < pool < 5.29e9                           # "5.285 GB"
    assert 4.75e9 < icfg.num_pages * 1152 * 6 * 64 < 4.76e9
    assert 13.07e9 < 2 * n + pool < 13.09e9                 # "13.08 GB"
    assert 2 * n + pool < 0.8 * 15.75 * 2 ** 30
    dep = hf["deployment"]
    assert (dep["chips"], dep["chips_sharing_a_layer"]) == (8, 1)
    assert 7 * 6 + 5 == hf["published"]["num_hidden_layers"] == 47
    # whole: 46 sparse layers 58.4 GB, 59.9 GB with the rest
    assert 58.4e9 < 2 * 46 * sparse < 58.5e9
    assert 59.8e9 < 2 * (dense + 46 * sparse + 2 * 154880 * 2048) < 59.9e9


def test_byte_and_operation_functions_against_hand_counts():
    hf = Cell.find(CELL).config
    assert latent.latent_row_bytes(hf) == (512 + 64) * 2
    assert latent.decode_bytes(hf, 10) == 11_520
    # a pair: 20 heads x (a score over 256 + a value of 256) x 2
    assert latent.prefill_attn_flops(hf, 3) == 3 * 20 * 512 * 2
    assert latent.sparse_layers(hf) == 5
    assert latent.routed_expert_bytes(hf) == 5 * 64 * 3 * 2048 * 1536 * 2
    # ISSUE 36's "7.16 GB of weights a step" is these and the rest of the
    # layers; the routed experts alone are 6.04 GB
    assert 6.03e9 < latent.routed_expert_bytes(hf) < 6.05e9
    # a 20480-token prompt: 6 layers x 209.7 M pairs, 25.8 TFLOP; the
    # mix's mean request (11254 tokens): 7.8 TFLOP
    pairs = 6 * 20480 * 20481 // 2
    assert 25.7e12 < latent.prefill_attn_flops(hf, pairs) < 25.8e12
    assert 7.7e12 < latent.prefill_attn_flops(
        hf, 6 * 11254 * 11255 // 2) < 7.9e12


def test_the_mix_and_its_probes_lie_inside_the_warmed_shapes():
    from benchmarks.kinds import serve
    from benchmarks.traffic.generator import length_table

    cell = Cell.find(CELL)
    icfg = cell.program_config().inference
    table = length_table(cell.mix)
    assert len(table) == 32 == cell.mix["clients"] == icfg.max_batch_size
    prompts, outputs = [p for p, _ in table], [o for _, o in table]
    assert (min(prompts), max(prompts), sum(prompts)) == (2812, 20480, 360_112)
    assert (min(outputs), max(outputs), sum(outputs)) == (105, 1024, 14_183)
    assert max(p + o for p, o in table) <= icfg.max_seq_len
    shapes = serve.cell_prefill_shapes(cell, icfg)
    assert len(shapes) == 15
    assert all(nb * s <= 20480 and s % 2048 == 0 for nb, s in shapes)
    assert (1, 20480) in shapes                 # admitted alone
    for n in cell.mix["probe_prompts"]:
        assert (1, -(-n // 2048) * 2048) in shapes
    assert cell.mix["probe_prompts"] == [1000, 5000, 12000, 20480]


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


def test_every_published_key_is_stated_and_only_depth_is_reduced():
    cell = Cell.find(CELL)
    hf, pub = cell.config, cell.published
    assert hf["reduced"] == ["num_hidden_layers"]
    for key, value in pub.items():
        stated = hf["published"][key] if key in hf["reduced"] else hf[key]
        assert stated == value, key
    catalog = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"GLM-4.7-Flash"' in l] if __import__("os").path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    for row in catalog:
        assert row["config"] == pub and row["source_url"] == hf["source"]


def _tiny_configuration():
    published = {
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 5,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 1000000,
        "tie_word_embeddings": False, "attention_bias": False,
        "q_lora_rank": 40, "kv_lora_rank": 48, "qk_nope_head_dim": 24,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
        "n_shared_experts": 1, "moe_intermediate_size": 32,
        "num_experts_per_tok": 2, "first_k_dense_replace": 1,
        "routed_scaling_factor": 1.8, "norm_topk_prob": True,
        "model_type": "glm4_moe_lite",
    }
    real = Cell.find(CELL).config["orion"]["widths"]
    cfg = dict(
        published, num_hidden_layers=3, source="test",
        reduced=["num_hidden_layers"], published={"num_hidden_layers": 5},
        assumed={}, role="serve", reference="glm",
        deployment={"chips_sharing_a_layer": 1},
        frontend={"prefill_token_budget": 128},
        orion={"preset": "tiny-glm",
               "overrides": ["inference.decode_window=4",
                             "inference.prefill_chunk=32"],
               "widths": real,
               "unchecked": {"model_type": "the family's name",
                             "norm_topk_prob": "no field"}},
        correct={"router_margin_min": 0.0, "limits": {
            "logit_rel_err_worst_probe_median_clear": 1e-3,
            "window_kv_rel_err_max": 1e-4, "window_token_gap_max": 1e-3}})
    return cfg, published


@pytest.fixture(scope="module")
def glm_root(tmp_path_factory):
    """The tests' tiny benchmark root with one more configuration and cell,
    listed under the metrics the real cell is listed under."""
    root = write_root(tmp_path_factory.mktemp("tiny_glm"))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append(add_configuration(
        root, "tiny-glm-serve", *_tiny_configuration()))
    bm["workloads"].append({"name": TINY, "config": "tiny-glm-serve",
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
        glm_root, capsys, monkeypatch):
    """``run.py`` itself, traced, on the CPU (counts only), with no edit to
    the harness: ``program_config`` takes the head sizes through
    ``orion.widths`` (hidden / heads is not the head size), the window link
    reads the ``latent`` leaf, the probes are correct against the expanded
    reference, and the one new metric that is an exact count is reported."""
    rc, lines = run_cell(glm_root, TINY, capsys, monkeypatch, trace=1)
    assert rc == 0
    out = json.loads(lines[-1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compiles_in_window.batch"] == 0
    # rows of 128 float32 numbers in pages of 8: 512 B and the page rounding
    assert 512 <= m["latent_cache_bytes_per_token.longctx"] < 1024
    assert "paged_decode_roofline.batch" not in m          # a K/V model's
    checks = dict(line.split(" = ")[0].split("check: ")[1:] + [line]
                  for line in lines if line.startswith("check: "))
    assert set(checks) == {"logit_rel_err_worst_probe_median_clear",
                           "window_kv_rel_err_max", "window_token_gap_max"}
    assert "window_kv_rel_err_max = 0.0 " in checks["window_kv_rel_err_max"]


def test_the_parent_of_this_configuration_reads_nothing():
    """The benchmark as this PR leaves it is laid over the parent too: where
    the program has no such counter or operation, the new readers return
    None and do not raise; nor do they on another configuration's keys."""
    cell = Cell.find(CELL)
    empty = {"timing": {}, "config": cell.config, "slots": 32,
             "decode_window": 8, "peaks": {"hbm_bytes_per_s": 819e9,
                                           "bf16_flops": 197e12},
             "trace": {"timing": {}, "op_s": {"fusion.1": 1.0},
                       "module_s": {"jit__unknown(1)": 1.0},
                       "module_n": {"jit__unknown(1)": 2}}}
    assert {m["name"] for m in cell.per_layer} >= set(NEW)
    other = Cell.find("mixtral-8x7b.serve-batch").config
    for name in NEW:
        assert cell.reader(name).read(empty) is None
        assert cell.reader(name).read(dict(empty, trace=None)) is None
        assert cell.reader(name).read(dict(empty, config=other)) is None


def test_the_readers_on_a_recorded_segment():
    """The arithmetic by hand on a small segment in the recorded form: two
    decode windows of 8 steps over 32 slots (six ``latent_paged_decode``
    calls a step, fifteen routed-expert fusions), one prefill dispatch of a
    5000-token prompt in a bucket of 6144."""
    from benchmarks.harness.device import PEAKS
    from benchmarks.trace import reduce

    cell = Cell.find(CELL)
    hf = cell.config
    rec = json.loads((REPO / "tests/benchmark/data/"
                      "trace_glm_longctx_small.json").read_text())
    tr = reduce.reduce(rec, rec["window_s"])
    tr["timing"] = rec["timing"]
    obs = {"trace": tr, "timing": rec["timing"], "config": hf,
           "peaks": PEAKS["TPU v5 lite"], "slots": rec["slots"],
           "decode_window": rec["decode_window"]}
    ops = rec["devices"]["0"]["XLA Ops"]

    def seconds(*parts):
        return sum(d for n, _, d in ops
                   if any(p in n for p in parts)) / 1e9

    t = rec["timing"]
    assert t["prefill_attn_pairs"] == 6 * 5000 * 5001 // 2
    assert t["decode_latent_token_layers"] == 6 * sum(
        32 * 11_000 + 32 * j for j in range(16))
    want = {
        NEW[0]: 100 * t["decode_latent_token_layers"] * 1152 / 819e9
        / seconds("latent_paged_decode."),
        NEW[1]: 100 * t["prefill_attn_pairs"] * 20 * 512 * 2 / 197e12
        / seconds("_custom-call_bf16_1_20_6144_256_"),
        NEW[2]: 100 * 16 * 5 * 64 * 3 * 2048 * 1536 * 2 / 819e9
        / seconds("_fusion_bf16_64_32_1536_", "_fusion_bf16_32_64_1_2048_"),
        NEW[3]: t["latent_live_page_bytes"] / (t["latent_live_tokens"] * 6),
    }
    for name in NEW:
        got = cell.reader(name).read(obs)
        assert got == pytest.approx(want[name], rel=1e-9), name
        if "roofline" in name:
            assert 0 < got < 100, name
    assert 1280 <= want[NEW[3]] < 1300


def test_the_planted_latent_faults_run_through_the_harness(
        glm_root, capsys, monkeypatch):
    """``tools/latent_fault_probe.py`` on the tiny cell (CPU): the
    benchmark's own ``probe_numbers`` and ``decide`` on an engine whose
    decode drops ``q_rope . k_pe``, then whose gates hold the selection
    bias. Unplanted the check passes; float32 on the CPU under a limit of
    1e-3 sees both faults (on the chip, in bfloat16 under the cell's limit
    and the benchmark's draw: PERF.md section 6, PR 36)."""
    import runpy

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(glm_root / ".c"))
    monkeypatch.setattr("sys.argv", [
        "latent_fault_probe.py", "--workload", TINY, "--seed", "77",
        "--root", str(glm_root), "--allow-cpu"])
    with pytest.raises(SystemExit) as done:
        runpy.run_path(str(REPO / "tools/latent_fault_probe.py"),
                       run_name="__main__")
    assert done.value.code == 0
    lines = capsys.readouterr().out.splitlines()
    assert [l for l in lines if l.startswith("-- fault planted")] == [
        "-- fault planted: none", "-- fault planted: rope",
        "-- fault planted: bias"]
    assert [l for l in lines if l.startswith("correct: ")] == [
        "correct: True", "correct: False", "correct: False"]
    assert lines[-1].endswith("the check sees ['rope', 'bias']")
