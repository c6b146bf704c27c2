"""The MiniCPM-SALA cell: the cut table's arithmetic from the configuration
file's own keys, every published key checked or listed with its reason, the
cell found by ``Cell.find``, each new reader on hand-made ``obs`` (None
without its counter or kernel; never past 100 % where the kernel's time is
the least time), the traffic table, the closed set of prefill shapes, and the
cell's kind run whole on the CPU at a tiny size."""

import dataclasses
import json

import pytest

from tests.benchmark.conftest import PUBLISHED, REPO, run_cell, write_root

CELL = "minicpm-sala.serve-longdoc-64k"
CONFIG = "minicpm-sala-serve-1chip"
FILE = json.loads(
    (REPO / "benchmarks" / "configs" / f"{CONFIG}.json").read_text())
SOURCE = json.loads((REPO / PUBLISHED / f"{CONFIG}.json").read_text())
NEW = ("sparse_paged_decode_roofline.longdoc",
       "sparse_prefill_roofline.longdoc", "lightning_decode_roofline.longdoc",
       "lightning_prefill_roofline.longdoc",
       "block_select_ms_per_step.longdoc", "sparse_visible_pct.longdoc",
       "sala_cache_bytes_per_token.longdoc")


@pytest.fixture(scope="module")
def cell():
    from benchmarks.harness.cell import Cell

    return Cell.find(CELL)


# -- the configuration ------------------------------------------------------------


def test_the_file_is_the_published_layers_9_to_16(cell):
    """The cut is of the depth alone: ``mixer_types`` stays whole, as
    published, and ``first_layer`` says where in it the stage's layers lie."""
    assert FILE["reduced"] == ["num_hidden_layers"]
    assert FILE["published"] == {
        "num_hidden_layers": SOURCE["num_hidden_layers"]}
    first, depth = FILE["first_layer"], FILE["num_hidden_layers"]
    assert (first, depth) == (9, 8) and "first_layer" not in SOURCE
    stage = FILE["mixer_types"][first:first + depth]
    assert stage == ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    for key, value in SOURCE.items():       # no width differs from the source
        if key not in FILE["reduced"]:
            assert FILE[key] == value, key
    assert cell.reference()._layers(FILE) == (
        ["sparse"] + ["lightning"] * 6 + ["sparse"])
    m = cell.program_config().model
    assert list(m.mixer_types) == stage
    assert [k.attention for k in m.layer_kinds] == (
        ["sparse"] + ["lightning"] * 6 + ["sparse"])
    assert m.layer_plan == (3, 1, 0, 0, (1, 6, 1))
    # what orion.unchecked names, held here
    sparse, lightning = m.layer_kinds[0], m.layer_kinds[1]
    assert sparse.rope is None and not FILE["attn_use_rope"]
    assert lightning.rope.theta == FILE["rope_theta"]
    assert FILE["lightning_use_rope"]
    assert lightning.n_kv_heads == FILE["lightning_nkv"] == m.n_heads
    assert m.activation == "swiglu" and FILE["hidden_act"] == "silu"
    assert m.residual_scale == pytest.approx(
        FILE["scale_depth"] / SOURCE["num_hidden_layers"] ** 0.5)
    assert m.logit_scale == FILE["dim_model_base"] / FILE["hidden_size"]
    assert m.attn_gate == "elementwise" and FILE["use_output_gate"]
    assert FILE["attn_use_output_gate"] and FILE["use_output_norm"]
    assert dataclasses.asdict(m.sparse) == FILE["assumed"]["sparse"]
    assert m.sparse.block == cell.program_config().inference.page_size


def test_every_published_key_is_checked_or_listed_with_its_reason():
    from benchmarks.harness.cell import widths

    mapped, unchecked = widths(FILE), FILE["orion"]["unchecked"]
    for key in SOURCE:
        assert (key in mapped) != (key in unchecked), key
    assert all(len(why) > 20 for why in unchecked.values())
    assert set(unchecked) <= set(SOURCE)


def test_the_cut_tables_arithmetic(cell):
    import jax

    D, F, V = (FILE["hidden_size"], FILE["intermediate_size"],
               FILE["vocab_size"])
    N, K, H = (FILE["num_attention_heads"], FILE["num_key_value_heads"],
               FILE["head_dim"])
    mlp, norms = 3 * D * F, 2 * D + 2 * H
    lightning = 5 * D * D + mlp + norms + D
    sparse = 3 * D * D + 2 * D * K * H + mlp + norms
    shared = 2 * V * D + D
    table = FILE["deployment"]["cut_table_bf16"]
    said = lambda needle: next(
        int(v.split(" parameters")[0].replace(",", ""))
        for k, v in table.items() if k.startswith(needle))
    assert said("lightning layer") == lightning == 285_225_216
    assert said("sparse layer") == sparse == 253_763_840
    assert said("6 lightning + 2 sparse") == 6 * lightning + 2 * sparse
    assert said("embedding + head") == shared
    total = 6 * lightning + 2 * sparse + shared
    assert said("weights") == total
    assert round(2 * total / 1e9, 3) == 5.641
    # the reference's tree is that count, and the program's is its layout
    spec = cell.reference().param_spec(FILE)
    count = lambda shape: int(__import__("math").prod(shape))
    assert sum(count(s) for s, _ in spec.values()) == total
    cfg = cell.program_config()
    from orion_tpu.models.transformer import init_params

    mine = jax.eval_shape(
        lambda: init_params(cfg.model, jax.random.PRNGKey(0)))
    flat = {tuple(str(getattr(p, "key", p)) for p in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(mine)[0]}
    assert flat == {path: shape for path, (shape, _) in spec.items()}
    # the cache: 2,112 B a position, 48 slots x 1120 pages + 1, state rows
    from orion_tpu.infer.kv_cache import init_cache, pages_per_seq

    icfg = cfg.inference
    cache = jax.eval_shape(lambda: init_cache(cfg.model, icfg))
    size = lambda a: count(a.shape) * a.dtype.itemsize
    paged = sum(size(a) for n, a in cache.items() if n != "lightning_state")
    assert paged // (icfg.num_pages * icfg.page_size) == 2_112
    assert pages_per_seq(icfg) == 1_120 and icfg.max_seq_len == 65_536 + 6_144
    assert icfg.num_pages == 48 * 1_120 + 1 == 53_761
    assert round(paged / 1e9, 3) == 7.267
    assert round(size(cache["lightning_state"]) / 1e9, 3) == 0.617
    held = 2 * total + paged + size(cache["lightning_state"])
    assert 12e9 < held < 0.82 * 16.9e9 and round(held / 1e9, 2) == 13.52


def test_the_cell_and_its_lists(cell):
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    assert cell.chips == 1 and cell.mix["kind"] == "serve_chunks"
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "setup_s"}
    mine = [m for m in bm["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)       # in order
    # (no count and no "last" is asserted: a later PR appends behind these)
    assert all(CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
               for m in mine)
    assert CELL in [w["name"] for w in bm["workloads"]]
    assert CONFIG in [c["name"] for c in bm["configs"]]
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(
        1, len(bm["workloads"]) // 4)


def test_the_traffic_table_and_the_closed_set_of_shapes(cell):
    from benchmarks.traffic import generator

    mix = cell.mix
    assert (mix["clients"], mix["block"], mix["warm_requests"]) == (48,) * 3
    assert mix["prompt"] == {"median": 16384, "sigma": 0.7, "min": 8192,
                             "max": 65536}
    assert mix["output"] == {"median": 1536, "sigma": 0.7, "min": 256,
                             "max": 6144}
    assert mix["probe_prompts"] == [1000, 9000, 33000, 65536]
    assert (mix["probe_windows"], mix["trace_seconds"]) == (2, 6.0)
    table = generator.length_table(mix)
    prompts, outputs = zip(*table)
    assert len(table) == 48
    assert min(prompts) == 8192 and max(prompts) == 65536
    assert sum(p == 8192 for p in prompts) == 8
    assert sum(p == 65536 for p in prompts) == 1
    assert round(sum(prompts) / 48) == 20_751
    assert min(outputs) >= 256 and max(outputs) <= 6144
    assert round(sum(outputs) / 48) == 1_911
    icfg = cell.program_config().inference
    assert max(p + o for p, o in table) <= icfg.max_seq_len
    shapes = cell.kind_module().cell_prefill_shapes(cell, icfg)
    assert shapes == [(1, 1024), (1, 2048), (1, 3072), (1, 4096)]
    assert FILE["frontend"]["prefill_token_budget"] == 65536


# -- the readers --------------------------------------------------------------------


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def _obs(timing, op_s=None):
    return {"config": FILE, "peaks": PEAKS, "timing": timing, "steps": 10,
            "decode_window": 8,
            "trace": None if op_s is None else {
                "op_s": op_s, "timing": timing, "module_n": {},
                "module_s": {}}}


@pytest.mark.parametrize("name, counter, count, kernel, least", [
    ("sparse_paged_decode_roofline.longdoc", "decode_sparse_visible_keys",
     10 ** 7, "sparse_paged_decode.3", 10 ** 7 * 512 / 819e9),
    ("sparse_prefill_roofline.longdoc", "prefill_sparse_visible_pairs",
     10 ** 10, "sparse_paged_prefill.7", 10 ** 10 * 4 * 128 / 197e12),
    ("lightning_decode_roofline.longdoc", "decode_lightning_slot_layers",
     288, "lightning_decode.1", 288 * 2 * 2_097_152 / 819e9),
])
def test_a_kernels_share_is_100_at_the_least_time_and_none_without(
        cell, name, counter, count, kernel, least):
    read = cell.reader(name).read
    assert read(_obs({counter: count}, {kernel: least})) == pytest.approx(100)
    assert read(_obs({counter: count}, {kernel: 4 * least})) == (
        pytest.approx(25))
    assert read(_obs({counter: count}, {"fusion.1": 1.0})) is None
    assert read(_obs({}, {kernel: least})) is None
    assert read(_obs({counter: count})) is None         # no trace


def test_the_counters_readers_and_the_scope_readers_without_a_trace(cell):
    read = lambda name, obs: cell.reader(name).read(obs)
    assert read("sparse_visible_pct.longdoc", _obs(
        {"decode_sparse_visible_keys": 25,
         "decode_sparse_context_keys": 100})) == 25.0
    assert read("sparse_visible_pct.longdoc", _obs({})) is None
    assert read("sala_cache_bytes_per_token.longdoc", _obs(
        {"sala_live_tokens": 1000, "sala_live_page_bytes": 2_112_000,
         "lightning_live_state_bytes": 888_000})) == 3000.0
    assert read("sala_cache_bytes_per_token.longdoc", _obs({})) is None
    for name in ("lightning_prefill_roofline.longdoc",
                 "block_select_ms_per_step.longdoc"):
        assert read(name, _obs({"prefill_lightning_token_layers": 5})) is None
    from benchmarks.metrics import sala

    assert sala.key_bytes(FILE) == 512
    assert sala.state_row_bytes(FILE) == 2_097_152
    assert sala.lightning_prefill_flops(FILE, 1) == 5 * 32 * 128 * 128


# -- the kind, whole, at a tiny size ---------------------------------------------------


TINY = dict(
    hidden_size=64, vocab_size=256, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
    intermediate_size=128, qk_norm=True, lightning_use_rope=True,
    attn_use_rope=False, rope_theta=10000.0, rms_norm_eps=1e-6,
    use_output_norm=True, use_output_gate=True, attn_use_output_gate=True,
    scale_emb=12, scale_depth=1.4, dim_model_base=16,
    tie_word_embeddings=False,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"])


def _tiny_root(root):
    write_root(root)
    cfg = dict(
        TINY, source="https://example.org/tiny-sala", reduced=[],
        published={"num_hidden_layers": 4}, role="serve", reference="sala",
        assumed={"sparse": dict(kernel=4, stride=2, block=8, init_blocks=1,
                                local_blocks=3, topk=6)},
        frontend={"prefill_token_budget": 256},
        orion={"preset": "tiny-sala", "overrides": [],
               "widths": {"qk_norm": "qk_norm", "scale_emb": "embed_scale"},
               "unchecked": {k: "a flag or a list: tests/test_sala.py" for k in (
                   "lightning_nh", "lightning_nkv", "lightning_head_dim",
                   "lightning_use_rope", "attn_use_rope", "use_output_norm",
                   "use_output_gate", "attn_use_output_gate", "scale_depth",
                   "dim_model_base", "mixer_types")}},
        correct={"limits": {
            "logit_rel_err_worst_probe_median_clear": 2e-5,
            "selection_regret_max": 1e-6, "window_kv_rel_err_max": 1e-6,
            "window_token_gap_max": 1e-3}})
    name, cell = "tiny-sala-serve", "tiny-sala.longdoc"
    (root / "benchmarks" / "configs" / f"{name}.json").write_text(
        json.dumps(cfg))
    (root / PUBLISHED / f"{name}.json").write_text(json.dumps(TINY))
    (root / "benchmarks" / "traffic" / "tiny-longdoc.json").write_text(
        json.dumps(dict(
            kind="serve_chunks", block=4, pair_seed=3, clients=2,
            prompt={"median": 70, "sigma": 0.6, "min": 20, "max": 120},
            output={"median": 6, "sigma": 0.5, "min": 4, "max": 8},
            warm_requests=2, trace_seconds=0.1, probe_prompts=[70],
            probe_windows=1)))
    bm = json.loads((root / "BENCHMARK.json").read_text())
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": name, "source": cfg["source"],
                          "reduced": [], "why": "test",
                          "file": f"benchmarks/configs/{name}.json"})
    bm["workloads"].append({"name": cell, "config": name,
                            "traffic": "tiny-longdoc", "chips": 1,
                            "why": "test"})
    listed = {m["name"] for m in real["end_to_end"] + real["per_layer"]
              if CELL in m.get("workloads", ())}
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in listed:
            m["workloads"] = m.get("workloads", []) + [cell]
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return cell


def test_the_kind_runs_whole_on_the_cpu(tmp_path, capsys, monkeypatch):
    """``benchmarks/run.py`` on a tiny root: probes in chunks against the
    reference and the selection's regret, the four shapes warmed, a window
    that compiles nothing and fails no request, the counters' readers in the
    line of a traced run."""
    cell = _tiny_root(tmp_path)
    rc, lines = run_cell(tmp_path, cell, capsys, monkeypatch, trace=1,
                         seconds=0.3)
    assert rc == 0, lines[-5:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["checks"]) == {
        "logit_rel_err_worst_probe_median_clear", "selection_regret_max",
        "window_kv_rel_err_max", "window_token_gap_max"}
    assert line["checks"]["selection_regret_max"]["value"] == 0.0
    assert "compiles_in_window: 0" in lines
    assert any(l.startswith("warmed prefill shapes (rows, tokens): "
                            "[(1, 16), (1, 32)]") for l in lines)
    got = line["metrics"]
    assert 0 < got["sparse_visible_pct.longdoc"]["value"] <= 100
    assert got["sala_cache_bytes_per_token.longdoc"]["value"] > 0
    # a CPU run prints no device metric
    assert not any("roofline" in name for name in got)
