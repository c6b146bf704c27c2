"""The configuration whose window layers keep a ring a slot beside full layers
of another K/V shape in pages, under a sigmoid router of which the chip holds
16 of 256 experts (MiMo-V2.5): its cut table, pool and rings from its file's
own keys, its published keys against the catalog's row, its mix's table, the
byte and operation functions against hand counts, its readers on what a traced
segment hands them and on what a parent would, a tiny configuration of the
same kind through ``run.py`` on the CPU, and its programs compiled at their
real sizes for a v5e that is described and not attached."""

import json
import math
import pathlib

import pytest

from benchmarks.harness.cell import Cell
from benchmarks.metrics import mimo
from benchmarks.traffic import generator
from tests.benchmark.conftest import (MIXES, REPO, add_configuration,
                                      run_cell, write_root)

CELL = "mimo-v2.5.serve-mixed-16k"
TINY = "tiny.mimo"
# the cell's four entries: decode kernel, flash forward, held experts, cache
NEW = ("sink_paged_decode_roofline.mixed16k",
       "sink_flash_prefill_roofline.mixed16k",
       "held_expert_ffn_roofline.mixed16k",
       "mixed_cache_bytes_per_token.mixed16k")
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_bytes_against_the_cut_table():
    """ISSUE 50's arithmetic, in bf16, from the file's own keys, against the
    reference's tree and the program's: 5.42 B parameters, 10.84 GB; the full
    layers' pages 2.01 GB, the window layers' rings 0.58 GB; 13.43 GB held."""
    cell = Cell.find(CELL)
    hf = cell.config
    n = sum(math.prod(shape)
            for shape, _ in cell.reference().param_spec(hf).values())
    D, N, H, Hv = 4096, 64, 192, 128
    full = D * N * H + D * 4 * H + D * 4 * Hv + N * Hv * D
    window = D * N * H + D * 8 * H + D * 8 * Hv + N * Hv * D + N   # + sinks
    assert (full, window) == (89_128_960, 94_371_904)
    dense, expert = 3 * D * 16384, 3 * D * 2048
    ffn = D * 256 + 256 + 16 * expert
    assert (dense, expert) == (201_326_592, 25_165_824)
    assert (full + dense + 2 * D, window + ffn + 2 * D, full + ffn + 2 * D
            ) == (290_463_744, 498_082_112, 492_839_168)
    assert n == (290_463_744 + 9 * 498_082_112 + 492_839_168
                 + 2 * 19072 * D + D) == 5_422_283_840
    assert 10.84e9 < 2 * n < 10.85e9
    cfg = cell.program_config()
    m, icfg = cfg.model, cfg.inference
    assert (m.n_layers, m.n_experts, m.resolved_router_width, m.expert_offset,
            m.vocab_size) == (11, 16, 256, 0, 19072)
    assert [k.window for k in m.layer_kinds] == (
        [None] + [128] * 4 + [None] + [128] * 5)
    assert [m.kv_heads_of(k) for k in m.layer_kinds] == (
        [4] + [8] * 4 + [4] + [8] * 5)
    assert [k.moe for k in m.layer_kinds] == [False] + [True] * 10
    assert (icfg.max_batch_size, icfg.page_size, icfg.decode_window,
            icfg.prefill_chunk) == (64, 64, 8, 2048)
    assert m.capacity_factor == 256 / 8                     # dropless
    assert icfg.max_seq_len == 16384 + 2048 == 288 * 64
    # the program's own tree and cache are the table's
    import jax

    from orion_tpu.infer.kv_cache import init_cache
    from orion_tpu.models.transformer import init_params

    shapes = jax.eval_shape(lambda: init_params(m, jax.random.key(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == n
    cache = jax.eval_shape(lambda: init_cache(m, icfg))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2 * 6144, 6, 64, 128), "v": (2 * 6144, 4, 64, 128),
        "ring_k": (9, 65, 3, 12, 64, 128), "ring_v": (9, 65, 3, 8, 64, 128)}
    size = {k: v.size * v.dtype.itemsize for k, v in cache.items()}
    # a position: 2 x 2560 B in the two full layers, with no padding of the
    # 192-wide key; 56 KB if all eleven layers kept it
    assert mimo.position_bytes(hf, "full") == 2560
    assert mimo.position_bytes(hf, "window") == 5120
    assert size["k"] + size["v"] == 6144 * 64 * 2 * 2560 == 2_013_265_920
    assert 2 * 2560 + 9 * 5120 == 51_200 and 11 * 5120 == 56_320
    assert size["ring_k"] + size["ring_v"] == (
        9 * 65 * 3 * 64 * 5120) == 575_078_400
    assert 9 * 3 * 64 * 5120 == 8_847_360                   # a slot's rings
    held = 2 * n + sum(size.values())
    assert 13.43e9 < held < 13.44e9 and 12.5 < held / 2 ** 30 < 12.52
    # the pool against the mix: 1.25 x the peak of live positions fits (the
    # mix's own order, one token a slot and step, prefill taken as instant)
    peak, mean = _replay(cell.mix, steps=40_000)
    assert (peak, round(mean)) == (288_579, 231_902)
    assert icfg.num_pages * 64 == 393_216 >= 1.25 * peak
    assert 64 * icfg.max_seq_len * 2 * 2560 > 6.0e9     # every slot's longest
    dep = hf["deployment"]
    assert dep["chips_sharing_a_layer"] == 16 and dep["experts_held"] == [0, 16]
    assert 16 * 16 == hf["published"]["n_routed_experts"] == 256
    assert dep["vocabulary_rows_held"] == [0, 19072]
    assert 8 * 19072 == hf["published"]["vocab_size"] == 152_576
    table = " ".join(dep["cut_table_bf16"].values())
    for said in ("5.422 B, 10.84 GB", "2.013 GB", "0.575 GB",
                 "13.43 GB = 12.51 GiB"):
        assert said in table, said


def _replay(mix: dict, steps: int) -> tuple[int, float]:
    """(peak, mean past the first 2000 steps) of the live positions of a
    closed loop over the mix's own order."""
    import random

    table = generator.length_table(mix)

    def stream():
        b = 0
        while True:
            order = list(range(len(table)))
            random.Random(mix["pair_seed"] * 1_000_003 + b).shuffle(order)
            yield from (table[k] for k in order)
            b += 1

    s = stream()
    slots = [[*next(s), 0] for _ in range(mix["clients"])]
    seen = []
    for _ in range(steps):
        seen.append(sum(p + d for p, _, d in slots))
        for slot in slots:
            slot[2] += 1
            if slot[2] >= slot[1]:
                slot[:] = [*next(s), 0]
    seen = seen[2000:]
    return max(seen), sum(seen) / len(seen)


def test_the_mix_and_its_probes_lie_inside_the_warmed_shapes():
    """The traffic file: 64 clients = 64 slots, a block of 64 with 8 prompts
    of 512 tokens or fewer and 8 of 8192 or more; every prompt, every probe
    and the longest request inside the engine's limits; 15 prefill shapes."""
    from benchmarks.kinds import serve, shapes

    cell = Cell.find(CELL)
    mix, icfg = cell.mix, cell.program_config().inference
    assert (mix["kind"], mix["clients"], mix["block"], mix["pair_seed"]) == (
        "serve_rows", 64, 64, 5011)
    table = generator.length_table(mix)
    prompts = [p for p, _ in table]
    assert sum(p <= 512 for p in prompts) == 8
    assert sum(p >= 8192 for p in prompts) == 8
    assert (min(prompts), max(prompts)) == (128, 16384)
    assert 3600 < sum(prompts) / 64 < 3680
    assert (min(o for _, o in table), max(o for _, o in table)) == (94, 2048)
    assert max(p + o for p, o in table) <= icfg.max_seq_len
    assert mix["probe_prompts"] == [100, 1000, 6000, 16384]
    new = mix["probe_windows"] * icfg.decode_window
    assert max(mix["probe_prompts"]) + new + 1 <= icfg.max_seq_len
    got = serve.cell_prefill_shapes(cell, icfg)
    assert len(got) == 15 and (1, 16384) in got and (8, 2048) in got
    budget = cell.config["frontend"]["prefill_token_budget"]
    assert all(nb * s <= budget for nb, s in got)
    for n in mix["probe_prompts"]:
        assert (1, shapes.bucket_len(n, icfg.prefill_chunk,
                                     icfg.max_seq_len)) in got


def test_every_published_key_is_stated_and_three_meanings_are_reduced():
    """The configuration file against the source's own keys, and those
    against the catalog's row where the catalog is on this machine."""
    cell = Cell.find(CELL)
    hf, pub = cell.config, cell.published
    assert hf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                             "vocab_size"]
    for key, value in pub.items():
        want = hf["published"][key] if key in hf["reduced"] else hf[key]
        assert want == value, key
    assert set(hf["published"]) == set(hf["reduced"])
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "v_head_dim", "swa_head_dim", "swa_v_head_dim",
              "num_experts_per_tok", "sliding_window", "num_attention_heads",
              "num_key_value_heads", "swa_num_key_value_heads")
    assert not set(widths) & set(hf["reduced"])
    for key in ("towers", "multi_token_prediction"):
        assert key in hf["left_out"]
    assert {"qk_norm", "sink", "attention_chunk_size",
            "attention_projection_layout"} <= set(hf["assumed"])
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bm["configs"] if c["name"] == cell.config_name)
    assert entry["source"] == hf["source"] and entry["reduced"] == hf["reduced"]
    if not CATALOG.exists():
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "MiMo-V2.5")
    assert row["config"] == pub and row["source_url"] == hf["source"]


def test_byte_and_operation_functions_against_hand_counts():
    hf = Cell.find(CELL).config
    assert mimo.sparse_layers(hf) == 10
    assert mimo.held_expert_bytes(hf) == 10 * 16 * 25_165_824 * 2
    assert 8.05e9 < mimo.held_expert_bytes(hf) < 8.06e9
    # 64 slots of 3640 positions: two full layers read them all, nine window
    # layers 128 of them
    full, ring = 2 * 64 * 3640, 9 * 64 * 128
    assert mimo.decode_kv_bytes(hf, full, ring) == (
        full * 4 * 320 * 2 + ring * 8 * 320 * 2)
    # a 16384-token prompt: the pairs its masks keep, 64 heads, 192 + 128
    n, w = 16384, 128
    pairs = 2 * n * (n + 1) // 2 + 9 * (w * (w + 1) // 2 + (n - w) * w)
    assert mimo.prefill_attn_flops(hf, pairs) == pairs * 64 * 320 * 2
    assert 11.7e12 < mimo.prefill_attn_flops(hf, pairs) < 11.9e12


def test_the_readers_arithmetic():
    """By hand on what a traced segment hands them: 30 windows of 8 steps
    over 64 slots of 3640 positions on average."""
    cell = Cell.find(CELL)
    hf = cell.config
    steps = 30 * 8
    t = {"decode_kv_token_layers_full": steps * 2 * 64 * 3640,
         "decode_kv_token_layers_ring": steps * 9 * 64 * 128,
         "prefill_attn_pairs": 3 * 10 ** 9,
         "kv_full_positions_live": 30 * 64 * 3640,
         "kv_full_bytes_live": 30 * 64 * 3640 * 5120,
         "kv_full_page_bytes_held": 30 * 64 * 68 * 327_680,
         "kv_window_bytes_held": 30 * 64 * 8_847_360,
         "kv_window_positions_held": 30 * 64 * 192}
    obs = {"timing": t, "config": hf, "slots": 64, "decode_window": 8,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "trace": {"timing": t, "op_s": {
               "paged_decode.3": 0.4, "paged_decode.7": 0.6,
               "attention.1_custom-call_bf16_1_64_16384_128_": 2.0,
               "attention.2_custom-call_bf16_8_64_2048_128_": 3.0,
               "rope.1_custom-call_bf16_1_16384_64_192_": 9.0},
                     "module_s": {}, "module_n": {}}}
    got = cell.reader(NEW[0]).read(obs)
    assert got == pytest.approx(100 * steps * 64 * (
        2 * 3640 * 2560 + 9 * 128 * 5120) / 819e9 / 1.0)
    assert 0 < got < 100
    got = cell.reader(NEW[1]).read(obs)
    assert got == pytest.approx(
        100 * 3e9 * 64 * 320 * 2 / 197e12 / 5.0)
    assert 0 < got < 100
    got = cell.reader(NEW[3]).read(obs)
    assert got == pytest.approx((3640 * 5120 + 8_847_360) / 3640)
    assert 7500 < got < 7600
    # every layer keeping every position would read 56 KB
    assert 11 * 5120 / got > 7


def test_the_parent_of_this_configuration_reads_nothing():
    """The benchmark as this PR leaves it is laid over the parent too: where
    the program has no such counter, operation or scope, the new readers
    return None and do not raise; nor do they on another configuration's
    keys. The four are the last entries of ``per_layer``, in this order, and
    the cell is listed under what every layer-plan serving cell is."""
    cell = Cell.find(CELL)
    empty = {"timing": {}, "config": cell.config, "slots": 64,
             "decode_window": 8, "peaks": {"hbm_bytes_per_s": 819e9,
                                           "bf16_flops": 197e12},
             "trace": {"timing": {}, "op_s": {"fusion.1": 1.0},
                       "module_s": {"jit__unknown(1)": 1.0},
                       "module_n": {"jit__unknown(1)": 2}}}
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bm["per_layer"]]
    at = names.index("hybrid_cache_bytes_per_token.reason128")
    assert tuple(names[at + 1:at + 5]) == NEW
    for m in bm["per_layer"][at + 1:at + 5]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the lists every serving cell is under (``compile_s``, the ``.batch``
    # metrics of cells with a layer plan) end with this cell; a reader of
    # another cell's mechanism (``latent_decode_roofline.longctx`` lists Ling)
    # finds nothing here, and a traced line that lacks a listed metric is
    # refused, so the cell is under none of those
    ling = "ling-3.0-flash.serve-reason-128"
    for m in bm["end_to_end"] + bm["per_layer"]:
        shared = m["name"].endswith(".batch") or m["name"] in (
            "compile_s", "serve_tokens_per_s")
        if ling in m.get("workloads", ()) and shared:
            assert m["workloads"][-1] == CELL, m["name"]
        if CELL in m.get("workloads", ()):
            assert shared or m["name"] in NEW, m["name"]
    mine = [m["name"] for m in cell.per_layer]
    assert [n for n in mine if n in NEW] == list(NEW) and len(mine) == 23 + 4
    assert {"compile_s", "decode_ffn_ms_per_step.batch",
            "decode_attn_kernel_ms_per_step.batch",
            "prefill_attn_ms_per_ktoken.batch"} <= set(mine)
    assert bm["workloads"][-1]["name"] == CELL
    assert bm["workloads"][-1]["chips"] == 1
    other = Cell.find("laguna-s-2.1.serve-batch-4k").config
    for name in NEW:
        assert cell.reader(name).read(empty) is None
        assert cell.reader(name).read(dict(empty, trace=None)) is None
        assert cell.reader(name).read(dict(empty, config=other)) is None
    # a traced segment in which no prompt was admitted: the counter is there
    # and reads 0, and a share of a roofline is left out, never reported as 0
    quiet = dict(empty, trace=dict(empty["trace"], timing={
        "prefill_attn_pairs": 0}, op_s={
            "attention.1_custom-call_bf16_1_64_2048_128_": 0.1}))
    assert cell.reader(NEW[1]).read(quiet) is None


def _tiny_configuration():
    published = {
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 7,
        "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 24,
        "v_head_dim": 16, "swa_num_key_value_heads": 4,
        "attention_value_scale": 0.707, "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "vocab_size": 256,
        "layernorm_epsilon": 1e-5, "rope_theta": 10000000,
        "swa_rope_theta": 10000, "partial_rotary_factor": 0.334,
        "tie_word_embeddings": False, "sliding_window": 8,
        "n_routed_experts": 16, "moe_intermediate_size": 32,
        "num_experts_per_tok": 4, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "routed_scaling_factor": None,
        "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1, 0],
        "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "model_type": "mimo_v2",
    }
    real = Cell.find(CELL).config["orion"]
    cfg = dict(
        published, n_routed_experts=8, source="test",
        reduced=["n_routed_experts"], published={"n_routed_experts": 16},
        assumed={}, role="serve", reference="mimo",
        deployment={"chips_sharing_a_layer": 2, "experts_held": [0, 8]},
        frontend={"prefill_token_budget": 128},
        orion={"preset": "tiny-mimo",
               "overrides": ["model.n_experts=8", "model.expert_offset=0",
                             "inference.prefill_chunk=32"],
               "widths": real["widths"],
               "unchecked": {k: real["unchecked"][k] for k in published
                             if k in real["unchecked"]}},
        correct={"router_margin_min": 0.0, "limits": {
            "logit_rel_err_worst_probe_median_clear": 1e-3,
            "window_kv_rel_err_max": 1e-4, "window_token_gap_max": 1e-3}})
    return cfg, published


@pytest.fixture(scope="module")
def mimo_root(tmp_path_factory):
    """The tests' tiny benchmark root with one more configuration, a mix of
    the kind this cell's traffic names, and a cell listed under every metric
    the real cell is listed under."""
    root = write_root(tmp_path_factory.mktemp("tiny_mimo"))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append(add_configuration(
        root, "tiny-mimo-serve", *_tiny_configuration()))
    (root / "benchmarks" / "traffic" / "tiny-ring.json").write_text(
        json.dumps(dict(MIXES["tiny-batch"], kind="serve_rows")))
    bm["workloads"].append({"name": TINY, "config": "tiny-mimo-serve",
                            "traffic": "tiny-ring", "chips": 1,
                            "why": "test"})
    mine = {m["name"] for m in real["end_to_end"] + real["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) <= mine and len(mine) == 1 + 23 + 4
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in mine:
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def test_a_tiny_configuration_of_this_kind_runs_end_to_end(
        mimo_root, capsys, monkeypatch):
    """``run.py`` itself, traced, on the CPU (counts only), with no edit to
    a file the harness had: the traffic names the kind whose tap holds the
    window link on the pages AND on a slot's rings; the chip's share (8 of
    16 experts under a 16-wide router) is the reference's; the probes (5 and
    40 tokens under a window of 8) are correct; of the four new metrics the
    one that is engine counters alone is reported."""
    rc, lines = run_cell(mimo_root, TINY, capsys, monkeypatch, trace=1)
    assert rc == 0
    out = json.loads(lines[-1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compiles_in_window.batch"] == 0
    assert m["slot_occupancy_pct.batch"] > 0
    assert [n for n in m if n.endswith(".mixed16k")] == [NEW[3]]
    # full layers 3 x (3 + 2) rows x 16 x 4 B = 960 B a position; the rings
    # 4 layers x (6 + 4) x 16 x 4 B = 2,560 B a held position, 16 at most
    assert 960 < m[NEW[3]] <= 960 + 2_560
    assert "paged_decode_page_rounding.batch" not in m       # Mixtral's alone
    assert "hybrid_cache_bytes_per_token.reason128" not in m
    checks = dict(line.split(" = ")[0].split("check: ")[1:] + [line]
                  for line in lines if line.startswith("check: "))
    assert set(checks) == {"logit_rel_err_worst_probe_median_clear",
                           "window_kv_rel_err_max", "window_token_gap_max"}
    assert "window_kv_rel_err_max = 0.0 " in checks["window_kv_rel_err_max"]


def test_the_parent_fails_at_the_preset_lookup_before_any_device_work():
    """With this PR's benchmark files laid over a program that lacks the
    preset (as the driver may run the parent), the cell stops in
    ``harness/cell.program_config`` with a message, before any weights are
    drawn or any program is built."""
    import orion_tpu.config as config

    cell = Cell.find(CELL)
    kept = config._PRESETS.pop("mimo-v2.5")
    try:
        with pytest.raises((KeyError, ValueError, SystemExit),
                           match="mimo-v2.5"):
            cell.program_config()
    finally:
        config._PRESETS["mimo-v2.5"] = kept


def test_decode_and_the_widest_bursts_fit_the_chip():
    """The cell's decode window and its widest bursts of prompts compiled
    for a described v5e (``test_aot_v5e.py`` finds cells of kind ``serve``
    alone): the paged kernel over packed keys with the sink's term is in the
    decode program (Mosaic takes it), the flash forward with values narrower
    than keys in prefill, and both fit beside the weights, the pool and the
    rings."""
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
        assert "paged_decode" in compiled.as_text()
        assert total(compiled) < 15.0 * 2 ** 30
        prefill = jax.jit(partial(
            runner.prefill_step, cfg=mcfg, mesh=None,
            paged_prefill=icfg.paged_prefill), donate_argnums=(1,))
        for nb, s in ((1, 16384), (8, 2048)):
            # (compiling at all is the check: the compiler refuses a program
            # that does not fit the chip, and fills what is free)
            compiled = prefill.lower(
                params, cache, i32(nb, s), i32(nb), i32(nb, s // 64), i32(nb),
                i32(nb, 0), i32(nb)).compile()
            assert "flash_fwd" in compiled.as_text()
    finally:
        for mod, fn in patched:
            mod.resolve_interpret = fn


def test_the_planted_faults_run_through_the_harness(
        mimo_root, capsys, monkeypatch):
    """``tools/mimo_fault_probe.py`` on the tiny cell (CPU): the benchmark's
    own ``probe_numbers`` and ``decide`` under the cell's tap, on an engine
    whose window layers have no sink, then whose values are not scaled,
    then whose rings are a page short (one page of 8 under a window of 8:
    decode reads what is left), then whose window is 9 positions. Unplanted
    the check passes; float32 on the CPU under a limit of 1e-3 sees all
    four (on the chip, in bfloat16 under the cell's limits: PERF.md section
    6, PR 50)."""
    import runpy

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(mimo_root / ".c"))
    monkeypatch.setattr("sys.argv", [
        "mimo_fault_probe.py", "--workload", TINY, "--seed", "77",
        "--root", str(mimo_root), "--allow-cpu"])
    with pytest.raises(SystemExit) as done:
        runpy.run_path(str(REPO / "tools/mimo_fault_probe.py"),
                       run_name="__main__")
    assert done.value.code == 0
    lines = capsys.readouterr().out.splitlines()
    assert [l for l in lines if l.startswith("-- fault planted")] == [
        f"-- fault planted: {f}"
        for f in ("none", "sink", "scale", "ring", "window")]
    assert [l for l in lines if l.startswith("correct: ")] == [
        "correct: True"] + ["correct: False"] * 4
    assert lines[-1].endswith(
        "the check sees ['sink', 'scale', 'ring', 'window']")
