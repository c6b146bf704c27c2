"""A model whose layers differ in shape (Laguna-S-2.1: a dense lead layer,
window and full layers of unlike head counts and rotary tables, a per-head
gate, a wide router over a held share of small experts and a shared one) at
a tiny size on the CPU: the training forward, the engine's prefill and decode
through the cache and the plain reference agree on logits; the shares of an
expert layer add up to the whole layer; models of one kind are, to the bit,
what they were."""

import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import RopeConfig, get_config
from orion_tpu.models import moe as moe_lib
from orion_tpu.models import transformer as T

REPO = pathlib.Path(__file__).resolve().parents[1]
PUBLISHED = json.loads((REPO / "tests/benchmark/data/published/"
                        "laguna-s-2.1-serve-1chip.json").read_text())


def _reference():
    from benchmarks.harness import cell

    return cell._load(REPO / "benchmarks" / "reference" / "laguna.py")


def tiny_hf(m, held=None) -> dict:
    """The tiny preset under the published key names, as the reference reads
    a configuration file; ``held`` = [first, end) of the experts held."""
    def rope(r: RopeConfig) -> dict:
        if r.yarn_factor is None:
            return {"rope_type": "default", "rope_theta": r.theta,
                    "partial_rotary_factor": r.rotary_fraction}
        return {"rope_type": "yarn", "rope_theta": r.theta,
                "factor": r.yarn_factor, "beta_fast": r.yarn_beta_fast,
                "beta_slow": r.yarn_beta_slow,
                "original_max_position_embeddings": r.yarn_original_max_pos,
                "attention_factor": r.attention_factor,
                "partial_rotary_factor": r.rotary_fraction}

    held = held or [0, m.resolved_router_width]
    return {
        "hidden_size": m.d_model, "head_dim": m.resolved_head_dim,
        "num_key_value_heads": m.n_kv_heads, "vocab_size": m.vocab_size,
        "num_hidden_layers": m.n_layers, "rms_norm_eps": m.norm_eps,
        "intermediate_size": m.d_ff, "moe_intermediate_size": m.moe_d_ff,
        "shared_expert_intermediate_size": m.shared_expert_d_ff,
        "num_experts": held[1] - held[0],
        "published": {"num_experts": m.resolved_router_width},
        "deployment": {"experts_held": held},
        "num_experts_per_tok": m.n_experts_per_token,
        "moe_routed_scaling_factor": m.router_scale,
        "sliding_window": m.sliding_window,
        "layer_types": list(m.layer_types),
        "num_attention_heads_per_layer": list(m.n_heads_per_layer),
        "mlp_layer_types": ["dense"] * m.n_dense_layers
        + ["sparse"] * (len(m.layer_types) - m.n_dense_layers),
        "rope_parameters": {"full_attention": rope(m.rope_full),
                            "sliding_attention": rope(m.rope_sliding)},
    }


def _share(params, first: int, end: int):
    """The tree of a chip that holds experts [first, end)."""
    def cut(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        if ("moe" in names and "shared" not in names
                and names[-1] in ("w_in", "w_gate", "w_out")):
            return leaf[..., first:end, :, :]
        return leaf

    return jax.tree_util.tree_map_with_path(cut, params)


def _rel(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tiny-laguna")
    return cfg, T.init_params(cfg.model, jax.random.key(5))


def test_the_layer_plan_crosses_the_period_and_the_tail(tiny):
    m = tiny[0].model
    plan = m.layer_plan
    assert (plan.lead, plan.period, plan.repeats, plan.tail) == (1, 4, 1, 1)
    assert plan.counts == (2, 1, 1, 1)
    assert [k.n_heads for k in m.layer_kinds] == [4, 6, 6, 6, 4, 6]
    assert [k.window for k in m.layer_kinds] == [None, 8, 8, 8, None, 8]
    assert [k.moe for k in m.layer_kinds] == [False] + [True] * 5
    assert m.page_window is None            # a full layer keeps every page
    full = get_config("laguna-s-2.1").model
    assert full.layer_plan == (1, 4, 11, 3, ())     # no runs: a layer an element
    # models of one kind have no plan: their tree and scans are untouched
    for preset in ("tiny-llama", "tiny-mixtral", "tiny-gemma2"):
        assert get_config(preset).model.layer_plan is None
    assert get_config("mistral-7b-fsdp").model.page_window == 4096
    assert get_config("tiny-gemma2").model.page_window is None
    # sliding_window_pattern is a way of writing the same list
    g = get_config("tiny-gemma2").model
    assert [k.window for k in g.layer_kinds] == [16, None, 16, None]
    with pytest.raises(ValueError, match="both write the per-layer list"):
        get_config("tiny-laguna",
                   ["model.sliding_window_pattern=2"]).model.layer_kinds


@pytest.mark.parametrize("held", [None, [0, 8], [8, 16]])
def test_forward_engine_and_reference_agree_on_logits(tiny, held):
    """Three ways, float32 on the CPU, the window (8) shorter than the
    prompt (30). Tolerance 2e-4 relative L2: the reference multiplies at
    ``highest`` precision and sums heads, experts and the softmax in another
    order; anything structural (a wrong table, gate, window, share) reads
    1e-2 or more."""
    from benchmarks.kinds import serve
    from orion_tpu.infer import InferenceEngine

    cfg, params = tiny
    if held is not None:
        cfg = get_config("tiny-laguna", [
            f"model.n_experts={held[1] - held[0]}",
            f"model.expert_offset={held[0]}"])
        params = _share(params, *held)
    m, ref, hf = cfg.model, _reference(), tiny_hf(cfg.model, held)
    toks = jax.random.randint(jax.random.key(1), (1, 40), 1, m.vocab_size)
    got, _ = jax.jit(lambda p, t: T.forward(p, t, m))(params, toks)
    want, margin = ref.logits_at(params, toks[0], jnp.arange(40), hf)
    assert margin.shape == (40,) and bool(jnp.all(margin >= 0))
    assert _rel(got[0], want) < 2e-4
    engine = InferenceEngine(cfg, params, seed=0)
    mix = {"probe_prompts": [30], "probe_windows": 2}
    n = serve.probe_numbers(engine, ref, hf, mix, seed=3, control="int8")
    assert len(n["err"]) == 1 + 2 * engine.decode_window
    assert max(n["err"]) < 2e-4
    assert max(n["window_kv_rel_err"]) < 1e-5
    assert min(n["control_err"]) > 50 * max(n["err"])     # the control fails
    t = engine.reset_timing()
    assert t["prefill_held_expert_rows"] == (0 if held is None else
                                             pytest.approx(31 * 4 * 5 / 2,
                                                           rel=0.25))
    engine.close()


def test_the_compiled_kernels_compute_it_too(tiny):
    """The same engine path with every Pallas kernel interpreted: the rope
    table kernel, flash attention and the paged decode kernel at GQA groups
    of 3 and 2."""
    from benchmarks.kinds import serve
    from orion_tpu.infer import InferenceEngine

    cfg = get_config("tiny-laguna", ["model.kernels=pallas_interpret",
                                     "inference.decode_window=2"])
    engine = InferenceEngine(cfg, tiny[1], seed=0)
    n = serve.probe_numbers(engine, _reference(), tiny_hf(cfg.model),
                            {"probe_prompts": [20], "probe_windows": 1}, 3)
    assert max(n["err"]) < 2e-4 and max(n["window_kv_rel_err"]) < 1e-5
    engine.close()


@pytest.mark.parametrize("grouped", [False, True])
def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer(
        tiny, grouped, monkeypatch):
    """One sparse layer: what shares [0, 8) and [8, 16) give, the shared
    expert counted once, is what the uncut reference gives for the whole
    layer; each share equals the reference's own cut (gates renormalised
    over ALL chosen experts, held or not), which differs from renormalising
    over the held ones. Both dispatches: capacity buckets (decode-sized
    blocks) and the grouped matmul (prefill)."""
    if grouped:      # the rule's tile term keeps tiny blocks off this path
        monkeypatch.setattr("orion_tpu.ops.grouped_matmul.ROW_TILE", 1)
    cfg, params = tiny
    m, ref = cfg.model, _reference()
    bp = jax.tree.map(lambda a: a[0], params["blocks"]["period"]["1"])
    h = jax.random.normal(jax.random.key(2), (2, 12, m.d_model), jnp.float32)
    valid = jnp.arange(12)[None, :] < jnp.asarray([12, 7])[:, None]
    flat = lambda y: jnp.where(valid[..., None], y, 0).reshape(24, -1)

    def program(first, end):
        c = get_config("tiny-laguna", [
            f"model.n_experts={end - first}",
            f"model.expert_offset={first}"]).model
        assert moe_lib.takes_grouped_path(c, 2, 12) == grouped
        y, _ = T.mlp_or_moe(h, _share(bp, first, end), c, valid=valid)
        return flat(y)

    def reference(first, end):
        hf = tiny_hf(m, [first, end])
        with jax.default_matmul_precision("highest"):
            return flat(ref._moe(h.reshape(24, -1),
                                 _share(bp, first, end)["moe"], hf,
                                 None)[0].reshape(2, 12, -1))

    with jax.default_matmul_precision("highest"):
        shared = flat(moe_lib._shared_expert(h, bp["moe"]["shared"], m))
    whole = reference(0, 16)
    parts = [program(0, 8), program(8, 16)]
    assert _rel(parts[0] + parts[1] - shared, whole) < 1e-5
    assert _rel(program(0, 16), whole) < 1e-5
    for part, (first, end) in zip(parts, ([0, 8], [8, 16])):
        assert _rel(part, reference(first, end)) < 1e-5
    # renormalised over the held experts only, a share would read otherwise
    alone = get_config("tiny-laguna", ["model.n_experts=8",
                                       "model.router_width=8"]).model
    bp8 = _share(bp, 0, 8)
    bp8["moe"]["router"] = bp8["moe"]["router"][:, :8]
    y, _ = T.mlp_or_moe(h, bp8, alone, valid=valid)
    assert _rel(flat(y), parts[0]) > 0.05


def test_rope_tables():
    """The program's table against the reference's own computation of it, at
    the published parameters; partial rotation leaves the other dims; the
    Pallas kernel (interpreted) equals the jnp path and its gradient."""
    from orion_tpu.ops.rope import KERNEL_MIN_SEQ, apply_rope, rope_table

    m, ref = get_config("laguna-s-2.1").model, _reference()
    for kind, rope in (("full_attention", m.rope_full),
                       ("sliding_attention", m.rope_sliding)):
        inv, scale, rot = rope_table(128, rope)
        want = ref._inv_freq(PUBLISHED["rope_parameters"][kind], 128)
        np.testing.assert_allclose(inv, np.asarray(want[0]), rtol=2e-6)
        assert (scale, rot) == (want[1], want[2])
    assert rope_table(128, m.rope_full)[2] == 64
    inv = rope_table(128, m.rope_full)[0]
    f = 500000.0 ** (np.arange(32) / 32)
    # fast dims keep 1/f, slow dims read 1/(128 f), a ramp between
    np.testing.assert_allclose(inv[:4], 1 / f[:4], rtol=1e-6)
    np.testing.assert_allclose(inv[-4:], 1 / (128 * f[-4:]), rtol=1e-6)
    S = KERNEL_MIN_SEQ + 8      # a length the kernel takes
    x = jax.random.normal(jax.random.key(0), (2, S, 6, 128), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None] * 100, (2, S))
    a = apply_rope(x, pos, rope=m.rope_full, impl="xla")
    np.testing.assert_array_equal(a[..., 64:], x[..., 64:])
    b = apply_rope(x, pos, rope=m.rope_full, impl="pallas_interpret")
    np.testing.assert_allclose(a, b, atol=2e-6)
    ga, gb = (jax.grad(lambda x: (apply_rope(
        x, pos, rope=m.rope_full, impl=impl) ** 2).sum())(x)
        for impl in ("xla", "pallas_interpret"))
    np.testing.assert_allclose(ga, gb, atol=1e-5)


def test_the_preset_is_the_published_configuration():
    """The lists and the nested group that the benchmark's harness cannot
    compare (a tuple is not a JSON list) against the source's file."""
    m, pub = get_config("laguna-s-2.1").model, PUBLISHED
    assert list(m.layer_types) == pub["layer_types"]
    assert list(m.n_heads_per_layer) == pub["num_attention_heads_per_layer"]
    assert list(range(m.n_dense_layers)) == pub["mlp_only_layers"]
    assert [("sparse" if k.moe else "dense") for k in m.layer_kinds] \
        == pub["mlp_layer_types"]
    assert set(pub["gating_types"]) == {"per_head"}
    assert m.attn_gate == pub["gating"] == "per-head"
    full = pub["rope_parameters"]["full_attention"]
    assert m.rope_full == RopeConfig(
        theta=full["rope_theta"], rotary_fraction=full["partial_rotary_factor"],
        yarn_factor=full["factor"],
        yarn_original_max_pos=full["original_max_position_embeddings"],
        yarn_beta_fast=full["beta_fast"], yarn_beta_slow=full["beta_slow"],
        attention_factor=full["attention_factor"])
    sliding = pub["rope_parameters"]["sliding_attention"]
    assert sliding["rope_type"] == "default"
    assert m.rope_sliding == RopeConfig(theta=sliding["rope_theta"])
    assert (m.resolved_router_width, m.n_experts_per_token, m.router_scale) \
        == (pub["num_experts"], pub["num_experts_per_tok"],
            pub["moe_routed_scaling_factor"])
    assert pub["norm_topk_prob"] and not pub["moe_apply_router_weight_on_input"]
    assert pub["moe_router_logit_softcapping"] == 0


@pytest.mark.parametrize("override, named", [
    ("inference.speculative=true", "inference.speculative"),
    ("inference.chunked_prefill=true", "inference.chunked_prefill"),
    ("model.weight_quant=int8", "model.weight_quant"),
])
def test_paths_that_nothing_compares_on_such_a_model_are_refused(
        tiny, override, named):
    from orion_tpu.infer import InferenceEngine

    with pytest.raises(ValueError, match=named):
        InferenceEngine(get_config("tiny-laguna", [override]), tiny[1])


def test_verify_accepts_the_chain_the_decode_window_wrote(tiny):
    """The comparison the refusal above says is missing, at the runner: from
    one cache (a 20-token prompt, the window 8), ``verify_step`` at W = 4 fed
    the chain the decode window itself produced accepts every draft and its
    bonus token is the window's next. It holds only because the layer kind,
    the head gate and the per-kind rotary table reach the paged backend
    through the one layer body. The engine still refuses speculation on such
    a model, for want of a cell (ROADMAP R6)."""
    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache

    params = tiny[1]
    cfg = get_config("tiny-laguna", [
        "inference.max_seq_len=64", "inference.page_size=8",
        "inference.num_pages=24", "inference.max_batch_size=2"])
    m, icfg = cfg.model, cfg.inference
    B, S, W = 2, 24, 4
    prompts = jax.random.randint(jax.random.key(7), (B, S), 1, m.vocab_size)
    lengths = jnp.asarray([20, 13], jnp.int32)
    pt = jnp.arange(1, 1 + B * 8, dtype=jnp.int32).reshape(B, 8)
    logits, cache = runner.prefill_step(
        params, init_cache(m, icfg), prompts, lengths, pt[:, :S // 8], cfg=m)
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    live = jnp.ones((B,), bool)
    toks, _ = runner.decode_window(
        params, dict(cache), first, lengths, pt, live,
        jax.random.split(jax.random.key(0), W), 0.0, 0, 1.0, m,
        icfg.max_seq_len)                                    # [W, B]
    chain = jnp.concatenate([first[:, None], toks[:W - 1].T], axis=1)
    accept, alt, _ = runner.verify_step(
        params, dict(cache), chain, lengths, jnp.full((B,), W, jnp.int32),
        pt, live, jax.random.key(0), 0.0, 0, 1.0, cfg=m,
        max_seq_len=icfg.max_seq_len)
    assert bool(accept[:, :W - 1].all()), accept
    assert (alt[:, W - 1] == toks[W - 1]).all(), (alt, toks)
    assert len(set(map(int, toks[:, 0]))) > 1       # not one token repeated


def test_kv_counters_know_window_layers_from_full_ones(tiny):
    """Host arithmetic of one decode window: 2 full layers read a slot's
    whole context, 4 window layers 8 positions at most; pages of 8 lying
    wholly under (length - 8 + 1) are dead for the window layers."""
    from orion_tpu.infer import InferenceEngine

    engine = InferenceEngine(tiny[0], tiny[1], seed=0)
    engine.reset_timing()
    engine._count_kv_by_layer_kind(np.asarray([30, 5], np.int64), 2)
    t = engine.reset_timing()
    assert t["decode_kv_token_layers"] == (
        2 * (30 + 31 + 5 + 6) + 4 * (8 + 8 + 5 + 6))
    assert t["kv_live_page_layers"] == 6 * (4 + 1)
    assert t["kv_dead_window_page_layers"] == 4 * ((30 - 8 + 1) // 8)
    engine.close()


def test_kv_pages_read_counts_whole_pages_by_layer_kind(tiny):
    """decode_kv_pages_read of one decode window: a full layer copies the
    pages up to the one that takes the new token (position 30: 4 pages of
    8), a window layer from the page of its window's first position
    (positions 23..30: 2 pages; 24..31: 1)."""
    from orion_tpu.infer import InferenceEngine

    engine = InferenceEngine(tiny[0], tiny[1], seed=0)
    engine.reset_timing()
    engine._count_kv_by_layer_kind(np.asarray([30, 5], np.int64), 2)
    t = engine.reset_timing()
    assert t["decode_kv_pages_read"] == 2 * (4 + 4 + 1 + 1) + 4 * (
        2 + 1 + 1 + 1)
    assert engine.reset_timing()["decode_kv_pages_read"] == 0
    engine.close()


# What models of one kind were at the parent commit (afec8d0): parameter
# tree (paths and bytes), logits and router loss of a fixed batch, sha256.
GOLDEN = {
    "tiny-llama": ["f69fb7b65ab2947c", "6ff0393864ced293", "df3f619804a92fdb"],
    "tiny-mixtral": ["f3c4e704c0b58028", "bfaf4064ffad585c",
                     "ae88813fdce0c720"],
    "tiny-gemma2": ["58b4ea4b68f2f562", "2be9ab6acdcfeaf9",
                    "df3f619804a92fdb"],
    "tiny": ["24e76af489660e4e", "3645074ff644da15", "df3f619804a92fdb"],
}


@pytest.mark.parametrize("preset", sorted(GOLDEN))
def test_models_of_one_kind_are_bit_equal_to_the_parent(preset):
    m = get_config(preset).model
    p = T.init_params(m, jax.random.key(7))
    toks = jax.random.randint(jax.random.key(8), (2, 48), 1, m.vocab_size)
    logits, aux = jax.jit(lambda p, t: T.forward(p, t, m))(p, toks)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    got = [h.hexdigest()[:16]] + [
        hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]
        for a in (logits, aux)]
    assert got == GOLDEN[preset]


def test_each_distinct_flash_kernel_is_traced_once_a_process(tiny, monkeypatch):
    """Set-up's cost, held here and not only by the driver's clock: the six
    unrolled layers of a prefill program hold six flash call sites and two
    distinct kernels (4 heads under no window, 6 under window 8), and the
    output check's fresh one-step jits of the same shape trace neither again.
    ``pallas_call`` alone re-traces its kernel at every call site (PERF.md
    section 6, PRs 29 and 31)."""
    import functools
    import importlib

    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache

    fa = importlib.import_module("orion_tpu.ops.pallas.flash_attention")
    cfg = get_config("tiny-laguna", [
        "model.kernels=pallas_interpret", "inference.max_seq_len=64",
        "inference.page_size=8", "inference.num_pages=24",
        "inference.max_batch_size=2"])
    m = cfg.model
    args = (tiny[1], init_cache(m, cfg.inference),
            jnp.ones((2, 24), jnp.int32), jnp.asarray([20, 13], jnp.int32),
            jnp.arange(1, 7, dtype=jnp.int32).reshape(2, 3))
    traced = []
    kernel = fa._fwd_kernel

    def counting(st, *rest):
        traced.append(st)
        return kernel(st, *rest)

    monkeypatch.setattr(fa, "_fwd_kernel", counting)
    jax.clear_caches()
    for _ in range(3):          # the warm program, then two fresh probes
        jax.jit(functools.partial(runner.prefill_step, cfg=m)).lower(*args)
        assert len(traced) == 2, traced
    assert {st.window for st in traced} == {None, 8}
