"""A model whose window layers differ from its full layers in their K/V heads
(MiMo-V2.5: window layers of 8 K/V heads with a learned sink beside full
layers of 4, keys wider than values, values scaled, two rotary tables, a
sigmoid router under a bias over a held share, no shared expert) at a tiny
size on the CPU: the training forward, the engine's prefill and decode
through BOTH cache kinds (pages for full layers, a ring a slot for window
layers) and the plain reference agree on logits; the kernels agree with the
XLA form with and without a sink; the shares of an expert layer add up to the
whole layer; the preset is the published configuration."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import (
    MIMO_HYBRID_LAYER_PATTERN, MIMO_MOE_LAYER_FREQ, get_config)
from orion_tpu.infer import kv_cache
from orion_tpu.models import moe as moe_lib
from orion_tpu.models import transformer as T
from orion_tpu.ops.attention import attention, attention_xla

REPO = pathlib.Path(__file__).resolve().parents[1]
PUBLISHED = json.loads((REPO / "tests/benchmark/data/published/"
                        "mimo-v2.5-serve-1chip.json").read_text())


def _reference():
    from benchmarks.harness import cell

    return cell._load(REPO / "benchmarks" / "reference" / "mimo.py")


def tiny_hf(m, held=None) -> dict:
    """The tiny preset under the published key names, as the reference reads
    a configuration file; ``held`` = [first, end) of the experts held."""
    held = held or [0, m.resolved_router_width]
    L = m.n_layers
    return {
        "hidden_size": m.d_model, "head_dim": m.resolved_head_dim,
        "v_head_dim": m.v_head_dim, "num_attention_heads": m.n_heads,
        "num_key_value_heads": m.n_kv_heads,
        "swa_num_key_value_heads": m.n_kv_heads_sliding,
        "attention_value_scale": m.value_scale,
        "add_swa_attention_sink_bias": m.attn_sink == "sliding",
        "add_full_attention_sink_bias": False,
        "vocab_size": m.vocab_size, "num_hidden_layers": L,
        "layernorm_epsilon": m.norm_eps, "intermediate_size": m.d_ff,
        "moe_intermediate_size": m.moe_d_ff,
        "n_routed_experts": held[1] - held[0],
        "published": {"n_routed_experts": m.resolved_router_width},
        "deployment": {"experts_held": held},
        "num_experts_per_tok": m.n_experts_per_token,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "routed_scaling_factor": None,
        "sliding_window": m.sliding_window,
        "partial_rotary_factor": m.rope_full.rotary_fraction,
        "rope_theta": m.rope_full.theta,
        "swa_rope_theta": m.rope_sliding.theta,
        "hybrid_layer_pattern": [
            int(t == "sliding_attention") for t in m.layer_types],
        "moe_layer_freq": [0] * m.n_dense_layers + [1] * (L - m.n_dense_layers),
    }


def _share(params, first: int, end: int):
    """The tree of a chip that holds experts [first, end)."""
    def cut(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        if "moe" in names and names[-1] in ("w_in", "w_gate", "w_out"):
            return leaf[..., first:end, :, :]
        return leaf

    return jax.tree_util.tree_map_with_path(cut, params)


def _rel(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _drawn(params):
    """``init_params``' tree with the leaves it starts at a constant drawn
    as the benchmark draws them (sinks over [0, 4), biases around 1): at 0 a
    sink or a bias that the program dropped would read the same."""
    def draw(path, leaf):
        name = getattr(path[-1], "key", None)
        key = jax.random.key(sum(map(ord, jax.tree_util.keystr(path))))
        if name == "sink":
            return 4.0 * jax.random.uniform(key, leaf.shape, leaf.dtype)
        if name == "router_bias":
            return 1.0 + 0.05 * jax.random.normal(key, leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(draw, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tiny-mimo")
    return cfg, _drawn(T.init_params(cfg.model, jax.random.key(5)))


def test_the_plan_and_the_two_cache_kinds(tiny):
    m = tiny[0].model
    plan = m.layer_plan
    assert (plan.lead, plan.period, plan.repeats, plan.tail) == (1, 3, 2, 0)
    kinds = m.layer_kinds
    assert [k.window for k in kinds] == [None, 8, 8, None, 8, 8, None]
    assert [m.kv_heads_of(k) for k in kinds] == [2, 4, 4, 2, 4, 4, 2]
    assert [k.sink for k in kinds] == [k.window is not None for k in kinds]
    assert [k.moe for k in kinds] == [False] + [True] * 6
    assert [k.rope.theta for k in kinds] == [
        1e4 if k.window else 1e7 for k in kinds]
    # the pool is the full layers': never freed behind a window; what a
    # window layer keeps is ring_window
    assert m.has_window_ring and m.page_window is None and m.ring_window == 8
    assert m.n_paged_layers == 3
    assert [m.cache_kind(k) for k in kinds] == [
        "ring" if k.window else "softmax" for k in kinds]
    # each layer's row among the layers of its cache kind
    rows = [m.cache_layer(l, j) for l, j in
            [(0, 0), (1, 1), (2, 2), (3, 3), (4, 1), (5, 2), (6, 3)]]
    assert rows == [0, 0, 1, 1, 2, 3, 2]
    cache = kv_cache.init_cache(m, tiny[0].inference)
    slots, psz = tiny[0].inference.max_batch_size, 8
    assert kv_cache.ring_pages(8, 8) == 2 and kv_cache.ring_pages(128, 64) == 3
    assert {n: a.shape for n, a in cache.items()} == {
        "k": (3 * 64, 3, psz, 16), "v": (3 * 64, 2, psz, 16),
        "ring_k": (4, slots + 1, 2, 6, psz, 16),
        "ring_v": (4, slots + 1, 2, 4, psz, 16)}
    # a model with one K/V shape keeps its one pool and has no ring
    for preset in ("tiny-laguna", "tiny-llama", "tiny-mixtral", "tiny-glm"):
        other = get_config(preset).model
        assert not other.has_window_ring and other.ring_window is None
        assert all(k.n_kv_heads is None and not k.sink
                   for k in other.layer_kinds)


def test_packed_keys_round_trip_and_score_as_whole_keys():
    key = jax.random.key(0)
    k = jax.random.normal(key, (3, 5, 4, 24))
    rows = kv_cache.pack_keys(k, 16)
    assert rows.shape == (3, 5, 6, 16)
    np.testing.assert_array_equal(kv_cache.unpack_keys(rows, 4), k)
    q = jax.random.normal(jax.random.key(1), (3, 5, 8, 24))
    qp = kv_cache.pack_queries(q, 4, 16)          # [.., 8, 32]
    want = jnp.einsum("bsnh,bskh->bsnk", q, k)
    main = jnp.einsum("bsnh,bskh->bsnk", qp[..., :16], rows[..., :4, :])
    pair = jnp.einsum("bsnh,bskh->bsnk", qp[..., 16:], rows[..., 4:, :])
    got = main + jnp.repeat(pair, 2, axis=-1)     # K/V head g reads pair g // 2
    own = jnp.arange(8)[:, None] // 2 == jnp.arange(4)[None, :]
    np.testing.assert_allclose(
        jnp.where(own, got, 0), jnp.where(own, want, 0), rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="packed key layout"):
        kv_cache.pack_keys(jnp.zeros((1, 1, 4, 32)), 16)


@pytest.mark.parametrize("held", [None, [0, 8], [8, 16]])
def test_forward_engine_and_reference_agree_on_logits(tiny, held):
    """Three ways, float32 on the CPU: prompts shorter than the window (5),
    equal to it (8) and long enough that decode takes the ring (2 pages of
    8) round three times behind a prefill that wrote only its last pages
    (50 + 8 tokens). Tolerance 2e-4 relative L2, as for the other layer-plan
    models; the window link is held on the pages AND on the slot's rings
    (kinds/serve_rows.py)."""
    from benchmarks.kinds import serve_rows
    from orion_tpu.infer import InferenceEngine

    cfg, params = tiny
    if held is not None:
        cfg = get_config("tiny-mimo", [
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
    mix = {"probe_prompts": [5, 8, 50], "probe_windows": 2}
    n = serve_rows.probe_numbers(engine, ref, hf, mix, seed=3, control="int8")
    assert len(n["err"]) == 3 * (1 + 2 * engine.decode_window)
    assert max(n["err"]) < 2e-4
    assert max(n["window_kv_rel_err"]) < 1e-5
    assert min(n["control_err"]) > 50 * max(n["err"])     # the control fails
    t, W = engine.reset_timing(), engine.decode_window
    if held is None:
        # ... and decode alone takes the ring round three times more: 52
        # steps from position 20 cross into pages 4, 6 and 8.
        n = serve_rows.probe_numbers(
            engine, ref, hf, {"probe_prompts": [20], "probe_windows": 13}, 4)
        assert max(n["err"]) < 2e-4 and max(n["window_kv_rel_err"]) < 1e-5
        t = engine.reset_timing()
    # The pages the decode kernels walked, by hand (pages of 8): every probe
    # alone, its windows' new tokens at consecutive positions from its
    # prompt's length on; a full layer (3 of them) walks every page up to
    # the new token's, a window layer (4) the pages its last 8 positions
    # lie in.
    pos = np.concatenate([np.arange(a, a + b * W) for a, b in (
        [(20, 13)] if held is None else [(5, 2), (8, 2), (50, 2)])])
    assert t["decode_kv_pages_read"] == (
        3 * int((pos // 8 + 1).sum())
        + 4 * int((pos // 8 - np.maximum(pos - 7, 0) // 8 + 1).sum()))
    assert (t["decode_kv_token_layers_full"] + t["decode_kv_token_layers_ring"]
            == t["decode_kv_token_layers"])
    assert t["kv_dead_window_page_layers"] == 0
    # a window layer holds a ring's reach of a slot at most, whatever its
    # length (a held position is 4 * 10 * 16 * 4 B over the window
    # layers); a full layer every position
    held_positions = t["kv_window_bytes_held"] // (4 * 10 * 16 * 4)
    assert 0 < held_positions < t["kv_full_positions_live"]
    assert t["kv_window_bytes_held"] > 0 < t["kv_full_bytes_live"]
    # a full layer's position: (3 + 2) rows x 16 x 4 B in each of 3 layers;
    # the pool holds whole pages for it, out to the prompt's bucket
    assert t["kv_full_bytes_live"] == 960 * t["kv_full_positions_live"]
    assert t["kv_full_page_bytes_held"] > t["kv_full_bytes_live"]
    assert t["kv_window_bytes_held"] % (4 * 10 * 16 * 4) == 0
    engine.close()


@pytest.mark.parametrize("fault", ["sink", "value_scale", "window"])
def test_a_planted_fault_reads_as_one(tiny, fault):
    """What the comparison is for: the program without the sink, without the
    value scale, or with the window one position wider reads at least 40
    times the sound program's error (float32: nothing hides a position)."""
    cfg, params = tiny
    m, ref, hf = cfg.model, _reference(), tiny_hf(cfg.model)
    bad = {"sink": {"add_swa_attention_sink_bias": False},
           "value_scale": {"attention_value_scale": 1.0},
           "window": {"sliding_window": 9}}[fault]
    toks = jax.random.randint(jax.random.key(1), (1, 40), 1, m.vocab_size)
    got, _ = jax.jit(lambda p, t: T.forward(p, t, m))(params, toks)
    want, _ = ref.logits_at(params, toks[0], jnp.arange(40), {**hf, **bad})
    assert _rel(got[0], want) > 40 * 2e-4


def test_the_compiled_kernels_compute_it_too(tiny):
    """The same engine path with every Pallas kernel interpreted: flash
    attention with a sink and values narrower than keys, and the paged
    decode kernel over packed keys, on the pool and on the rings."""
    from benchmarks.kinds import serve_rows
    from orion_tpu.infer import InferenceEngine

    cfg = get_config("tiny-mimo", ["model.kernels=pallas_interpret",
                                   "inference.decode_window=2"])
    engine = InferenceEngine(cfg, tiny[1], seed=0)
    n = serve_rows.probe_numbers(
        engine, _reference(), tiny_hf(cfg.model),
        {"probe_prompts": [5, 40], "probe_windows": 3}, 3)
    assert max(n["err"]) < 2e-4 and max(n["window_kv_rel_err"]) < 1e-5
    engine.close()


@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("length", [5, 8, 70])
def test_flash_forward_with_a_sink_and_narrow_values(sink, length):
    """The flash forward kernel (interpreted) against ``ops/attention.py``'s
    XLA form: keys 24 wide (padded to lanes under their own scale), values
    16, a window of 8, prompts shorter than it, equal to it and far longer,
    with and without the sink; and no backward."""
    ks = jax.random.split(jax.random.key(length), 4)
    q = jax.random.normal(ks[0], (2, length, 8, 24))
    k = jax.random.normal(ks[1], (2, length, 4, 24))
    v = jax.random.normal(ks[2], (2, length, 4, 16))
    b = 2.0 * jax.random.normal(ks[3], (8,)) if sink else None
    for window in (None, 8):
        want = attention_xla(q, k, v, window=window, sink=b)
        got = attention(q, k, v, window=window, sink=b,
                        impl="pallas_interpret")
        assert got.shape == (2, length, 8, 16)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if sink:        # a row's weights add up to less than 1
        ones = attention_xla(q, k, jnp.ones_like(v), sink=b)
        assert float(ones.max()) < 1.0
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda q_: attention(
            q_, k, v, sink=b, impl="pallas_interpret").sum())(q)


@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("length", [5, 8, 60])
def test_paged_kernel_over_packed_keys_and_a_ring(sink, length):
    """The paged decode kernel (interpreted) over packed key rows against
    the XLA form on the whole keys: a slot of ``length`` positions whose last
    ring pages alone are kept (2 pages of 8 under a window of 8: at 60 the
    ring has gone round three times), the new token's write fused in."""
    from orion_tpu.ops.pallas.paged_attention import attend as kernel

    psz, RP, K, N, H, Hv, win = 8, 2, 4, 8, 24, 16, 8
    ks = jax.random.split(jax.random.key(length), 4)
    k = jax.random.normal(ks[0], (1, length + 1, K, H))
    v = jax.random.normal(ks[1], (1, length + 1, K, Hv))
    q = jax.random.normal(ks[2], (1, 1, N, H))
    b = 2.0 * jax.random.normal(ks[3], (N,)) if sink else None
    # the ring as a prefill and earlier steps left it: position p in ring
    # page (p // psz) % RP, the newest position not yet written
    ring_k = jnp.zeros((RP + 1, K + K // 2, psz, Hv))
    ring_v = jnp.zeros((RP + 1, K, psz, Hv))
    rows = kv_cache.pack_keys(k, Hv)[0]
    first = max(length // psz - (RP - 1), 0)
    for p in range(first * psz, length):
        at = (1 + (p // psz) % RP, slice(None), p % psz)
        ring_k, ring_v = ring_k.at[at].set(rows[p]), ring_v.at[at].set(v[0, p])
    table = 1 + (first + jnp.arange(RP)[None, :]) % RP
    start = jnp.asarray([length - first * psz], jnp.int32)
    out, new_k, new_v = kernel(
        kv_cache.pack_queries(q, K, Hv), ring_k, ring_v, table, start,
        jnp.ones_like(start), layer_base=0, k_new=rows[None, length:],
        v_new=v[:, length:], logit_softcap=None, window=win, interpret=True,
        k_scale=None, v_scale=None, sink=b, scale=H ** -0.5)
    want = attention_xla(q, k, v, q_offset=length, window=win, sink=b)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    at = (1 + (length // psz) % RP, slice(None), length % psz)
    np.testing.assert_array_equal(new_k[at], rows[length])
    np.testing.assert_array_equal(new_v[at], v[0, length])


@pytest.mark.parametrize("grouped", [False, True])
def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_whole_layer(
        tiny, grouped, monkeypatch):
    """One sparse layer: what the sixteen chips that share it give, each
    holding one of its 16 experts (no shared expert: nothing is counted
    twice), adds up to what the uncut reference gives for the whole layer;
    a share equals the reference's own cut. Both dispatches."""
    if grouped:      # the rule's tile term keeps tiny blocks off this path
        monkeypatch.setattr("orion_tpu.ops.grouped_matmul.ROW_TILE", 1)
    cfg, params = tiny
    m, ref = cfg.model, _reference()
    bp = jax.tree.map(lambda a: a[0], params["blocks"]["period"]["1"])
    h = jax.random.normal(jax.random.key(2), (2, 12, m.d_model), jnp.float32)
    valid = jnp.arange(12)[None, :] < jnp.asarray([12, 7])[:, None]
    flat = lambda y: jnp.where(valid[..., None], y, 0).reshape(24, -1)

    def program(first, end):
        c = get_config("tiny-mimo", [
            f"model.n_experts={end - first}",
            f"model.expert_offset={first}"]).model
        if end - first > 1:     # (one expert: the rule keeps its buckets)
            assert moe_lib.takes_grouped_path(c, 2, 12) == grouped
        y, _ = T.mlp_or_moe(h, _share(bp, first, end), c, valid=valid)
        return flat(y)

    def reference(first, end):
        hf = tiny_hf(m, [first, end])
        with jax.default_matmul_precision("highest"):
            return flat(ref._moe(h.reshape(24, -1),
                                 _share(bp, first, end)["moe"], hf,
                                 None)[0].reshape(2, 12, -1))

    whole = reference(0, 16)
    # sixteen chips of one expert each (capacity buckets), or, where the
    # grouped matmul is asked for, the same layer in two shares of eight
    shares = ([(0, 8), (8, 16)] if grouped
              else [(e, e + 1) for e in range(16)])
    parts = [program(*s) for s in shares]
    assert _rel(sum(parts), whole) < 1e-5
    assert _rel(program(0, 16), whole) < 1e-5
    for part, s in list(zip(parts, shares))[::7]:
        # (against the layer's size: an expert no row chose gives 0)
        assert float(jnp.linalg.norm(part - reference(*s))) < 1e-5 * float(
            jnp.linalg.norm(whole))


def test_the_preset_is_the_published_configuration():
    """Entry by entry against the source's config.json (the catalog row's
    ``config``), the per-layer lists among them."""
    m, p = get_config("mimo-v2.5").model, PUBLISHED
    assert list(MIMO_HYBRID_LAYER_PATTERN) == p["hybrid_layer_pattern"]
    assert list(MIMO_MOE_LAYER_FREQ) == p["moe_layer_freq"]
    kinds = m.layer_kinds
    assert len(kinds) == p["num_hidden_layers"] == 48
    for l, (w, s) in enumerate(zip(p["hybrid_layer_pattern"],
                                   p["moe_layer_freq"])):
        k = kinds[l]
        assert k.window == (p["sliding_window"] if w else None), l
        assert k.moe == bool(s), l
        assert m.kv_heads_of(k) == (p["swa_num_key_value_heads"] if w
                                    else p["num_key_value_heads"]), l
        assert k.sink == bool(p["add_swa_attention_sink_bias"] if w
                              else p["add_full_attention_sink_bias"]), l
        assert k.rope.theta == (p["swa_rope_theta"] if w
                                else p["rope_theta"]), l
        assert k.rope.rotary_fraction == p["partial_rotary_factor"], l
        assert k.n_heads == p["num_attention_heads"] == p[
            "swa_num_attention_heads"]
    assert sum(k.window is None for k in kinds) == 9
    assert m.layer_plan == (1, 6, 7, 5, ())
    assert (m.d_model, m.resolved_head_dim, m.v_head_dim, m.d_ff,
            m.moe_d_ff) == (p["hidden_size"], p["head_dim"], p["v_head_dim"],
                            p["intermediate_size"],
                            p["moe_intermediate_size"])
    assert p["swa_head_dim"] == p["head_dim"] and (
        p["swa_v_head_dim"] == p["v_head_dim"])
    assert int(m.resolved_head_dim * m.rope_full.rotary_fraction) == 64
    assert (m.n_experts, m.resolved_router_width, m.n_experts_per_token) == (
        p["n_routed_experts"], 256, p["num_experts_per_tok"])
    assert (m.router_score, m.router_bias, m.n_group, m.topk_group) == (
        p["scoring_func"], p["topk_method"] == "noaux_tc", p["n_group"],
        p["topk_group"])
    assert m.shared_expert_d_ff == 0 and p["n_shared_experts"] is None
    assert m.router_scale == 1.0 and p["routed_scaling_factor"] is None
    assert (m.value_scale, m.norm_eps, m.vocab_size, m.sliding_window) == (
        p["attention_value_scale"], p["layernorm_epsilon"], p["vocab_size"],
        p["sliding_window_size"])
    assert not m.tie_embeddings and not m.attn_bias and not m.qk_norm
    assert m.capacity_factor >= 256 / 8


@pytest.mark.parametrize("override, named", [
    ("inference.prefix_cache=true", "inference.prefix_cache"),
    ("inference.host_tier_bytes=1000000", "inference.host_tier_bytes"),
    ("inference.chunked_prefill=true", "inference.chunked_prefill"),
    ("inference.speculative=true", "inference.speculative"),
    ("inference.kv_quant=int8", "inference.kv_quant"),
])
def test_what_a_ring_cannot_be_served_with_is_refused_by_name(
        tiny, override, named):
    from orion_tpu.infer import InferenceEngine

    with pytest.raises(ValueError, match=rf"ring a request.*unset.*{named}"):
        InferenceEngine(get_config("tiny-mimo", [override]), tiny[1])
