"""Unit tests for the transformer model family and ops (SURVEY.md §5 unit tier)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu import ops
from orion_tpu.config import get_config
from orion_tpu.models import forward, init_params, loss_fn, param_logical_axes

# Too heavy for the tier-1 CPU budget; runs in the full tier (no
# `-m "not slow"`).
pytestmark = pytest.mark.slow



@pytest.mark.parametrize(
    "preset", ["tiny", "tiny-llama", "tiny-mixtral", "tiny-gemma2"]
)
def test_forward_shapes_and_finite(preset):
    cfg = get_config(preset).model
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    logits, aux = forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(float(aux))
    if cfg.is_moe:
        assert float(aux) > 0.0


def test_gemma2_pallas_matches_xla():
    """The Gemma-2 block shape through the flash kernels (softcap + window
    + grouped interleave, interpret mode) must reproduce the xla path."""
    import dataclasses

    cfg = get_config("tiny-gemma2").model
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0,
                                cfg.vocab_size)
    ref, _ = forward(params, tokens, cfg)
    pcfg = dataclasses.replace(cfg, kernels="pallas_interpret")
    got, _ = forward(params, tokens, pcfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=1e-3)


def test_gemma2_trains():
    """tiny-gemma2 end-to-end through the GROUPED layer scan under remat:
    loss falls (the grouped scan + post-norms are differentiable and
    remat-compatible)."""
    import dataclasses

    from orion_tpu.config import get_config as _gc
    from orion_tpu.train import Trainer

    cfg = _gc("tiny-gemma2", [
        "runtime.platform=cpu", "model.remat=full", "train.num_steps=10",
        "train.log_interval=100", "optimizer.warmup_steps=2",
    ])
    hist = Trainer(cfg).fit()
    assert hist[-1].loss < hist[0].loss - 0.1


def test_logical_axes_match_params():
    for preset in ("tiny", "tiny-llama", "tiny-mixtral", "tiny-gemma2"):
        cfg = get_config(preset).model
        params = init_params(cfg, jax.random.key(0))
        axes = param_logical_axes(cfg)
        jax.tree.map(
            lambda p, a: None
            if p.ndim == len(a)
            else pytest.fail(f"{preset}: {p.shape} vs axes {a}"),
            params,
            axes,
            is_leaf=lambda x: isinstance(x, tuple),
        )


def test_causality():
    """Changing a future token must not change past logits."""
    cfg = get_config("tiny-llama").model
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 12), 0, cfg.vocab_size)
    logits1, _ = forward(params, tokens, cfg)
    tokens2 = tokens.at[0, 8].set((tokens[0, 8] + 1) % cfg.vocab_size)
    logits2, _ = forward(params, tokens2, cfg)
    np.testing.assert_allclose(
        np.asarray(logits1[0, :8]), np.asarray(logits2[0, :8]), atol=1e-5
    )
    assert not np.allclose(np.asarray(logits1[0, 8:]), np.asarray(logits2[0, 8:]))


def test_gqa_matches_full_heads_when_kv_repeated():
    """GQA with duplicated kv weights == MHA with the same weights."""
    cfg_g = get_config("tiny-llama").model  # n_heads=4, n_kv_heads=2
    cfg_f = get_config("tiny-llama", ["model.n_kv_heads=4"]).model
    params = init_params(cfg_g, jax.random.key(0))

    def widen(p):
        # wk/wv: [L, D, K*H] -> [L, D, N*H] by repeating each head's block.
        L, D, KH = p.shape
        H = cfg_g.resolved_head_dim
        K = KH // H
        rep = cfg_g.n_heads // K
        heads = p.reshape(L, D, K, H)
        return jnp.repeat(heads, rep, axis=2).reshape(L, D, -1)

    pf = jax.tree.map(lambda x: x, params)
    pf["blocks"]["attn"]["wk"] = widen(params["blocks"]["attn"]["wk"])
    pf["blocks"]["attn"]["wv"] = widen(params["blocks"]["attn"]["wv"])

    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg_g.vocab_size)
    lg, _ = forward(params, tokens, cfg_g)
    lf, _ = forward(pf, tokens, cfg_f)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lf), atol=2e-5)


def test_scan_vs_unrolled_layers():
    cfg_s = get_config("tiny-llama").model
    cfg_u = get_config("tiny-llama", ["model.scan_layers=false"]).model
    params = init_params(cfg_s, jax.random.key(0))
    # Unstack the scanned params into a per-layer list.
    L = cfg_s.n_layers
    unstacked = [
        jax.tree.map(lambda x: x[i], params["blocks"]) for i in range(L)
    ]
    pu = dict(params, blocks=unstacked)
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg_s.vocab_size)
    ls, _ = forward(params, tokens, cfg_s)
    lu, _ = forward(pu, tokens, cfg_u)
    np.testing.assert_allclose(np.asarray(ls), np.asarray(lu), atol=1e-5)


def test_scan_unroll_matches_rolled():
    """model.scan_unroll changes scheduling, not semantics."""
    cfg = get_config("tiny-llama").model
    cfg_u = get_config("tiny-llama", ["model.scan_unroll=2"]).model
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    l1, _ = forward(params, tokens, cfg)
    l2, _ = forward(params, tokens, cfg_u)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)


def test_scan_group_composes_with_unroll():
    """model.scan_group (groups of statically-unrolled layers) matches the
    per-layer scan and composes with scan_unroll (which then unrolls GROUP
    steps). tests/test_scan_remat.py owns the grad-equivalence + HLO
    suite; the unscanned stack is covered by test_scan_vs_unrolled_layers
    (scan_group>1 with scan_layers=false is rejected by the Trainer)."""
    cfg = get_config("tiny-llama", ["model.n_layers=4"]).model
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    ref, _ = forward(params, tokens, cfg)
    for ov in (["model.scan_group=2"],
               ["model.scan_group=2", "model.scan_unroll=2"],
               ["model.scan_group=4"]):
        cfg_g = get_config("tiny-llama", ["model.n_layers=4"] + ov).model
        got, _ = forward(params, tokens, cfg_g)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-5, err_msg=str(ov)
        )


def test_remat_matches_no_remat():
    cfg = get_config("tiny-llama").model
    cfg_r = get_config("tiny-llama", ["model.remat=full"]).model
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    batch = {"inputs": tokens, "targets": tokens}
    g1 = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)
    g2 = jax.grad(lambda p: loss_fn(p, batch, cfg_r)[0])(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        g1,
        g2,
    )


def test_chunked_loss_matches_dense():
    """loss_chunk streams the vocab projection; same loss + grads as dense."""
    cfg = get_config("tiny-llama").model
    cfg_c = get_config("tiny-llama", ["model.loss_chunk=4"]).model
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    mask = (jax.random.uniform(jax.random.key(2), (2, 16)) > 0.3).astype(
        jnp.float32
    )
    batch = {"inputs": tokens, "targets": tokens, "loss_mask": mask}
    (l1, aux1), g1 = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg), has_aux=True
    )(params)
    (l2, aux2), g2 = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg_c), has_aux=True
    )(params)
    assert float(l1) == pytest.approx(float(l2), abs=1e-5)
    assert float(aux1["tokens"]) == float(aux2["tokens"])
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        g1,
        g2,
    )


def test_chunked_loss_non_dividing_raises():
    """A chunk that doesn't divide seq_len must refuse, not silently fall
    back to the dense logits the knob exists to avoid."""
    cfg_c = get_config("tiny-llama", ["model.loss_chunk=5"]).model
    cfg = get_config("tiny-llama").model
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 16), 0, cfg.vocab_size)
    batch = {"inputs": tokens, "targets": tokens}
    with pytest.raises(ValueError, match="must divide seq_len"):
        loss_fn(params, batch, cfg_c)
    # chunk == seq_len is the dense path by construction and stays allowed.
    cfg_eq = get_config("tiny-llama", ["model.loss_chunk=16"]).model
    l1, _ = loss_fn(params, batch, cfg)
    l2, _ = loss_fn(params, batch, cfg_eq)
    assert float(l1) == pytest.approx(float(l2), abs=1e-6)


def test_rope_properties():
    # Rotation preserves norms; position 0 is identity.
    x = jax.random.normal(jax.random.key(0), (1, 6, 2, 8))
    pos = jnp.arange(6)[None, :]
    y = ops.apply_rope(x, pos, theta=10_000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        atol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(y[0, 0]), np.asarray(x[0, 0]), atol=1e-6)
    # Relative property: q.k depends only on distance.
    q = jax.random.normal(jax.random.key(1), (1, 1, 1, 8))
    k = jax.random.normal(jax.random.key(2), (1, 1, 1, 8))
    def dot_at(pq, pk):
        qq = ops.apply_rope(q, jnp.array([[pq]]), theta=10_000.0)
        kk = ops.apply_rope(k, jnp.array([[pk]]), theta=10_000.0)
        return float(jnp.sum(qq * kk))
    assert dot_at(3, 1) == pytest.approx(dot_at(7, 5), abs=1e-4)


def test_rmsnorm_reference():
    x = jax.random.normal(jax.random.key(0), (4, 32))
    scale = jax.random.normal(jax.random.key(1), (32,))
    y = ops.rmsnorm(x, scale, eps=1e-6)
    ref = np.asarray(x) / np.sqrt(
        np.mean(np.asarray(x) ** 2, -1, keepdims=True) + 1e-6
    ) * np.asarray(scale)
    np.testing.assert_allclose(np.asarray(y), ref, atol=1e-5)


def test_attention_segment_masking():
    """Packed sequences must not attend across segment boundaries."""
    q = jax.random.normal(jax.random.key(0), (1, 8, 2, 4))
    k = jax.random.normal(jax.random.key(1), (1, 8, 2, 4))
    v = jax.random.normal(jax.random.key(2), (1, 8, 2, 4))
    seg = jnp.array([[0, 0, 0, 0, 1, 1, 1, 1]])
    out = ops.attention(q, k, v, q_segment_ids=seg, kv_segment_ids=seg)
    # Second segment with segment ids == first 4 tokens of a fresh call.
    out2 = ops.attention(q[:, 4:], k[:, 4:], v[:, 4:])
    np.testing.assert_allclose(
        np.asarray(out[:, 4:]), np.asarray(out2), atol=1e-5
    )


def _moe_setup(seed=0, B=2, S=32, D=16, overflow=False):
    import dataclasses

    from orion_tpu.models import moe as moe_lib

    cfg = get_config("tiny-mixtral").model
    if overflow:
        # Capacity well under demand so the drop path is exercised.
        cfg = dataclasses.replace(cfg, capacity_factor=0.5)
    keys = jax.random.split(jax.random.key(seed), 5)
    E, F = cfg.n_experts, cfg.d_ff
    x = jax.random.normal(keys[0], (B, S, D), jnp.float32)
    params = {
        "router": jax.random.normal(keys[1], (D, E), jnp.float32) * 0.3,
        "w_in": jax.random.normal(keys[2], (E, D, F), jnp.float32) * 0.1,
        "w_gate": jax.random.normal(keys[3], (E, D, F), jnp.float32) * 0.1,
        "w_out": jax.random.normal(keys[4], (E, F, D), jnp.float32) * 0.1,
    }
    return moe_lib, cfg, x, params


@pytest.mark.parametrize("overflow", [False, True])
def test_moe_sorted_matches_einsum(overflow):
    """The ragged scatter/gather dispatch implements the einsum path's exact
    drop semantics (slot-major priority, first-come within slot, capacity
    per batch row) — outputs and aux loss must agree, including under
    capacity overflow."""
    moe_lib, cfg, x, params = _moe_setup(overflow=overflow)
    y_e, aux_e = moe_lib.moe_mlp(x, params, cfg)
    y_s, aux_s = moe_lib.moe_mlp_sorted(x, params, cfg)
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e), atol=2e-5)
    np.testing.assert_allclose(float(aux_s), float(aux_e), rtol=1e-6)


@pytest.mark.parametrize("overflow", [False, True])
def test_moe_sorted_grads_match_einsum(overflow):
    moe_lib, cfg, x, params = _moe_setup(seed=3, overflow=overflow)

    def loss(fn, x, params):
        y, aux = fn(x, params, cfg)
        return (y ** 2).sum() + aux

    g_e = jax.grad(lambda x, p: loss(moe_lib.moe_mlp, x, p),
                   argnums=(0, 1))(x, params)
    g_s = jax.grad(lambda x, p: loss(moe_lib.moe_mlp_sorted, x, p),
                   argnums=(0, 1))(x, params)
    np.testing.assert_allclose(np.asarray(g_s[0]), np.asarray(g_e[0]),
                               atol=5e-5)
    for k in g_e[1]:
        np.testing.assert_allclose(
            np.asarray(g_s[1][k]), np.asarray(g_e[1][k]), atol=5e-5,
            err_msg=k,
        )


def _grouped_case(routing, B, S):
    """Dropless tiny-mixtral MoE inputs whose router is steered through a
    constant feature of x: ``empty`` keeps expert 1 out of every top-2,
    ``full`` puts expert 2 into every top-2."""
    import dataclasses

    moe_lib, cfg, x, params = _moe_setup(seed=7, B=B, S=S)
    cfg = dataclasses.replace(
        cfg, capacity_factor=cfg.n_experts / cfg.n_experts_per_token)
    x = x.at[..., 0].set(1.0)
    bias = {"any": [0, 0, 0, 0], "empty": [0, -50, 0, 0],
            "full": [0, 0, 50, 0]}[routing]
    params["router"] = params["router"].at[0].set(jnp.asarray(bias, float))
    return moe_lib, cfg, x, params


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize(
    "routing,B,S", [("any", 3, 16), ("any", 1, 40), ("empty", 2, 16),
                    ("full", 2, 16)])
def test_moe_grouped_matches_sorted(routing, B, S, masked, impl):
    """The dropless grouped dispatch against the capacity dispatch at a
    capacity that drops nothing: outputs, aux loss and every gradient agree
    over several rows, an expert with no token and one with every token.
    With ``valid`` the real positions agree and the masked ones are zero,
    in the output and in x's gradient."""
    import dataclasses

    moe_lib, cfg, x, params = _grouped_case(routing, B, S)
    gcfg = dataclasses.replace(cfg, kernels=impl)
    _, _, idx = moe_lib._router_topk(x, params["router"], cfg)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=cfg.n_experts)
    if routing == "empty":
        assert counts[1] == 0
    if routing == "full":
        assert counts[2] == B * S
    valid = None
    keep = jnp.ones((B, S, 1), bool)
    if masked:
        lengths = jnp.asarray([S, 5, 1][:B])
        valid = jnp.arange(S)[None] < lengths[:, None]
        keep = valid[..., None]
    w = jax.random.normal(jax.random.key(11), x.shape)

    def loss(fn, x, p):
        y, aux = fn(x, p)
        return jnp.sum(jnp.where(keep, y * w, 0)) + aux, y

    sorted_fn = lambda x, p: moe_lib.moe_mlp_sorted(x, p, cfg)
    grouped_fn = lambda x, p: moe_lib.moe_mlp_grouped(x, p, gcfg, valid)
    (_, y_s), g_s = jax.value_and_grad(
        lambda x, p: loss(sorted_fn, x, p), argnums=(0, 1), has_aux=True
    )(x, params)
    (_, y_g), g_g = jax.value_and_grad(
        lambda x, p: loss(grouped_fn, x, p), argnums=(0, 1), has_aux=True
    )(x, params)
    np.testing.assert_allclose(
        np.asarray(jnp.where(keep, y_g, 0)),
        np.asarray(jnp.where(keep, y_s, 0)), atol=2e-6)
    assert not np.asarray(jnp.where(keep, 0, y_g)).any()
    # The aux loss reads every position on both paths, so x's gradient at a
    # masked position is the router's alone there: compare the real ones.
    np.testing.assert_allclose(
        np.asarray(jnp.where(keep, g_g[0], 0)),
        np.asarray(jnp.where(keep, g_s[0], 0)), atol=2e-6)
    for name in params:
        np.testing.assert_allclose(
            np.asarray(g_g[1][name]), np.asarray(g_s[1][name]), atol=5e-6,
            err_msg=name)


def test_moe_dispatch_unknown_mode_raises():
    import dataclasses

    moe_lib, cfg, x, params = _moe_setup()
    bad = dataclasses.replace(cfg, moe_dispatch="banana")
    with pytest.raises(ValueError, match="moe_dispatch"):
        moe_lib.moe_dispatch(x, params, bad)


def test_moe_aux_loss_balanced_router_is_one():
    """A perfectly uniform router gives aux loss ~= 1 (Switch normalization)."""
    from orion_tpu.models import moe as moe_lib

    cfg = get_config("tiny-mixtral").model
    x = jax.random.normal(jax.random.key(0), (2, 16, cfg.d_model))
    router = jnp.zeros((cfg.d_model, cfg.n_experts))  # uniform logits
    disp, comb, aux = moe_lib.route(x, router, cfg)
    assert float(aux) == pytest.approx(1.0, rel=0.05)
    # Every token dispatched (capacity permitting): combine weights sum to ~1.
    assert disp.shape == (2, 16, cfg.n_experts, moe_lib.moe_capacity(cfg, 16))
