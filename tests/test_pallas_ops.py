"""Unit tier: Pallas kernels vs jnp/XLA reference implementations.

SURVEY.md §5: kernels run through the Pallas interpreter on CPU so the same
code paths are exercised without a TPU; fwd and grads must match the xla ops
to fp32 tolerance.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.ops.attention import attention_xla
from orion_tpu.ops.norms import _rmsnorm_xla
from orion_tpu.ops.pallas import flash_attention, rmsnorm_pallas, rope_pallas
from orion_tpu.ops.rope import _rope_xla


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(key), shape, dtype=dtype)


def _qkv(B=2, Sq=64, Skv=64, N=4, K=4, H=32, dtype=jnp.float32):
    return (
        _rand(0, B, Sq, N, H, dtype=dtype),
        _rand(1, B, Skv, K, H, dtype=dtype),
        _rand(2, B, Skv, K, H, dtype=dtype),
    )


class TestFlashAttention:
    def test_causal_fwd(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=True, interpret=True)
        ref = attention_xla(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_non_causal_fwd(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=False, interpret=True)
        ref = attention_xla(q, k, v, causal=False)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_gqa(self):
        q, k, v = _qkv(N=8, K=2)
        out = flash_attention(q, k, v, interpret=True)
        ref = attention_xla(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_multiple_kv_blocks(self):
        # Sequence longer than one block forces the online-softmax carry.
        q, k, v = _qkv(Sq=160, Skv=160)
        out = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
        ref = attention_xla(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_ragged_padding(self):
        # Non-multiple-of-block lengths exercise the padding mask.
        q, k, v = _qkv(Sq=100, Skv=100)
        out = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
        ref = attention_xla(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_segment_ids(self):
        q, k, v = _qkv()
        seg = jnp.concatenate(
            [jnp.zeros((2, 32), jnp.int32), jnp.ones((2, 32), jnp.int32)], axis=1
        )
        out = flash_attention(
            q, k, v, q_segment_ids=seg, kv_segment_ids=seg, interpret=True
        )
        ref = attention_xla(q, k, v, q_segment_ids=seg, kv_segment_ids=seg)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_softcap(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, logit_softcap=20.0, interpret=True)
        ref = attention_xla(q, k, v, logit_softcap=20.0)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_q_offset_decode(self):
        # Decode-style: 8 new queries attending into a longer kv history.
        q, k, v = _qkv(Sq=8, Skv=72)
        out = flash_attention(q, k, v, q_offset=64, interpret=True)
        ref = attention_xla(q, k, v, q_offset=64)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("case", ["mha", "gqa", "softcap", "ragged"])
    def test_grads_match_xla(self, case):
        kw = {}
        if case == "gqa":
            q, k, v = _qkv(N=8, K=2)
        elif case == "softcap":
            q, k, v = _qkv()
            kw["logit_softcap"] = 20.0
        elif case == "ragged":
            q, k, v = _qkv(Sq=100, Skv=100)
        else:
            q, k, v = _qkv()

        def loss_pallas(q, k, v):
            o = flash_attention(
                q, k, v, interpret=True, block_q=64, block_kv=64, **kw
            )
            return jnp.sum(o * o)

        def loss_xla(q, k, v):
            o = attention_xla(q, k, v, **kw)
            return jnp.sum(o * o)

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gp, gx, "qkv"):
            np.testing.assert_allclose(
                a, b, rtol=2e-4, atol=2e-4, err_msg=f"d{name} mismatch"
            )

    def test_grads_segment_ids(self):
        q, k, v = _qkv()
        seg = jnp.concatenate(
            [jnp.zeros((2, 32), jnp.int32), jnp.ones((2, 32), jnp.int32)], axis=1
        )

        def lp(q, k, v):
            return jnp.sum(
                flash_attention(
                    q, k, v, q_segment_ids=seg, kv_segment_ids=seg, interpret=True
                ) ** 2
            )

        def lx(q, k, v):
            return jnp.sum(
                attention_xla(q, k, v, q_segment_ids=seg, kv_segment_ids=seg) ** 2
            )

        gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(lx, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)

    def test_explicit_positions_match_permuted_reference(self):
        """Position-based causal masking (striped/permuted layouts): flash
        on a permuted sequence with explicit positions equals the natural-
        order reference with rows/cols permuted, fwd and grads."""
        B, S, N, K, H = 2, 64, 4, 2, 32
        q, kk, v = _qkv(B=B, Sq=S, Skv=S, N=N, K=K, H=H)
        perm = jax.random.permutation(jax.random.key(7), S)
        pos = jnp.broadcast_to(perm[None], (B, S))

        qp, kp, vp = q[:, perm], kk[:, perm], v[:, perm]

        def loss_p(qp, kp, vp):
            out = flash_attention(
                qp, kp, vp, causal=True, interpret=True,
                q_positions=pos, kv_positions=pos,
            )
            return jnp.sum(out ** 2), out

        def loss_r(q, kk, v):
            out = attention_xla(q, kk, v, causal=True)
            return jnp.sum(out[:, perm] ** 2), out

        (_, out_p), g_p = jax.value_and_grad(
            loss_p, argnums=(0, 1, 2), has_aux=True)(qp, kp, vp)
        (_, out_r), g_r = jax.value_and_grad(
            loss_r, argnums=(0, 1, 2), has_aux=True)(q, kk, v)
        np.testing.assert_allclose(
            np.asarray(out_p), np.asarray(out_r[:, perm]),
            rtol=1e-5, atol=1e-5,
        )
        for a, b in zip(g_p, g_r):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b[:, perm]), rtol=1e-4, atol=1e-4
            )

    def test_bf16(self):
        q, k, v = _qkv(dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, interpret=True)
        ref = attention_xla(q, k, v)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref.astype(jnp.float32), rtol=2e-2, atol=2e-2
        )


class TestRMSNorm:
    def test_fwd(self):
        x = _rand(0, 4, 96, 128)
        s = _rand(1, 128) * 0.1 + 1.0
        out = rmsnorm_pallas(x, s, eps=1e-5, interpret=True)
        ref = _rmsnorm_xla(x, s, 1e-5)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_fwd_ragged_rows(self):
        x = _rand(0, 3, 37, 64)
        s = _rand(1, 64)
        out = rmsnorm_pallas(x, s, eps=1e-6, interpret=True, block_rows=32)
        ref = _rmsnorm_xla(x, s, 1e-6)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_grads(self):
        x = _rand(0, 2, 24, 64)
        s = _rand(1, 64) * 0.1 + 1.0

        def lp(x, s):
            return jnp.sum(rmsnorm_pallas(x, s, eps=1e-5, interpret=True) ** 2)

        def lx(x, s):
            return jnp.sum(_rmsnorm_xla(x, s, 1e-5) ** 2)

        gp = jax.grad(lp, argnums=(0, 1))(x, s)
        gx = jax.grad(lx, argnums=(0, 1))(x, s)
        np.testing.assert_allclose(gp[0], gx[0], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(gp[1], gx[1], rtol=1e-4, atol=1e-4)


class TestRoPE:
    def test_fwd(self):
        x = _rand(0, 2, 48, 4, 32)
        pos = jnp.broadcast_to(jnp.arange(48)[None, :], (2, 48))
        out = rope_pallas(x, pos, theta=10_000.0, interpret=True)
        ref = _rope_xla(x, pos, 10_000.0)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_fwd_1d_positions_and_offset(self):
        # Decode: positions far from zero.
        x = _rand(0, 2, 8, 4, 32)
        pos = jnp.arange(1000, 1008)
        out = rope_pallas(x, pos, theta=500_000.0, interpret=True)
        ref = _rope_xla(x, pos, 500_000.0)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)

    def test_grads(self):
        x = _rand(0, 1, 16, 2, 16)
        pos = jnp.arange(16)[None, :]

        def lp(x):
            return jnp.sum(rope_pallas(x, pos, theta=10_000.0, interpret=True) ** 2)

        def lx(x):
            return jnp.sum(_rope_xla(x, pos, 10_000.0) ** 2)

        gp = jax.grad(lp)(x)
        gx = jax.grad(lx)(x)
        np.testing.assert_allclose(gp, gx, rtol=1e-4, atol=1e-4)


class TestModelWithPallasKernels:
    def test_forward_matches_xla_kernels(self):
        """Whole-model parity: tiny llama with kernels=pallas_interpret."""
        from orion_tpu.config import get_config
        from orion_tpu.models import forward, init_params

        cfg = get_config("tiny-llama", ["model.dtype=float32"]).model
        params = init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)

        logits_xla, _ = forward(params, tokens, cfg)
        import dataclasses

        cfg_p = dataclasses.replace(cfg, kernels="pallas_interpret")
        logits_pallas, _ = forward(params, tokens, cfg_p)
        np.testing.assert_allclose(
            logits_pallas, logits_xla, rtol=5e-4, atol=5e-4
        )


# -- paged decode attention -------------------------------------------------


def _paged_reference(q, k_pool, v_pool, page_table, last_pos, window=None):
    from orion_tpu.ops.attention import attention_xla

    B, N, H = q.shape
    P = page_table.shape[1]
    K, psz = k_pool.shape[1], k_pool.shape[2]
    # Pool pages are [K, psz, H] (kv_cache.py layout).
    k_ctx = k_pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
        B, P * psz, K, H)
    v_ctx = v_pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
        B, P * psz, K, H)
    pos = jnp.arange(P * psz, dtype=jnp.int32)[None, None, :]
    mask = pos <= last_pos[:, None, None]
    if window is not None:
        mask &= pos >= (last_pos - window + 1)[:, None, None]
    return attention_xla(
        q[:, None], k_ctx, v_ctx, causal=False, mask=mask
    )[:, 0]


@pytest.mark.parametrize("gqa", [(8, 8), (8, 2), (4, 1)])
def test_paged_attention_matches_gather(gqa):
    from orion_tpu.ops.pallas.paged_attention import paged_attention

    N, K = gqa
    B, H, psz, P, num_pages = 3, 64, 16, 4, 32
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (B, N, H), jnp.float32)
    k_pool = jax.random.normal(keys[1], (num_pages, K, psz, H), jnp.float32)
    v_pool = jax.random.normal(keys[2], (num_pages, K, psz, H), jnp.float32)
    # Shuffled non-contiguous page assignment, ragged lengths.
    page_table = jnp.asarray(
        [[5, 17, 2, 9], [30, 1, 7, 3], [11, 4, 0, 22]], jnp.int32
    )
    last_pos = jnp.asarray([0, 37, 63], jnp.int32)  # 1, 38, 64 valid tokens

    ref = _paged_reference(q, k_pool, v_pool, page_table, last_pos)
    out = paged_attention(
        q, k_pool, v_pool, page_table, last_pos, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_attention_fused_write():
    """The in-kernel KV write (input/output-aliased pool) must equal an
    external scatter followed by attention."""
    from orion_tpu.ops.pallas.paged_attention import paged_attention

    N, K = 8, 2
    B, H, psz, P, num_pages = 3, 64, 16, 4, 32
    keys = jax.random.split(jax.random.key(3), 6)
    q = jax.random.normal(keys[0], (B, N, H), jnp.float32)
    k_pool = jax.random.normal(keys[1], (num_pages, K, psz, H), jnp.float32)
    v_pool = jax.random.normal(keys[2], (num_pages, K, psz, H), jnp.float32)
    k_new = jax.random.normal(keys[3], (B, K, H), jnp.float32)
    v_new = jax.random.normal(keys[4], (B, K, H), jnp.float32)
    page_table = jnp.asarray(
        [[5, 17, 2, 9], [30, 1, 7, 3], [11, 4, 0, 22]], jnp.int32
    )
    last_pos = jnp.asarray([0, 37, 63], jnp.int32)  # the position written

    # Reference: scatter externally, then attend.
    rows = page_table[jnp.arange(B), last_pos // psz]
    kp_ref = k_pool.at[rows, :, last_pos % psz].set(k_new)
    vp_ref = v_pool.at[rows, :, last_pos % psz].set(v_new)
    ref = _paged_reference(q, kp_ref, vp_ref, page_table, last_pos)

    out, kp, vp = paged_attention(
        q, k_pool, v_pool, page_table, last_pos,
        k_new=k_new, v_new=v_new, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(kp[rows, :, last_pos % psz]), np.asarray(k_new), atol=0
    )
    np.testing.assert_allclose(
        np.asarray(vp[rows, :, last_pos % psz]), np.asarray(v_new), atol=0
    )


def test_flash_ragged_padding_rows_parity_and_grads():
    """Segment id 0 marks padding (ragged prefill / packed tails): the
    all-padding block SKIP must not change results — parity vs the xla
    reference with the same segment mask, fwd and grads, at per-row
    ragged lengths that leave whole blocks padded."""
    from orion_tpu.ops.attention import attention_xla
    from orion_tpu.ops.pallas.flash_attention import flash_attention

    B, S, N, K, H = 3, 256, 4, 2, 64
    ks = jax.random.split(jax.random.key(17), 3)
    q = jax.random.normal(ks[0], (B, S, N, H), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, H), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, H), jnp.float32)
    lengths = jnp.asarray([256, 70, 3])      # full, mid-block, tiny
    seg = (jnp.arange(S)[None, :] < lengths[:, None]).astype(jnp.int32)

    def loss_p(q, k, v):
        o = flash_attention(q, k, v, causal=True, q_segment_ids=seg,
                            kv_segment_ids=seg, seg_pad_zero=True,
                            block_q=64, block_kv=64, interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2 * seg[..., None, None])

    def loss_x(q, k, v):
        o = attention_xla(q, k, v, causal=True, q_segment_ids=seg,
                          kv_segment_ids=seg)
        return jnp.sum(o.astype(jnp.float32) ** 2 * seg[..., None, None])

    o_p = flash_attention(q, k, v, causal=True, q_segment_ids=seg,
                          kv_segment_ids=seg, seg_pad_zero=True,
                          block_q=64, block_kv=64, interpret=True)
    o_x = attention_xla(q, k, v, causal=True, q_segment_ids=seg,
                        kv_segment_ids=seg)
    # Compare only real rows: padding rows are garbage by contract.
    m = np.asarray(seg, bool)
    np.testing.assert_allclose(
        np.asarray(o_p)[m], np.asarray(o_x)[m], atol=2e-5)
    g_p = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    g_x = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_x, g_p):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-4)


def test_paged_attention_int8_matches_dequantized_reference():
    """int8 pools + per-(token, head) scales: the kernel's in-place
    dequantization (K scales on logit columns, V scales folded into the
    probabilities) must reproduce masked attention over the explicitly
    dequantized pools, including the fused in-kernel quantized write."""
    from orion_tpu.infer.kv_cache import quantize_kv
    from orion_tpu.ops.pallas.paged_attention import paged_attention

    N, K = 8, 2
    B, H, psz, P, num_pages = 3, 64, 16, 4, 32
    SW = 128
    keys = jax.random.split(jax.random.key(11), 6)
    q = jax.random.normal(keys[0], (B, N, H), jnp.float32)
    kf = jax.random.normal(keys[1], (num_pages, K, psz, H), jnp.float32)
    vf = jax.random.normal(keys[2], (num_pages, K, psz, H), jnp.float32)
    k_new = jax.random.normal(keys[3], (B, K, H), jnp.float32)
    v_new = jax.random.normal(keys[4], (B, K, H), jnp.float32)
    page_table = jnp.asarray(
        [[5, 17, 2, 9], [30, 1, 7, 3], [11, 4, 0, 22]], jnp.int32
    )
    last_pos = jnp.asarray([0, 37, 63], jnp.int32)

    # Host-side quantization (the prefill path): [rows, K, psz, H] over H.
    kq, ks = quantize_kv(kf.transpose(0, 2, 1, 3))   # scale [rows, psz, K]
    vq, vs = quantize_kv(vf.transpose(0, 2, 1, 3))
    kq = kq.transpose(0, 2, 1, 3)
    vq = vq.transpose(0, 2, 1, 3)
    k_scale = jnp.zeros((num_pages, K, SW), jnp.float32
                        ).at[:, :, :psz].set(ks.transpose(0, 2, 1))
    v_scale = jnp.zeros((num_pages, K, SW), jnp.float32
                        ).at[:, :, :psz].set(vs.transpose(0, 2, 1))

    # Reference: dequantize everything ([rows, K, psz] scales broadcast
    # over H), external scatter, masked attention.
    kd = kq.astype(jnp.float32) * k_scale[:, :, :psz][..., None]
    vd = vq.astype(jnp.float32) * v_scale[:, :, :psz][..., None]
    knq, kns = quantize_kv(k_new)
    vnq, vns = quantize_kv(v_new)
    rows = page_table[jnp.arange(B), last_pos // psz]
    kd_ref = kd.at[rows, :, last_pos % psz].set(
        knq.astype(jnp.float32) * kns[..., None])
    vd_ref = vd.at[rows, :, last_pos % psz].set(
        vnq.astype(jnp.float32) * vns[..., None])
    ref = _paged_reference(q, kd_ref, vd_ref, page_table, last_pos)

    out, kp2, vp2, ks2, vs2 = paged_attention(
        q, kq, vq, page_table, last_pos,
        k_new=k_new, v_new=v_new,
        k_scale=k_scale, v_scale=v_scale, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # In-kernel quantized write matches the host-side quantization.
    np.testing.assert_allclose(
        np.asarray(kp2[rows, :, last_pos % psz]), np.asarray(knq), atol=0)
    np.testing.assert_allclose(
        np.asarray(ks2[rows, :, last_pos % psz]), np.asarray(kns),
        rtol=1e-6)
    # And the quantized attention is close to the float answer.
    float_ref = _paged_reference(
        q, kf.at[rows, :, last_pos % psz].set(k_new),
        vf.at[rows, :, last_pos % psz].set(v_new), page_table, last_pos)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(float_ref), atol=0.06)


def test_paged_attention_softcap():
    from orion_tpu.ops.pallas.paged_attention import paged_attention

    B, N, K, H, psz, P, num_pages = 2, 4, 2, 32, 8, 3, 16
    keys = jax.random.split(jax.random.key(1), 4)
    q = jax.random.normal(keys[0], (B, N, H), jnp.float32) * 4
    k_pool = jax.random.normal(keys[1], (num_pages, K, psz, H), jnp.float32)
    v_pool = jax.random.normal(keys[2], (num_pages, K, psz, H), jnp.float32)
    page_table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    last_pos = jnp.asarray([10, 20], jnp.int32)

    from orion_tpu.ops.attention import attention_xla

    k_ctx = k_pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
        B, P * psz, K, H)
    v_ctx = v_pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
        B, P * psz, K, H)
    mask = (
        jnp.arange(P * psz, dtype=jnp.int32)[None, None, :]
        <= last_pos[:, None, None]
    )
    ref = attention_xla(
        q[:, None], k_ctx, v_ctx, causal=False, mask=mask, logit_softcap=20.0
    )[:, 0]
    out = paged_attention(
        q, k_pool, v_pool, page_table, last_pos,
        logit_softcap=20.0, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


class TestSlidingWindow:
    """Sliding-window attention (Mistral-family): flash kernel vs xla vs a
    hand-built mask, fwd + grads, across block boundaries."""

    def _ref(self, q, k, v, window, seg=None):
        # Independent reference: explicit boolean mask, not attention_mask.
        Sq, Skv = q.shape[1], k.shape[1]
        d = jnp.arange(Sq)[:, None] - jnp.arange(Skv)[None, :]
        mask = (d >= 0) & (d < window)
        if seg is not None:
            mask = mask[None] & (seg[:, :, None] == seg[:, None, :])
        return attention_xla(q, k, v, causal=False, mask=mask)

    def test_xla_window_matches_manual_mask(self):
        q, k, v = _qkv(Sq=96, Skv=96)
        out = attention_xla(q, k, v, causal=True, window=17)
        np.testing.assert_allclose(
            out, self._ref(q, k, v, 17), rtol=1e-5, atol=1e-5
        )

    @pytest.mark.parametrize("window", [8, 64, 80, 1000])
    def test_flash_window_matches_xla(self, window):
        # Window smaller / equal / larger than the 64-wide blocks: the
        # behind-the-window block skip must never drop visible columns.
        q, k, v = _qkv(Sq=192, Skv=192)
        out = flash_attention(
            q, k, v, window=window, block_q=64, block_kv=64, interpret=True
        )
        np.testing.assert_allclose(
            out, self._ref(q, k, v, window), rtol=1e-5, atol=1e-5
        )

    def test_flash_window_with_segments(self):
        q, k, v = _qkv(Sq=96, Skv=96)
        seg = jnp.asarray(
            np.repeat([[1, 2, 3]], 2, 0).repeat(32, 1), jnp.int32
        )
        out = flash_attention(
            q, k, v, window=10, q_segment_ids=seg, kv_segment_ids=seg,
            block_q=32, block_kv=32, interpret=True,
        )
        np.testing.assert_allclose(
            out, self._ref(q, k, v, 10, seg), rtol=1e-5, atol=1e-5
        )

    def test_flash_window_grads_match_xla(self):
        q, k, v = _qkv(Sq=128, Skv=128)

        def loss_flash(q, k, v):
            return flash_attention(
                q, k, v, window=24, block_q=64, block_kv=64, interpret=True
            ).sum()

        def loss_xla(q, k, v):
            return attention_xla(q, k, v, causal=True, window=24).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gx):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    def test_flash_window_explicit_positions(self):
        # Permuted layout: positions carried explicitly; window distance
        # must follow positions, not indices.
        q, k, v = _qkv(Sq=64, Skv=64)
        perm = np.asarray(np.random.default_rng(0).permutation(64))
        pos = jnp.asarray(perm, jnp.int32)
        out = flash_attention(
            q, k, v, window=9, q_positions=pos, kv_positions=pos,
            block_q=32, block_kv=32, interpret=True,
        )
        # Reference: unpermute, run index-based, re-permute.
        inv = np.argsort(perm)
        ref_sorted = self._ref(q[:, inv], k[:, inv], v[:, inv], 9)
        np.testing.assert_allclose(
            out, ref_sorted[:, perm], rtol=1e-5, atol=1e-5
        )

    def test_window_requires_causal(self):
        q, k, v = _qkv()
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, window=4, interpret=True)
        with pytest.raises(ValueError, match="causal"):
            attention_xla(q, k, v, causal=False, window=4)

    def test_model_level_sliding_window(self):
        """End-to-end: a model with sliding_window trains and differs from
        full attention exactly when context exceeds the window."""
        from orion_tpu.config import get_config
        from orion_tpu.models import forward, init_params

        cfg_full = get_config("tiny-llama").model
        cfg_win = get_config("tiny-llama", ["model.sliding_window=4"]).model
        params = init_params(cfg_full, jax.random.key(0))
        tokens = jax.random.randint(
            jax.random.key(1), (1, 16), 0, cfg_full.vocab_size
        )
        lf, _ = forward(params, tokens, cfg_full)
        lw, _ = forward(params, tokens, cfg_win)
        # First window tokens see identical context; later ones don't.
        np.testing.assert_allclose(
            np.asarray(lf[:, :4]), np.asarray(lw[:, :4]), atol=1e-5
        )
        assert not np.allclose(np.asarray(lf[:, 8:]), np.asarray(lw[:, 8:]))


@pytest.mark.parametrize("window", [5, 16, 40, 1000])
def test_paged_attention_sliding_window(window):
    """Windowed paged decode: pages behind the window are skipped (their
    DMAs clamp to the window's first page) yet the result equals the
    masked gather reference."""
    from orion_tpu.ops.attention import attention_xla
    from orion_tpu.ops.pallas.paged_attention import paged_attention

    N, K = 8, 2
    B, H, psz, P, num_pages = 3, 64, 16, 4, 32
    keys = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(keys[0], (B, N, H), jnp.float32)
    k_pool = jax.random.normal(keys[1], (num_pages, K, psz, H), jnp.float32)
    v_pool = jax.random.normal(keys[2], (num_pages, K, psz, H), jnp.float32)
    page_table = jnp.asarray(
        [[5, 17, 2, 9], [30, 1, 7, 3], [11, 4, 0, 22]], jnp.int32
    )
    last_pos = jnp.asarray([0, 37, 63], jnp.int32)

    k_ctx = k_pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
        B, P * psz, K, H)
    v_ctx = v_pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
        B, P * psz, K, H)
    pos = jnp.arange(P * psz, dtype=jnp.int32)[None, None, :]
    mask = (pos <= last_pos[:, None, None]) & (
        pos >= (last_pos - window + 1)[:, None, None]
    )
    ref = attention_xla(q[:, None], k_ctx, v_ctx, causal=False, mask=mask)[
        :, 0
    ]
    out = paged_attention(
        q, k_pool, v_pool, page_table, last_pos, window=window,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_attention_rejects_degenerate_window():
    from orion_tpu.ops.pallas.paged_attention import paged_attention

    q = jnp.zeros((1, 4, 64))
    pool = jnp.zeros((4, 2, 16, 64))
    with pytest.raises(ValueError, match="window"):
        paged_attention(
            q, pool, pool, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros(1, jnp.int32), window=0, interpret=True,
        )


# The block walk (PR 29): 20 pages of 16 in blocks of 8, so a row's pages
# fill three grid steps, the last of them partial. Contexts: inside the
# first page (blocks 1 and 2 all dead), ending on a page edge that is also a
# block edge, one token past it (the write lands on a page's FIRST row, in a
# block of its own), mid-way, and the whole table (a page's LAST row).
_WALK_LAST_POS = (5, 127, 128, 200, 319)


def _walk_case(G, K=2, key=29, dtype=jnp.float32):
    B, H, psz, P, num_pages = len(_WALK_LAST_POS), 64, 16, 20, 128
    keys = jax.random.split(jax.random.key(key), 5)
    q = jax.random.normal(keys[0], (B, K * G, H), dtype)
    k_pool = jax.random.normal(keys[1], (num_pages, K, psz, H), dtype)
    v_pool = jax.random.normal(keys[2], (num_pages, K, psz, H), dtype)
    k_new = jax.random.normal(keys[3], (B, K, H), dtype)
    v_new = jax.random.normal(keys[4], (B, K, H), dtype)
    # Distinct pages for every (row, logical page), none of them page 0.
    perm = np.random.default_rng(key).permutation(num_pages - 1) + 1
    page_table = jnp.asarray(perm[: B * P].reshape(B, P), jnp.int32)
    last_pos = jnp.asarray(_WALK_LAST_POS, jnp.int32)
    return q, k_pool, v_pool, k_new, v_new, page_table, last_pos


@pytest.mark.parametrize("window", [None, 40, 1000])
@pytest.mark.parametrize("G", [4, 6, 9])
def test_paged_block_walk_matches_gather(G, window):
    """The block walk against the gather reference over a flat 2-layer
    pool at layer_base > 0: query groups of 4, 6 and 9 heads (row bands of
    8 and 16), no window, a window that starts mid-block (40) and one that
    covers every page; and the fused write leaves every page of the pool
    other than each row's ``last_pos`` page bitwise as it was."""
    from orion_tpu.ops.pallas.paged_attention import paged_attention

    q, kp, vp, kn, vn, pt, last_pos = _walk_case(G)
    num_pages, psz = kp.shape[0], kp.shape[2]
    B = q.shape[0]
    kp2 = jnp.concatenate([kp * 0.5, kp], axis=0)
    vp2 = jnp.concatenate([vp * 0.5, vp], axis=0)
    rows = pt[jnp.arange(B), last_pos // psz]
    kp_ref = kp.at[rows, :, last_pos % psz].set(kn)
    vp_ref = vp.at[rows, :, last_pos % psz].set(vn)
    ref = _paged_reference(q, kp_ref, vp_ref, pt, last_pos, window)

    out, kp3, vp3 = jax.jit(
        lambda q, kp, vp, kn, vn: paged_attention(
            q, kp, vp, pt, last_pos, layer_base=jnp.int32(num_pages),
            k_new=kn, v_new=vn, window=window, interpret=True)
    )(q, kp2, vp2, kn, vn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # Layer 0 untouched; layer 1 is the scatter, so every page but the
    # rows' last_pos pages is bitwise the input and those differ in one row.
    assert (np.asarray(kp3[:num_pages]) == np.asarray(kp2[:num_pages])).all()
    assert (np.asarray(vp3[:num_pages]) == np.asarray(vp2[:num_pages])).all()
    assert (np.asarray(kp3[num_pages:]) == np.asarray(kp_ref)).all()
    assert (np.asarray(vp3[num_pages:]) == np.asarray(vp_ref)).all()
    touched = np.zeros(num_pages, bool)
    touched[np.asarray(rows)] = True
    assert (np.asarray(kp3[num_pages:])[~touched]
            == np.asarray(kp)[~touched]).all()

    # Read-only call (no k_new): same walk, nothing written.
    out_ro = paged_attention(
        q, kp_ref, vp_ref, pt, last_pos, window=window, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_ro), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("nb", [2, 4, 16])
def test_paged_block_walk_any_block_size(nb, monkeypatch):
    """The walk is right at every block size the sweep tries, a block
    wider than what is left of the table (16 of 20) included."""
    from orion_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "BLOCK_PAGES", nb)
    q, kp, vp, kn, vn, pt, last_pos = _walk_case(4)
    psz = kp.shape[2]
    rows = pt[jnp.arange(q.shape[0]), last_pos // psz]
    kp_ref = kp.at[rows, :, last_pos % psz].set(kn)
    vp_ref = vp.at[rows, :, last_pos % psz].set(vn)
    for window in (None, 40):
        out, kp2, vp2 = pa.paged_attention(
            q, kp, vp, pt, last_pos, k_new=kn, v_new=vn, window=window,
            interpret=True)
        ref = _paged_reference(q, kp_ref, vp_ref, pt, last_pos, window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5)
        assert (np.asarray(kp2) == np.asarray(kp_ref)).all()
        assert (np.asarray(vp2) == np.asarray(vp_ref)).all()


@pytest.mark.parametrize("window", [None, 40])
def test_paged_block_walk_int8(window):
    """int8 pools under the block walk: the per-page scale rows are
    gathered beside the pages, the quantized write lands bitwise, and
    every unwritten page and scale row is untouched."""
    from orion_tpu.infer.kv_cache import SCALE_LANES, quantize_kv
    from orion_tpu.ops.pallas.paged_attention import paged_attention

    q, kf, vf, kn, vn, pt, last_pos = _walk_case(4, key=31)
    num_pages, K, psz, H = kf.shape
    B = q.shape[0]
    pools = []
    for pool in (kf, vf):
        qv, s = quantize_kv(pool.transpose(0, 2, 1, 3))
        sc = jnp.zeros((num_pages, K, SCALE_LANES), jnp.float32
                       ).at[:, :, :psz].set(s.transpose(0, 2, 1))
        pools.append((qv.transpose(0, 2, 1, 3), sc))
    (kq, k_sc), (vq, v_sc) = pools
    knq, kns = quantize_kv(kn)
    vnq, vns = quantize_kv(vn)
    rows, off = pt[jnp.arange(B), last_pos // psz], last_pos % psz
    kq_ref = kq.at[rows, :, off].set(knq)
    vq_ref = vq.at[rows, :, off].set(vnq)
    ks_ref = k_sc.at[rows, :, off].set(kns)
    vs_ref = v_sc.at[rows, :, off].set(vns)
    ref = _paged_reference(
        q, kq_ref.astype(jnp.float32) * ks_ref[:, :, :psz][..., None],
        vq_ref.astype(jnp.float32) * vs_ref[:, :, :psz][..., None],
        pt, last_pos, window)

    out, kq2, vq2, ks2, vs2 = paged_attention(
        q, kq, vq, pt, last_pos, k_new=kn, v_new=vn,
        k_scale=k_sc, v_scale=v_sc, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for got, want in ((kq2, kq_ref), (vq2, vq_ref), (ks2, ks_ref),
                      (vs2, vs_ref)):
        assert (np.asarray(got) == np.asarray(want)).all()


def test_paged_block_walk_bf16_operands():
    """A bf16 pool is multiplied as bf16 (the serving cells' precision):
    the result is within bf16's rounding of the f32 reference over the
    same bf16 values, and the written rows are the new token's bits."""
    from orion_tpu.ops.pallas.paged_attention import paged_attention

    q, kp, vp, kn, vn, pt, last_pos = _walk_case(4, dtype=jnp.bfloat16)
    psz = kp.shape[2]
    rows = pt[jnp.arange(q.shape[0]), last_pos // psz]
    kp_ref = kp.at[rows, :, last_pos % psz].set(kn)
    vp_ref = vp.at[rows, :, last_pos % psz].set(vn)
    out, kp2, vp2 = paged_attention(
        q, kp, vp, pt, last_pos, k_new=kn, v_new=vn, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _paged_reference(
        q.astype(jnp.float32), kp_ref.astype(jnp.float32),
        vp_ref.astype(jnp.float32), pt, last_pos, None)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2)
    assert (np.asarray(kp2) == np.asarray(kp_ref)).all()
    assert (np.asarray(vp2) == np.asarray(vp_ref)).all()


# -- multi-query ragged paged attention (speculative verification) ----------


def _ragged_reference(q, k_pool, v_pool, page_table, start, lens,
                      k_new=None, v_new=None, window=None, softcap=None):
    """The XLA branch of runner._paged_layer: scatter all real tokens
    (padding tokens park on a dummy extra row — the engine's scratch page
    stand-in, since these tests use page 0 as a real page), gather the
    padded context, mask per query (own position + earlier same-dispatch
    drafts; optional sliding window)."""
    from orion_tpu.ops.attention import attention_xla

    B, W, N, H = q.shape
    K, psz = k_pool.shape[1], k_pool.shape[2]
    P = page_table.shape[1]
    npg = k_pool.shape[0]
    steps = jnp.arange(W, dtype=jnp.int32)[None, :]
    q_pos = start[:, None] + steps                         # [B, W]
    if k_new is not None:
        valid = steps < lens[:, None]
        k_pool = jnp.concatenate(
            [k_pool, jnp.zeros((1,) + k_pool.shape[1:], k_pool.dtype)])
        v_pool = jnp.concatenate(
            [v_pool, jnp.zeros((1,) + v_pool.shape[1:], v_pool.dtype)])
        rows = jnp.where(
            valid, page_table[jnp.arange(B)[:, None], q_pos // psz], npg
        )
        off = q_pos % psz
        k_pool = k_pool.at[rows, :, off].set(k_new)[:npg]
        v_pool = v_pool.at[rows, :, off].set(v_new)[:npg]
    k_ctx = k_pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
        B, P * psz, K, H)
    v_ctx = v_pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
        B, P * psz, K, H)
    kv = jnp.arange(P * psz, dtype=jnp.int32)[None, None, :]
    mask = kv <= q_pos[:, :, None]
    if window is not None:
        mask &= kv >= (q_pos - window + 1)[:, :, None]
    out = attention_xla(
        q, k_ctx, v_ctx, causal=False, mask=mask, logit_softcap=softcap
    )
    return out, k_pool, v_pool


def _ragged_case(key=2, W=5, N=8, K=2):
    B, H, psz, num_pages = 3, 64, 16, 32
    ks = jax.random.split(jax.random.key(key), 6)
    q = jax.random.normal(ks[0], (B, W, N, H), jnp.float32)
    k_pool = jax.random.normal(ks[1], (num_pages, K, psz, H), jnp.float32)
    v_pool = jax.random.normal(ks[2], (num_pages, K, psz, H), jnp.float32)
    k_new = jax.random.normal(ks[3], (B, W, K, H), jnp.float32)
    v_new = jax.random.normal(ks[4], (B, W, K, H), jnp.float32)
    page_table = jnp.asarray(
        [[5, 17, 2, 9], [30, 1, 7, 3], [11, 4, 0, 22]], jnp.int32
    )
    # Full-width from zero / single mid-page / ragged near the table end.
    start = jnp.asarray([0, 13, 59], jnp.int32)
    lens = jnp.asarray([W, 1, 3], jnp.int32)
    return q, k_pool, v_pool, k_new, v_new, page_table, start, lens


def _assert_real_rows_close(got, want, lens, atol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    for b in range(len(lens)):
        w = int(lens[b])
        np.testing.assert_allclose(got[b, :w], want[b, :w], atol=atol)


@pytest.mark.parametrize("gqa", [(8, 8), (8, 2), (4, 1)])
def test_ragged_paged_attention_matches_gather(gqa):
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    N, K = gqa
    q, kp, vp, _, _, pt, start, lens = _ragged_case(N=N, K=K)
    ref, _, _ = _ragged_reference(q, kp, vp, pt, start, lens)
    out = ragged_paged_attention(q, kp, vp, pt, start, lens, interpret=True)
    _assert_real_rows_close(out, ref, lens)


def test_ragged_paged_attention_fused_write():
    """In-kernel multi-token KV write == external scatter + attention:
    outputs match and the written pools are BITWISE equal (padding tokens
    and clamped tail revisits leave every unwritten position untouched).
    The causal structure among the W new positions rides the same check:
    each query's reference context includes the earlier drafts of its own
    dispatch."""
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    q, kp, vp, kn, vn, pt, start, lens = _ragged_case()
    ref, kpr, vpr = _ragged_reference(q, kp, vp, pt, start, lens, kn, vn)
    out, kp2, vp2 = ragged_paged_attention(
        q, kp, vp, pt, start, lens, k_new=kn, v_new=vn, interpret=True
    )
    _assert_real_rows_close(out, ref, lens)
    assert (np.asarray(kp2) == np.asarray(kpr)).all()
    assert (np.asarray(vp2) == np.asarray(vpr)).all()

    # Page-boundary straddle: rows whose W tokens span two pages (the
    # merge must select per-token target pages, and the tail clamp must
    # re-apply the LAST page's merge on revisits).
    start2 = jnp.asarray([14, 30, 46], jnp.int32)
    lens2 = jnp.asarray([5, 4, 2], jnp.int32)
    ref2, kpr2, vpr2 = _ragged_reference(
        q, kp, vp, pt, start2, lens2, kn, vn)
    out2, kp3, vp3 = ragged_paged_attention(
        q, kp, vp, pt, start2, lens2, k_new=kn, v_new=vn, interpret=True
    )
    _assert_real_rows_close(out2, ref2, lens2)
    assert (np.asarray(kp3) == np.asarray(kpr2)).all()
    assert (np.asarray(vp3) == np.asarray(vpr2)).all()


def test_ragged_paged_attention_int8_bitwise():
    """int8 pools: the in-kernel quantized write of all W drafts must be
    BITWISE the host-side common.quantize_kv (values and per-(token,
    kv-head) scales) — the property that keeps speculative acceptance
    numerics identical to sequential decode under kv_quant — and the
    attention must match the dequantized-pool reference."""
    from orion_tpu.infer.kv_cache import SCALE_LANES, quantize_kv
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    q, kf, vf, kn, vn, pt, start, lens = _ragged_case(key=11)
    num_pages, K, psz, H = kf.shape
    kq, ks = quantize_kv(kf.transpose(0, 2, 1, 3))
    vq, vs = quantize_kv(vf.transpose(0, 2, 1, 3))
    kq, vq = kq.transpose(0, 2, 1, 3), vq.transpose(0, 2, 1, 3)
    k_sc = jnp.zeros((num_pages, K, SCALE_LANES), jnp.float32
                     ).at[:, :, :psz].set(ks.transpose(0, 2, 1))
    v_sc = jnp.zeros((num_pages, K, SCALE_LANES), jnp.float32
                     ).at[:, :, :psz].set(vs.transpose(0, 2, 1))

    out, kp2, vp2, ks2, vs2 = ragged_paged_attention(
        q, kq, vq, pt, start, lens, k_new=kn, v_new=vn,
        k_scale=k_sc, v_scale=v_sc, interpret=True,
    )
    knq, kns = quantize_kv(kn)            # [B,W,K,H] i8, [B,W,K]
    vnq, vns = quantize_kv(vn)
    B = q.shape[0]
    written = set()
    for b in range(B):
        for j in range(int(lens[b])):
            p = int(start[b]) + j
            r, o = int(pt[b, p // psz]), p % psz
            written.add((r, o))
            assert (np.asarray(kp2[r, :, o]) == np.asarray(knq[b, j])).all()
            assert (np.asarray(vp2[r, :, o]) == np.asarray(vnq[b, j])).all()
            assert (np.asarray(ks2[r, :, o]) == np.asarray(kns[b, j])).all()
            assert (np.asarray(vs2[r, :, o]) == np.asarray(vns[b, j])).all()
    # Every unwritten pool/scale position is untouched.
    kp2n, kqn = np.asarray(kp2), np.asarray(kq)
    ks2n, kscn = np.asarray(ks2), np.asarray(k_sc)
    for r in range(num_pages):
        for o in range(psz):
            if (r, o) not in written:
                assert (kp2n[r, :, o] == kqn[r, :, o]).all()
                assert (ks2n[r, :, o] == kscn[r, :, o]).all()

    # Attention vs the explicitly dequantized reference.
    kd = kq.astype(jnp.float32) * k_sc[:, :, :psz][..., None]
    vd = vq.astype(jnp.float32) * v_sc[:, :, :psz][..., None]
    ref, _, _ = _ragged_reference(
        q, kd, vd, pt, start, lens,
        knq.astype(jnp.float32) * kns[..., None],
        vnq.astype(jnp.float32) * vns[..., None],
    )
    _assert_real_rows_close(out, ref, lens)


@pytest.mark.parametrize("window", [5, 20, 1000])
def test_ragged_paged_attention_sliding_window(window):
    """Per-query sliding windows over the W new positions: pages behind
    the EARLIEST query's window skip (clamped DMAs); later queries'
    tighter windows ride the mask."""
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    q, kp, vp, kn, vn, pt, start, lens = _ragged_case(key=7)
    ref, _, _ = _ragged_reference(
        q, kp, vp, pt, start, lens, kn, vn, window=window)
    out, _, _ = ragged_paged_attention(
        q, kp, vp, pt, start, lens, k_new=kn, v_new=vn, window=window,
        interpret=True,
    )
    _assert_real_rows_close(out, ref, lens)


def test_ragged_paged_attention_softcap():
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    q, kp, vp, kn, vn, pt, start, lens = _ragged_case(key=5)
    q = q * 4                    # push logits into the tanh's curved region
    ref, _, _ = _ragged_reference(
        q, kp, vp, pt, start, lens, kn, vn, softcap=20.0)
    out, _, _ = ragged_paged_attention(
        q, kp, vp, pt, start, lens, k_new=kn, v_new=vn,
        logit_softcap=20.0, interpret=True,
    )
    _assert_real_rows_close(out, ref, lens)


def test_ragged_w1_matches_paged_kernel_bitwise():
    """W=1 degenerates to the single-query fused-write kernel BITWISE
    (output and written pools): the ragged kernel really is the same
    kernel generalized, so spec-on pallas serving reproduces the W=1
    pallas decode stream exactly."""
    from orion_tpu.ops.pallas.paged_attention import paged_attention
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    q, kp, vp, kn, vn, pt, start, _ = _ragged_case()
    l1 = jnp.ones(q.shape[0], jnp.int32)
    oA, kpA, vpA = ragged_paged_attention(
        q[:, :1], kp, vp, pt, start, l1,
        k_new=kn[:, :1], v_new=vn[:, :1], interpret=True,
    )
    oB, kpB, vpB = paged_attention(
        q[:, 0], kp, vp, pt, start, k_new=kn[:, 0], v_new=vn[:, 0],
        interpret=True,
    )
    assert (np.asarray(oA[:, 0]) == np.asarray(oB)).all()
    assert (np.asarray(kpA) == np.asarray(kpB)).all()
    assert (np.asarray(vpA) == np.asarray(vpB)).all()


def test_ragged_paged_attention_layer_base():
    """Traced layer_base over a flat 2-layer pool (the layer-scan calling
    convention): reads and fused writes both land in layer 1's rows."""
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    q, kp, vp, kn, vn, pt, start, lens = _ragged_case()
    num_pages = kp.shape[0]
    kp2 = jnp.concatenate([kp, kp * 0.5], axis=0)
    vp2 = jnp.concatenate([vp, vp * 0.5], axis=0)
    ref, kpr, vpr = _ragged_reference(
        q, kp * 0.5, vp * 0.5, pt, start, lens, kn, vn)
    out, kp3, vp3 = jax.jit(
        lambda q, kp, vp, kn, vn: ragged_paged_attention(
            q, kp, vp, pt, start, lens,
            layer_base=jnp.int32(num_pages), k_new=kn, v_new=vn,
            interpret=True,
        )
    )(q, kp2, vp2, kn, vn)
    _assert_real_rows_close(out, ref, lens)
    # Layer 0's rows untouched; layer 1's equal the reference scatter.
    assert (np.asarray(kp3[:num_pages]) == np.asarray(kp)).all()
    assert (np.asarray(kp3[num_pages:]) == np.asarray(kpr)).all()
    assert (np.asarray(vp3[num_pages:]) == np.asarray(vpr)).all()


def test_ragged_verify_fit_check():
    """The VMEM fit estimate rejects hopeless verify widths with an error
    naming the config knob, and passes the serving-scale shapes the
    kernel is built for."""
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        check_verify_fit,
        verify_vmem_bytes,
    )

    shape = dict(n_heads=32, n_kv_heads=8, head_dim=128, page_size=64)
    check_verify_fit(7, kv_quant=None, dtype_itemsize=2, **shape)
    check_verify_fit(7, kv_quant="int8", **shape)
    with pytest.raises(ValueError, match="speculate_tokens"):
        check_verify_fit(512, kv_quant=None, dtype_itemsize=2, **shape)
    # The estimate grows with W (the q/out/new-token blocks scale).
    small = verify_vmem_bytes(
        2, kv_itemsize=2, quant=False, **shape)
    big = verify_vmem_bytes(
        64, kv_itemsize=2, quant=False, **shape)
    assert big > small


def test_ragged_paged_attention_rejects_degenerate_window():
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    q = jnp.zeros((1, 2, 4, 64))
    pool = jnp.zeros((4, 2, 16, 64))
    with pytest.raises(ValueError, match="window"):
        ragged_paged_attention(
            q, pool, pool, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros(1, jnp.int32), jnp.ones(1, jnp.int32),
            window=0, interpret=True,
        )


# -- token-tree ancestor masks (tree speculation, ISSUE 11) ------------------


def _chain_tree_arrays(B, W):
    """Chain-shaped [B, W] depth / packed-ancestor-word arrays — the
    degenerate tree whose mask must be bitwise the positional mask."""
    steps = np.arange(W, dtype=np.int64)
    depths = np.tile(steps.astype(np.int32), (B, 1))
    words = np.tile(
        ((np.int64(1) << (steps + 1)) - 1).astype(np.int32), (B, 1)
    )
    return jnp.asarray(depths), jnp.asarray(words)


def _tree_arrays(B, W, parents):
    """[B, W] depth/word arrays for one tree shape shared by all rows.
    ``parents`` is the parent COLUMN per node column 1..n (DraftTree
    layout); columns past the tree stay chain-shaped padding."""
    from orion_tpu.infer.spec_decode import DraftTree

    t = DraftTree(tokens=[0] * len(parents), parents=list(parents))
    depths, words = _chain_tree_arrays(B, W)
    n = len(parents) + 1
    depths = depths.at[:, :n].set(jnp.asarray(t.depths(), jnp.int32))
    words = words.at[:, :n].set(jnp.asarray(t.mask_words(), jnp.int32))
    return depths, words


def _tree_reference(q, k_pool, v_pool, page_table, start, lens,
                    k_new, v_new, depths, words, window=None):
    """The verify body's xla semantics under an ancestor mask: writes
    stay slot-sequential (identical to _ragged_reference's scatter), the
    committed context is visible to every query, and among the W new
    slots query c sees slot i iff bit i of its word is set (or i == c);
    sliding windows measure DEPTH distance among the new slots."""
    from orion_tpu.ops.attention import attention_xla

    B, W, N, H = q.shape
    K, psz = k_pool.shape[1], k_pool.shape[2]
    P = page_table.shape[1]
    npg = k_pool.shape[0]
    steps = jnp.arange(W, dtype=jnp.int32)[None, :]
    wpos = start[:, None] + steps                          # write slots
    valid = steps < lens[:, None]
    kp = jnp.concatenate(
        [k_pool, jnp.zeros((1,) + k_pool.shape[1:], k_pool.dtype)])
    vp = jnp.concatenate(
        [v_pool, jnp.zeros((1,) + v_pool.shape[1:], v_pool.dtype)])
    rows = jnp.where(
        valid, page_table[jnp.arange(B)[:, None], wpos // psz], npg
    )
    off = wpos % psz
    kp = kp.at[rows, :, off].set(k_new)[:npg]
    vp = vp.at[rows, :, off].set(v_new)[:npg]
    k_ctx = kp[page_table].transpose(0, 1, 3, 2, 4).reshape(B, P * psz, K, H)
    v_ctx = vp[page_table].transpose(0, 1, 3, 2, 4).reshape(B, P * psz, K, H)
    kv = jnp.arange(P * psz, dtype=jnp.int32)[None, None, :]
    slot = kv - start[:, None, None]                       # [B, 1, P*psz]
    in_new = (slot >= 0) & (slot < W)
    slot_c = jnp.clip(slot, 0, W - 1)
    anc = ((words[:, :, None] >> steps[None, :, :]) & 1).astype(bool)
    anc = anc | jnp.eye(W, dtype=bool)[None]
    vis = jnp.take_along_axis(
        anc, jnp.broadcast_to(slot_c, (B, W, P * psz)), axis=2
    )
    mask = jnp.where(in_new, vis, kv < start[:, None, None])
    if window is not None:
        sdep = jnp.take_along_axis(
            jnp.broadcast_to(depths[:, None, :], (B, 1, W)), slot_c, axis=2
        )
        qdep = depths[:, :, None]
        mask &= jnp.where(
            in_new, sdep >= qdep - window + 1,
            kv >= start[:, None, None] + qdep - window + 1,
        )
    out = attention_xla(q, k_ctx, v_ctx, causal=False, mask=mask)
    return out, kp, vp


def test_ragged_tree_chain_degenerate_bitwise():
    """Chain-shaped tree words/depths produce BITWISE the plain kernel's
    outputs and written pools — the degenerate tree IS today's W-query
    verify (tree machinery adds ops, not numerics)."""
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    q, kp, vp, kn, vn, pt, start, lens = _ragged_case()
    B, W = q.shape[0], q.shape[1]
    depths, words = _chain_tree_arrays(B, W)
    for win in (None, 20):
        plain = ragged_paged_attention(
            q, kp, vp, pt, start, lens, k_new=kn, v_new=vn, window=win,
            interpret=True,
        )
        tree = ragged_paged_attention(
            q, kp, vp, pt, start, lens, k_new=kn, v_new=vn, window=win,
            tree_mask=words, depths=depths, interpret=True,
        )
        for a, b in zip(plain, tree):
            assert (np.asarray(a) == np.asarray(b)).all(), win


def test_ragged_tree_branchy_matches_reference():
    """A branchy ancestor mask (two sibling branches off the root, one
    nested branch) against the scatter + ancestor-masked-gather
    reference: sibling slots must NOT see each other, nested nodes see
    exactly their path, and the fused write stays slot-sequential."""
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    q, kp, vp, kn, vn, pt, start, lens = _ragged_case(key=9)
    B, W = q.shape[0], q.shape[1]
    # Columns: 1<-0, 2<-1 (primary chain), 3<-0 (sibling), 4<-3 (nested).
    depths, words = _tree_arrays(B, W, parents=[0, 1, 0, 3])
    lens = jnp.asarray([W, 1, 3], jnp.int32)
    ref, kpr, vpr = _tree_reference(
        q, kp, vp, pt, start, lens, kn, vn, depths, words)
    out, kp2, vp2 = ragged_paged_attention(
        q, kp, vp, pt, start, lens, k_new=kn, v_new=vn,
        tree_mask=words, depths=depths, interpret=True,
    )
    _assert_real_rows_close(out, ref, lens)
    assert (np.asarray(kp2) == np.asarray(kpr)).all()
    assert (np.asarray(vp2) == np.asarray(vpr)).all()

    # Sliding window over the tree: depth distance, not slot distance.
    ref_w, _, _ = _tree_reference(
        q, kp, vp, pt, start, lens, kn, vn, depths, words, window=2)
    out_w, _, _ = ragged_paged_attention(
        q, kp, vp, pt, start, lens, k_new=kn, v_new=vn,
        tree_mask=words, depths=depths, window=2, interpret=True,
    )
    _assert_real_rows_close(out_w, ref_w, lens)


def test_ragged_tree_width_limit():
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    q = jnp.zeros((1, 32, 4, 64))
    pool = jnp.zeros((8, 2, 16, 64))
    with pytest.raises(ValueError, match="31"):
        ragged_paged_attention(
            q, pool, pool, jnp.zeros((1, 32), jnp.int32),
            jnp.zeros(1, jnp.int32), jnp.ones(1, jnp.int32),
            tree_mask=jnp.zeros((1, 32), jnp.int32),
            depths=jnp.zeros((1, 32), jnp.int32), interpret=True,
        )


@pytest.mark.parametrize(
    "layout", ["one_device", "tp_n", "tp_contract", "layer_stack"])
def test_grouped_matmul_pallas_matches_ragged_dot(layout):
    """The megablox kernel behind ``ops.grouped_matmul`` (interpret mode)
    against ``lax.ragged_dot``, values and gradients: an empty group, rows
    past the last group (unspecified, so not compared), a row count that is
    no whole number of m-tiles, under a mesh the kernel per ``tp`` shard
    with the weights' n axis or the contraction axis split, and the weights
    read out of a layer stack at a traced layer index."""
    from orion_tpu.ops.grouped_matmul import grouped_matmul
    from tests.conftest import make_mesh

    m, k, n = 300, 64, 96
    sizes = jnp.asarray([120, 0, 37, 93], jnp.int32)       # 250 of 300 rows
    lhs, rhs = _rand(0, m, k), _rand(1, 4, k, n) * 0.2
    live = (jnp.arange(m) < int(sizes.sum()))[:, None]
    mesh = None
    if layout in ("tp_n", "tp_contract"):
        mesh = make_mesh(jax.devices("cpu")[:8], dp=4, tp=2)
    # Layer 1 of a stack of 3, its index an argument of the jit (traced, as
    # a layer scan's is); None without a stack.
    layer = jnp.int32(1) if layout == "layer_stack" else None

    def run(impl, a, w, layer):
        if layer is not None:
            w = jnp.stack([w + 1, w, w - 1])
        out = grouped_matmul(a, w, sizes, impl=impl, mesh=mesh, layer=layer,
                             contract_tp=layout == "tp_contract")
        return jnp.where(live, out, 0)

    def loss(impl, a, w, layer):
        return jnp.sum(run(impl, a, w, layer) * jnp.cos(jnp.arange(n)))

    ref = run("xla", lhs, rhs, layer)
    out = jax.jit(partial(run, "pallas_interpret"))(lhs, rhs, layer)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    g_ref = jax.grad(partial(loss, "xla"), argnums=(0, 1))(lhs, rhs, layer)
    g_out = jax.jit(jax.grad(partial(loss, "pallas_interpret"),
                             argnums=(0, 1)))(lhs, rhs, layer)
    np.testing.assert_allclose(
        jnp.where(live, g_out[0], 0), g_ref[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g_out[1], g_ref[1], rtol=1e-5, atol=1e-5)


# -- flash attention: the live-block grid (PR 32) -----------------------------
# Every case runs forward and all three gradients through the interpreter
# against attention_xla. Blocks are small so that windows, offsets and kv
# padding cross several of them.

def _fa():
    import importlib

    return importlib.import_module("orion_tpu.ops.pallas.flash_attention")


def _halves(B, S):
    return jnp.concatenate(
        [jnp.zeros((B, S // 2), jnp.int32), jnp.ones((B, S - S // 2), jnp.int32)],
        axis=1)


_LIVE_CASES = {
    # window against 64-wide blocks: smaller, equal to k blocks, no multiple
    "window<block": dict(S=(256, 256), kw=dict(window=24)),
    "window=2blocks": dict(S=(256, 256), kw=dict(window=128)),
    "window=block": dict(S=(256, 256), kw=dict(window=64)),
    "window!%block": dict(S=(256, 256), kw=dict(window=100)),
    "window>seq": dict(S=(192, 192), kw=dict(window=1000)),
    "unequal-blocks": dict(S=(256, 256), kw=dict(window=90), blocks=(128, 32)),
    "unequal-blocks-T": dict(S=(256, 256), kw=dict(window=90), blocks=(32, 128)),
    # a tail of queries over prefix + tail
    "q_offset": dict(S=(64, 192), kw=dict(q_offset=128)),
    "q_offset+window": dict(S=(64, 192), kw=dict(q_offset=128, window=70)),
    "kv-padding": dict(S=(40, 100), kw=dict(q_offset=60), blocks=(32, 32)),
    "kv-padding+window": dict(S=(40, 100), kw=dict(q_offset=60, window=33),
                              blocks=(32, 32)),
    "q-padding": dict(S=(100, 100), kw=dict(window=50)),
    "non-causal": dict(S=(128, 100), kw=dict(causal=False)),
    "softcap+window": dict(S=(192, 192), kw=dict(window=70, logit_softcap=5.0)),
    "gqa4": dict(S=(192, 192), kw=dict(window=70), heads=(8, 2)),
    "gqa6": dict(S=(192, 192), kw=dict(window=70), heads=(12, 2)),
    "gqa9": dict(S=(192, 192), kw=dict(window=70), heads=(18, 2)),
    # segment ids: 0 is a real id unless the caller says otherwise
    "segments-0-real": dict(S=(256, 256), kw=dict(window=100), seg="halves",
                            blocks=(128, 128)),
    "segments-pad0": dict(S=(256, 256), kw=dict(window=100), seg="burst",
                          blocks=(128, 128)),
}


@pytest.mark.parametrize("case", sorted(_LIVE_CASES))
def test_flash_live_blocks_fwd_and_grads(case):
    c = _LIVE_CASES[case]
    (Sq, Skv), kw = c["S"], dict(c["kw"])
    N, K = c.get("heads", (4, 2))
    bq, bk = c.get("blocks", (64, 64))
    B = 2
    q, k, v = _qkv(B=B, Sq=Sq, Skv=Skv, N=N, K=K, H=32)
    fkw, rows = {}, np.ones((B, Sq), bool)
    if c.get("seg") == "halves":
        kw.update(q_segment_ids=_halves(B, Sq), kv_segment_ids=_halves(B, Skv))
    elif c.get("seg") == "burst":
        # pack_rows / prefill convention: id 0 is padding (rows are garbage)
        seg = (jnp.arange(Sq)[None, :] < jnp.asarray([[Sq], [70]])).astype(
            jnp.int32)
        kw.update(q_segment_ids=seg, kv_segment_ids=seg)
        fkw["seg_pad_zero"] = True
        rows = np.asarray(seg, bool)
    w = jnp.asarray(rows, jnp.float32)[:, :, None, None]

    def loss(fn, extra):
        def f(q, k, v):
            o = fn(q, k, v, **kw, **extra)
            return jnp.sum((o * w) ** 2), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, o_p), g_p = loss(flash_attention, dict(
        block_q=bq, block_kv=bk, interpret=True, **fkw))(q, k, v)
    (_, o_x), g_x = loss(attention_xla, {})(q, k, v)
    np.testing.assert_allclose(
        np.asarray(o_p)[rows], np.asarray(o_x)[rows], rtol=1e-5, atol=1e-5)
    for a, b, name in zip(g_p, g_x, "qkv"):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name}")


def test_flash_segment_zero_is_a_real_id_without_the_flag():
    """Rows of segment 0 attend each other (0 == 0) unless the caller
    declares 0 padding: the skip is opt-in."""
    q, k, v = _qkv(Sq=128, Skv=128)
    seg = _halves(2, 128)
    out = flash_attention(q, k, v, q_segment_ids=seg, kv_segment_ids=seg,
                          block_q=64, block_kv=64, interpret=True)
    alone = attention_xla(q[:, :64], k[:, :64], v[:, :64])
    np.testing.assert_allclose(out[:, :64], alone, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, 40])
def test_flash_with_lse_positions_window_and_lse_cotangent(window):
    """The ring's case: explicit positions (a kv block that starts before
    the q block, and one wholly ahead of it), a window on true distance, and
    a loss that reads the lse."""
    from orion_tpu.ops.pallas.flash_attention import flash_attention_with_lse

    B, S, N, K, H = 2, 128, 4, 2, 32
    q, k, v = _qkv(B=B, Sq=S, Skv=S, N=N, K=K, H=H)
    qpos = jnp.arange(S, dtype=jnp.int32) + 64
    kpos = jnp.arange(S, dtype=jnp.int32) + 32    # kv 32..159, q 64..191

    def ref(q, k, v):
        logits = jnp.einsum("bqnh,bknh->bnqk", q, jnp.repeat(k, N // K, 2),
                            ) * H ** -0.5
        d = qpos[:, None] - kpos[None, :]
        m = d >= 0
        if window is not None:
            m &= d < window
        logits = jnp.where(m[None, None], logits, -jnp.inf)
        lse = jax.nn.logsumexp(logits, axis=-1)
        p = jnp.exp(logits - lse[..., None])
        return jnp.einsum("bnqk,bknh->bqnh", p, jnp.repeat(v, N // K, 2)), lse

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse)), (o, lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, (o_p, l_p)), g_p = loss(lambda q, k, v: flash_attention_with_lse(
        q, k, v, q_positions=qpos, kv_positions=kpos, window=window,
        block_q=128, block_kv=128, interpret=True))(q, k, v)
    (_, (o_x, l_x)), g_x = loss(ref)(q, k, v)
    np.testing.assert_allclose(o_p, o_x, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(l_p, l_x, rtol=1e-5, atol=1e-5)
    for a, b in zip(g_p, g_x):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("mode", ["index", "positions"])
def test_flash_fully_masked_rows_keep_lse_and_zero_grads(mode):
    """Rows that attend nothing (queries past every key's window; a ring
    step whose kv block lies wholly ahead): out 0, lse -inf, gradients
    finite and zero on those rows, including whole q blocks that no grid
    step visits."""
    from orion_tpu.ops.pallas.flash_attention import flash_attention_with_lse

    q, k, v = _qkv(Sq=128, Skv=32)
    if mode == "index":
        kw = dict(window=16)                    # rows >= 47 see nothing
        dead = np.arange(128) >= 47
    else:
        kw = dict(q_positions=jnp.arange(128, dtype=jnp.int32),
                  kv_positions=jnp.arange(32, dtype=jnp.int32) + 64)
        dead = np.arange(128) < 64

    def f(q, k, v):
        o, lse = flash_attention_with_lse(
            q, k, v, block_q=32, block_kv=32, interpret=True, **kw)
        fin = jnp.where(jnp.isfinite(lse), lse, 0.0)
        return jnp.sum(o ** 2) + jnp.sum(fin), (o, lse)

    (_, (o, lse)), (dq, dk, dv) = jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert bool(jnp.all(jnp.isneginf(lse[:, :, dead])))
    assert bool(jnp.all(jnp.isfinite(lse[:, :, ~dead])))
    assert not np.asarray(o)[:, dead].any()
    assert not np.asarray(dq)[:, dead].any()
    for g in (dq, dk, dv):
        assert bool(jnp.all(jnp.isfinite(g)))
    assert np.asarray(dk).any() and np.asarray(dv).any()


def _kernel_dots(fn, *args):
    """(operand dtypes, result dtype) of every matmul inside the Pallas
    kernels of ``fn``'s program."""
    found = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if inside and eqn.primitive.name == "dot_general":
                found.append((eqn.invars[0].aval.dtype, eqn.invars[1].aval.dtype,
                              eqn.outvars[0].aval.dtype))
            kernel = eqn.primitive.name == "pallas_call"
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (tuple, list)) else [val]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, inside or kernel)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return found


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_multiplies_on_the_inputs_own_dtype(dtype):
    """q, k, v, dO and the probabilities reach the MXU in the inputs' dtype
    with float32 accumulation: bf16 inputs are not upcast, f32 inputs give
    f32 products."""
    dt = jnp.dtype(dtype)
    q, k, v = _qkv(Sq=128, Skv=128, dtype=dt)

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(
            *a, window=40, block_q=64, block_kv=64, interpret=True,
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    dots = _kernel_dots(grads, q, k, v)
    assert len(dots) == 2 + 3 + 4           # forward, dq, dk/dv
    for a, b, out in dots:
        assert (a, b, out) == (dt, dt, jnp.float32), dots


_GRID_TABLE = [
    # Sq, Skv, bq, bk, q_offset, window
    (8192, 8192, 1024, 1024, 0, 4096),
    (8192, 8192, 512, 512, 0, 4096),
    (4096, 4096, 512, 512, 0, 512),
    (4096, 4096, 1024, 1024, 0, 512),
    (4096, 4096, 1024, 1024, 0, None),
    (256, 256, 64, 64, 0, 24),
    (256, 256, 64, 64, 0, 64),
    (256, 256, 64, 64, 0, 65),
    (256, 256, 128, 32, 0, 90),
    (256, 256, 32, 128, 0, 90),
    (64, 192, 64, 64, 128, 70),
    (40, 100, 32, 32, 60, 33),
    (100, 100, 64, 64, 0, 50),
    (70, 128, 64, 32, 0, None),         # keys past the last real query
    (128, 32, 32, 32, 0, 16),           # queries past every key's window
    (8, 72, 8, 64, 64, None),
]


@pytest.mark.parametrize("shape", _GRID_TABLE, ids=lambda s: "-".join(map(str, s)))
def test_flash_visits_exactly_the_live_blocks(shape):
    """In index mode the grid visits a block if and only if the dense mask
    has a True in it: no live block missed, no dead block visited, from the
    forward's side (kv range of a q row) and from dk/dv's (q range of a kv
    block); the grid's inner axis is the longest range."""
    fa = _fa()
    Sq, Skv, bq, bk, off, window = shape
    nq, nk = -(-Sq // bq), -(-Skv // bk)
    st = fa._Statics(causal=True, logit_softcap=None, q_offset=off, seq_q=Sq,
                     seq_kv=Skv, block_q=bq, block_kv=bk, interpret=True,
                     window=window)
    d = (np.arange(Sq)[:, None] + off) - np.arange(Skv)[None, :]
    dense = (d >= 0) if window is None else (d >= 0) & (d < window)
    pad = np.zeros((nq * bq, nk * bk), bool)
    pad[:Sq, :Skv] = dense
    live = pad.reshape(nq, bq, nk, bk).any(axis=(1, 3))
    pad[Sq:, :] = True      # padded q rows are sliced off: masked or not
    whole = pad.reshape(nq, bq, nk, bk).all(axis=(1, 3))

    seen = fa.visited_blocks(st, nq, nk)
    np.testing.assert_array_equal(seen, live)
    lo, cnt = fa._q_range(st, np.arange(nk), nq, np)
    iq = np.arange(nq)[:, None]
    np.testing.assert_array_equal((iq >= lo[None]) & (iq < (lo + cnt)[None]),
                                  live)
    counts = fa.block_counts(st, nq, nk)
    assert counts["visited"] == live.sum() and counts["full"] == nq * nk
    assert counts["steps"] == nq * max(live.sum(axis=1).max(), 1)
    # a block that skips its mask step must be wholly True in the dense mask
    skip = np.asarray(fa._unmasked(st, iq, np.arange(nk)[None, :])) & seen
    assert not (skip & ~whole).any()
    assert counts["unmasked"] == skip.sum()
    if Sq % bq == 0:
        assert skip.sum() == (whole & live).sum()


# -- the grouped matmul and the tiles it is lowered with ----------------------

# (m, group sizes of G = 4; rows behind their sum belong to no group)
_GMM_CASES = {
    "even": (512, [128, 128, 128, 128]),
    "empty_groups": (512, [0, 300, 0, 212]),
    "a_group_over_a_tile_edge": (512, [100, 60, 250, 102]),
    "rows_behind_the_last_group": (512, [70, 0, 90, 33]),
    "m_no_multiple_of_the_tile": (328, [40, 200, 8, 80]),
}
# What ``_tiles`` can return, at toy widths (k, n) = (192, 320): the whole
# matrix as one weight tile under either row tile, n-tiles that do not divide
# n (768 under 512), a contraction cut in k-tiles; "rule" is the rule itself.
_GMM_TILES = {
    "rule": None,
    "one_tile_128": (128, 192, 320), "one_tile_256": (256, 192, 320),
    "n_tiles": (256, 192, 128), "k_tiles": (256, 64, 256),
}


@pytest.mark.parametrize("stacked", [False, True], ids=["flat", "layer_stack"])
@pytest.mark.parametrize("case", list(_GMM_CASES))
@pytest.mark.parametrize("tiles", list(_GMM_TILES))
def test_grouped_matmul_against_ragged_dot(tiles, case, stacked, monkeypatch):
    """The megablox kernel (interpreted) under every form of tiling against
    ``lax.ragged_dot`` on the rows that belong to a group: a row's result
    does not depend on which rows share its tile. ``layer_stack`` reads one
    layer's matrices out of a stack of three in place."""
    from orion_tpu.ops import grouped_matmul as gm

    if _GMM_TILES[tiles]:
        monkeypatch.setattr(gm, "_tiles", lambda *a, **kw: _GMM_TILES[tiles])
    m, sizes = _GMM_CASES[case]
    k, n, G = 192, 320, len(sizes)
    lhs = _rand(1, m, k)
    rhs = _rand(2, 3, G, k, n)
    gs = jnp.asarray(sizes, jnp.int32)
    want = jax.lax.ragged_dot(lhs, rhs[1], gs)
    if stacked:
        got = gm.grouped_matmul(lhs, rhs, gs, impl="pallas_interpret",
                                layer=jnp.int32(1))
    else:
        got = gm.grouped_matmul(lhs, rhs[1], gs, impl="pallas_interpret")
    assert got.shape == (m, n) and got.dtype == lhs.dtype
    real = sum(sizes)
    assert np.abs(np.asarray(want[:real])).max() > 1
    np.testing.assert_allclose(got[:real], want[:real], rtol=1e-5, atol=1e-4)


def test_the_tiles_follow_the_calls_shape():
    """``_tiles(m, G, k, n)``: Mixtral's call shapes, and every call at the
    widths the wide tiles were fitted on, return those tiles; so do the
    widths whose whole matrix does not fit the VMEM as one tile, forward or
    in reverse; SDAR's block forward (32 rows a group) gets a smaller row
    tile and n-tiles that divide n; every tile divides the padded m and
    fits 16 MiB of scoped VMEM."""
    from orion_tpu.ops.grouped_matmul import (
        ROW_TILE, VMEM_BYTES, _tiles, tile_vmem_bytes,
    )

    wide_in, wide_out = (256, 4096, 512), (256, 1024, 2048)
    for m in (1024, 2048, 3072, 4096, 6144, 8192):      # Mixtral's prefill
        assert _tiles(m, 8, 4096, 14336) == wide_in
        assert _tiles(m, 8, 14336, 4096) == wide_out
    for G in (8, 64, 128):
        for rows in (1, 32, 256, 1024):
            assert _tiles(rows * G, G, 4096, 14336) == wide_in
            assert _tiles(rows * G, G, 14336, 4096) == wide_out
    # one tile forward, refused in reverse (2560 x 768), or too wide for one
    for k, n in ((2560, 768), (3072, 1024), (2048, 1536), (4096, 2048)):
        for m in (5120, 8192, 65536):
            assert _tiles(m, 128, k, n) == (256, k, 512)
            assert _tiles(m, 128, n, k) == (256, n, 512)
    # SDAR: 128 experts of [2048, 768] and [768, 2048]
    assert _tiles(4096, 128, 2048, 768) == (128, 2048, 768)
    assert _tiles(4096, 128, 768, 2048) == (128, 768, 2048)
    assert _tiles(8192, 128, 2048, 768)[0] == 128            # 64 rows a group
    for m in (16384, 32768, 65536):                          # 128 and more
        assert _tiles(m, 128, 2048, 768) == (256, 2048, 768)
        assert _tiles(m, 128, 768, 2048) == (256, 768, 2048)
    for m in (8, 328, 4096, 40960):
        for G in (2, 8, 128, 768):
            for k, n in ((64, 32), (192, 320), (768, 2048), (2048, 768),
                         (2560, 768), (3072, 1024), (4096, 14336),
                         (14336, 4096)):
                tm, tk, tn = _tiles(m, G, k, n)
                assert tm <= ROW_TILE and (m + -m % tm) % tm == 0
                assert tm % 16 == 0 and tk <= max(k, 1024) and tn <= n
                assert tile_vmem_bytes(tm, tk, tn) <= VMEM_BYTES
                if tn < n:          # a cut n is cut in whole lanes
                    assert tn % 128 == 0
