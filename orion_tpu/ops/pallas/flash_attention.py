"""Flash attention as a Pallas TPU kernel, forward + custom VJP.

TPU-native equivalent of the reference's fused CUDA attention in ``orion.ops``
(BASELINE.json:5); semantics match ``orion_tpu.ops.attention.attention_xla``
exactly: grouped-query causal attention, optional segment masking (packed
sequences), logit soft-capping, and a ``q_offset`` for decode steps.

Design (SURVEY.md §8 hard-part #1):

- Layout inside the kernel is [batch, heads, seq, head_dim]; the public
  wrapper transposes from the model's [B, S, N, H].
- Grid is (batch, q_head, q_block, kv_block) with the kv block innermost, so
  the online-softmax state (m, l, acc) lives in VMEM scratch carried across
  the kv iterations of one q block.
- GQA is expressed through the k/v BlockSpec index maps (q head n reads kv
  head n * K // N); the backward dk/dv kernel accumulates over the group.
- Causal skipping: blocks strictly above the diagonal skip their compute via
  ``pl.when`` (DMAs still happen — acceptable; revisit with a kv-bound grid).
- The backward pass recomputes attention probabilities from saved (lse) as in
  the flash-attention-2 formulation: two kernels, one accumulating dq over kv
  blocks, one accumulating dk/dv over (group, q-block).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas.common import NEG_INF, pad_axis, resolve_interpret, round_up

LANES = 128


@dataclasses.dataclass(frozen=True)
class _Statics:
    """Hashable static config for the custom-VJP core."""

    causal: bool
    logit_softcap: Optional[float]
    q_offset: int
    # Unpadded kv length: padded kv columns are masked in-kernel. Padded q
    # ROWS are deliberately not masked — they produce garbage that the
    # wrapper slices off, and their cotangents are zero in backward.
    seq_kv: int
    block_q: int
    block_kv: int
    interpret: bool
    # Explicit per-token positions provided (striped/permuted layouts):
    # causal masking compares position ARRAYS instead of index iotas, and
    # the causal block-skip becomes a dynamic min/max test on them.
    has_pos: bool = False
    # Sliding-window attention (Mistral-family): attend only to the last
    # `window` positions, i.e. 0 <= q_pos - kv_pos < window (requires
    # causal). Blocks entirely behind the window skip like causal blocks
    # entirely ahead of the diagonal.
    window: Optional[int] = None
    # Opt-in declaration that segment id 0 means PADDING (the pack_rows /
    # ragged-prefill convention): all-padding blocks then SKIP their
    # compute. Off by default — the base segment semantics allow 0 as a
    # real segment id (0==0 attends), and skipping would change results
    # for such callers.
    seg_pad_zero: bool = False


def _unpack_refs(has_seg: bool, has_pos: bool, refs):
    """(q, k, v, qseg, kseg, qpos, kpos, rest) from a kernel's ref list.

    Input order matches _io_args: q, k, v, [qseg, kseg], [qpos, kpos], then
    the kernel-specific inputs/outputs/scratch in ``rest``.
    """
    i = 3
    qseg = kseg = qpos = kpos = None
    if has_seg:
        qseg, kseg = refs[i], refs[i + 1]
        i += 2
    if has_pos:
        qpos, kpos = refs[i], refs[i + 1]
        i += 2
    return refs[0], refs[1], refs[2], qseg, kseg, qpos, kpos, refs[i:]


def _block_mask(st: _Statics, iq, ik, qseg_ref, kseg_ref, qpos_ref, kpos_ref):
    """[bq, bk] bool mask for grid cell (iq, ik); True = attend.

    qseg/kseg (and qpos/kpos) hold the FULL padded sequence of per-token
    ids (blocked (1, 1, S) — TPU tiling forbids (1, bq) blocks); sliced
    here by grid cell.
    """
    bq, bk = st.block_q, st.block_kv
    kv_idx = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kv_idx < st.seq_kv  # kv padding
    if st.causal:
        if st.has_pos:
            q_ids = qpos_ref[0, 0, pl.ds(iq * bq, bq)]
            kv_ids = kpos_ref[0, 0, pl.ds(ik * bk, bk)]
            dist = q_ids[:, None] - kv_ids[None, :]
        else:
            q_pos = iq * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0
            )
            dist = (q_pos + st.q_offset) - kv_idx
        mask &= dist >= 0
        if st.window is not None:
            mask &= dist < st.window
    if qseg_ref is not None:
        q_ids = qseg_ref[0, 0, pl.ds(iq * bq, bq)]
        kv_ids = kseg_ref[0, 0, pl.ds(ik * bk, bk)]
        mask &= q_ids[:, None] == kv_ids[None, :]
    return mask


def _block_run(st: _Statics, iq, ik, qpos_ref, kpos_ref,
               qseg_ref=None, kseg_ref=None):
    """Block-skip condition for grid cell (iq, ik).

    Causal — index mode: static-shape comparison on block indices;
    position mode: dynamic — a block is skippable only if its largest q
    position precedes its smallest kv position (stripe layouts make this
    the common case for half the blocks, preserving the 2x causal saving).

    Segments — under ``st.seg_pad_zero`` (the caller declares id 0 =
    padding, the data/loader.pack_rows / infer ragged-prefill convention):
    a block whose q rows or kv columns are ALL padding contributes nothing
    anywhere, so it skips. This is what makes mixed-length prefill bursts
    and packed rows pay actual-length compute instead of bucket-padded
    compute. Without the flag, segment blocks never skip (0 may be a real
    segment id).
    """
    run = True
    bq, bk = st.block_q, st.block_kv
    if st.causal:
        if st.has_pos:
            q_ids = qpos_ref[0, 0, pl.ds(iq * bq, bq)]
            kv_ids = kpos_ref[0, 0, pl.ds(ik * bk, bk)]
            run = jnp.max(q_ids) >= jnp.min(kv_ids)
            if st.window is not None:
                # Skip blocks entirely behind the window: largest kv
                # position within reach of the smallest q position. (kv
                # padding is PAD_POS_KV, so padded blocks stay
                # runnable-but-masked.)
                run &= jnp.max(kv_ids) > jnp.min(q_ids) - st.window
        else:
            q_max = iq * bq + bq - 1 + st.q_offset
            run = ik * bk <= q_max
            if st.window is not None:
                q_min = iq * bq + st.q_offset
                run = run & (ik * bk + bk - 1 > q_min - st.window)
    if st.seg_pad_zero and qseg_ref is not None:
        q_seg = qseg_ref[0, 0, pl.ds(iq * bq, bq)]
        kv_seg = kseg_ref[0, 0, pl.ds(ik * bk, bk)]
        run &= (jnp.max(q_seg) > 0) & (jnp.max(kv_seg) > 0)
    return run


def _scaled_logits(st: _Statics, q, k, scale):
    """Returns (z, dz_dscale_factor) where z is the softcapped logit block.

    The second value is tanh(s/cap) (needed by backward) or None.
    """
    s = jax.lax.dot_general(
        q.astype(jnp.float32) * scale,
        k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if st.logit_softcap is not None:
        t = jnp.tanh(s / st.logit_softcap)
        return st.logit_softcap * t, t
    return s, None


def _fwd_kernel(st: _Statics, has_seg, *refs):
    (q_ref, k_ref, v_ref, qseg, kseg, qpos, kpos,
     (o_ref, lse_ref, m_s, l_s, acc_s)) = _unpack_refs(
        has_seg, st.has_pos, refs)

    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    scale = q_ref.shape[-1] ** -0.5

    @pl.when(ik == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # Skip blocks with nothing visible under the causal mask.
    run = _block_run(st, iq, ik, qpos, kpos, qseg, kseg)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        z, _ = _scaled_logits(st, q, k, scale)
        mask = _block_mask(st, iq, ik, qseg, kseg, qpos, kpos)
        z = jnp.where(mask, z, NEG_INF)

        m_prev = m_s[:, :1]                       # [bq, 1]
        m_new = jnp.maximum(m_prev, z.max(axis=-1, keepdims=True))
        # Masked rows keep m == NEG_INF; exp(z - m) would be exp(0) = 1
        # there, so re-apply the mask multiplicatively.
        p = jnp.exp(z - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)           # [bq, 1]
        l_new = l_s[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_s[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_s[:] / l_safe).astype(o_ref.dtype)
        lse = m_s[:, :1] + jnp.log(l_safe)
        lse = jnp.where(l == 0.0, NEG_INF, lse)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _dq_kernel(st: _Statics, has_seg, *refs):
    (q_ref, k_ref, v_ref, qseg, kseg, qpos, kpos,
     (do_ref, lse_ref, delta_ref, dq_ref, dq_s)) = _unpack_refs(
        has_seg, st.has_pos, refs)

    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    scale = q_ref.shape[-1] ** -0.5

    @pl.when(ik == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    run = _block_run(st, iq, ik, qpos, kpos, qseg, kseg)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        z, t = _scaled_logits(st, q, k, scale)
        mask = _block_mask(st, iq, ik, qseg, kseg, qpos, kpos)
        lse = lse_ref[0, 0][:, :1]                # [bq, 1] (lanes-broadcast)
        # Mask INSIDE the exp (as the forward does): a fully-masked q row
        # carries the finite NEG_INF lse stand-in, so exp(z - lse) on its
        # raw logits overflows to inf and inf * 0-mask is NaN (hit by the
        # round-5 compiled ring-merge parity check).
        p = jnp.exp(jnp.where(mask, z - lse, NEG_INF))
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dz = p * (dp - delta_ref[0, 0][:, :1])
        ds = dz if t is None else dz * (1.0 - t * t)
        dq_s[:] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_s[:].astype(dq_ref.dtype)


def _dkv_kernel(st: _Statics, has_seg, *refs):
    (q_ref, k_ref, v_ref, qseg, kseg, qpos, kpos,
     (do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s)) = _unpack_refs(
        has_seg, st.has_pos, refs)

    # grid = (batch, kv_head, kv_block, group, q_block)
    ik, g, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    ng, nq = pl.num_programs(3), pl.num_programs(4)
    scale = q_ref.shape[-1] ** -0.5

    @pl.when((g == 0) & (iq == 0))
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    run = _block_run(st, iq, ik, qpos, kpos, qseg, kseg)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        z, t = _scaled_logits(st, q, k, scale)
        mask = _block_mask(st, iq, ik, qseg, kseg, qpos, kpos)
        lse = lse_ref[0, 0][:, :1]
        # Masked inside the exp — see _dq_kernel for the NaN rationale.
        p = jnp.exp(jnp.where(mask, z - lse, NEG_INF))
        dv_s[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dz = p * (dp - delta_ref[0, 0][:, :1])
        ds = dz if t is None else dz * (1.0 - t * t)
        dk_s[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when((g == ng - 1) & (iq == nq - 1))
    def _finish():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


def _seg_specs(Sq_p: int, Skv_p: int, batch_index):
    """Full-sequence (1, 1, S) segment-id blocks (TPU tiling-legal); the
    kernels slice the current block's ids with pl.ds."""
    return [
        pl.BlockSpec((1, 1, Sq_p), batch_index),
        pl.BlockSpec((1, 1, Skv_p), batch_index),
    ]


def _fwd_call(st: _Statics, q, k, v, qseg, kseg, qpos=None, kpos=None):
    """q: [B,N,Sq,H]; k,v: [B,K,Skv,H] (padded) -> (o, lse[f32 B,N,Sq])."""
    B, N, Sq, H = q.shape
    K, Skv = k.shape[1], k.shape[2]
    G = N // K
    nq, nk = Sq // st.block_q, Skv // st.block_kv
    grid = (B, N, nq, nk)

    q_spec = pl.BlockSpec((1, 1, st.block_q, H), lambda b, n, iq, ik: (b, n, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, st.block_kv, H), lambda b, n, iq, ik: (b, n // G, ik, 0)
    )
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k, v]
    if qseg is not None:
        in_specs += _seg_specs(Sq, Skv, lambda b, n, iq, ik: (b, 0, 0))
        args += [qseg, kseg]
    if qpos is not None:
        in_specs += _seg_specs(Sq, Skv, lambda b, n, iq, ik: (b, 0, 0))
        args += [qpos, kpos]

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, st, qseg is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, st.block_q, H), lambda b, n, iq, ik: (b, n, iq, 0)),
            pl.BlockSpec(
                (1, 1, st.block_q, LANES), lambda b, n, iq, ik: (b, n, iq, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            # lse is lanes-broadcast [B, N, Sq, 128]: TPU tiling forbids a
            # (1, 1, block_q) block, so the row stat rides a full lane dim.
            jax.ShapeDtypeStruct((B, N, Sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((st.block_q, LANES), jnp.float32),
            pltpu.VMEM((st.block_q, LANES), jnp.float32),
            pltpu.VMEM((st.block_q, H), jnp.float32),
        ],
        interpret=st.interpret,
        name="flash_fwd",
    )(*args)
    return out[0], out[1]


def _bwd_call(st: _Statics, q, k, v, qseg, kseg, o, lse, do, g_lse=None,
              qpos=None, kpos=None):
    B, N, Sq, H = q.shape
    K, Skv = k.shape[1], k.shape[2]
    G = N // K
    nq, nk = Sq // st.block_q, Skv // st.block_kv

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        # lse cotangent: d lse_i / d z_ij = p_ij, so the dlse term enters dz
        # as +g_lse_i * p_ij — exactly -g_lse folded into delta, since the
        # kernels compute dz = p * (dp - delta).
        delta = delta - g_lse
    delta = jnp.broadcast_to(delta[..., None], (B, N, Sq, LANES))

    q_spec4 = pl.BlockSpec((1, 1, st.block_q, H), lambda b, n, iq, ik: (b, n, iq, 0))
    kv_spec4 = pl.BlockSpec(
        (1, 1, st.block_kv, H), lambda b, n, iq, ik: (b, n // G, ik, 0)
    )
    row_spec4 = pl.BlockSpec(
        (1, 1, st.block_q, LANES), lambda b, n, iq, ik: (b, n, iq, 0)
    )
    in_specs = [q_spec4, kv_spec4, kv_spec4]
    args = [q, k, v]
    if qseg is not None:
        in_specs += _seg_specs(Sq, Skv, lambda b, n, iq, ik: (b, 0, 0))
        args += [qseg, kseg]
    if qpos is not None:
        in_specs += _seg_specs(Sq, Skv, lambda b, n, iq, ik: (b, 0, 0))
        args += [qpos, kpos]
    in_specs += [q_spec4, row_spec4, row_spec4]
    args += [do, lse, delta]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, st, qseg is not None),
        grid=(B, N, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec4,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((st.block_q, H), jnp.float32)],
        interpret=st.interpret,
        name="flash_bwd_dq",
    )(*args)

    # grid = (batch, kv_head, kv_block, group, q_block): the dk/dv output
    # block for (b, kh, ik) is revisited across the two inner dims, so the
    # accumulator scratch carries over the whole group x q sweep.
    def _q_map5(b, kh, ik, g, iq):
        return (b, kh * G + g, iq, 0)

    def _row_map5(b, kh, ik, g, iq):
        return (b, kh * G + g, iq, 0)

    q_spec5 = pl.BlockSpec((1, 1, st.block_q, H), _q_map5)
    kv_spec5 = pl.BlockSpec(
        (1, 1, st.block_kv, H), lambda b, kh, ik, g, iq: (b, kh, ik, 0)
    )
    row_spec5 = pl.BlockSpec((1, 1, st.block_q, LANES), _row_map5)
    in_specs5 = [q_spec5, kv_spec5, kv_spec5]
    args5 = [q, k, v]
    if qseg is not None:
        in_specs5 += _seg_specs(Sq, Skv, lambda b, kh, ik, g, iq: (b, 0, 0))
        args5 += [qseg, kseg]
    if qpos is not None:
        in_specs5 += _seg_specs(Sq, Skv, lambda b, kh, ik, g, iq: (b, 0, 0))
        args5 += [qpos, kpos]
    in_specs5 += [q_spec5, row_spec5, row_spec5]
    args5 += [do, lse, delta]

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, st, qseg is not None),
        grid=(B, K, nk, G, nq),
        in_specs=in_specs5,
        out_specs=[kv_spec5, kv_spec5],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((st.block_kv, H), jnp.float32),
            pltpu.VMEM((st.block_kv, H), jnp.float32),
        ],
        interpret=st.interpret,
        name="flash_bwd_dkv",
    )(*args5)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(st: _Statics, q, k, v, qseg, kseg, qpos, kpos):
    o, _ = _fwd_call(st, q, k, v, qseg, kseg, qpos, kpos)
    return o


def _flash_fwd(st, q, k, v, qseg, kseg, qpos, kpos):
    o, lse = _fwd_call(st, q, k, v, qseg, kseg, qpos, kpos)
    return o, (q, k, v, qseg, kseg, qpos, kpos, o, lse)


def _flash_bwd(st, res, do):
    q, k, v, qseg, kseg, qpos, kpos, o, lse = res
    dq, dk, dv = _bwd_call(st, q, k, v, qseg, kseg, o, lse, do,
                           qpos=qpos, kpos=kpos)
    return dq, dk, dv, None, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_lse(st: _Statics, q, k, v, qseg, kseg, qpos, kpos):
    """Like _flash but also returns the lanes-broadcast lse residual as a
    differentiable output (ring attention's block merge needs it)."""
    return _fwd_call(st, q, k, v, qseg, kseg, qpos, kpos)


def _flash_lse_fwd(st, q, k, v, qseg, kseg, qpos, kpos):
    o, lse = _fwd_call(st, q, k, v, qseg, kseg, qpos, kpos)
    return (o, lse), (q, k, v, qseg, kseg, qpos, kpos, o, lse)


def _flash_lse_bwd(st, res, cts):
    q, k, v, qseg, kseg, qpos, kpos, o, lse = res
    do, dlse = cts
    # The primal lse output is lanes-broadcast [B, N, Sq, LANES]; the true
    # scalar-per-row cotangent is the sum over the broadcast lane copies.
    g_lse = dlse.sum(axis=-1)
    dq, dk, dv = _bwd_call(st, q, k, v, qseg, kseg, o, lse, do, g_lse=g_lse,
                           qpos=qpos, kpos=kpos)
    return dq, dk, dv, None, None, None, None


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    logit_softcap: Optional[float] = None,
    q_offset: int = 0,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: bool = False,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Flash attention returning ``(out, lse)``; the blockwise unit of ring
    attention (parallel/sequence.py merges partial outputs via their lse).

    out: [B, Sq, N, H] in q.dtype; lse: [B, N, Sq] float32, ``-inf`` on rows
    where nothing was attended (fully masked). Differentiable in both
    outputs. ``q_positions``/``kv_positions`` and ``window`` as in
    ``flash_attention`` (ring layouts pass blocks' global positions so the
    sliding window measures true sequence distance).
    """
    st, qt, kt, vt, qseg, kseg, qpos, kpos, Sq = _prep(
        q, k, v, q_segment_ids, kv_segment_ids,
        causal, logit_softcap, q_offset, block_q, block_kv, interpret,
        q_positions, kv_positions, window,
    )
    o, lse = _flash_lse(st, qt, kt, vt, qseg, kseg, qpos, kpos)
    o = o[:, :, :Sq, :].transpose(0, 2, 1, 3)
    lse = lse[:, :, :Sq, 0]
    # In-kernel "nothing attended" rows carry the finite NEG_INF stand-in;
    # the ring merge keys off true -inf.
    lse = jnp.where(lse <= NEG_INF / 2, -jnp.inf, lse)
    return o, lse


PAD_POS_KV = 2 ** 30  # kv-position pad: larger than any real position, so
#                       padded columns never pass the >= causal test and
#                       fully-padded blocks are skippable by min().


def _prep(
    q, k, v, q_segment_ids, kv_segment_ids,
    causal, logit_softcap, q_offset, block_q, block_kv, interpret,
    q_positions=None, kv_positions=None, window=None, seg_pad_zero=False,
):
    """Shared wrapper prep: statics + [B,N,S,H] transpose + block padding.

    block_q/block_kv default to large (1024) tiles: on v5e the online-softmax
    bookkeeping (max/sum/rescale on the VPU) is amortized over tile area, and
    1024x1024 measured ~2.3x xla attention fwd+bwd at the bench shapes while
    the conservative 128x128 was ~2x *slower* than xla.
    """
    assert (q_segment_ids is None) == (kv_segment_ids is None)
    assert (q_positions is None) == (kv_positions is None)
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} requires causal attention and window >= 1"
        )
    B, Sq, N, H = q.shape
    Skv, K = k.shape[1], k.shape[2]
    assert N % K == 0, (N, K)

    bq = min(block_q or 1024, round_up(Sq, 8))
    bk = min(block_kv or 1024, round_up(Skv, 8))
    if q_segment_ids is not None or q_positions is not None:
        # Segment/position refs are full-length (B, 1, S) int32 arrays that
        # the kernel slices at dynamic lane offsets (i * block). Mosaic
        # requires dynamic lane slices to be provably 128-aligned, so the
        # blocks (and hence every offset, a multiple of the block) must be
        # multiples of the 128-lane tile — the round-5 compiled run died
        # on a 64-wide i32 load here. Padded q rows slice off at the end;
        # padded kv columns stay masked (seg 0 / PAD_POS_KV conventions).
        bq = round_up(bq, 128)
        bk = round_up(bk, 128)
    Sq_p, Skv_p = round_up(Sq, bq), round_up(Skv, bk)

    st = _Statics(
        causal=causal,
        logit_softcap=logit_softcap,
        q_offset=q_offset,
        seq_kv=Skv,
        block_q=bq,
        block_kv=bk,
        interpret=resolve_interpret(interpret),
        has_pos=q_positions is not None,
        window=window,
        seg_pad_zero=seg_pad_zero and q_segment_ids is not None,
    )

    qt = pad_axis(q.transpose(0, 2, 1, 3), 2, Sq_p)
    kt = pad_axis(k.transpose(0, 2, 1, 3), 2, Skv_p)
    vt = pad_axis(v.transpose(0, 2, 1, 3), 2, Skv_p)
    qseg = kseg = None
    if q_segment_ids is not None:
        # (B, 1, S) so the full-seq segment blocks are TPU tiling-legal.
        qseg = pad_axis(q_segment_ids.astype(jnp.int32), 1, Sq_p)[:, None, :]
        kseg = pad_axis(kv_segment_ids.astype(jnp.int32), 1, Skv_p)[:, None, :]
    qpos = kpos = None
    if q_positions is not None:
        if q_positions.ndim == 1:
            q_positions = jnp.broadcast_to(q_positions[None], (B, Sq))
        if kv_positions.ndim == 1:
            kv_positions = jnp.broadcast_to(kv_positions[None], (B, Skv))
        # q pad -1 (rows sliced off; never attends under >=), kv pad huge
        # (never attended; keeps fully-padded blocks skippable).
        qpos = pad_axis(
            q_positions.astype(jnp.int32) + 1, 1, Sq_p
        )[:, None, :] - 1
        kpos = jnp.pad(
            kv_positions.astype(jnp.int32), ((0, 0), (0, Skv_p - Skv)),
            constant_values=PAD_POS_KV,
        )[:, None, :]
    return st, qt, kt, vt, qseg, kseg, qpos, kpos, Sq


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    logit_softcap: Optional[float] = None,
    q_offset: int = 0,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: bool = False,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
    seg_pad_zero: bool = False,
) -> jax.Array:
    """Flash attention; shapes/semantics match ``attention_xla``.

    q: [B, Sq, N, H]; k, v: [B, Skv, K, H] with N % K == 0 -> [B, Sq, N, H].
    With ``q_positions``/``kv_positions`` ([B, S] or [S] int32), causal
    masking compares those explicit positions (permuted/striped sequence
    layouts); otherwise token index + ``q_offset``. ``window`` restricts
    attention to the last ``window`` positions (sliding-window / Mistral;
    blocks fully behind the window skip their compute). ``seg_pad_zero``
    declares segment id 0 as padding, letting all-padding blocks SKIP
    (ragged prefill / packed tails) — only set it when the caller
    guarantees the pack_rows convention.
    See ``_prep`` for the tile-size default rationale.
    """
    st, qt, kt, vt, qseg, kseg, qpos, kpos, Sq = _prep(
        q, k, v, q_segment_ids, kv_segment_ids,
        causal, logit_softcap, q_offset, block_q, block_kv, interpret,
        q_positions, kv_positions, window, seg_pad_zero,
    )
    o = _flash(st, qt, kt, vt, qseg, kseg, qpos, kpos)
    return o[:, :, :Sq, :].transpose(0, 2, 1, 3)
