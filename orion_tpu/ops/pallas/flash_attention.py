"""Flash attention as a Pallas TPU kernel, forward + custom VJP.

TPU-native equivalent of the reference's fused CUDA attention in ``orion.ops``
(BASELINE.json:5); semantics match ``orion_tpu.ops.attention.attention_xla``
exactly: grouped-query causal attention, optional segment masking (packed
sequences), logit soft-capping, and a ``q_offset`` for decode steps.

Design (SURVEY.md §8 hard-part #1):

- Layout inside the kernel is [batch, heads, seq, head_dim]; the public
  wrapper transposes from the model's [B, S, N, H].
- Grid is (batch, q_head, q_block, kv_step) with the kv step innermost, so
  the online-softmax state (m, l, acc) lives in VMEM scratch carried across
  the kv steps of one q block.
- GQA is expressed through the k/v BlockSpec index maps (q head n reads kv
  head n * K // N); the backward dk/dv kernel accumulates over the group.
- Only LIVE blocks are visited (PR 32). Where the causal mask and the window
  are on token index (no explicit positions) a q row's live kv blocks are a
  range that ``_kv_range`` computes from the statics, the kv axis of the grid
  is as long as the longest such range, and the index maps take step j of row
  iq to block ``first + j``: a dead block costs neither a grid step nor its
  DMA (a row shorter than the longest keeps pointing at its last block, which
  is not fetched again, and skips). ``_q_range`` is the same for the dk/dv
  kernel's q axis. With explicit positions (ring / striped layouts) or
  ``seg_pad_zero`` liveness is data: the range is the static superset (the
  whole row under positions) and a ``pl.when`` test on the block's positions
  / segment ids skips inside it. ``block_counts`` reports the visited set.
- Under a window much narrower than a block the wrapper hands the same kernel
  a BAND (PR 53, ``_band_chunk`` / ``_fold_bands``): query chunks folded into
  the batch, each with its own keys behind the ``window`` before them, so that
  a chunk is one grid step with no carried softmax state.
- Every visited block is masked, also the 18 of 30 at the train shape that no
  edge crosses: the mask on token index is three compares on one iota
  difference, and skipping it costs more than it saves (under a ``lax.cond``
  the forward took 6.4 ms a call where masking everywhere took 4.6; a second,
  unmasked copy of the body would double what every program lowers for at
  most the 0.05 to 0.25 ms that no mask at all saves; PERF.md §6 PR 32).
- The matmuls take their operands in the inputs' own dtype (bf16 in every
  cell) with f32 accumulation; scale, softcap, running max / sum, ``lse``,
  ``delta`` and the accumulators are f32. (Not for speed: f32 operands time
  the same, the MXU is not what bounds these kernels.)
- The backward pass recomputes attention probabilities from saved (lse) as in
  the flash-attention-2 formulation: two kernels, one accumulating dq over kv
  blocks, one accumulating dk/dv over (group, q-block).
- ``_fwd_call`` / ``_bwd_call`` are jitted on their statics, so a process
  traces each distinct kernel once and a program lowers it once however many
  call sites hold it (``pallas_call`` alone re-traces at every call site; a
  model whose layers are unrolled paid for that in set-up, PERF.md §6 PR 31).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
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
    # Unpadded lengths. Padded kv columns are masked in-kernel. Padded q
    # ROWS are deliberately not masked — they produce garbage that the
    # wrapper slices off, and their cotangents are zero in backward; seq_q
    # only keeps a block that nothing but padded rows reach off the grid.
    seq_q: int
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
    # causal). Blocks entirely behind the window are not visited, like
    # causal blocks entirely ahead of the diagonal.
    window: Optional[int] = None
    # Opt-in declaration that segment id 0 means PADDING (the pack_rows /
    # ragged-prefill convention): all-padding blocks then SKIP their
    # compute. Off by default — the base segment semantics allow 0 as a
    # real segment id (0==0 attends), and skipping would change results
    # for such callers.
    seg_pad_zero: bool = False
    # The logits' scale where it is not q's (padded) width ** -0.5, and
    # whether a learned sink logit a query head joins the softmax's
    # denominator (forward only: ``flash_attention``'s ``sink``).
    scale: Optional[float] = None
    sink: bool = False
    # False: no log-sum-exp output (the forward-only call, whose [B, N, S,
    # 128] float32 rows nothing reads: 512 MiB at 16384 tokens of 64 heads).
    lse: bool = True
    # Generation by diffusion over blocks: the causal diagonal rounded up to
    # the end of the query's block of ``block`` positions (a power of two
    # that divides both tile sizes and ``q_offset``), on token index; 0 is
    # the plain causal mask. Forward only, like the sink.
    block: int = 0


def _static_range(st: _Statics) -> bool:
    """Liveness is a function of block indices alone."""
    return st.causal and not st.has_pos


def _fdiv(x, b: int):
    """floor(x / b) for x >= 0; a shift where ``b`` is a power of two (the
    index maps run this on the scalar core at every grid step)."""
    return x >> (b.bit_length() - 1) if b & (b - 1) == 0 else x // b


def _kv_range(st: _Statics, iq, nk: int, xp=jnp):
    """(first live kv block, number of live kv blocks) of q block ``iq``.

    Exact on token index: a block is in the range if and only if one of its
    (real q row, real kv column) pairs passes the causal and window tests.
    The index maps call it on grid indices and ``block_counts`` on numpy
    ranges (``xp``): one function, so what is counted is what is visited.
    """
    if not _static_range(st):
        return 0 * iq, 0 * iq + nk
    bq, bk = st.block_q, st.block_kv
    q_min = iq * bq + st.q_offset
    q_max = xp.minimum(iq * bq + bq - 1, st.seq_q - 1) + st.q_offset
    if st.block:
        # The last row sees to the end of its block: inside the same kv
        # tile, whose width the block divides, so ``hi`` does not move.
        q_max = q_max | (st.block - 1)
    hi = xp.where(q_max < 0, -1,
                  xp.minimum(_fdiv(xp.maximum(q_max, 0), bk), nk - 1))
    lo = 0 * iq
    if st.window is not None:
        # The oldest position the block's FIRST row still sees; past the
        # last real column, nothing in the row's window exists.
        oldest = xp.maximum(q_min - st.window + 1, 0)
        lo = xp.where(oldest > st.seq_kv - 1, nk, _fdiv(oldest, bk))
    return lo, xp.maximum(hi - lo + 1, 0)


def _q_range(st: _Statics, ik, nq: int, xp=jnp):
    """(first live q block, number of live q blocks) of kv block ``ik``:
    ``_kv_range`` seen from the dk/dv kernel's side."""
    if not _static_range(st):
        return 0 * ik, 0 * ik + nq
    bq, bk = st.block_q, st.block_kv
    first = ik * bk - st.q_offset        # the first q row that sees the block
    lo = xp.where(first > st.seq_q - 1, nq, _fdiv(xp.maximum(first, 0), bq))
    hi = 0 * ik + nq - 1
    if st.window is not None:
        kv_max = xp.minimum(ik * bk + bk - 1, st.seq_kv - 1)
        last = kv_max + st.window - 1 - st.q_offset   # the last q row
        hi = xp.where(last < 0, -1,
                      xp.minimum(_fdiv(xp.maximum(last, 0), bq), nq - 1))
    return lo, xp.maximum(hi - lo + 1, 0)


def _steps(st: _Statics, rng, n_outer: int, n_inner: int) -> int:
    """Length of the grid's inner (visited) axis: the longest live range."""
    if not _static_range(st):
        return n_inner
    _, cnt = rng(st, np.arange(n_outer), n_inner, np)
    return max(int(cnt.max()), 1)


def _step(st: _Statics, rng, outer, j, n_inner: int):
    """(block that step ``j`` of row ``outer`` visits, whether it is inside
    the row's live range). Past the range the step stays on the range's last
    block, so nothing is copied for it."""
    if not _static_range(st):
        return j, True
    lo, cnt = rng(st, outer, n_inner)
    return jnp.clip(lo + jnp.minimum(j, cnt - 1), 0, n_inner - 1), j < cnt


def _unmasked(st: _Statics, iq, ik):
    """Whether every pair of block (iq, ik) attends on token index: no edge
    (diagonal, window, kv padding) crosses it. Counted, not used: the
    kernels mask these blocks too (module docstring). Padded q rows count
    as rows."""
    bq, bk = st.block_q, st.block_kv
    full = ik * bk + bk <= st.seq_kv + 0 * iq
    if st.causal:
        q_min = iq * bq + st.q_offset
        full &= ik * bk + bk - 1 <= (q_min | (st.block - 1) if st.block
                                     else q_min)
        if st.window is not None:
            full &= q_min + bq - 1 - ik * bk < st.window
    return full


def visited_blocks(st: _Statics, nq: int, nk: int) -> np.ndarray:
    """[nq, nk] bool: the (q block, kv block) cells whose body runs in
    index mode (under positions or ``seg_pad_zero``: may run)."""
    lo, cnt = _kv_range(st, np.arange(nq), nk, np)
    ik = np.arange(nk)[None, :]
    return (ik >= lo[:, None]) & (ik < (lo + cnt)[:, None])


def block_counts(st: _Statics, nq: int, nk: int, has_seg: bool = False) -> dict:
    """The grid of one head of one sequence: blocks in the full grid, grid
    steps taken, blocks visited, and visited blocks that no edge crosses."""
    seen = visited_blocks(st, nq, nk)
    iq, ik = np.arange(nq)[:, None], np.arange(nk)[None, :]
    return {
        "full": nq * nk,
        "steps": nq * _steps(st, _kv_range, nq, nk),
        "visited": int(seen.sum()),
        "unmasked": 0 if has_seg or st.has_pos else int(
            (seen & _unmasked(st, iq, ik)).sum()),
    }


def _unpack_refs(has_seg: bool, has_pos: bool, refs):
    """(q, k, v, qseg, kseg, qpos, kpos, rest) from a kernel's ref list.

    Input order: q, k, v, [qseg, kseg], [qpos, kpos], then the
    kernel-specific inputs/outputs/scratch in ``rest``.
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


def _block_mask(st: _Statics, iq, ik, qseg_ref, kseg_ref, qpos_ref, kpos_ref,
                kv_rows: bool = False):
    """[bq, bk] bool mask for grid cell (iq, ik) ([bk, bq] with ``kv_rows``),
    or None where every pair attends by construction; True = attend.

    qseg/kseg (and qpos/kpos) hold the FULL padded sequence of per-token
    ids (blocked (1, 1, S) — TPU tiling forbids (1, bq) blocks); sliced
    here by grid cell.
    """
    bq, bk = st.block_q, st.block_kv
    shape, q_ax, kv_ax = ((bk, bq), 1, 0) if kv_rows else ((bq, bk), 0, 1)
    mask = None

    def both(a, b):
        return b if a is None else a & b

    def outer(ref_q, ref_kv):
        q_ids = ref_q[0, 0, pl.ds(iq * bq, bq)]
        kv_ids = ref_kv[0, 0, pl.ds(ik * bk, bk)]
        if kv_rows:
            return q_ids[None, :], kv_ids[:, None]
        return q_ids[:, None], kv_ids[None, :]

    kv_i = jax.lax.broadcasted_iota(jnp.int32, shape, kv_ax)
    if st.seq_kv % bk:
        mask = kv_i < st.seq_kv - ik * bk  # kv padding
    if st.causal:
        if st.has_pos:
            q_ids, kv_ids = outer(qpos_ref, kpos_ref)
            dist = q_ids - kv_ids
            first = 0
        else:
            # Token index: q position - kv position is (row - col) less a
            # scalar of the block, so the compares are on ONE iota difference.
            row = jax.lax.broadcasted_iota(jnp.int32, shape, q_ax)
            if st.block:
                # The block divides the tile and the offset: rounding the
                # row up inside the tile rounds the position up.
                row = row | (st.block - 1)
            dist = row - kv_i
            first = ik * bk - iq * bq - st.q_offset
        mask = both(mask, dist >= first)
        if st.window is not None:
            mask = both(mask, dist < first + st.window)
    if qseg_ref is not None:
        q_ids, kv_ids = outer(qseg_ref, kseg_ref)
        mask = both(mask, q_ids == kv_ids)
    return mask


def _mask_logits(st: _Statics, z, iq, ik, qseg, kseg, qpos, kpos,
                 kv_rows: bool = False):
    """``z`` with the pairs that do not attend at NEG_INF."""
    mask = _block_mask(st, iq, ik, qseg, kseg, qpos, kpos, kv_rows)
    if mask is None:
        return z
    return jnp.where(mask, z, NEG_INF)


def _data_run(st: _Statics, iq, ik, qpos_ref, kpos_ref, qseg_ref, kseg_ref):
    """The part of a block's liveness that is data, tested inside the static
    range; True where there is none.

    Positions: a block is skippable only if its largest q position precedes
    its smallest kv position (stripe layouts make this the common case for
    half the blocks, preserving the 2x causal saving), or it lies wholly
    behind the window.

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
    if st.causal and st.has_pos:
        q_ids = qpos_ref[0, 0, pl.ds(iq * bq, bq)]
        kv_ids = kpos_ref[0, 0, pl.ds(ik * bk, bk)]
        run = jnp.max(q_ids) >= jnp.min(kv_ids)
        if st.window is not None:
            # Skip blocks entirely behind the window: largest kv
            # position within reach of the smallest q position. (kv
            # padding is PAD_POS_KV, so padded blocks stay
            # runnable-but-masked.)
            run &= jnp.max(kv_ids) > jnp.min(q_ids) - st.window
    if st.seg_pad_zero and qseg_ref is not None:
        q_seg = qseg_ref[0, 0, pl.ds(iq * bq, bq)]
        kv_seg = kseg_ref[0, 0, pl.ds(ik * bk, bk)]
        run &= (jnp.max(q_seg) > 0) & (jnp.max(kv_seg) > 0)
    return run


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=jnp.float32)


def _scaled_logits(st: _Statics, a, b, scale):
    """Returns (z, dz_dscale_factor) where z is the softcapped logit block
    ``a @ b.T`` ([bq, bk] for (q, k), [bk, bq] for (k, q)).

    The second value is tanh(s/cap) (needed by backward) or None.
    """
    s = _dot(a, b, ((1,), (1,))) * scale
    if st.logit_softcap is not None:
        t = jnp.tanh(s / st.logit_softcap)
        return st.logit_softcap * t, t
    return s, None


def _lane_sums(p):
    """[bq, LANES] whose lanes add up to ``p``'s row sums: the column chunks
    added up lane for lane. The running sum stays spread over the lanes and
    is reduced across them once a q block, not once a grid step (at the
    train shape the forward takes 4.33 ms a call this way and 4.55 with a
    row sum a step; PERF.md §6 PR 32)."""
    bq, bk = p.shape
    if bk % LANES:
        return jnp.broadcast_to(
            p.sum(axis=-1, keepdims=True) * (1.0 / LANES), (bq, LANES))
    part = p[:, :LANES]
    for c in range(1, bk // LANES):
        part = part + p[:, c * LANES:(c + 1) * LANES]
    return part


def _fwd_kernel(st: _Statics, has_seg, nk, *refs):
    (q_ref, k_ref, v_ref, qseg, kseg, qpos, kpos,
     rest) = _unpack_refs(has_seg, st.has_pos, refs)
    sink_ref = None
    if st.sink:
        sink_ref, rest = rest[0], rest[1:]
    lse_ref = None
    if st.lse:
        o_ref, lse_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, m_s, l_s, acc_s = rest

    iq, j = pl.program_id(2), pl.program_id(3)
    ik, live = _step(st, _kv_range, iq, j, nk)
    scale = q_ref.shape[-1] ** -0.5 if st.scale is None else st.scale

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(live & _data_run(st, iq, ik, qpos, kpos, qseg, kseg))
    def _body():
        v = v_ref[0, 0]
        z, _ = _scaled_logits(st, q_ref[0, 0], k_ref[0, 0], scale)
        z = _mask_logits(st, z, iq, ik, qseg, kseg, qpos, kpos)

        m_prev = m_s[:, :1]                       # [bq, 1]
        m_new = jnp.maximum(m_prev, z.max(axis=-1, keepdims=True))
        # A row with nothing attended yet keeps m == NEG_INF, and
        # exp(z - m) would be exp(0) = 1 on its masked pairs: subtract 0
        # there, so that they read exp(NEG_INF) = 0.
        m_sub = jnp.where(m_new > 0.5 * NEG_INF, m_new, 0.0)
        p = jnp.exp(z - m_sub)
        alpha = jnp.exp(m_prev - m_sub)           # [bq, 1]
        l_s[:] = l_s[:] * alpha + _lane_sums(p)
        acc_s[:] = acc_s[:] * alpha + _dot(
            p.astype(v.dtype), v, ((1,), (0,)))
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = l_s[:].sum(axis=-1, keepdims=True)
        if sink_ref is not None:
            # One more term of the denominator and no column: the head's
            # sink logit under the row's running maximum.
            l = l + jnp.exp(sink_ref[0, :1, :1] - m_s[:, :1])
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_s[:] / l_safe).astype(o_ref.dtype)
        if lse_ref is not None:
            lse = m_s[:, :1] + jnp.log(l_safe)
            lse = jnp.where(l == 0.0, NEG_INF, lse)
            lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _bwd_block(st: _Statics, iq, ik, scale, q_ref, k_ref, v_ref, qseg, kseg,
               qpos, kpos, do_ref, lse_ref, delta_ref, kv_rows: bool = False):
    """(p, ds, do) of one block, f32 p / ds: what both backward kernels
    recompute before their own products. [bq, bk], or with ``kv_rows``
    [bk, bq]: the orientation in which dk/dv's products contract p and ds
    over their columns, so that neither is transposed."""
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    if kv_rows:
        z, t = _scaled_logits(st, k_ref[0, 0], q_ref[0, 0], scale)
        lse = lse_ref[0, 0].T[:1]                 # [1, bq] (lanes-broadcast)
        delta = delta_ref[0, 0].T[:1]
        dp = _dot(v, do, ((1,), (1,)))
    else:
        z, t = _scaled_logits(st, q_ref[0, 0], k_ref[0, 0], scale)
        lse = lse_ref[0, 0][:, :1]                # [bq, 1]
        delta = delta_ref[0, 0][:, :1]
        dp = _dot(do, v, ((1,), (1,)))
    # Mask INSIDE the exp (as the forward does): a fully-masked q row
    # carries the finite NEG_INF lse stand-in, so exp(z - lse) on its
    # raw logits overflows to inf and inf * 0-mask is NaN (hit by the
    # round-5 compiled ring-merge parity check).
    p = jnp.exp(_mask_logits(
        st, z - lse, iq, ik, qseg, kseg, qpos, kpos, kv_rows))
    dz = p * (dp - delta)
    ds = dz if t is None else dz * (1.0 - t * t)
    return p, ds, do


def _dq_kernel(st: _Statics, has_seg, nk, *refs):
    (q_ref, k_ref, v_ref, qseg, kseg, qpos, kpos,
     (do_ref, lse_ref, delta_ref, dq_ref, dq_s)) = _unpack_refs(
        has_seg, st.has_pos, refs)

    iq, j = pl.program_id(2), pl.program_id(3)
    ik, live = _step(st, _kv_range, iq, j, nk)
    scale = q_ref.shape[-1] ** -0.5

    @pl.when(j == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    @pl.when(live & _data_run(st, iq, ik, qpos, kpos, qseg, kseg))
    def _body():
        _, ds, _ = _bwd_block(st, iq, ik, scale, q_ref, k_ref, v_ref, qseg,
                              kseg, qpos, kpos, do_ref, lse_ref, delta_ref)
        k = k_ref[0, 0]
        dq_s[:] += _dot(ds.astype(k.dtype), k, ((1,), (0,))) * scale

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = dq_s[:].astype(dq_ref.dtype)


def _dkv_kernel(st: _Statics, has_seg, nq, *refs):
    (q_ref, k_ref, v_ref, qseg, kseg, qpos, kpos,
     (do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s)) = _unpack_refs(
        has_seg, st.has_pos, refs)

    # grid = (batch, kv_head, kv_block, group, q_step)
    ik, g, j = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    iq, live = _step(st, _q_range, ik, j, nq)
    scale = q_ref.shape[-1] ** -0.5

    @pl.when((g == 0) & (j == 0))
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    @pl.when(live & _data_run(st, iq, ik, qpos, kpos, qseg, kseg))
    def _body():
        # p and ds as [bk, bq]: both products contract their columns, so
        # neither block is transposed (6.5 ms a call at the train shape
        # against 6.8 with [bq, bk] and a transposing contraction).
        p, ds, do = _bwd_block(st, iq, ik, scale, q_ref, k_ref, v_ref, qseg,
                               kseg, qpos, kpos, do_ref, lse_ref, delta_ref,
                               kv_rows=True)
        q = q_ref[0, 0]
        dv_s[:] += _dot(p.astype(do.dtype), do, ((1,), (0,)))
        dk_s[:] += _dot(ds.astype(q.dtype), q, ((1,), (0,))) * scale

    @pl.when((g == pl.num_programs(3) - 1) & (j == pl.num_programs(4) - 1))
    def _finish():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


def _specs(st: _Statics, H: int, Sq: int, Skv: int, n_ids: int, q_at, kv_at):
    """BlockSpecs of one grid: q-side [.., bq, H] and [.., bq, LANES] blocks,
    the kv block, and ``n_ids`` pairs of full-sequence (1, 1, S) id blocks
    (TPU tiling-legal; the kernels slice the current block's ids with
    pl.ds). ``q_at`` / ``kv_at`` take the grid indices to the (batch, head,
    block) of the q-side and the kv-side block."""
    def q_map(*grid):
        return (*q_at(*grid), 0)

    def kv_map(*grid):
        return (*kv_at(*grid), 0)

    def ids_map(b, *_):
        return (b, 0, 0)

    ids = [pl.BlockSpec((1, 1, Sq), ids_map),
           pl.BlockSpec((1, 1, Skv), ids_map)] * n_ids
    return (pl.BlockSpec((1, 1, st.block_q, H), q_map),
            pl.BlockSpec((1, 1, st.block_q, LANES), q_map),
            pl.BlockSpec((1, 1, st.block_kv, H), kv_map), ids)


def _row_specs(st: _Statics, G: int, H: int, nk: int, Sq: int, Skv: int,
               n_ids: int):
    """``_specs`` of the (batch, q head, q block, kv step) grid."""
    return _specs(
        st, H, Sq, Skv, n_ids,
        lambda b, n, iq, j: (b, n, iq),
        lambda b, n, iq, j: (b, n // G, _step(st, _kv_range, iq, j, nk)[0]))


def _ids(qseg, kseg, qpos, kpos):
    return [a for a in (qseg, kseg, qpos, kpos) if a is not None]


@functools.partial(jax.jit, static_argnums=0)
def _fwd_call(st: _Statics, q, k, v, qseg, kseg, qpos=None, kpos=None,
              sink=None):
    """q: [B,N,Sq,H]; k: [B,K,Skv,H], v: [B,K,Skv,Hv] (padded) -> (o
    [B,N,Sq,Hv], lse[f32 B,N,Sq]). ``sink`` [N, 8, LANES] float32 (a head's
    logit on every row and lane) with ``st.sink``."""
    B, N, Sq, H = q.shape
    K, Skv, Hv = k.shape[1], k.shape[2], v.shape[3]
    nq, nk = Sq // st.block_q, Skv // st.block_kv
    ids = _ids(qseg, kseg, qpos, kpos)
    q_spec, row_spec, kv_spec, id_specs = _row_specs(
        st, N // K, H, nk, Sq, Skv, len(ids) // 2)
    if Hv == H:
        o_spec, v_spec = q_spec, kv_spec
    else:
        o_spec, _, v_spec, _ = _row_specs(st, N // K, Hv, nk, Sq, Skv, 0)
    extra, extra_specs = [], []
    if st.sink:
        extra = [sink]
        extra_specs = [pl.BlockSpec((1, 8, LANES), lambda b, n, *_: (n, 0, 0))]

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, st, qseg is not None, nk),
        grid=(B, N, nq, _steps(st, _kv_range, nq, nk)),
        in_specs=[q_spec, kv_spec, v_spec, *id_specs, *extra_specs],
        out_specs=[o_spec, row_spec][:1 + st.lse],
        out_shape=[
            jax.ShapeDtypeStruct((B, N, Sq, Hv), q.dtype),
            # lse is lanes-broadcast [B, N, Sq, 128]: TPU tiling forbids a
            # (1, 1, block_q) block, so the row stat rides a full lane dim.
            jax.ShapeDtypeStruct((B, N, Sq, LANES), jnp.float32),
        ][:1 + st.lse],
        scratch_shapes=[
            pltpu.VMEM((st.block_q, LANES), jnp.float32),
            pltpu.VMEM((st.block_q, LANES), jnp.float32),
            pltpu.VMEM((st.block_q, Hv), jnp.float32),
        ],
        interpret=st.interpret,
        name="flash_fwd",
    )(q, k, v, *ids, *extra)
    return out[0], (out[1] if st.lse else None)


@functools.partial(jax.jit, static_argnums=0)
def _bwd_call(st: _Statics, q, k, v, qseg, kseg, o, lse, do, g_lse=None,
              qpos=None, kpos=None):
    B, N, Sq, H = q.shape
    K, Skv = k.shape[1], k.shape[2]
    G = N // K
    nq, nk = Sq // st.block_q, Skv // st.block_kv
    has_seg = qseg is not None
    ids = _ids(qseg, kseg, qpos, kpos)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        # lse cotangent: d lse_i / d z_ij = p_ij, so the dlse term enters dz
        # as +g_lse_i * p_ij — exactly -g_lse folded into delta, since the
        # kernels compute dz = p * (dp - delta).
        delta = delta - g_lse
    delta = jnp.broadcast_to(delta[..., None], (B, N, Sq, LANES))

    q_spec, row_spec, kv_spec, id_specs = _row_specs(
        st, G, H, nk, Sq, Skv, len(ids) // 2)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, st, has_seg, nk),
        grid=(B, N, nq, _steps(st, _kv_range, nq, nk)),
        in_specs=[q_spec, kv_spec, kv_spec, *id_specs,
                  q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((st.block_q, H), jnp.float32)],
        interpret=st.interpret,
        name="flash_bwd_dq",
    )(q, k, v, *ids, do, lse, delta)

    # grid = (batch, kv_head, kv_block, group, q_step): the dk/dv output
    # block for (b, kh, ik) is revisited across the two inner dims, so the
    # accumulator scratch carries over the whole group x q sweep.
    q_spec, row_spec, kv_spec, id_specs = _specs(
        st, H, Sq, Skv, len(ids) // 2,
        lambda b, kh, ik, g, j: (
            b, kh * G + g, _step(st, _q_range, ik, j, nq)[0]),
        lambda b, kh, ik, g, j: (b, kh, ik))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, st, has_seg, nq),
        grid=(B, K, nk, G, _steps(st, _q_range, nk, nq)),
        in_specs=[q_spec, kv_spec, kv_spec, *id_specs,
                  q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
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
    )(q, k, v, *ids, do, lse, delta)
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

    block_q/block_kv default to large (1024) tiles, whatever the head count
    and under any window wider than ``BAND_MAX_WINDOW``: a grid step
    costs the forward about a microsecond of per-row bookkeeping (running
    max, rescale of the accumulator) whatever its width, so a narrow kv
    block loses more than its tighter fit to the mask saves. The sweep
    (tools/flash_sweep.py on a v5e, PERF.md §6 PR 32): at the train shape
    (8192 under window 4096) forward / dq / dkv take 4.3 / 5.4 / 6.5 ms a
    call at 1024 x 1024, 5.4 / 5.5 / 6.7 at 512 x 512 and 11.8 / 8.9 / 10.5
    at 256 x 256, though those visit 30, 27 and 25.5 M pairs; with no window
    at 4096 tokens 1024 x 1024 takes 2.4 ms and 512 x 512 3.8. The one shape
    where 512 wins is Laguna's window 512 at 4096 tokens (15 blocks of half
    the pairs: 2.41 ms against 2.65), not enough of a prefill program to
    earn a rule. 2048-wide blocks overrun VMEM.

    Under a window of 128 no block size of this walk is good, which is why
    such a call is folded into bands before it gets here (``_band_chunk``).
    The forward alone at MiMo's window layer (a sink, 64 / 8 heads, keys 192
    padded to 256, values 128, ragged rows; tools/flash_sweep.py ``--only
    mimo`` on a v5e, PERF.md §6 PR 53), ms a call:

        rows x length   1024^2  512^2  256^2 | band C=256  C=512  C=1024
        1 x 16384        12.37   9.37  11.31 |       5.96   5.39    6.56
        2 x 8192         11.54   8.87  10.97 |       5.68   5.10    6.25
        8 x 2048          9.56   7.59   9.53 |       5.10   4.67    6.27

    A chunk of C rows against its C + 128 keys is one grid step with no
    carried softmax state; 512 is ahead at every shape (256 pays twice the
    steps, 1024 a 1024 x 1152 block of which a ninth attends).
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
        seq_q=Sq,
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


# A window of at most a quarter of the default block is attended as a band:
# there the two 1024-wide blocks a query block visits keep under an eighth of
# their pairs. Chunks of BAND_CHUNK query rows: the table in ``_prep``'s
# docstring (tools/flash_sweep.py's group ``mimo-window`` on a v5e).
BAND_MAX_WINDOW = 256
BAND_CHUNK = 512


def _band_chunk(causal, window, q_offset, Sq, Skv, has_pos, has_seg,
                seg_pad_zero, blocks) -> Optional[int]:
    """Query rows a chunk where the call is attended as a band, else None.

    On what the call itself says, at trace time: a causal window on token
    index, narrow (``BAND_MAX_WINDOW``), over q and kv of one length that is
    more than one chunk, at offset 0 and the default blocks. Segment ids,
    where given, hold 0 for padding (the band's front rows carry it).
    """
    narrow = causal and window is not None and window <= BAND_MAX_WINDOW
    plain = not has_pos and q_offset == 0 and blocks == (None, None)
    if (narrow and plain and Sq == Skv and Sq > BAND_CHUNK
            and (seg_pad_zero or not has_seg)):
        return BAND_CHUNK
    return None


def _fold_bands(q, k, v, q_seg, kv_seg, window: int, chunk: int):
    """(q, k, v, q_seg, kv_seg) of the band form: q ``[B, S, N, H]`` as
    ``[B n, chunk, N, H]`` (S padded to n chunks), k and v as the
    overlapping bands ``[B n, window + chunk, K, H]``: a chunk's own rows
    behind the ``window`` rows before them, zeros of segment 0 before the
    first. Without segment ids every real position is segment 1.

    With ``q_offset=window`` row r of a chunk sits at band column
    ``window + r``, so the causal window ``0 <= (r + window) - c < window``
    keeps exactly the positions the unfolded call keeps; one block of
    ``chunk x (window + chunk)`` holds them all.
    """
    B, S = q.shape[:2]
    n = -(-S // chunk)
    if q_seg is None:
        q_seg = kv_seg = jnp.ones((B, S), jnp.int32)

    def rows(x):
        return pad_axis(x, 1, n * chunk).reshape(B * n, chunk, *x.shape[2:])

    def bands(x):
        x = jnp.pad(x, ((0, 0), (window, n * chunk - S))
                    + ((0, 0),) * (x.ndim - 2))
        own = x[:, window:].reshape(B, n, chunk, *x.shape[2:])
        halo = x[:, :n * chunk].reshape(own.shape)[:, :, :window]
        return jnp.concatenate([halo, own], axis=2).reshape(
            B * n, window + chunk, *x.shape[2:])

    return rows(q), bands(k), bands(v), rows(q_seg), bands(kv_seg)


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
    sink: Optional[jax.Array] = None,
    block: int = 0,
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

    ``sink`` ([N] learned logits, one a query head) adds ``exp(sink)`` to
    each row's softmax denominator and no column; values may be narrower
    than keys (``v`` [B, Skv, K, Hv] -> [B, Sq, N, Hv]), and keys whose
    width is not a whole number of 128-lane tiles are padded with zeros
    under the scale of their own width. ``block`` (a power of two) rounds
    the causal diagonal up to the end of the query's block of that many
    positions (``attention_mask``'s; which tiles are visited does not
    change, the mask inside them does). Each is the FORWARD alone: no
    backward is defined for them.

    A causal window of at most ``BAND_MAX_WINDOW`` positions over more than
    ``BAND_CHUNK`` of them is attended as a band (``_band_chunk``): the same
    pairs under the same masks through the same kernel, one grid step a
    chunk of query rows.
    """
    chunk = _band_chunk(
        causal, window, q_offset, q.shape[1], k.shape[1],
        q_positions is not None, q_segment_ids is not None, seg_pad_zero,
        (block_q, block_kv))
    if chunk is not None:
        B, S = q.shape[:2]
        q, k, v, q_seg, kv_seg = _fold_bands(
            q, k, v, q_segment_ids, kv_segment_ids, window, chunk)
        o = flash_attention(
            q, k, v, causal=True, q_segment_ids=q_seg, kv_segment_ids=kv_seg,
            logit_softcap=logit_softcap, q_offset=window, block_q=chunk,
            block_kv=chunk + window, interpret=interpret, window=window,
            seg_pad_zero=True, sink=sink)
        return o.reshape(B, -1, *o.shape[2:])[:, :S]
    H, Hv = q.shape[-1], v.shape[-1]
    fwd_only = sink is not None or Hv != H or bool(block)
    scale = None
    if fwd_only and H % LANES:
        scale = H ** -0.5
        q, k = (pad_axis(a, 3, round_up(H, LANES)) for a in (q, k))
    st, qt, kt, vt, qseg, kseg, qpos, kpos, Sq = _prep(
        q, k, v, q_segment_ids, kv_segment_ids,
        causal, logit_softcap, q_offset, block_q, block_kv, interpret,
        q_positions, kv_positions, window, seg_pad_zero,
    )
    if block:
        if (not causal or window is not None or q_positions is not None
                or block & (block - 1) or q_offset % block
                or st.block_q % block or st.block_kv % block):
            raise ValueError(
                f"block={block} needs causal attention on token index with "
                f"no window, and a power of two that divides q_offset="
                f"{q_offset} and the tiles {st.block_q} x {st.block_kv}")
    if fwd_only:
        st = dataclasses.replace(
            st, scale=scale, sink=sink is not None, lse=False, block=block)
        rows = None if sink is None else jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None, None], (q.shape[2], 8, LANES))
        o = _flash_forward_only(st, qt, kt, vt, qseg, kseg, qpos, kpos, rows)
        return o[:, :, :Sq, :].transpose(0, 2, 1, 3)
    o = _flash(st, qt, kt, vt, qseg, kseg, qpos, kpos)
    return o[:, :, :Sq, :].transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_forward_only(st: _Statics, q, k, v, qseg, kseg, qpos, kpos, sink):
    return _fwd_call(st, q, k, v, qseg, kseg, qpos, kpos, sink)[0]


def _forward_only_fwd(st, *args):
    return _flash_forward_only(st, *args), None


def _forward_only_bwd(st, _, g):
    raise NotImplementedError(
        "flash attention with a sink, with values narrower than keys or "
        "under a block mask (block=) has no backward: train such a model "
        "with impl='xla'")


_flash_forward_only.defvjp(_forward_only_fwd, _forward_only_bwd)
