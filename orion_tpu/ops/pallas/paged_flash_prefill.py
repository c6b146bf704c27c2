"""Blockwise paged-flash prefill: chunk queries attend paged KV history.

Chunked prefill (``runner._dense_layer`` with P_pre > 0) is a
mid-sequence tail prefill: S_pad new tokens per slot attend the slot's
ENTIRE paged KV history. The XLA reference body gathers the P_pre prefix
pages into a dense [Nb, P_pre*psz, K, H] copy per chunk per layer —
O(padded_context) HBM traffic that grows with the cursor, exactly the
copy tax that makes long-context prefill copy-bound instead of
FLOPs-bound (PERF.md §"Long context"). This kernel is the ragged
paged-attention kernel (W <= 31 verify queries) generalized to full
prefill-chunk query blocks, sharing its design decisions:

  - (slot, q_block, walk_page) grid over a COMBINED page walk: the
    scalar-prefetched walk table is ``concat([prefix_pages, chunk_pages],
    axis=1)`` — walk steps below P_pre read history pages from the pool,
    steps at/above P_pre own the chunk's pages. Per-dispatch VMEM is
    bounded by one page block, never by the context length.
  - Same clamped-index DMA elision: prefix pages past a row's own
    prefix clamp DOWN to its last real prefix page, behind-window
    prefix pages clamp UP to the q block's window start — Mosaic elides
    the revisit DMA either way. Chunk pages past the q block's causal
    horizon clamp DOWN to the q block's own page (causal block-skip
    among the new positions).
  - The chunk's OWN K/V is read from a dense per-page operand, never
    from the pool — so write timing can never affect reads, and the new
    tokens are attended RAW (unquantized), exactly like the XLA
    reference's ``concat([k_pre, k])``.
  - Chunk pages are written INSIDE the kernel via input/output aliasing.
    Because chunks are page-aligned (the engine page-aligns mid-prompt
    chunk sizes), every chunk page is overwritten WHOLE — no one-hot
    merge needed, the write is ``quantize_kv(page)`` (or the raw page)
    and is idempotent, so clamped revisits re-applying it are harmless.
    Written pool bytes match the XLA scatter bit-for-bit: same
    ``common.quantize_kv``, same whole-page layout, padding columns
    included (the XLA path writes padding garbage too; decode masks it).
  - Int8 history pages dequantize in-kernel via the lanes-padded scale
    pools; new scale pages land in the first psz scale columns with the
    remaining lanes passed through, matching ``.at[rows, :, :psz].set``.

Like the ragged kernel, padding queries (rows past ``lens``) compute
garbage the caller discards — the XLA reference's discard semantics are
the contract. Prefill is inference-only; no VJP is defined.
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas.common import (
    NEG_INF,
    quantize_kv,
    resolve_interpret,
    round_up,
)

LANES = 128
VMEM_BUDGET_BYTES = 12 * 2 ** 20


def prefill_vmem_bytes(
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    page_size: int,
    kv_itemsize: int,
    quant: bool,
) -> int:
    """Estimated VMEM footprint of one paged-flash-prefill grid step: the
    q/out blocks (one page of queries x GQA group), double-buffered
    in+out pool page blocks, the dense chunk K/V page blocks, the f32
    online-softmax scratch, and the scale blocks under quant. Page-block
    bounded — S never appears."""
    K = n_kv_heads
    G = n_heads // K
    QG8 = max(round_up(page_size * G, 8), 8)
    q_io = 2 * K * QG8 * head_dim * 4
    kv_io = 2 * 2 * 2 * K * page_size * head_dim * kv_itemsize
    new = 2 * 2 * K * page_size * head_dim * 4
    scratch = K * QG8 * (2 * LANES + head_dim) * 4
    scales = (2 * 2 * 2 * K * LANES * 4) if quant else 0
    return q_io + kv_io + new + scratch + scales


def check_prefill_fit(
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    page_size: int,
    kv_quant: Optional[str],
    dtype_itemsize: int = 2,
) -> None:
    """Reject a page size the prefill kernel cannot hold in VMEM — called
    by the engine at init when chunked prefill rides the pallas kernel
    path, so the failure is a config error naming the knob, not a Mosaic
    allocation failure mid-serving."""
    quant = kv_quant == "int8"
    need = prefill_vmem_bytes(
        n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
        page_size=page_size, kv_itemsize=1 if quant else dtype_itemsize,
        quant=quant,
    )
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"paged-flash prefill needs ~{need / 2**20:.1f} MiB of VMEM "
            f"per kernel step at page_size={page_size}, over the "
            f"~{VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget; lower "
            f"inference.page_size, set inference.paged_prefill=false, or "
            f"serve with model.kernels='xla'"
        )


def _kernel(
    softcap: Optional[float],
    psz: int,
    K: int,
    G: int,
    P_pre: int,
    NC: int,
    QG8: int,
    window: Optional[int],
    quant: bool,
    wt_ref,        # [B, P_pre+NC] scalar-prefetched combined page walk
    base_ref,      # [1] scalar-prefetched flat-pool row base (layer * NP)
    st_ref,        # [B] scalar-prefetched cursor (page-aligned prefix len)
    ln_ref,        # [B] scalar-prefetched real chunk tokens per row
    *refs,
):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    ks_ref = vs_ref = None
    if quant:
        ks_ref, vs_ref = refs[i], refs[i + 1]
        i += 2
    kn_ref, vn_ref = refs[i], refs[i + 1]
    i += 2
    o_ref, ko_ref, vo_ref = refs[i], refs[i + 1], refs[i + 2]
    i += 3
    kso_ref = vso_ref = None
    if quant:
        kso_ref, vso_ref = refs[i], refs[i + 1]
        i += 2
    m_s, l_s, acc_s = refs[i:]

    b, qb, ip = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    start = st_ref[b]      # page-aligned: tokens already in the pool
    qlen = ln_ref[b]       # real new tokens this row (1..NC*psz)
    H = q_ref.shape[-1]
    scale = H ** -0.5
    is_chunk = ip >= P_pre
    cb = ip - P_pre        # raw chunk-block index (valid when run_ch)

    @pl.when(ip == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # Packed-row decomposition shared by both phases: row r of a K band
    # holds query qw = r // G at chunk-local position qb*psz + qw
    # (padding rows past psz*G clamp to the block's last query; their
    # outputs are sliced away by the caller).
    rowq = lax.broadcasted_iota(jnp.int32, (K * QG8, psz), 0) % QG8
    qw = jnp.minimum(rowq // G, psz - 1)
    q_loc = qb * psz + qw                       # chunk-local query pos

    def update(z, mask):
        """One online-softmax step over a masked [K*QG8, psz] logit
        block: folds the block into m/l scratch, returns (p, alpha) for
        the caller's acc update."""
        z = jnp.where(mask, z, NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, z.max(axis=-1, keepdims=True))
        p = jnp.exp(z - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_s[:] = jnp.broadcast_to(
            l_s[:, :1] * alpha + p.sum(axis=-1, keepdims=True), l_s.shape
        )
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        return p, alpha

    # History phase: this q block's queries against one prefix page.
    # Skip pages wholly past the row's prefix, wholly behind the earliest
    # query's window, or belonging to an all-padding q block.
    run_pre = (~is_chunk) & (ip * psz < start) & (qb * psz < qlen)
    if window is not None:
        run_pre &= ip * psz + psz - 1 >= start + qb * psz - window + 1

    @pl.when(run_pre)
    def _pre():
        q = q_ref[0, 0].reshape(K, QG8, H).astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)             # [K, psz, H]
        v = v_ref[0].astype(jnp.float32)
        z = lax.dot_general(
            q * scale, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                            # [K, QG8, psz]
        if quant:
            z = z * ks_ref[0][:, :psz][:, None, :]
        z = z.reshape(K * QG8, psz)
        if softcap is not None:
            z = softcap * jnp.tanh(z / softcap)
        kv_pos = ip * psz + lax.broadcasted_iota(
            jnp.int32, (K * QG8, psz), 1
        )
        # Prefix columns are causal for every new query; the segment is
        # the row's own prefix length (clamped revisits mask entirely).
        mask = kv_pos < start
        if window is not None:
            mask &= kv_pos >= start + q_loc - window + 1
        p, alpha = update(z, mask)
        pw = p.reshape(K, QG8, psz)
        if quant:
            pw = pw * vs_ref[0][:, :psz][:, None, :]
        pv = lax.dot_general(
            pw, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_s[:] = acc_s[:] * alpha + pv.reshape(K * QG8, H)

    # Chunk phase: the q block against one of the chunk's own pages, read
    # RAW from the dense operand (never the pool). Causal block-skip:
    # pages past the q block do nothing; pages wholly past the row's real
    # tokens hold only padding every real query masks.
    run_ch = is_chunk & (cb <= qb) & (cb * psz < qlen) & (qb * psz < qlen)
    if window is not None:
        run_ch &= cb * psz + psz - 1 >= qb * psz - window + 1

    @pl.when(run_ch)
    def _ch():
        q = q_ref[0, 0].reshape(K, QG8, H).astype(jnp.float32)
        k = kn_ref[0, 0].astype(jnp.float32)         # [K, psz, H] raw
        v = vn_ref[0, 0].astype(jnp.float32)
        z = lax.dot_general(
            q * scale, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).reshape(K * QG8, psz)
        if softcap is not None:
            z = softcap * jnp.tanh(z / softcap)
        kv_loc = cb * psz + lax.broadcasted_iota(
            jnp.int32, (K * QG8, psz), 1
        )
        mask = kv_loc <= q_loc
        if window is not None:
            mask &= kv_loc >= q_loc - window + 1
        p, alpha = update(z, mask)
        pv = lax.dot_general(
            p.reshape(K, QG8, psz), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_s[:] = acc_s[:] * alpha + pv.reshape(K * QG8, H)

    # Fused page write: chunk pages are whole-page overwrites (chunks are
    # page-aligned), recomputed identically on every visit — clamped
    # revisits are harmless. Prefix-phase steps pass the fetched block
    # through so a revisit's write-back never clobbers history.
    @pl.when(is_chunk)
    def _write():
        if not quant:
            ko_ref[0] = kn_ref[0, 0].astype(ko_ref.dtype)
            vo_ref[0] = vn_ref[0, 0].astype(vo_ref.dtype)
        else:
            SW = kso_ref.shape[-1]
            colc = lax.broadcasted_iota(jnp.int32, (SW, psz), 0)
            tokc = lax.broadcasted_iota(jnp.int32, (SW, psz), 1)
            selc = (colc == tokc).astype(jnp.float32)    # [SW, psz]
            col_has = selc.sum(axis=1) > 0.5             # [SW]
            sel_c = jnp.broadcast_to(selc[None], (K, SW, psz))
            for new_ref, out_ref, sin_ref, sout_ref in (
                (kn_ref, ko_ref, ks_ref, kso_ref),
                (vn_ref, vo_ref, vs_ref, vso_ref),
            ):
                qv, s = quantize_kv(new_ref[0, 0])   # [K,psz,H], [K,psz]
                out_ref[0] = qv.astype(out_ref.dtype)
                s_m = lax.dot_general(
                    sel_c, s, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )                                        # [K, SW]
                sout_ref[0] = jnp.where(col_has[None, :], s_m, sin_ref[0])

    @pl.when(~is_chunk)
    def _passthru():
        ko_ref[0] = k_ref[0]
        vo_ref[0] = v_ref[0]
        if quant:
            kso_ref[0] = ks_ref[0]
            vso_ref[0] = vs_ref[0]

    @pl.when(ip == P_pre + NC - 1)
    def _finish():
        l = l_s[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_s[:] / l_safe).astype(o_ref.dtype)


def _call(q, k_pool, v_pool, walk, start, lens, base, k_new, v_new,
          P_pre, softcap, window, interpret, k_scale=None, v_scale=None):
    B, S, N, H = q.shape
    _, K, psz, _ = k_pool.shape
    assert S % psz == 0, (S, psz)
    NC = S // psz
    G = N // K
    QG = psz * G
    QG8 = max(round_up(QG, 8), 8)
    quant = k_scale is not None

    # Pack each page-sized q block's GQA bands per kv head, padded to a
    # sublane multiple: [B, NC, K*QG8, H], row = qw * G + g.
    qg = q.reshape(B, NC, psz, K, G, H).transpose(0, 1, 3, 2, 4, 5)
    qg = qg.reshape(B, NC, K, QG, H)
    if QG8 != QG:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, QG8 - QG), (0, 0)))
    qg = qg.reshape(B, NC, K * QG8, H)
    # Chunk K/V pre-arranged in page layout: [B, NC, K, psz, H] so walk
    # step P_pre + cb's dense block IS the page to write.
    kn = k_new.reshape(B, NC, psz, K, H).transpose(0, 1, 3, 2, 4)
    vn = v_new.reshape(B, NC, psz, K, H).transpose(0, 1, 3, 2, 4)

    def chunk_cb(qb, ip):
        # Causal clamp: chunk pages past the q block elide their DMA by
        # re-requesting the q block's own page (idempotent rewrite).
        cb = jnp.clip(ip - P_pre, 0, NC - 1)
        return jnp.minimum(cb, qb)

    def kv_index(b, qb, ip, wt, bs, st, ln):
        # Prefix half: clamp DOWN past the row's own prefix, UP behind
        # the q block's earliest window — both elide the revisit DMA.
        last_pre = jnp.maximum(st[b] // psz - 1, 0)
        pre_ip = jnp.minimum(ip, last_pre)
        if window is not None:
            first = jnp.maximum(st[b] + qb * psz - window + 1, 0) // psz
            pre_ip = jnp.maximum(pre_ip, jnp.minimum(first, last_pre))
        idx = jnp.where(ip < P_pre, pre_ip, P_pre + chunk_cb(qb, ip))
        return (bs[0] + wt[b, idx], 0, 0, 0)

    q_spec = pl.BlockSpec(
        (1, 1, K * QG8, H), lambda b, qb, ip, *_: (b, qb, 0, 0)
    )
    kv_spec = pl.BlockSpec((1, K, psz, H), kv_index)
    new_spec = pl.BlockSpec(
        (1, 1, K, psz, H),
        lambda b, qb, ip, *_: (b, chunk_cb(qb, ip), 0, 0, 0),
    )
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [qg, k_pool, v_pool]
    if quant:
        sw = k_scale.shape[-1]
        sc_spec = pl.BlockSpec(
            (1, K, sw),
            lambda b, qb, ip, wt, bs, st, ln: kv_index(
                b, qb, ip, wt, bs, st, ln)[:3],
        )
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]
    in_specs += [new_spec, new_spec]
    args += [kn, vn]
    out_specs = [q_spec, kv_spec, kv_spec]
    out_shape = [
        jax.ShapeDtypeStruct((B, NC, K * QG8, H), q.dtype),
        jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
        jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
    ]
    # Operand order: 4 scalar-prefetch args, then q, pools, [scales,]
    # kn, vn. The pools (and scale pools) alias outputs 1.. so the fused
    # write is in place.
    if quant:
        out_specs += [sc_spec, sc_spec]
        out_shape += [
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ]
        aliases = {5 + i: 1 + i for i in range(4)}
    else:
        aliases = {5: 1, 6: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, NC, P_pre + NC),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((K * QG8, LANES), jnp.float32),
            pltpu.VMEM((K * QG8, LANES), jnp.float32),
            pltpu.VMEM((K * QG8, H), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, softcap, psz, K, G, P_pre, NC, QG8, window, quant,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=resolve_interpret(interpret),
        name="paged_flash_prefill",
    )(
        walk.astype(jnp.int32), base, start.astype(jnp.int32),
        lens.astype(jnp.int32), *args,
    )
    attn = out[0].reshape(B, NC, K, QG8, H)[:, :, :, :QG, :]
    attn = attn.reshape(B, NC, K, psz, G, H).transpose(0, 1, 3, 2, 4, 5)
    attn = attn.reshape(B, S, N, H)
    return (attn, *out[1:])


def paged_flash_prefill(
    q: jax.Array,            # [B, S_pad, N, H] the chunk's queries
    k_pool: jax.Array,       # [L*num_pages, K, psz, H] flat pool
    v_pool: jax.Array,       # [L*num_pages, K, psz, H]
    walk: jax.Array,         # [B, P_pre + S_pad//psz] int32 page walk:
    #                          prefix pages ++ the chunk's own pages
    start: jax.Array,        # [B] int32 page-aligned cursor (prefix len)
    lens: jax.Array,         # [B] int32 real new tokens per row
    k_new: jax.Array,        # [B, S_pad, K, H] chunk K/V (raw dtype)
    v_new: jax.Array,
    *,
    n_prefix_pages: int,
    layer_base: Union[jax.Array, int] = 0,
    logit_softcap: Optional[float] = None,
    window: Optional[int] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    tp_axis: str = "tp",
):
    """Chunk-of-S_pad-queries prefill attention over the paged pool, the
    chunk's own pages written in place (aliased).

    Row b resumes at page-aligned ``start[b]``: query j (absolute
    position ``start[b] + j``) attends the row's whole paged history
    (walk steps < n_prefix_pages) plus the chunk's earlier positions,
    under the optional sliding window and logit softcap. Returns
    ``(out [B, S_pad, N, H], k_pool', v_pool'[, k_scale', v_scale'])``.
    Semantics match the XLA route of ``runner._dense_layer``: the dense
    prefix gather + flash attention + page scatter collapse into one
    kernel whose HBM traffic is O(real context), not O(padded gather
    copy), and whose VMEM is bounded by the page block, not S.
    """
    assert (k_scale is None) == (v_scale is None)
    if window is not None and window < 1:
        raise ValueError(f"window={window} must be >= 1")
    K = k_pool.shape[1]
    assert q.shape[2] % K == 0, (q.shape, K)
    base = jnp.asarray(layer_base, jnp.int32).reshape(1)

    tp = mesh.shape.get(tp_axis, 1) if mesh is not None else 1
    if tp > 1:
        # Head-sharded serving, the ragged kernel's scheme verbatim: the
        # page walk is head-independent, each device owns K/tp of every
        # page and G = N/K is preserved per shard.
        N = q.shape[2]
        if N % tp or K % tp:
            raise ValueError(
                f"tp-sharded paged-flash prefill needs n_heads ({N}) and "
                f"n_kv_heads ({K}) divisible by {tp_axis}={tp}; lower tp "
                f"or serve with kernels='xla'"
            )
        from jax.sharding import PartitionSpec as P

        qspec = P(None, None, tp_axis, None)
        poolspec = P(None, tp_axis, None, None)
        rep2, rep1 = P(None, None), P(None)
        args = [q, k_pool, v_pool, walk, start, lens, base, k_new, v_new]
        in_specs = [
            qspec, poolspec, poolspec, rep2, rep1, rep1, rep1, qspec,
            qspec,
        ]
        out_specs = [qspec, poolspec, poolspec]
        have_scale = k_scale is not None
        if have_scale:
            scspec = P(None, tp_axis, None)
            args += [k_scale, v_scale]
            in_specs += [scspec, scspec]
            out_specs += [scspec, scspec]

        def body(q_, kp_, vp_, wt_, st_, ln_, base_, kn_, vn_, *rest):
            ks = vs = None
            if have_scale:
                ks, vs = rest[0], rest[1]
            return _call(
                q_, kp_, vp_, wt_, st_, ln_, base_, kn_, vn_,
                n_prefix_pages, logit_softcap, window, interpret, ks, vs,
            )

        mapped = jax.shard_map(
            body, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=tuple(out_specs), check_vma=False,
        )
        return tuple(mapped(*args))

    return _call(
        q, k_pool, v_pool, walk, start, lens, base, k_new, v_new,
        n_prefix_pages, logit_softcap, window, interpret, k_scale, v_scale,
    )
