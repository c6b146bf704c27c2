"""Paged decode attention as a Pallas TPU kernel, KV write fused in.

The inference engine's decode step attends one new token per sequence
against that sequence's KV pages (PAPERS.md:9 "ragged paged attention for
TPU LLM inference"; SURVEY.md §3 `ops`: fused attention, "ragged/paged
variant for inference"). The jnp reference path scatters the new token's
K/V into the pool and materializes every sequence's full padded context via
a pool gather; this kernel walks the page table directly and performs the
KV write itself:

  - ``page_table``/``last_pos``/``layer base`` ride the scalar-prefetch
    channel, so each grid step's k/v BlockSpec index map points the DMA at
    the NEXT physical page while the current one computes — the gather
    never materializes. The base offset makes the kernel work on the flat
    [L*num_pages, ...] pool at a *traced* layer index, so the layer scan
    can carry one pool array and update it in place.
  - The new token's K/V is written INSIDE the kernel (on the grid step
    whose page contains ``last_pos``), with the pool passed through via
    ``input_output_aliases``. An external scatter followed by a pallas read
    defeats XLA's in-place buffer analysis — the custom call made XLA
    materialize a fresh multi-GB pool copy per layer (measured 140 ms/step
    vs ~7 ms with the fused write).
  - Pool layout is [rows, K, psz, H]: all K kv-heads of a page form one
    (1, K, psz, H) block whose minor dims (psz, H) are (8, 128)-tiling
    legal, and the head dim is a dot_general *batch* dim — one batched MXU
    op per page instead of a K-step head loop (11x on a v5e) or a
    (batch, head, page) grid of tiny blocks (worse still).
  - Grid is (batch, page). Pages wholly past a sequence's length skip
    their compute (`pl.when`) AND their fetch: the index map clamps them to
    the sequence's first page, so the invalid tail re-requests the block
    already resident and Mosaic elides the copies. Compute and traffic are
    both proportional to the ragged ACTUAL context lengths — the "ragged"
    in ragged paged attention.
  - The grouped query heads of one kv head form a G8-row band of the
    [K*G8, H] q block.

Decode is inference-only; no VJP is defined.

Under chunked prefill (``runner.mixed_step``) this kernel serves the
decode rows of the unified mixed dispatch — same contract, one query
token per sequence with the fused in-place write — while prompt-chunk
rows ride the flash kernel's segment-id path in the same program; the
two in-place pool updates touch disjoint pages (the engine masks
mid-prefill slots' decode rows onto the scratch page).
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


def _kernel(
    softcap: Optional[float],
    psz: int,
    K: int,
    G8: int,
    fused_write: bool,
    window: Optional[int],
    quant: bool,
    pt_ref,        # [B, P] scalar-prefetched page table (per-layer-relative)
    base_ref,      # [1] scalar-prefetched flat-pool row base (layer * NP)
    sl_ref,        # [B] scalar-prefetched last valid position per sequence
    *refs,
):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    ks_ref = vs_ref = kn_ref = vn_ref = None
    if quant:
        ks_ref, vs_ref = refs[i], refs[i + 1]
        i += 2
    if fused_write:
        kn_ref, vn_ref = refs[i], refs[i + 1]
        i += 2
    o_ref = refs[i]
    i += 1
    ko_ref = vo_ref = kso_ref = vso_ref = None
    if fused_write:
        ko_ref, vo_ref = refs[i], refs[i + 1]
        i += 2
        if quant:
            kso_ref, vso_ref = refs[i], refs[i + 1]
            i += 2
    m_s, l_s, acc_s = refs[i:]

    b, ip = pl.program_id(0), pl.program_id(1)
    npages = pl.num_programs(1)
    last_pos = sl_ref[b]
    H = q_ref.shape[-1]
    scale = H ** -0.5

    @pl.when(ip == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    if fused_write:
        # Pass the page through (aliased in/out), inserting the new token's
        # K/V on the page that owns position last_pos. The invalid tail is
        # clamped onto that same (last valid) page, and the insert re-runs
        # on every revisit: the revisits re-copy the STALE input block
        # (fetched before any write-back), so a single insert at the owning
        # grid step would be clobbered by the tail's final write-back.
        # The insert is a MASKED full-block merge, not a dynamic-index row
        # store: Mosaic rejects vector stores at runtime-computed sublane /
        # lane offsets ("cannot statically prove the index is a multiple of
        # the tile"), which the round-5 compiled run hit; a select against a
        # sublane iota stores the whole (tiling-legal) block instead.
        off = last_pos % psz
        insert = ip >= last_pos // psz
        row = lax.broadcasted_iota(jnp.int32, (K, psz, 1), 1)
        sel = insert & (row == off)                       # [K, psz, 1]
        if not quant:
            ko_ref[0] = jnp.where(
                sel, kn_ref[0][:, None, :].astype(ko_ref.dtype), k_ref[0]
            )
            vo_ref[0] = jnp.where(
                sel, vn_ref[0][:, None, :].astype(vo_ref.dtype), v_ref[0]
            )
        else:
            # Quantize the new token's K/V in-kernel via the SAME function
            # the jnp prefill path uses (common.quantize_kv) — decode and
            # prefill quantization agree bit-for-bit by construction. The
            # scale pools merge the same way against a lane iota.
            col = lax.broadcasted_iota(jnp.int32, ks_ref[0].shape, 1)
            scol = insert & (col == off)                  # [K, SCALE_LANES]
            for new_ref, in_ref, out_ref, sin_ref, sout_ref in (
                (kn_ref, k_ref, ko_ref, ks_ref, kso_ref),
                (vn_ref, v_ref, vo_ref, vs_ref, vso_ref),
            ):
                qv, s = quantize_kv(new_ref[0])             # [K, H], [K]
                out_ref[0] = jnp.where(
                    sel, qv.astype(out_ref.dtype)[:, None, :], in_ref[0]
                )
                sout_ref[0] = jnp.where(scol, s[:, None], sin_ref[0])

        k_src, v_src = ko_ref, vo_ref
        ks_src, vs_src = kso_ref, vso_ref
    else:
        k_src, v_src = k_ref, v_ref
        ks_src, vs_src = ks_ref, vs_ref

    # Ragged skip: pages wholly beyond this sequence's context do nothing
    # (their fetches were elided by the clamped index map). With a sliding
    # window, pages wholly BEHIND the window skip too (same elision via the
    # index map's lower clamp), so compute and traffic are O(window).
    run = ip * psz <= last_pos
    if window is not None:
        run &= ip * psz + psz - 1 >= last_pos - window + 1

    @pl.when(run)
    def _body():
        q = q_ref[0].reshape(K, G8, H).astype(jnp.float32)
        k = k_src[0].astype(jnp.float32)                 # [K, psz, H]
        v = v_src[0].astype(jnp.float32)
        z = lax.dot_general(
            q * scale, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                # [K, G8, psz]
        if quant:
            # int8 pool: the per-(head, token) K scale applies to the logit
            # COLUMNS after the matmul (cheaper than dequantizing the
            # [K, psz, H] block before it).
            z = z * ks_src[0][:, :psz][:, None, :]
        z = z.reshape(K * G8, psz)
        if softcap is not None:
            z = softcap * jnp.tanh(z / softcap)
        kv_pos = ip * psz + lax.broadcasted_iota(
            jnp.int32, (K * G8, psz), 1
        )
        mask = kv_pos <= last_pos
        if window is not None:
            # q sits at last_pos: attend iff last_pos - kv_pos < window.
            mask &= kv_pos >= last_pos - window + 1
        z = jnp.where(mask, z, NEG_INF)

        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, z.max(axis=-1, keepdims=True))
        p = jnp.exp(z - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_s[:] = jnp.broadcast_to(
            l_s[:, :1] * alpha + p.sum(axis=-1, keepdims=True), l_s.shape
        )
        pw = p.reshape(K, G8, psz)
        if quant:
            # Fold the V scale into the probabilities (per kv column), so
            # the PV matmul consumes the int8 block directly.
            pw = pw * vs_src[0][:, :psz][:, None, :]
        pv = lax.dot_general(
            pw, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                # [K, G8, H]
        acc_s[:] = acc_s[:] * alpha + pv.reshape(K * G8, H)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(ip == npages - 1)
    def _finish():
        l = l_s[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_s[:] / l_safe).astype(o_ref.dtype)


def _call(q, k_pool, v_pool, page_table, last_pos, base, k_new, v_new,
          softcap, window, interpret, k_scale=None, v_scale=None):
    B, N, H = q.shape
    rows_total, K, psz, _ = k_pool.shape
    P = page_table.shape[1]
    G = N // K
    G8 = max(round_up(G, 8), 8)
    fused_write = k_new is not None
    quant = k_scale is not None

    qg = q.reshape(B, K, G, H)
    if G8 != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, G8 - G), (0, 0)))
    qg = qg.reshape(B, K * G8, H)

    def kv_index(b, ip, pt, bs, sl):
        # Clamp the invalid tail (pages past the context) to the LAST valid
        # page: consecutive identical block requests elide the DMA, and in
        # fused-write mode the tail's write-backs then re-target the page
        # that received the new token (which re-applies its insert — see
        # _kernel) instead of clobbering some other page. With a sliding
        # window, pages wholly behind the window clamp UP to the window's
        # first page the same way (their write-backs rewrite that page with
        # its own just-fetched data — harmless), eliding their DMAs too.
        valid_ip = jnp.minimum(ip, sl[b] // psz)
        if window is not None:
            first = jnp.maximum(sl[b] - window + 1, 0) // psz
            valid_ip = jnp.maximum(valid_ip, jnp.minimum(first, sl[b] // psz))
        return (bs[0] + pt[b, valid_ip], 0, 0, 0)

    def row_index(b, ip, pt, bs, sl):
        return (b, 0, 0)

    q_spec = pl.BlockSpec((1, K * G8, H), row_index)
    kv_spec = pl.BlockSpec((1, K, psz, H), kv_index)
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [qg, k_pool, v_pool]
    if quant:
        # One page's scales: (1, K, SCALE_LANES) f32 — a full (8, 128)
        # lane tile, same clamped page walk as the data blocks.
        sw = k_scale.shape[-1]
        sc_spec = pl.BlockSpec(
            (1, K, sw), lambda b, ip, pt, bs, sl: kv_index(
                b, ip, pt, bs, sl)[:3]
        )
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B, K * G8, H), q.dtype)]
    aliases = {}
    if fused_write:
        new_spec = pl.BlockSpec((1, K, H), row_index)
        in_specs += [new_spec, new_spec]
        args += [k_new, v_new]
        out_specs += [kv_spec, kv_spec]
        out_shape += [
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ]
        # Operand indices count the scalar-prefetch args (pt, base, sl) and
        # q before the pools; without quant the pools are operands 4 and 5
        # -> outputs 1 and 2. With quant the scale pools sit between the
        # data pools and k_new/v_new, and are themselves aliased outputs.
        if quant:
            sw = k_scale.shape[-1]
            out_specs += [sc_spec, sc_spec]
            out_shape += [
                jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
            ]
            aliases = {4: 1, 5: 2, 6: 3, 7: 4}
        else:
            aliases = {4: 1, 5: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, P),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((K * G8, LANES), jnp.float32),
            pltpu.VMEM((K * G8, LANES), jnp.float32),
            pltpu.VMEM((K * G8, H), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, softcap, psz, K, G8, fused_write, window, quant
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=resolve_interpret(interpret),
        name="paged_decode",
    )(page_table.astype(jnp.int32), base, last_pos.astype(jnp.int32), *args)
    attn = out[0].reshape(B, K, G8, H)[:, :, :G, :].reshape(B, N, H)
    if fused_write:
        if quant:
            return attn, out[1], out[2], out[3], out[4]
        return attn, out[1], out[2]
    return attn, k_pool, v_pool


def paged_attention(
    q: jax.Array,            # [B, N, H] (the new token's queries)
    k_pool: jax.Array,       # [L*num_pages, K, psz, H] flat pool
    v_pool: jax.Array,       # [L*num_pages, K, psz, H]
    page_table: jax.Array,   # [B, P] int32 per-layer-relative page ids
    last_pos: jax.Array,     # [B] int32: highest valid position (inclusive)
    *,
    layer_base: Union[jax.Array, int] = 0,  # flat-pool row offset (layer*NP)
    k_new: Optional[jax.Array] = None,      # [B, K, H]: K/V of the token at
    v_new: Optional[jax.Array] = None,      #   last_pos, written in-kernel
    logit_softcap: Optional[float] = None,
    window: Optional[int] = None,           # sliding window: attend iff
    #                                         last_pos - kv_pos < window
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,    # [rows, K, SCALE_LANES] f32:
    v_scale: Optional[jax.Array] = None,    #   int8-pool per-token scales
    mesh: Optional[jax.sharding.Mesh] = None,
    tp_axis: str = "tp",
):
    """Decode attention over the paged KV pool.

    Returns [B, N, H] when ``k_new``/``v_new`` are None, else
    ``(out, k_pool', v_pool')`` with the new token's K/V written into row
    ``layer_base + page_table[b, last_pos // psz]`` at column
    ``last_pos % psz`` — in place via input/output aliasing (an external
    scatter feeding this call costs a full pool copy per layer instead).

    Semantics match gathering each sequence's pages (rows ``layer_base +
    page_table``) into a [B, P*psz, K, H] context, applying the scatter,
    and running masked attention (positions <= last_pos attend).
    ``layer_base`` may be traced (it rides the scalar-prefetch channel), so
    the call sits inside a layer scan over one carried flat pool.

    With ``k_scale``/``v_scale`` the pools are int8 (inference.kv_quant):
    the kernel dequantizes in place — K scales multiply the logit columns
    after the QK matmul, V scales fold into the probabilities before PV —
    and the fused write quantizes the new token in-kernel
    (kv_cache.quantize_kv semantics), returning
    ``(out, k_pool', v_pool', k_scale', v_scale')``.
    """
    assert (k_new is None) == (v_new is None)
    assert (k_scale is None) == (v_scale is None)
    if window is not None and window < 1:
        raise ValueError(f"window={window} must be >= 1")
    K = k_pool.shape[1]
    assert q.shape[1] % K == 0, (q.shape, K)
    base = jnp.asarray(layer_base, jnp.int32).reshape(1)

    tp = mesh.shape.get(tp_axis, 1) if mesh is not None else 1
    if tp > 1:
        # Tensor-parallel serving: split the HEAD axes (q heads, pool kv
        # heads, new-token kv heads, scale-pool kv heads) across ``tp_axis``
        # and run the kernel per shard — a bare pallas_call is opaque to
        # XLA's partitioner, so jitting it over a tp-sharded pool would
        # gather the whole multi-GB pool onto every device. The page walk
        # is head-independent (page_table/last_pos/base replicate), and the
        # fused in-place write stays consistent per shard: each device
        # owns its K/tp slice of every page. G = N/K is preserved per
        # shard, so the in-kernel GQA mapping is unchanged.
        N = q.shape[1]
        if N % tp or K % tp:
            raise ValueError(
                f"tp-sharded paged attention needs n_heads ({N}) and "
                f"n_kv_heads ({K}) divisible by {tp_axis}={tp}; lower tp "
                f"or serve with kernels='xla'"
            )
        from jax.sharding import PartitionSpec as P

        qspec = P(None, tp_axis, None)          # [B, N, H]
        poolspec = P(None, tp_axis, None, None)  # [rows, K, psz, H]
        rep2, rep1 = P(None, None), P(None)
        args = [q, k_pool, v_pool, page_table, last_pos, base]
        in_specs = [qspec, poolspec, poolspec, rep2, rep1, rep1]
        out_specs = [qspec]
        have_new, have_scale = k_new is not None, k_scale is not None
        if have_new:
            args += [k_new, v_new]
            in_specs += [qspec, qspec]           # [B, K, H]
            out_specs += [poolspec, poolspec]
        if have_scale:
            scspec = P(None, tp_axis, None)      # [rows, K, SCALE_LANES]
            args += [k_scale, v_scale]
            in_specs += [scspec, scspec]
            if have_new:
                out_specs += [scspec, scspec]

        def body(q_, kp_, vp_, pt_, lp_, base_, *rest):
            kn = vn = ks = vs = None
            rest = list(rest)
            if have_new:
                kn, vn = rest[0], rest[1]
                rest = rest[2:]
            if have_scale:
                ks, vs = rest[0], rest[1]
            res = _call(
                q_, kp_, vp_, pt_, lp_, base_, kn, vn,
                logit_softcap, window, interpret, ks, vs,
            )
            if not have_new:
                return res[0]
            return res[:3] if not have_scale else res

        mapped = jax.shard_map(
            body, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=tuple(out_specs) if have_new else out_specs[0],
            check_vma=False,
        )
        out = mapped(*args)
        if not have_new:
            return out
        return tuple(out)

    out = _call(
        q, k_pool, v_pool, page_table, last_pos, base, k_new, v_new,
        logit_softcap, window, interpret, k_scale, v_scale,
    )
    if k_new is None:
        return out[0]
    if k_scale is None:
        return out[:3]
    return out
