"""Paged decode attention as a Pallas TPU kernel, KV write fused in.

The inference engine's decode step attends one new token per sequence
against that sequence's KV pages (PAPERS.md:9 "ragged paged attention for
TPU LLM inference"; SURVEY.md §3 `ops`: fused attention, "ragged/paged
variant for inference"); speculative verification attends W of them
(``ragged_paged_attention.py``, a thin wrapper over this module). The jnp
reference path scatters the new tokens' K/V into the pool and materializes
every sequence's full padded context via a pool gather; this kernel walks
the page table itself, in BLOCKS of several pages, and performs the KV
write itself. One kernel body serves both: decode is verify at W = 1, so
the two agree bitwise by construction.

  - Grid is (batch, ceil(P / nb)): a grid step owns ``nb`` consecutive
    entries of a sequence's page table (``BLOCK_PAGES``). Pages are not
    contiguous in the pool, so no BlockSpec can fetch them: the pools stay
    in HBM (``pl.ANY``) and the step issues one async copy per LIVE page
    into a double-buffered ``[2, K, nb*psz, H]`` VMEM scratch, starting
    the next live block's copies (of this sequence, or of the next
    sequence's first live block) before it computes on the current one.
    Pages past a row's last position, and under a sliding window pages
    wholly behind it, start no copy; a block with no live page does
    nothing at all. ``page_table``/cursor/``layer base`` ride the
    scalar-prefetch channel; the base offset makes the kernel work on the
    flat [L*num_pages, ...] pool at a *traced* layer index, so the layer
    scan carries one pool array and updates it in place.
  - One QK and one PV product per kv head over the whole block, operands
    in the pool's dtype (bf16 in serving; int8 pools cast to the query's
    dtype, exact), f32 accumulation. Scale, softcap, the softmax
    statistics and the accumulator are f32. The kv-head dim is a
    dot_general *batch* dim; the grouped query heads of one kv head (times
    W) form a row band of the [K*WG8, H] q block.
  - Only the pages that take new tokens are written. The pools are
    aliased in/out; the step that owns such a page merges the new rows
    into its VMEM copy by a masked select against a position iota (Mosaic
    rejects vector stores at runtime sublane offsets — the round-5
    lesson), attends over the merged block and copies that one page back.
    Every other page is read only. No external scatter feeds the kernel:
    that defeats XLA's in-place buffer analysis and cost a fresh multi-GB
    pool copy per layer (measured 140 ms/step vs ~7 ms fused).
  - Dead columns of a live block (dead pages, positions past the query)
    hold stale but finite data — the scratch is zeroed once per call —
    so their masked probabilities contribute exact zeros.

Rows whose page-table entries are 0 (inactive / mid-prefill slots) read
and write only the reserved scratch page; its content is unobservable.
Padding queries of a W-row (``j >= lens``) write nothing and return
garbage rows the caller discards — the XLA reference's discard semantics
are the contract. Inference-only; no VJP is defined.

Under chunked prefill (``runner.mixed_step``) this kernel serves the
decode rows of the unified mixed dispatch while prompt-chunk rows ride
the flash kernel's segment-id path in the same program; the two in-place
pool updates touch disjoint pages.
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
# Pages per grid step (fewer where the page table is narrower), chosen on a
# v5e by tools/paged_decode_sweep.py over {2, 4, 8, 16} at both serving
# cells' shapes: 2 is a fifth to a half slower everywhere, 4 up to a tenth
# slower on window layers, 16 no faster than 8 on full layers, 5 % faster on
# a 512-token window's nine pages and 20 % slower at one page a sequence.
BLOCK_PAGES = 8


# Mosaic's default scoped-VMEM limit is 16 MiB; over this estimate the call
# raises its own limit, and the engine refuses a verify width outright.
VMEM_BUDGET_BYTES = 12 * 2 ** 20


def vmem_bytes(W, *, n_heads, n_kv_heads, head_dim, page_size,
               kv_itemsize, quant, block_pages=BLOCK_PAGES) -> int:
    """Estimated VMEM footprint of one grid step: the q/out blocks, the
    double-buffered K and V block scratch, the new-token blocks, the f32
    scratch (m/l/acc), the logits and probabilities of one block, and the
    scale blocks under quant. An estimate (Mosaic's allocator has its own
    padding)."""
    K = n_kv_heads
    T = block_pages * page_size
    rows = K * max(round_up(W * n_heads // K, 8), 8)
    q_io = 2 * 2 * rows * head_dim * 4
    kv = 2 * 2 * K * T * head_dim * kv_itemsize
    new = 2 * 2 * W * max(K, 8) * head_dim * 4
    scratch = rows * (2 * LANES + head_dim) * 4
    logits = 3 * rows * T * 4
    scales = (2 * 2 * block_pages * max(K, 8) * LANES * 4) if quant else 0
    return q_io + kv + new + scratch + logits + scales


def _kernel(
    softcap: Optional[float],
    psz: int,
    P: int,
    G: int,
    W: int,
    nb: int,
    fused_write: bool,
    window: Optional[int],
    quant: bool,
    tree: bool,
    split: bool,
    sink: bool,
    scale: Optional[float],
    pt_ref,        # [B, P] scalar-prefetched page table (per-layer-relative)
    base_ref,      # [1] scalar-prefetched flat-pool row base (layer * NP)
    st_ref,        # [B] scalar-prefetched cursor (first new position)
    ln_ref,        # [B] scalar-prefetched real query count per row (1..W)
    *refs,
):
    refs = list(refs)
    tm_ref = dp_ref = None
    if tree:
        # Token-tree verification: packed per-column ancestor words and
        # tree depths ride the scalar prefetch like the page table.
        tm_ref, dp_ref = refs[:2]           # [B, W] i32 each
        refs = refs[2:]
    n_pool = 4 if quant else 2              # k, v (+ k_scale, v_scale)
    q_ref, pools = refs[0], refs[1:1 + n_pool]
    refs = refs[1 + n_pool:]
    new_refs = ()
    if fused_write:
        new_refs, refs = refs[:2], refs[2:]
    sink_ref = None
    if sink:
        sink_ref, refs = refs[0], refs[1:]
    o_ref, refs = refs[0], refs[1:]
    if fused_write:
        # Aliased in/out: read through the OUTPUT refs, so a page written
        # earlier in the call reads back the same here as under the
        # interpreter (whose outputs are copies of the inputs).
        pools, refs = refs[:n_pool], refs[n_pool:]
    m_s, l_s, acc_s = refs[:3]
    bufs = refs[3:3 + n_pool]               # [2, K, nb*psz, H] / [2, nb, K, SW]
    sems, wsems, slot_ref = refs[3 + n_pool:]

    b, ib = pl.program_id(0), pl.program_id(1)
    B = pl.num_programs(0)
    # The V block's: one row of heads a kv head (a split K block holds half
    # as many rows again, below).
    K, T, H = bufs[1].shape[1:]
    WG8 = q_ref.shape[1] // K
    cdt = q_ref.dtype if quant else bufs[0].dtype

    def span(bb):
        """Row bb's cursor, last position and first/last live page."""
        start = st_ref[bb]
        # The clamp keeps a degenerate caller (cursor at the context edge)
        # in-bounds the way the XLA body's scratch redirect does.
        last = jnp.minimum(start + ln_ref[bb] - 1, P * psz - 1)
        hi = last // psz
        lo = 0
        if window is not None:
            # Pages wholly behind the EARLIEST query's window; later
            # queries' tighter windows ride the mask.
            lo = jnp.minimum(jnp.maximum(start - window + 1, 0) // psz, hi)
        return start, last, lo, hi

    def page_buf(s, slot, j):
        if s < 2:
            at = pl.ds(pl.multiple_of(j * psz, psz), psz)
            return bufs[s].at[slot, :, at, :]
        return bufs[s].at[slot, j]

    def fetch(bb, blk, slot, lo_b, hi_b, wait):
        """Start (or wait for) the copies of block blk's live pages: a loop
        over them, so the body is traced once whatever ``nb``."""
        def page(j, carry):
            row = base_ref[0] + pt_ref[bb, blk * nb + j]
            for s in range(n_pool):
                cp = pltpu.make_async_copy(
                    pools[s].at[row], page_buf(s, slot, j), sems.at[slot, s])
                if wait:
                    cp.wait()
                else:
                    cp.start()
            return carry

        lax.fori_loop(jnp.maximum(lo_b - blk * nb, 0),
                      jnp.minimum(hi_b - blk * nb + 1, nb), page, 0)

    start, last, lo, hi = span(b)
    first_blk, last_blk = lo // nb, hi // nb

    @pl.when((ib >= first_blk) & (ib <= last_blk))
    def _step():
        @pl.when((b == 0) & (ib == first_blk))
        def _prime():
            slot_ref[0] = 0
            for buf in bufs:
                buf[...] = jnp.zeros(buf.shape, buf.dtype)
            fetch(b, ib, 0, lo, hi, wait=False)

        slot = slot_ref[0]
        at_end = ib == last_blk
        nxt = jnp.where(at_end, b + 1, b)
        nxt_c = jnp.minimum(nxt, B - 1)
        _, _, lo_n, hi_n = span(nxt_c)

        @pl.when(nxt < B)
        def _prefetch():
            fetch(nxt_c, jnp.where(at_end, lo_n // nb, ib + 1), 1 - slot,
                  lo_n, hi_n, wait=False)

        fetch(b, ib, slot, lo, hi, wait=True)
        slot_ref[0] = 1 - slot

        @pl.when(ib == first_blk)
        def _init():
            m_s[:] = jnp.full_like(m_s, NEG_INF)
            l_s[:] = jnp.zeros_like(l_s)
            acc_s[:] = jnp.zeros_like(acc_s)

        def write_back(wait):
            """Merge the row's new tokens into the pages of this block
            that own them and copy those pages back (wait=False), or wait
            for the copies. W consecutive positions touch at most
            ceil((W - 1) / psz) + 1 pages."""
            for t in range((W - 1 + psz - 1) // psz + 1):
                pg = start // psz + t
                j = pg - ib * nb

                @pl.when((pg <= hi) & (j >= 0) & (j < nb))
                def _():
                    row = base_ref[0] + pt_ref[b, pg]
                    copies = [
                        pltpu.make_async_copy(
                            page_buf(s, slot, j), pools[s].at[row],
                            wsems.at[s],
                        )
                        for s in range(n_pool)
                    ]
                    if wait:
                        for cp in copies:
                            cp.wait()
                        return
                    at = pl.ds(pl.multiple_of(j * psz, psz), psz)
                    pos = pg * psz + lax.broadcasted_iota(
                        jnp.int32, (1, psz, 1), 1)
                    if quant:
                        # One lanes-padded scale row per kv head: the
                        # page's tokens are its first psz lanes.
                        lane = lax.broadcasted_iota(
                            jnp.int32, (1, bufs[2].shape[-1]), 1)
                        spos = jnp.where(lane < psz, pg * psz + lane, -1)
                    for s, new_ref in enumerate(new_refs):
                        page = bufs[s][slot, :, at, :]       # [K, psz, H]
                        if quant:
                            sc = bufs[s + 2][slot, j]        # [K, SW]
                        for w in range(W):
                            here = (w < ln_ref[b]) & (start + w <= last)
                            new = new_ref[0, w]              # [K, H]
                            if quant:
                                # The SAME function the jnp cache paths
                                # use, so decode, prefill and verify
                                # quantize bit-for-bit alike.
                                new, s_new = quantize_kv(new)
                                sc = jnp.where(
                                    here & (spos == start + w),
                                    s_new[:, None], sc)
                            page = jnp.where(
                                here & (pos == start + w),
                                new[:, None, :].astype(page.dtype), page)
                        bufs[s][slot, :, at, :] = page
                        if quant:
                            bufs[s + 2][slot, j] = sc
                    for cp in copies:
                        cp.start()

        if fused_write:
            write_back(wait=False)

        if split:
            # Keys wider than values: a head's last H key dims in row k of
            # the K block, and the dims before them, two heads to a row of
            # H lanes, in rows K.. (kv_cache.pack_keys). The query comes
            # as [its last H dims | the others in its head's half of a
            # row, zeros in the other half], so a pair's row serves both.
            qf = q_ref[0].astype(cdt)                        # [K*WG8, 2H]
            k = bufs[0][slot].astype(cdt)                    # [K + K/2, T, H]
            v = bufs[1][slot].astype(cdt)
            dims = (((2,), (2,)), ((0,), (0,)))
            z = lax.dot_general(
                qf[:, :H].reshape(K, WG8, H), k[:K], dims,
                preferred_element_type=jnp.float32)
            zx = lax.dot_general(
                qf[:, H:].reshape(K // 2, 2 * WG8, H), k[K:], dims,
                preferred_element_type=jnp.float32)
            z = (z + zx.reshape(K, WG8, T)) * scale
        else:
            q = q_ref[0].reshape(K, WG8, H).astype(cdt)
            k = bufs[0][slot].astype(cdt)                    # [K, T, H]
            v = bufs[1][slot].astype(cdt)
            z = lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * (H ** -0.5 if scale is None else scale)      # [K, WG8, T]
        if quant:
            # int8 pool: the per-(head, token) K scale applies to the
            # logit COLUMNS after the matmul (cheaper than dequantizing
            # the [K, T, H] block before it).
            ks, vs = (
                jnp.concatenate(
                    [bufs[s][slot, j][:, :psz] for j in range(nb)], axis=-1)
                for s in (2, 3)
            )                                                # [K, T] each
            z = z * ks[:, None, :]
        z = z.reshape(K * WG8, T)
        if softcap is not None:
            z = softcap * jnp.tanh(z / softcap)
        kv_pos = ib * T + lax.broadcasted_iota(jnp.int32, (K * WG8, T), 1)
        # Row r of a K-band holds query w = r // G (padding rows past W*G
        # clamp to the last query; their outputs are sliced away).
        qw = 0
        if W > 1:
            rowq = lax.broadcasted_iota(jnp.int32, (K * WG8, T), 0) % WG8
            qw = jnp.minimum(rowq // G, W - 1)
        if not tree:
            q_pos = start + qw
            mask = kv_pos <= q_pos
            if window is not None:
                mask &= kv_pos >= q_pos - window + 1
        else:
            # Token tree: committed context (kv_pos < start) is visible to
            # every query; among the W new slots, query w sees slot i iff
            # bit i of its ancestor word is set (or i == w). Depths
            # replace slot order for logical positions: W static and
            # small, so the per-row word/depth vectors build as W unrolled
            # scalar-SMEM selects (Mosaic has no vector gather from SMEM).
            word = jnp.zeros_like(kv_pos)
            qdep = jnp.zeros_like(kv_pos)
            for w in range(W):
                word = jnp.where(qw == w, tm_ref[b, w], word)
                qdep = jnp.where(qw == w, dp_ref[b, w], qdep)
            slot_i = kv_pos - start
            in_new = (slot_i >= 0) & (slot_i < W)
            bit = (
                lax.shift_right_logical(word, jnp.clip(slot_i, 0, 31)) & 1
            ) == 1
            # Boolean algebra, not a select between boolean vectors:
            # Mosaic lowers an i1-valued select through i8 and refuses the
            # truncation back ("Unsupported target bitwidth for
            # truncation", libtpu 0.0.34).
            mask = (in_new & (bit | (slot_i == qw))) | (
                ~in_new & (kv_pos < start)
            )
            if window is not None:
                # Window distance among new slots is DEPTH distance (two
                # siblings at one depth are window-equivalent even though
                # their pool slots differ).
                sdep = jnp.zeros_like(slot_i)
                for w in range(W):
                    sdep = jnp.where(slot_i == w, dp_ref[b, w], sdep)
                mask &= (in_new & (sdep >= qdep - window + 1)) | (
                    ~in_new & (kv_pos >= start + qdep - window + 1)
                )
        z = jnp.where(mask, z, NEG_INF)

        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, z.max(axis=-1, keepdims=True))
        p = jnp.exp(z - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_s[:] = jnp.broadcast_to(
            l_s[:, :1] * alpha + p.sum(axis=-1, keepdims=True), l_s.shape
        )
        pw = p.reshape(K, WG8, T)
        if quant:
            # Fold the V scale into the probabilities (per kv column), so
            # the PV matmul consumes the int8 block directly.
            pw = pw * vs[:, None, :]
        pv = lax.dot_general(
            pw.astype(cdt), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                    # [K, WG8, H]
        acc_s[:] = acc_s[:] * alpha + pv.reshape(K * WG8, H)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)

        if fused_write:
            write_back(wait=True)

        @pl.when(at_end)
        def _finish():
            l = l_s[:, :1]
            if sink_ref is not None:
                # One more term of the denominator and no column: each
                # row's head's sink logit under the row's running maximum.
                l = l + jnp.exp(sink_ref[:, :1] - m_s[:, :1])
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_s[:] / l_safe).astype(o_ref.dtype)


# Jitted so that a program with many instances of the kernel (a decode
# window holds steps x layers of them) traces and lowers each distinct one
# once, not once a call site: pallas_call itself re-traces its kernel on
# every call, and the block walk's body is long.
@functools.partial(jax.jit, static_argnames=(
    "softcap", "window", "interpret", "name", "nb", "scale"))
def _call(q, k_pool, v_pool, page_table, start, lens, base, k_new, v_new,
          k_scale, v_scale, tree_mask, depths, sink=None, *, softcap, window,
          interpret, name, nb, scale=None):
    B, W, N, Hq = q.shape
    _, K, psz, H = v_pool.shape
    # Keys wider than values (``_kernel``'s split): q is two pool rows wide.
    split = k_pool.shape[1] != K
    assert Hq == (2 * H if split else H), (q.shape, k_pool.shape, v_pool.shape)
    P = page_table.shape[1]
    G = N // K
    WG = W * G
    WG8 = max(round_up(WG, 8), 8)
    fused_write = k_new is not None
    quant = k_scale is not None
    tree = tree_mask is not None

    # Pack the W queries' GQA bands per kv head: [K, W*G] rows, padded to
    # a sublane multiple — the kernel recovers (w, g) from the row index.
    qg = q.reshape(B, W, K, G, Hq).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, K, WG, Hq)
    if WG8 != WG:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, WG8 - WG), (0, 0)))
    qg = qg.reshape(B, K * WG8, Hq)

    prefetch = [
        page_table.astype(jnp.int32), base, start.astype(jnp.int32),
        lens.astype(jnp.int32),
    ]
    if tree:
        prefetch += [tree_mask.astype(jnp.int32), depths.astype(jnp.int32)]

    o_spec = pl.BlockSpec((1, K * WG8, H), lambda b, ib, *_: (b, 0, 0))
    q_spec = o_spec if Hq == H else pl.BlockSpec(
        (1, K * WG8, Hq), lambda b, ib, *_: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    pools = [k_pool, v_pool] + ([k_scale, v_scale] if quant else [])
    in_specs = [q_spec] + [hbm] * len(pools)
    args = [qg, *pools]
    out_specs = [o_spec]
    out_shape = [jax.ShapeDtypeStruct((B, K * WG8, H), q.dtype)]
    aliases = {}
    if fused_write:
        # The runner's [B, W, K, H] as it is: token w is a leading index.
        new_spec = pl.BlockSpec(
            (1, W, K, H), lambda b, ib, *_: (b, 0, 0, 0))
        in_specs += [new_spec if not split else pl.BlockSpec(
            (1, W, k_pool.shape[1], H), lambda b, ib, *_: (b, 0, 0, 0)),
            new_spec]
        args += [k_new, v_new]
        out_specs += [hbm] * len(pools)
        out_shape += [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools]
        # Operand indices count the scalar-prefetch args and q before the
        # pools; pool i aliases output 1 + i.
        aliases = {len(prefetch) + 1 + i: 1 + i for i in range(len(pools))}
    if sink is not None:
        # A head's logit on the rows its queries have in the q block.
        rows = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(K, 1, G), (K, W, G))
        rows = jnp.pad(rows.reshape(K, WG), ((0, 0), (0, WG8 - WG)))
        in_specs += [pl.BlockSpec((K * WG8, LANES), lambda b, ib, *_: (0, 0))]
        args += [jnp.broadcast_to(
            rows.reshape(K * WG8, 1), (K * WG8, LANES))]

    T = nb * psz
    scratch = [
        pltpu.VMEM((K * WG8, LANES), jnp.float32),
        pltpu.VMEM((K * WG8, LANES), jnp.float32),
        pltpu.VMEM((K * WG8, H), jnp.float32),
        pltpu.VMEM((2, k_pool.shape[1], T, H), k_pool.dtype),
        pltpu.VMEM((2, K, T, H), v_pool.dtype),
    ]
    if quant:
        scratch += [pltpu.VMEM((2, nb, K, k_scale.shape[-1]), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2, len(pools))),
        pltpu.SemaphoreType.DMA((len(pools),)),
        pltpu.SMEM((1,), jnp.int32),
    ]
    need = vmem_bytes(
        W, n_heads=N, n_kv_heads=K, head_dim=H, page_size=psz,
        kv_itemsize=k_pool.dtype.itemsize, quant=quant, block_pages=nb)
    out = pl.pallas_call(
        functools.partial(
            _kernel, softcap, psz, P, G, W, nb, fused_write, window, quant,
            tree, split, sink is not None, scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, pl.cdiv(P, nb)),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            # The block pipeline carries state from one grid step to the
            # next: both axes run in order on one core.
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(
                None if need <= VMEM_BUDGET_BYTES else need + 8 * 2 ** 20),
        ),
        interpret=resolve_interpret(interpret),
        name=name,
    )(*prefetch, *args)
    attn = out[0].reshape(B, K, WG8, H)[:, :, :WG, :]
    attn = attn.reshape(B, K, W, G, H).transpose(0, 2, 1, 3, 4)
    return (attn.reshape(B, W, N, H), *(out[1:] if fused_write else ()))


def attend(q, k_pool, v_pool, page_table, start, lens, *, layer_base,
           k_new, v_new, logit_softcap, window, interpret, k_scale, v_scale,
           tree_mask=None, depths=None, mesh=None, tp_axis="tp",
           name="paged_decode", sink=None, scale=None):
    """W-query attention over the paged pool: ``paged_attention`` and
    ``ragged_paged_attention`` are this at W = 1 and at W. Returns
    ``(out [B, W, N, H], *written pools)``.

    ``sink`` [N] (a learned logit a query head) adds ``exp(sink)`` to each
    row's softmax denominator and no column. Keys wider than values: a K
    pool of half as many rows of heads again as the V pool's
    (``kv_cache.pack_keys``), ``q`` as ``kv_cache.pack_queries`` lays it
    out and ``scale`` the logits' own (the key's true width ** -0.5)."""
    assert (k_new is None) == (v_new is None)
    assert (k_scale is None) == (v_scale is None)
    if (tree_mask is None) != (depths is None):
        raise ValueError("tree_mask and depths must be given together")
    if tree_mask is not None and q.shape[1] > 31:
        raise ValueError(
            f"tree verification packs the ancestor mask into int32 words: "
            f"W={q.shape[1]} columns exceed the 31-bit budget; lower "
            f"inference.speculate_tokens"
        )
    if window is not None and window < 1:
        raise ValueError(f"window={window} must be >= 1")
    N, K = q.shape[2], v_pool.shape[1]
    assert N % K == 0, (q.shape, K)
    base = jnp.asarray(layer_base, jnp.int32).reshape(1)
    # Optional operands, in _call's order; None where absent.
    opt = [k_new, v_new, k_scale, v_scale, tree_mask, depths, sink]

    def run(q_, kp_, vp_, pt_, st_, ln_, base_, *given):
        it = iter(given)
        full = [next(it) if o is not None else None for o in opt]
        return _call(
            q_, kp_, vp_, pt_, st_, ln_, base_, *full,
            softcap=logit_softcap, window=window, interpret=interpret,
            name=name, nb=min(BLOCK_PAGES, pt_.shape[1]), scale=scale,
        )

    args = [q, k_pool, v_pool, page_table, start, lens, base]
    given = [o for o in opt if o is not None]
    tp = mesh.shape.get(tp_axis, 1) if mesh is not None else 1
    if tp == 1:
        return run(*args, *given)
    # Tensor-parallel serving: split the HEAD axes (q heads, pool kv heads,
    # new-token kv heads, scale-pool kv heads) across ``tp_axis`` and run
    # the kernel per shard — a bare pallas_call is opaque to XLA's
    # partitioner, so jitting it over a tp-sharded pool would gather the
    # whole multi-GB pool onto every device. The page walk is
    # head-independent (page table, cursors, base, tree words replicate),
    # and the fused in-place write stays consistent per shard: each device
    # owns its K/tp slice of every page. G = N/K is preserved per shard.
    if sink is not None or k_pool.shape[1] != K:
        raise ValueError(
            "paged attention with a sink or a packed K pool runs on one "
            "device: neither is split over a mesh yet")
    if N % tp or K % tp:
        raise ValueError(
            f"tp-sharded paged attention needs n_heads ({N}) and "
            f"n_kv_heads ({K}) divisible by {tp_axis}={tp}; lower tp "
            f"or serve with kernels='xla'"
        )
    from jax.sharding import PartitionSpec as PS

    heads4 = PS(None, None, tp_axis, None)      # q, k_new/v_new [B, W, *, H]
    poolspec = PS(None, tp_axis, None, None)    # [rows, K, psz, H]
    scspec = PS(None, tp_axis, None)            # [rows, K, SCALE_LANES]
    rep = PS()
    opt_specs = [heads4, heads4, scspec, scspec, rep, rep]
    out_specs = [heads4]
    if k_new is not None:
        out_specs += [poolspec, poolspec]
        out_specs += [scspec, scspec] if k_scale is not None else []
    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(heads4, poolspec, poolspec, rep, rep, rep, rep, *(
            s for s, o in zip(opt_specs, opt) if o is not None)),
        out_specs=tuple(out_specs), check_vma=False,
    )(*args, *given)


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
    scatter feeding this call costs a full pool copy per layer instead);
    every other page of the pool is bitwise untouched.

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
    out = attend(
        q[:, None], k_pool, v_pool, page_table, last_pos,
        jnp.ones_like(last_pos),
        layer_base=layer_base,
        k_new=None if k_new is None else k_new[:, None],
        v_new=None if v_new is None else v_new[:, None],
        logit_softcap=logit_softcap, window=window, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale, mesh=mesh, tp_axis=tp_axis,
    )
    if k_new is None:
        return out[0][:, 0]
    return (out[0][:, 0], *out[1:])
