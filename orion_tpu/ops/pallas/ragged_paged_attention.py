"""Multi-query ragged paged attention: W queries per slot, KV write fused.

Speculative verification (``runner.verify_step`` / ``mixed_verify_step``)
scores each slot's pending token + drafts — W = speculate_tokens + 1 query
positions per slot — in one pass over the weights. The XLA reference body
scatters all W tokens' K/V into the pool and re-materializes every slot's
full padded context via a pool gather, exactly the copy tax the W=1 paged
kernel (``paged_attention.py``) exists to avoid. This kernel is that
kernel generalized from 1 to W ragged queries per slot (PAPERS.md: ragged
paged attention), sharing its design decisions:

  - Same (batch, page) grid, scalar-prefetched page walk, clamped index
    map (invalid tail pages re-request the last valid block and Mosaic
    elides the DMA; behind-window pages clamp UP to the window's first
    page), and [rows, K, psz, H] heads-major pool with the head dim as a
    dot_general batch dim.
  - The W new tokens' K/V are written INSIDE the kernel on the grid steps
    whose pages own their positions (``start + j`` for j < ``lens``),
    via input/output aliasing. The insert is a one-hot matmul merge — a
    [psz, W8] selection matrix built from iotas contracts with the
    [K, W8, H] new-token block — because Mosaic rejects vector stores at
    runtime-computed sublane/lane offsets (the round-5 compiled lesson);
    the one-hot contraction is exact (rows multiply by 1.0/0.0), so
    written pool bytes match an external scatter bit-for-bit. Clamped
    revisits re-apply their target page's merge so the final write-back
    is never the stale pre-insert block.
  - Under ``kv_quant=int8`` the new tokens quantize in-kernel with the
    SAME ``common.quantize_kv`` the jnp paths use — per-(token, kv-head)
    scales merged into the lanes-padded scale pools by the same one-hot
    trick — so acceptance numerics stay bit-identical to sequential
    decode.
  - Causal masking among the W new positions rides the same kv-position
    mask as raggedness: query w at position ``start + w`` attends
    kv_pos <= start + w, which includes the earlier drafts of the same
    dispatch (their K/V is already merged into the block being read).
    Rows shorter than W (``lens``) exclude their padding tokens from the
    merge, so padding never touches the pool (the XLA path parks it on
    scratch instead — both are unobservable); padding QUERIES still
    compute, masked like a real query at ``start + j``, and return
    garbage rows the caller discards — do NOT "fix" them to fully
    masked, the XLA reference's discard semantics are the contract.

Per-query numerics match the W=1 kernel's op-for-op: the extra pages a
non-final query visits (between its own position and the row's last) are
exact no-ops in the online softmax (fully masked blocks contribute p=0,
alpha=1), so greedy acceptance on this path reproduces the sequential
pallas decode stream.

Verification is inference-only; no VJP is defined.
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

# Conservative per-kernel VMEM budget for the fit check below: one v5e/v6e
# core has ~16 MiB of VMEM; leave headroom for Mosaic's own buffers.
VMEM_BUDGET_BYTES = 12 * 2 ** 20


def verify_vmem_bytes(
    W: int,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    page_size: int,
    kv_itemsize: int,
    quant: bool,
) -> int:
    """Estimated VMEM footprint of one ragged-paged-attention grid step.

    Counts the q/out blocks, the double-buffered in+out KV page blocks,
    the new-token blocks, the f32 scratch (m/l/acc), and the scale blocks
    under quant. An estimate (Mosaic's allocator has its own padding),
    used only to reject hopeless configs with an actionable error instead
    of a Mosaic OOM."""
    K = n_kv_heads
    G = n_heads // K
    WG8 = max(round_up(W * G, 8), 8)
    W8 = max(round_up(W, 8), 8)
    q_io = 2 * K * WG8 * head_dim * 4                 # q + out blocks
    kv_io = 2 * 2 * 2 * K * page_size * head_dim * kv_itemsize
    new = 2 * 2 * K * W8 * head_dim * 4               # k_new + v_new
    scratch = K * WG8 * (2 * LANES + head_dim) * 4    # m, l, acc
    scales = (2 * 2 * 2 * K * LANES * 4) if quant else 0
    return q_io + kv_io + new + scratch + scales


def check_verify_fit(
    W: int,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    page_size: int,
    kv_quant: Optional[str],
    dtype_itemsize: int = 2,
) -> None:
    """Reject a speculative verify width the kernel cannot hold in VMEM.

    Called by the engine at init when ``inference.speculative`` rides the
    pallas kernel path, so the failure is a config error naming the knob,
    not a Mosaic allocation failure mid-serving."""
    quant = kv_quant == "int8"
    need = verify_vmem_bytes(
        W, n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
        page_size=page_size, kv_itemsize=1 if quant else dtype_itemsize,
        quant=quant,
    )
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"speculative verify width W={W} "
            f"(inference.speculate_tokens={W - 1}) needs ~"
            f"{need / 2**20:.1f} MiB of VMEM per kernel step, over the "
            f"~{VMEM_BUDGET_BYTES / 2**20:.0f} MiB the ragged "
            f"paged-attention kernel budgets; lower "
            f"inference.speculate_tokens or serve with model.kernels='xla'"
        )


def _kernel(
    softcap: Optional[float],
    psz: int,
    K: int,
    G: int,
    W: int,
    WG8: int,
    W8: int,
    fused_write: bool,
    window: Optional[int],
    quant: bool,
    tree: bool,
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
        tm_ref, dp_ref = refs[0], refs[1]   # [B, W] i32 each
        refs = refs[2:]
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
    start = st_ref[b]
    wlen = ln_ref[b]
    # Highest position this row writes/attends; the clamp keeps a
    # degenerate caller (cursor at the context edge) in-bounds the way
    # the XLA body's scratch redirect does.
    last = jnp.minimum(start + wlen - 1, npages * psz - 1)
    H = q_ref.shape[-1]
    scale = H ** -0.5

    @pl.when(ip == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    if fused_write:
        # Which of the W new tokens land on THIS grid step's DMA-target
        # page: the index map's clamp, replicated, so clamped revisits
        # (invalid tail pages down to the last valid page; behind-window
        # pages up to the window's first) re-apply their target page's
        # merge — a single application would be clobbered by a revisit's
        # stale write-back, exactly the W=1 kernel's insert discipline.
        valid_ip = jnp.minimum(ip, last // psz)
        if window is not None:
            first = jnp.maximum(start - window + 1, 0) // psz
            valid_ip = jnp.maximum(valid_ip, jnp.minimum(first, last // psz))
        tok = lax.broadcasted_iota(jnp.int32, (psz, W8), 1)
        row = lax.broadcasted_iota(jnp.int32, (psz, W8), 0)
        pos = start + tok
        sel = (
            (tok < wlen) & (pos <= last)
            & (pos // psz == valid_ip) & (pos % psz == row)
        )
        # One-hot merge instead of a dynamic-index row store (Mosaic
        # rejects vector stores at runtime-computed sublane offsets —
        # round 5): sel has at most one 1 per page row (the W positions
        # are consecutive, so two tokens sharing an in-page offset are a
        # whole page apart and fail the page test), making the f32
        # contraction below an exact select of the new token's vector.
        selm = sel.astype(jnp.float32)                   # [psz, W8]
        row_has = selm.sum(axis=1) > 0.5                 # [psz]
        sel_k = jnp.broadcast_to(selm[None], (K, psz, W8))
        if not quant:
            for new_ref, in_ref, out_ref in (
                (kn_ref, k_ref, ko_ref), (vn_ref, v_ref, vo_ref),
            ):
                merged = lax.dot_general(
                    sel_k, new_ref[0].astype(jnp.float32),
                    (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )                                        # [K, psz, H]
                out_ref[0] = jnp.where(
                    row_has[None, :, None],
                    merged.astype(out_ref.dtype), in_ref[0],
                )
        else:
            # Quantize the W new tokens in-kernel via the SAME function
            # the jnp paths use (common.quantize_kv): pool bytes and
            # scales match a sequential decode's bit-for-bit. The scale
            # pools merge by the same one-hot trick against a lane iota.
            SW = ks_ref.shape[-1]
            tokc = lax.broadcasted_iota(jnp.int32, (SW, W8), 1)
            colc = lax.broadcasted_iota(jnp.int32, (SW, W8), 0)
            posc = start + tokc
            selc = (
                (tokc < wlen) & (posc <= last)
                & (posc // psz == valid_ip) & (posc % psz == colc)
            ).astype(jnp.float32)                        # [SW, W8]
            col_has = selc.sum(axis=1) > 0.5             # [SW]
            sel_c = jnp.broadcast_to(selc[None], (K, SW, W8))
            for new_ref, in_ref, out_ref, sin_ref, sout_ref in (
                (kn_ref, k_ref, ko_ref, ks_ref, kso_ref),
                (vn_ref, v_ref, vo_ref, vs_ref, vso_ref),
            ):
                qv, s = quantize_kv(new_ref[0])    # [K, W8, H], [K, W8]
                merged = lax.dot_general(
                    sel_k, qv.astype(jnp.float32),
                    (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )
                out_ref[0] = jnp.where(
                    row_has[None, :, None],
                    merged.astype(out_ref.dtype), in_ref[0],
                )
                s_merged = lax.dot_general(
                    sel_c, s, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )                                        # [K, SW]
                sout_ref[0] = jnp.where(
                    col_has[None, :], s_merged, sin_ref[0]
                )

        k_src, v_src = ko_ref, vo_ref
        ks_src, vs_src = kso_ref, vso_ref
    else:
        k_src, v_src = k_ref, v_ref
        ks_src, vs_src = ks_ref, vs_ref

    # Ragged skip: pages wholly beyond the row's LAST query position do
    # nothing (their fetches were elided by the clamped index map); with a
    # sliding window, pages wholly behind the EARLIEST query's window skip
    # too. Later queries' tighter windows are handled by the mask — their
    # extra visited pages are exact online-softmax no-ops.
    run = ip * psz <= last
    if window is not None:
        run &= ip * psz + psz - 1 >= start - window + 1

    @pl.when(run)
    def _body():
        q = q_ref[0].reshape(K, WG8, H).astype(jnp.float32)
        k = k_src[0].astype(jnp.float32)                 # [K, psz, H]
        v = v_src[0].astype(jnp.float32)
        z = lax.dot_general(
            q * scale, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                # [K, WG8, psz]
        if quant:
            z = z * ks_src[0][:, :psz][:, None, :]
        z = z.reshape(K * WG8, psz)
        if softcap is not None:
            z = softcap * jnp.tanh(z / softcap)
        kv_pos = ip * psz + lax.broadcasted_iota(
            jnp.int32, (K * WG8, psz), 1
        )
        # Row r of a K-band holds query w = r // G (padding rows past
        # W*G clamp to the last query; their outputs are sliced away).
        rowq = lax.broadcasted_iota(jnp.int32, (K * WG8, psz), 0) % WG8
        qw = jnp.minimum(rowq // G, W - 1)
        if not tree:
            q_pos = start + qw
            mask = kv_pos <= q_pos
            if window is not None:
                mask &= kv_pos >= q_pos - window + 1
        else:
            # Token tree: committed context (kv_pos < start) is visible
            # to every query; among the W new slots, query w sees slot i
            # iff bit i of its ancestor word is set (or i == w). Depths
            # replace slot order for logical positions: W static and
            # small, so the per-row word/depth vectors build as W
            # unrolled scalar-SMEM selects (Mosaic has no vector gather
            # from SMEM), noise next to the dot_generals.
            word = jnp.zeros_like(qw)
            qdep = jnp.zeros_like(qw)
            for w in range(W):
                word = jnp.where(qw == w, tm_ref[b, w], word)
                qdep = jnp.where(qw == w, dp_ref[b, w], qdep)
            slot = kv_pos - start
            in_new = (slot >= 0) & (slot < W)
            bit = (
                lax.shift_right_logical(word, jnp.clip(slot, 0, 31)) & 1
            ) == 1
            # Boolean algebra, not a select between boolean vectors:
            # Mosaic lowers an i1-valued select through i8 and refuses the
            # truncation back ("Unsupported target bitwidth for
            # truncation", libtpu 0.0.34).
            mask = (in_new & (bit | (slot == qw))) | (
                ~in_new & (kv_pos < start)
            )
            if window is not None:
                # Window distance among new slots is DEPTH distance
                # (two siblings at one depth are window-equivalent even
                # though their pool slots differ).
                sdep = jnp.zeros_like(slot)
                for w in range(W):
                    sdep = jnp.where(slot == w, dp_ref[b, w], sdep)
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
        pw = p.reshape(K, WG8, psz)
        if quant:
            pw = pw * vs_src[0][:, :psz][:, None, :]
        pv = lax.dot_general(
            pw, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                # [K, WG8, H]
        acc_s[:] = acc_s[:] * alpha + pv.reshape(K * WG8, H)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(ip == npages - 1)
    def _finish():
        l = l_s[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_s[:] / l_safe).astype(o_ref.dtype)


def _call(q, k_pool, v_pool, page_table, start, lens, base, k_new, v_new,
          softcap, window, interpret, k_scale=None, v_scale=None,
          tree_mask=None, depths=None):
    B, W, N, H = q.shape
    rows_total, K, psz, _ = k_pool.shape
    P = page_table.shape[1]
    G = N // K
    WG = W * G
    WG8 = max(round_up(WG, 8), 8)
    W8 = max(round_up(W, 8), 8)
    fused_write = k_new is not None
    quant = k_scale is not None
    tree = tree_mask is not None

    # Pack the W queries' GQA bands per kv head: [K, W*G] rows, padded to
    # a sublane multiple — the kernel recovers (w, g) from the row index.
    qg = q.reshape(B, W, K, G, H).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, K, WG, H)
    if WG8 != WG:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, WG8 - WG), (0, 0)))
    qg = qg.reshape(B, K * WG8, H)

    def kv_index(b, ip, pt, bs, st, ln, *_):
        # Same clamp discipline as the W=1 kernel's (see its kv_index):
        # tail pages clamp DOWN to the row's last valid page, behind-
        # window pages clamp UP to the window's first — both elide the
        # DMA and keep revisit write-backs self-consistent. (*_ absorbs
        # the tree-mode scalar-prefetch operands; the page walk is
        # tree-agnostic — slots stay cursor-sequential.)
        last = jnp.minimum(st[b] + ln[b] - 1, P * psz - 1)
        valid_ip = jnp.minimum(ip, last // psz)
        if window is not None:
            first = jnp.maximum(st[b] - window + 1, 0) // psz
            valid_ip = jnp.maximum(valid_ip, jnp.minimum(first, last // psz))
        return (bs[0] + pt[b, valid_ip], 0, 0, 0)

    def row_index(b, ip, pt, bs, st, ln, *_):
        return (b, 0, 0)

    q_spec = pl.BlockSpec((1, K * WG8, H), row_index)
    kv_spec = pl.BlockSpec((1, K, psz, H), kv_index)
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [qg, k_pool, v_pool]
    if quant:
        sw = k_scale.shape[-1]
        sc_spec = pl.BlockSpec(
            (1, K, sw), lambda b, ip, pt, bs, st, ln, *_: kv_index(
                b, ip, pt, bs, st, ln)[:3]
        )
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B, K * WG8, H), q.dtype)]
    aliases = {}
    if fused_write:
        # [B, W, K, H] -> [B, K, W8, H]: heads-major like the pool, token
        # dim padded to a sublane multiple for the one-hot contraction.
        kn = k_new.transpose(0, 2, 1, 3)
        vn = v_new.transpose(0, 2, 1, 3)
        if W8 != W:
            kn = jnp.pad(kn, ((0, 0), (0, 0), (0, W8 - W), (0, 0)))
            vn = jnp.pad(vn, ((0, 0), (0, 0), (0, W8 - W), (0, 0)))
        new_spec = pl.BlockSpec(
            (1, K, W8, H), lambda b, ip, pt, bs, st, ln, *_: (b, 0, 0, 0)
        )
        in_specs += [new_spec, new_spec]
        args += [kn, vn]
        out_specs += [kv_spec, kv_spec]
        out_shape += [
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ]
        # Operand indices count the scalar-prefetch args (pt, base, st,
        # ln, + tree words/depths in tree mode) and q before the pools;
        # without quant the pools are the next two operands after q ->
        # outputs 1 and 2. With quant the scale pools sit between the
        # data pools and k_new/v_new, aliased alongside.
        n_prefetch = 6 if tree else 4
        base_op = n_prefetch + 1            # q sits right after prefetch
        if quant:
            out_specs += [sc_spec, sc_spec]
            out_shape += [
                jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
            ]
            aliases = {base_op + i: 1 + i for i in range(4)}
        else:
            aliases = {base_op: 1, base_op + 1: 2}

    prefetch = [
        page_table.astype(jnp.int32), base, start.astype(jnp.int32),
        lens.astype(jnp.int32),
    ]
    if tree:
        prefetch += [
            tree_mask.astype(jnp.int32), depths.astype(jnp.int32)
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, P),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((K * WG8, LANES), jnp.float32),
            pltpu.VMEM((K * WG8, LANES), jnp.float32),
            pltpu.VMEM((K * WG8, H), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, softcap, psz, K, G, W, WG8, W8, fused_write, window,
            quant, tree,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=resolve_interpret(interpret),
        name="ragged_paged",
    )(*prefetch, *args)
    attn = out[0].reshape(B, K, WG8, H)[:, :, :WG, :]
    attn = attn.reshape(B, K, W, G, H).transpose(0, 2, 1, 3, 4)
    attn = attn.reshape(B, W, N, H)
    if fused_write:
        if quant:
            return attn, out[1], out[2], out[3], out[4]
        return attn, out[1], out[2]
    return attn, k_pool, v_pool


def ragged_paged_attention(
    q: jax.Array,            # [B, W, N, H] the W new positions' queries
    k_pool: jax.Array,       # [L*num_pages, K, psz, H] flat pool
    v_pool: jax.Array,       # [L*num_pages, K, psz, H]
    page_table: jax.Array,   # [B, P] int32 per-layer-relative page ids
    start: jax.Array,        # [B] int32: first new position (the cursor)
    lens: jax.Array,         # [B] int32: real queries this row (1..W)
    *,
    layer_base: Union[jax.Array, int] = 0,  # flat-pool row offset (layer*NP)
    k_new: Optional[jax.Array] = None,      # [B, W, K, H]: K/V of the W
    v_new: Optional[jax.Array] = None,      #   tokens, written in-kernel
    logit_softcap: Optional[float] = None,
    window: Optional[int] = None,           # sliding window per query:
    #                                         attend iff q_pos - kv_pos < w
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,    # [rows, K, SCALE_LANES] f32:
    v_scale: Optional[jax.Array] = None,    #   int8-pool per-token scales
    tree_mask: Optional[jax.Array] = None,  # [B, W] i32 packed ancestor
    #                                         words (bit i of word j: query
    #                                         j may attend new slot i)
    depths: Optional[jax.Array] = None,     # [B, W] i32 tree depth per
    #                                         column (logical position =
    #                                         start + depth)
    mesh: Optional[jax.sharding.Mesh] = None,
    tp_axis: str = "tp",
):
    """W-query ragged decode attention over the paged KV pool.

    Row b holds ``lens[b]`` real queries at positions ``start[b] + j``;
    query j attends every pool position <= its own (earlier same-dispatch
    tokens included) under the optional sliding window. Returns
    [B, W, N, H] when ``k_new``/``v_new`` are None, else ``(out, pools...)``
    with all ``lens[b]`` tokens' K/V written in place (aliased); padding
    queries (j >= lens[b]) write nothing and return garbage rows the
    caller discards. Rows whose page-table entries are 0 (inactive /
    mid-prefill slots) read and write only the reserved scratch page.

    Semantics match ``runner._verify_layer``'s XLA reference: scatter all
    W tokens, gather the padded context, mask per query. With
    ``k_scale``/``v_scale`` the pools are int8 (inference.kv_quant) and
    the fused write quantizes in-kernel (kv_cache.quantize_kv semantics),
    returning ``(out, k_pool', v_pool', k_scale', v_scale')``.

    Token trees (``tree_mask``/``depths``): the intra-dispatch causal
    mask generalizes to an arbitrary ancestor mask — query j attends the
    committed context plus exactly the new slots whose bits are set in
    its packed word (ancestors/root/self), at logical position
    ``start + depths[b, j]``; KV WRITES stay slot-sequential
    (``start + j``), so the page walk, fused write and provisioning are
    unchanged. Chain-shaped words/depths reproduce the positional mask
    bit-for-bit (the degenerate case IS the plain W-query verify). Mask
    words are int32, so tree verification caps W at 31 columns.
    """
    assert (k_new is None) == (v_new is None)
    assert (k_scale is None) == (v_scale is None)
    if (tree_mask is None) != (depths is None):
        raise ValueError("tree_mask and depths must be given together")
    if window is not None and window < 1:
        raise ValueError(f"window={window} must be >= 1")
    if tree_mask is not None and q.shape[1] > 31:
        raise ValueError(
            f"tree verification packs the ancestor mask into int32 words: "
            f"W={q.shape[1]} columns exceed the 31-bit budget; lower "
            f"inference.speculate_tokens"
        )
    K = k_pool.shape[1]
    assert q.shape[2] % K == 0, (q.shape, K)
    base = jnp.asarray(layer_base, jnp.int32).reshape(1)

    tp = mesh.shape.get(tp_axis, 1) if mesh is not None else 1
    if tp > 1:
        # Head-sharded serving, exactly the W=1 kernel's scheme: the page
        # walk is head-independent, each device owns K/tp of every page,
        # and G = N/K is preserved per shard.
        N = q.shape[2]
        if N % tp or K % tp:
            raise ValueError(
                f"tp-sharded ragged paged attention needs n_heads ({N}) "
                f"and n_kv_heads ({K}) divisible by {tp_axis}={tp}; lower "
                f"tp or serve with kernels='xla'"
            )
        from jax.sharding import PartitionSpec as P

        qspec = P(None, None, tp_axis, None)     # [B, W, N, H]
        poolspec = P(None, tp_axis, None, None)  # [rows, K, psz, H]
        rep2, rep1 = P(None, None), P(None)
        args = [q, k_pool, v_pool, page_table, start, lens, base]
        in_specs = [qspec, poolspec, poolspec, rep2, rep1, rep1, rep1]
        out_specs = [qspec]
        have_new, have_scale = k_new is not None, k_scale is not None
        if have_new:
            args += [k_new, v_new]
            in_specs += [qspec, qspec]           # [B, W, K, H]
            out_specs += [poolspec, poolspec]
        if have_scale:
            scspec = P(None, tp_axis, None)      # [rows, K, SCALE_LANES]
            args += [k_scale, v_scale]
            in_specs += [scspec, scspec]
            if have_new:
                out_specs += [scspec, scspec]
        have_tree = tree_mask is not None
        if have_tree:
            # Ancestor words/depths are head-independent: replicated,
            # like the page table.
            args += [tree_mask, depths]
            in_specs += [rep2, rep2]

        def body(q_, kp_, vp_, pt_, st_, ln_, base_, *rest):
            kn = vn = ks = vs = tm = dp = None
            rest = list(rest)
            if have_new:
                kn, vn = rest[0], rest[1]
                rest = rest[2:]
            if have_scale:
                ks, vs = rest[0], rest[1]
                rest = rest[2:]
            if have_tree:
                tm, dp = rest[0], rest[1]
            res = _call(
                q_, kp_, vp_, pt_, st_, ln_, base_, kn, vn,
                logit_softcap, window, interpret, ks, vs, tm, dp,
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
        q, k_pool, v_pool, page_table, start, lens, base, k_new, v_new,
        logit_softcap, window, interpret, k_scale, v_scale,
        tree_mask, depths,
    )
    if k_new is None:
        return out[0]
    if k_scale is None:
        return out[:3]
    return out
