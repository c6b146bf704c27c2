"""Cache-aware forward passes: prefill and decode over the paged KV pool.

The reference serves generation through prefill/decode phases over a KV
cache (BASELINE.json:11; SURVEY.md §4 stack B). TPU-native shape discipline:

  - ``prefill_step`` processes a batch of same-bucket prompts padded to a
    static bucket length (one jit specialization per bucket/batch pair),
    runs ordinary causal (flash) attention, and scatters the computed K/V
    pages into the pool.
  - ``decode_window`` advances ALL batch slots ``n_steps`` tokens in a
    single program of fully static shape, sampling fused in: scatter each
    new token's K/V into each sequence's current page, attend via the
    ragged paged kernel (or a masked gather under xla), sample, feed the
    token back — one dispatch and ONE host fetch per window, so the host
    round-trip is paid once per window instead of once per token.

Memory discipline (the part that makes decode bandwidth-bound instead of
copy-bound): the KV pool is a single flat [L*num_pages, K, psz, H] array
(heads-major pages — see kv_cache.py) carried through the layer scan, and
every update is a sparse in-place write at rows ``l*num_pages + page`` —
performed INSIDE the paged-attention kernel on the pallas path, because an
external scatter feeding a custom call makes XLA materialize a fresh pool
copy per layer. Carrying per-layer pool slices as scan xs/ys instead makes
XLA rewrite the whole pool every step (measured 5.4 GB/step on the 1B
bench model — 20x the useful traffic).

Model math is shared with training via models.transformer.qkv_proj /
out_proj / mlp_or_moe — the cache runner only changes what attention reads.
Inactive batch slots point at the reserved scratch page 0 and are masked by
seq_lens alone — no dynamic batch shapes anywhere.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from orion_tpu.config import ModelConfig
from orion_tpu.models import moe as moe_lib
from orion_tpu.models.transformer import (
    Params,
    _norm,
    embed,
    mlp_or_moe,
    out_proj,
    qkv_proj,
    scan_layer_plan,
    unembed,
)
from orion_tpu.ops import attention
from orion_tpu.ops.attention import attention_xla

Cache = dict[str, jax.Array]


def _scan_layers(params: Params, cfg: ModelConfig, body, init_carry):
    """Run ``body(carry, bp, l, j, stack=None) -> carry`` over all layers.

    ``l`` is the layer index (traced under scan, static ints otherwise);
    ``j`` is the STATIC index of a layer of this layer's kind
    (``cfg.layer_kind(j)``: window, query heads, rotary table, feed-forward)
    — a kind is static in every kernel, so interleaved local/global models
    (Gemma-family; j = l % sliding_window_pattern) scan over GROUPS of
    ``pattern`` layers with one body call per static position, and a model
    whose layers differ in shape runs its layer plan
    (``transformer.scan_layer_plan``, which also hands the body ``stack``).
    """
    L = cfg.n_layers
    pattern = cfg.window_pattern
    if cfg.layer_plan is not None:
        if not cfg.scan_layers:
            raise ValueError(
                "a model whose layers differ in shape needs scan_layers=true")
        return scan_layer_plan(
            params["blocks"], cfg.layer_plan, body, init_carry)
    if cfg.scan_layers:
        if pattern is None:
            def scan_body(carry, xs):
                bp, l = xs
                return body(carry, bp, l, 0), None

            carry, _ = jax.lax.scan(
                scan_body, init_carry, (params["blocks"], jnp.arange(L))
            )
            return carry
        if L % pattern:
            raise ValueError(
                f"n_layers={L} must be divisible by "
                f"sliding_window_pattern={pattern}"
            )
        grouped = jax.tree.map(
            lambda a: a.reshape(L // pattern, pattern, *a.shape[1:]),
            params["blocks"],
        )

        def group_body(carry, xs):
            gbp, g = xs
            for j in range(pattern):
                carry = body(
                    carry, jax.tree.map(lambda a: a[j], gbp),
                    g * pattern + j, j,
                )
            return carry, None

        carry, _ = jax.lax.scan(
            group_body, init_carry, (grouped, jnp.arange(L // pattern))
        )
        return carry
    carry = init_carry
    for l, bp in enumerate(params["blocks"]):
        carry = body(carry, bp, l, l % pattern if pattern else 0)
    return carry


def _prefill_ctx(
    params: Params,
    cache: Cache,
    tokens: jax.Array,
    lengths: jax.Array,
    pages: jax.Array,
    prefix_lens: Optional[jax.Array],
    prefix_pages: Optional[jax.Array],
    cfg: ModelConfig,
    paged_prefill: bool = False,
) -> dict:
    """Batch-level tensors the per-layer prefill body consumes (positions,
    segment ids, page arithmetic). Shared by whole-prompt prefill, the
    prefix-cache tail prefill, and the chunked-prefill rows of a mixed
    step — a prefill CHUNK is exactly a mid-sequence tail prefill that
    resumes at a page-aligned ``prefix_lens`` over already-written pages.

    ``paged_prefill`` (inference.paged_prefill, pallas path only) routes
    the P_pre > 0 layers through the blockwise paged-flash prefill kernel
    instead of the dense prefix gather + flash attention + scatter: the
    chunk's queries walk the paged history directly and the chunk's own
    pages are written in-kernel (aliased), so per-chunk HBM traffic is
    O(real context) instead of O(padded gather copy)."""
    from orion_tpu.ops._dispatch import resolve_impl

    Nb, S_pad = tokens.shape
    psz = cache["k"].shape[2]
    NP = cache["k"].shape[0] // cfg.n_layers
    quant = "k_scale" in cache
    P_pre = 0 if prefix_pages is None else prefix_pages.shape[1]
    use_pallas, interpret = resolve_impl(cfg.kernels)
    paged = bool(paged_prefill and P_pre and use_pallas and S_pad % psz == 0)
    kv_pos = kv_seg = None
    if P_pre:
        positions = prefix_lens[:, None] + jnp.arange(S_pad, dtype=jnp.int32)
        pre_idx = jnp.arange(P_pre * psz, dtype=jnp.int32)
        # Prefix kv positions are absolute [0, P_pre*psz); columns past a
        # row's own prefix are garbage -> segment id 0 (and, under SWA,
        # behind the window anyway for pages the engine mapped to scratch).
        kv_pos = jnp.concatenate(
            [jnp.broadcast_to(pre_idx[None], (Nb, P_pre * psz)), positions],
            axis=1,
        )
        seg = (
            jnp.arange(S_pad, dtype=jnp.int32)[None] < lengths[:, None]
        ).astype(jnp.int32)
        kv_seg = jnp.concatenate(
            [(pre_idx[None] < prefix_lens[:, None]).astype(jnp.int32), seg],
            axis=1,
        )
    else:
        positions = jnp.broadcast_to(
            jnp.arange(S_pad, dtype=jnp.int32), (Nb, S_pad)
        )
        # Ragged burst: rows shorter than the bucket mark their padding tail
        # with segment id 0 — the flash kernel SKIPS all-padding blocks, so a
        # mixed-length admission burst pays per-row actual-length compute in
        # one dispatch instead of bucket-padded compute per bucket.
        seg = (positions < lengths[:, None]).astype(jnp.int32)
    walk = None
    if paged:
        # Combined page walk for the paged-flash kernel: the row's prefix
        # pages, then the chunk's own pages (walk step P_pre + cb OWNS
        # chunk page cb — the kernel's fused write targets it).
        walk = jnp.concatenate([prefix_pages, pages], axis=1)
    # The layer-stacked expert weights, where the layer scan slices one
    # stack [L, ...] (no window pattern): the dropless MoE dispatch reads a
    # layer's matrices out of it in place (ops.grouped_matmul).
    moe_stack = None
    if (cfg.is_moe and cfg.scan_layers and cfg.window_pattern is None
            and cfg.layer_plan is None):
        moe_stack = params["blocks"]["moe"]
    return dict(
        moe_stack=moe_stack,
        Nb=Nb, S_pad=S_pad, psz=psz, NP=NP, n_pages=S_pad // psz,
        quant=quant, P_pre=P_pre, positions=positions, seg=seg,
        kv_pos=kv_pos, kv_seg=kv_seg, pages=pages,
        prefix_pages=prefix_pages, prefix_lens=prefix_lens,
        lengths=lengths, paged=paged, interpret=interpret, walk=walk,
    )


def _prefill_layer(
    x: jax.Array,
    cc: Cache,
    bp: Any,
    l,
    j: int,
    ctx: dict,
    cfg: ModelConfig,
    mesh: Optional[jax.sharding.Mesh],
    stack=None,
) -> tuple[jax.Array, Cache]:
    """One transformer layer of (possibly mid-sequence) prefill: flash/xla
    attention over [gathered prefix pages + own K/V], then scatter the new
    K/V pages into the carried pool. ``stack``: ``_scan_layers``' (a model
    with a layer plan; else the one stack of ``ctx``)."""
    Nb, psz, NP = ctx["Nb"], ctx["psz"], ctx["NP"]
    n_pages, quant, P_pre = ctx["n_pages"], ctx["quant"], ctx["P_pre"]
    positions, seg = ctx["positions"], ctx["seg"]
    layer_stack = stack
    if ctx["moe_stack"] is not None:
        layer_stack = (ctx["moe_stack"], l)
    h = _norm(x, bp["attn_norm"], cfg, mesh)
    q, k, v = qkv_proj(h, bp["attn"], cfg, positions, mesh, _kind(cfg, j))
    if P_pre and ctx["paged"]:
        # Paged-flash prefill: the chunk's queries walk the paged history
        # in-kernel (no dense prefix gather) and the chunk's own pages
        # are written fused (no external scatter) — one kernel replaces
        # the whole gather/attend/scatter body below, O(real context)
        # HBM traffic per chunk.
        from orion_tpu.ops.pallas.paged_flash_prefill import (
            paged_flash_prefill,
        )

        res = paged_flash_prefill(
            q, cc["k"], cc["v"], ctx["walk"], ctx["prefix_lens"],
            ctx["lengths"], k, v,
            n_prefix_pages=P_pre, layer_base=l * NP,
            logit_softcap=cfg.attn_logit_softcap,
            window=cfg.layer_window(j), interpret=ctx["interpret"],
            k_scale=cc.get("k_scale"), v_scale=cc.get("v_scale"),
            mesh=mesh,
        )
        cc = dict(cc)
        if quant:
            out, cc["k"], cc["v"], cc["k_scale"], cc["v_scale"] = res
        else:
            out, cc["k"], cc["v"] = res
        a = out_proj(out, bp["attn"], cfg, h)
        if cfg.post_norms:
            a = _norm(a, bp["post_attn_norm"], cfg, mesh)
        x = x + a
        h2 = _norm(x, bp["mlp_norm"], cfg, mesh)
        y, _ = mlp_or_moe(
            h2, bp, cfg, mesh, valid=seg > 0, layer_stack=layer_stack)
        _count_held_rows(cc, h2, bp, cfg, seg > 0)
        if cfg.post_norms:
            y = _norm(y, bp["post_mlp_norm"], cfg, mesh)
        return x + y, cc
    if P_pre:
        # Gather this layer's cached prefix K/V pages from the pool
        # and attend tail queries over prefix + tail. [Nb, P_pre] page
        # rows -> [Nb, P_pre*psz, K, H] (heads-major pages).
        Kh, Hd = k.shape[2], k.shape[3]
        rows_pre = l * NP + ctx["prefix_pages"]
        k_pre = cc["k"][rows_pre].transpose(0, 1, 3, 2, 4)
        v_pre = cc["v"][rows_pre].transpose(0, 1, 3, 2, 4)
        if quant:
            ksc = cc["k_scale"][rows_pre][..., :psz]   # [Nb,P,K,psz]
            vsc = cc["v_scale"][rows_pre][..., :psz]
            k_pre = k_pre.astype(jnp.float32) * ksc.transpose(
                0, 1, 3, 2)[..., None]
            v_pre = v_pre.astype(jnp.float32) * vsc.transpose(
                0, 1, 3, 2)[..., None]
        k_pre = k_pre.reshape(Nb, P_pre * psz, Kh, Hd).astype(k.dtype)
        v_pre = v_pre.reshape(Nb, P_pre * psz, Kh, Hd).astype(v.dtype)
        out = attention(
            q,
            jnp.concatenate([k_pre, k], axis=1),
            jnp.concatenate([v_pre, v], axis=1),
            causal=True,
            q_segment_ids=seg, kv_segment_ids=ctx["kv_seg"],
            seg_pad_zero=True,
            q_positions=positions, kv_positions=ctx["kv_pos"],
            logit_softcap=cfg.attn_logit_softcap,
            window=cfg.layer_window(j),
            block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv,
            impl=cfg.kernels, mesh=mesh,
        )
    else:
        out = attention(
            q, k, v, causal=True,
            q_segment_ids=seg, kv_segment_ids=seg, seg_pad_zero=True,
            logit_softcap=cfg.attn_logit_softcap,
            window=cfg.layer_window(j),
            block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv,
            impl=cfg.kernels, mesh=mesh,
        )
    a = out_proj(out, bp["attn"], cfg, h)
    if cfg.post_norms:
        a = _norm(a, bp["post_attn_norm"], cfg, mesh)
    x = x + a
    h2 = _norm(x, bp["mlp_norm"], cfg, mesh)
    # Padded positions are not routed: nothing reads their activations
    # (segment ids mask them in attention, their KV goes to the scratch
    # page, logits come off each row's last real position).
    y, _ = mlp_or_moe(
        h2, bp, cfg, mesh, valid=seg > 0, layer_stack=layer_stack)
    _count_held_rows(cc, h2, bp, cfg, seg > 0)
    if cfg.post_norms:
        y = _norm(y, bp["post_mlp_norm"], cfg, mesh)
    x = x + y
    # Scatter this layer's K/V pages into the pool (in-place on the
    # carried flat pool). Positions beyond each row's `length` hold
    # garbage from the padding — decode masks them out via seq_lens,
    # and the next real token overwrites its slot.
    K, H = k.shape[2], k.shape[3]
    rows = l * NP + ctx["pages"]                 # [Nb, n_pages]
    cc = dict(cc)
    if quant:
        from orion_tpu.infer.kv_cache import quantize_kv

        # Per (token, head) int8 + f32 scale; scale pages land in the
        # first psz columns of the lanes-padded scale pool rows.
        k, ks = quantize_kv(k)               # [Nb,S,K,H] i8, [Nb,S,K]
        v, vs = quantize_kv(v)
        kspg = ks.reshape(Nb, n_pages, psz, K).transpose(0, 1, 3, 2)
        vspg = vs.reshape(Nb, n_pages, psz, K).transpose(0, 1, 3, 2)
        cc["k_scale"] = cc["k_scale"].at[rows, :, :psz].set(kspg)
        cc["v_scale"] = cc["v_scale"].at[rows, :, :psz].set(vspg)
    # Pool pages are [K, psz, H] (heads major, see kv_cache.py).
    kpages = k.reshape(Nb, n_pages, psz, K, H).transpose(0, 1, 3, 2, 4)
    vpages = v.reshape(Nb, n_pages, psz, K, H).transpose(0, 1, 3, 2, 4)
    cc["k"] = cc["k"].at[rows].set(kpages)
    cc["v"] = cc["v"].at[rows].set(vpages)
    return x, cc


def _kind(cfg: ModelConfig, j: int):
    """The layer's kind for ``qkv_proj`` where a model's layers differ
    (``j`` static, ``_scan_layers``); None is the model's one kind."""
    return None if cfg.layer_plan is None else cfg.layer_kind(j)


HELD_ROWS = "held_expert_rows"


def _count_held_rows(cc: Cache, h2, bp, cfg: ModelConfig, valid) -> None:
    """Where ``prefill_step`` carries the counter (a model that holds a
    share of its experts), add this layer's routed rows on held experts."""
    if HELD_ROWS in cc and "moe" in bp:
        cc[HELD_ROWS] = cc[HELD_ROWS] + moe_lib.held_rows(
            h2, bp["moe"]["router"], cfg, valid)


def _prefill_logits(
    params: Params, x: jax.Array, lengths: jax.Array, cfg: ModelConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> jax.Array:
    """Next-token logits [Nb, V] off each row's last real position.

    Gathers before the LM head so the vocab matmul is [Nb, 1, V], not
    [Nb, S_pad, V]."""
    idx = (lengths - 1).astype(jnp.int32)[:, None, None]
    x_last = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[-1])), axis=1
    )
    return unembed(params, x_last, cfg, mesh)[:, 0]


def prefill_step(
    params: Params,
    cache: Cache,
    tokens: jax.Array,        # [Nb, S_pad]  (padded prompts, one bucket)
    lengths: jax.Array,       # [Nb] int32: true prompt lengths
    pages: jax.Array,         # [Nb, S_pad // page_size] int32 page ids
    prefix_lens: Optional[jax.Array] = None,   # [Nb] int32 cached tokens
    prefix_pages: Optional[jax.Array] = None,  # [Nb, P_pre] int32 page ids
    *,
    cfg: ModelConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
    paged_prefill: bool = False,
) -> tuple[jax.Array, Cache]:
    """Prefill a batch of same-bucket prompts in ONE dispatch.

    ``mesh`` (tensor-parallel serving) makes the flash kernel run under a
    head-sharded shard_map instead of gathering tp-sharded q/k/v; the
    dense matmuls partition from the params' shardings as usual.

    Prefix caching (``prefix_pages`` with static width P_pre > 0): rows
    start MID-SEQUENCE — ``tokens`` holds only the uncached tail,
    positions (RoPE / learned PE) begin at each row's ``prefix_lens``, and
    attention runs tail queries against the CACHED prefix K/V (gathered
    from the pool pages per layer) concatenated with the tail's own K/V.
    Explicit q/kv positions + segment ids carry the mid-sequence causal
    structure through both kernel paths (the flash kernel's segment
    masking skips all-padding prefix blocks for rows with shorter
    matches). With P_pre == 0 the program is byte-identical to the
    pre-prefix-cache prefill. The tail's page scatter is unchanged: cached
    prefixes are page-aligned, so tail token t keeps in-page offset
    ``t % page_size``. Chunked prefill (mixed_step) reuses this row type
    unchanged: a chunk is a tail prefill resuming at its chunk cursor.

    Returns (next-token logits [Nb, V], updated cache). Rows are independent
    sequences (separate page sets); a burst of admissions is served by a
    single program instead of Nb serialized dispatches (VERDICT r2 item 4).
    Padding rows (engine rounds the batch up to a bucket size) carry
    all-zero page lists: their K/V lands on the reserved scratch page 0 and
    is never read.
    """
    ctx = _prefill_ctx(
        params, cache, tokens, lengths, pages, prefix_lens, prefix_pages,
        cfg, paged_prefill=paged_prefill,
    )

    def body(carry, bp, l, j, stack=None):
        x, cc = carry
        return _prefill_layer(x, cc, bp, l, j, ctx, cfg, mesh, stack)

    x = embed(params, tokens, ctx["positions"], cfg)
    cache = dict(cache)
    if cfg.holds_expert_share:
        # Rides the layer scan beside the pool and leaves as a third
        # result: the engine's prefill_held_expert_rows counter.
        cache[HELD_ROWS] = jnp.zeros((), jnp.int32)
    x, cache = _scan_layers(params, cfg, body, (x, cache))
    logits = _prefill_logits(params, x, lengths, cfg, mesh)
    if cfg.holds_expert_share:
        return logits, cache, cache.pop(HELD_ROWS)
    return logits, cache


def _decode_ctx(
    cache: Cache,
    write_pos: jax.Array,
    page_table: jax.Array,
    cfg: ModelConfig,
) -> dict:
    """Batch-level tensors the per-layer decode body consumes."""
    B = write_pos.shape[0]
    kp = cache["k"]
    psz = kp.shape[2]
    NP = kp.shape[0] // cfg.n_layers
    P = page_table.shape[1]
    batch_idx = jnp.arange(B)
    page_idx = page_table[batch_idx, write_pos // psz]   # [B]
    offset = write_pos % psz                             # [B]
    # KV positions valid after the write: arange <= write_pos; the
    # (per-layer) sliding window narrows it inside the body.
    kv_arange = jnp.arange(P * psz, dtype=jnp.int32)[None, None, :]
    kv_base_mask = kv_arange <= write_pos[:, None, None]  # [B, 1, P*psz]

    from orion_tpu.ops._dispatch import resolve_impl

    use_pallas, interpret = resolve_impl(cfg.kernels)
    return dict(
        B=B, psz=psz, NP=NP, P=P, quant="k_scale" in cache,
        write_pos=write_pos, page_table=page_table,
        positions=write_pos[:, None], page_idx=page_idx, offset=offset,
        kv_arange=kv_arange, kv_base_mask=kv_base_mask,
        use_pallas=use_pallas, interpret=interpret,
    )


def _decode_layer(
    x: jax.Array,
    cc: Cache,
    bp: Any,
    l,
    j: int,
    ctx: dict,
    cfg: ModelConfig,
    mesh: Optional[jax.sharding.Mesh],
) -> tuple[jax.Array, Cache]:
    """One transformer layer of single-token decode: fused-write ragged
    paged attention (pallas) or scatter + masked pool gather (xla).

    LOCKSTEP: _verify_layer is this body generalized from 1 to W queries
    per slot, branch for branch (its pallas branch is the multi-query
    ragged paged-attention kernel, its xla branch this scatter+gather
    with a W dim), and speculative byte-identity (greedy spec-on ==
    spec-off, enforced by tests/test_spec_decode.py across kv_quant /
    SWA / prefix-cache compositions) holds only while the two agree
    op-for-op on the write/gather/dequant/mask math — fix both together.
    """
    B, psz, NP, P = ctx["B"], ctx["psz"], ctx["NP"], ctx["P"]
    quant = ctx["quant"]
    write_pos, page_table = ctx["write_pos"], ctx["page_table"]
    page_idx, offset = ctx["page_idx"], ctx["offset"]
    cc = dict(cc)
    win = cfg.layer_window(j)
    h = _norm(x, bp["attn_norm"], cfg, mesh)
    q, k, v = qkv_proj(
        h, bp["attn"], cfg, ctx["positions"], mesh, _kind(cfg, j))
    K, H = k.shape[2], k.shape[3]
    if ctx["use_pallas"]:
        # Ragged paged-attention kernel: walks the page table directly
        # (compute proportional to actual context lengths) and writes
        # the new token's K/V itself — the pool stays in place through
        # the kernel's input/output aliasing, where an external scatter
        # feeding the kernel would cost a pool copy per layer. Under
        # kv_quant the kernel also dequantizes in place and quantizes
        # the written token (scales aliased alongside).
        from orion_tpu.ops.pallas.paged_attention import paged_attention

        res = paged_attention(
            q[:, 0], cc["k"], cc["v"], page_table, write_pos,
            layer_base=l * NP,
            k_new=k[:, 0], v_new=v[:, 0],
            logit_softcap=cfg.attn_logit_softcap,
            window=win,
            interpret=ctx["interpret"],
            k_scale=cc.get("k_scale"),
            v_scale=cc.get("v_scale"),
            mesh=mesh,
        )
        if quant:
            out, cc["k"], cc["v"], cc["k_scale"], cc["v_scale"] = res
        else:
            out, cc["k"], cc["v"] = res
        out = out[:, None]
    else:
        rows = l * NP + page_idx
        if quant:
            from orion_tpu.infer.kv_cache import quantize_kv

            kq, ks = quantize_kv(k[:, 0])    # [B,K,H] i8, [B,K]
            vq, vs = quantize_kv(v[:, 0])
            cc["k"] = cc["k"].at[rows, :, offset].set(kq)
            cc["v"] = cc["v"].at[rows, :, offset].set(vq)
            cc["k_scale"] = cc["k_scale"].at[rows, :, offset].set(ks)
            cc["v_scale"] = cc["v_scale"].at[rows, :, offset].set(vs)
        else:
            cc["k"] = cc["k"].at[rows, :, offset].set(k[:, 0])
            cc["v"] = cc["v"].at[rows, :, offset].set(v[:, 0])
        # [B, P, K, psz, H] -> [B, P*psz, K, H] padded-context gather.
        k_ctx = cc["k"][l * NP + page_table].transpose(0, 1, 3, 2, 4)
        v_ctx = cc["v"][l * NP + page_table].transpose(0, 1, 3, 2, 4)
        if quant:
            # Dequantize the gathered context: [B, P, psz, K] scales.
            ksc = cc["k_scale"][l * NP + page_table][..., :psz]
            vsc = cc["v_scale"][l * NP + page_table][..., :psz]
            k_ctx = k_ctx.astype(jnp.float32) * ksc.transpose(
                0, 1, 3, 2)[..., None]
            v_ctx = v_ctx.astype(jnp.float32) * vsc.transpose(
                0, 1, 3, 2)[..., None]
            k_ctx = k_ctx.astype(q.dtype)
            v_ctx = v_ctx.astype(q.dtype)
        k_ctx = k_ctx.reshape(B, P * psz, K, H)
        v_ctx = v_ctx.reshape(B, P * psz, K, H)
        kv_mask = ctx["kv_base_mask"]
        if win is not None:
            kv_mask = kv_mask & (
                ctx["kv_arange"] >= (write_pos - win + 1)[:, None, None]
            )
        out = attention_xla(
            q, k_ctx, v_ctx, causal=False, mask=kv_mask,
            logit_softcap=cfg.attn_logit_softcap,
        )
    a = out_proj(out, bp["attn"], cfg, h)
    if cfg.post_norms:
        a = _norm(a, bp["post_attn_norm"], cfg, mesh)
    x = x + a
    h2 = _norm(x, bp["mlp_norm"], cfg, mesh)
    y, _ = mlp_or_moe(h2, bp, cfg)
    if cfg.post_norms:
        y = _norm(y, bp["post_mlp_norm"], cfg, mesh)
    return x + y, cc


def _decode_core(
    params: Params,
    cache: Cache,
    tokens: jax.Array,        # [B] newest token per slot
    write_pos: jax.Array,     # [B] int32 position being written/attended
    page_table: jax.Array,    # [B, pages_per_seq] int32 (per-layer-relative)
    cfg: ModelConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> tuple[jax.Array, Cache]:
    """One decode forward for every slot -> (logits [B, V], cache')."""
    ctx = _decode_ctx(cache, write_pos, page_table, cfg)

    def body(carry, bp, l, j, stack=None):
        x, cc = carry
        return _decode_layer(x, cc, bp, l, j, ctx, cfg, mesh)

    x = embed(params, tokens[:, None], ctx["positions"], cfg)
    x, cache = _scan_layers(params, cfg, body, (x, dict(cache)))
    logits = unembed(params, x, cfg, mesh)    # [B, 1, V]
    return logits[:, 0], cache


def decode_window(
    params: Params,
    cache: Cache,
    tokens: jax.Array,        # [B] newest token per slot
    seq_lens: jax.Array,      # [B] int32
    page_table: jax.Array,    # [B, pages_per_seq] int32
    active: jax.Array,        # [B] bool: slot holds a live request
    keys: jax.Array,          # [W] PRNG keys, one per inner step
    temperature: jax.Array,   # [B] f32 per-request (vLLM-style params)
    top_k: jax.Array,         # [B] i32
    top_p: jax.Array,         # [B] f32
    cfg: ModelConfig,
    max_seq_len: int,
    mesh: Optional[jax.sharding.Mesh] = None,
    nan_guard: bool = False,
) -> tuple[jax.Array, ...]:
    """W fused decode+sample steps; returns (tokens [W, B] int32, cache).

    The engine fetches the whole [W, B] token block once per window and does
    its bookkeeping (EOS, max_new, admission) on the host afterwards; slots
    that finish mid-window keep decoding garbage the host discards — wasted
    FLOPs traded for W-fold fewer host round-trips. Slots advance only while
    ``active`` and within the context window; frozen slots clamp their
    write position to max_seq_len - 1 (their own last slot — garbage there
    is unreachable because the host has already finished them).

    With ``nan_guard`` the return is ``(tokens, ok, cache)``: ``ok`` [B]
    bool is per-slot "every live inner step's logits were finite" — the
    engine quarantines slots that trip it. Guard off keeps the carry and
    trace exactly the pre-guard program.
    """
    from orion_tpu.infer.sampling import sample

    def stepf(carry, sub):
        if nan_guard:
            tok, sl, ok, cc = carry
        else:
            tok, sl, cc = carry
        act = active & (sl < max_seq_len)
        wp = jnp.minimum(sl, max_seq_len - 1)
        logits, cc = _decode_core(params, cc, tok, wp, page_table, cfg, mesh)
        toks = sample(
            logits, sub, temperature=temperature, top_k=top_k, top_p=top_p
        )
        tok = jnp.where(act, toks, tok)
        sl = sl + act.astype(sl.dtype)
        if nan_guard:
            ok = ok & (jnp.isfinite(logits).all(-1) | ~act)
            return (tok, sl, ok, cc), toks
        return (tok, sl, cc), toks

    if nan_guard:
        init = (
            tokens, seq_lens, jnp.ones_like(active, dtype=bool), dict(cache)
        )
        (_, _, ok, cache), toks = jax.lax.scan(stepf, init, keys)
        return toks, ok, cache
    (_, _, cache), toks = jax.lax.scan(
        stepf, (tokens, seq_lens, dict(cache)), keys
    )
    return toks, cache


def _verify_ctx(
    cache: Cache,
    seq_lens: jax.Array,      # [B] accepted-token cursor per slot
    lens: jax.Array,          # [B] real verify tokens this row (1..W)
    page_table: jax.Array,    # [B, pages_per_seq]
    active: jax.Array,        # [B] bool
    W: int,
    max_seq_len: int,
    cfg: ModelConfig,
    depths: Optional[jax.Array] = None,     # [B, W] tree depth per column
    tree_mask: Optional[jax.Array] = None,  # [B, W] packed ancestor words
) -> dict:
    """Batch-level tensors for the verify body (speculative decoding).

    Row b holds ``lens[b]`` real tokens — the pending last token plus its
    drafts — writing KV at positions ``seq_lens[b] + j``. Unlike prefill
    chunks these start MID-PAGE (the cursor is arbitrary), so per-token
    (page, offset) pairs come from the page table exactly as decode's do;
    unlike decode there are W of them per row. Padding positions (j >=
    lens, inactive rows, past max_seq_len) scatter to scratch page 0 on
    the xla branch — never clamped onto a real page, so a row near the
    context limit cannot clobber its own final KV slot the way a clamp
    would; the pallas kernel excludes them from its in-kernel merge
    instead. Both leave every real page untouched.

    Token trees (``depths``/``tree_mask`` given, inference.spec_tree_width
    > 1): column j still WRITES its KV at pool position ``seq_lens + j``
    (slot-sequential — page provisioning and the fused write are
    layout-identical to the chain), but its LOGICAL position (RoPE,
    causal/window structure) is ``seq_lens + depths[b, j]`` and it
    attends, among the W new columns, exactly the columns whose bits are
    set in ``tree_mask[b, j]`` (its ancestors, the root, itself) instead
    of every earlier column. Chain-shaped inputs (depths == steps, words
    == the causal prefix bits) produce bit-identical masks to the
    position-order formulation, so the degenerate tree IS today's
    verify; with both None this function is untouched (same trace).
    """
    B = seq_lens.shape[0]
    kp = cache["k"]
    psz = kp.shape[2]
    NP = kp.shape[0] // cfg.n_layers
    P = page_table.shape[1]
    batch_idx = jnp.arange(B)[:, None]
    steps = jnp.arange(W, dtype=jnp.int32)[None, :]
    tree = tree_mask is not None
    assert (depths is None) == (tree_mask is None)
    # WRITE positions are always slot-sequential (cursor + column).
    write_pos = seq_lens[:, None] + steps                   # [B, W] true
    wp = jnp.minimum(write_pos, max_seq_len - 1)            # in-bounds
    valid = (
        active[:, None] & (steps < lens[:, None])
        & (write_pos < max_seq_len)
    )
    page_idx = jnp.where(
        valid, page_table[batch_idx, wp // psz], 0
    )                                                       # [B, W]
    offset = wp % psz
    kv_arange = jnp.arange(P * psz, dtype=jnp.int32)[None, None, :]
    if not tree:
        # Chain: logical position == write position; each query attends
        # everything at or before its own position (earlier drafts of
        # the same dispatch included — they sit at seq_lens..q_pos).
        q_pos = write_pos
        rope_pos = wp
        kv_base_mask = kv_arange <= q_pos[:, :, None]
        in_slots = slot_depth = None
    else:
        q_pos = seq_lens[:, None] + depths.astype(jnp.int32)
        rope_pos = jnp.minimum(q_pos, max_seq_len - 1)
        # Committed context (below the cursor) is visible to every
        # query; the W new columns are visible by ancestor bit.
        slot_idx = kv_arange - seq_lens[:, None, None]      # [B, 1, P*psz]
        in_slots = (slot_idx >= 0) & (slot_idx < W)
        anc = (
            jnp.right_shift(
                tree_mask.astype(jnp.int32)[:, :, None],
                steps[None, :, :],
            )
            & 1
        ).astype(bool)                                      # [B, W(q), W(kv)]
        anc = anc | jnp.eye(W, dtype=bool)[None]            # self-visibility
        slot_c = jnp.clip(slot_idx, 0, W - 1)
        vis_new = jnp.take_along_axis(
            anc, jnp.broadcast_to(slot_c, (B, W, P * psz)), axis=2
        )
        # Per-kv-position slot depth (for the sliding-window test among
        # new columns, which windows DEPTH, not pool offset).
        slot_depth = jnp.take_along_axis(
            jnp.broadcast_to(
                depths.astype(jnp.int32)[:, None, :], (B, 1, W)
            ),
            slot_c, axis=2,
        )                                                   # [B, 1, P*psz]
        kv_base_mask = jnp.where(
            in_slots, vis_new, kv_arange < seq_lens[:, None, None]
        )

    from orion_tpu.ops._dispatch import resolve_impl

    use_pallas, interpret = resolve_impl(cfg.kernels)
    # Ragged-kernel view of the same layout: the cursor and a real-token
    # count clamped so start + lens - 1 stays inside the context — for
    # live rows the engine already guarantees it (drafts are capped at
    # max_seq_len - 1 - cursor), so the clamp is an identity there;
    # inactive/mid-prefill rows carry all-zero page-table rows and land
    # on the scratch page, the same sink the XLA body's `valid` redirect
    # uses.
    start = jnp.minimum(seq_lens, max_seq_len - 1).astype(jnp.int32)
    k_lens = jnp.clip(jnp.minimum(lens, max_seq_len - start), 1, W)
    return dict(
        B=B, W=W, psz=psz, NP=NP, P=P, quant="k_scale" in cache,
        page_table=page_table, positions=rope_pos, q_pos=q_pos,
        page_idx=page_idx, offset=offset,
        kv_arange=kv_arange, kv_base_mask=kv_base_mask,
        start=start, k_lens=k_lens,
        depths=depths, tree_mask=tree_mask,
        in_slots=in_slots, slot_depth=slot_depth,
        use_pallas=use_pallas, interpret=interpret,
    )


def _verify_layer(
    x: jax.Array,
    cc: Cache,
    bp: Any,
    l,
    j: int,
    ctx: dict,
    cfg: ModelConfig,
    mesh: Optional[jax.sharding.Mesh],
) -> tuple[jax.Array, Cache]:
    """One transformer layer of batched draft verification: the decode
    body generalized from one query to W per slot — every draft
    position's K/V lands in the pool first (quantized under kv_quant,
    exactly as a sequential decode would have written it), then each
    query attends the context up to its own position. One pass over this
    layer's weights serves all W positions of all slots; position i's
    logits therefore match the i-th sequential decode step's bit-for-bit,
    which is what makes greedy acceptance exact.

    Pallas branch: the multi-query ragged paged-attention kernel
    (ops/pallas/ragged_paged_attention.py) — the fused-write W=1 decode
    kernel generalized to W ragged queries, writing all lens[b] drafts'
    K/V in-kernel (aliased pools, quantized in-kernel under kv_quant with
    the shared common.quantize_kv, so its written bytes match this body's
    xla scatter bit-for-bit). XLA branch: scatter + masked padded-context
    gather, the reference.

    LOCKSTEP: this is _decode_layer with a W dimension, branch for
    branch — any change to either body's write/gather/dequant/mask math
    must land in both, or the greedy spec-on == spec-off equivalence
    suite (tests/test_spec_decode.py) fails."""
    B, W, psz, NP, P = ctx["B"], ctx["W"], ctx["psz"], ctx["NP"], ctx["P"]
    quant = ctx["quant"]
    page_table = ctx["page_table"]
    page_idx, offset = ctx["page_idx"], ctx["offset"]
    cc = dict(cc)
    win = cfg.layer_window(j)
    h = _norm(x, bp["attn_norm"], cfg, mesh)
    q, k, v = qkv_proj(
        h, bp["attn"], cfg, ctx["positions"], mesh, _kind(cfg, j))
    K, H = k.shape[2], k.shape[3]
    if ctx["use_pallas"]:
        # Multi-query ragged paged attention: one kernel walks each
        # slot's pages once for all W queries (page DMAs amortized W×),
        # writes every real draft's K/V in place through the aliased
        # pools, and masks queries causally among the W new positions —
        # the verify step stops being the one step type that abandons
        # the fused kernels. Rows with all-zero page-table entries
        # (inactive / mid-prefill slots) read and write only the
        # reserved scratch page, like the xla branch's `valid` redirect.
        from orion_tpu.ops.pallas.ragged_paged_attention import (
            ragged_paged_attention,
        )

        res = ragged_paged_attention(
            q, cc["k"], cc["v"], page_table, ctx["start"], ctx["k_lens"],
            layer_base=l * NP,
            k_new=k, v_new=v,
            logit_softcap=cfg.attn_logit_softcap,
            window=win,
            interpret=ctx["interpret"],
            k_scale=cc.get("k_scale"),
            v_scale=cc.get("v_scale"),
            tree_mask=ctx["tree_mask"],
            depths=ctx["depths"],
            mesh=mesh,
        )
        if quant:
            out, cc["k"], cc["v"], cc["k_scale"], cc["v_scale"] = res
        else:
            out, cc["k"], cc["v"] = res
    else:
        rows = l * NP + page_idx                   # [B, W]
        if quant:
            from orion_tpu.infer.kv_cache import quantize_kv

            kq, ks = quantize_kv(k)                # [B,W,K,H] i8, [B,W,K]
            vq, vs = quantize_kv(v)
            cc["k"] = cc["k"].at[rows, :, offset].set(kq)
            cc["v"] = cc["v"].at[rows, :, offset].set(vq)
            cc["k_scale"] = cc["k_scale"].at[rows, :, offset].set(ks)
            cc["v_scale"] = cc["v_scale"].at[rows, :, offset].set(vs)
        else:
            cc["k"] = cc["k"].at[rows, :, offset].set(k)
            cc["v"] = cc["v"].at[rows, :, offset].set(v)
        # [B, P, K, psz, H] -> [B, P*psz, K, H] padded-context gather (the
        # just-written draft K/V reads back out of the pool, so under
        # kv_quant each query attends its drafts DEQUANTIZED — the decode
        # path's exact numerics).
        k_ctx = cc["k"][l * NP + page_table].transpose(0, 1, 3, 2, 4)
        v_ctx = cc["v"][l * NP + page_table].transpose(0, 1, 3, 2, 4)
        if quant:
            ksc = cc["k_scale"][l * NP + page_table][..., :psz]
            vsc = cc["v_scale"][l * NP + page_table][..., :psz]
            k_ctx = k_ctx.astype(jnp.float32) * ksc.transpose(
                0, 1, 3, 2)[..., None]
            v_ctx = v_ctx.astype(jnp.float32) * vsc.transpose(
                0, 1, 3, 2)[..., None]
            k_ctx = k_ctx.astype(q.dtype)
            v_ctx = v_ctx.astype(q.dtype)
        k_ctx = k_ctx.reshape(B, P * psz, K, H)
        v_ctx = v_ctx.reshape(B, P * psz, K, H)
        kv_mask = ctx["kv_base_mask"]
        if win is not None:
            wmask = (
                ctx["kv_arange"] >= (ctx["q_pos"] - win + 1)[:, :, None]
            )
            if ctx["tree_mask"] is not None:
                # Among the W new columns the window measures DEPTH
                # distance (logical positions), not pool-slot distance —
                # chain-degenerate trees make the two identical.
                dmask = ctx["slot_depth"] >= (
                    ctx["depths"].astype(jnp.int32) - win + 1
                )[:, :, None]
                wmask = jnp.where(ctx["in_slots"], dmask, wmask)
            kv_mask = kv_mask & wmask
        out = attention_xla(
            q, k_ctx, v_ctx, causal=False, mask=kv_mask,
            logit_softcap=cfg.attn_logit_softcap,
        )
    a = out_proj(out, bp["attn"], cfg, h)
    if cfg.post_norms:
        a = _norm(a, bp["post_attn_norm"], cfg, mesh)
    x = x + a
    h2 = _norm(x, bp["mlp_norm"], cfg, mesh)
    y, _ = mlp_or_moe(h2, bp, cfg)
    if cfg.post_norms:
        y = _norm(y, bp["post_mlp_norm"], cfg, mesh)
    return x + y, cc


def _draft_next(tokens: jax.Array, lens: jax.Array) -> jax.Array:
    """[B, W] draft-under-check per logits position: position j's logits
    predict the token at j+1, so they check ``tokens[:, j+1]`` — or
    nothing (-1: the row's bonus/correction position, and all padding)."""
    B, W = tokens.shape
    shifted = jnp.concatenate(
        [tokens[:, 1:], jnp.full((B, 1), -1, jnp.int32)], axis=1
    )
    steps = jnp.arange(W, dtype=jnp.int32)[None, :]
    return jnp.where(steps + 1 < lens[:, None], shifted, -1)


def verify_step(
    params: Params,
    cache: Cache,
    tokens: jax.Array,        # [B, W]: pending last token + its drafts
    seq_lens: jax.Array,      # [B] int32 accepted-token cursor
    lens: jax.Array,          # [B] int32 real verify tokens (1..W)
    page_table: jax.Array,    # [B, pages_per_seq] int32
    active: jax.Array,        # [B] bool: slot holds a live decode request
    key: jax.Array,           # PRNG key (sampled acceptance draws)
    temperature: jax.Array,   # [B] f32 per-request sampling params
    top_k: jax.Array,         # [B] i32   (python scalars for the all-
    top_p: jax.Array,         # [B] f32    defaults greedy specialization)
    cfg: ModelConfig,
    max_seq_len: int,
    mesh: Optional[jax.sharding.Mesh] = None,
    nan_guard: bool = False,
    depths: Optional[jax.Array] = None,     # [B, W] tree depth per column
    parents: Optional[jax.Array] = None,    # [B, W] parent column per col
    tree_mask: Optional[jax.Array] = None,  # [B, W] packed ancestor words
    legal_mask: Optional[jax.Array] = None,  # [B, W, V] constraint masks
) -> tuple[jax.Array, ...]:
    """Score K drafts for EVERY live slot in ONE dispatch (speculative
    decoding's verification half; drafting is infer/spec_decode.py).

    Structurally the [W, B] decode-window shape turned sideways: W = max
    drafts + 1 positions per slot in a single forward pass instead of W
    sequential passes — ONE pass over the weights emits up to W tokens per
    slot, which is the whole speculative bargain. Per-slot real lengths
    ride in ``lens`` (the dispatch width is static at speculate_tokens+1;
    shorter rows pad, and padding positions write to scratch page 0).
    Draft KV is written INTO the paged pool as it goes — accepted
    positions' KV is already in place, so acceptance costs nothing; the
    engine rewinds rejected positions afterwards (cursor retreat + page
    release, kv_cache.rollback_pages) and the garbage beyond the rewound
    cursor is masked by seq_lens exactly like decode-window overshoot.

    Returns ``(accept [B, W] bool, alt [B, W] int32, cache)`` — the
    per-position acceptance verdicts and fallback tokens of
    sampling.spec_verify_sample; the engine walks each row to its first
    rejection and emits ``accepted drafts + one bonus/correction token``.

    The body follows the decode step's resolve_impl switch: under
    kernels='pallas' each layer runs the multi-query ragged
    paged-attention kernel (page walk + in-kernel fused write for all W
    positions — the pool gather never materializes, and the page DMAs
    amortize over the W queries); under 'xla' it is the decode body's
    scatter + masked gather with a W dimension, kept as the reference.
    Either way the per-position logits match sequential decode on the
    same kernel setting bit-for-bit.

    Token trees (``depths``/``parents``/``tree_mask`` given): columns
    1..lens-1 hold a flattened DraftTree instead of a chain — writes
    stay slot-sequential, attention follows the ancestor mask, and
    acceptance becomes the CHILD-indexed tree walk of
    ``sampling.spec_verify_sample_tree``. With all three None this is
    bit-for-bit the chain program.

    ``legal_mask`` (constrained decoding, [B, W, V] bool): the host
    precomputes position j's legal-token bitmask by walking the FSM
    along the row's draft prefix (chain) or ancestor path (tree) — the
    states are known before dispatch because the drafts are — and the
    mask composes into the SAME filtered target the acceptance math
    already uses. ``None`` keeps this the unconstrained trace (its own
    jit specialization), which is what the byte-identity pin tests.
    """
    from orion_tpu.infer.sampling import (
        spec_verify_sample,
        spec_verify_sample_tree,
    )

    W = tokens.shape[1]
    ctx = _verify_ctx(
        cache, seq_lens, lens, page_table, active, W, max_seq_len, cfg,
        depths=depths, tree_mask=tree_mask,
    )

    def body(carry, bp, l, j, stack=None):
        x, cc = carry
        return _verify_layer(x, cc, bp, l, j, ctx, cfg, mesh)

    x = embed(params, tokens, ctx["positions"], cfg)
    x, cache = _scan_layers(params, cfg, body, (x, dict(cache)))
    logits = unembed(params, x, cfg, mesh)                 # [B, W, V]
    if parents is None:
        accept, alt = spec_verify_sample(
            logits, _draft_next(tokens, lens), key,
            temperature=temperature, top_k=top_k, top_p=top_p,
            legal_mask=legal_mask,
        )
    else:
        accept, alt = spec_verify_sample_tree(
            logits, tokens, parents, lens, key,
            temperature=temperature, top_k=top_k, top_p=top_p,
            legal_mask=legal_mask,
        )
    if nan_guard:
        # Per-slot finite check over the row's REAL positions only (padding
        # positions compute on scratch-page garbage by design).
        steps = jnp.arange(W, dtype=jnp.int32)[None, :]
        valid = active[:, None] & (steps < lens[:, None])
        ok = jnp.where(valid, jnp.isfinite(logits).all(-1), True).all(-1)
        return accept, alt, ok, cache
    return accept, alt, cache


def mixed_step(
    params: Params,
    cache: Cache,
    tokens: jax.Array,        # [B] newest token per decode slot
    seq_lens: jax.Array,      # [B] int32
    page_table: jax.Array,    # [B, pages_per_seq] int32; mid-prefill slots
    #                           carry all-zero rows (their write -> scratch)
    active: jax.Array,        # [B] bool: slot holds a DECODING request
    key: jax.Array,           # PRNG key for the decode sample
    p_tokens: jax.Array,      # [Nc, S_chunk] prompt-chunk tail tokens
    p_lengths: jax.Array,     # [Nc] int32: true chunk lengths
    p_pages: jax.Array,       # [Nc, S_chunk // psz] pages the chunk writes
    p_prefix_lens: jax.Array, # [Nc] int32: context tokens already in cache
    p_prefix_pages: jax.Array,  # [Nc, P_pre] pages holding that context
    temperature: jax.Array,   # [B] f32 per-request decode sampling params
    top_k: jax.Array,         # [B] i32   (python scalars for the all-
    top_p: jax.Array,         # [B] f32    defaults greedy specialization)
    *,
    cfg: ModelConfig,
    max_seq_len: int,
    mesh: Optional[jax.sharding.Mesh] = None,
    nan_guard: bool = False,
    paged_prefill: bool = False,
) -> tuple[jax.Array, ...]:
    """One UNIFIED mixed prefill+decode step (inference.chunked_prefill):
    a single-token decode for every live slot fused with up to the chunk
    budget of prompt-tail tokens, in ONE dispatch.

    Returns ``(decode_tokens [B], chunk_logits [Nc, V], cache)``.

    Each layer runs the decode body (fused-write ragged paged attention —
    the same math as ``decode_window`` with W=1, so the greedy decode
    stream is bit-identical to unchunked serving; sampled decode matches
    a decode_window=1 engine at equal PRNG state, while W>1 windows group
    key splits differently) and the prefill body (a
    prefill chunk is exactly the prefix-cache mid-sequence tail prefill:
    resume at a page-aligned ``p_prefix_lens`` over the pages earlier
    chunks already wrote, flash attention with per-row segment ids
    skipping padding blocks) over the SAME carried pool and the SAME
    block params — one pass over the weights serves both, which is the
    MBU point of mixing: bandwidth-bound decode and compute-bound prefill
    share the chip instead of alternating. Chunk rows and decode rows
    touch disjoint pages (a slot is either decoding or prefilling, and
    mid-prefill slots' decode rows are masked onto scratch page 0 by the
    engine), so the two in-place pool updates commute.

    ``chunk_logits`` holds every chunk row's last-position logits; the
    host samples only the rows whose prompt just completed (fetching the
    array lazily, so non-finishing steps never pay the [Nc, V] transfer).
    """
    from orion_tpu.infer.sampling import sample

    if not nan_guard:
        del active  # host-side bookkeeping filters; kept for decode parity
    wp = jnp.minimum(seq_lens, max_seq_len - 1)
    pctx = _prefill_ctx(
        params, cache, p_tokens, p_lengths, p_pages, p_prefix_lens,
        p_prefix_pages, cfg, paged_prefill=paged_prefill,
    )
    dctx = _decode_ctx(cache, wp, page_table, cfg)

    def body(carry, bp, l, j, stack=None):
        xp, xd, cc = carry
        xp, cc = _prefill_layer(xp, cc, bp, l, j, pctx, cfg, mesh, stack)
        xd, cc = _decode_layer(xd, cc, bp, l, j, dctx, cfg, mesh)
        return xp, xd, cc

    xp = embed(params, p_tokens, pctx["positions"], cfg)
    xd = embed(params, tokens[:, None], dctx["positions"], cfg)
    xp, xd, cache = _scan_layers(params, cfg, body, (xp, xd, dict(cache)))
    # Two unembed calls, not one over a concat: the decode half must stay
    # op-for-op identical to decode_window's so its tokens are bitwise
    # unchanged by the rider chunk rows.
    d_logits = unembed(params, xd, cfg, mesh)[:, 0]      # [B, V]
    toks = sample(
        d_logits, key, temperature=temperature, top_k=top_k, top_p=top_p
    )
    p_logits = _prefill_logits(params, xp, p_lengths, cfg, mesh)
    if nan_guard:
        ok = jnp.isfinite(d_logits).all(-1) | ~active
        return toks, ok, p_logits, cache
    return toks, p_logits, cache


def mixed_verify_step(
    params: Params,
    cache: Cache,
    tokens: jax.Array,        # [B, W]: pending last token + drafts per slot
    seq_lens: jax.Array,      # [B] int32 accepted-token cursor
    lens: jax.Array,          # [B] int32 real verify tokens (1..W)
    page_table: jax.Array,    # [B, pages_per_seq] int32; mid-prefill slots
    #                           carry all-zero rows (their writes -> scratch)
    active: jax.Array,        # [B] bool: slot holds a DECODING request
    key: jax.Array,           # PRNG key (sampled acceptance draws)
    p_tokens: jax.Array,      # [Nc, S_chunk] prompt-chunk tail tokens
    p_lengths: jax.Array,     # [Nc] int32: true chunk lengths
    p_pages: jax.Array,       # [Nc, S_chunk // psz] pages the chunk writes
    p_prefix_lens: jax.Array, # [Nc] int32: context tokens already in cache
    p_prefix_pages: jax.Array,  # [Nc, P_pre] pages holding that context
    temperature: jax.Array,   # [B] f32 per-request decode sampling params
    top_k: jax.Array,         # [B] i32
    top_p: jax.Array,         # [B] f32
    *,
    cfg: ModelConfig,
    max_seq_len: int,
    mesh: Optional[jax.sharding.Mesh] = None,
    nan_guard: bool = False,
    paged_prefill: bool = False,
    depths: Optional[jax.Array] = None,     # [B, W] tree depth per column
    parents: Optional[jax.Array] = None,    # [B, W] parent column per col
    tree_mask: Optional[jax.Array] = None,  # [B, W] packed ancestor words
    legal_mask: Optional[jax.Array] = None,  # [B, W, V] constraint masks
) -> tuple[jax.Array, ...]:
    """``mixed_step`` with the decode half replaced by the verify body:
    speculative decoding composed with chunked prefill. One dispatch runs
    up to the chunk budget of prompt tail (prompt-phase slots — they skip
    drafting by construction, their prompts ARE the chunk rows) AND a
    W-position draft verification for every decoding slot, over the same
    carried pool and the same pass over the weights.

    Returns ``(accept [B, W], alt [B, W], chunk_logits [Nc, V], cache)``.
    Chunk rows and verify rows touch disjoint pages for the same reason
    mixed_step's halves do: a slot is either prefilling (its verify row is
    masked onto scratch by the engine's zeroed page-table copy) or
    decoding (its pages are not in any chunk row), so the in-place pool
    updates commute.
    """
    from orion_tpu.infer.sampling import (
        spec_verify_sample,
        spec_verify_sample_tree,
    )

    W = tokens.shape[1]
    pctx = _prefill_ctx(
        params, cache, p_tokens, p_lengths, p_pages, p_prefix_lens,
        p_prefix_pages, cfg, paged_prefill=paged_prefill,
    )
    vctx = _verify_ctx(
        cache, seq_lens, lens, page_table, active, W, max_seq_len, cfg,
        depths=depths, tree_mask=tree_mask,
    )

    def body(carry, bp, l, j, stack=None):
        xp, xv, cc = carry
        xp, cc = _prefill_layer(xp, cc, bp, l, j, pctx, cfg, mesh, stack)
        xv, cc = _verify_layer(xv, cc, bp, l, j, vctx, cfg, mesh)
        return xp, xv, cc

    xp = embed(params, p_tokens, pctx["positions"], cfg)
    xv = embed(params, tokens, vctx["positions"], cfg)
    xp, xv, cache = _scan_layers(params, cfg, body, (xp, xv, dict(cache)))
    logits = unembed(params, xv, cfg, mesh)                # [B, W, V]
    if parents is None:
        accept, alt = spec_verify_sample(
            logits, _draft_next(tokens, lens), key,
            temperature=temperature, top_k=top_k, top_p=top_p,
            legal_mask=legal_mask,
        )
    else:
        accept, alt = spec_verify_sample_tree(
            logits, tokens, parents, lens, key,
            temperature=temperature, top_k=top_k, top_p=top_p,
            legal_mask=legal_mask,
        )
    p_logits = _prefill_logits(params, xp, p_lengths, cfg, mesh)
    if nan_guard:
        steps = jnp.arange(W, dtype=jnp.int32)[None, :]
        valid = active[:, None] & (steps < lens[:, None])
        ok = jnp.where(valid, jnp.isfinite(logits).all(-1), True).all(-1)
        return accept, alt, ok, p_logits, cache
    return accept, alt, p_logits, cache
