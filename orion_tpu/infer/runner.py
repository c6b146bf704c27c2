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

The layer itself is ``models.transformer.block``, the one body training
runs too: this module only supplies what attention reads and where K/V go,
as two cache backends. ``_dense_layer`` (prefill, and the chunk rows of a
mixed step) attends a block of new tokens over [gathered prefix + own K/V]
and writes whole pages; ``_paged_layer`` writes W new tokens a slot into the
pool and attends over it — ``verify_step`` at W = speculate_tokens + 1, the
decode window's step at W = 1 (``_decode_core``), so a drafted position and
a decoded one are computed by the same code and agree bit for bit.
Inactive batch slots point at the reserved scratch page 0 and are masked by
seq_lens alone — no dynamic batch shapes anywhere.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from orion_tpu.config import ModelConfig
from orion_tpu.infer.kv_cache import (
    COMPRESSED,
    KDA_CONV,
    KDA_STATE,
    LATENT,
    LIGHTNING_STATE,
    RING_K,
    RING_V,
    pack_keys,
    pack_queries,
    page_geometry,
    unpack_keys,
)
from orion_tpu.models import moe as moe_lib
from orion_tpu.models.transformer import (
    Params,
    block,
    embed,
    kda_activate,
    latent_absorb,
    latent_attention,
    latent_unabsorb,
    scan_layer_plan,
    unembed,
)
from orion_tpu.ops import attention
from orion_tpu.ops.attention import attention_xla

Cache = dict[str, jax.Array]
# The part (``orion_tpu.obs.parts``) of a cache write that a layer's ``attend``
# hands back for its caller to trace behind the feed-forward, outside the
# layer's own ``attention`` scope.
_CACHE_PART = "attention/cache"
# A kernel with a fused write hands the pools back in this order (the scale
# pools only where the cache is int8).
_POOLS = ("k", "v", "k_scale", "v_scale")


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
    psz, NP = page_geometry(cache, cfg.n_paged_layers)
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
    return dict(
        moe_stack=_moe_stack(params, cfg), psz=psz, NP=NP,
        P_pre=P_pre, positions=positions, seg=seg,
        kv_pos=kv_pos, kv_seg=kv_seg, pages=pages,
        prefix_pages=prefix_pages, prefix_lens=prefix_lens,
        lengths=lengths, paged=paged, interpret=interpret, walk=walk,
    )


def _moe_stack(params: Params, cfg: ModelConfig):
    """The layer-stacked expert weights where the layer scan slices ONE stack
    [L, ...] (no window pattern, no layer plan), else None."""
    if (cfg.is_moe and cfg.scan_layers and cfg.window_pattern is None
            and cfg.layer_plan is None):
        return params["blocks"]["moe"]
    return None


def _dense_layer(
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
    """The dense backend: one layer of (possibly mid-sequence) prefill.
    Attention is flash/xla over [gathered prefix pages + own K/V] with the
    new K/V pages scattered into the carried pool, or the paged-flash kernel
    that does both; the layer itself is ``transformer.block``. ``stack``:
    ``_scan_layers``' (a model with a layer plan; else the one stack of
    ``ctx``)."""
    psz, NP, P_pre = ctx["psz"], ctx["NP"], ctx["P_pre"]
    positions, seg = ctx["positions"], ctx["seg"]
    win = cfg.layer_window(j)

    def attend(q, k, v):
        """-> (out, the pools this layer writes, not yet traced: the page
        scatter stays behind the feed-forward, where the compiled prefill
        has always had it)."""
        if P_pre and ctx["paged"]:
            # Paged-flash prefill: the chunk's queries walk the paged history
            # in-kernel (no dense prefix gather) and the chunk's own pages
            # are written fused (no external scatter) — one kernel replaces
            # the whole gather/attend/scatter below, O(real context) HBM
            # traffic per chunk.
            from orion_tpu.ops.pallas.paged_flash_prefill import (
                paged_flash_prefill,
            )

            with jax.named_scope("kernel"):
                out, *pools = paged_flash_prefill(
                    q, cc["k"], cc["v"], ctx["walk"], ctx["prefix_lens"],
                    ctx["lengths"], k, v,
                    n_prefix_pages=P_pre, layer_base=l * NP,
                    logit_softcap=cfg.attn_logit_softcap,
                    window=win, interpret=ctx["interpret"],
                    k_scale=cc.get("k_scale"), v_scale=cc.get("v_scale"),
                    mesh=mesh,
                )
            return out, lambda: dict(zip(_POOLS, pools))
        kv, kv_seg, kv_at = (k, v), seg, {}
        if P_pre:
            # Gather this layer's cached prefix K/V pages from the pool
            # and attend tail queries over prefix + tail. [Nb, P_pre] page
            # rows -> [Nb, P_pre*psz, K, H] (heads-major pages).
            with jax.named_scope("cache"):
                k_pre, v_pre = _gather_context(
                    cc, l * NP + ctx["prefix_pages"], psz, k.dtype)
                kv = (jnp.concatenate([k_pre, k], axis=1),
                      jnp.concatenate([v_pre, v], axis=1))
            kv_seg = ctx["kv_seg"]
            kv_at = dict(q_positions=positions, kv_positions=ctx["kv_pos"])
        with jax.named_scope("kernel"):
            out = attention(
                q, *kv, causal=True,
                q_segment_ids=seg, kv_segment_ids=kv_seg, seg_pad_zero=True,
                **kv_at,
                logit_softcap=cfg.attn_logit_softcap, window=win,
                impl=cfg.kernels, mesh=mesh, block=cfg.block_length,
            )
        return out, lambda: _scatter_pages(cc, k, v, l * NP + ctx["pages"])

    # Padded positions are not routed: nothing reads their activations
    # (segment ids mask them in attention, their KV goes to the scratch
    # page, logits come off each row's last real position).
    valid = seg > 0
    if ctx["moe_stack"] is not None:
        stack = (ctx["moe_stack"], l)
    return _prefill_block(x, cc, bp, j, positions, attend, valid, stack, cfg,
                          mesh)


def _prefill_block(x, cc: Cache, bp: Any, j: int, positions, attend, valid,
                   stack, cfg: ModelConfig, mesh) -> tuple[jax.Array, Cache]:
    """``transformer.block`` for one layer of a prefill, whatever its
    backend: ``attend`` hands back what the layer writes (traced behind the
    feed-forward, under the cache part), and where ``prefill_step`` carries
    the counters (a model that holds a share of its experts) this layer's
    routed rows on held experts are added to the one and, where the
    dispatch bounds its rows, whether they passed the bound to the other."""
    held, over, tap = cc.get(HELD_ROWS), cc.get(HELD_OVERFLOWS), None
    if held is not None and "moe" in bp:
        def tap(h2):
            nonlocal held, over
            rows = moe_lib.held_rows(
                h2, bp["moe"]["router"], cfg, valid,
                bp["moe"].get("router_bias"))
            held = held + rows
            if over is not None:
                over = over + (rows > moe_lib.held_row_bound(
                    cfg, x.shape[0] * x.shape[1])).astype(jnp.int32)

    x, _, written = block(
        x, bp, cfg, positions, attend, kind=_kind(cfg, j), mesh=mesh,
        ffn_mesh=mesh, valid=valid, layer_stack=stack, ffn_tap=tap)
    with jax.named_scope(_CACHE_PART):
        cc = {**cc, **written()}
    if held is not None:
        cc[HELD_ROWS] = held
    if over is not None:
        cc[HELD_OVERFLOWS] = over
    return x, cc


def _scatter_pages(cc: Cache, k: jax.Array, v: jax.Array, rows: jax.Array):
    """Whole pages of new K/V [Nb, S, K, H] into pool rows ``rows``
    [Nb, S // psz] (in place on the carried flat pool): the pools written,
    by name. Positions beyond a row's length hold garbage from the padding
    — decode masks them out via seq_lens, and the next real token
    overwrites its slot."""
    (Nb, n_pages), psz = rows.shape, cc["k"].shape[2]
    K, H = k.shape[2], k.shape[3]
    Kv, Hv = v.shape[2], v.shape[3]     # (a packed K pool has more rows)
    new = {}
    if "k_scale" in cc:
        from orion_tpu.infer.kv_cache import quantize_kv

        # Per (token, head) int8 + f32 scale; scale pages land in the
        # first psz columns of the lanes-padded scale pool rows.
        k, ks = quantize_kv(k)               # [Nb,S,K,H] i8, [Nb,S,K]
        v, vs = quantize_kv(v)
        kspg = ks.reshape(Nb, n_pages, psz, K).transpose(0, 1, 3, 2)
        vspg = vs.reshape(Nb, n_pages, psz, K).transpose(0, 1, 3, 2)
        new["k_scale"] = cc["k_scale"].at[rows, :, :psz].set(kspg)
        new["v_scale"] = cc["v_scale"].at[rows, :, :psz].set(vspg)
    # Pool pages are [K, psz, H] (heads major, see kv_cache.py).
    kpages = k.reshape(Nb, n_pages, psz, K, H).transpose(0, 1, 3, 2, 4)
    vpages = v.reshape(Nb, n_pages, psz, Kv, Hv).transpose(0, 1, 3, 2, 4)
    new["k"] = cc["k"].at[rows].set(kpages)
    new["v"] = cc["v"].at[rows].set(vpages)
    return new


def _gather_context(cc: Cache, rows: jax.Array, psz: int, dtype):
    """The pool rows ``rows`` [B, P] as a padded context: K and V
    [B, P*psz, K, H] in ``dtype``, dequantized where the pool is int8."""
    B, P = rows.shape
    out = []
    for name in ("k", "v"):
        # [B, P, K, psz, H] -> [B, P, psz, K, H] (heads-major pages).
        c = cc[name][rows].transpose(0, 1, 3, 2, 4)
        if name + "_scale" in cc:
            sc = cc[name + "_scale"][rows][..., :psz]      # [B, P, K, psz]
            c = c.astype(jnp.float32) * sc.transpose(0, 1, 3, 2)[..., None]
        out.append(c.reshape(B, P * psz, *c.shape[3:]).astype(dtype))
    return out


def _kind(cfg: ModelConfig, j: int):
    """The layer's kind for ``qkv_proj`` where a model's layers differ
    (``j`` static, ``_scan_layers``); None is the model's one kind."""
    return None if cfg.layer_plan is None else cfg.layer_kind(j)


HELD_ROWS = "held_expert_rows"
HELD_OVERFLOWS = "held_bound_overflows"


def _prefill_logits(
    params: Params, x: jax.Array, lengths: jax.Array, cfg: ModelConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> jax.Array:
    """Next-token logits [Nb, V] off each row's last real position.

    Gathers before the LM head so the vocab matmul is [Nb, 1, V], not
    [Nb, S_pad, V]."""
    with jax.named_scope("unembed"):
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
    state_rows: Optional[jax.Array] = None,    # [Nb] int32: slot + 1, a
    #              power-retention model's (0 = the scratch row); else None
    slots: Optional[jax.Array] = None,         # [Nb] int32: each row's slot
    #              (a padding row: B, which the scatter drops)
    last_token: Optional[jax.Array] = None,    # [B] int32: the step's
    key: Optional[jax.Array] = None,           # the engine's PRNG key
    *,
    cfg: ModelConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
    paged_prefill: bool = False,
) -> tuple[jax.Array, ...]:
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

    With ``last_token`` (the engine's call; ``slots`` and ``key`` come with
    it) the first tokens are picked here and stay on the device: the
    results between the logits and the cache are then the greedy picks
    ``[Nb]`` int32, ``last_token`` with each row's pick at its slot (what
    the step's decode window takes as its tokens), and the key the
    engine's stream holds after one sampling event (``split(key)[0]``, what
    the eager sampler would leave). A burst that is not greedy samples
    from the logits on the host's side and takes none of the three.
    """
    out = _prefill(params, cache, tokens, lengths, pages, prefix_lens,
                   prefix_pages, state_rows, cfg, mesh, paged_prefill)
    if last_token is None:
        return out
    from orion_tpu.infer.sampling import sample

    logits, cache, *held = out
    if cfg.block_length:
        # Generation by blocks: prefill samples nothing (the logits are over
        # the token AT the last whole block's end, which is the prompt's
        # own), and the key stays where it is.
        picks = jnp.zeros((tokens.shape[0],), jnp.int32)
        return logits, picks, last_token, key, *held, cache
    picks = sample(logits, key)     # scalar temperature 0: the bare argmax
    with jax.named_scope("sample"):
        last_token = last_token.at[slots].set(picks, mode="drop")
    return logits, picks, last_token, jax.random.split(key)[0], *held, cache


def _prefill(params, cache, tokens, lengths, pages, prefix_lens,
             prefix_pages, state_rows, cfg, mesh, paged_prefill):
    """``prefill_step``'s (logits, cache), or (logits, cache, rows on held
    experts) for a model that holds a share of its experts, or (logits,
    cache, those rows, layer-dispatches that passed ``moe.held_row_bound``)
    where its dispatch bounds the rows of this block."""
    if cfg.is_retention:
        if prefix_pages is not None and prefix_pages.shape[1]:
            raise ValueError(
                "a power-retention model prefills whole prompts: a cached "
                "prefix would need its state, which nothing snapshots")
        return _retained_prefill(
            params, cache, tokens, lengths, pages, state_rows, cfg, mesh)
    if cfg.is_latent and prefix_pages is not None and prefix_pages.shape[1]:
        raise ValueError(
            "a latent-attention model prefills whole prompts: a cached "
            "prefix would have to be expanded again, which no path does")
    if cfg.has_kda and prefix_pages is not None and prefix_pages.shape[1]:
        raise ValueError(
            "a model with KDA layers prefills whole prompts: a cached prefix "
            "would need its state, which nothing snapshots")
    if cfg.resumes_prefill:
        return _resumed_prefill(params, cache, tokens, lengths, pages,
                                prefix_lens, prefix_pages, state_rows, cfg,
                                mesh)
    ctx = _prefill_ctx(
        params, cache, tokens, lengths, pages, prefix_lens, prefix_pages,
        cfg, paged_prefill=paged_prefill,
    )
    if cfg.has_kda or cfg.has_window_ring:
        # The state row each row of the burst owns (slot + 1; padding rows
        # and a caller that says nothing take scratch row 0).
        ctx["state_rows"] = (jnp.zeros((tokens.shape[0],), jnp.int32)
                             if state_rows is None else state_rows)
    if cfg.has_window_ring:
        if ctx["P_pre"]:
            raise ValueError(
                "a model whose window layers keep a ring prefills whole "
                "prompts: a cached prefix's window rows are gone")
        layer = _split_prefill_layer
    elif cfg.has_kda:
        layer = _hybrid(_kda_prefill_layer, _latent_prefill_layer)
    else:
        layer = _latent_prefill_layer if cfg.is_latent else _dense_layer

    def body(carry, bp, l, j, stack=None):
        x, cc = carry
        return layer(x, cc, bp, l, j, ctx, cfg, mesh, stack)

    x = embed(params, tokens, ctx["positions"], cfg)
    cache = dict(cache)
    counters = []
    if cfg.holds_expert_share:
        # Ride the layer scan beside the pool and leave as further results:
        # the engine's prefill_held_expert_rows counter and, where the
        # dispatch bounds the rows of this block (moe.bounds_held_rows), its
        # prefill_held_bound_overflows.
        counters = [HELD_ROWS]
        if moe_lib.bounds_held_rows(cfg, tokens.size):
            counters.append(HELD_OVERFLOWS)
        cache.update({name: jnp.zeros((), jnp.int32) for name in counters})
    x, cache = _scan_layers(params, cfg, body, (x, cache))
    logits = _prefill_logits(params, x, lengths, cfg, mesh)
    return logits, cache, *(cache.pop(name) for name in counters)


def _decode_core(
    params: Params,
    cache: Cache,
    tokens: jax.Array,        # [B] newest token per slot
    write_pos: jax.Array,     # [B] int32 position being written/attended
    page_table: jax.Array,    # [B, pages_per_seq] int32 (per-layer-relative)
    cfg: ModelConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
    active: Optional[jax.Array] = None,
) -> tuple[jax.Array, Cache]:
    """One decode forward for every slot -> (logits [B, V], cache'): the
    paged backend at W = 1 (the retained backend for a power-retention
    model: it reads a slot's state row and never writes it, so the body can
    be run again on the cache it handed back; the latent backend for a
    latent-attention model; the KDA backend beside it for a model with KDA
    layers, which ADVANCES the state rows of the ``active`` slots, default
    all: run again on the cache it handed back it computes the next
    position's step)."""
    if cfg.has_window_ring:
        ctx, layer = _split_ctx(
            cache, write_pos, page_table, cfg), _split_layer
    elif cfg.resumes_prefill:
        ctx = _selected_ctx(cache, write_pos, page_table, cfg, active)
        layer = _hybrid(lightning=_lightning_layer, sparse=_sparse_layer)
    elif cfg.has_kda:
        ctx = {**_latent_ctx(cache, write_pos, page_table, cfg),
               "active": active}
        layer = _hybrid(_kda_layer, _latent_layer)
    elif cfg.is_retention:
        ctx, layer = _retained_ctx(
            cache, write_pos, page_table, cfg), _retained_layer
    elif cfg.is_latent:
        ctx, layer = _latent_ctx(
            cache, write_pos, page_table, cfg), _latent_layer
    else:
        ctx, layer = _one_token_ctx(
            cache, write_pos, page_table, cfg), _paged_layer

    def body(carry, bp, l, j, stack=None):
        x, cc = carry
        return layer(x, cc, bp, l, j, ctx, cfg, mesh)

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
    keys: jax.Array,          # [W] PRNG keys, one per inner step (with
    #                           ``window``: the engine's one key)
    temperature: jax.Array,   # [B] f32 per-request (vLLM-style params)
    top_k: jax.Array,         # [B] i32
    top_p: jax.Array,         # [B] f32
    cfg: ModelConfig,
    max_seq_len: int,
    mesh: Optional[jax.sharding.Mesh] = None,
    nan_guard: bool = False,
    window: Optional[int] = None,
) -> tuple[jax.Array, ...]:
    """W fused decode+sample steps; returns (tokens [W, B] int32, cache).

    With ``window`` (static; the engine's call) ``keys`` is the engine's
    ONE key and the W keys are derived here, as the host derived them:
    ``key', sub = split(key)``, ``split(sub, window)``. ``key'`` is then
    the result before the cache, and no key program runs between two
    dispatches.

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
        # ``act``: a KDA layer's state rows advance where live (no other
        # backend reads it).
        logits, cc = _decode_core(
            params, cc, tok, wp, page_table, cfg, mesh, active=act)
        toks = sample(
            logits, sub, temperature=temperature, top_k=top_k, top_p=top_p
        )
        tok = jnp.where(act, toks, tok)
        sl = sl + act.astype(sl.dtype)
        if nan_guard:
            ok = ok & (jnp.isfinite(logits).all(-1) | ~act)
            return (tok, sl, ok, cc), toks
        return (tok, sl, cc), toks

    key = ()
    if window is not None:
        key_next, sub = jax.random.split(keys)
        key, keys = (key_next,), jax.random.split(sub, window)
    if nan_guard:
        init = (
            tokens, seq_lens, jnp.ones_like(active, dtype=bool), dict(cache)
        )
        (_, _, ok, cache), toks = jax.lax.scan(stepf, init, keys)
        return toks, ok, *key, cache
    (_, _, cache), toks = jax.lax.scan(
        stepf, (tokens, seq_lens, dict(cache)), keys
    )
    return toks, *key, cache


def _paged_ctx(
    cache: Cache,
    seq_lens: jax.Array,      # [B] accepted-token cursor per slot
    lens: jax.Array,          # [B] real tokens this row (1..W)
    page_table: jax.Array,    # [B, pages_per_seq]
    active: jax.Array,        # [B] bool
    W: int,
    max_seq_len: int,
    cfg: ModelConfig,
    name: str = "ragged_paged",             # the kernel's name in a trace
    depths: Optional[jax.Array] = None,     # [B, W] tree depth per column
    tree_mask: Optional[jax.Array] = None,  # [B, W] packed ancestor words
    geometry: Optional[tuple[int, int]] = None,  # (page size, pages a
    #           layer) of the leaves walked, where not the pool's (a ring)
) -> dict:
    """Batch-level tensors of the paged backend (``_paged_layer``): W new
    tokens per slot written into the pool and attended over it. Draft
    verification is this at W = speculate_tokens + 1, the decode window's
    step at W = 1 (``_one_token_ctx``).

    Row b holds ``lens[b]`` real tokens — the pending last token plus its
    drafts — writing KV at positions ``seq_lens[b] + j``. Unlike prefill
    chunks these start MID-PAGE (the cursor is arbitrary), so per-token
    (page, offset) pairs come from the page table. Padding positions (j >=
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
    psz, NP = geometry or page_geometry(cache, cfg.n_paged_layers)
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
    if W == 1:      # what the clip says, which XLA does not fold
        k_lens = jnp.ones_like(start)
    return dict(
        psz=psz, NP=NP, name=name,
        page_table=page_table, positions=rope_pos, q_pos=q_pos,
        page_idx=page_idx, offset=offset,
        kv_arange=kv_arange, kv_base_mask=kv_base_mask,
        start=start, k_lens=k_lens,
        depths=depths, tree_mask=tree_mask,
        in_slots=in_slots, slot_depth=slot_depth,
        use_pallas=use_pallas, interpret=interpret,
    )


def _one_token_ctx(
    cache: Cache, write_pos: jax.Array, page_table: jax.Array,
    cfg: ModelConfig,
) -> dict:
    """The paged backend's tensors for ONE token per slot at ``write_pos``
    [B], every row live: the decode window's step and the decode half of a
    mixed step. The caller keeps ``write_pos`` inside the context
    (``decode_window`` clamps a frozen slot onto its own last column), so
    the context limit here is the page table's reach and no write is sent
    to scratch; the kernel keeps the name the decode metrics read."""
    return _paged_ctx(
        cache, write_pos, jnp.ones_like(write_pos), page_table,
        jnp.ones(write_pos.shape, bool), 1,
        page_table.shape[1] * page_geometry(cache, cfg.n_paged_layers)[0], cfg,
        name="paged_decode")


def _paged_layer(
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
    """The paged backend: one layer of W new tokens per slot — every
    position's K/V lands in the pool first (quantized under kv_quant,
    exactly as a sequential decode would have written it), then each query
    attends the context up to its own position; the layer itself is
    ``transformer.block``. One pass over this layer's weights serves all W
    positions of all slots, and because the decode step IS this at W = 1,
    position i's logits match the i-th sequential decode step's
    bit-for-bit, which is what makes greedy acceptance exact
    (tests/test_spec_decode.py).

    Pallas branch: the ragged paged-attention kernel
    (ops/pallas/paged_attention.attend) walks each slot's page table once
    for all W queries (compute proportional to actual context lengths),
    masks queries causally among the W new positions, and writes every real
    token's K/V itself — the pool stays in place through the kernel's
    input/output aliasing, where an external scatter feeding the kernel
    would cost a pool copy per layer. Under kv_quant it dequantizes in
    place and quantizes the written tokens with the shared
    common.quantize_kv, so its written bytes match the xla scatter
    bit-for-bit. Rows with all-zero page-table entries (inactive /
    mid-prefill slots) read and write only the reserved scratch page, like
    the xla branch's `valid` redirect. XLA branch: scatter + masked
    padded-context gather, the reference."""
    psz, NP, page_table = ctx["psz"], ctx["NP"], ctx["page_table"]
    win = cfg.layer_window(j)

    def attend(q, k, v):
        new = dict(cc)
        if ctx["use_pallas"]:
            from orion_tpu.ops.pallas.paged_attention import attend as kernel

            with jax.named_scope("kernel"):
                out, *pools = kernel(
                    q, cc["k"], cc["v"], page_table, ctx["start"],
                    ctx["k_lens"],
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
                    name=ctx["name"],
                )
            new.update(zip(_POOLS, pools))
            return out, new
        written = {"k": k, "v": v}
        with jax.named_scope("cache"):
            rows, offset = l * NP + ctx["page_idx"], ctx["offset"]  # [B, W]
            if "k_scale" in cc:
                from orion_tpu.infer.kv_cache import quantize_kv

                written["k"], written["k_scale"] = quantize_kv(k)
                written["v"], written["v_scale"] = quantize_kv(v)
            for name, val in written.items():   # [B,W,K,H] (scales [B,W,K])
                new[name] = cc[name].at[rows, :, offset].set(val)
            # Padded-context gather (the just-written K/V reads back out of
            # the pool, so under kv_quant each query attends its own
            # dispatch's tokens DEQUANTIZED — what a later step reads of
            # them).
            k_ctx, v_ctx = _gather_context(
                new, l * NP + page_table, psz, q.dtype)
        with jax.named_scope("kernel"):
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
        return out, new

    x, _, cc = block(
        x, bp, cfg, ctx["positions"], attend, kind=_kind(cfg, j), mesh=mesh,
        layer_stack=stack)
    return x, cc


# -- the split cache: full layers in pages, window layers in a ring -----------
#
# A model whose window layers differ from its full layers in their K/V heads
# (``ModelConfig.has_window_ring``; kv_cache.ring_cache). A full layer is the
# paged backend over ``k`` / ``v`` (rows of the layer's index among the full
# layers); a window layer is the SAME kernel over ``ring_k`` / ``ring_v``
# through a page table that is arithmetic: the ``ring_pages`` pages up to the
# one that takes the new token, each at its place in the slot's ring, with
# the positions shifted down by whole pages to match (causal and window
# masks compare differences, which a shift leaves alone). So the kernel's
# walk is ``ring_pages`` entries whatever the request's length. Keys are kept
# as ``kv_cache.pack_keys`` lays them out; both kinds may carry a sink.


def _scatter_ring(cc: Cache, k: jax.Array, v: jax.Array, li, ctx: dict):
    """A prefill's K rows [Nb, S, K + K / 2, Hv] and V [Nb, S, K, Hv] of
    window layer ``li`` (its index among them) into each row's slot's ring:
    the pages of the last ``ring_pages`` x page positions up to the row's
    length, and nothing before them (a short prompt's pages twice over,
    with the same rows)."""
    psz, RP, lengths = ctx["psz"], cc[RING_K].shape[2], ctx["lengths"]
    Nb, S = k.shape[:2]
    last = (jnp.maximum(lengths, 1) - 1) // psz                   # [Nb]
    src = jnp.clip(
        last[:, None] - (RP - 1) + jnp.arange(RP, dtype=jnp.int32)[None],
        0, S // psz - 1)                                           # [Nb, RP]
    new = {}
    for name, a in ((RING_K, k), (RING_V, v)):
        pages = a.reshape(Nb, S // psz, psz, *a.shape[2:])
        pages = jnp.take_along_axis(
            pages, src[:, :, None, None, None], axis=1)
        new[name] = cc[name].at[
            li, ctx["state_rows"][:, None], src % RP].set(
                pages.transpose(0, 1, 3, 2, 4))
    return new


def _split_prefill_layer(x, cc: Cache, bp: Any, l, j: int, ctx: dict,
                         cfg: ModelConfig, mesh, stack=None):
    """One layer of a whole-prompt prefill of a split-cache model: flash
    (or XLA) attention over the prompt's own K/V under the layer's window
    and sink; a full layer's pages go to the pool, a window layer's last
    pages to the rings."""
    kind, li = cfg.layer_kind(j), cfg.cache_layer(l, j)
    positions, seg = ctx["positions"], ctx["seg"]

    def attend(q, k, v, sink=None):
        with jax.named_scope("kernel"):
            out = attention(
                q, k, v, causal=True, q_segment_ids=seg, kv_segment_ids=seg,
                seg_pad_zero=True, logit_softcap=cfg.attn_logit_softcap,
                window=kind.window, impl=cfg.kernels, mesh=mesh, sink=sink)
        if kind.window is None:
            return out, lambda: _scatter_pages(
                cc, pack_keys(k, v.shape[-1]), v,
                li * ctx["NP"] + ctx["pages"])
        return out, lambda: _scatter_ring(
            cc, pack_keys(k, v.shape[-1]), v, li, ctx)

    x, cc = _prefill_block(x, cc, bp, j, positions, attend, seg > 0, stack,
                           cfg, mesh)
    # The residual stream is written out at each layer's end: the eleven
    # layers of this plan are one straight program (no scan carries x), and
    # XLA otherwise keeps every layer's addends to sum them again wherever x
    # is read (ten [16384, 4096] buffers alive at once: 1.3 GB the chip does
    # not have beside the weights and the cache). The held-rows count goes
    # through the same barrier, or its router runs at the program's end on
    # every layer's normed rows, kept until then.
    counts = {n: cc[n] for n in (HELD_ROWS, HELD_OVERFLOWS) if n in cc}
    if counts:
        x, counts = jax.lax.optimization_barrier((x, counts))
        return x, {**cc, **counts}
    return jax.lax.optimization_barrier(x), cc


def _split_ctx(cache: Cache, write_pos: jax.Array, page_table: jax.Array,
               cfg: ModelConfig) -> dict:
    """The decode step's tensors of a split-cache model: the paged
    backend's for the full layers (``full``) and, for the window layers
    (``ring``), the same over each slot's ring: its ``ring_pages`` pages up
    to the one that takes the new token, positions shifted down by the
    pages before them."""
    full = _one_token_ctx(cache, write_pos, page_table, cfg)
    psz = full["psz"]
    slots, RP = cache[RING_K].shape[1:3]
    B = write_pos.shape[0]
    first = jnp.maximum(write_pos // psz - (RP - 1), 0)            # [B]
    table = (jnp.arange(1, B + 1, dtype=jnp.int32)[:, None] * RP
             + (first[:, None] + jnp.arange(RP, dtype=jnp.int32)[None]) % RP)
    shifted = write_pos - first * psz
    ring = _paged_ctx(
        cache, shifted, jnp.ones_like(shifted), table,
        jnp.ones(shifted.shape, bool), 1, RP * psz, cfg,
        name="paged_decode", geometry=(psz, slots * RP))
    return {"full": full, "ring": ring, "positions": full["positions"]}


def _split_layer(x: jax.Array, cc: Cache, bp: Any, l, j: int, ctx: dict,
                 cfg: ModelConfig, mesh) -> tuple[jax.Array, Cache]:
    """One layer of a split-cache model's decode step (one token a slot):
    the paged kernel with the new token's write fused in, over the pool for
    a full layer and over the rings for a window layer (XLA: a scatter and
    a masked gather of the same rows, the reference)."""
    kind = cfg.layer_kind(j)
    ring = kind.window is not None
    sub = ctx["ring" if ring else "full"]
    kn, vn = (RING_K, RING_V) if ring else ("k", "v")
    base = cfg.cache_layer(l, j) * sub["NP"]
    K, win = cfg.kv_heads_of(kind), kind.window

    def attend(q, k, v, sink=None):
        new, Hv = dict(cc), v.shape[-1]
        # The leaves in the paged layout ([rows, heads, page, width]: a
        # ring's leading dimensions flat) and back.
        flat = {n: cc[n].reshape(-1, *cc[n].shape[-3:]) for n in (kn, vn)}
        if sub["use_pallas"]:
            from orion_tpu.ops.pallas.paged_attention import attend as kernel

            with jax.named_scope("kernel"):
                out, kp, vp = kernel(
                    pack_queries(q, K, Hv), flat[kn], flat[vn],
                    sub["page_table"], sub["start"], sub["k_lens"],
                    layer_base=base, k_new=pack_keys(k, Hv), v_new=v,
                    logit_softcap=cfg.attn_logit_softcap, window=win,
                    interpret=sub["interpret"], k_scale=None, v_scale=None,
                    name=sub["name"], sink=sink, scale=q.shape[-1] ** -0.5)
            new[kn], new[vn] = (kp.reshape(cc[kn].shape),
                                vp.reshape(cc[vn].shape))
            return out, new
        with jax.named_scope("cache"):
            at, offset = base + sub["page_idx"], sub["offset"]      # [B, 1]
            flat[kn] = flat[kn].at[at, :, offset].set(pack_keys(k, Hv))
            flat[vn] = flat[vn].at[at, :, offset].set(v)
            walk = base + sub["page_table"]                         # [B, P]
            B, P = walk.shape
            k_ctx, v_ctx = (
                flat[name][walk].transpose(0, 1, 3, 2, 4).reshape(
                    B, P * sub["psz"], -1, Hv) for name in (kn, vn))
            k_ctx = unpack_keys(k_ctx, K)
            new[kn], new[vn] = (flat[kn].reshape(cc[kn].shape),
                                flat[vn].reshape(cc[vn].shape))
        with jax.named_scope("kernel"):
            mask = sub["kv_base_mask"]
            if win is not None:
                mask = mask & (
                    sub["kv_arange"] >= (sub["q_pos"] - win + 1)[:, :, None])
            out = attention_xla(
                q, k_ctx, v_ctx.astype(q.dtype), causal=False, mask=mask,
                logit_softcap=cfg.attn_logit_softcap, sink=sink)
        return out, new

    x, _, cc = block(
        x, bp, cfg, ctx["positions"], attend, kind=kind, mesh=mesh)
    return x, cc


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


def _verdicts(
    logits: jax.Array,        # [B, W, V]
    tokens: jax.Array, lens: jax.Array, active: jax.Array, key: jax.Array,
    *, temperature, top_k, top_p, parents, legal_mask, nan_guard: bool,
) -> tuple[jax.Array, ...]:
    """``(accept [B, W], alt [B, W])`` off a verify dispatch's logits (the
    chain walk, or the CHILD-indexed tree walk where ``parents`` is given),
    with ``ok`` [B] third under ``nan_guard``."""
    from orion_tpu.infer.sampling import (
        spec_verify_sample,
        spec_verify_sample_tree,
    )

    sampling = dict(temperature=temperature, top_k=top_k, top_p=top_p,
                    legal_mask=legal_mask)
    if parents is None:
        verdicts = spec_verify_sample(
            logits, _draft_next(tokens, lens), key, **sampling)
    else:
        verdicts = spec_verify_sample_tree(
            logits, tokens, parents, lens, key, **sampling)
    if nan_guard:
        # Per-slot finite check over the row's REAL positions only (padding
        # positions compute on scratch-page garbage by design).
        steps = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
        valid = active[:, None] & (steps < lens[:, None])
        ok = jnp.where(valid, jnp.isfinite(logits).all(-1), True).all(-1)
        return (*verdicts, ok)
    return tuple(verdicts)


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

    Each layer is ``_paged_layer``, the code the decode window's step runs
    at W = 1: under kernels='pallas' the ragged paged-attention kernel (page
    walk + in-kernel fused write for all W positions — the pool gather never
    materializes, and the page DMAs amortize over the W queries); under
    'xla' scatter + masked gather, kept as the reference. Either way the
    per-position logits match sequential decode on the same kernel setting
    bit-for-bit.

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
    ctx = _paged_ctx(
        cache, seq_lens, lens, page_table, active, tokens.shape[1],
        max_seq_len, cfg, depths=depths, tree_mask=tree_mask,
    )

    def body(carry, bp, l, j, stack=None):
        x, cc = carry
        return _paged_layer(x, cc, bp, l, j, ctx, cfg, mesh)

    x = embed(params, tokens, ctx["positions"], cfg)
    x, cache = _scan_layers(params, cfg, body, (x, dict(cache)))
    logits = unembed(params, x, cfg, mesh)                 # [B, W, V]
    return (*_verdicts(
        logits, tokens, lens, active, key, temperature=temperature,
        top_k=top_k, top_p=top_p, parents=parents, legal_mask=legal_mask,
        nan_guard=nan_guard), cache)


def denoise_schedule(block_length: int, steps: int) -> tuple[int, ...]:
    """Positions the static rule decides at each denoising forward: an even
    share of the block, the remainder to the first forwards."""
    each, rest = divmod(block_length, steps)
    return tuple(each + (s < rest) for s in range(steps))


def choose_positions(conf: jax.Array, undecided: jax.Array, count,
                     remasking: str, threshold: float) -> jax.Array:
    """[B, L] bool: the undecided positions one denoising forward decides.
    ``conf`` is the probability of the token drawn at each position.
    ``low_confidence_static``: the ``min(count, undecided left)`` undecided
    positions of largest ``conf`` (ties: the lower index).
    ``low_confidence_dynamic``: every undecided position with ``conf >
    threshold``, or the static set where those are fewer than ``count``."""
    score = jnp.where(undecided, conf, -1.0)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    left = undecided.sum(-1, keepdims=True)
    take = undecided & (rank < jnp.minimum(count, left))
    if remasking == "low_confidence_dynamic":
        over = undecided & (conf > threshold)
        take = jnp.where(over.sum(-1, keepdims=True) < count, take, over)
    return take


def _block_ctx(cache: Cache, seq_lens: jax.Array, page_table: jax.Array,
               active: jax.Array, max_seq_len: int, cfg: ModelConfig) -> dict:
    """The paged backend's tensors for one block of ``cfg.block_length``
    positions a slot from ``seq_lens`` on, every new row visible to every
    query: the W-query kernel's tree path under FULL ancestor words and the
    chain's depths (a block's positions see each other and everything below
    the cursor; positions and writes as the chain's)."""
    B, L = seq_lens.shape[0], cfg.block_length
    return _paged_ctx(
        cache, seq_lens, jnp.full((B,), L, jnp.int32), page_table, active, L,
        max_seq_len, cfg, name="block_paged",
        depths=jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L)),
        tree_mask=jnp.full((B, L), (1 << L) - 1, jnp.int32),
    )


def _block_hidden(params: Params, cache: Cache, fed: jax.Array, ctx: dict,
                  cfg: ModelConfig, mesh) -> tuple[jax.Array, Cache]:
    """The layers on one block as fed [B, L] -> (hidden [B, L, D], cache
    with the block's K/V rows written at the cursor)."""
    # A block of B x L rows takes the dropless grouped dispatch, which reads
    # a layer's matrices out of the stack in place (as prefill's does).
    moe_stack = _moe_stack(params, cfg)

    def body(carry, bp, l, j, stack=None):
        x, cc = carry
        if moe_stack is not None:
            stack = (moe_stack, l)
        return _paged_layer(x, cc, bp, l, j, ctx, cfg, mesh, stack)

    x = embed(params, fed, ctx["positions"], cfg)
    return _scan_layers(params, cfg, body, (x, dict(cache)))


def block_forward(
    params: Params, cache: Cache, fed: jax.Array, seq_lens: jax.Array,
    page_table: jax.Array, active: jax.Array, cfg: ModelConfig,
    max_seq_len: int, mesh: Optional[jax.sharding.Mesh] = None,
) -> tuple[jax.Array, Cache]:
    """ONE forward of a block as fed [B, L] -> (logits [B, L, V] over the
    token AT each position, cache with the block's rows written): the layers
    ``denoise_block`` runs ``steps`` times and once more without the head,
    and the head at EVERY row, where the program's own forwards take it at
    the rows still undecided alone. What a check holds the block program to,
    forward by forward."""
    ctx = _block_ctx(cache, seq_lens, page_table, active, max_seq_len, cfg)
    x, cache = _block_hidden(params, cache, fed, ctx, cfg, mesh)
    return _block_logits(params, x, cfg, mesh).reshape(*fed.shape, -1), cache


def _block_logits(params: Params, x: jax.Array, cfg: ModelConfig, mesh
                  ) -> jax.Array:
    """The head on hidden states [B, R, D] -> float32 logits [B x R, V], a
    row a position: accumulated and left in float32 (the choice of positions
    ranks probabilities of them, and a check's one-forward body has to rank
    them the same), and flat, so that nothing of the vocabulary's width is
    laid out again for the sampler."""
    B, R, D = x.shape
    return unembed(params, x.reshape(1, B * R, D), cfg, mesh, precise=True)[0]


def draw_with_confidence(logits: jax.Array, key: jax.Array, *, temperature,
                         top_k, top_p) -> tuple[jax.Array, jax.Array]:
    """float32 logits [R, V] -> (the token ``sampling.sample`` draws a row [R]
    int32, its float32 softmax probability [R]): ``exp(log_softmax(logits))``
    at the drawn column, taken as reductions over a row (its largest logit,
    the sum of ``exp(logits - largest)``) and ONE column of it, in the
    operations and the order ``jax.nn.log_softmax`` gives that column, with
    no ``[R, V]`` result laid out."""
    from orion_tpu.infer.sampling import sample

    drawn = sample(logits, key, temperature=temperature, top_k=top_k,
                   top_p=top_p)
    top = logits.max(axis=-1)
    norm = jnp.log(jnp.exp(logits - top[:, None]).sum(axis=-1))
    picked = jnp.take_along_axis(logits, drawn[:, None], axis=-1)[:, 0]
    return drawn, jnp.exp(picked - top - norm)


def undecided_bounds(block_length: int, steps: int) -> tuple[int, ...]:
    """The most positions a slot can have undecided at each denoising forward:
    a forward decides its ``denoise_schedule`` count or all that are left
    (the dynamic rule: at least that), so forward ``s`` meets at most the
    block less the counts before it. The rows a slot the head is given."""
    schedule = denoise_schedule(block_length, steps)
    return tuple(block_length - sum(schedule[:s]) for s in range(steps))


def denoise_block(
    params: Params,
    cache: Cache,
    tokens: jax.Array,        # [B, L]: the block as the host knows it (a
    #                           prompt's tail at its first positions)
    n_decided: jax.Array,     # [B] int32: leading positions that are decided
    seq_lens: jax.Array,      # [B] int32: the block's first position
    page_table: jax.Array,    # [B, pages_per_seq] int32
    active: jax.Array,        # [B] bool: slot holds a live request
    key: jax.Array,           # the engine's ONE key
    temperature: jax.Array,   # [B] f32 per-request sampling params (python
    top_k: jax.Array,         # [B] i32   scalars for the all-defaults
    top_p: jax.Array,         # [B] f32   specialization)
    cfg: ModelConfig,
    max_seq_len: int,
    mesh: Optional[jax.sharding.Mesh] = None,
    nan_guard: bool = False,
    steps: int = 1,
    remasking: str = "low_confidence_static",
    threshold: float = 0.9,
) -> tuple[jax.Array, ...]:
    """One block of ``cfg.block_length`` positions for EVERY live slot in one
    dispatch: ``steps`` denoising forwards and the commit forward; returns
    ``(tokens [B, L] int32, decided_at [B, L] int32, key', cache)`` (``ok``
    [B] third under ``nan_guard``). ``decided_at`` is the forward that
    decided a position (-1: it came decided, a prompt's tail).

    A forward feeds the block's L positions, decided tokens as they are and
    undecided ones as ``cfg.mask_token_id``, through ``_paged_layer`` at
    W = L with every new row visible to every query (``_block_ctx``). The
    logits at a position are over the token AT it. The head and the choice
    run at the rows that can still be decided: forward ``s`` takes a slot's
    first ``undecided_bounds(L, steps)[s]`` undecided positions (all it can
    have by then; a slot with fewer, a prompt's tail in its first block,
    fills up with decided positions, which the choice passes by), draws a
    token at each from the filtered distribution with its float32 softmax
    probability as its confidence (``draw_with_confidence``: reductions of
    the logits, no second ``[rows, V]`` array), and lays both back over
    ``[B, L]``, where ``choose_positions`` decides some, which keep their
    token for good. The forwards differ in their rows, so they are a Python
    loop (the layers stay scanned inside each). The rows a denoising
    forward writes land BEYOND the slot's cursor (``seq_lens`` does not
    move), where the next forward overwrites them and nothing else reads
    them; the commit forward, on the finished block and without the head,
    leaves the rows that stay. The trip count is fixed: a block decided
    early makes its remaining forwards for nothing.

    The key is handled as ``decode_window`` handles it: ``key', sub =
    split(key)``, one of ``split(sub, steps)`` a forward (a forward draws
    its ``B x rows`` rows at once, slot by slot)."""
    B, L = tokens.shape
    ctx = _block_ctx(cache, seq_lens, page_table, active, max_seq_len, cfg)
    key_next, sub = jax.random.split(key)
    keys = jax.random.split(sub, steps)
    came = jnp.arange(L, dtype=jnp.int32)[None, :] < n_decided[:, None]
    toks, at = tokens, jnp.where(came, -1, steps).astype(jnp.int32)
    ok, cache = jnp.ones((B,), bool), dict(cache)
    for s, (count, U) in enumerate(zip(denoise_schedule(L, steps),
                                       undecided_bounds(L, steps))):
        decided = at < s
        with jax.named_scope("denoise/forward"):
            x, cache = _block_hidden(
                params, cache, jnp.where(decided, toks, cfg.mask_token_id),
                ctx, cfg, mesh)
        if not U:           # more forwards than positions: all are decided
            continue
        # A slot's undecided positions first, in their order.
        at_rows = jnp.argsort(decided, axis=-1, stable=True)[:, :U]
        with jax.named_scope("denoise/forward"):
            logits = _block_logits(
                params, jnp.take_along_axis(x, at_rows[..., None], axis=1),
                cfg, mesh)                                  # [B x U, V]
        with jax.named_scope("denoise/choose"):
            # a [B] sampling parameter for each of a slot's U rows
            per_row = lambda a: a if jnp.ndim(a) == 0 else jnp.repeat(a, U)
            drawn, conf = draw_with_confidence(
                logits, keys[s], temperature=per_row(temperature),
                top_k=per_row(top_k), top_p=per_row(top_p))
            # back over the block; a position that was given no row is a
            # decided one, which the choice passes by
            over_block = lambda r: jnp.put_along_axis(
                jnp.zeros((B, L), r.dtype), at_rows, r.reshape(B, U),
                axis=1, inplace=False)
            take = choose_positions(
                over_block(conf), ~decided, count, remasking, threshold)
            toks = jnp.where(take, over_block(drawn), toks)
            at = jnp.where(take, s, at)
            if nan_guard:   # the rows whose tokens can still change
                ok = ok & (jnp.isfinite(logits).reshape(B, -1).all(-1)
                           | ~active)
    with jax.named_scope("denoise/commit"):
        _, cache = _block_hidden(params, cache, toks, ctx, cfg, mesh)
    if nan_guard:
        return toks, at, ok, key_next, cache
    return toks, at, key_next, cache


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

    Each layer runs the paged backend at W = 1 (fused-write ragged paged
    attention — ``decode_window``'s own step, so the greedy decode
    stream is bit-identical to unchunked serving; sampled decode matches
    a decode_window=1 engine at equal PRNG state, while W>1 windows group
    key splits differently) and the dense backend (a
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
    dctx = _one_token_ctx(cache, wp, page_table, cfg)

    def body(carry, bp, l, j, stack=None):
        xp, xd, cc = carry
        xp, cc = _dense_layer(xp, cc, bp, l, j, pctx, cfg, mesh, stack)
        xd, cc = _paged_layer(xd, cc, bp, l, j, dctx, cfg, mesh)
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
    """``mixed_step`` with the decode half at W verify positions a slot:
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
    pctx = _prefill_ctx(
        params, cache, p_tokens, p_lengths, p_pages, p_prefix_lens,
        p_prefix_pages, cfg, paged_prefill=paged_prefill,
    )
    vctx = _paged_ctx(
        cache, seq_lens, lens, page_table, active, tokens.shape[1],
        max_seq_len, cfg, depths=depths, tree_mask=tree_mask,
    )

    def body(carry, bp, l, j, stack=None):
        xp, xv, cc = carry
        xp, cc = _dense_layer(xp, cc, bp, l, j, pctx, cfg, mesh, stack)
        xv, cc = _paged_layer(xv, cc, bp, l, j, vctx, cfg, mesh)
        return xp, xv, cc

    xp = embed(params, p_tokens, pctx["positions"], cfg)
    xv = embed(params, tokens, vctx["positions"], cfg)
    xp, xv, cache = _scan_layers(params, cfg, body, (xp, xv, dict(cache)))
    logits = unembed(params, xv, cfg, mesh)                # [B, W, V]
    verdicts = _verdicts(
        logits, tokens, lens, active, key, temperature=temperature,
        top_k=top_k, top_p=top_p, parents=parents, legal_mask=legal_mask,
        nan_guard=nan_guard)
    p_logits = _prefill_logits(params, xp, p_lengths, cfg, mesh)
    return (*verdicts, p_logits, cache)


# -- the latent backend: one compressed row a token and layer ------------------
#
# A latent-attention model (model.kv_lora_rank; transformer.latent_proj)
# caches of a position ONE row a layer, ``[c_kv | k_pe | zeros]``
# (kv_cache.latent_leaf). Prefill attends in the EXPANDED form (K and V of
# every head rebuilt from the rows, the flash kernel) and writes whole pages
# of rows; the decode window attends in the ABSORBED form over the pages
# themselves, one shared "head" for all query heads, which is what makes the
# small cache pay: a step reads a cached row once.


def _latent_prefill_layer(x, cc: Cache, bp: Any, l, j: int, ctx: dict,
                          cfg: ModelConfig, mesh, stack=None
                          ) -> tuple[jax.Array, Cache]:
    """One layer of whole-prompt prefill: expanded attention over the
    block's own rows, the rows into ``ctx['pages']`` (behind the
    feed-forward, as the dense backend's scatter is)."""
    psz, NP, seg = ctx["psz"], ctx["NP"], ctx["seg"]
    pool = cc[LATENT]
    l = cfg.cache_layer(l, j)       # among the layers that keep pages

    def attend(q, row, wkv_b):
        with jax.named_scope("kernel"):
            out = latent_attention(
                q, row, wkv_b, cfg, q_segment_ids=seg, kv_segment_ids=seg,
                seg_pad_zero=True, impl=cfg.kernels, mesh=mesh)

        def written():
            Nb, S, w = row.shape
            pages = jnp.pad(row, ((0, 0), (0, 0), (0, pool.shape[-1] - w)))
            return {LATENT: pool.at[l * NP + ctx["pages"]].set(
                pages.reshape(Nb, S // psz, 1, psz, -1).astype(pool.dtype))}

        return out, written

    return _prefill_block(x, cc, bp, j, ctx["positions"], attend, seg > 0,
                          stack, cfg, mesh)


def _latent_ctx(cache: Cache, pos: jax.Array, page_table: jax.Array,
                cfg: ModelConfig) -> dict:
    """Batch-level tensors of ``_latent_layer``: one new token a slot at
    position ``pos`` [B] (the caller keeps it inside the page table's
    reach, as for ``_one_token_ctx``)."""
    from orion_tpu.ops._dispatch import resolve_impl

    psz, NP = page_geometry(cache, cfg.n_paged_layers)
    P = page_table.shape[1]
    at = jnp.minimum(pos, P * psz - 1)
    use_pallas, interpret = resolve_impl(cfg.kernels)
    return dict(
        psz=psz, NP=NP, at=at, positions=at[:, None], page_table=page_table,
        page=page_table[jnp.arange(pos.shape[0]), at // psz], offset=at % psz,
        live=jnp.arange(P * psz, dtype=jnp.int32)[None, :] <= at[:, None],
        use_pallas=use_pallas, interpret=interpret,
    )


def _latent_layer(x, cc: Cache, bp: Any, l, j: int, ctx: dict,
                  cfg: ModelConfig, mesh) -> tuple[jax.Array, Cache]:
    """The latent backend: one layer of one new token a slot. Its row lands
    in its page (inside the kernel on the pallas path) and its queries,
    absorbed into the rows' space, attend the slot's pages; the output
    comes back through W_uv. XLA branch: scatter + masked padded-context
    gather, the reference."""
    NP, page_table = ctx["NP"], ctx["page_table"]
    R, scale = cfg.kv_lora_rank, cfg.latent_head_dim ** -0.5
    pool = cc[LATENT]
    l = cfg.cache_layer(l, j)       # among the layers that keep pages

    def attend(q, row, wkv_b):
        pad = pool.shape[-1] - row.shape[-1]
        with jax.named_scope("kernel"):
            q_lat = jnp.pad(latent_absorb(q, wkv_b, cfg)[:, 0],
                            ((0, 0), (0, 0), (0, pad)))       # [B, N, Wd]
            new = jnp.pad(row[:, 0], ((0, 0), (0, pad))).astype(pool.dtype)
        if ctx["use_pallas"]:
            if mesh is not None:
                raise ValueError(
                    "the latent decode kernel runs on one device")
            from orion_tpu.ops.pallas.latent_paged_attention import (
                latent_paged_attention,
            )

            with jax.named_scope("kernel"):
                o_lat, written = latent_paged_attention(
                    q_lat, pool, page_table, ctx["at"], new,
                    layer_base=l * NP, value_width=R, scale=scale,
                    interpret=ctx["interpret"])
        else:
            with jax.named_scope("cache"):
                written = pool.at[
                    l * NP + ctx["page"], 0, ctx["offset"]].set(new)
                rows = written[l * NP + page_table][:, :, 0]  # [B, P, psz, Wd]
                rows = rows.reshape(rows.shape[0], -1, rows.shape[-1])
            with jax.named_scope("kernel"):
                z = jnp.einsum("bnw,btw->bnt", q_lat, rows,
                               preferred_element_type=jnp.float32) * scale
                z = jnp.where(ctx["live"][:, None, :], z, -jnp.inf)
                o_lat = jnp.einsum(
                    "bnt,btr->bnr",
                    jax.nn.softmax(z, axis=-1).astype(q.dtype), rows[..., :R])
        with jax.named_scope("kernel"):
            out = latent_unabsorb(o_lat[:, None].astype(q.dtype), wkv_b, cfg)
        return out, {**cc, LATENT: written}

    x, _, cc = block(x, bp, cfg, ctx["positions"], attend,
                     kind=_kind(cfg, j), mesh=mesh)
    return x, cc


# -- the KDA backend: a state row and a convolution's tail a slot, no page -----
#
# A KDA layer (model.attention=kda; ops/kda.py) keeps of a sequence its
# recurrence's state and the last rows in front of its convolution, both a
# SLOT's (``kv_cache.kda_leaves``: row slot + 1 of the layer's index among
# the KDA layers, ``cfg.cache_layer``). Prefill computes a whole prompt in
# the chunked form from a zero state and writes the slot's rows whole; a
# decode step advances them in place (inside the kernel on the pallas path,
# the state aliased through it) for the slots that are live. Such layers
# stand AMONG latent layers in one layer plan: ``_hybrid`` picks a layer's
# backend by its kind, and the latent layers' pool is sized over them alone.


def _hybrid(kda_layer=None, latent_layer=None, **by_attention):
    """One layer function for a model whose layers differ in backend: each
    layer's by its ``LayerKind.attention``."""
    by_attention = {"kda": kda_layer, "latent": latent_layer, **by_attention}

    def layer(x, cc, bp, l, j, ctx, cfg, *rest):
        fn = by_attention[cfg.layer_kind(j).attention]
        return fn(x, cc, bp, l, j, ctx, cfg, *rest)

    return layer


def _kda_prefill_layer(x, cc: Cache, bp: Any, l, j: int, ctx: dict,
                       cfg: ModelConfig, mesh, stack=None
                       ) -> tuple[jax.Array, Cache]:
    """One KDA layer of whole-prompt prefill: the convolution and the
    chunked form over the block's own rows; each row's final state and the
    convolution's tail into its slot's rows (behind the feed-forward, as
    the other backends' writes are)."""
    from orion_tpu.ops.kda import kda_chunked, short_conv

    ki, rows, lengths = cfg.cache_layer(l, j), ctx["state_rows"], ctx["lengths"]

    def attend(xs, g, b, conv):
        with jax.named_scope("qkv"), jax.named_scope("kda/conv"):
            y, tail = short_conv(xs, conv, lengths)
            q, k, v = kda_activate(y, cfg)
        with jax.named_scope("kernel"):
            o, state = kda_chunked(q, k, v, g, b, lengths=lengths)

        def written():
            return {
                KDA_STATE: cc[KDA_STATE].at[ki, rows].set(
                    jnp.swapaxes(state, -1, -2)),
                KDA_CONV: cc[KDA_CONV].at[ki, rows].set(
                    tail.astype(cc[KDA_CONV].dtype)),
            }

        return o.astype(xs.dtype), written

    return _prefill_block(x, cc, bp, j, ctx["positions"], attend,
                          ctx["seg"] > 0, stack, cfg, mesh)


def _kda_layer(x, cc: Cache, bp: Any, l, j: int, ctx: dict,
               cfg: ModelConfig, mesh) -> tuple[jax.Array, Cache]:
    """The KDA backend: one layer of one new token a slot. The slot's
    convolution tail takes the new row and its state advances one position
    (``ctx['active']`` [B]: the slots that do; None: all), both in place."""
    from orion_tpu.ops.kda import kda_step, short_conv_step

    ki, active = cfg.cache_layer(l, j), ctx["active"]
    B = ctx["at"].shape[0]

    def attend(xs, g, b, conv):
        tails = cc[KDA_CONV]
        at = (ki, 1, 0, 0)                      # the slots' rows of the layer
        with jax.named_scope("cache"):
            tail = jax.lax.dynamic_slice(
                tails, at, (1, B, *tails.shape[2:]))[0]
        with jax.named_scope("qkv"), jax.named_scope("kda/conv"):
            y, moved = short_conv_step(xs[:, 0], tail, conv)
            q, k, v = kda_activate(y, cfg)
        with jax.named_scope("kernel"):
            if ctx["use_pallas"]:
                if mesh is not None:
                    raise ValueError("the KDA decode kernel runs on one device")
                from orion_tpu.ops.pallas.kda import kda_decode

                o, state = kda_decode(
                    cc[KDA_STATE], q, k, v, g[:, 0], b[:, 0], layer=ki,
                    active=active, interpret=ctx["interpret"])
            else:
                st = cc[KDA_STATE]
                at_s = (ki, 1, 0, 0, 0)
                o, new = kda_step(
                    jax.lax.dynamic_slice(
                        st, at_s, (1, B, *st.shape[2:]))[0],
                    q, k, v, g[:, 0], b[:, 0], active)
                state = jax.lax.dynamic_update_slice(st, new[None], at_s)
        with jax.named_scope("cache"):
            if active is not None:
                moved = jnp.where(active[:, None, None], moved, tail)
            tails = jax.lax.dynamic_update_slice(tails, moved[None], at)
        return (o[:, None].astype(xs.dtype),
                {**cc, KDA_STATE: state, KDA_CONV: tails})

    x, _, cc = block(x, bp, cfg, ctx["positions"], attend,
                     kind=_kind(cfg, j), mesh=mesh)
    return x, cc


# -- the sparse and lightning backends: pages a query selects, a state row ----
#
# A model of ``mixer_types`` (config.ModelConfig; kv_cache.sala_leaves). A
# SPARSE layer writes K and V into pages as the paged backend does, and the
# compressed keys of the kernels that its new positions complete beside them;
# each query then selects its pages through the compressed keys and attends
# to those alone (ops/sparse.py: a page list a query and K/V head; a decode
# step walks it by the paged decode kernel over virtual slots, a prompt's
# chunk a block of queries at a time). A LIGHTNING layer keeps a state
# row a slot and no page (ops/lightning.py). Both RESUME: a prefill block is
# a page-aligned chunk that starts at ``prefix_lens`` (0: the prompt's first)
# over the row's page table ``prefix_pages`` (the slot's WHOLE row, the
# chunk's own pages at their places), reads the history's pages and
# compressed keys through it, and takes the lightning state the last chunk
# left in the slot's row. With no ``prefix_pages`` the block is a whole
# prompt over its own pages. A decode step is the same layer code at one
# query a slot, the new token's write fused into the kernel.

# An extra leaf a caller may put into the cache it hands a program: [sparse
# layers, rows, K/V heads, topk] int32, which each sparse layer then fills
# with the block ids it selected at each row's LAST real position (the
# benchmark's probe reads the selection back through it). The engine's own
# cache never has it.
SELECTED = "sparse_ids"


def _resumed_prefill(params, cache, tokens, lengths, pages, prefix_lens,
                     prefix_pages, state_rows, cfg, mesh):
    """``_prefill`` for a model of sparse and lightning layers: one block of
    each row's prompt, from ``prefix_lens`` on."""
    from orion_tpu.ops._dispatch import resolve_impl

    Nb, S = tokens.shape
    psz, NP = page_geometry(cache, cfg.n_paged_layers)
    resumed = prefix_pages is not None and prefix_pages.shape[1] > 0
    start = (prefix_lens.astype(jnp.int32) if resumed
             else jnp.zeros((Nb,), jnp.int32))
    at = jnp.arange(S, dtype=jnp.int32)[None]
    use_pallas, interpret = resolve_impl(cfg.kernels)
    ctx = dict(
        psz=psz, NP=NP, positions=start[:, None] + at,
        seg=(at < lengths[:, None]).astype(jnp.int32), lengths=lengths,
        start=start, pages=pages, table=prefix_pages if resumed else pages,
        state_rows=(jnp.zeros((Nb,), jnp.int32) if state_rows is None
                    else state_rows),
        use_pallas=use_pallas, interpret=interpret, fused=False,
    )
    layer = _hybrid(lightning=_lightning_prefill_layer,
                    sparse=_sparse_prefill_layer)

    def body(carry, bp, l, j, stack=None):
        x, cc = carry
        return layer(x, cc, bp, l, j, ctx, cfg, mesh, stack)

    x = embed(params, tokens, ctx["positions"], cfg)
    x, cache = _scan_layers(params, cfg, body, (x, dict(cache)))
    return _prefill_logits(params, x, lengths, cfg, mesh), cache


def _selected_ctx(cache: Cache, pos: jax.Array, page_table: jax.Array,
                  cfg: ModelConfig, active) -> dict:
    """The decode step's tensors of a model of sparse and lightning layers:
    one new position a slot at ``pos`` [B]."""
    from orion_tpu.ops._dispatch import resolve_impl

    psz, NP = page_geometry(cache, cfg.n_paged_layers)
    at = jnp.minimum(pos, page_table.shape[1] * psz - 1).astype(jnp.int32)
    use_pallas, interpret = resolve_impl(cfg.kernels)
    return dict(psz=psz, NP=NP, positions=at[:, None], table=page_table,
                active=active, use_pallas=use_pallas, interpret=interpret,
                fused=True)


def _sparse_attend(q, k, v, cc: Cache, li, ctx: dict, cfg: ModelConfig):
    """A sparse layer's attention for the new positions ``ctx['positions']``
    [B, Q] of each row (queries q [B, Q, N, H], keys and values [B, Q, K, H]):
    K, V and the compressed keys they complete into the pool, each query's
    selection, and attention over the selected pages. -> (out [B, Q, N, H],
    the leaves written)."""
    from orion_tpu.ops import sparse

    sp, psz, NP = cfg.sparse, ctx["psz"], ctx["NP"]
    kpp = sparse.kernels_per_page(sp)
    table, pos, base = ctx["table"], ctx["positions"], li * NP
    (B, Q), K = pos.shape, k.shape[2]
    fused, use_pallas = ctx["fused"], ctx["use_pallas"]
    page_of = lambda idx: jnp.take_along_axis(table, idx, axis=1)
    # The pools keep one K/V head a row: the rows of pages [...] -> [..., K].
    heads = lambda pages: (base + pages)[..., None] * K + jnp.arange(K)
    new = {}
    with jax.named_scope("cache"):
        if fused:
            # One position a row: the kernel that ends with it (if one
            # does) from the keys before it in the pool and the new key.
            t = pos[:, 0]
            done = ((t + 1) % sp.stride == 0) & (t + 1 >= sp.kernel)
            back = jnp.maximum(
                t[:, None] - (sp.kernel - 1)
                + jnp.arange(sp.kernel - 1, dtype=jnp.int32)[None], 0)
            earlier = cc["k"][heads(page_of(back // psz)), 0,
                              (back % psz)[..., None]]      # [B, .., K, H]
            c = sparse.compress_one(
                jnp.concatenate([earlier, k.astype(earlier.dtype)], 1), sp)
            jn = jnp.maximum((t + 1 - sp.kernel) // sp.stride, 0)
            crow = jnp.where(done, base + page_of((jn // kpp)[:, None])[:, 0],
                             0)
            ck = cc[COMPRESSED].at[crow, :, jn % kpp].set(
                c.astype(cc[COMPRESSED].dtype))
            k_pool, v_pool = cc["k"], cc["v"]
            if not use_pallas:      # (the kernel writes the position itself)
                at = (heads(page_of(pos // psz)), 0, (pos % psz)[..., None])
                k_pool = k_pool.at[at].set(k.astype(k_pool.dtype))
                v_pool = v_pool.at[at].set(v.astype(v_pool.dtype))
        else:
            # A page-aligned chunk: its pages, then the kernels that end
            # inside it (the first belongs to the page before the chunk).
            rows = heads(ctx["pages"])                      # [B, pages, K]
            paged = lambda a: a.reshape(B, Q // psz, psz, K, -1).transpose(
                0, 1, 3, 2, 4)
            k_pool = cc["k"].at[rows, 0].set(paged(k).astype(cc["k"].dtype))
            v_pool = cc["v"].at[rows, 0].set(paged(v).astype(cc["v"].dtype))
            s0 = ctx["start"]
            before = jnp.where(
                s0 > 0,
                page_of(jnp.maximum(s0 // psz - 1, 0)[:, None])[:, 0], 0)
            c = sparse.compress(
                k_pool[heads(before), 0].transpose(0, 2, 1, 3), k, sp)
            jn = (s0[:, None] // sp.stride - 1
                  + jnp.arange(Q // sp.stride, dtype=jnp.int32)[None])
            jc = jnp.maximum(jn, 0)
            crow = jnp.where(jn >= 0, base + page_of(jc // kpp), 0)
            ck = cc[COMPRESSED].at[crow, :, jc % kpp].set(
                c.astype(cc[COMPRESSED].dtype))
        new[COMPRESSED] = ck
    with jax.named_scope("kernel"):
        with jax.named_scope("select"):
            # The row's compressed keys in position order, [B, J, K, H].
            P = table.shape[1]
            ckg = ck[base + table].transpose(0, 1, 3, 2, 4).reshape(
                B, P * kpp, K, ck.shape[-1])

        def tile(qt, post, live=None):
            with jax.named_scope("select"):
                ids, n = sparse.select(qt, ckg.astype(qt.dtype), post, sp)
                T = ids.shape[-1]
                used = jnp.arange(T)[None, None, None] < n[..., None]
                pages = jnp.where(used, jnp.take_along_axis(
                    jnp.broadcast_to(
                        table[:, None, None, :], (*ids.shape[:3], P)),
                    jnp.minimum(ids, P - 1), axis=-1), 0)
            with jax.named_scope("sparse"):
                if not use_pallas:
                    out, written = sparse.attend_xla(
                        qt, k_pool, v_pool, base + pages, ids, n, post), ()
                elif fused:
                    out, *written = sparse.attend_pallas(
                        qt, k_pool, v_pool, pages, n, post, layer_base=base,
                        k_new=k, v_new=v, interpret=ctx["interpret"])
                else:
                    out, written = sparse.attend_blocks(
                        qt, k_pool, v_pool, pages, post, sp, layer_base=base,
                        live=live, interpret=ctx["interpret"]), ()
            return out, ids, written

        if fused:               # (the kernel wrote the position)
            out, ids, written = tile(q, pos)
            if written:
                k_pool, v_pool = written
        else:
            # Whole blocks of queries a tile; a block past a row's last real
            # position attends nothing.
            Qt = sparse.query_tile(Q, B * K, psz)
            live = ctx["seg"][:, ::psz] > 0
            if Qt == Q:
                out, ids, _ = tile(q, pos, live)
            else:
                cut = lambda a, n: jnp.moveaxis(
                    a.reshape(B, Q // Qt, n, *a.shape[2:]), 1, 0)
                out, ids = jax.lax.map(
                    lambda xs: tile(*xs)[:2],
                    (cut(q, Qt), cut(pos, Qt), cut(live, Qt // psz)))
                out = jnp.moveaxis(out, 0, 1).reshape(q.shape)
                ids = jnp.moveaxis(ids, 0, 2).reshape(
                    B, ids.shape[2], Q, ids.shape[-1])
    new["k"], new["v"] = k_pool, v_pool
    if SELECTED in cc:
        # Each row's selection at its last real position.
        last = (jnp.zeros((B,), jnp.int32) if fused
                else jnp.maximum(ctx["lengths"], 1) - 1)
        new[SELECTED] = cc[SELECTED].at[li].set(jnp.take_along_axis(
            ids, last[:, None, None, None], axis=2)[:, :, 0])
    return out, new


def _sparse_prefill_layer(x, cc: Cache, bp: Any, l, j: int, ctx: dict,
                          cfg: ModelConfig, mesh, stack=None):
    """One sparse layer of a prompt's block: the block's pages and compressed
    keys are written BEFORE its queries attend (they read their own block
    out of the pool), so nothing is left to write behind the feed-forward."""
    li = cfg.cache_layer(l, j)

    def attend(q, k, v):
        out, new = _sparse_attend(q, k, v, cc, li, ctx, cfg)
        return out, lambda: new

    return _prefill_block(x, cc, bp, j, ctx["positions"], attend,
                          ctx["seg"] > 0, stack, cfg, mesh)


def _sparse_layer(x, cc: Cache, bp: Any, l, j: int, ctx: dict,
                  cfg: ModelConfig, mesh) -> tuple[jax.Array, Cache]:
    """One sparse layer of a decode step (one new position a slot)."""
    li = cfg.cache_layer(l, j)

    def attend(q, k, v):
        out, new = _sparse_attend(q, k, v, cc, li, ctx, cfg)
        return out, {**cc, **new}

    x, _, cc = block(x, bp, cfg, ctx["positions"], attend,
                     kind=_kind(cfg, j), mesh=mesh)
    return x, cc


def _lightning_prefill_layer(x, cc: Cache, bp: Any, l, j: int, ctx: dict,
                             cfg: ModelConfig, mesh, stack=None):
    """One lightning layer of a prompt's block: the chunked form from the
    state the last block left in the row's slot (zeros where the block
    starts the prompt), and the state after the block's last real position
    back into it."""
    from orion_tpu.ops.lightning import lightning_chunked

    li, rows = cfg.cache_layer(l, j), ctx["state_rows"]

    def attend(q, k, v):
        with jax.named_scope("kernel"), jax.named_scope("lightning"):
            held = cc[LIGHTNING_STATE][li, rows]            # [Nb, N, H, H]
            held = jnp.where(
                ctx["start"][:, None, None, None] > 0, held, 0.0)
            o, state = lightning_chunked(
                q * jnp.asarray(q.shape[-1] ** -0.5, q.dtype), k, v, held,
                ctx["lengths"])
        return o.astype(q.dtype), lambda: {
            LIGHTNING_STATE: cc[LIGHTNING_STATE].at[li, rows].set(state)}

    return _prefill_block(x, cc, bp, j, ctx["positions"], attend,
                          ctx["seg"] > 0, stack, cfg, mesh)


def _lightning_layer(x, cc: Cache, bp: Any, l, j: int, ctx: dict,
                     cfg: ModelConfig, mesh) -> tuple[jax.Array, Cache]:
    """One lightning layer of a decode step: the slot's state advances one
    position in place (``ctx['active']`` [B]: the slots that do; None: all)."""
    from orion_tpu.ops.lightning import lightning_step

    li, active = cfg.cache_layer(l, j), ctx["active"]
    B = ctx["positions"].shape[0]

    def attend(q, k, v):
        q1 = q[:, 0] * jnp.asarray(q.shape[-1] ** -0.5, q.dtype)
        st = cc[LIGHTNING_STATE]
        with jax.named_scope("kernel"), jax.named_scope("lightning"):
            if ctx["use_pallas"]:
                if mesh is not None:
                    raise ValueError(
                        "the lightning decode kernel runs on one device")
                from orion_tpu.ops.pallas.lightning import lightning_decode

                o, state = lightning_decode(
                    st, q1, k[:, 0], v[:, 0], layer=li, active=active,
                    interpret=ctx["interpret"])
            else:
                at = (li, 1, 0, 0, 0)       # the slots' rows of the layer
                o, moved = lightning_step(
                    jax.lax.dynamic_slice(st, at, (1, B, *st.shape[2:]))[0],
                    q1, k[:, 0], v[:, 0], active)
                state = jax.lax.dynamic_update_slice(st, moved[None], at)
        return o[:, None].astype(q.dtype), {**cc, LIGHTNING_STATE: state}

    x, _, cc = block(x, bp, cfg, ctx["positions"], attend,
                     kind=_kind(cfg, j), mesh=mesh)
    return x, cc


# -- the retained backend: a fixed-size state row a slot beside a paged tail --
#
# A power-retention model (model.attention, ops/retention.py) keeps of a
# sequence a state row (``state`` / ``state_z``: everything below
# ``state_len``, a multiple of the model's chunk, ``_chunk``) and, in pages, only
# the K and V of the positions from there on. Those positions' cumulative
# log-gates, each within its own chunk, are the slot's too (``g``
# [layers, slots + 1, K, T]: column c is position ``state_len + c``,
# ``kv_cache.retention_leaves``): the array a decode step hands its kernel,
# kept as it is. The state is written in two places only: at the end of
# prefill and by ``fold_step``, which are also the two that re-base a row of
# ``g``. The page table stays indexed by absolute position; entries behind
# the state may point anywhere.


def _chunk(cfg: ModelConfig) -> int:
    from orion_tpu.ops.retention import fold_chunk

    return fold_chunk(cfg.max_seq_len)


def _retained_prefill(params, cache, tokens, lengths, pages, state_rows,
                      cfg: ModelConfig, mesh):
    """Whole prompts: every complete chunk of a row into its state row
    (``state_rows``: slot + 1; padding rows and a warm-up take scratch row
    0) and the gates of the positions behind them into its row of ``g``,
    written whole; every position's K and V into ``pages`` (the engine
    points the pages behind a row's state at scratch page 0)."""
    from orion_tpu.ops.retention import chunk_cumsum, power_retention

    Nb, S_pad = tokens.shape
    _, NP = page_geometry(cache, cfg.n_paged_layers)
    n_rows = cache["state_len"].shape[0]
    C, T = _chunk(cfg), cache["g"].shape[-1]
    if state_rows is None:
        # A caller of the bare function with a K/V model's seven arguments
        # (tests/benchmark/test_aot_v5e.py); the engine's program always
        # gets an array (executor.jit_program), so that one program serves
        # the warm-up and the window.
        state_rows = jnp.zeros((Nb,), jnp.int32)
    positions = jnp.broadcast_to(
        jnp.arange(S_pad, dtype=jnp.int32), (Nb, S_pad))
    valid = positions < lengths[:, None]
    folded = lengths // C * C
    # Column c of a row of ``g`` is position folded + c: the row's tail,
    # then zeros (nothing of the slot's last tenant stays).
    tail = folded[:, None] + jnp.arange(T, dtype=jnp.int32)     # [Nb, T]
    tail_at = jnp.minimum(tail, S_pad - 1)[:, None, :]
    tail_live = (tail < lengths[:, None])[:, None, :]

    def body(carry, bp, l, j, stack=None):
        x, cc = carry

        def attend(q, k, v, log_g):
            with jax.named_scope("kernel"):
                y, (S, z) = power_retention(
                    q, k, v, log_g, lengths=lengths, chunk=C,
                    impl=cfg.kernels)

            def written():
                new = _scatter_pages(cc, k, v, l * NP + pages)
                b = jnp.swapaxes(chunk_cumsum(log_g, C), 1, 2)  # [Nb, K, S]
                new["g"] = cc["g"].at[l, state_rows].set(jnp.where(
                    tail_live, jnp.take_along_axis(b, tail_at, axis=2), 0.0))
                srows = l * n_rows + state_rows
                new["state"] = cc["state"].at[srows].set(
                    S.astype(cc["state"].dtype))
                new["state_z"] = cc["state_z"].at[srows].set(
                    jnp.swapaxes(z, 1, 2))
                return new

            return y, written

        x, _, written = block(
            x, bp, cfg, positions, attend, kind=_kind(cfg, j), mesh=mesh,
            ffn_mesh=mesh, valid=valid)
        with jax.named_scope(_CACHE_PART):
            return x, {**cc, **written()}

    x = embed(params, tokens, positions, cfg)
    x, cache = _scan_layers(params, cfg, body, (x, dict(cache)))
    with jax.named_scope(_CACHE_PART):
        cache["state_len"] = cache["state_len"].at[state_rows].set(folded)
    return _prefill_logits(params, x, lengths, cfg, mesh), cache


def _retained_ctx(cache: Cache, pos: jax.Array, page_table: jax.Array,
                  cfg: ModelConfig) -> dict:
    """Batch-level tensors of ``_retained_layer``: one new token a slot at
    position ``pos`` (slot b of the page table owns state row b + 1)."""
    from orion_tpu.ops._dispatch import resolve_impl

    psz, NP = page_geometry(cache, cfg.n_paged_layers)
    C = _chunk(cfg)
    F = cache["state_len"][1:]                                 # [B]
    at = jnp.minimum(pos, page_table.shape[1] * psz - 1)
    col = jnp.arange(cache["g"].shape[-1], dtype=jnp.int32)    # [T]
    jpos = F[:, None] + col                                    # [B, T]
    use_pallas, interpret = resolve_impl(cfg.kernels)
    return dict(
        NP=NP, C=C, F=F, pos=pos, at=at, positions=at[:, None],
        page_table=page_table,
        # The columns of a slot's row of ``g`` that hold the new position
        # and the one before it; a chunk's sum restarts at its first
        # position, which therefore has no column before it.
        new_col=jpos == at[:, None],
        prev_col=(jpos == pos[:, None] - 1) & (pos % C != 0)[:, None],
        second=jpos >= (F + C)[:, None],
        live=jpos <= at[:, None],
        n_rows=cache["state_len"].shape[0],
        use_pallas=use_pallas, interpret=interpret,
    )


def _retained_layer(x, cc: Cache, bp: Any, l, j: int, ctx: dict,
                    cfg: ModelConfig, mesh) -> tuple[jax.Array, Cache]:
    """The retained backend: one layer of one new token a slot. Its K and V
    land in the tail page (inside the kernel on the pallas path, as the
    paged backend's do) and its cumulative log-gate in one column of the
    slot's row of ``g``; the query attends its slot's tail and reads its
    state row. The gates are this function's: what the kernel is handed is,
    for the new token and for every tail position, the log-decay since
    ``state_len``. A column behind the newest position may hold anything (a
    quarantined tenant's NaN): each is read behind a ``where``."""
    from orion_tpu.ops.retention import BIG, retention_decode_xla

    NP, C, F, pos, at = ctx["NP"], ctx["C"], ctx["F"], ctx["pos"], ctx["at"]
    B = at.shape[0]

    def attend(q, k, v, log_g):
        g = cc["g"]
        at_l = (l, 1, 0, 0)                     # the slots' rows of layer l
        with jax.named_scope("cache"):
            bt = jax.lax.dynamic_slice(g, at_l, (1, B, *g.shape[2:]))[0]
            b_new = log_g[:, 0] + jnp.where(
                ctx["prev_col"][:, None, :], bt, 0.0).sum(-1)  # [B, K]
            bt = jnp.where(ctx["new_col"][:, None, :], b_new[:, :, None], bt)
        with jax.named_scope("kernel"):
            # A tail spans two chunks at most: positions of the second add
            # the whole first chunk's sum (its last position's entry).
            first = bt[:, :, C - 1]                            # [B, K]
            c_tail = bt + jnp.where(ctx["second"][:, None, :],
                                    first[:, :, None], 0.0)
            c_tail = jnp.where(ctx["live"][:, None, :], c_tail, BIG)
            c_q = b_new + jnp.where((at >= F + C)[:, None], first, 0.0)
            kw = dict(layer_base=l * NP, state_base=l * ctx["n_rows"])
            args = (q[:, 0], k[:, 0], v[:, 0], c_q, c_tail, cc["k"], cc["v"],
                    cc["state"], cc["state_z"], ctx["page_table"], F, pos)
            if ctx["use_pallas"]:
                if mesh is not None:
                    raise ValueError(
                        "the retention kernels run on one device")
                from orion_tpu.ops.pallas.retention import retention_decode

                y, kp, vp = retention_decode(
                    *args, interpret=ctx["interpret"], **kw)
            else:
                y, kp, vp = retention_decode_xla(*args, **kw)
            y = y[:, None]
        with jax.named_scope("cache"):
            g = jax.lax.dynamic_update_slice(g, bt[None], at_l)
        return y, {**cc, "k": kp, "v": vp, "g": g}

    x, _, cc = block(x, bp, cfg, ctx["positions"], attend,
                     kind=_kind(cfg, j), mesh=mesh)
    return x, cc


def fold_step(cache: Cache, slot: jax.Array, page_row: jax.Array, *,
              cfg: ModelConfig, mesh=None) -> Cache:
    """Fold the complete chunk at the head of ONE slot's tail (positions
    ``state_len .. state_len + chunk`` of slot ``slot``: K and V through
    its page-table row ``page_row`` [P], gates from the first ``chunk``
    columns of its row of ``g``) into its state row, in every layer; then
    advance ``state_len`` and move the row of ``g`` down a chunk with it.
    The engine runs it at the start of a decode window for each slot whose
    tail holds a complete chunk, then frees the chunk's pages. No weights
    are read."""
    from orion_tpu.ops._dispatch import resolve_impl
    from orion_tpu.ops.retention import retention_fold_xla

    del mesh
    psz, NP = page_geometry(cache, cfg.n_paged_layers)
    n_rows = cache["state_len"].shape[0]
    C, P = _chunk(cfg), page_row.shape[0]
    use_pallas, interpret = resolve_impl(cfg.kernels)
    with jax.named_scope(_CACHE_PART):
        F = cache["state_len"][slot + 1]
        pages = page_row[jnp.minimum(F // psz + jnp.arange(C // psz), P - 1)]
        gates = jax.lax.dynamic_index_in_dim(
            cache["g"], slot + 1, axis=1, keepdims=False)      # [L, K, T]

    @jax.named_scope(_CACHE_PART)
    def chunk(pool, rows):      # [n, K, psz, ...] -> [K, C, ...]
        x = jnp.moveaxis(pool[rows], 1, 0)
        return x.reshape(x.shape[0], C, *x.shape[3:])

    def body(carry, xs):
        state, z = carry
        l, b = xs
        rows = l * NP + pages
        args = (state, z, chunk(cache["k"], rows), chunk(cache["v"], rows),
                b, l * n_rows + slot + 1)
        with jax.named_scope("attention/kernel"):
            if use_pallas:
                from orion_tpu.ops.pallas.retention import retention_fold

                return tuple(retention_fold(*args, interpret=interpret)), None
            return retention_fold_xla(*args), None

    (state, z), _ = jax.lax.scan(
        body, (cache["state"], cache["state_z"]),
        (jnp.arange(cfg.n_layers), gates[:, :, :C]))
    with jax.named_scope(_CACHE_PART):
        moved = jnp.pad(gates[:, :, C:], ((0, 0), (0, 0), (0, C)))
        return {**cache, "state": state, "state_z": z,
                "g": jax.lax.dynamic_update_index_in_dim(
                    cache["g"], moved, slot + 1, 1),
                "state_len": cache["state_len"].at[slot + 1].add(C)}
