"""Multi-head attention (reference ``orion.ops`` fused-attention equivalent).

The xla implementation is the semantic reference: grouped-query causal
attention with a numerically stable float32 softmax, optional segment masking
(packed sequences) and logit soft-capping. The Pallas flash kernel
(orion_tpu.ops.pallas.flash_attention) implements the same contract with
blockwise online softmax; both are exercised against each other in tests.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _gqa_expand(k: jax.Array, n_heads: int) -> jax.Array:
    """[B, S, K, H] -> [B, S, N, H] by repeating each kv head N/K times."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    assert n_heads % n_kv == 0, (n_heads, n_kv)
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def attention_mask(
    q_len: int,
    kv_len: int,
    *,
    causal: bool = True,
    q_offset: int = 0,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
    block: int = 0,
) -> Optional[jax.Array]:
    """Boolean [.., q_len, kv_len] mask; True = attend.

    ``window`` (sliding-window / Mistral-family) keeps only the last
    ``window`` positions: 0 <= q_pos - kv_pos < window. Positions default
    to token index (+ q_offset for q); explicit per-token positions
    ([.., q_len] / [.., kv_len]) serve packed/permuted layouts.

    ``block`` (generation by diffusion over blocks): the causal mask with
    its diagonal rounded up to the end of the query's block of ``block``
    positions, kv_pos // block <= q_pos // block.
    """
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} requires causal attention and window >= 1"
        )
    if block and (not causal or window is not None):
        raise ValueError(
            f"block={block} requires causal attention and no window")
    mask = None
    if causal:
        q_pos = (
            q_positions
            if q_positions is not None
            else jnp.arange(q_len) + q_offset
        )
        kv_pos = (
            kv_positions if kv_positions is not None else jnp.arange(kv_len)
        )
        if block:
            q_pos = q_pos // block * block + block - 1
        dist = q_pos[..., :, None] - kv_pos[..., None, :]
        mask = dist >= 0
        if window is not None:
            mask &= dist < window
    if q_segment_ids is not None:
        seg = q_segment_ids[..., :, None] == kv_segment_ids[..., None, :]
        mask = seg if mask is None else (mask & seg)
    return mask


def attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    mask: Optional[jax.Array] = None,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    logit_softcap: Optional[float] = None,
    q_offset: int = 0,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
    block: int = 0,
) -> jax.Array:
    """q: [B, Sq, N, H]; k: [B, Skv, K, H], v: [B, Skv, K, Hv] with N % K
    == 0 -> [B, Sq, N, Hv]. ``sink`` [N]: a learned logit a query head that
    joins each row's softmax and whose column is dropped (the weights of a
    row add up to less than 1)."""
    dtype = q.dtype
    n_heads, head_dim = q.shape[2], q.shape[3]
    k = _gqa_expand(k, n_heads)
    v = _gqa_expand(v, n_heads)

    if mask is not None and window is not None:
        raise ValueError(
            "window cannot combine with an explicit mask (it would be "
            "silently ignored); fold the window into the mask or drop it"
        )
    scale = head_dim ** -0.5
    logits = jnp.einsum(
        "bqnh,bknh->bnqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)

    if mask is None:
        mask = attention_mask(
            q.shape[1],
            k.shape[1],
            causal=causal,
            q_offset=q_offset,
            q_segment_ids=q_segment_ids,
            kv_segment_ids=kv_segment_ids,
            q_positions=q_positions,
            kv_positions=kv_positions,
            window=window,
            block=block,
        )
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None, :, :]
        elif mask.ndim == 3:  # [B, q, kv]
            mask = mask[:, None, :, :]
        logits = jnp.where(mask, logits, NEG_INF)

    if sink is not None:
        col = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None],
            (*logits.shape[:3], 1))
        probs = jax.nn.softmax(
            jnp.concatenate([logits, col], axis=-1), axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnqk,bknh->bqnh", probs.astype(dtype), v)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    mask: Optional[jax.Array] = None,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    logit_softcap: Optional[float] = None,
    q_offset: int = 0,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    impl: str = "xla",
    seg_pad_zero: bool = False,
    mesh: Optional[jax.sharding.Mesh] = None,
    tp_axis: str = "tp",
    sink: Optional[jax.Array] = None,
    block: int = 0,
) -> jax.Array:
    """Grouped-query scaled-dot-product attention. Shapes as attention_xla.

    ``seg_pad_zero`` declares segment id 0 = padding so the flash kernel
    may SKIP all-padding blocks (ragged prefill / packed tails); results
    are unchanged for callers honoring the pack_rows convention, and the
    xla path ignores it (no block structure to skip).

    ``mesh`` (the mesh the enclosing jit spans) runs the flash kernel per
    shard under a ``shard_map`` that splits the batch over dp/fsdp and the
    HEAD axes over ``tp_axis``: a Mosaic kernel cannot be auto-partitioned
    (ops/_dispatch.py), so on more than one device the wrapper is what
    lets the program compile at all, and it keeps the kernel's operands
    sharded instead of gathered. The xla path ignores ``mesh`` — einsums
    partition natively from the operands' shardings.
    """
    from orion_tpu.ops._dispatch import (
        _BATCH_AXES, resolve_impl, shard_kernel, split_axes,
    )

    use_pallas, interpret = resolve_impl(impl)
    if use_pallas:
        if mask is not None:
            raise ValueError(
                "explicit `mask` is only supported by impl='xla'; express the "
                "mask via causal/q_segment_ids for the flash kernel"
            )
        from orion_tpu.ops.pallas.flash_attention import flash_attention

        kernel_kw = dict(
            causal=causal,
            logit_softcap=logit_softcap,
            q_offset=q_offset,
            window=window,
            block_q=block_q,
            block_kv=block_kv,
            interpret=interpret,
            seg_pad_zero=seg_pad_zero,
        )
        tp = mesh.shape.get(tp_axis, 1) if mesh is not None else 1
        n_heads, n_kv = q.shape[2], k.shape[2]
        if sink is not None:
            if mesh is not None and mesh.size > 1:
                raise ValueError(
                    "flash attention with a sink runs on one device: its "
                    "per-head logits are not split over a mesh yet")
            kernel_kw["sink"] = sink
        if block:
            kernel_kw["block"] = block
        if n_heads % tp or n_kv % tp:
            raise ValueError(
                f"tp-sharded flash attention needs n_heads ({n_heads}) "
                f"and n_kv_heads ({n_kv}) divisible by {tp_axis}={tp}; "
                f"lower tp or use impl='xla'"
            )
        # Optional operands join the arg list only when present so the
        # shard_map signature stays positional.
        present = {
            name: a for name, a in (
                ("q_segment_ids", q_segment_ids),
                ("kv_segment_ids", kv_segment_ids),
                ("q_positions", q_positions),
                ("kv_positions", kv_positions),
            ) if a is not None
        }
        extras = list(present.values())

        def specs(m, manual):
            # Batch and heads split; sequence stays whole (sequence
            # parallelism has its own shard_map, parallel/sequence.py).
            b = split_axes(m, _BATCH_AXES, q.shape[0], manual)
            hspec = P(b, None, split_axes(m, (tp_axis,), n_kv, manual), None)
            # Segments are [B, S]; positions [B, S] or [S].
            especs = [P(b, None) if a.ndim == 2 else P(None) for a in extras]
            return (hspec, hspec, hspec, *especs), hspec

        def body(q_, k_, v_, *rest):
            return flash_attention(
                q_, k_, v_, **kernel_kw, **dict(zip(present, rest))
            )

        return shard_kernel(body, mesh, specs)(q, k, v, *extras)
    return attention_xla(
        q,
        k,
        v,
        causal=causal,
        mask=mask,
        q_segment_ids=q_segment_ids,
        kv_segment_ids=kv_segment_ids,
        logit_softcap=logit_softcap,
        q_offset=q_offset,
        q_positions=q_positions,
        kv_positions=kv_positions,
        window=window,
        sink=sink,
        block=block,
    )
