"""Rotary position embeddings (reference ``orion.ops`` fused-RoPE equivalent).

Llama rotate-half convention: the head dim is split in two halves, rotated by
position-dependent angles with base ``theta``. Frequencies are computed once
in float32; application casts back to the activation dtype.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def rope_frequencies(
    head_dim: int, positions: jax.Array, theta: float
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for given integer positions.

    positions: [...,] int array (any shape, typically [B, S] or [S]).
    Returns (cos, sin), each [..., head_dim // 2], float32.
    """
    half = head_dim // 2
    freq = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = positions.astype(jnp.float32)[..., None] * freq  # [..., half]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    *,
    theta: float = 500_000.0,
    impl: str = "xla",
    mesh: Optional[jax.sharding.Mesh] = None,
) -> jax.Array:
    """Apply rotary embedding to q or k.

    x: [B, S, N, H]; positions: [B, S] (or [S], broadcast over batch).
    ``mesh`` (the mesh the enclosing jit spans) runs the Pallas kernel per
    shard — batch, sequence and heads split over their mesh axes — because
    a Mosaic kernel cannot be auto-partitioned; the xla path ignores it.
    """
    from orion_tpu.ops._dispatch import (
        _BATCH_AXES, resolve_impl, shard_kernel, split_axes,
    )

    use_pallas, interpret = resolve_impl(impl)
    if use_pallas:
        from orion_tpu.ops.pallas.rope import rope_pallas

        if positions.ndim == 1:
            positions = jnp.broadcast_to(positions[None, :], x.shape[:2])

        def specs(m, manual):
            b = split_axes(m, _BATCH_AXES, x.shape[0], manual)
            s = split_axes(m, ("sp",), x.shape[1], manual)
            h = split_axes(m, ("tp",), x.shape[2], manual)
            xs = P(b, s, h, None)
            return (xs, P(b, s)), xs

        return shard_kernel(
            lambda x_, p_: rope_pallas(
                x_, p_, theta=theta, interpret=interpret
            ),
            mesh, specs,
        )(x, positions)
    return _rope_xla(x, positions, theta)


def _rope_xla(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    dtype = x.dtype
    head_dim = x.shape[-1]
    if positions.ndim == 1:
        positions = positions[None, :]
    cos, sin = rope_frequencies(head_dim, positions, theta)  # [B, S, half]
    cos = cos[:, :, None, :]  # broadcast over heads
    sin = sin[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(dtype)
