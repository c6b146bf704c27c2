"""Rotary position embeddings (reference ``orion.ops`` fused-RoPE equivalent).

Llama rotate-half convention: the head dim is split in two halves, rotated by
position-dependent angles with base ``theta``. Frequencies are computed once
in float32; application casts back to the activation dtype.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
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


def rope_table(head_dim: int, rope) -> tuple[tuple, float, int]:
    """(inv_freq: rot // 2 float32 values as a tuple, so that the table is
    hashable; cos/sin scale; rot) of one rotary
    table. ``rope`` carries ``config.RopeConfig``'s fields: the first
    ``rot = head_dim * rotary_fraction`` dims of a head rotate; with
    ``yarn_factor`` the frequencies are YaRN's as ``transformers`` computes
    them (1/f and 1/(factor f) blended by the linear ramp between the
    correction dims of beta_fast and beta_slow at the original context),
    and cos and sin are multiplied by ``attention_factor``."""
    rot = int(head_dim * rope.rotary_fraction)
    f = float(rope.theta) ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    inv = 1.0 / f
    if rope.yarn_factor is not None:
        def correction_dim(n_rot):
            return (rot * math.log(rope.yarn_original_max_pos
                                   / (n_rot * 2 * math.pi))
                    / (2 * math.log(rope.theta)))

        low = max(math.floor(correction_dim(rope.yarn_beta_fast)), 0)
        high = min(math.ceil(correction_dim(rope.yarn_beta_slow)), rot - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
        inv = inv / rope.yarn_factor * ramp + inv * (1.0 - ramp)
    return (tuple(inv.astype(np.float32).tolist()),
            float(rope.attention_factor), rot)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    *,
    theta: float = 500_000.0,
    rope=None,
    impl: str = "xla",
    mesh: Optional[jax.sharding.Mesh] = None,
) -> jax.Array:
    """Apply rotary embedding to q or k.

    x: [B, S, N, H]; positions: [B, S] (or [S], broadcast over batch).
    ``rope`` (a ``config.RopeConfig``) selects a table that is not the plain
    one at ``theta`` (partial rotation, YaRN frequencies, scaled cos/sin).
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

        table = None if rope is None else rope_table(x.shape[-1], rope)
        return shard_kernel(
            lambda x_, p_: rope_pallas(
                x_, p_, theta=theta, table=table, interpret=interpret
            ),
            mesh, specs,
        )(x, positions)
    if rope is not None:
        return _rope_xla_table(x, positions, *rope_table(x.shape[-1], rope))
    return _rope_xla(x, positions, theta)


def _rope_xla_table(x, positions, inv_freq, scale, rot):
    """The rotation by a table: the first ``rot`` dims of each head rotate
    (rotate-half pairing within them), the rest pass through."""
    dtype = x.dtype
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos = (jnp.cos(ang) * scale)[:, :, None, :]
    sin = (jnp.sin(ang) * scale)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : rot // 2], xf[..., rot // 2: rot]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xf[..., rot:]], axis=-1
    )
    return out.astype(dtype)


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
