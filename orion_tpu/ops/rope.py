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

# Sequence lengths from here up reach the Pallas kernel under
# ``impl="pallas"``; shorter ones (decode's one new token a slot, a verify
# window, a short chunk) are the XLA forms below, which the compiler fuses
# with the q/k norm before them and the layout change after them. From
# tools/rope_sweep.py on a v5e (PERF.md section 6, PR 34), bf16
# ``[B, S, N, 128]`` at B = 32, us a call in a chain of calls, kernel / XLA:
#
#   S        N = 8        N = 32       N = 40       N = 72      N = 72 (t)
#   1     11.3 / 0.9   14.1 / 2.2   60.4 / 2.6   44.2 / 4.3   28.2 / 5.1
#   8     13.7 / 4.8   24.2 / 16.0  71.7 / 21.1  62.3 / 33.8  47.8 / 40.3
#   16    17.3 / 9.3   38.7 / 31.3  94.9 / 38.1  95.6 / 68.0  77.0 / 75.8
#   32    27.9 / 18.5  71.4 / 63.9   142 / 71.9   138 / 102   99.6 / 114
#   64    48.6 / 37.7   100 / 89     196 / 104    213 / 283    147 / 401
#   128     91 / 74     153 / 256    328 / 424    424 / 1205   324 / 1369
#   512    237 / 302    906 / 2542  1517 / 3193  2049 / 5563  1933 / 6918
#
# ((t): the table kernel on a YaRN, half-rotated table.) The kernel's grid
# is one batch row a step on a block of at least 8 rows, 0.35-1.9 us a step
# whatever the rows hold: at one row it costs 5 to 23 times the XLA form.
# Through 32 rows the XLA form is ahead at every head count on the plain
# table and within 15 % on a table; it falls off from 48 rows on a table
# (2x at 48, 2.7x at 64) and from 64-128 on the plain one (1.3 to 3.6 times
# the kernel's time at 512). At B = 1 the two are within a tenth at 8 and
# 32 heads and the XLA form is ahead by up to 3x at 40 and 72. The kernel
# handed all ``B x S`` rows as ONE sequence reads 0.9-6.2 us at S = 1, level
# with the XLA form alone (0.9-5.1) and so behind it inside a program, where
# only the XLA form fuses with its neighbours: no second kernel was built.
KERNEL_MIN_SEQ = 64


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
    ``impl="pallas"`` means the kernel from ``KERNEL_MIN_SEQ`` rows up and
    the XLA form under it: the length the caller hands over decides, at
    trace time.
    """
    from orion_tpu.ops._dispatch import (
        _BATCH_AXES, resolve_impl, shard_kernel, split_axes,
    )

    use_pallas, interpret = resolve_impl(impl)
    if use_pallas and x.shape[1] >= KERNEL_MIN_SEQ:
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
