"""Fused rotary embedding as a Pallas TPU kernel (reference fused RoPE).

One VMEM pass per (batch, seq-block): computes the f32 angle tables from the
integer positions in-kernel (no host-side cos/sin materialization in HBM) and
applies the Llama rotate-half convention to all heads of the block.

The rotation is linear and orthogonal in x, so the VJP is the same kernel
with the angle sign flipped: dx = rope(g, -theta-angles).

Which lengths reach it: ``ops.rope.apply_rope`` hands this kernel sequences
of ``ops.rope.KERNEL_MIN_SEQ`` rows and longer (prefill buckets, training);
shorter ones (a decode step's one new token a slot, a verify window) it
rotates with the XLA form. The grid is one batch row a step over sequence
blocks of a multiple of 8 rows, so at ``S = 1`` and 32 slots the kernel ran
32 steps on blocks of which 7 rows of 8 were padding, 11-60 us a call where
the fused XLA code takes 1-5 (the sweep's table is beside the constant).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas.common import (
    ROW_BLOCK_F32_BYTES,
    pad_axis,
    resolve_interpret,
    round_up,
)


def _rope_kernel(theta, flip, x_ref, pos_ref, o_ref):
    # x_ref: [1, bs, N, H]; pos_ref: [1, 1, bs] (3D for TPU tiling)
    H = x_ref.shape[-1]
    half = H // 2
    x = x_ref[0].astype(jnp.float32)                      # [bs, N, H]
    pos = pos_ref[0, 0, :].astype(jnp.float32)            # [bs]
    expo = (
        jax.lax.broadcasted_iota(jnp.int32, (1, half), 1).astype(jnp.float32)
        / half
    )
    freq = jnp.exp(-jnp.log(theta) * expo)                # [1, half]
    angles = pos[:, None] * freq                          # [bs, half]
    cos = jnp.cos(angles)[:, None, :]                     # [bs, 1, half]
    sin = jnp.sin(angles)[:, None, :]
    if flip:
        sin = -sin
    x1 = x[..., :half]
    x2 = x[..., half:]
    o_ref[0] = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(o_ref.dtype)


def _rope_table_kernel(r2, flip, x_ref, pos_ref, tab_ref, o_ref):
    """The rotation by a table. tab_ref [8, H] float32 rows: 0 the angle
    frequency of each lane (0 past the rotated dims), 1 the factor on cos
    (the scale on rotated lanes, 1 past them), 2 / 3 the factors on sin for
    the lane's partner ``r2`` lanes above / below it (-scale on the first
    half of the rotated dims, +scale on the second, 0 elsewhere). Whole-lane
    rolls stand in for the half-split, so no slice is narrower than a
    lane tile."""
    H = x_ref.shape[-1]
    x = x_ref[0].astype(jnp.float32)                      # [bs, N, H]
    pos = pos_ref[0, 0, :].astype(jnp.float32)            # [bs]
    ang = pos[:, None] * tab_ref[0:1, :]                  # [bs, H]
    cos = (jnp.cos(ang) * tab_ref[1:2, :])[:, None, :]
    sin = jnp.sin(ang)
    if flip:
        sin = -sin
    up = (sin * tab_ref[2:3, :])[:, None, :]
    down = (sin * tab_ref[3:4, :])[:, None, :]
    o_ref[0] = (
        x * cos
        + pltpu.roll(x, H - r2, 2) * up      # lane i reads lane i + r2
        + pltpu.roll(x, r2, 2) * down        # lane i reads lane i - r2
    ).astype(o_ref.dtype)


def _table_rows(table, H):
    inv_freq, scale, rot = table
    r2 = rot // 2
    rows = np.zeros((8, H), np.float32)
    rows[0, :r2] = rows[0, r2:rot] = inv_freq
    rows[1] = 1.0
    rows[1, :rot] = scale
    rows[2, :r2] = -scale
    rows[3, r2:rot] = scale
    return rows, r2


def _rope_call(theta, flip, block_seq, interpret, x, positions, table=None):
    B, S, N, H = x.shape
    # Never below 128 rows: the (1, 1, bs) position block rides the lanes.
    # Where 128 rows of every head overrun the row-block budget (72 heads of
    # 128: 4.5 MiB in float32, and the compiler refuses the kernel), the
    # heads are blocked too, in the largest multiple of 8 that divides them
    # and fits.
    bn = N
    if 4 * N * H * 128 > ROW_BLOCK_F32_BYTES:
        bn = max((d for d in range(8, N, 8) if N % d == 0
                  and 4 * d * H * 128 <= ROW_BLOCK_F32_BYTES), default=N)
    fit = max(128, ROW_BLOCK_F32_BYTES // (4 * bn * H) // 128 * 128)
    bs = min(block_seq, fit, round_up(S, 8))
    Sp = round_up(S, bs)
    xp = pad_axis(x, 1, Sp)
    pp = pad_axis(positions, 1, Sp)[:, None, :]  # (B, 1, Sp): TPU tiling
    grid = (B, Sp // bs) + ((N // bn,) if bn < N else ())
    x_spec = pl.BlockSpec(
        (1, bs, bn, H), lambda b, i, *n: (b, i, n[0] if n else 0, 0))
    in_specs = [x_spec, pl.BlockSpec((1, 1, bs), lambda b, i, *n: (b, 0, i))]
    if table is not None:
        rows, r2 = _table_rows(table, H)
        kernel = functools.partial(_rope_table_kernel, r2, flip)
        in_specs.append(pl.BlockSpec((8, H), lambda b, i, *n: (0, 0)))
        args = (xp, pp, jnp.asarray(rows))
    else:
        kernel = functools.partial(_rope_kernel, theta, flip)
        args = (xp, pp)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        interpret=interpret,
        name="rope",
    )(*args)
    return out[:, :S]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _rope(theta, table, block_seq, interpret, x, positions):
    return _rope_call(theta, False, block_seq, interpret, x, positions, table)


def _rope_fwd(theta, table, block_seq, interpret, x, positions):
    return _rope(theta, table, block_seq, interpret, x, positions), positions


def _rope_bwd(theta, table, block_seq, interpret, positions, g):
    return _rope_call(
        theta, True, block_seq, interpret, g, positions, table), None


_rope.defvjp(_rope_fwd, _rope_bwd)


def rope_pallas(
    x: jax.Array,
    positions: jax.Array,
    *,
    theta: float = 500_000.0,
    table=None,
    block_seq: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Apply rotary embedding; x [B, S, N, H], positions [B, S] or [S].
    ``table`` (``ops.rope.rope_table``'s result) in place of the plain
    table at ``theta``."""
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :], x.shape[:2])
    return _rope(
        None if table is not None else float(theta), table, block_seq,
        resolve_interpret(interpret), x, positions.astype(jnp.int32)
    )
