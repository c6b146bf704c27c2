"""Fused rotary embedding as a Pallas TPU kernel (reference fused RoPE).

One VMEM pass per (batch, seq-block): computes the f32 angle tables from the
integer positions in-kernel (no host-side cos/sin materialization in HBM) and
applies the Llama rotate-half convention to all heads of the block.

The rotation is linear and orthogonal in x, so the VJP is the same kernel
with the angle sign flipped: dx = rope(g, -theta-angles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

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


def _rope_call(theta, flip, block_seq, interpret, x, positions):
    B, S, N, H = x.shape
    # Never below 128 rows: the (1, 1, bs) position block rides the lanes.
    fit = max(128, ROW_BLOCK_F32_BYTES // (4 * N * H) // 128 * 128)
    bs = min(block_seq, fit, round_up(S, 8))
    Sp = round_up(S, bs)
    xp = pad_axis(x, 1, Sp)
    pp = pad_axis(positions, 1, Sp)[:, None, :]  # (B, 1, Sp): TPU tiling
    out = pl.pallas_call(
        functools.partial(_rope_kernel, theta, flip),
        grid=(B, Sp // bs),
        in_specs=[
            pl.BlockSpec((1, bs, N, H), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, bs), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bs, N, H), lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        interpret=interpret,
        name="rope",
    )(xp, pp)
    return out[:, :S]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _rope(theta, block_seq, interpret, x, positions):
    return _rope_call(theta, False, block_seq, interpret, x, positions)


def _rope_fwd(theta, block_seq, interpret, x, positions):
    return _rope(theta, block_seq, interpret, x, positions), positions


def _rope_bwd(theta, block_seq, interpret, positions, g):
    return _rope_call(theta, True, block_seq, interpret, g, positions), None


_rope.defvjp(_rope_fwd, _rope_bwd)


def rope_pallas(
    x: jax.Array,
    positions: jax.Array,
    *,
    theta: float = 500_000.0,
    block_seq: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Apply rotary embedding; x [B, S, N, H], positions [B, S] or [S]."""
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :], x.shape[:2])
    return _rope(
        float(theta), block_seq, resolve_interpret(interpret), x, positions.astype(jnp.int32)
    )
