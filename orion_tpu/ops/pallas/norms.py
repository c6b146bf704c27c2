"""Fused RMSNorm as a Pallas TPU kernel (reference ``orion.ops`` fused norm).

Forward fuses the square-mean reduction, rsqrt, and scale multiply in one
VMEM pass over row blocks. The custom VJP computes dx with a second fused
kernel (recomputing the row rstd instead of storing it); dscale is a single
cross-row reduction left to XLA, which emits an optimal fused reduce.

dx derivation for y = x * r * s with r = rsqrt(mean(x^2) + eps):
  dx = r * (g*s - x * r^2 * mean(g*s*x, axis=-1))
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas.common import (
    ROW_BLOCK_F32_BYTES,
    pad_axis,
    resolve_interpret,
    round_up,
)


def _fwd_kernel(eps, x_ref, s_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[:] = (x * r * s_ref[0, :].astype(jnp.float32)[None, :]).astype(
        o_ref.dtype
    )


def _dx_kernel(eps, x_ref, s_ref, g_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    s = s_ref[0, :].astype(jnp.float32)[None, :]
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    u = g * s
    o_ref[:] = (r * (u - x * r * r * jnp.mean(u * x, axis=-1, keepdims=True))).astype(
        o_ref.dtype
    )


def _rows_call(kernel, eps, block_rows, interpret, out_dtype, x2d, scale2d, *extra):
    R, D = x2d.shape
    fit = max(8, ROW_BLOCK_F32_BYTES // (4 * D) // 8 * 8)
    br = min(block_rows, fit, round_up(R, 8))
    Rp = round_up(R, br)
    x2d = pad_axis(x2d, 0, Rp)
    extra = [pad_axis(e, 0, Rp) for e in extra]
    row_spec = pl.BlockSpec((br, D), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(kernel, eps),
        grid=(Rp // br,),
        in_specs=[row_spec, pl.BlockSpec((1, D), lambda i: (0, 0))]
        + [row_spec] * len(extra),
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((Rp, D), out_dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2d, scale2d, *extra)
    return out[:R]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _rmsnorm(eps, block_rows, interpret, x2d, scale):
    return _rows_call(
        _fwd_kernel, eps, block_rows, interpret, x2d.dtype, x2d, scale[None, :]
    )


def _rmsnorm_fwd(eps, block_rows, interpret, x2d, scale):
    return _rmsnorm(eps, block_rows, interpret, x2d, scale), (x2d, scale)


def _rmsnorm_bwd(eps, block_rows, interpret, res, g):
    x2d, scale = res
    dx = _rows_call(
        _dx_kernel, eps, block_rows, interpret, x2d.dtype, x2d, scale[None, :], g
    )
    xf = x2d.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    dscale = jnp.einsum("rd,rd->d", g.astype(jnp.float32), xf * r)
    return dx, dscale.astype(scale.dtype)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm_pallas(
    x: jax.Array,
    scale: jax.Array,
    *,
    eps: float = 1e-5,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """RMSNorm over the last axis; x [..., D], scale [D]."""
    D = x.shape[-1]
    x2d = x.reshape(-1, D)
    out = _rmsnorm(eps, block_rows, resolve_interpret(interpret), x2d, scale)
    return out.reshape(x.shape)
