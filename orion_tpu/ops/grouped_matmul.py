"""Grouped matmul: ``lhs[rows of group g] @ rhs[g]`` for row groups laid end
to end — the expert matmul of the dropless MoE dispatch
(``models/moe.moe_mlp_grouped``).

``lhs`` [m, k] holds the rows sorted by group, ``rhs`` [G, k, n] one matrix a
group, ``group_sizes`` [G] int32 the rows of each. Rows past
``sum(group_sizes)`` belong to no group: the xla path returns zeros there,
the Pallas kernel never visits them and leaves what was in memory, so the
caller masks them. bf16 operands accumulate in float32; the result has
``lhs``'s dtype.

  - ``xla``    — ``lax.ragged_dot`` (the portable form; on a TPU XLA lowers
                 it to its own 512^3-tiled kernel).
  - ``pallas`` — the megablox ``gmm`` kernel that ships with JAX (imported,
                 not vendored; its grid holds only the tiles the groups
                 touch), with the tiles of ``_tiles``. The trace shows it as
                 ``gmm.N``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

# Tiles of the Pallas kernel, from tools/grouped_matmul_sweep.py on a v5e at
# G = 8, m in 1024 / 4096 / 8192, (k, n) = (4096, 14336) and (14336, 4096)
# (PERF.md section 6, PR 26). tm = 256 is the best m-tile at every m once
# half the rows are padding and within 3 % of 512 at m = 8192 without; a
# contraction that fits one k-tile needs no accumulator pass (133 TFLOP/s at
# m = 8192), a longer one does best with the widest n-tile that 16 MiB of
# scoped VMEM admits (123). ``lax.ragged_dot`` reads 92-95 there (XLA's own
# kernel at 512^3) and the library's default of 128^3 reads 10.
# A group that ends inside an m-tile costs the whole tile, so
# ``models/moe.takes_grouped_path`` charges the grouped path ``G * TILE_M``
# rows of rounding (on every backend: one rule, whatever runs the matmul).
TILE_M = 256


def _tiles(k: int, n: int) -> tuple[int, int, int]:
    if k <= 4096:
        return TILE_M, k, min(512, n)
    return TILE_M, 1024, min(2048, n)


def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    group_sizes: jax.Array,
    *,
    impl: str = "xla",
    mesh: Optional[jax.sharding.Mesh] = None,
    contract_tp: bool = False,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """``mesh`` (the mesh the enclosing jit spans) runs the Pallas kernel per
    shard, split over ``tp`` alone: ``rhs``'s n axis, or with ``contract_tp``
    the contraction axis of both operands (the expert down-projection, whose
    partial products are then summed over ``tp``). Rows stay whole on every
    device: they are sorted by group across the whole block. The xla path
    leaves partitioning to XLA and ignores both.

    With ``layer`` (an index, traced under the layer scan), ``rhs`` is the
    layer stack [L, G, k, n] and the product uses ``rhs[layer]``. The kernel
    reads that layer's tiles out of the stack in place: a custom call cannot
    fuse the scan's dynamic slice, so a sliced ``rhs`` reaches it as a copy
    of every expert's matrix (0.94 GB at Mixtral's widths, a quarter of a
    second of a six-second trace; PERF.md section 6, PR 26)."""
    from orion_tpu.ops._dispatch import (
        manual_context, resolve_impl, shard_kernel, split_axes,
    )

    use_pallas, interpret = resolve_impl(impl)
    if not use_pallas:
        if layer is not None:
            rhs = lax.dynamic_index_in_dim(rhs, layer, keepdims=False)
        return lax.ragged_dot(lhs, rhs, group_sizes)

    from jax.experimental.pallas.ops.tpu.megablox import gmm

    if layer is not None:
        # One flat run of L*G groups, all but this layer's empty: the
        # kernel's grid holds no tile for an empty group.
        L, G = rhs.shape[:2]
        rhs = rhs.reshape(L * G, *rhs.shape[2:])
        group_sizes = lax.dynamic_update_slice(
            jnp.zeros((L * G,), jnp.int32), group_sizes.astype(jnp.int32),
            (layer * G,))
    m, k = lhs.shape
    n = rhs.shape[2]
    pad = -m % TILE_M          # the kernel wants whole m-tiles
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tp = None
    if mesh is not None:
        msh, manual = manual_context(mesh)
        tp = split_axes(msh, ("tp",), k if contract_tp else n, manual)
    reduce = contract_tp and tp is not None

    def specs(msh, manual):
        del msh, manual
        if contract_tp:
            return (P(None, tp), P(None, tp, None), P(None)), P(None, None)
        return (P(None, None), P(None, None, tp), P(None)), P(None, tp)

    def run(a, w, g):
        out = gmm(a, w, g, tiling=_tiles(a.shape[1], w.shape[2]),
                  interpret=interpret,
                  preferred_element_type=jnp.float32 if reduce else a.dtype)
        return lax.psum(out, "tp").astype(a.dtype) if reduce else out

    out = shard_kernel(run, mesh, specs)(lhs, rhs, group_sizes)
    return out[:m] if pad else out
