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
                 touch), with the tiles ``_tiles`` chooses from the call's
                 shape. The trace shows it as ``gmm.N``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

# Tiles of the Pallas kernel, from tools/grouped_matmul_sweep.py on a v5e
# (PERF.md section 6: PR 26 at Mixtral's widths, PR 55 at every cell's own
# call shapes; ms a call, even and skewed group sizes alike).
#
# The wide tiles were fitted at G = 8, m in 1024 / 4096 / 8192, (k, n) =
# (4096, 14336) and (14336, 4096): tm = 256 is the best m-tile at every m
# once half the rows are padding and within 3 % of 512 at m = 8192 without; a
# contraction that fits one k-tile needs no accumulator pass (133 TFLOP/s at
# m = 8192), a longer one does best with the widest n-tile that 16 MiB of
# scoped VMEM admits (123). ``lax.ragged_dot`` reads 92-95 there (XLA's own
# kernel at 512^3) and the library's default of 128^3 reads 10. PR 55 read
# them again at 128 and 256 rows a group: still the best of every (tm, tn)
# (under a contraction cut in k-tiles a smaller tm re-reads the weights: tm
# 128 is 1.27x and tm 64 1.9x the time of 256 at 128 rows a group).
#
# Where an expert's whole matrix fits the scoped VMEM as ONE weight tile
# (tk = k, tn = n) the grid is the visits alone, every row is read once and a
# group's weights once (its visits are consecutive grid steps with one block
# index, so the pipeline copies them once whatever tm is). At [2048, 768] /
# [768, 2048] with 128 groups that alone is 0.958 -> 0.736 and 0.902 -> 0.756
# ms a call of 4096 rows against (256, k, 512), whose second n-tile of 768 is
# half empty and whose four of 2048 read the rows four times. The row tile
# then follows the rows a group gets: under 128 a group tm = 128 (0.679 /
# 0.691 ms there, 0.98x at 64 rows a group; tm 64 and 32 read 3-12 % behind
# 128: the MXU streams at least 128 rows a weight tile), from 128 rows a
# group on 256 (0.98x of tm 128). "Fits" counts the library's reverse mode
# too, which runs its transposed kernel under the same tiles with the whole
# [k, n] as output tile and float32 accumulator: [2560, 768] is one tile
# forward and is refused by the chip's compiler in reverse (compiled for a
# described v5e, PR 55), so it keeps the wide tiles.
#
# A group that ends inside an m-tile costs the whole tile, so
# ``models/moe.takes_grouped_path`` charges the grouped path ``G * ROW_TILE``
# rows of rounding (on every backend: one rule, whatever runs the matmul) and
# ``moe.held_row_bound`` rounds its bound to ``ROW_TILE``: the widest row tile
# ``_tiles`` returns, the worst case whatever tile a call gets.
ROW_TILE = 256
VMEM_BYTES = 16 * 2 ** 20      # the scoped VMEM a kernel may take on a v5e


def tile_vmem_bytes(tm: int, tk: int, tn: int, reverse: bool = False,
                    itemsize: int = 2) -> int:
    """What the kernel keeps in VMEM under ``(tm, tk, tn)``: the row, weight
    and output tiles, each double buffered by the pipeline, and the float32
    accumulator, ``[tm, tn]`` forward and ``[tk, tn]`` in the reverse mode's
    ``tgmm`` (rows^T @ cotangent rows, under the same tiles)."""
    acc = tk * tn if reverse else tm * tn
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * acc


def _tiles(m: int, G: int, k: int, n: int, itemsize: int = 2
           ) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` for ``lhs [m, k] @ rhs [G, k, n]``, all four static
    at trace time; ``G`` counts the groups that can hold rows (one layer's
    experts, also where ``rhs`` is a stack of layers)."""
    tm = 128 if m < 128 * G else ROW_TILE
    if max(tile_vmem_bytes(tm, k, n, rev, itemsize)
           for rev in (False, True)) <= VMEM_BYTES:
        return tm, k, n
    if k <= 4096:
        return ROW_TILE, k, min(512, n)
    return ROW_TILE, 1024, min(2048, n)


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

    m, k = lhs.shape
    G, n = rhs.shape[-3], rhs.shape[-1]
    if layer is not None:
        # One flat run of L*G groups, all but this layer's empty: the
        # kernel's grid holds no tile for an empty group.
        L = rhs.shape[0]
        rhs = rhs.reshape(L * G, *rhs.shape[2:])
        group_sizes = lax.dynamic_update_slice(
            jnp.zeros((L * G,), jnp.int32), group_sizes.astype(jnp.int32),
            (layer * G,))
    tp, shards = None, 1
    if mesh is not None:
        msh, manual = manual_context(mesh)
        tp = split_axes(msh, ("tp",), k if contract_tp else n, manual)
        shards = msh.shape["tp"] if tp else 1
    reduce = contract_tp and tp is not None
    # From the widths a shard's kernel sees; rows are never split.
    ks, ns = (k // shards, n) if contract_tp else (k, n // shards)
    tiles = _tiles(m, G, ks, ns, itemsize=lhs.dtype.itemsize)
    pad = -m % tiles[0]        # the kernel wants whole m-tiles
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))

    def specs(msh, manual):
        del msh, manual
        if contract_tp:
            return (P(None, tp), P(None, tp, None), P(None)), P(None, None)
        return (P(None, None), P(None, None, tp), P(None)), P(None, tp)

    def run(a, w, g):
        out = gmm(a, w, g, tiling=tiles, interpret=interpret,
                  preferred_element_type=jnp.float32 if reduce else a.dtype)
        return lax.psum(out, "tp").astype(a.dtype) if reduce else out

    out = shard_kernel(run, mesh, specs)(lhs, rhs, group_sizes)
    return out[:m] if pad else out
