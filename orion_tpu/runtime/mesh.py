"""Device-mesh construction over ICI / DCN.

The mesh is the TPU-native communicator: every parallelism strategy in
``orion_tpu.parallel`` is a set of named axes here (SURVEY.md §2 layer L1/L2).
Axis order is chosen for ICI locality — the innermost (fastest-varying) axes
get physically adjacent devices, so the bandwidth-hungry axes (tp, then sp/ep)
ride the shortest ICI hops, while pp/dp tolerate the outermost placement and
any DCN split.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from orion_tpu.config import ParallelConfig

log = logging.getLogger("orion_tpu.runtime")

# Outermost -> innermost. tp innermost (highest-bandwidth collectives),
# pp outermost (lowest-frequency p2p traffic).
MESH_AXES: tuple[str, ...] = ("pp", "dp", "fsdp", "ep", "sp", "tp")


def mesh_devices(platform: Optional[str] = None) -> list[jax.Device]:
    """All devices for mesh construction, honoring an explicit platform."""
    if platform is not None:
        return list(jax.devices(platform))
    return list(jax.devices())


def hybrid_shapes(
    parallel: ParallelConfig,
) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """(ici_shape, dcn_shape) for a multi-slice mesh, in MESH_AXES order.

    Axes named in ``parallel.dcn_axes`` cross DCN (one mesh dim per slice);
    all other axes stay intra-slice on ICI. Unknown axis names raise — a
    typo here would otherwise silently produce a pure-ICI layout.
    """
    bad = set(parallel.dcn_axes) - set(MESH_AXES)
    if bad:
        raise ValueError(
            f"parallel.dcn_axes names unknown mesh axes {sorted(bad)}; "
            f"valid: {MESH_AXES}"
        )
    sizes = parallel.axis_sizes
    ici = tuple(
        1 if a in parallel.dcn_axes else sizes[a] for a in MESH_AXES
    )
    dcn = tuple(
        sizes[a] if a in parallel.dcn_axes else 1 for a in MESH_AXES
    )
    return ici, dcn


def build_mesh(
    parallel: ParallelConfig,
    devices: Optional[Sequence[jax.Device]] = None,
    platform: Optional[str] = None,
) -> Mesh:
    """Build the named Mesh for a ParallelConfig.

    Single-slice: devices are laid out with ``mesh_utils.create_device_mesh``
    so ICI topology is respected. Multi-slice (``parallel.dcn_axes`` set):
    hybrid mesh with the listed axes crossing DCN.
    """
    devs = list(devices) if devices is not None else mesh_devices(platform)
    sizes = parallel.axis_sizes
    n = parallel.num_devices
    if n > len(devs):
        raise ValueError(
            f"parallel config wants {n} devices "
            f"({dict(sizes)}), but only {len(devs)} are available"
        )
    if n < len(devs):
        log.warning(
            "parallel config uses %d of %d available devices", n, len(devs)
        )
        devs = devs[:n]
    shape = tuple(sizes[a] for a in MESH_AXES)

    if parallel.dcn_axes:
        ici_shape, dcn_shape = hybrid_shapes(parallel)
        return Mesh(
            _hybrid_device_array(ici_shape, dcn_shape, devs), MESH_AXES
        )

    if devices is None and devs and devs[0].platform == "tpu":
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(shape, devices=devs)
    else:
        # CPU fake devices / explicit device list: plain row-major reshape.
        arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, MESH_AXES)


def _hybrid_device_array(
    ici_shape: tuple[int, ...],
    dcn_shape: tuple[int, ...],
    devs: Sequence[jax.Device],
) -> np.ndarray:
    """Device array for a hybrid ICI/DCN mesh.

    Real TPU multi-slice devices carry ``slice_index``: delegate to
    ``mesh_utils.create_hybrid_device_mesh`` (topology-aware per-slice
    arrangement). CPU multi-process runs have no slices — the process
    boundary IS the DCN stand-in (loopback Gloo), so group devices by
    ``process_index`` and tile the groups over the DCN axes; this is what
    lets the dcn_axes code path run over a REAL process boundary in tests
    instead of being stubbed. Single-process fake devices (no grouping
    possible) fall back to a plain row-major reshape — construction-only
    semantics, which is all a one-process mesh has anyway.
    """
    if devs and devs[0].platform == "tpu":
        from jax.experimental import mesh_utils

        return mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devs
        )
    n_groups = int(np.prod(dcn_shape))
    per_group = int(np.prod(ici_shape))
    groups: dict[int, list[jax.Device]] = {}
    for d in devs:
        groups.setdefault(d.process_index, []).append(d)
    shape = tuple(i * d for i, d in zip(ici_shape, dcn_shape))
    per_process = sorted((p, len(g)) for p, g in groups.items())
    uniform = len({n for _, n in per_process}) == 1
    if (
        len(groups) != n_groups
        and len(groups) % n_groups == 0
        and uniform
    ):
        # Non-trivial per-slice factor (e.g. 2 slices x 2 processes each):
        # a CPU "slice" is a GROUP of consecutive processes, so an ICI
        # axis can span process boundaries within a slice while the DCN
        # axes cross slice groups — the 2-slice x 2-host factorization of
        # a real multi-slice pod, stood in by loopback Gloo. Only merges
        # equal-sized per-process groups: uneven contributions must fail
        # validation below, not silently build an irregular layout.
        k = len(groups) // n_groups
        pids = sorted(groups)
        groups = {
            pids[i * k]: sum((groups[p] for p in pids[i * k:(i + 1) * k]), [])
            for i in range(n_groups)
        }
    if len(groups) != n_groups or any(
        len(g) != per_group for g in groups.values()
    ):
        if len(groups) == 1:
            # Single-process fake-device testing: no real boundary exists;
            # a deterministic reshape validates the axis bookkeeping.
            return np.asarray(devs).reshape(shape)
        raise ValueError(
            f"dcn_axes wants {n_groups} process groups of {per_group} "
            f"devices, but processes provide {per_process} "
            f"(per-process device counts, pre-merge)"
        )
    out = np.empty(shape, dtype=object)
    for gi, pid in enumerate(sorted(groups)):
        coord = np.unravel_index(gi, dcn_shape)
        block = np.asarray(groups[pid]).reshape(ici_shape)
        out[tuple(
            slice(c * i, c * i + i) for c, i in zip(coord, ici_shape)
        )] = block
    return out


def local_mesh(platform: Optional[str] = None) -> Mesh:
    """Trivial all-ones mesh over however many devices exist locally (dp)."""
    devs = mesh_devices(platform)
    cfg = ParallelConfig(dp=len(devs))
    return build_mesh(cfg, devices=devs)
