"""Runtime: device/mesh discovery and multi-host bring-up.

TPU-native replacement for the reference's ``orion.distributed`` process-group
initialization (NCCL rendezvous); see SURVEY.md §4 stack C. Here bring-up is
``jax.distributed.initialize`` (DCN rendezvous) plus construction of a named
`jax.sharding.Mesh` over ICI; collectives are compiled in by XLA from sharding
annotations rather than issued through a communicator handle.
"""

from orion_tpu.runtime.mesh import (
    MESH_AXES,
    build_mesh,
    local_mesh,
    mesh_devices,
)
from orion_tpu.runtime.distributed import (
    enable_compile_cache,
    initialize,
    runtime_info,
)

__all__ = [
    "MESH_AXES",
    "build_mesh",
    "local_mesh",
    "mesh_devices",
    "enable_compile_cache",
    "initialize",
    "runtime_info",
]
