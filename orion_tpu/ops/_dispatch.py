"""Kernel-implementation dispatch shared by the op wrappers.

``ModelConfig.kernels`` selects the op backend:
  - "xla"              — pure-jnp reference path (CPU/test default)
  - "pallas"           — compiled Pallas TPU kernels; raises on any
                         backend other than ``tpu``
  - "pallas_interpret" — same kernels through the Pallas interpreter (the
                         only way to reach it; for the fake-CPU-device
                         test mesh, SURVEY.md §5)
"""

from __future__ import annotations

import jax

_VALID = ("xla", "pallas", "pallas_interpret")


def resolve_impl(impl: str) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)."""
    if impl not in _VALID:
        raise ValueError(f"unknown kernel impl {impl!r}; expected one of {_VALID}")
    return impl != "xla", impl == "pallas_interpret"


# -- running a kernel per shard ----------------------------------------------
# A Mosaic kernel cannot be partitioned by XLA: inside a jit that spans more
# than one device a bare ``pallas_call`` is refused at lowering ("Mosaic
# kernels cannot be automatically partitioned. Please wrap the call in a
# shard_map"). The interpreter lowers to plain HLO and never shows it, so the
# fake-CPU-device mesh cannot catch a missing wrapper; a TPU compile can.
# Every Pallas op wrapper therefore takes the caller's mesh and runs its
# kernel through ``shard_kernel``.

_BATCH_AXES = ("dp", "fsdp")   # parallel.sharding.DEFAULT_RULES["batch"]


def manual_context(mesh):
    """(mesh a shard_map opened here must bind, axes already manual).

    Inside a region that is manual over some axis (the pipeline's pp
    shard_map) a nested shard_map must bind the CONTEXT abstract mesh —
    re-binding the concrete all-Auto mesh is rejected there — and may only
    go manual over the axes that are still Auto.
    """
    ctx = jax.sharding.get_abstract_mesh()
    manual = frozenset(
        a for a, t in zip(ctx.axis_names, ctx.axis_types)
        if t == jax.sharding.AxisType.Manual
    )
    return (ctx if manual else mesh), manual


def split_axes(mesh, names, dim: int, manual=frozenset()):
    """PartitionSpec entry splitting a dim of length ``dim`` over the live
    (size > 1, still Auto) mesh axes among ``names``; None when there are
    none or they do not divide it (the kernel then sees the whole dim on
    every device — redundant work, same result)."""
    axes = tuple(
        a for a in names if mesh.shape.get(a, 1) > 1 and a not in manual
    )
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if not axes or dim % n:
        return None
    return axes if len(axes) > 1 else axes[0]


def shard_kernel(fn, mesh, specs_fn):
    """``fn`` run per shard of ``mesh`` (see the section comment).

    ``specs_fn(mesh, manual) -> (in_specs, out_specs)`` builds the
    PartitionSpecs against the mesh actually bound. With no mesh, one
    device, or every live axis already manual (the caller is inside a
    full shard_map), ``fn`` is returned as is.
    """
    if mesh is None:
        return fn
    mesh, manual = manual_context(mesh)
    auto = frozenset(
        a for a in mesh.axis_names
        if mesh.shape[a] > 1 and a not in manual
    )
    if not auto:
        return fn
    in_specs, out_specs = specs_fn(mesh, manual)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=frozenset(mesh.axis_names) - manual, check_vma=False,
    )
