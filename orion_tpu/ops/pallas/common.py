"""Shared helpers for the Pallas TPU kernels (reference ``orion.ops`` L0).

All kernels in this package follow the same conventions:

- Block shapes are static; callers pad to block multiples and the kernels
  mask padded positions (XLA/Mosaic require static shapes, SURVEY.md §8).
- Math is float32 inside the kernel regardless of the activation dtype
  (bf16-safe convention shared with the xla reference ops).
- ``interpret=True`` runs the kernel through the Pallas interpreter so the
  same code is testable on the fake-CPU-device mesh (SURVEY.md §5).
"""

from __future__ import annotations

import jax

NEG_INF = -1e30  # finite -inf stand-in: exp(NEG_INF - m) underflows to 0.

# f32 bytes of one block of the row-wise kernels (RMSNorm, RoPE). Each keeps
# a handful of f32 temporaries of the block live beside its double-buffered
# bf16 in/out blocks, all inside Mosaic's 16 MiB scoped-VMEM limit: at 2 MiB
# the whole grid step takes about 8 MiB whatever the width. The kernels size
# their row blocks from this, because a FIXED row count scales with the
# width — 256 rows were 2 MiB at llama-1b's widths and 4 MiB at Mistral-7B's,
# which the v5e compiler refuses (16.11 and 18.75 MiB scoped).
ROW_BLOCK_F32_BYTES = 2 * 2 ** 20


def resolve_interpret(interpret: bool) -> bool:
    """The kernel's ``interpret`` flag, checked against the backend.

    Compiled (``interpret=False``) means Mosaic-compiled for a TPU, always:
    on any other backend this raises, naming the backend, so a run can
    never pass with every kernel quietly interpreted. The interpreter is
    reached only by asking for it (``kernels="pallas_interpret"``).
    """
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"compiled Pallas kernels need a TPU, but the default JAX "
            f"backend is {jax.default_backend()!r}; use "
            f"kernels='pallas_interpret' (interpret=True) to run the "
            f"kernels through the Pallas interpreter"
        )
    return bool(interpret)


def round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def pad_axis(x: jax.Array, axis: int, target: int) -> jax.Array:
    """Zero-pad ``axis`` of x up to length ``target``."""
    if x.shape[axis] == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - x.shape[axis])
    import jax.numpy as jnp

    return jnp.pad(x, pads)


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-vector int8: x [..., H] -> (q int8 [..., H], scale
    [...] f32) with x ~ q * scale. Scale is per (token, kv-head).

    Lives here (plain jnp, Pallas-kernel-legal) because it is the SINGLE
    definition both the jnp cache paths (infer/kv_cache.py re-exports it)
    and the paged kernels' fused in-kernel writes must share — decode,
    prefill, and speculative verification have to agree bit-for-bit.

    The scale is an explicit multiply by the f32 constant 1/127, NOT a
    division by 127: XLA keeps a true f32 divide on the host path but
    rewrites constant divides to reciprocal multiplies inside compiled /
    interpreted Pallas bodies, and the two round differently by 1 ULP on
    some inputs — enough to flip a greedy argmax between the kernel and
    jnp cache paths. One fixed multiply lowers identically everywhere.
    """
    import jax.numpy as jnp
    import numpy as np

    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) * np.float32(
        1.0 / 127.0
    )
    s = jnp.maximum(s, 1e-8)
    q = jnp.round(x.astype(jnp.float32) / s[..., None])
    return q.astype(jnp.int8), s
