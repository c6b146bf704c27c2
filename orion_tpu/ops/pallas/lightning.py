"""One decode step of lightning attention as a Pallas TPU kernel.

``ops/lightning.py`` has the recurrence. At decode a slot's state in a
layer is ``heads x d_v x d_k`` float32 (32 x 128 x 128: 2 MiB), read AND
written every step, and the kernel is bound by those bytes: it is
``ops/pallas/kda.py``'s walk (a slot's whole row a grid step where the
pipeline's four buffers of it fit, the state aliased in/out, value-major so
that a key and a query are rows that broadcast over sublanes) around a
smaller body:

    M = M . a + v k^T        o = M q

``a`` is the head's fixed decay, a number, handed in as a row like the
key. ``v`` arrives as a row and ``o`` leaves as one; both change hands with
the column form through an identity mask, as in the KDA kernel.

Dead slots (``active`` false) are passed by: their blocks' indices point at
the scratch row 0 of the layer and nothing is computed. Inference-only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas.common import resolve_interpret
from orion_tpu.ops.pallas.kda import GROUP, head_block


def _kernel(hb: int, rows_ref, layer_ref, act_ref,
            q_ref, k_ref, a_ref, v_ref, s_ref, o_ref, so_ref):
    del rows_ref, layer_ref
    b = pl.program_id(0)
    dv = s_ref.shape[-2]
    gb = GROUP if hb % GROUP == 0 else hb

    @pl.when(act_ref[b] == 0)
    def _dead():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def group(j, carry):
        """Heads ``j * gb ..`` of the block, their rows one tile each."""
        at = j * gb if hb == gb else pl.multiple_of(j * gb, GROUP)
        q, k, a, v = (ref[0, pl.ds(at, gb), :]
                      for ref in (q_ref, k_ref, a_ref, v_ref))
        eye = (lax.broadcasted_iota(jnp.int32, (dv, dv), 0)
               == lax.broadcasted_iota(jnp.int32, (dv, dv), 1)
               ).astype(jnp.float32)
        for i in range(gb):
            row = lambda x: x[i:i + 1]                       # [1, d]
            v_col = (eye * row(v)).sum(-1, keepdims=True)    # [dv, 1]
            m = s_ref[0, 0, at + i] * row(a) + v_col * row(k)
            so_ref[0, 0, at + i] = m
            o_col = (m * row(q)).sum(-1, keepdims=True)      # [dv, 1]
            o_ref[0, pl.ds(at + i, 1), :] = (eye * o_col).sum(
                0, keepdims=True)
        return carry

    @pl.when(act_ref[b] != 0)
    def _live():
        if hb == gb:
            group(0, None)
        else:
            lax.fori_loop(0, hb // gb, group, None)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _call(state, q, k, a, v, layer, active, *, interpret, name):
    B, N, dk = q.shape
    dv = v.shape[-1]
    hb = head_block(N, dv, dk)
    act = active.astype(jnp.int32)
    prefetch = [jnp.where(act > 0, jnp.arange(1, B + 1, dtype=jnp.int32), 0),
                layer, act]
    vec = lambda d: pl.BlockSpec(
        (1, hb, d), lambda b, h, rows, layer, act: (b, h, 0))
    # A dead slot's blocks all sit at (layer, 0, 0): fetched and written
    # back once for a run of them.
    st = pl.BlockSpec(
        (1, 1, hb, dv, dk),
        lambda b, h, rows, layer, act: (layer[0], rows[b], h * act[b], 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, N // hb),
            in_specs=[vec(dk), vec(dk), vec(dk), vec(dv), st],
            out_specs=[vec(dv), st],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, N, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # Operand indices count the scalar-prefetch arguments.
        input_output_aliases={len(prefetch) + 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name=name,
    )(*prefetch, q, k, a, v, state)
    return o, state


def lightning_decode(
    state: jax.Array,     # [layers, slots + 1, N, d_v, d_k] float32
    q: jax.Array,         # [B, N, d_k] (scaled)
    k: jax.Array,         # [B, N, d_k]
    v: jax.Array,         # [B, N, d_v]
    *,
    layer,                # which of the state's layers (may be traced)
    active=None,          # [B] bool: the slots that advance (default all)
    interpret: bool = False,
    name: str = "lightning_decode",
):
    """-> (o [B, N, d_v] float32, state'): slot s's row (s + 1 of ``layer``)
    advanced one position in place; every other row, every other layer and
    the rows of dead slots are bitwise untouched (but for the scratch row
    0). Semantics: ``ops.lightning.lightning_step`` on ``state[layer, 1:]``."""
    from orion_tpu.ops.lightning import slopes

    f32 = jnp.float32
    q, k, v = (x.astype(f32) for x in (q, k, v))
    a = jnp.broadcast_to(
        jnp.exp(-slopes(q.shape[1]))[None, :, None], q.shape)
    if active is None:
        active = jnp.ones((q.shape[0],), bool)
    return _call(state, q, k, a, v,
                 jnp.asarray(layer, jnp.int32).reshape(1), active,
                 interpret=interpret, name=name)
