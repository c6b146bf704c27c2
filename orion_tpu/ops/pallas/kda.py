"""One decode step of Kimi delta attention as a Pallas TPU kernel.

``ops/kda.py`` has the recurrence. At decode a slot's state in a layer is
``heads x d_v x d_k`` float32 (32 x 128 x 128: 2 MiB), read AND written
every step: the kernel is bound by those bytes and by nothing else, so it
walks (slot, block of heads), takes a block of the state through the
pipeline, updates it on the vector unit in float32 and hands it back to the
same place (the state is aliased in/out; under the engine's donated cache
nothing is copied, which is what PR 37 measured a copy out and back to
cost). The state is kept VALUE-major, ``M = S^T`` [d_v, d_k]: a key
channel's decay, the key and the query are then rows that broadcast over
sublanes, and the two products along d_k are lane reductions:

    M' = M . a            r = M' k          u = v - r
    M  = M' + u (b k)^T   o = M q

``v`` arrives as a row and ``o`` leaves as one; both change hands with the
column form through an identity mask (one multiply and one reduction of a
[d_v, d_v] tile, beside six of the state's own).

Dead slots (``active`` false) are passed by: their blocks' indices point at
the scratch row 0 of the layer, which the pipeline fetches once for a run of
them, and nothing is computed. Inference-only; no VJP.

The prefill's chunked form is ``ops.kda.kda_chunked`` in XLA under the scope
``kda/chunk`` on every backend: the chunk's products are the MXU's either
way, and a Pallas kernel of it is left to a later PR (PERF.md section 7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas.common import resolve_interpret

HEAD_BLOCK = 8


def _kernel(hb: int, rows_ref, layer_ref, act_ref,
            q_ref, k_ref, kb_ref, g_ref, v_ref, s_ref, o_ref, so_ref):
    del rows_ref, layer_ref
    b = pl.program_id(0)
    dv = s_ref.shape[-2]

    @pl.when(act_ref[b] == 0)
    def _dead():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(act_ref[b] != 0)
    def _live():
        eye = (lax.broadcasted_iota(jnp.int32, (dv, dv), 0)
               == lax.broadcasted_iota(jnp.int32, (dv, dv), 1)
               ).astype(jnp.float32)
        for i in range(hb):
            row = lambda ref: ref[0, i:i + 1, :]            # [1, d]
            m = s_ref[0, 0, i] * jnp.exp(row(g_ref))         # [dv, dk]
            r = (m * row(k_ref)).sum(-1, keepdims=True)      # [dv, 1]
            v_col = (eye * row(v_ref)).sum(-1, keepdims=True)
            m = m + (v_col - r) * row(kb_ref)
            so_ref[0, 0, i] = m
            o_col = (m * row(q_ref)).sum(-1, keepdims=True)  # [dv, 1]
            o_ref[0, i:i + 1, :] = (eye * o_col).sum(0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _call(state, q, k, kb, g, v, layer, active, *, interpret, name):
    B, N, dk = q.shape
    dv = v.shape[-1]
    hb = HEAD_BLOCK if N % HEAD_BLOCK == 0 else N
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
            in_specs=[vec(dk), vec(dk), vec(dk), vec(dk), vec(dv), st],
            out_specs=[vec(dv), st],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, N, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # Operand indices count the scalar-prefetch arguments.
        input_output_aliases={len(prefetch) + 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name=name,
    )(*prefetch, q, k, kb, g, v, state)
    return o, state


def kda_decode(
    state: jax.Array,     # [layers, slots + 1, N, d_v, d_k] float32
    q: jax.Array,         # [B, N, d_k] (l2-normalised, scaled)
    k: jax.Array,         # [B, N, d_k] (l2-normalised)
    v: jax.Array,         # [B, N, d_v]
    g: jax.Array,         # [B, N, d_k] log-decay (<= 0)
    b: jax.Array,         # [B, N] write strength
    *,
    layer,                # which of the state's layers (may be traced)
    active=None,          # [B] bool: the slots that advance (default all)
    interpret: bool = False,
    name: str = "kda_decode",
):
    """-> (o [B, N, d_v] float32, state'): slot s's row (s + 1 of ``layer``)
    advanced one position in place; every other row, every other layer and
    the rows of dead slots are bitwise untouched (but for the scratch row
    0). Semantics: ``ops.kda.kda_step`` on ``state[layer, 1:]``."""
    f32 = jnp.float32
    q, k, v, g = (x.astype(f32) for x in (q, k, v, g))
    if active is None:
        active = jnp.ones((q.shape[0],), bool)
    return _call(state, q, k, k * b.astype(f32)[..., None], g, v,
                 jnp.asarray(layer, jnp.int32).reshape(1), active,
                 interpret=interpret, name=name)
