"""One decode step of Kimi delta attention as a Pallas TPU kernel.

``ops/kda.py`` has the recurrence. At decode a slot's state in a layer is
``heads x d_v x d_k`` float32 (32 x 128 x 128: 2 MiB), read AND written
every step: the kernel is bound by those bytes and by nothing else, so a
grid step carries a slot's WHOLE row where the pipeline's four buffers of
it fit (``head_block``: 32 heads, 2 MiB in and 2 MiB out, one step a slot),
updates it on the vector unit in float32, eight heads a turn of a loop,
and hands it back to the same place (the state is aliased in/out; under the
engine's donated cache nothing is copied, which is what PR 37 measured a
copy out and back to cost). At that size a head's vector work hides under
the copies and the kernel runs at their pace: 77 % of 2 x the row over
819 GB/s, which is what the chip gives ANY equal stream of reads and
writes through VMEM (``tools/kda_decode_sweep.py``: the body cut to the
decay alone reads the same at 8, 16 and 32 heads a step and at two to
eight copies in flight; blocks of 8 heads, PR 41's, read 70 %). The state
is kept VALUE-major, ``M = S^T`` [d_v, d_k]: a key channel's decay, the
key and the query are then rows that broadcast over sublanes, and the two
products along d_k are lane reductions:

    M' = M . a            r = M' k          u = v - r
    M  = M' + u (b k)^T   o = M q

``v`` arrives as a row and ``o`` leaves as one; both change hands with the
column form through an identity mask (one multiply and one reduction of a
[d_v, d_v] tile, beside six of the state's own; turning a group of heads
at once through a transpose and column slices measured SLOWER).

Dead slots (``active`` false) are passed by: their blocks' indices point at
the scratch row 0 of the layer, which the pipeline fetches once for a run of
them, and nothing is computed. Inference-only; no VJP.

The prefill's chunked form is ``ops.kda.kda_chunked`` in XLA under the scope
``kda/chunk`` on every backend (since PR 48 in segments of 256 positions,
the triangular inverse by block elimination in whole 64 x 64 products). A
Pallas kernel of it was tried in PR 45 and refused: with the XLA form gone
from a prefill program of two or more rows the chip halts in a fusion beside
the call (ROADMAP S1 (g), PERF.md section 7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas.common import resolve_interpret

# What the pipeline's buffers of the state (two in, two out) may take of
# VMEM: a 2 MiB row in each, half of Mosaic's 16 MiB scoped default on the
# v5e (the small operands' blocks and a group's tiles take well under 1 MiB).
STATE_VMEM_BYTES = 8 * 2 ** 20
GROUP = 8       # heads in one sublane tile of the [heads, d] operands


def head_block(N: int, dv: int, dk: int) -> int:
    """The heads a grid step carries: the most that divide ``N`` and whose
    four buffers fit ``STATE_VMEM_BYTES`` (all of them, up to 32 heads of
    128 x 128)."""
    most = max(STATE_VMEM_BYTES // (4 * dv * dk * 4), 1)
    return max(h for h in range(1, min(N, most) + 1) if N % h == 0)


def _kernel(hb: int, rows_ref, layer_ref, act_ref,
            q_ref, k_ref, kb_ref, g_ref, v_ref, s_ref, o_ref, so_ref):
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
        q, k, kb, v = (ref[0, pl.ds(at, gb), :]
                       for ref in (q_ref, k_ref, kb_ref, v_ref))
        a = jnp.exp(g_ref[0, pl.ds(at, gb), :])
        eye = (lax.broadcasted_iota(jnp.int32, (dv, dv), 0)
               == lax.broadcasted_iota(jnp.int32, (dv, dv), 1)
               ).astype(jnp.float32)
        for i in range(gb):
            row = lambda x: x[i:i + 1]                       # [1, d]
            m = s_ref[0, 0, at + i] * row(a)                 # [dv, dk]
            r = (m * row(k)).sum(-1, keepdims=True)          # [dv, 1]
            v_col = (eye * row(v)).sum(-1, keepdims=True)
            m = m + (v_col - r) * row(kb)
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
def _call(state, q, k, kb, g, v, layer, active, *, interpret, name):
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
