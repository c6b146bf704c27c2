"""Paged decode attention over LATENT rows as a Pallas TPU kernel.

A latent-attention model (``model.kv_lora_rank``; ``models/transformer.
latent_proj``) caches ONE row a position and layer: the normed compressed
row (R numbers) beside the rotary key all heads share, zero-padded to whole
lane tiles (``kv_cache.latent_leaf``: 512 + 64 -> 640). In the ABSORBED form
every query head is carried into that space (``latent_absorb``), so decode
is attention of N query heads over one shared "head" whose key is the whole
row and whose value is the row's first R columns. ``paged_attention.attend``
would serve it with the pool handed in twice, as K and as V, and would copy
every page twice: the bytes are all this kernel is bound by, so it copies a
page once and uses the block as key and as value.

The walk is ``paged_attention``'s, at one query a slot: grid (slot, blocks
of ``BLOCK_PAGES`` page-table entries), the pool in HBM, one async copy a
live page into a double-buffered ``[2, nb * page, width]`` scratch with the
next live block's copies started before this one is computed on, the page
table / position / layer base on the scalar-prefetch channel, the new
token's row merged into its page in VMEM and that page copied back (pool
aliased in/out), f32 softmax statistics and accumulator. Dead columns hold
stale but finite data (the scratch is zeroed once a call) under a mask.
Inference-only; no VJP. W = 1 only: nothing verifies drafts on this cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas.common import NEG_INF, resolve_interpret, round_up

LANES = 128
# Pages a grid step: a slot of this model's cell holds 44-336 pages, so a
# block twice ``paged_attention``'s halves the steps a walk takes; its two
# buffers are 2.6 MB of VMEM at a page of 64 rows of 640.
BLOCK_PAGES = 16


def _kernel(
    psz: int, P: int, nb: int, R: int, scale: float,
    pt_ref,        # [B, P] scalar-prefetched page table (layer-relative)
    base_ref,      # [1] flat-pool row base (layer * num_pages)
    pos_ref,       # [B] the new token's position (the last one attended)
    q_ref,         # [1, N8, Wd] absorbed queries
    pool_in,       # [rows, 1, psz, Wd] in HBM (aliased to ``pool``)
    new_ref,       # [1, 1, Wd] the new token's row
    o_ref,         # [1, N8, R]
    pool,          # the same pool, as output: read and written through it
    m_s, l_s, acc_s, buf, sems, wsem, slot_ref,
):
    del pool_in
    b, ib = pl.program_id(0), pl.program_id(1)
    B = pl.num_programs(0)
    T = nb * psz

    def span(bb):
        last = jnp.minimum(pos_ref[bb], P * psz - 1)
        return last, last // psz

    def fetch(bb, blk, slot, hi_b, wait):
        def page(j, carry):
            row = base_ref[0] + pt_ref[bb, blk * nb + j]
            cp = pltpu.make_async_copy(
                pool.at[row, 0],
                buf.at[slot, pl.ds(pl.multiple_of(j * psz, psz), psz), :],
                sems.at[slot])
            if wait:
                cp.wait()
            else:
                cp.start()
            return carry

        lax.fori_loop(0, jnp.minimum(hi_b - blk * nb + 1, nb), page, 0)

    last, hi = span(b)
    last_blk = hi // nb

    @pl.when(ib <= last_blk)
    def _step():
        @pl.when((b == 0) & (ib == 0))
        def _prime():
            slot_ref[0] = 0
            buf[...] = jnp.zeros(buf.shape, buf.dtype)
            fetch(b, ib, 0, hi, wait=False)

        slot = slot_ref[0]
        at_end = ib == last_blk
        nxt = jnp.where(at_end, b + 1, b)
        nxt_c = jnp.minimum(nxt, B - 1)
        _, hi_n = span(nxt_c)

        @pl.when(nxt < B)
        def _prefetch():
            fetch(nxt_c, jnp.where(at_end, 0, ib + 1), 1 - slot, hi_n,
                  wait=False)

        fetch(b, ib, slot, hi, wait=True)
        slot_ref[0] = 1 - slot

        @pl.when(ib == 0)
        def _init():
            m_s[:] = jnp.full_like(m_s, NEG_INF)
            l_s[:] = jnp.zeros_like(l_s)
            acc_s[:] = jnp.zeros_like(acc_s)

        # The new token's row into the page that owns its position (the
        # last live page, so always in the row's last block), merged by a
        # select against a position iota, and that page copied back.
        j_new = hi - ib * nb
        at_new = pl.ds(pl.multiple_of(j_new * psz, psz), psz)

        def back():
            return pltpu.make_async_copy(
                buf.at[slot, at_new, :],
                pool.at[base_ref[0] + pt_ref[b, hi], 0], wsem.at[0])

        @pl.when(at_end)
        def _write():
            page = buf[slot, at_new, :]                      # [psz, Wd]
            pos = hi * psz + lax.broadcasted_iota(jnp.int32, (psz, 1), 0)
            buf[slot, at_new, :] = jnp.where(
                pos == last, new_ref[0].astype(page.dtype), page)
            back().start()

        kv = buf[slot]                                       # [T, Wd]
        z = lax.dot_general(
            q_ref[0].astype(kv.dtype), kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                            # [N8, T]
        kv_pos = ib * T + lax.broadcasted_iota(jnp.int32, z.shape, 1)
        mask = kv_pos <= last
        z = jnp.where(mask, z, NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, z.max(axis=-1, keepdims=True))
        p = jnp.exp(z - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_s[:] = jnp.broadcast_to(
            l_s[:, :1] * alpha + p.sum(axis=-1, keepdims=True), l_s.shape)
        # The value IS the row's first R columns: the block copied once.
        pv = lax.dot_general(
            p.astype(kv.dtype), kv[:, :R], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                    # [N8, R]
        acc_s[:] = acc_s[:] * alpha + pv
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)

        @pl.when(at_end)
        def _finish():
            back().wait()
            l = l_s[:, :1]
            o_ref[0] = (acc_s[:] / jnp.where(l == 0.0, 1.0, l)).astype(
                o_ref.dtype)


# Jitted for the reason ``paged_attention._call`` is: a decode window holds
# steps x layers instances, traced and lowered once.
@functools.partial(jax.jit, static_argnames=(
    "value_width", "scale", "interpret", "name", "nb"))
def _call(q, pool, page_table, pos, base, new, *, value_width, scale,
          interpret, name, nb):
    B, N, Wd = q.shape
    psz = pool.shape[2]
    P = page_table.shape[1]
    R = value_width
    N8 = round_up(N, 16)      # whole sublane tiles of a 16-bit dtype
    qp = jnp.pad(q, ((0, 0), (0, N8 - N), (0, 0)))
    prefetch = [page_table.astype(jnp.int32), base, pos.astype(jnp.int32)]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    T = nb * psz
    out, pool = pl.pallas_call(
        functools.partial(_kernel, psz, P, nb, R, scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, pl.cdiv(P, nb)),
            in_specs=[
                pl.BlockSpec((1, N8, Wd), lambda b, ib, *_: (b, 0, 0)),
                hbm,
                pl.BlockSpec((1, 1, Wd), lambda b, ib, *_: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, N8, R), lambda b, ib, *_: (b, 0, 0)),
                hbm,
            ],
            scratch_shapes=[
                pltpu.VMEM((N8, LANES), jnp.float32),
                pltpu.VMEM((N8, LANES), jnp.float32),
                pltpu.VMEM((N8, R), jnp.float32),
                pltpu.VMEM((2, T, Wd), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, N8, R), q.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # Operand indices count the scalar-prefetch arguments and q.
        input_output_aliases={len(prefetch) + 1: 1},
        compiler_params=pltpu.CompilerParams(
            # The block pipeline carries state from one grid step to the
            # next: both axes run in order on one core.
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name=name,
    )(*prefetch, qp, pool, new[:, None, :])
    return out[:, :N], pool


def latent_paged_attention(
    q: jax.Array,            # [B, N, Wd] absorbed queries, zero past the row
    pool: jax.Array,         # [L * num_pages, 1, psz, Wd] flat latent pool
    page_table: jax.Array,   # [B, P] int32 per-layer-relative page ids
    pos: jax.Array,          # [B] int32: the new token's position
    new: jax.Array,          # [B, Wd] the new token's row, written in-kernel
    *,
    layer_base,              # flat-pool row offset (layer * num_pages)
    value_width: int,        # R: the row's leading columns that are the value
    scale: float,
    interpret: bool = False,
    name: str = "latent_paged_decode",
):
    """-> (out [B, N, R], pool'): each slot's N queries attend its pages'
    rows at positions <= ``pos`` (the new row among them, written at ``pos``
    in place through input/output aliasing; every other page is bitwise
    untouched). Semantics: gather the slot's pages into [P * psz, Wd], write
    the row, softmax(q . rows * scale) over the live positions, times
    rows[:, :R]."""
    return _call(
        q, pool, page_table, pos, jnp.asarray(layer_base, jnp.int32).reshape(1),
        new, value_width=value_width, scale=float(scale), interpret=interpret,
        name=name, nb=min(BLOCK_PAGES, page_table.shape[1]))
