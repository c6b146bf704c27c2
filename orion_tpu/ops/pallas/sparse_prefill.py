"""Block-sparse prefill attention as a Pallas TPU kernel: the unit of work is
a query BLOCK, the positions of one page, not a query.

A sparse layer's query attends the pages its selection names
(``ops/sparse.py``). The queries of one block of a prompt's chunk share their
own block, so they share the pages their positions force, and every page
while the context is short enough that nothing is left to choose
(``sparse.split_blocks``): those pages are SHARED by the block's queries and
the rest of each query's list is PRIVATE to it.

  - Grid is one axis of (row of the chunk, query block, K/V head). A grid row
    keeps the block's queries resident, ``[queries, G', H]`` (the grouped
    heads of the K/V head, padded to a sublane tile), with one running
    maximum, sum and accumulator a query row in f32, and makes two walks in
    its own body; both add into those statistics, so what comes out is ONE
    softmax over exactly the positions the gather form takes
    (``sparse.attend_xla``), summed in another order.
  - The shared walk takes ``SHARED_PAGES`` pages a step: each page-head is
    copied once and multiplied against every row of the block. The last
    shared page is the block's own: query i of the block sees its columns up
    to i. Read as one run of columns, the walk's mask is the plain causal
    one of a sequence whose last page is the own block.
  - The private walk takes one query at a time, ``PRIVATE_PAGES`` pages a
    step against that query's G' rows. Its pages lie wholly in the past, and
    a block's queries have all of them or none.
  - Pages are not contiguous in the pool, so the pools stay in HBM
    (``pl.ANY``) and a step issues one async copy a live page and pool into a
    double-buffered VMEM scratch, the next step's before it computes on this
    one's. The lists ride the scalar prefetch as flat pool rows; the private
    ones as a 2-D operand, a (query, K/V head) a row, as the selection's
    ``top_k`` leaves them: through that operand's layout the compiler sorts
    with the queries on the lanes. Flattened, it sorted ALONG the lanes and
    a chunk's sorts took ten times as long (PERF.md section 6, PR 59). A walk
    with no live page starts no copy and does nothing.
  - Operands in the pool's dtype, f32 scores, statistics and accumulator, as
    ``paged_attention`` keeps them. Dead columns of a live step hold stale
    but finite data (the scratch is zeroed once a call) and their masked
    probabilities are exact zeros.

Read-only on the pools: a chunk's K and V are written before its queries
attend. Inference-only; no VJP is defined.
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
# Pages a step of the two walks, chosen on a v5e by
# tools/sparse_prefill_sweep.py at the SALA cell's shape (PERF.md section 5):
# a shared step costs ~4 us whatever it holds and 0.13 us a page, so 4 pages a
# step take 2.5 times the 16's time on a first chunk, and 32 compute on dead
# columns and pass the default scoped VMEM; a private step is bound by issuing
# its copies, and a query's whole list (31 pages there) in one step, its
# copies written out, takes half the time of four steps of 8.
SHARED_PAGES = 16
PRIVATE_PAGES = 32


def _kernel(psz, nbs, nbp, TS, TP, K, scale,
            sh_ref,        # [R * TS] pool rows of each grid row's shared pages
            ns_ref,        # [R] how many of them are real
            pv_ref,        # [B * Q * K, TP] a (query, head)'s private pages
            np_ref,        # [R] how many of them a query has: all or none
            q_ref, k_hbm, v_hbm, o_ref, m_s, l_s, acc_s, kbuf, vbuf, sems):
    r = pl.program_id(0)
    bq, Gp, H = q_ref.shape[1:]

    @pl.when(r == 0)
    def _clear():
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)

    m_s[...] = jnp.full(m_s.shape, NEG_INF, m_s.dtype)
    l_s[...] = jnp.zeros(l_s.shape, l_s.dtype)
    acc_s[...] = jnp.zeros(acc_s.shape, acc_s.dtype)

    def fetch(entry, live, slot, wait):
        """Start (or wait for) the copies of ``live`` pages, page j the pool
        row ``entry(j)``. A count known when the kernel is traced is
        written out page by page: issuing copies is what a private step
        waits for most, and a loop's page costs twice a written-out one."""
        def page(j, carry=None):
            # (A wait needs the copy's shape and semaphore, not its source.)
            row = 0 if wait else entry(j)
            at = pl.ds(j * psz if isinstance(j, int)
                       else pl.multiple_of(j * psz, psz), psz)
            for s, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                cp = pltpu.make_async_copy(
                    pool.at[row, 0], buf.at[slot, at], sems.at[slot, s])
                if wait:
                    cp.wait()
                else:
                    cp.start()
            return carry

        if isinstance(live, int):
            for j in range(live):
                page(j)
        else:
            lax.fori_loop(0, live, page, 0)

    def walk(steps, entry, live, attend):
        """``steps`` steps, step u over ``live(u)`` pages, page j of it the
        pool row ``entry(u)(j)``, the next step's copies in flight while
        ``attend(u, slot)`` computes on this one's."""
        @pl.when(steps > 0)
        def _():
            fetch(entry(0), live(0), 0, wait=False)

            def step(u, carry):
                slot = u % 2

                @pl.when(u + 1 < steps)
                def _next():
                    fetch(entry(u + 1), live(u + 1), 1 - slot, wait=False)

                fetch(entry(u), live(u), slot, wait=True)
                attend(u, slot)
                return carry

            lax.fori_loop(0, steps, step, 0)

    def softmax_step(at, q, slot, T, visible):
        """One step of the running softmax for the query rows ``at`` (an
        index into the statistics' leading axis) over the first ``T``
        columns of buffer ``slot``; ``visible(rows, T)`` their mask (None:
        every column counts)."""
        rows = q.shape[0]
        get = lambda ref: ref[at].reshape(rows, ref.shape[-1])

        def put(ref, x):
            ref[at] = x.reshape(ref.shape[0 if isinstance(at, slice) else 1:])

        z = lax.dot_general(
            q, kbuf[slot, :T], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # [rows, T]
        if visible is not None:
            mask = visible(rows, T)
            z = jnp.where(mask, z, NEG_INF)
        m_prev = get(m_s)[:, :1]
        m_new = jnp.maximum(m_prev, z.max(axis=-1, keepdims=True))
        p = jnp.exp(z - m_new)
        if visible is not None:
            p = p * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_new = get(l_s)[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        pv = lax.dot_general(
            p.astype(vbuf.dtype), vbuf[slot, :T], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [rows, H]
        put(acc_s, get(acc_s) * alpha + pv)
        put(m_s, jnp.broadcast_to(m_new, (rows, LANES)))
        put(l_s, jnp.broadcast_to(l_new, (rows, LANES)))

    ns = ns_ref[r]

    def shared(u, slot):
        # The own block is the last shared page: read as one run of
        # columns, query i sees up to column i of it and nothing behind.
        own = (ns - 1 - u * nbs) * psz

        def visible(rows, T):
            col = lax.broadcasted_iota(jnp.int32, (rows, T), 1)
            i = lax.broadcasted_iota(jnp.int32, (rows, T), 0) // Gp
            return col <= own + i

        softmax_step(slice(None), q_ref[0].reshape(bq * Gp, H), slot,
                     nbs * psz, visible)

    walk(pl.cdiv(ns, nbs), lambda u: lambda j: sh_ref[r * TS + u * nbs + j],
         lambda u: jnp.minimum(ns - u * nbs, nbs), shared)

    if TP:
        # A block's queries have all their free choices or none.
        per = pl.cdiv(TP, nbp)          # steps a query

        def left(u):
            return TP if per == 1 else jnp.minimum(TP - (u % per) * nbp, nbp)

        def private(u, slot):
            def visible(rows, T):
                col = lax.broadcasted_iota(jnp.int32, (rows, T), 1)
                return col < left(u) * psz

            i = u // per
            softmax_step(i, q_ref[0, i], slot, min(nbp, TP) * psz,
                         None if per == 1 else visible)

        def entry(u):
            # (The lists lie a (query, K/V head) a row, as the selection
            # leaves them.)
            row = ((r // K) * bq + u // per) * K + r % K
            return lambda j: pv_ref[row, (u % per) * nbp + j]

        walk(jnp.where(np_ref[r] > 0, bq * per, 0), entry, left, private)

    l = l_s[...][..., :1]
    o_ref[0] = (acc_s[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "interpret", "nbs", "nbp", "K"))
def _call(q, k_pool, v_pool, shared, n_shared, private, n_private, *,
          interpret, nbs, nbp, K):
    R, bq, Gp, H = q.shape
    psz = k_pool.shape[2]
    TS, TP = shared.shape[-1], private.shape[-1]
    nbs = min(nbs, TS)
    T = max(nbs, min(nbp, TP)) * psz
    block = pl.BlockSpec((1, bq, Gp, H), lambda r, *_: (r, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, psz, nbs, nbp, TS, TP, K, H ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(R,),
            in_specs=[block, hbm, hbm],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((bq, Gp, LANES), jnp.float32),
                pltpu.VMEM((bq, Gp, LANES), jnp.float32),
                pltpu.VMEM((bq, Gp, H), jnp.float32),
                pltpu.VMEM((2, T, H), k_pool.dtype),
                pltpu.VMEM((2, T, H), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            # The buffers are cleared in the first grid row alone.
            dimension_semantics=("arbitrary",),
        ),
        interpret=resolve_interpret(interpret),
        name="sparse_paged_prefill",
    )(shared.reshape(-1).astype(jnp.int32), n_shared.astype(jnp.int32),
      # (No private page at all: the walk is not traced, the list not read.)
      private.reshape(-1, TP).astype(jnp.int32) if TP else n_private,
      n_private.astype(jnp.int32), q, k_pool, v_pool)


def attend(q, k_pool, v_pool, shared, n_shared, private, n_private, *,
           layer_base, interpret=False):
    """q [B, Q, N, H], Q a whole number of pages; the pools one K/V head a
    row, [rows x K, 1, psz, H] (``kv_cache.sala_leaves``); ``shared`` [B, K,
    Q / psz, TS] the per-layer page ids every query of a block attends, the
    block's own last, ``n_shared`` [B, K, Q / psz] how many are real (0: the
    block attends nothing and comes out zero); ``private`` [B, K, Q, TP] each
    query's own, ``n_private`` [B, K, Q / psz] how many of them a query of
    the block has, all TP or none -> [B, Q, N, H]."""
    B, Q, N, H = q.shape
    K, psz = shared.shape[1], k_pool.shape[2]
    QB, G = Q // psz, N // K
    assert Q % psz == 0 and N % K == 0, (q.shape, psz, K)
    # A query's rows fill whole sublane tiles of its dtype.
    Gp = round_up(G, 8 * 4 // q.dtype.itemsize)
    flat = lambda pages: (layer_base + pages) * K + jnp.arange(K)[
        None, :, None, None]
    # Grid row (b, query block, K/V head).
    rows = lambda a: a.transpose(0, 2, 1, *range(3, a.ndim))
    qb = q.reshape(B, QB, psz, K, G, H).transpose(0, 1, 3, 2, 4, 5)
    if Gp != G:
        qb = jnp.pad(qb, ((0, 0),) * 4 + ((0, Gp - G), (0, 0)))
    out = _call(
        qb.reshape(B * QB * K, psz, Gp, H), k_pool, v_pool,
        rows(flat(shared)), rows(n_shared).reshape(-1),
        flat(private).transpose(0, 2, 1, 3),
        rows(n_private).reshape(-1),
        interpret=interpret, nbs=SHARED_PAGES, nbp=PRIVATE_PAGES, K=K)
    out = out.reshape(B, QB, K, psz, Gp, H)[..., :G, :]
    return out.transpose(0, 1, 3, 2, 4, 5).reshape(B, Q, N, H)
