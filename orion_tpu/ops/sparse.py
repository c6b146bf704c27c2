"""Block-sparse attention that selects its pages through compressed keys
(InfLLM-v2 as MiniCPM4 publishes it; ``config.SparseConfig`` has the sizes).

A "sparse" layer caches K and V in pages like any softmax layer and, beside
them, COMPRESSED keys: kernel j of a K/V head is the mean of its (normed)
keys at positions ``stride j .. stride j + kernel - 1``. A block is a page,
so a page holds ``block / stride`` kernels, the last of which needs the first
``kernel - stride`` keys of the NEXT page: a kernel is written when its last
key arrives (``compress`` for a chunk of a prompt, ``compress_one`` for a
decoded position).

A query at position t scores the kernels that lie wholly in its past with a
softmax a head (``select``); the 16 heads of a K/V group are summed; a block
takes the maximum over the kernels that overlap it; the first
``init_blocks`` blocks and the ``local_blocks`` that end with t's own are
forced, and the ``topk`` best causal blocks are taken, forced ones among them
(so with ``topk`` causal blocks or fewer every block is taken: plain causal
attention). What comes out is a PAGE LIST a query and K/V head, ascending,
the query's own block last.

Attention over the list: one softmax over the positions <= t of the selected
pages (``attend_xla``, the gather form: the CPU path and the tests' oracle).
The kernels differ by what a program has to attend.

A DECODE step is one query a slot (``attend_pallas``): the list read as a
page table of a sequence of its own, the own block is the last page and the
causal mask is the one of a plain paged sequence whose newest position is
``(n - 1) x block + t % block``, so ``ops/pallas/paged_attention.attend``
walks it over VIRTUAL slots, one a (query, K/V head), on a pool that keeps
one head a row, 16 query rows against up to ``topk`` pages each, the new
token's write fused in.

A prompt's CHUNK is whole blocks of queries (``attend_blocks``), and the
queries of a block share their own block: the forced pages are the same for
all of them, and while the block has ``topk`` causal blocks or fewer so is
the whole list (``split_blocks``, a rule on positions alone). The kernel of
``ops/pallas/sparse_prefill.py`` walks those SHARED pages once a (block, K/V
head) against every row of the block, and only what is left of each query's
list, its free choices, one query at a time. A chunk goes through in tiles of
queries (``query_tile``) that bound the selection's scores.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# (Query, K/V head) lists a tile of a prompt's chunk: the selection scores a
# tile's queries against every kernel of the row at once (256 queries x 16
# heads x 4,480 kernels in float32 are 73 MB a K/V head before the softmax's
# temporaries), and the tile's lists ride one kernel call's scalar prefetch
# (512 x 31 private ids are 62 KiB).
TILE_SLOTS = 512
_BIG = 1e30


def kernels_per_page(sp) -> int:
    """(``SparseConfig`` holds kernel = 2 x stride and stride | block.)"""
    return sp.block // sp.stride


def compress(prev: jax.Array, k: jax.Array, sp) -> jax.Array:
    """The kernels that END inside a page-aligned chunk. ``prev`` [Nb, block,
    K, H]: the page before the chunk (its last ``stride`` keys are read; any
    values where the chunk starts the sequence); ``k`` [Nb, S, K, H] the
    chunk's keys -> [Nb, S / stride, K, H] float32, entry i the kernel of
    positions ``s0 - stride + stride i ..`` (s0 the chunk's start): the first
    is the LAST kernel of the page before, the rest fill the chunk's pages
    but for the last page's last kernel."""
    Nb, S, K, H = k.shape
    st = sp.stride
    x = jnp.concatenate([prev[:, -st:], k], axis=1).astype(jnp.float32)
    g = x.reshape(Nb, S // st + 1, st, K, H).sum(2)
    return (g[:, :-1] + g[:, 1:]) / sp.kernel


def compress_one(window: jax.Array, sp) -> jax.Array:
    """One kernel from its ``kernel`` keys [B, kernel, K, H] (summed in
    ``compress``'s order) -> [B, K, H] float32."""
    B, _, K, H = window.shape
    g = window.astype(jnp.float32).reshape(B, 2, sp.stride, K, H).sum(2)
    return (g[:, 0] + g[:, 1]) / sp.kernel


def block_scores(q: jax.Array, ck: jax.Array, pos: jax.Array, sp):
    """q [B, Q, N, H]; ck [B, J, K, H] (J = blocks x kernels a page, in
    position order); pos [B, Q] -> [B, K, Q, blocks] float32: a block's
    score (-inf where no kernel that overlaps it lies wholly in the past)."""
    B, Q, N, H = q.shape
    J, K = ck.shape[1], ck.shape[2]
    kpp = kernels_per_page(sp)
    z = jnp.einsum("bqkgh,bjkh->bkgqj", q.reshape(B, Q, K, N // K, H), ck,
                   preferred_element_type=jnp.float32) * H ** -0.5
    seen = (sp.stride * jnp.arange(J) + sp.kernel - 1)[None, None] <= (
        pos[:, :, None])                                        # [B, Q, J]
    z = jnp.where(seen[:, None, None], z, -jnp.inf)
    m = z.max(-1, keepdims=True)
    e = jnp.exp(z - jnp.where(jnp.isfinite(m), m, 0.0))
    total = e.sum(-1, keepdims=True)
    r = (e / jnp.where(total == 0.0, 1.0, total)).sum(2)       # [B, K, Q, J]
    r = jnp.where(seen[:, None], r, -jnp.inf).reshape(B, K, Q, J // kpp, kpp)
    before = jnp.concatenate(
        [jnp.full_like(r[..., :1, 0], -jnp.inf), r[..., :-1, kpp - 1]], -1)
    return jnp.maximum(r.max(-1), before)


def forced_blocks(pos: jax.Array, n_blocks: int, sp) -> jax.Array:
    """[..., blocks] bool: the blocks a query at ``pos`` takes whatever
    their score (the causal ones among the first and the local ones)."""
    b = jnp.arange(n_blocks)
    own = (pos // sp.block)[..., None]
    return (b <= own) & ((b < sp.init_blocks) | (b > own - sp.local_blocks))


def select(q: jax.Array, ck: jax.Array, pos: jax.Array, sp):
    """-> (ids [B, K, Q, T] int32: the selected blocks ascending, the own
    block last, then ``blocks`` for the entries a short context leaves
    unused; n [B, K, Q]: how many are selected; T = min(topk, blocks))."""
    score = block_scores(q, ck, pos, sp)
    nb = score.shape[-1]
    causal = jnp.arange(nb) <= (pos // sp.block)[..., None]    # [B, Q, nb]
    s = jnp.where(forced_blocks(pos, nb, sp)[:, None], _BIG, score)
    s = jnp.where(causal[:, None], s, -jnp.inf)
    vals, ids = jax.lax.top_k(s, min(sp.topk, nb))
    ok = vals > -jnp.inf
    ids = jnp.sort(jnp.where(ok, ids, nb).astype(jnp.int32), axis=-1)
    return ids, ok.sum(-1).astype(jnp.int32)


def whole_sequence(q, k, v, sp):
    """A sparse layer over whole sequences from position 0, with no cache
    (training, and the tests' oracle of the paged forms): q [B, S, N, H],
    k / v [B, S, K, H] -> [B, S, N, H]. The selection as a mask over a
    dense product: every block a query did not select is masked."""
    B, S, N, H = q.shape
    K = k.shape[2]
    pad = -S % sp.block
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    c = compress(jnp.zeros_like(kp[:, :sp.block]), kp, sp)
    # Entry i of ``compress`` is kernel i - 1; the last page's last kernel
    # is never complete.
    ck = jnp.concatenate([c[:, 1:], jnp.zeros_like(c[:, :1])], 1).astype(
        k.dtype)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ids, _ = select(q, ck, pos, sp)                             # [B,K,S,T]
    nb = (S + pad) // sp.block
    chosen = (ids[..., None] == jnp.arange(nb)).any(-2)         # [B,K,S,nb]
    at = jnp.arange(S)
    live = chosen[..., at // sp.block] & (at[None, :] <= at[:, None])
    z = jnp.einsum("bqkgh,btkh->bkgqt", q.reshape(B, S, K, N // K, H), k,
                   preferred_element_type=jnp.float32) * H ** -0.5
    p = jax.nn.softmax(jnp.where(live[:, :, None], z, -jnp.inf), axis=-1)
    out = jnp.einsum("bkgqt,btkh->bqkgh", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, N, H).astype(q.dtype)


def attend_xla(q, k_pool, v_pool, rows, ids, n, pos):
    """The gather form. q [B, Q, N, H]; pools [pages x K, 1, psz, H], one
    K/V head a row (``kv_cache.sala_leaves``); ``rows`` [B, K, Q, T] the
    selected pages (layer base included); ``ids`` their block numbers, ``n``
    [B, K, Q] how many are real, pos [B, Q] -> [B, Q, N, H]."""
    B, Q, N, H = q.shape
    K, psz = rows.shape[1], k_pool.shape[2]
    T = ids.shape[-1]
    rows = rows * K + jnp.arange(K)[None, :, None, None]
    kg, vg = (pool[rows, 0] for pool in (k_pool, v_pool))       # [B,K,Q,T,p,H]
    at = ids[..., None] * psz + jnp.arange(psz)                 # [B,K,Q,T,p]
    live = (jnp.arange(T)[None, None, None, :, None] < n[..., None, None]) & (
        at <= pos[:, None, :, None, None])
    z = jnp.einsum("bqkgh,bkqtph->bkgqtp", q.reshape(B, Q, K, N // K, H),
                   kg.astype(q.dtype),
                   preferred_element_type=jnp.float32) * H ** -0.5
    z = jnp.where(live[:, :, None], z, -jnp.inf).reshape(
        B, K, N // K, Q, T * psz)
    p = jax.nn.softmax(z, axis=-1).reshape(B, K, N // K, Q, T, psz)
    out = jnp.einsum("bkgqtp,bkqtph->bqkgh", p.astype(q.dtype),
                     vg.astype(q.dtype), preferred_element_type=jnp.float32)
    return out.reshape(B, Q, N, H).astype(q.dtype)


def attend_pallas(q, k_pool, v_pool, pages, n, pos, *, layer_base,
                  k_new=None, v_new=None, interpret=False):
    """The kernel form of a decode step: ``pages`` [B, K, Q, T] per-layer
    page ids (0 where unused), the pools one K/V head a row
    (``kv_cache.sala_leaves``) and every (query, K/V head) a virtual slot of
    ``paged_attention.attend``. With ``k_new`` / ``v_new`` [B, 1, K, H] (Q =
    1) the new position is written into the own page in the kernel. -> (out
    [B, Q, N, H], *pools written)."""
    from orion_tpu.ops.pallas.paged_attention import attend

    B, Q, N, H = q.shape
    K, psz = pages.shape[1], k_pool.shape[2]
    T = pages.shape[-1]
    table = (pages * K + jnp.arange(K)[None, :, None, None]).transpose(
        0, 2, 1, 3).reshape(B * Q * K, T)
    start = ((n - 1) * psz + (pos % psz)[:, None, :]).transpose(
        0, 2, 1).reshape(B * Q * K)
    if k_new is not None:
        assert Q == 1, q.shape
        k_new, v_new = (a.reshape(B * K, 1, 1, H) for a in (k_new, v_new))
    out, *pools = attend(
        q.reshape(B * Q * K, 1, N // K, H), k_pool, v_pool, table, start,
        jnp.ones_like(start),
        layer_base=layer_base * K, k_new=k_new, v_new=v_new,
        logit_softcap=None, window=None, interpret=interpret, k_scale=None,
        v_scale=None, name="sparse_paged_decode")
    return (out.reshape(B, Q, N, H), *pools)


def split_blocks(pages: jax.Array, pos: jax.Array, sp, live=None):
    """The lists of whole blocks of queries, ``pages`` [B, K, Q, T] (a
    selection's ids or their pages, ascending, the own block last) at
    positions ``pos`` [B, Q] that start on a block's first, split by what
    the positions alone determine. The queries of a block share its forced
    pages (``forced_blocks``: the first ``init_blocks`` and the
    ``local_blocks`` that end with the own), and with T causal blocks or
    fewer they share every page, the selection having nothing to choose.
    -> (shared [B, K, Q / block, T], n_shared [B, K, Q / block]: the pages
    every query of a block attends, the own block last, and how many are
    real; private [B, K, Q, T - forced], n_private [B, K, Q / block]: what
    is left of each query's list and how many of them a query of the block
    has, all or none). ``live`` [B, Q / block] bool: the blocks that hold a
    real position; the others get no page."""
    T = pages.shape[-1]
    forced = sp.init_blocks + sp.local_blocks
    own = pos[:, None, ::sp.block] // sp.block                  # [B, 1, QB]
    lead = pages[:, :, ::sp.block]          # a block's first query's list
    whole = jnp.broadcast_to(own < T, lead.shape[:3])
    if T <= forced:     # (no free choice: a block's queries hold one list)
        shared, private = lead, pages[..., :0]
    else:
        window = jnp.concatenate(
            [lead[..., :sp.init_blocks], lead[..., T - sp.local_blocks:],
             jnp.zeros_like(lead[..., forced:])], -1)
        shared = jnp.where(whole[..., None], lead, window)
        private = pages[..., sp.init_blocks:T - sp.local_blocks]
    n_shared = jnp.where(whole, own + 1, forced).astype(jnp.int32)
    n_private = jnp.where(whole, 0, T - forced).astype(jnp.int32)
    if live is not None:
        n_shared, n_private = (jnp.where(live[:, None], a, 0)
                               for a in (n_shared, n_private))
    return shared, n_shared, private, n_private


def attend_blocks(q, k_pool, v_pool, pages, pos, sp, *, layer_base,
                  live=None, interpret=False):
    """The kernel form of a prompt's chunk: q [B, Q, N, H] at positions
    ``pos`` [B, Q], whole blocks from a block's first on; ``pages`` [B, K,
    Q, T] per-layer page ids (0 where unused); the pools one K/V head a row,
    the chunk's K and V in them already. Each block's shared pages are
    walked once for all its queries, its queries' private pages a query at a
    time (``split_blocks``), in one softmax a query. Blocks outside ``live``
    [B, Q / block] come out zero. -> [B, Q, N, H]."""
    from orion_tpu.ops.pallas import sparse_prefill

    return sparse_prefill.attend(
        q, k_pool, v_pool, *split_blocks(pages, pos, sp, live),
        layer_base=layer_base, interpret=interpret)


def query_tile(S: int, rows: int, block: int) -> int:
    """Queries a tile of a chunk of ``S`` positions, whole blocks: the
    largest divisor of S in blocks whose lists (``rows`` a query) fit
    TILE_SLOTS, at least one block."""
    blocks = S // block
    most = max(TILE_SLOTS // (rows * block), 1)
    return block * max(
        t for t in range(1, min(blocks, most) + 1) if blocks % t == 0)
