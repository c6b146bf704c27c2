"""Paged KV-cache pool + host-side page allocator.

The reference's continuous-batching server manages a paged KV cache
(BASELINE.json:11; PAPERS.md:9 "ragged paged attention for TPU"). TPU-native
design: one global pool of fixed-size pages per layer, so every jit program
sees static shapes; sequences own pages through an integer page table, and
the *allocator* — the only dynamic piece — lives on the host, where it is a
free list, not a device computation.

Layout:
    k_pool, v_pool: [n_layers * num_pages, n_kv_heads, page_size, head_dim]
    page_table:     [max_batch, pages_per_seq] int32 (host, shipped per step)
    seq_lens:       [max_batch] int32            (host, shipped per step)

Heads sit OUTSIDE the (page_size, head_dim) minor dims so one page's whole
(1, K, psz, H) block is TPU-tiling-legal for the ragged paged-attention
kernel, with the head dim as a batched-matmul dim (see
ops/pallas/paged_attention.py).

The layer dim is FLATTENED into the page dim (layer l's pages are rows
[l*num_pages, (l+1)*num_pages)): the pool can then be a single scan carry
whose per-layer updates are in-place scatters at dynamic row offsets —
carrying it as per-layer scan xs/ys instead would make XLA rewrite the
entire multi-GB pool every step (measured 5.4 GB/step on the 1B bench
model). Page ids in page tables are per-layer-relative; device code adds
``l * num_pages``.

Page 0 (of each layer region) is reserved as a scratch page: every inactive
batch slot points at it, so device-side gathers/scatters are always
in-bounds and slot masking is done with seq_lens alone.
"""

from __future__ import annotations

import itertools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu.config import InferenceConfig, ModelConfig

Cache = dict[str, jax.Array]


def pages_per_seq(icfg: InferenceConfig) -> int:
    assert icfg.max_seq_len % icfg.page_size == 0, (
        icfg.max_seq_len, icfg.page_size)
    return icfg.max_seq_len // icfg.page_size


SCALE_LANES = 128  # scale pools pad the token dim to a full lane tile so
#                    their (1, K, SCALE_LANES) kernel blocks are (8, 128)-
#                    tiling-legal f32; columns >= page_size are dead.


def scale_width(psz: int) -> int:
    if psz > SCALE_LANES:
        raise ValueError(
            f"kv_quant='int8' requires page_size <= {SCALE_LANES}, "
            f"got {psz} (one lane tile holds one page's scales)"
        )
    return SCALE_LANES


# Single definition shared with the paged kernel's fused in-kernel write
# (decode and prefill quantization must agree bit-for-bit).
from orion_tpu.ops.pallas.common import quantize_kv  # noqa: F401,E402


# The one paged leaf of a latent-attention model (``latent_leaf``).
LATENT = "latent"


def paged_leaf(cache: "Cache") -> jax.Array:
    """The leaf that says how a cache's pages are laid out ([layers x
    pages, heads, page, width]): ``k`` of a K/V cache, the one row leaf of
    a latent cache. Every place that needs a pool's page size or page count
    reads it here, so a backend adds a leaf and not a branch at each."""
    return cache["k"] if "k" in cache else cache[LATENT]


def page_geometry(cache: "Cache", n_layers: int) -> tuple[int, int]:
    """(page size, pages a layer) of a cache's pool."""
    leaf = paged_leaf(cache)
    if "ck" in cache:       # ``sala_leaves``: K and V one head a row
        return leaf.shape[2], cache["ck"].shape[0] // n_layers
    return leaf.shape[2], leaf.shape[0] // n_layers


def page_rows(arr: jax.Array, rows: jax.Array, total: int) -> jax.Array:
    """The rows of leaf ``arr`` that pool rows ``rows`` (layer x pages +
    page, of ``total``) own: themselves, or, in a leaf that keeps several
    rows a page (``sala_leaves``: one a K/V head), all of those."""
    per = arr.shape[0] // total
    if per == 1:
        return rows
    return (rows[:, None] * per + jnp.arange(per, dtype=rows.dtype)).reshape(-1)


def latent_width(mcfg: ModelConfig) -> int:
    """Columns a latent pool's row holds: ``kv_lora_rank +
    qk_rope_head_dim`` padded with zeros to whole lane tiles (512 + 64 ->
    640), so that a page is [page, 5 x 128] to the decode kernel's copies
    and products. A device lays a 576-wide (or a 64-wide) minor dimension
    out in whole 128-lane tiles in any case: the padding costs no memory a
    split into a 512 leaf and a 64 leaf would save."""
    return -(-mcfg.latent_row_width // 128) * 128


def latent_leaf(mcfg: ModelConfig, icfg: InferenceConfig, dtype) -> "Cache":
    """A latent-attention model's whole cache: ONE row a token and layer
    (the normed compressed row | the shared rotary key | zeros), in the
    paged layout with one "head", [layers x pages, 1, page, width]; the
    layers are the latent ones (``ModelConfig.n_paged_layers``)."""
    return {LATENT: jnp.zeros(
        (mcfg.n_paged_layers * icfg.num_pages, 1, icfg.page_size,
         latent_width(mcfg)), dtype)}


KDA_STATE, KDA_CONV = "kda_state", "kda_conv"


def kda_leaves(mcfg: ModelConfig, icfg: InferenceConfig, dtype) -> "Cache":
    """What a model's KDA layers keep: a SLOT's rows and no page, [KDA
    layers, slots + 1, ...] with row 0 a scratch row as page 0 is and slot b
    owning row b + 1. ``kda_state`` [.., heads, d_v, d_k] float32 is the
    recurrence's state, value-major (the transpose of ``ops/kda.py``'s S:
    the decode kernel broadcasts a key channel's decay over lanes);
    ``kda_conv`` [.., K - 1, 3 x heads x d] the last K - 1 rows of q | k | v
    BEFORE the convolution. A prefill writes a slot's rows whole (from a
    zero state), a decode step updates them in place; nothing folds."""
    L, slots = mcfg.n_layers_of("kda"), icfg.max_batch_size + 1
    N, H = mcfg.n_heads, mcfg.resolved_head_dim
    return {
        KDA_STATE: jnp.zeros((L, slots, N, H, H), jnp.float32),
        KDA_CONV: jnp.zeros(
            (L, slots, mcfg.kda_conv_size - 1, 3 * N * H), dtype),
    }


COMPRESSED, LIGHTNING_STATE = "ck", "lightning_state"


def sala_leaves(mcfg: ModelConfig, icfg: InferenceConfig, dtype) -> "Cache":
    """The cache of a model of sparse and lightning layers
    (``ModelConfig.mixer_types``), by cache kind. ``k`` / ``v``: the SPARSE
    layers' pages, which the allocator counts as ever, ONE K/V HEAD A ROW:
    [sparse layers x pages x K/V heads, 1, page, head], head g of page p of
    layer l at row ``(l x pages + p) x heads + g``. A query selects its
    pages a K/V head, so the decode kernel walks a page list a (slot, head)
    and copies one head's rows of a page; the compiler keeps a [.., 2, 64,
    128] leaf with the two heads interleaved, and a view of it one head a
    row is then a copy of the pool at every call. ``ck``: the compressed
    keys, a page's ``block / stride`` kernels a row, [sparse layers x pages,
    K/V heads, kernels, head] (page p's last kernel reads into page p + 1
    and is written when that key arrives; ``ops/sparse.py``). A page and
    its compressed keys are allocated, freed and scrubbed together
    (``page_rows``).
    ``lightning_state``: the LIGHTNING layers' recurrence, a slot's row and
    no page, [lightning layers, slots + 1, heads, d_v, d_k] float32
    (value-major; slot b owns row b + 1, row 0 a scratch row). A prompt's
    chunk resumes from all three."""
    from orion_tpu.ops.sparse import kernels_per_page

    if not mcfg.has_sparse:
        raise ValueError(
            "a model of lightning layers alone has no paged layer, which "
            "the engine's allocator counts sequences by: not served")
    if mcfg.sparse.block != icfg.page_size:
        raise ValueError(
            f"a selected block is a page: inference.page_size="
            f"{icfg.page_size} must be model.sparse.block="
            f"{mcfg.sparse.block}")
    rows = mcfg.n_paged_layers * icfg.num_pages
    K, N, H = mcfg.n_kv_heads, mcfg.n_heads, mcfg.resolved_head_dim
    return {
        "k": jnp.zeros((rows * K, 1, icfg.page_size, H), dtype),
        "v": jnp.zeros((rows * K, 1, icfg.page_size, H), dtype),
        COMPRESSED: jnp.zeros(
            (rows, K, kernels_per_page(mcfg.sparse), H), dtype),
        LIGHTNING_STATE: jnp.zeros(
            (mcfg.n_layers_of("lightning"), icfg.max_batch_size + 1, N, H, H),
            jnp.float32),
    }


RING_K, RING_V = "ring_k", "ring_v"


def ring_pages(window: int, psz: int) -> int:
    """Pages a slot's ring holds in a window layer: what the ``window``
    positions up to any position span, ``ceil((window - 1) / page) + 1``
    (the page that takes the new token, and the pages the window reaches
    back over). A page comes round again only when every position it held
    lies behind the window."""
    return -(-(window - 1) // psz) + 1


def _packed(H: int, Hv: int, K: int) -> int:
    """The dims of a key that sit in the paired rows: keys ``H`` wide
    beside values ``Hv`` wide are kept where a head's last ``Hv`` dims
    fill one row and the ``H - Hv`` before them half a row."""
    if 2 * (H - Hv) != Hv or K % 2:
        raise ValueError(
            f"keys {H} wide beside values {Hv} wide over {K} K/V heads: the "
            f"packed key layout needs H - Hv = Hv / 2 and an even head count")
    return H - Hv


def pack_keys(k: jax.Array, Hv: int) -> jax.Array:
    """Keys [..., K, H] as the rows a K pool of a model with values
    narrower than keys holds a position, [..., K + K / 2, Hv]: rows 0..K-1
    each head's LAST Hv dims, rows K.. the dims before them, heads 2i and
    2i + 1 side by side in row K + i. Every row is Hv lanes (128 at the
    published sizes: no padding, where a 192-wide minor dimension would be
    laid out 256 wide)."""
    *lead, K, H = k.shape
    X = _packed(H, Hv, K)
    return jnp.concatenate(
        [k[..., X:], k[..., :X].reshape(*lead, K // 2, 2 * X)], axis=-2)


def unpack_keys(rows: jax.Array, K: int) -> jax.Array:
    """``pack_keys``' inverse: [..., K + K / 2, Hv] -> [..., K, H]."""
    *lead, _, Hv = rows.shape
    extra = rows[..., K:, :].reshape(*lead, K, Hv // 2)
    return jnp.concatenate([extra, rows[..., :K, :]], axis=-1)


def pack_queries(q: jax.Array, K: int, Hv: int) -> jax.Array:
    """Queries [..., N, H] as the paged kernel reads them against packed
    keys, [..., N, 2 Hv]: a head's last Hv dims, then one paired row's
    width with the head's other dims in the half its K/V head has there and
    zeros in the other half."""
    *_, N, H = q.shape
    X = _packed(H, Hv, K)
    odd = ((jnp.arange(N) // (N // K)) % 2 == 1)[:, None]
    extra = q[..., :X]
    zero = jnp.zeros_like(extra)
    return jnp.concatenate(
        [q[..., X:], jnp.where(odd, zero, extra), jnp.where(odd, extra, zero)],
        axis=-1)


def ring_cache(mcfg: ModelConfig, icfg: InferenceConfig, dtype) -> "Cache":
    """The cache of a model whose window layers differ from its full
    layers in their K/V heads (``ModelConfig.has_window_ring``), by cache
    kind. ``k`` / ``v``: the FULL layers' pages, [full layers x pages, ...],
    which the allocator counts as ever. ``ring_k`` / ``ring_v``: the WINDOW
    layers' rows, a ring of ``ring_pages`` pages a slot, [window layers,
    slots + 1, ring pages, heads, page, width] (slot b owns row b + 1, row
    0 a scratch ring as page 0 is; the runner walks the leaf flat, [layers
    x slots x ring pages, ...], in the paged layout): position p of a slot lives in ring page
    ``(p // page) % ring_pages`` at column ``p % page``, whatever the
    request's length; a prefill writes the pages of its last positions
    alone, a decode step goes round. A slot's own and ``NOT_PAGED``: the
    allocator never sees them (a ring was chosen over freeing pool pages
    behind the window because the two kinds' pages differ in shape, so one
    pool could not hand a freed window page to a full layer anyway, and a
    ring needs no page table entry, no allocation and no host arithmetic a
    step). Keys are ``pack_keys``' rows: both leaves of a kind are Hv wide."""
    Kf, Kw = mcfg.n_kv_heads, mcfg.n_kv_heads_sliding
    psz, Hv = icfg.page_size, mcfg.resolved_v_head_dim
    for K in (Kf, Kw):      # (refused here, by name, and not in a kernel)
        _packed(mcfg.resolved_head_dim, Hv, K)
    full = mcfg.n_paged_layers * icfg.num_pages
    ring = (mcfg.n_layers - mcfg.n_paged_layers, icfg.max_batch_size + 1,
            ring_pages(mcfg.sliding_window, psz))
    return {
        "k": jnp.zeros((full, Kf + Kf // 2, psz, Hv), dtype),
        "v": jnp.zeros((full, Kf, psz, Hv), dtype),
        RING_K: jnp.zeros((*ring, Kw + Kw // 2, psz, Hv), dtype),
        RING_V: jnp.zeros((*ring, Kw, psz, Hv), dtype),
    }


def init_cache(
    mcfg: ModelConfig,
    icfg: InferenceConfig,
    device: Optional[jax.Device] = None,
) -> Cache:
    """Allocate the paged KV pool (zeros).

    With ``inference.kv_quant='int8'`` the pools are int8 and carry f32
    scale pools ``k_scale``/``v_scale`` of shape [rows, K, SCALE_LANES]
    (column t = token t's scale on that page; lanes-padded past
    page_size). Presence of the scale keys is what runner/kernel code
    keys off — the cache dict is the single source of truth.
    """
    rows = mcfg.n_layers * icfg.num_pages
    K, psz, H = mcfg.n_kv_heads, icfg.page_size, mcfg.resolved_head_dim
    shape = (rows, K, psz, H)

    def alloc():
        if icfg.kv_quant == "int8":
            sw = scale_width(psz)
            return {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros((rows, K, sw), jnp.float32),
                "v_scale": jnp.zeros((rows, K, sw), jnp.float32),
            }
        if icfg.kv_quant is not None:
            raise ValueError(f"unknown inference.kv_quant={icfg.kv_quant!r}")
        dtype = jnp.dtype(mcfg.dtype)
        if mcfg.has_window_ring:
            return ring_cache(mcfg, icfg, dtype)
        if mcfg.mixer_types is not None:
            return sala_leaves(mcfg, icfg, dtype)
        if mcfg.has_kda:
            if not mcfg.has_latent:
                raise ValueError(
                    "a model of KDA layers alone has no paged layer, which "
                    "the engine's allocator counts sequences by: not served")
            return {**latent_leaf(mcfg, icfg, dtype),
                    **kda_leaves(mcfg, icfg, dtype)}
        if mcfg.is_latent:
            return latent_leaf(mcfg, icfg, dtype)
        cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if mcfg.is_retention:
            cache.update(retention_leaves(mcfg, icfg, dtype))
        return cache

    if device is not None:
        with jax.default_device(device):
            return alloc()
    return alloc()


# The leaves of ``retention_leaves`` that are a slot's and not a page's.
SLOT_LEAVES = ("state", "state_z", "state_len", "g")
# Every leaf of any backend that is a slot's: what page operations pass by.
NOT_PAGED = SLOT_LEAVES + (KDA_STATE, KDA_CONV, RING_K, RING_V,
                          LIGHTNING_STATE)


def retention_leaves(mcfg: ModelConfig, icfg: InferenceConfig, dtype) -> Cache:
    """What a power-retention model keeps beside its pages, which hold only
    the K and V of a sequence's positions since its last fold (its tail).
    Everything else is a SLOT's, [layers x (slots + 1), ...] with row 0 of
    each layer a scratch row as page 0 is and slot b owning row b + 1:

    ``state`` [layers x (slots + 1), K, R, H, H] and ``state_z`` [layers x
    (slots + 1), R, K, H] are the fixed-size state (``ops/retention``: R
    slabs of H), and ``state_len`` [slots + 1] the positions it holds: a
    multiple of the chunk, written by prefill and by the fold alone.

    ``g`` [layers, slots + 1, K, T] float32 holds the tail's cumulative
    log-gates, each position's sum within its own chunk: column c is
    position ``state_len + c``, T = ``tail_pages`` x page (a chunk, a
    decode window, rounded up to whole 128-lane rows). It is the very
    array a decode step hands its kernel, kept and not rebuilt from pages:
    a step writes one column of a slot's row, a fold moves the row down a
    chunk, a prefill writes it whole (zeros behind the newest position),
    and no read of a column behind the newest position is used."""
    from orion_tpu.ops.retention import fold_chunk, n_slabs, tail_pages

    K, H = mcfg.n_kv_heads, mcfg.resolved_head_dim
    R, slots = n_slabs(H), icfg.max_batch_size + 1
    T = tail_pages(fold_chunk(mcfg.max_seq_len),
                   icfg.page_size) * icfg.page_size
    return {
        "g": jnp.zeros((mcfg.n_layers, slots, K, T), jnp.float32),
        "state": jnp.zeros((mcfg.n_layers * slots, K, R, H, H), dtype),
        "state_z": jnp.zeros((mcfg.n_layers * slots, R, K, H), jnp.float32),
        "state_len": jnp.zeros((slots,), jnp.int32),
    }


class PageAllocator:
    """Host-side refcounted free list over the page pool (page 0 = scratch).

    Pages are refcounted so the prefix cache (infer/prefix_cache.py) and
    live requests can SHARE immutable pages: ``alloc`` hands out pages at
    refcount 1, ``retain`` adds an owner, and ``release`` drops one — the
    page returns to the free list only when its last owner lets go. The
    single accounting invariant every owner relies on:

        free_pages + sum(refcounted live pages) == num_pages - 1

    where a page is live iff its refcount > 0 (owners: one per mapping in a
    live request's page table, plus one for the radix-tree node that caches
    it). ``free`` remains as a bulk release for owners holding exactly one
    ref per page.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is scratch)")
        self.num_pages = num_pages
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        self._refs: list[int] = [0] * num_pages

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def refcount(self, page: int) -> int:
        return self._refs[page]

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise MemoryError(
                f"KV cache pool exhausted: want {n} pages, have "
                f"{len(self._free)}"
            )
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def retain(self, page: int) -> None:
        """Add an owner to a live (shared) page."""
        assert 0 < page < self.num_pages, page
        assert self._refs[page] > 0, f"retain of free page {page}"
        self._refs[page] += 1

    def release(self, page: int) -> bool:
        """Drop one ownership ref; returns True iff the page was freed."""
        assert 0 < page < self.num_pages, page
        assert self._refs[page] > 0, f"release of free page {page}"
        self._refs[page] -= 1
        if self._refs[page] == 0:
            self._free.append(page)
            return True
        return False

    def free(self, pages: list[int]) -> None:
        """Bulk release for owners holding one ref per page."""
        for p in pages:
            self.release(p)


def rollback_pages(
    alloc: PageAllocator,
    pages: list,
    n_keep: int,
) -> list[int]:
    """Speculative-decode rollback: truncate a request's page list to its
    first ``n_keep`` entries, releasing the tail back to the pool.

    The verify step pre-provisions pages for the whole draft window
    (write positions may run speculate_tokens past the accepted cursor);
    after acceptance, pages covering ONLY rejected tokens are dead — no
    position below the rewound cursor lives in them, and the next window's
    provisioning re-allocates from the free list (LIFO, so the same pages
    come straight back if speculation continues). Releasing them here
    restores exactly the page footprint a non-speculative (window=1)
    engine holds after its step, which is what keeps pool-pressure
    preemption and the admission math speculation-agnostic.

    Tail entries are always privately-owned (refcount 1): shared prefix
    pages and SWA-rolled ``None`` placeholders live strictly below any
    live cursor, hence below ``n_keep``. Returns the released page ids
    (the caller zeroes their page-table columns).
    """
    assert n_keep >= 0, n_keep
    dead = [p for p in pages[n_keep:] if p is not None]
    del pages[n_keep:]
    alloc.free(dead)
    return dead


def compact_draft_kv(
    cache: Cache,
    page_table: jax.Array,    # [B, P] int32 per-layer-relative page ids
    seq_lens: jax.Array,      # [B] int32: the verify-time cursor (start)
    src: jax.Array,           # [B, W] int32: column whose KV moves to
    #                           position start + i (identity = no move)
    *,
    n_layers: int,
    num_pages: int,
) -> Cache:
    """Tree-speculation KV compaction: move accepted off-path draft KV
    into cursor-contiguous positions.

    A verify step writes tree column j's K/V at pool position
    ``start + j``; an accepted root-path of depth d consists of columns
    ``path[1..d]``, which are slot-contiguous ONLY when the accepted path
    is the tree's first inserted chain. For any other branch, position
    ``start + i`` must end up holding column ``path[i]``'s KV before the
    next decode step reads it. This gathers every (b, i) source entry
    (position ``start + src[b, i]``) across ALL layers and cache arrays
    (int8 pools move with their scale columns) and scatters it to
    position ``start + i`` — gather-before-scatter, so overlapping moves
    (dst slots are always <= src slots: depth <= column index) read
    pre-move bytes. Identity entries copy onto themselves; rows past a
    slot's real width point at whatever the clamp hits, which is either
    a self-copy or the scratch page — both unobservable. One jitted
    program serves every step (the engine pads ``src`` with identity).

    Accepted KV bytes are MOVED verbatim (quantized bytes + scales under
    kv_quant), so the compacted pool is bitwise the pool a sequential
    decode of the accepted tokens would have produced — the greedy
    byte-identity argument runs through this function.
    """
    B, W = src.shape
    psz = paged_leaf(cache).shape[2]
    P = page_table.shape[1]
    max_pos = P * psz - 1
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    steps = jnp.arange(W, dtype=jnp.int32)[None, :]
    src_pos = jnp.minimum(seq_lens[:, None] + src.astype(jnp.int32),
                          max_pos)
    dst_pos = jnp.minimum(seq_lens[:, None] + steps, max_pos)
    layer = jnp.arange(n_layers, dtype=jnp.int32)[:, None, None] * num_pages
    rows_src = layer + page_table[bidx, src_pos // psz][None]   # [L, B, W]
    rows_dst = layer + page_table[bidx, dst_pos // psz][None]
    off_src = jnp.broadcast_to(src_pos % psz, (n_layers, B, W))
    off_dst = jnp.broadcast_to(dst_pos % psz, (n_layers, B, W))
    out = dict(cache)
    for name, arr in cache.items():
        # Pools are [rows, K, psz, H]; scale pools [rows, K, SCALE_LANES].
        # Either way the per-token column is axis 2 of the row block.
        vals = arr[rows_src, :, off_src]
        out[name] = arr.at[rows_dst, :, off_dst].set(vals)
    return out


def poison_page(cache: Cache, page, *, n_layers: int, num_pages: int) -> Cache:
    """Overwrite one pool page's K rows (a latent pool's rows; all layers) with NaN — the fault
    INJECTION primitive behind the NaN-quarantine tests (runtime/fault.py
    FaultSpec kind="nan"): real NaNs flow through the real attention into
    exactly one slot's logits, because no other slot ever reads this
    request's pages. Under kv_quant the int8 pool cannot hold a NaN, so the
    f32 ``k_scale`` rows are poisoned instead (dequantized K goes NaN, same
    blast radius). ``page`` may be a traced scalar."""
    layer_rows = jnp.arange(n_layers, dtype=jnp.int32) * num_pages + page
    target = ("k_scale" if "k_scale" in cache
              else "k" if "k" in cache else LATENT)
    out = dict(cache)
    arr = out[target]
    out[target] = arr.at[page_rows(
        arr, layer_rows, n_layers * num_pages)].set(
            jnp.asarray(jnp.nan, arr.dtype))
    return out


def scrub_pages(
    cache: Cache, pages: jax.Array, *, n_layers: int, num_pages: int
) -> Cache:
    """Zero the given pool pages' rows across every cache array (all
    layers): the quarantine path scrubs a poisoned request's private pages
    before returning them to the free list, so stale NaNs can never leak
    into a later tenant of the same page. ``pages`` may contain repeats
    and scratch page 0 (padding) — zeroing scratch is harmless, it is
    never read."""
    layer_rows = (
        jnp.arange(n_layers, dtype=jnp.int32)[:, None] * num_pages
        + pages[None, :].astype(jnp.int32)
    ).reshape(-1)
    out = {}
    for name, arr in cache.items():
        if name in NOT_PAGED:       # no page: the next prefill writes the row
            out[name] = arr
        else:                       # [layers x pages, ...]
            out[name] = arr.at[page_rows(
                arr, layer_rows, n_layers * num_pages)].set(
                    jnp.zeros((), arr.dtype))
    return out


class HostPagePool:
    """Host-RAM page store: the second tier behind the radix tree.

    ``PageAllocator``'s counterpart for host memory — same refcounted
    free-list discipline (slots at refcount 1 from ``alloc``, ``retain``
    adds an owner, ``release`` drops one) plus the two things a HOST tier
    needs that the device pool does not:

    * byte storage: ``store``/``load`` move page blocks (the per-array
      ``[n, n_layers, ...]`` stacks that ``gather_pages`` produces) into
      and out of preallocated numpy buffers, one slot per page. The
      buffers are allocated lazily on the first ``store`` so the pool
      never needs the cache dict's dtypes up front, and they are plain
      pinned-by-the-OS host arrays — no device allocation ever.
    * its own LRU clock: ``touch`` stamps a slot on every store/load,
      ``evict_lru`` frees the coldest UNREFERENCED slots. A slot with
      refcount > 1 is skipped, never reclaimed out from under an extra
      owner (e.g. an in-flight restore's ref) — the evict-while-
      referenced refusal.

    One object-store shape serves KV pages today and adapter pages later
    (ROADMAP LoRA item): nothing here knows what the bytes mean.
    """

    def __init__(self, capacity: int, page_bytes: int = 0):
        if capacity < 1:
            raise ValueError(f"HostPagePool needs capacity >= 1, got {capacity}")
        self.capacity = capacity
        self.page_bytes = page_bytes
        self._free: list[int] = list(range(capacity - 1, -1, -1))
        self._refs: list[int] = [0] * capacity
        self._stamps: list[int] = [0] * capacity
        self._clock = itertools.count(1)
        self._store: dict[str, np.ndarray] = {}

    @property
    def free_slots(self) -> int:
        return len(self._free)

    def refcount(self, hid: int) -> int:
        return self._refs[hid]

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise MemoryError(
                f"host page pool exhausted: want {n} slots, have "
                f"{len(self._free)}"
            )
        hids = [self._free.pop() for _ in range(n)]
        now = next(self._clock)
        for h in hids:
            self._refs[h] = 1
            self._stamps[h] = now
        return hids

    def retain(self, hid: int) -> None:
        assert 0 <= hid < self.capacity, hid
        assert self._refs[hid] > 0, f"retain of free host slot {hid}"
        self._refs[hid] += 1

    def release(self, hid: int) -> bool:
        """Drop one ownership ref; returns True iff the slot was freed."""
        assert 0 <= hid < self.capacity, hid
        assert self._refs[hid] > 0, f"release of free host slot {hid}"
        self._refs[hid] -= 1
        if self._refs[hid] == 0:
            self._free.append(hid)
            return True
        return False

    def free(self, hids: list[int]) -> None:
        """Bulk release for owners holding one ref per slot."""
        for h in hids:
            self.release(h)

    def touch(self, hid: int) -> None:
        self._stamps[hid] = next(self._clock)

    def evict_lru(self, n: int) -> list[int]:
        """Free up to ``n`` of the coldest single-owner slots.

        Only slots at refcount exactly 1 are reclaimable: a second ref
        means someone (an in-flight restore, a future adapter mapping)
        is actively relying on the bytes, and evicting those would tear
        them — such slots are skipped, not stolen. Returns the freed
        slot ids; the CALLER owns dropping its tree/table entries for
        them (this pool knows nothing about the radix tree).
        """
        if n <= 0:
            return []
        victims = sorted(
            (h for h in range(self.capacity) if self._refs[h] == 1),
            key=lambda h: self._stamps[h],
        )[:n]
        for h in victims:
            self.release(h)
        return victims

    def store(self, hids: list[int], blocks: dict[str, np.ndarray],
              n: Optional[int] = None) -> None:
        """Copy the first ``n`` rows of each per-array page block into the
        given slots (``blocks`` row i -> ``hids[i]``). Rows past ``n`` are
        dispatch padding (scratch-page gathers) and are dropped here —
        padding never occupies host RAM."""
        n = len(hids) if n is None else n
        assert n <= len(hids), (n, len(hids))
        rows = list(hids[:n])
        now = next(self._clock)
        for name, blk in blocks.items():
            blk = np.asarray(blk)
            buf = self._store.get(name)
            if buf is None:
                buf = np.empty((self.capacity,) + blk.shape[1:], blk.dtype)
                self._store[name] = buf
            buf[rows] = blk[:n]
        for h in rows:
            self._stamps[h] = now

    def load(self, hids: list[int]) -> dict[str, np.ndarray]:
        """Stack the given slots' bytes into per-array page blocks
        (row i = ``hids[i]``), shaped for ``scatter_pages``."""
        rows = list(hids)
        now = next(self._clock)
        for h in rows:
            self._stamps[h] = now
        return {name: buf[rows] for name, buf in self._store.items()}


def gather_pages(
    cache: Cache, pages: jax.Array, *, n_layers: int, num_pages: int
) -> Cache:
    """Gather whole pool pages (all layers, all cache arrays) into dense
    per-array blocks ``[n, n_layers, ...]`` — the device half of the ONE
    batched d2h an eviction sweep performs. ``pages`` may contain scratch
    page 0 as padding (one jit program per pow2 batch size); padding rows
    gather scratch bytes, which the caller drops before storing. Scale
    pools under kv_quant ride along because the gather walks the whole
    cache dict. No donation: the pool is read, not consumed."""
    rows = (
        pages[:, None].astype(jnp.int32)
        + jnp.arange(n_layers, dtype=jnp.int32)[None, :] * num_pages
    )
    return {name: arr[rows] for name, arr in cache.items()}


def scatter_pages(
    cache: Cache, pages: jax.Array, blocks: Cache,
    *, n_layers: int, num_pages: int,
) -> Cache:
    """Scatter dense page blocks (``gather_pages``' shape) back into the
    pool pages — the device half of the ONE batched h2d a restore
    performs. Padding entries target scratch page 0 (never read; repeated
    scatter indices land arbitrarily but harmlessly there). The engine
    jits this with the pool donated: restore rewrites rows in place."""
    rows = (
        pages[:, None].astype(jnp.int32)
        + jnp.arange(n_layers, dtype=jnp.int32)[None, :] * num_pages
    )
    return {
        name: arr.at[rows].set(blocks[name].astype(arr.dtype))
        for name, arr in cache.items()
    }


def host_page_bytes(cache: Cache, n_layers: int) -> int:
    """Host bytes one pool page occupies across every paged cache array
    (``n_layers`` layers; scale pools included under kv_quant) — the unit the
    ``inference.host_tier_bytes`` budget is divided by."""
    total = 0
    for name, arr in cache.items():
        if name in NOT_PAGED:
            continue
        per_row = math.prod(arr.shape[1:]) * arr.dtype.itemsize
        total += n_layers * per_row
    return total


def host_tier_break_even_tokens(
    page_bytes: int,
    page_size: int,
    h2d_gbps: float,
    restore_overhead_s: float,
    prefill_tok_s: float,
) -> Optional[int]:
    """Break-even match length: the token count above which restoring a
    host-resident prefix beats recomputing it (PERF.md "Host-tier
    break-even").

        restore(t)   = overhead + t * bytes_per_token / (bw * 1e9)
        recompute(t) = t / prefill_tok_s

    Both are linear in t; restore pays a fixed dispatch/sync overhead but
    a (typically much) cheaper per-token slope, so the lines cross at

        t* = overhead / (1/prefill_tok_s - bytes_per_token/bw)

    Returns ``None`` when the restore slope is >= the recompute slope
    (restore NEVER wins — e.g. a slow interconnect against a tiny model);
    otherwise the crossing, floored at one page so a sub-page match never
    qualifies. The constants are config knobs with measured defaults
    (``tools/prefix_cache_bench.py --capacity-sweep`` reports real ones).
    """
    per_tok_restore = (page_bytes / page_size) / (h2d_gbps * 1e9)
    per_tok_compute = 1.0 / prefill_tok_s
    if per_tok_restore >= per_tok_compute:
        return None
    gain = per_tok_compute - per_tok_restore
    return max(page_size, math.ceil(restore_overhead_s / gain))


def copy_page(cache: Cache, src, dst, *, n_layers: int, num_pages: int) -> Cache:
    """Copy one pool page's rows (all layers, all cache arrays) src -> dst.

    The copy-on-write primitive behind prefix caching: when a request's
    whole context is cached, its first decode step must (re)write the KV
    slot of the final token — which lives in a SHARED page. The engine
    copies that page into a private one first, so shared pages stay
    immutable. ``src``/``dst`` may be traced scalars (one jit program
    serves every copy); scale pools under kv_quant ride along because the
    copy walks the whole cache dict.
    """
    layer_rows = jnp.arange(n_layers, dtype=jnp.int32) * num_pages
    rows_src = layer_rows + src
    rows_dst = layer_rows + dst
    return {
        name: arr.at[rows_dst].set(arr[rows_src])
        for name, arr in cache.items()
    }
