"""Power retention: attention whose weights are a power of the score under a
learned decay, and which is therefore also a recurrence on a fixed-size state.

    A_ij = exp(sum_{j<l<=i} g_l) * (q_i . k_j / sqrt(H))^2      (j <= i)
    y_i  = sum_j A_ij v_j / sum_j A_ij

with ``g`` the log of a gate in (0, 1), one number a K/V head and position,
and a query head reading its group's K/V head and gate. With ``phi`` the
symmetric square of a head vector (``phi(q) . phi(k) = (q . k)^2``) the same
thing is ``S_t = e^{g_t} S_{t-1} + phi(k_t) v_t^T``, ``z_t = e^{g_t} z_{t-1}
+ phi(k_t)``, ``y_t = phi(q_t)^T S_t / phi(q_t)^T z_t``: a sequence's past
costs ``[D, H]`` numbers a K/V head however long it is. The two forms combine
by chunks: a chunk's queries read the state of everything before the chunk and
attend quadratically inside it.

``phi`` here is laid out for the lanes of a TPU: H/2 + 1 SLABS of H numbers,
slab r holding ``x * roll(x, r)`` (``x_a x_{a-r}``), weighted sqrt 2 where a
pair appears once in it (0 < r < H/2) and 1 where it is a square (r = 0) or
appears twice (r = H/2). That is ``D = (H/2 + 1) H`` entries, H/2 more than
the H (H + 1) / 2 of the symmetric square (8320 against 8256 at H = 128), and
every slab is one lane rotation and one multiply away from the vector.

This module is the plain ``jax.numpy`` form (float32 accumulation): what
training differentiates, what the CPU tests and the engine's XLA fallback
run, and what the Pallas kernels of ``ops/pallas/retention.py`` are held to.
The cumulative log-gates of a sequence are kept PER CHUNK of ``chunk``
positions (``chunk_cumsum``: position t holds the sum over its own chunk up
to and including t), so that no sum grows with the sequence.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from orion_tpu.ops._dispatch import resolve_impl

SQRT2 = 2.0 ** 0.5
# Stands for "never" in a cumulative log-gate: exp(c - BIG) is an exact 0.
BIG = 1e30
# The chunk: what the chunked forms attend quadratically inside, and how many
# positions a serving slot's tail holds before they are folded into its state
# (one state write a CHUNK tokens). The program's choice, not the model's, and
# no result depends on it. Measured in the Brumby cell, 512 against 1024 (PR
# 33, PERF.md section 6; six pairs of runs): half the tail a decode step
# reads, as many fold milliseconds a token, prefill 5 % dearer a token,
# 1.6-2.1 % more tokens a second.
CHUNK = 512


def fold_chunk(max_seq_len: int) -> int:
    """The chunk of a model whose longest sequence is ``max_seq_len``: CHUNK,
    or an eighth of that sequence where it is shorter (a state beside a tail
    as long as the sequence would save nothing; a test's model folds every
    16 positions of its 128). A multiple of the serving page size."""
    return min(CHUNK, max_seq_len // 8)


def query_units(n: int, head_dim: int) -> int:
    """What the queries of a sequence of n positions cost at the least, in
    units of ``head_dim`` multiply-adds a query head: position t (from 0)
    either attends its t + 1 predecessors (a score and a weighted value
    each) or reads a state of D = H (H + 1) / 2 entries, whichever is
    cheaper. No chunking undercuts it (the engine's
    ``prefill_retention_units`` is this a layer)."""
    D = head_dim * (head_dim + 1) // 2
    quad = min(n, D // 2)
    return quad * (quad + 1) + (n - quad) * D


def n_slabs(head_dim: int) -> int:
    if head_dim % 2:
        raise ValueError(f"power retention needs an even head size, "
                         f"got {head_dim}")
    return head_dim // 2 + 1


def slab_weight(r, R: int):
    """1 for the squares (r = 0) and the slab that holds each pair twice
    (r = R - 1), sqrt 2 between."""
    return jnp.where((r == 0) | (r == R - 1), 1.0, SQRT2).astype(jnp.float32)


def phi(x: jax.Array) -> jax.Array:
    """[..., H] -> [..., R, H] in float32: the slabs of the symmetric
    square."""
    R = n_slabs(x.shape[-1])
    xf = x.astype(jnp.float32)
    return jnp.stack(
        [slab_weight(r, R) * xf * jnp.roll(xf, r, axis=-1) for r in range(R)],
        axis=-2)


def chunk_cumsum(log_g: jax.Array, chunk: int) -> jax.Array:
    """[B, S, K] -> [B, S, K]: position t holds the sum of the log-gates of
    its own chunk (positions t // chunk * chunk .. t)."""
    B, S, K = log_g.shape
    pad = -S % chunk
    g = jnp.pad(log_g.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    b = jnp.cumsum(g.reshape(B, -1, chunk, K), axis=2)
    return b.reshape(B, S + pad, K)[:, :S]


def _state_read(qg, S, z):
    """phi(q)^T S and phi(q)^T z, a slab at a time (a whole phi of a chunk
    of queries is R times the queries): qg [B, C, K, G, H] f32, S [B, K, R,
    H, H], z [B, K, R, H] -> ([B, C, K, G, H], [B, C, K, G])."""
    R = S.shape[2]

    def slab(carry, r):
        num, den = carry
        ph = slab_weight(r, R) * qg * jnp.roll(qg, r, axis=-1)
        num = num + jnp.einsum("bckga,bkah->bckgh", ph, S[:, :, r])
        den = den + jnp.einsum("bckga,bka->bckg", ph, z[:, :, r])
        return (num, den), None

    init = (jnp.zeros(qg.shape, jnp.float32),
            jnp.zeros(qg.shape[:-1], jnp.float32))
    (num, den), _ = jax.lax.scan(slab, init, jnp.arange(R))
    return num, den


def state_update(S, z, kc, vc, b, keep=None):
    """One chunk into the state: S [B, K, R, H, H], z [B, K, R, H] (f32),
    kc / vc [B, C, K, H], b [B, C, K] the chunk's cumulative log-gates.
    ``keep`` [B] bool leaves a row's state as it was."""
    total = b[:, -1]                                         # [B, K]
    dk = jnp.exp(total[:, None] - b)                         # [B, C, K]
    pk = phi(kc)                                             # [B, C, K, R, H]
    vd = vc.astype(jnp.float32) * dk[..., None]
    eB = jnp.exp(total)
    S2 = eB[:, :, None, None, None] * S + jnp.einsum(
        "bckra,bckh->bkrah", pk, vd)
    z2 = eB[:, :, None, None] * z + jnp.einsum("bckra,bck->bkra", pk, dk)
    if keep is None:
        return S2, z2
    return (jnp.where(keep[:, None, None, None, None], S, S2),
            jnp.where(keep[:, None, None, None], z, z2))


def empty_state(B: int, K: int, H: int):
    R = n_slabs(H)
    return (jnp.zeros((B, K, R, H, H), jnp.float32),
            jnp.zeros((B, K, R, H), jnp.float32))


def _power_retention_xla(q, k, v, log_g, lengths, chunk):
    B, S, N, H = q.shape
    K = k.shape[2]
    G = N // K
    C = chunk
    pad = -S % C
    nC = (S + pad) // C
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)

    def chunks(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(B, nC, C, *x.shape[2:]), 1, 0)

    b_all = chunk_cumsum(log_g, C)
    xs = (chunks(q.reshape(B, S, K, G, H)), chunks(k), chunks(v),
          chunks(b_all), jnp.arange(nC))
    idx = jnp.arange(C)
    causal = idx[:, None] >= idx[None, :]

    def one(carry, x):
        S_, z_ = carry
        qc, kc, vc, b, c = x
        qf = qc.astype(jnp.float32)
        n_valid = lengths - c * C                                # [B]
        s = jnp.einsum("bikgh,bjkh->bkgij", qf,
                       kc.astype(jnp.float32)) * (H ** -0.5)
        live = causal[None] & (idx[None, None, :] < n_valid[:, None, None])
        # [B, K, i, j]: the decay from j to i, and no weight where j is
        # later than i or is padding.
        bt = jnp.swapaxes(b, 1, 2)                               # [B, K, C]
        decay = jnp.where(live[:, None], bt[:, :, :, None] - bt[:, :, None],
                          -BIG)
        a = s * s * jnp.exp(decay)[:, :, None]
        num = jnp.einsum("bkgij,bjkh->bikgh", a, vc.astype(jnp.float32))
        den = jnp.moveaxis(a.sum(-1), 3, 1)                      # [B,i,K,G]
        ns, ds = _state_read(qf, S_, z_)
        dq = jnp.exp(b)[..., None] / H                           # [B,C,K,1]
        num = num + dq[..., None] * ns
        den = den + dq * ds
        y = num / jnp.where(den == 0.0, 1.0, den)[..., None]
        # Padding rows are zeros, not whatever their division gave: their
        # K/V reach the tail pages, where a masked weight of 0 times a
        # non-finite value would not be 0.
        y = jnp.where((idx[None, :] < n_valid[:, None])[..., None, None, None],
                      y, 0.0)
        S_, z_ = state_update(S_, z_, kc, vc, b, keep=n_valid < C)
        return (S_, z_), y

    (S1, z1), ys = jax.lax.scan(one, empty_state(B, K, H), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nC * C, N, H)[:, :S]
    return y.astype(q.dtype), (S1, z1)


def power_retention(
    q: jax.Array,                  # [B, S, N, H]
    k: jax.Array,                  # [B, S, K, H]
    v: jax.Array,                  # [B, S, K, H]
    log_g: jax.Array,              # [B, S, K] f32: log of the gate
    *,
    lengths: Optional[jax.Array] = None,   # [B]: real positions a row
    chunk: int,
    impl: str = "xla",
) -> tuple[jax.Array, tuple]:
    """Causal power retention of whole sequences (rows padded at the end).

    Returns ``(y [B, S, N, H], (S [B, K, R, H, H], z [B, K, R, H]))`` in
    float32. The state handed out is that of a row's COMPLETE chunks,
    positions ``[0, length // chunk * chunk)``; no state is handed in (no
    caller resumes a sequence yet: chunked prefill and a cached prefix are
    refused); the rest of the row is the caller's tail (the
    serving engine keeps its K, V and gates in pages until the chunk
    completes, ``retention_fold``). Padding contributes nothing to a state
    and its rows of ``y`` are zeros."""
    use_pallas, interpret = resolve_impl(impl)
    if use_pallas:
        from orion_tpu.ops.pallas.retention import retention_prefill

        return retention_prefill(q, k, v, chunk_cumsum(log_g, chunk),
                                 lengths=lengths, chunk=chunk,
                                 interpret=interpret)
    return _power_retention_xla(q, k, v, log_g, lengths, chunk)


# -- serving: one new token a slot over a state row and a paged tail ----------


def tail_pages(chunk: int, page_size: int) -> int:
    """Entries of a page table a slot's tail can span: a complete chunk that
    waits for the next window's fold and the window's own tokens, and as
    many more as make the tail a whole number of 128-lane rows."""
    n = chunk // page_size + 1
    lanes = 128
    if lanes % page_size == 0:
        per = lanes // page_size
        n = -(-n // per) * per
    return n


def retention_decode_xla(q, k_new, v_new, c_q, c_tail, k_pool, v_pool,
                         state, state_z, page_table, state_len, pos, *,
                         layer_base, state_base):
    """The decode kernel's contract in ``jax.numpy`` (``retention_decode`` of
    ``ops/pallas/retention.py`` has the arguments): write the new token's K
    and V at position ``pos``, attend over the tail pages from ``state_len``
    on and over the slot's state row."""
    B, N, H = q.shape
    _, K, psz, _ = k_pool.shape
    G = N // K
    P = page_table.shape[1]
    nT = c_tail.shape[-1] // psz
    rows = layer_base + jnp.take_along_axis(
        page_table, jnp.minimum(pos // psz, P - 1)[:, None], axis=1)[:, 0]
    k_pool = k_pool.at[rows, :, pos % psz].set(k_new)
    v_pool = v_pool.at[rows, :, pos % psz].set(v_new)
    tp = jnp.minimum(state_len[:, None] // psz + jnp.arange(nT), P - 1)
    trows = layer_base + jnp.take_along_axis(page_table, tp, axis=1)
    kt = k_pool[trows].transpose(0, 2, 1, 3, 4).reshape(B, K, nT * psz, H)
    vt = v_pool[trows].transpose(0, 2, 1, 3, 4).reshape(B, K, nT * psz, H)
    qf = q.reshape(B, 1, K, G, H).astype(jnp.float32)
    s = jnp.einsum("bwkgh,bkjh->bkwgj", qf,
                   kt.astype(jnp.float32)) * (H ** -0.5)
    jpos = state_len[:, None] + jnp.arange(nT * psz)             # [B, T]
    live = jpos <= pos[:, None]
    decay = jnp.where(live[:, None], c_q[..., None] - c_tail, -BIG)  # [B,K,T]
    a = s * s * jnp.exp(decay)[:, :, None, None]
    num = jnp.einsum("bkwgj,bkjh->bwkgh", a, vt.astype(jnp.float32))
    den = jnp.moveaxis(a.sum(-1), 1, 2)                          # [B,1,K,G]
    srow = state_base + 1 + jnp.arange(B)
    ns, ds = _state_read(qf, state[srow].astype(jnp.float32),
                         jnp.swapaxes(state_z[srow], 1, 2))
    dq = jnp.exp(c_q)[:, None, :, None] / H                      # [B,1,K,1]
    num = num + dq[..., None] * ns
    den = den + dq * ds
    y = num / jnp.where(den == 0.0, 1.0, den)[..., None]
    return y.reshape(B, N, H).astype(q.dtype), k_pool, v_pool


def retention_fold_xla(state, state_z, kc, vc, b, row):
    """One complete tail chunk of ONE slot into its state row, in place:
    state [rows, K, R, H, H], state_z [rows, R, K, H], kc / vc [K, C, H],
    b [K, C] the chunk's cumulative log-gates, ``row`` the flat row."""
    S = state[row][None].astype(jnp.float32)
    z = jnp.swapaxes(state_z[row], 0, 1)[None]
    S2, z2 = state_update(S, z, jnp.swapaxes(kc, 0, 1)[None],
                          jnp.swapaxes(vc, 0, 1)[None], b.T[None])
    return (state.at[row].set(S2[0].astype(state.dtype)),
            state_z.at[row].set(jnp.swapaxes(z2[0], 0, 1)))
