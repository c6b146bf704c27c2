"""Kimi delta attention (KDA): a delta rule under a per-channel decay.

A head keeps a state ``S`` [d_k, d_v]. A position t brings a query and a key
(both l2-normalised), a value, a log-decay ``g_t`` <= 0 a key channel
(``a_t = exp(g_t)``) and a write strength ``b_t`` in (0, 1):

    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

The state is ERASED along k before it is written (a rank-one correction a
position, not a sum), which is what sets it apart from a gated sum and from
``ops/retention.py`` (a feature map, a scalar gate a head, a tail that folds).

Three forms, all float32 inside:

``kda_recurrent``  the recurrence itself, ``lax.scan`` over positions: the
    oracle of every test and of ``tools/tpu_parity.py --only kda``. Not on
    the served path.
``kda_chunked``    prefill and training: matmuls over chunks of ``CHUNK``
    positions. With ``G_i`` the cumulative log-decay inside a chunk and
    ``u_i = v_i - (diag(a_i) S_{i-1})^T k_i`` the value a position really
    writes, ``(I + A) U = V - (K . e^G) S_0`` with ``A_ij = b_j sum_c k_ic
    k_jc e^(G_ic - G_jc)`` (j < i): a unit lower-triangular system, inverted
    once a chunk; ``O = (Q . e^G) S_0 + B U`` (B as A with q_i for k_i and
    j <= i), ``S_C = e^(G_C) . S_0 + (K . e^(G_C - G) . b)^T U``. Every decay
    is a DIFFERENCE of cumulative logs that is <= 0: inside a sub-chunk of
    ``SUB`` positions per pair, across sub-chunks through the row block's
    first position (``e^(G_i - G_r)`` and ``e^(G_r - G_j)``, both <= 1). No
    ``exp(-G_j)`` of a whole cumulative sum is ever formed: at the gate's
    bound of -5 a step a chunk's sum reaches -320 and the usual ratio trick
    overflows float32.
    The triangular inverse is block elimination in whole ``[CHUNK, CHUNK]``
    products under a mask (PR 48: ten products of 64 rows a chunk and head
    where doubling on 16 x 16 blocks and forward substitution issued
    thirty-two of 16), and what a chunk needs beside the state is made for
    ``SEGMENT`` positions at a time: the form is bound by the traffic of its
    intermediates, and at 256 (128-256 chunk-heads a segment) a segment
    inside the outer scan costs what a block of one segment costs, where at
    2048 it cost 1.7 times that (PERF.md section 6, PR 48). Four child
    scopes of ``kda/chunk`` (``scores``, ``inverse``, ``apply``, ``carry``)
    split its device time.
``kda_step``       one new position a slot (decode): the XLA form of
    ``ops/pallas/kda.kda_decode``.

``short_conv`` / ``short_conv_step`` are the depthwise causal convolution in
front of q, k and v (the last ``K`` positions, the current one included).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

CHUNK = 64
SUB = 16
SEGMENT = 256      # positions of a block whose chunks are prepared together
_HI = jax.lax.Precision.HIGHEST


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x / sqrt(sum x^2 + eps) over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def safe_log_decay(z: jax.Array, a_log: jax.Array, dt_bias: jax.Array,
                   lower_bound: float) -> jax.Array:
    """The bounded gate: ``g = lower_bound x sigmoid(exp(A_log) (z +
    dt_bias))`` with lower_bound < 0, so a step's log-decay lies in
    (lower_bound, 0). z [..., H, d_k]; a_log [H]; dt_bias [H, d_k]."""
    z = z.astype(jnp.float32) + dt_bias.astype(jnp.float32)
    return lower_bound * jax.nn.sigmoid(
        jnp.exp(a_log.astype(jnp.float32))[:, None] * z)


# -- the short convolution -----------------------------------------------------


def short_conv(x: jax.Array, w: jax.Array,
               lengths: Optional[jax.Array] = None
               ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution over the last K positions: x [B, S, C],
    w [K, C] (tap K - 1 is the current position) -> (y [B, S, C], tail
    [B, K - 1, C]: the last K - 1 INPUT rows of each sequence, zeros where
    it is shorter; ``lengths`` [B], default S)."""
    B, S, C = x.shape
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + S] * w[i].astype(x.dtype) for i in range(K))
    if lengths is None:
        return y, xp[:, S:]
    # Row i of the tail is position length - (K - 1) + i = padded index
    # length + i.
    at = lengths[:, None] + jnp.arange(K - 1, dtype=lengths.dtype)[None, :]
    tail = jnp.take_along_axis(xp, at[:, :, None], axis=1)
    return y, tail


def short_conv_step(x: jax.Array, tail: jax.Array, w: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """One new position: x [B, C], tail [B, K - 1, C] -> (y [B, C], the
    tail moved up one row)."""
    rows = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    y = (rows.astype(x.dtype) * w.astype(x.dtype)[None]).sum(1)
    return y, rows[:, 1:]


# -- the recurrence --------------------------------------------------------------


def kda_recurrent(q, k, v, g, b, state: Optional[jax.Array] = None,
                  lengths: Optional[jax.Array] = None):
    """q, k, g [B, S, H, d_k]; v [B, S, H, d_v]; b [B, S, H]; ``state``
    [B, H, d_k, d_v] (default zeros) -> (o [B, S, H, d_v], the state behind
    the last position, or behind position ``lengths`` - 1)."""
    f32 = jnp.float32
    q, k, v, g, b = (x.astype(f32) for x in (q, k, v, g, b))
    B, S, H, dk = q.shape
    if state is None:
        state = jnp.zeros((B, H, dk, v.shape[-1]), f32)
    live = (jnp.ones((B, S), bool) if lengths is None
            else jnp.arange(S)[None, :] < lengths[:, None])

    def step(s, xs):
        qt, kt, vt, gt, bt, on = xs
        s_new, o = _step(s, qt, kt, vt, gt, bt)
        return jnp.where(on[:, None, None, None], s_new, s), o

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, b, live))
    state, o = jax.lax.scan(step, state.astype(f32), xs)
    return jnp.moveaxis(o, 0, 1), state


def _step(s, q, k, v, g, b):
    """One position of the recurrence on float32 operands: s [B, H, d_k,
    d_v]; q, k, g [B, H, d_k]; v [B, H, d_v]; b [B, H]."""
    s = s * jnp.exp(g)[..., None]
    u = v - jnp.einsum("bhkv,bhk->bhv", s, k, precision=_HI)
    s = s + (b[..., None] * k)[..., None] * u[..., None, :]
    return s, jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI)


def kda_step(state, q, k, v, g, b, active: Optional[jax.Array] = None):
    """Decode: one new position a slot. ``state`` [B, H, d_v, d_k] float32
    (a slot's row as the cache keeps it: value-major, the TRANSPOSE of S,
    ``ops/pallas/kda.py``); q, k, g [B, H, d_k]; v [B, H, d_v]; b [B, H];
    ``active`` [B] bool: the rows that advance (default all) -> (o [B, H,
    d_v] float32, state')."""
    f32 = jnp.float32
    s, o = _step(jnp.swapaxes(state, -1, -2).astype(f32),
                 *(x.astype(f32) for x in (q, k, v, g, b)))
    new = jnp.swapaxes(s, -1, -2).astype(state.dtype)
    if active is not None:
        new = jnp.where(active[:, None, None, None], new, state)
    return o, new


# -- the chunked form ------------------------------------------------------------


def _pair_scores(rows, keys, G, sub: int):
    """[..., C, C]: ``sum_c rows_ic keys_jc e^(G_ic - G_jc)`` for j <= i, 0
    above the diagonal. rows, keys, G [..., C, d_k] float32, G the
    cumulative log-decay inside the chunk. Decays as differences <= 0 only
    (module docstring)."""
    *lead, C, dk = rows.shape
    n = C // sub
    blk = lambda x: x.reshape(*lead, n, sub, dk)
    rb, kb, Gb = blk(rows), blk(keys), blk(G)
    # Across sub-chunks, through each row block's first position r:
    # e^(G_i - G_r) <= 1 for i in the block, e^(G_r - G_j) <= 1 for j before
    # it (later j are masked; the clamp keeps their exp finite).
    Gr = Gb[..., :1, :]                                    # [..., n, 1, dk]
    left = rb * jnp.exp(Gb - Gr)                           # [..., n, sub, dk]
    right = keys[..., None, :, :] * jnp.exp(
        jnp.minimum(Gr - G[..., None, :, :], 0.0))         # [..., n, C, dk]
    off = jnp.einsum("...nik,...njk->...nij", left, right, precision=_HI)
    col = jnp.arange(C)[None, None, :]
    first = (jnp.arange(n) * sub)[:, None, None]
    off = jnp.where(col < first, off, 0.0)                 # [..., n, sub, C]
    # Inside a sub-chunk, per pair.
    d = Gb[..., :, None, :] - Gb[..., None, :, :]          # [..., n, i, j, dk]
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    diag = (rb[..., :, None, :] * kb[..., None, :, :]
            * jnp.exp(jnp.where(tri[..., None], d, 0.0))).sum(-1)
    diag = jnp.where(tri, diag, 0.0)                       # [..., n, sub, sub]
    eye = jnp.eye(n, dtype=diag.dtype)
    full = off.reshape(*lead, n, sub, n, sub) + (
        diag[..., :, :, None, :] * eye[:, None, :, None])
    return full.reshape(*lead, C, C)


def _unit_lower_inverse(A):
    """(I + A)^-1 for A [..., C, C] strictly lower triangular, C a power of
    two, by block elimination from the diagonal up: with the inverses of
    the diagonal blocks of s positions in X (s = 1: the identity), those of
    2s are ``X - X A_s X``, A_s the lower-left quarter of every block of 2s
    ([[T1, 0], [-T2 A21 T1, T2]]). Whole [C, C] products under a mask, in
    full float32, no slice and no power of A (nothing cancels that forward
    substitution would not cancel): five levels of two products of 64 rows
    at C = 64. A loop of ONE body (two matmuls), not
    unrolled: at ``highest`` precision a matmul is six passes, and thirteen
    of them a copy of this function were most of a prefill program's
    code."""
    C = A.shape[-1]
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    mm = lambda x, y: jnp.matmul(x, y, precision=_HI)

    def quarter(l):
        return jnp.where((i >> (l + 1) == j >> (l + 1)) & ((i >> l) & 1 == 1)
                         & ((j >> l) & 1 == 0), A, 0.0)

    def couple(l, X):
        return X - mm(X, mm(quarter(l), X))

    return jax.lax.fori_loop(1, C.bit_length() - 1, couple,
                             jnp.eye(C, dtype=A.dtype) - quarter(0))


def kda_chunked(q, k, v, g, b, state: Optional[jax.Array] = None,
                lengths: Optional[jax.Array] = None, chunk: int = CHUNK,
                sub: int = SUB, segment: int = SEGMENT):
    """As ``kda_recurrent``, by chunks (module docstring). S is padded to
    whole chunks; positions at and behind ``lengths`` (and the padding)
    write nothing and decay nothing, so the state handed back is the one
    behind each row's last real position. What a chunk needs beside the
    state (the pair scores, the inverse) is made for about ``segment``
    positions of the block at a time, an outer scan over the sequence, so
    that a block of 8192 positions keeps a part of it alive and not all.
    The four child scopes of ``kda/chunk`` are its tracing
    (``tools/kda_prefill_sweep.py`` times each alone)."""
    f32 = jnp.float32
    q, k, v, g, b = (x.astype(f32) for x in (q, k, v, g, b))
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    if state is None:
        state = jnp.zeros((B, H, dk, dv), f32)
    live = (jnp.ones((B, S), bool) if lengths is None
            else jnp.arange(S)[None, :] < lengths[:, None])
    g = jnp.where(live[..., None, None], g, 0.0)
    b = jnp.where(live[..., None], b, 0.0)
    m = max(1, segment // (chunk * B))          # chunks a segment
    m = min(m, -(-S // chunk))
    pad = -S % (chunk * m)
    if pad:
        q, k, v, g, b = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                         for x in (q, k, v, g, b))
    n = (S + pad) // chunk

    def chunks(x):     # [B, S, H, ...] -> [n / m, m, B, H, C, ...]
        x = x.reshape(B, n // m, m, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 4, 3), 0, 2)

    def one_segment(state, xs):
        qc, kc, vc, gc, bc = xs                            # [m, B, H, C, ..]
        G = jnp.cumsum(gc, axis=-2)
        with jax.named_scope("kda/chunk/scores"):
            bj = bc[..., None, :]                          # over columns j
            A = _pair_scores(kc, kc, G, sub)
            A = A * bj * jnp.tril(jnp.ones((chunk, chunk), f32), -1)
            Bm = _pair_scores(qc, kc, G, sub) * bj
        with jax.named_scope("kda/chunk/inverse"):
            T = _unit_lower_inverse(A)
        with jax.named_scope("kda/chunk/apply"):
            eG = jnp.exp(G)
            tv = jnp.matmul(T, vc, precision=_HI)          # [.., C, dv]
            tk = jnp.matmul(T, kc * eG, precision=_HI)     # [.., C, dk]
            GC = G[..., -1:, :]                            # [.., 1, dk]
            kd = kc * jnp.exp(GC - G) * bc[..., None]      # [.., C, dk]
            decay = jnp.exp(GC)                            # [.., 1, dk]

        def step(st, ys):
            # ``st`` is the state VALUE-major, S^T [B, H, dv, dk], as the
            # cache keeps a slot's row: what a prefill writes there comes
            # out of these matmuls as it is stored, with no transposition
            # (one in front of a write under a layer scan made the compiler
            # re-lay the whole leaf around the loop).
            tv_, tk_, qg_, bm_, kd_, dec_ = ys
            u = tv_ - jnp.einsum("bhck,bhvk->bhcv", tk_, st, precision=_HI)
            o = (jnp.einsum("bhck,bhvk->bhcv", qg_, st, precision=_HI)
                 + jnp.matmul(bm_, u, precision=_HI))
            st = dec_ * st + jnp.einsum(
                "bhcv,bhck->bhvk", u, kd_, precision=_HI)
            return st, o

        with jax.named_scope("kda/chunk/carry"):
            return jax.lax.scan(step, state, (tv, tk, qc * eG, Bm, kd, decay))

    with jax.named_scope("kda/chunk"):
        state, o = jax.lax.scan(
            one_segment, jnp.swapaxes(state.astype(f32), -1, -2),
            tuple(chunks(x) for x in (q, k, v, g, b)))
    state = jnp.swapaxes(state, -1, -2)
    # [n / m, m, B, H, C, dv] -> [B, S, H, dv]
    o = jnp.moveaxis(o.reshape(n, B, H, chunk, dv), 1, 0)
    o = jnp.moveaxis(o, 3, 2)
    return o.reshape(B, n * chunk, H, dv)[:, :S], state
