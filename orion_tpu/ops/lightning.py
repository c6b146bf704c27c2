"""Lightning attention: linear attention under a FIXED scalar decay a head.

A head keeps a state ``M`` [d_v, d_k] (value-major, as ``ops/pallas/kda.py``
keeps its own: a key and a query are then rows that broadcast over
sublanes). A position t brings a query (already scaled), a key and a value;
with ``lambda_h = exp(-s_h)`` and ``s_h = 2^(-8 (h + 1) / heads)`` (ALiBi's
slopes, the same in every layer):

    M_t = lambda_h M_{t-1} + v_t k_t^T          o_t = M_t q_t

No delta rule, no convolution, no learned gate: what sets it apart from
``ops/kda.py`` (a per-channel learned decay under an erase) and from
``ops/retention.py`` (a feature map, a learned scalar gate, a paged tail).

Three forms, the state float32 in all of them:

``lightning_recurrent``  the recurrence itself, ``lax.scan`` over positions:
    the oracle of the tests. Not on the served path.
``lightning_chunked``    prefill and training: over chunks of ``CHUNK``
    positions, inside a chunk the decayed, causally masked ``q k^T`` against
    ``v``, between chunks the state. It TAKES a state and returns one, so a
    prompt that enters in chunks resumes from the row the last chunk left.
    Positions at or past a row's ``lengths`` neither write nor decay: their
    log-decay is 0 and their key is 0, so the state handed back is the one
    after the row's last real position whatever the padding. Every decay is
    ``exp`` of a difference of cumulative logs that is <= 0.
``lightning_step``       one new position a slot (decode): the XLA form of
    ``ops/pallas/lightning.lightning_decode``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

CHUNK = 128
_HI = jax.lax.Precision.HIGHEST


def slopes(n_heads: int) -> jax.Array:
    """[heads] float32: ``s_h = 2^(-8 (h + 1) / heads)``."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / n_heads)


def lightning_recurrent(q, k, v, state=None):
    """q, k, v [B, S, N, H] -> (o [B, S, N, H] float32, M [B, N, H, H])."""
    f32 = jnp.float32
    B, S, N, H = q.shape
    lam = jnp.exp(-slopes(N))[None, :, None, None]
    M0 = jnp.zeros((B, N, v.shape[-1], H), f32) if state is None else state

    def step(M, x):
        qt, kt, vt = (a.astype(f32) for a in x)             # [B, N, H]
        M = lam * M + vt[..., :, None] * kt[..., None, :]
        return M, jnp.einsum("bnvk,bnk->bnv", M, qt, precision=_HI)

    M, o = jax.lax.scan(
        step, M0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    return jnp.moveaxis(o, 0, 1), M


def lightning_chunked(q, k, v, state: Optional[jax.Array] = None,
                      lengths: Optional[jax.Array] = None,
                      chunk: int = CHUNK):
    """q (scaled), k, v [B, S, N, H]; ``state`` [B, N, H, H] float32 (None:
    zeros); ``lengths`` [B] real positions a row (None: all) -> (o [B, S, N,
    H] float32, state' after each row's last real position)."""
    f32 = jnp.float32
    B, S, N, H = q.shape
    C = min(chunk, S)
    pad = -S % C
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    nc = (S + pad) // C
    real = jnp.arange(S + pad)[None] < (
        jnp.full((B,), S) if lengths is None else lengths)[:, None]  # [B, S']
    with jax.named_scope("lightning/chunk"):
        g = jnp.where(real[..., None], -slopes(N)[None, None], 0.0)  # [B,S',N]
        k = jnp.where(real[..., None, None], k, jnp.zeros((), k.dtype))
        qc, kc, vc = (a.reshape(B, nc, C, N, H) for a in (q, k, v))
        G = jnp.cumsum(g.reshape(B, nc, C, N), axis=2)               # <= 0
        # Inside a chunk: (q_i . k_j) e^(G_i - G_j) for j <= i, against v.
        tri = jnp.tril(jnp.ones((C, C), bool))
        D = jnp.where(tri[None, None, :, :, None],
                      G[:, :, :, None, :] - G[:, :, None, :, :], -jnp.inf)
        A = jnp.einsum("bcink,bcjnk->bcijn", qc, kc,
                       preferred_element_type=f32) * jnp.exp(D)
        o = jnp.einsum("bcijn,bcjnv->bcinv", A.astype(v.dtype), vc,
                       preferred_element_type=f32)
        # Between chunks: what a chunk adds to the state, and its decay.
        last = G[:, :, -1:, :]                                       # [B,nc,1,N]
        kd = kc.astype(f32) * jnp.exp(last - G)[..., None]
        U = jnp.einsum("bcjnv,bcjnk->bcnvk", vc.astype(f32), kd,
                       precision=_HI)                                # [B,nc,N,H,H]
        a = jnp.exp(last[:, :, 0, :])                                # [B, nc, N]
        M0 = (jnp.zeros((B, N, H, H), f32) if state is None
              else state.astype(f32))

        def carry(M, x):
            a_c, U_c = x
            return a_c[..., None, None] * M + U_c, M

        M, before = jax.lax.scan(
            carry, M0, (jnp.moveaxis(a, 1, 0), jnp.moveaxis(U, 1, 0)))
        before = jnp.moveaxis(before, 0, 1)                          # [B,nc,..]
        o = o + jnp.einsum(
            "bcink,bcnvk->bcinv", qc.astype(f32) * jnp.exp(G)[..., None],
            before, precision=_HI)
    return o.reshape(B, nc * C, N, H)[:, :S], M


def lightning_step(state, q, k, v, active=None):
    """One position a slot. ``state`` [B, N, H, H] float32; q (scaled), k, v
    [B, N, H]; ``active`` [B] bool (None: all) -> (o [B, N, H] float32,
    state'): a dead slot's row is handed back as it came."""
    f32 = jnp.float32
    q, k, v = (a.astype(f32) for a in (q, k, v))
    lam = jnp.exp(-slopes(q.shape[1]))[None, :, None, None]
    new = lam * state + v[..., :, None] * k[..., None, :]
    if active is not None:
        new = jnp.where(active[:, None, None, None], new, state)
    return jnp.einsum("bnvk,bnk->bnv", new, q, precision=_HI), new
