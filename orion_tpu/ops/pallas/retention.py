"""Power retention as Pallas TPU kernels (``ops/retention.py`` has the
operator, its equations and the layout of ``phi``; this file is held to it).

Three kernels, each under ``jax.jit`` with its statics static and its loops
as ``fori_loop``, so that a process traces each distinct kernel once:

  - ``retention_prefill``: whole sequences by chunks. Grid (row, K/V head,
    chunk), the chunk axis in order. A step attends its chunk quadratically
    (query blocks of ``BLOCK_Q`` rows, one K/V head's group of query heads),
    reads the state of the chunks before it a slab of ``phi`` at a time,
    built in VMEM from a lane rotation and never written to HBM, and adds a
    complete chunk to the state, which lives in the output block for the
    whole row and is written to HBM once.
  - ``retention_decode``: one new token a slot. Grid (slot, block of state
    slabs): the slot's state row streams through in blocks while the live
    tail pages (``state_len`` to the newest position) are copied from the
    HBM pools one async copy a page, as the paged attention kernel walks
    them; the new token's K and V are merged into the page that takes them
    in VMEM and that page alone is copied back (pools aliased in and out).
    It never writes the state.
  - ``retention_fold``: one complete tail chunk of one slot into its state
    row, in place (state aliased in and out), a block of slabs a step.

Products run on the MXU in the inputs' dtype with float32 accumulation;
scores, decays, the state's accumulation and every sum are float32.
Inference-only: training differentiates the XLA form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas.common import resolve_interpret, round_up
from orion_tpu.ops.retention import BIG, SQRT2, n_slabs

LANES = 128
BLOCK_Q = 256
VMEM_LIMIT_BYTES = 96 * 2 ** 20


def _phi_slab(x, r, R: int):
    """Slab r of phi of the rows of x [M, H] (f32): one lane rotation, two
    multiplies. ``r`` may be traced."""
    w = jnp.where((r == 0) | (r == R - 1), 1.0, SQRT2).astype(jnp.float32)
    return x * pltpu.roll(x, r, 1) * w


def _slab_block(R: int, limit: int) -> int:
    """The largest divisor of R that is at most ``limit``."""
    return max(d for d in range(1, limit + 1) if R % d == 0)


# -- prefill -------------------------------------------------------------------


def _prefill_kernel(C, G, R, bq, len_ref, q_ref, k_ref, v_ref, br_ref,
                    bc_ref, y_ref, s_ref, z_ref):
    b, c = pl.program_id(0), pl.program_id(2)
    H = k_ref.shape[-1]
    n_valid = len_ref[b] - c * C
    cdt = k_ref.dtype

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)
        z_ref[...] = jnp.zeros(z_ref.shape, z_ref.dtype)

    @pl.when(n_valid <= 0)
    def _padding():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(n_valid > 0)
    def _chunk():
        kk = k_ref[0, 0]                                     # [C, H]
        vv = v_ref[0, 0]
        brow = br_ref[0, 0]                                  # [1, C]
        col = lax.broadcasted_iota(jnp.int32, (bq, C), 1)

        def q_block(qi, carry):
            at = pl.ds(pl.multiple_of(qi * bq, bq), bq)
            bcol = bc_ref[0, 0, at, :]                       # [bq, 1]
            row = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, C), 0)
            live = (col <= row) & (col < n_valid)
            decay = jnp.exp(jnp.where(live, bcol - brow, -BIG))
            dq = jnp.exp(bcol) * (1.0 / H)
            for g in range(G):
                qg = q_ref[0, 0, g, at, :]                   # [bq, H]
                s = lax.dot_general(
                    qg, kk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * (H ** -0.5)
                a = s * s * decay
                num = jnp.dot(a.astype(cdt), vv,
                              preferred_element_type=jnp.float32)
                den = a.sum(axis=-1, keepdims=True)

                def from_state(qg=qg):
                    qf = qg.astype(jnp.float32)

                    def slab(r, acc):
                        ns, ds = acc
                        ph = _phi_slab(qf, r, R)
                        ns = ns + jnp.dot(
                            ph.astype(cdt), s_ref[0, 0, r].astype(cdt),
                            preferred_element_type=jnp.float32)
                        ds = ds + (ph * z_ref[0, 0, r][:1]).sum(
                            axis=-1, keepdims=True)
                        return ns, ds

                    return lax.fori_loop(
                        0, R, slab, (jnp.zeros((bq, H), jnp.float32),
                                     jnp.zeros((bq, 1), jnp.float32)))

                ns, ds = lax.cond(       # the first chunk has none to read
                    c > 0, from_state,
                    lambda: (jnp.zeros((bq, H), jnp.float32),
                             jnp.zeros((bq, 1), jnp.float32)))
                num = num + dq * ns
                den = den + dq * ds
                y = num / jnp.where(den == 0.0, 1.0, den)
                real = row[:, :1] < n_valid
                y_ref[0, 0, g, at, :] = jnp.where(real, y, 0.0).astype(
                    y_ref.dtype)
            return carry

        lax.fori_loop(0, C // bq, q_block, 0)

        @pl.when(n_valid >= C)
        def _update():
            # The chunk's whole sum is its last entry, and (a log-gate is
            # never positive) its least: a reduction, where a slice at lane
            # C - 1 is a layout Mosaic does not broadcast from.
            total = jnp.min(bc_ref[0, 0], axis=0, keepdims=True)   # [1, 1]
            dk = jnp.exp(total - bc_ref[0, 0])               # [C, 1]
            eB = jnp.exp(total)
            kf = kk.astype(jnp.float32)
            vd = (vv.astype(jnp.float32) * dk).astype(cdt)

            def slab(r, carry):
                ph = _phi_slab(kf, r, R)                     # [C, H]
                upd = jnp.dot(ph.T.astype(cdt), vd,
                              preferred_element_type=jnp.float32)
                s_ref[0, 0, r] = eB * s_ref[0, 0, r] + upd
                zs = (ph * dk).sum(axis=0, keepdims=True)    # [1, H]
                z_ref[0, 0, r] = eB * z_ref[0, 0, r] + zs
                return carry

            lax.fori_loop(0, R, slab, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "name"))
def _prefill_call(q, k, v, b, lengths, *, chunk, interpret, name):
    B, S, N, H = q.shape
    K = k.shape[2]
    G, C, R = N // K, chunk, n_slabs(H)
    pad = -S % C
    nC = (S + pad) // C
    bq = min(BLOCK_Q, C)
    if C % bq:
        raise ValueError(f"retention chunk {C} is not a multiple of {bq}")

    def rows(x):            # [B, S, ...] -> [B, ..., S + pad] heads first
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

    qg = rows(q).reshape(B, S + pad, K, G, H).transpose(0, 2, 3, 1, 4)
    kt = rows(k).transpose(0, 2, 1, 3)
    vt = rows(v).transpose(0, 2, 1, 3)
    bt = rows(b.astype(jnp.float32)).transpose(0, 2, 1)      # [B, K, S]
    st_spec = pl.BlockSpec((1, 1, R, H, H), lambda i, j, c, *_: (i, j, 0, 0, 0))
    z_spec = pl.BlockSpec((1, 1, R, 8, H), lambda i, j, c, *_: (i, j, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, C, H), lambda i, j, c, *_: (i, j, c, 0))
    in_specs = [
        pl.BlockSpec((1, 1, G, C, H), lambda i, j, c, *_: (i, j, 0, c, 0)),
        kv_spec, kv_spec,
        pl.BlockSpec((1, 1, 1, C), lambda i, j, c, *_: (i, j, 0, c)),
        pl.BlockSpec((1, 1, C, 1), lambda i, j, c, *_: (i, j, c, 0)),
    ]
    y, S1, z1 = pl.pallas_call(
        functools.partial(_prefill_kernel, C, G, R, bq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, K, nC), in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, G, C, H),
                             lambda i, j, c, *_: (i, j, 0, c, 0)),
                st_spec, z_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, K, G, S + pad, H), q.dtype),
            jax.ShapeDtypeStruct((B, K, R, H, H), jnp.float32),
            jax.ShapeDtypeStruct((B, K, R, 8, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name=name,
    )(lengths.astype(jnp.int32), qg, kt, vt, bt[:, :, None, :], bt[..., None])
    y = y.transpose(0, 3, 1, 2, 4).reshape(B, S + pad, N, H)[:, :S]
    return y, (S1, z1[:, :, :, 0])


def retention_prefill(q, k, v, b, *, lengths=None, chunk, interpret=False,
                      name="retention_prefill"):
    """``ops.retention.power_retention`` with ``b`` the per-chunk cumulative
    log-gates [B, S, K] (``chunk_cumsum``). -> (y, (S, z)) in float32."""
    if lengths is None:
        lengths = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    return _prefill_call(q, k, v, b, lengths, chunk=chunk,
                         interpret=interpret, name=name)


# -- decode --------------------------------------------------------------------


def _decode_kernel(psz, P, nT, rb, R,
                   pt_ref, base_ref, sl_ref, pos_ref,
                   q_ref, cq_ref, ct_ref, kp_in, vp_in, st_ref, z_ref,
                   kn_ref, vn_ref,
                   o_ref, kp_ref, vp_ref,
                   num_s, den_s, kbuf, vbuf, sems, wsems):
    # The pools are read through the OUTPUT refs (aliased), as the paged
    # attention kernel does: a page written earlier in the call reads back
    # the same here as under the interpreter.
    del kp_in, vp_in
    pools, bufs, new_refs = (kp_ref, vp_ref), (kbuf, vbuf), (kn_ref, vn_ref)
    b, d = pl.program_id(0), pl.program_id(1)
    nD = pl.num_programs(1)
    K, T, H = kbuf.shape
    G8 = q_ref.shape[1] // K
    cdt = kbuf.dtype
    F, pos = sl_ref[b], pos_ref[b]
    lo = F // psz
    hi = jnp.minimum(jnp.minimum(pos // psz, lo + nT - 1), P - 1)

    def walk(wait):
        def page(j, carry):
            row = base_ref[0] + pt_ref[b, lo + j]
            at = pl.ds(pl.multiple_of(j * psz, psz), psz)
            for s in range(2):
                cp = pltpu.make_async_copy(
                    pools[s].at[row], bufs[s].at[:, at, :], sems.at[s])
                if wait:
                    cp.wait()
                else:
                    cp.start()
            return carry

        lax.fori_loop(0, hi - lo + 1, page, 0)

    @pl.when(d == 0)
    def _start():
        @pl.when(b == 0)
        def _zero():
            for buf in bufs:
                buf[...] = jnp.zeros(buf.shape, buf.dtype)

        num_s[...] = jnp.zeros(num_s.shape, num_s.dtype)
        den_s[...] = jnp.zeros(den_s.shape, den_s.dtype)
        walk(wait=False)

    # This block of the state's slabs, every K/V head at once.
    q2 = q_ref[0].astype(jnp.float32)                        # [K*G8, H]
    num, den = num_s[...], den_s[...]
    for i in range(rb):
        r = d * rb + i
        ph = _phi_slab(q2, r, R)
        num = num + lax.dot_general(
            ph.reshape(K, G8, H).astype(cdt), st_ref[0, :, i].astype(cdt),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32).reshape(K * G8, H)
        zr = z_ref[0, r]                                     # [K, H]
        den = den + (ph.reshape(K, G8, H) * zr[:, None, :]).sum(
            axis=-1).reshape(K * G8, 1)
    num_s[...] = num
    den_s[...] = den

    @pl.when(d == nD - 1)
    def _tail():
        walk(wait=True)
        # Merge the new token into the page that takes it and copy that
        # page back.
        pg = pos // psz
        j = pg - lo

        @pl.when((pg <= hi) & (j >= 0))
        def _():
            row = base_ref[0] + pt_ref[b, pg]
            at = pl.ds(pl.multiple_of(j * psz, psz), psz)
            at_pos = pg * psz + lax.broadcasted_iota(
                jnp.int32, (1, psz, 1), 1)
            for s in range(2):
                new = new_refs[s][0, 0]                      # [K, H]
                bufs[s][:, at, :] = jnp.where(
                    at_pos == pos, new[:, None, :].astype(cdt),
                    bufs[s][:, at, :])
                cp = pltpu.make_async_copy(
                    bufs[s].at[:, at, :], pools[s].at[row], wsems.at[s])
                cp.start()
                cp.wait()

        q3 = q_ref[0].reshape(K, G8, H)
        s = lax.dot_general(
            q3.astype(cdt), kbuf[...], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * (H ** -0.5)    # [K,G8,T]
        cq = cq_ref[0].reshape(K, G8, 1)
        ct = ct_ref[0][:, None, :]                               # [K, 1, T]
        live = F + lax.broadcasted_iota(jnp.int32, (K, G8, T), 2) <= pos
        a = jnp.where(live, s * s * jnp.exp(jnp.minimum(cq - ct, 0.0)), 0.0)
        num_t = lax.dot_general(
            a.astype(cdt), vbuf[...], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                  # [K,G8,H]
        den_t = a.sum(axis=-1, keepdims=True)
        dq = jnp.exp(cq) * (1.0 / H)
        num = num_t + dq * num_s[...].reshape(K, G8, H)
        den = den_t + dq * den_s[...][:, :1].reshape(K, G8, 1)
        y = num / jnp.where(den == 0.0, 1.0, den)
        o_ref[0] = y.reshape(K * G8, H).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "name", "rb"))
def _decode_call(q, k_pool, v_pool, state, state_z, page_table, state_len,
                 pos, c_q, c_tail, k_new, v_new, base, *, interpret, name,
                 rb):
    B, N, H = q.shape
    _, K, psz, _ = k_pool.shape
    P = page_table.shape[1]
    G = N // K
    G8 = max(round_up(G, 8), 8)
    T = c_tail.shape[-1]
    nT = T // psz
    R = state.shape[2]

    def pack(x):            # [B, K, G, ...] -> [B, K * G8, ...]
        x = jnp.pad(x, ((0, 0), (0, 0), (0, G8 - G))
                    + ((0, 0),) * (x.ndim - 3))
        return x.reshape(B, K * G8, *x.shape[3:])

    qg = pack(q.reshape(B, K, G, H))
    cq = pack(jnp.broadcast_to(
        c_q.astype(jnp.float32)[..., None, None], (B, K, G, 1)))
    prefetch = [page_table.astype(jnp.int32), base.astype(jnp.int32),
                state_len.astype(jnp.int32), pos.astype(jnp.int32)]
    q_spec = pl.BlockSpec((1, K * G8, H), lambda b, d, *_: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    new_spec = pl.BlockSpec((1, 1, K, H), lambda b, d, *_: (b, 0, 0, 0))
    in_specs = [
        q_spec,
        pl.BlockSpec((1, K * G8, 1), lambda b, d, *_: (b, 0, 0)),
        pl.BlockSpec((1, K, T), lambda b, d, *_: (b, 0, 0)),
        hbm, hbm,
        pl.BlockSpec((1, K, rb, H, H),
                     lambda b, d, pt, base, *_: (base[1] + 1 + b, 0, d, 0, 0)),
        pl.BlockSpec((1, R, K, H),
                     lambda b, d, pt, base, *_: (base[1] + 1 + b, 0, 0, 0)),
        new_spec, new_spec,
    ]
    out = pl.pallas_call(
        functools.partial(_decode_kernel, psz, P, nT, rb, R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(B, R // rb),
            in_specs=in_specs, out_specs=[q_spec, hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((K * G8, H), jnp.float32),
                pltpu.VMEM((K * G8, LANES), jnp.float32),
                pltpu.VMEM((K, T, H), k_pool.dtype),
                pltpu.VMEM((K, T, H), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, K * G8, H), q.dtype),
                   jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # Operand indices count the scalar-prefetch arguments.
        input_output_aliases={len(prefetch) + 3: 1, len(prefetch) + 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name=name,
    )(*prefetch, qg, cq, c_tail.astype(jnp.float32), k_pool, v_pool, state,
      state_z, k_new[:, None], v_new[:, None])
    y = out[0].reshape(B, K, G8, H)[:, :, :G]
    return y.reshape(B, N, H), out[1], out[2]


def retention_decode(q, k_new, v_new, c_q, c_tail, k_pool, v_pool, state,
                     state_z, page_table, state_len, pos, *, layer_base,
                     state_base, interpret=False, name="retention_decode"):
    """One new token a slot over the slot's state row and its paged tail
    (the decode window's step; nothing verifies drafts on such a model yet).

    q [B, N, H]; k_new / v_new [B, K, H], written at position ``pos`` [B]
    of the pools [L * pages, K, page, H] through ``page_table`` [B, P]
    (absolute position // page; rows before the tail may point anywhere);
    ``state`` [L * (B + 1), K, R, H, H] and ``state_z`` [L * (B + 1), R, K,
    H], slot b's row ``state_base + 1 + b``, holding positions below
    ``state_len`` [B]; ``c_q`` [B, K] the new token's log-decay since
    ``state_len`` (inclusive), ``c_tail`` [B, K, T] that of the T tail
    positions from ``state_len`` on, ``BIG`` where there is none.
    -> (y [B, N, H], k_pool', v_pool')."""
    base = jnp.stack([jnp.asarray(layer_base, jnp.int32),
                      jnp.asarray(state_base, jnp.int32)])
    R = state.shape[2]
    return _decode_call(q, k_pool, v_pool, state, state_z, page_table,
                        state_len, pos, c_q, c_tail, k_new, v_new, base,
                        interpret=interpret, name=name,
                        rb=_slab_block(R, 13))


# -- fold ----------------------------------------------------------------------


def _fold_kernel(C, R, rb, row_ref, k_ref, v_ref, bc_ref, st_in, z_in,
                 st_ref, z_ref):
    del row_ref
    d = pl.program_id(0)
    K, _, H = k_ref.shape
    cdt = k_ref.dtype
    bcol = bc_ref[...]                                       # [K, C, 1]
    # The chunk's whole sum: its last entry and, a log-gate never being
    # positive, its least (see the prefill kernel).
    total = jnp.min(bcol, axis=1, keepdims=True)             # [K, 1, 1]
    dk = jnp.exp(total - bcol)
    eB = jnp.exp(total)
    kf = k_ref[...].astype(jnp.float32).reshape(K * C, H)
    vd = (v_ref[...].astype(jnp.float32) * dk).astype(cdt)   # [K, C, H]
    for i in range(rb):
        ph = _phi_slab(kf, d * rb + i, R).reshape(K, C, H)
        for kh in range(K):
            upd = jnp.dot(ph[kh].T.astype(cdt), vd[kh],
                          preferred_element_type=jnp.float32)
            st_ref[0, kh, i] = (
                eB[kh] * st_in[0, kh, i].astype(jnp.float32) + upd
            ).astype(st_ref.dtype)
        z_ref[0, i] = eB[:, 0] * z_in[0, i] + (ph * dk).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("interpret", "name", "rb"))
def _fold_call(state, state_z, kc, vc, b, row, *, interpret, name, rb):
    _, K, R, H, _ = state.shape
    C = kc.shape[1]
    whole = lambda shape: pl.BlockSpec(       # noqa: E731
        shape, lambda d, *_: (0,) * len(shape))
    st_spec = pl.BlockSpec((1, K, rb, H, H),
                           lambda d, row: (row[0], 0, d, 0, 0))
    z_spec = pl.BlockSpec((1, rb, K, H), lambda d, row: (row[0], d, 0, 0))
    return pl.pallas_call(
        functools.partial(_fold_kernel, C, R, rb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R // rb,),
            in_specs=[whole((K, C, H)), whole((K, C, H)), whole((K, C, 1)),
                      st_spec, z_spec],
            out_specs=[st_spec, z_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(state_z.shape, state_z.dtype)],
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name=name,
    )(row.astype(jnp.int32).reshape(1), kc, vc,
      b.astype(jnp.float32)[..., None], state, state_z)


def retention_fold(state, state_z, kc, vc, b, row, *, interpret=False,
                   name="retention_fold"):
    """``ops.retention.retention_fold_xla`` in place: one complete chunk
    (kc / vc [K, C, H], cumulative log-gates b [K, C]) into flat row
    ``row`` of ``state`` / ``state_z``."""
    R = state.shape[2]
    return _fold_call(state, state_z, kc, vc, b, jnp.asarray(row),
                      interpret=interpret, name=name, rb=_slab_block(R, 5))
