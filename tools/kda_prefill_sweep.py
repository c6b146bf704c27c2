"""Time the chunked delta rule (``ops/kda.kda_chunked``) alone on the chip at
the prefill shapes of the Ling cell (rows x positions, 32 heads of 128 x 128)
against what the recurrence's operations allow at the chip's bf16 peak
(``benchmarks/metrics/kda.prefill_flops``: the number
``kda_prefill_roofline.reason128`` reads), whole and by part. ``ops/kda.py``'s
grouping of the chunk's products and its constants come from this script's
table (PERF.md section 6, PR 48); the form that lost is kept HERE for the
comparison and nowhere else.

    chiprun -- python tools/kda_prefill_sweep.py [--only old,tree,...]

``tree`` is this checkout's ``kda_chunked``; ``seg<N>`` and ``chunk<N>`` are
it at another ``SEGMENT`` and ``CHUNK``. ``old`` is the form as PR 41 wrote it: the tree's pair scores, the
inverse by doubling on 16 x 16 blocks and forward substitution over four row
blocks, ``SEGMENT`` 2048. The others are the tree with ONE thing changed
(``FORMS``; ``<name>.seg<N>`` at another ``SEGMENT``): ``inverse_old``;
``read_once`` (``T V`` and ``T K`` one product, a step of the carry reading the
state once); pair scores on ``[k; q]`` stacked: ``stacked16`` (a row block's
product of 32 rows), ``levels<sub>`` (by midpoint levels, a ``[128, 128] x
[128, 64]`` product a level), ``ends16`` (every pair of blocks in one ``[192,
128] x [128, 64]`` product).

Per variant and shape: seven calls chained in one program (each call's values
take the one before's outputs), best of four, on gates drawn near 1
(``-0.02 sigmoid``) and at the -5 bound (one program, two draws); ms a call,
the share of the roofline (of the faster draw),
the program's temporary bytes, and the largest difference of outputs and state
from ``tree``'s, relative to their largest entry. Then each part alone
(``scores``, ``inverse``, ``apply``, ``carry``: the child scopes of
``kda/chunk``) on the operands of 2048 positions at once, every form's; alone a
part also reads its operands from memory and writes its results there, which
inside the program the next part's fusions may not.

Prints one JSON line a measurement and keeps them in
``chiprun_out/kda_prefill_sweep.jsonl``. Raises without a TPU; ``--cpu`` runs
every variant at tiny shapes for the comparison alone and prints no time."""

from __future__ import annotations

import functools
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.metrics.kda import prefill_flops  # noqa: E402
from orion_tpu.ops import kda  # noqa: E402

HEADS, D = 32, 128
LAYERS = 7
PEAK_FLOPS = 197e12             # benchmarks/harness/device.PEAKS, bf16
OUT = "chiprun_out/kda_prefill_sweep.jsonl"
SHAPES = [(1, 64), (1, 128), (1, 256), (1, 512), (1, 1024), (1, 2048),
          (1, 4096), (1, 8192), (2, 4096), (4, 2048), (8, 1024), (16, 512)]
# the shapes the other variants are timed at: one row of one to eight
# buckets, and the four blocks of 8192 positions
FEW = [(1, 1024), (1, 2048), (1, 8192), (2, 4096), (4, 2048), (8, 1024),
       (16, 512)]
GATES = {"near1": -0.02, "bound": -5.0}
_HI = jax.lax.Precision.HIGHEST


def _mm(x, y):
    return jnp.matmul(x, y, precision=_HI)


# -- what lost, and the inverse before PR 48 -------------------------------------


def stacked_pair_scores(rows, keys, G, sub):
    """Neutral (PR 48): the module's pair scores on ``[k; q]`` stacked (rows
    [..., 2 C, d_k]): a row block's product carries both sets of rows (32
    rows where there are two of 16) and the keys' side is made once; inside
    a block a set of rows at a time. The product is half the time and the
    re-laying of its operand and result takes it back: faster at three of
    the five shapes of 8192 positions, slower at two, by 0.6 ms each way."""
    *lead, R, dk = rows.shape
    C = keys.shape[-2]
    P, n = R // C, C // sub
    rb = rows.reshape(*lead, P, n, sub, dk)
    kb, Gb = (x.reshape(*lead, n, sub, dk) for x in (keys, G))
    Gr = Gb[..., :1, :]                                    # [.., n, 1, dk]
    left = jnp.moveaxis(rb * jnp.exp(Gb - Gr)[..., None, :, :, :], -4, -3)
    right = keys[..., None, :, :] * jnp.exp(
        jnp.minimum(Gr - G[..., None, :, :], 0.0))         # [.., n, C, dk]
    off = jnp.einsum("...nik,...njk->...nij",
                     left.reshape(*lead, n, P * sub, dk), right, precision=_HI)
    col = jnp.arange(C)[None, None, :]
    first = (jnp.arange(n) * sub)[:, None, None]
    off = jnp.where(col < first, off, 0.0)                 # [.., n, P sub, C]
    off = jnp.moveaxis(off.reshape(*lead, n, P, sub, n, sub), -4, -5)
    # Inside a sub-chunk, per pair; a set of rows at a time, so that each
    # reduction computes its exponentials where it uses them (one tensor of
    # them for both sets is written out and read back: 134 M numbers a
    # segment of 2048 positions and 32 heads).
    d = Gb[..., :, None, :] - Gb[..., None, :, :]          # [.., n, i, j, dk]
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    diag = jnp.stack([
        (rb[..., p, :, :, None, :] * kb[..., None, :, :]
         * jnp.exp(jnp.where(tri[..., None], d, 0.0))).sum(-1)
        for p in range(P)], -4)
    diag = jnp.where(tri, diag, 0.0)                       # [.., P, n, sub, sub]
    eye = jnp.eye(n, dtype=diag.dtype)
    full = off + diag[..., :, :, None, :] * eye[:, None, :, None]
    return full.reshape(*lead, R, C)


def old_unit_lower_inverse(A, sub):
    *lead, C, _ = A.shape
    n = C // sub
    eye = jnp.eye(sub, dtype=A.dtype)
    blocks = A.reshape(*lead, n, sub, n, sub)
    N = -jnp.einsum("...isjt,ij->...ist", blocks, jnp.eye(n, dtype=A.dtype))

    def double(_, TP):
        T, P = TP
        P = _mm(P, P)
        return _mm(T, eye + P), P

    T, _ = jax.lax.fori_loop(
        0, max(sub.bit_length() - 2, 0), double, (eye + N, N))

    def row_block(i, X):
        at = (0,) * len(lead)
        a = jax.lax.dynamic_slice(A, (*at, i * sub, 0), (*lead, sub, C))
        e = (jnp.arange(C)[None, :] == i * sub + jnp.arange(sub)[:, None]
             ).astype(A.dtype) - _mm(a, X)
        t = jax.lax.dynamic_index_in_dim(T, i, axis=len(lead), keepdims=False)
        return jax.lax.dynamic_update_slice(X, _mm(t, e), (*at, i * sub, 0))

    return jax.lax.fori_loop(0, n, row_block, jnp.zeros_like(A))


def stacked(pair_scores, sub):
    """``A`` and ``B`` from ONE call on ``[k; q]``."""
    C = kda.CHUNK

    def scores(qc, kc, G, bc):
        AB = pair_scores(jnp.concatenate([kc, qc], -2), kc, G, sub)
        AB = AB * bc[..., None, :]
        return (AB[..., :C, :] * jnp.tril(jnp.ones((C, C), jnp.float32), -1),
                AB[..., C:, :])

    return scores


def twice(pair_scores, sub):
    """``A`` and ``B`` from a call each, as PR 41 wrote it."""
    C = kda.CHUNK

    def scores(qc, kc, G, bc):
        bj = bc[..., None, :]
        A = pair_scores(kc, kc, G, sub) * bj * jnp.tril(
            jnp.ones((C, C), jnp.float32), -1)
        return A, pair_scores(qc, kc, G, sub) * bj

    return scores


def levels_pair_scores(rows, keys, G, sub):
    """Lost (PR 48): a pair that shares a block of 2s positions and not one
    of s goes through that block's midpoint (``e^(G_i - G_mid)`` on the row,
    ``e^(G_mid - G_j)`` on the key), s = C / 2 ... ``sub``: one ``[R, d_k] x
    [d_k, C]`` product a level, the levels a batch dimension, each masked to
    its pairs; inside a block of ``sub`` per pair (``sub`` 1: the diagonal
    alone, a row sum). The level operands are written out and read back."""
    *lead, R, dk = rows.shape
    C = keys.shape[-2]
    rows = rows.reshape(*lead, R // C, C, dk)
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    sizes = [C >> l for l in range(1, C.bit_length()) if C >> l >= sub]
    if sizes:
        # |G - G_mid| is G_mid - G in front of the midpoint (the key's
        # side) and G - G_mid at and behind it (the row's side).
        E = jnp.exp(-jnp.abs(jnp.stack([
            (G.reshape(*lead, C // (2 * s), 2 * s, dk)
             - G.reshape(*lead, C // (2 * s), 2 * s, dk)[..., s:s + 1, :]
             ).reshape(*lead, C, dk) for s in sizes], -3)))  # [.., L, C, dk]
        left = (rows[..., None, :, :, :] * E[..., None, :, :]).reshape(
            *lead, len(sizes), R, dk)
        by_level = jnp.einsum("...lik,...ljk->...lij", left,
                              keys[..., None, :, :] * E, precision=_HI)
        pairs = jnp.stack([(i // (2 * s) == j // (2 * s)) & (i % (2 * s) >= s)
                           & (j % (2 * s) < s) for s in sizes])
        out = jnp.where(pairs[:, None], by_level.reshape(
            *lead, len(sizes), R // C, C, C), 0.0).sum(-4)
    else:
        out = jnp.zeros((*lead, R // C, C, C), rows.dtype)
    n = C // sub
    if sub == 1:
        diag = (rows * keys[..., None, :, :]).sum(-1)[..., None, None]
    else:
        rb = rows.reshape(*lead, R // C, n, sub, dk)
        kb, Gb = (x.reshape(*lead, 1, n, sub, dk) for x in (keys, G))
        d = Gb[..., :, None, :] - Gb[..., None, :, :]      # [.., n, i, j, dk]
        tri = jnp.tril(jnp.ones((sub, sub), bool))
        diag = (rb[..., :, None, :] * kb[..., None, :, :]
                * jnp.exp(jnp.where(tri[..., None], d, 0.0))).sum(-1)
        diag = jnp.where(tri, diag, 0.0)                   # [.., n, sub, sub]
    eye = jnp.eye(n, dtype=out.dtype)
    out = out.reshape(*lead, R // C, n, sub, n, sub) + (
        diag[..., :, :, None, :] * eye[:, None, :, None])
    return out.reshape(*lead, R, C)




def ends_pair_scores(rows, keys, G, sub):
    """Lost (PR 48): a pair of blocks (I, J < I) of ``sub`` positions goes
    through the LAST position of the key block (one scaling of the keys for
    every row block, a variant of block I's rows a J): all variants of all
    stacked rows are ONE ``[P sub n (n - 1) / 2, d_k] x [d_k, C]`` product a
    chunk and head, and the blocks are cut out of it and put in place.
    Inside a block per pair, both sets of rows at once (one tensor of
    exponentials, written out: the other thing that lost)."""
    *lead, R, dk = rows.shape
    C = keys.shape[-2]
    P, n = R // C, C // sub
    rb = rows.reshape(*lead, P, n, sub, dk)
    kb, Gb = (x.reshape(*lead, n, sub, dk) for x in (keys, G))
    Ge = Gb[..., -1:, :]                                   # [.., n, 1, dk]
    right = (kb * jnp.exp(Ge - Gb)).reshape(*lead, C, dk)
    pairs = [(I, J) for I in range(n) for J in range(I)]
    left = jnp.concatenate(
        [rb[..., I, :, :] * jnp.exp(Gb[..., None, I, :, :]
                                    - Ge[..., None, J, :, :])
         for I, J in pairs], -2)                           # [.., P, T sub, dk]
    off = jnp.einsum("...pik,...jk->...pij", left, right, precision=_HI)
    block = {(I, J): off[..., t * sub:(t + 1) * sub, J * sub:(J + 1) * sub]
             for t, (I, J) in enumerate(pairs)}
    d = Gb[..., :, None, :] - Gb[..., None, :, :]          # [.., n, i, j, dk]
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    diag = (rb[..., :, None, :] * kb[..., None, :, None, :, :]
            * jnp.exp(jnp.where(tri[..., None], d, 0.0))[..., None, :, :, :, :]
            ).sum(-1)
    diag = jnp.where(tri, diag, 0.0)                       # [.., P, n, i, j]
    zero = jnp.zeros_like(diag[..., 0, :, :])
    out = jnp.concatenate([jnp.concatenate(
        [block[I, J] if J < I else diag[..., I, :, :] if J == I else zero
         for J in range(n)], -1) for I in range(n)], -2)   # [.., P, C, C]
    return out.reshape(*lead, R, C)



def parts(scores="tree", inverse="tree", read_once=False) -> dict:
    """The four parts of a segment (``kda_chunked``'s child scopes), each a
    function of the chunked operands ``[m, B, H, C, ..]`` and of the part
    before. ``scores``: ``tree`` (the module's, a call for ``A`` and one
    for ``B``, as PR 41 wrote them), ``stacked<sub>``, ``levels<sub>``,
    ``ends<sub>`` (one call on ``[k; q]``); ``inverse``:
    ``tree`` or ``old``; ``read_once``: ``T V`` and ``T (K . e^G)`` one
    product and a step of the carry reading the state once for ``[T K; Q]``
    (lost: the concatenations cost what the products save)."""
    C = kda.CHUNK
    kinds = {"tree": lambda sub: twice(kda._pair_scores, sub),
             "stacked": lambda sub: stacked(stacked_pair_scores, sub),
             "levels": lambda sub: stacked(levels_pair_scores, sub),
             "ends": lambda sub: stacked(ends_pair_scores, sub)}
    kind = scores.rstrip("0123456789")
    sub = int(scores[len(kind):] or kda.SUB)

    def apply(T, qc, kc, vc, G, bc):
        eG = jnp.exp(G)
        GC = G[..., -1:, :]
        kd, decay = kc * jnp.exp(GC - G) * bc[..., None], jnp.exp(GC)
        if not read_once:
            return (_mm(T, vc), _mm(T, kc * eG), qc * eG, kd, decay)
        tvk = _mm(T, jnp.concatenate([vc, kc * eG], -1))
        dv = vc.shape[-1]
        return (tvk[..., :dv],
                jnp.concatenate([tvk[..., dv:], qc * eG], -2), kd, decay)

    def carry(state, tv, *rest):
        def step(st, ys):
            tv_, *kq_, bm_, kd_, dec_ = ys
            read = lambda x: jnp.einsum("bhck,bhvk->bhcv", x, st,
                                        precision=_HI)
            if read_once:                   # kq_ is ([T K; Q],)
                both = read(kq_[0])
                tks, qs = both[..., :C, :], both[..., C:, :]
            else:                           # kq_ is (T K, Q)
                tks, qs = read(kq_[0]), read(kq_[1])
            u = tv_ - tks
            o = qs + _mm(bm_, u)
            st = dec_ * st + jnp.einsum(
                "bhcv,bhck->bhvk", u, kd_, precision=_HI)
            return st, o

        return jax.lax.scan(step, state, (tv, *rest))

    return {"scores": kinds[kind](sub),
            "inverse": (kda._unit_lower_inverse if inverse == "tree"
                        else lambda A: old_unit_lower_inverse(A, 16)),
            "apply": apply, "carry": carry}


def whole(parts):
    """``kda_chunked`` from four parts (the tree's is the module's own
    function)."""

    def chunked(q, k, v, g, b, state=None, lengths=None, chunk=kda.CHUNK,
                segment=kda.SEGMENT):
        f32 = jnp.float32
        B, S, H, dk = q.shape
        dv = v.shape[-1]
        if state is None:
            state = jnp.zeros((B, H, dk, dv), f32)
        m = min(max(1, segment // (chunk * B)), -(-S // chunk))
        pad = -S % (chunk * m)
        if pad:
            q, k, v, g, b = (
                jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                for x in (q, k, v, g, b))
        n = (S + pad) // chunk

        def chunks(x):
            x = x.reshape(B, n // m, m, chunk, *x.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(x, 4, 3), 0, 2)

        def one_segment(state, xs):
            qc, kc, vc, gc, bc = xs
            G = jnp.cumsum(gc, axis=-2)
            A, Bm = parts["scores"](qc, kc, G, bc)
            T = parts["inverse"](A)
            tv, *mid, kd, decay = parts["apply"](T, qc, kc, vc, G, bc)
            return parts["carry"](state, tv, *mid, Bm, kd, decay)

        state, o = jax.lax.scan(
            one_segment, jnp.swapaxes(state.astype(f32), -1, -2),
            tuple(chunks(x) for x in (q, k, v, g, b)))
        o = jnp.moveaxis(o.reshape(n, B, H, chunk, dv), 1, 0)
        o = jnp.moveaxis(o, 3, 2)
        return (o.reshape(B, n * chunk, H, dv)[:, :S],
                jnp.swapaxes(state, -1, -2))

    return chunked


# name -> (scores, inverse, read_once), at the module's SEGMENT; ``.seg<N>``
# behind a name is the form at another; ``old`` is ``inverse_old.seg2048``
FORMS = {"inverse_old": ("tree", "old", False),
         "read_once": ("tree", "tree", True),
         "stacked16": ("stacked16", "tree", False),
         "levels16": ("levels16", "tree", False),
         "levels1": ("levels1", "tree", False),
         "ends16": ("ends16", "tree", False)}
SEGMENTS = (128, 256, 512, 1024, 2048)


def variants() -> dict:
    out = {"tree": kda.kda_chunked}
    out.update({f"seg{n}": functools.partial(kda.kda_chunked, segment=n)
                for n in SEGMENTS if n != kda.SEGMENT})
    out.update({f"chunk{n}": functools.partial(kda.kda_chunked, chunk=n)
                for n in (32, 128)})
    for name, form in FORMS.items():
        out[name] = fn = whole(parts(*form))
        out.update({f"{name}.seg{n}": functools.partial(fn, segment=n)
                    for n in SEGMENTS})
    out["old"] = out["inverse_old.seg2048"]
    return out


# -- the programs ----------------------------------------------------------------


def draw(B, S, gscale, heads=HEADS, d=D):
    ks = jax.random.split(jax.random.key(48), 6)
    q = kda.l2norm(jax.random.normal(ks[0], (B, S, heads, d))) * d ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (B, S, heads, d)))
    v = jax.random.normal(ks[2], (B, S, heads, d))
    g = gscale * jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, heads, d)))
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, heads)))
    state = 0.1 * jax.random.normal(ks[5], (B, heads, d, d))
    return q, k, v, g, b, state


def chained(fn):
    """LAYERS calls in one program, each on the one before's outputs."""

    def run(q, k, v, g, b, state):
        def layer(c, _):
            o, st = c
            o, st = fn(q, k, v + 1e-3 * o, g, b, state=st)
            return (o, st), None

        (o, st), _ = jax.lax.scan(layer, (jnp.zeros_like(v), state), None,
                                  length=LAYERS)
        return o, st

    return jax.jit(run)


def chained_part(fn, n_out):
    """One part LAYERS times, its first operand moved by its last output."""

    def run(first, *rest):
        def again(c, _):
            out = fn(first + c, *rest)
            out = out if isinstance(out, tuple) else (out,)
            return 1e-30 * out[-1].ravel()[0], out

        _, outs = jax.lax.scan(again, jnp.float32(0), None, length=LAYERS)
        return tuple(x[-1] for x in outs)[:n_out]

    return jax.jit(run)


def best_of(compiled, args, n=4):
    best, out = float("inf"), None
    for _ in range(n):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        best = min(best, time.perf_counter() - t0)
    return best, out


def rel(got, want) -> float:
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def segment_operands(gscale, B=1, S=2048, heads=HEADS, d=D):
    """One segment's chunked operands ``[m, B, H, C, ..]`` and ``G``."""
    q, k, v, g, b, state = draw(B, S, gscale, heads, d)
    C = kda.CHUNK
    cut = lambda x: jnp.moveaxis(jnp.moveaxis(
        x.reshape(B, S // C, C, *x.shape[2:]), 3, 2), 0, 1)
    qc, kc, vc, gc, bc = (cut(x) for x in (q, k, v, g, b))
    return qc, kc, vc, jnp.cumsum(gc, -2), bc, jnp.swapaxes(state, -1, -2)


def part_runs(four, ops) -> dict:
    """name -> (function, operands, outputs kept) of each part alone, the
    operands made by the parts in front of it."""
    qc, kc, vc, G, bc, state = ops
    A, Bm = jax.jit(four["scores"])(qc, kc, G, bc)
    T = jax.jit(four["inverse"])(A)
    tv, *mid, kd, decay = jax.jit(four["apply"])(T, qc, kc, vc, G, bc)
    return {"scores": (four["scores"], (qc, kc, G, bc), 2),
            "inverse": (four["inverse"], (A,), 1),
            "apply": (four["apply"], (T, qc, kc, vc, G, bc), 2),
            "carry": (four["carry"], (state, tv, *mid, Bm, kd, decay), 2)}


def main():
    cpu = "--cpu" in sys.argv
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not cpu:
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    only = None
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1].split(",")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    hf = {"num_attention_heads": HEADS, "head_dim": D}
    heads, d = (2, 16) if cpu else (HEADS, D)
    shapes = [(1, 64), (2, 192)] if cpu else SHAPES
    few = shapes if cpu else FEW
    forms = variants()
    runs = {n: f for n, f in forms.items() if not only or n in only}
    with open(OUT, "w") as sink:

        def say(row):
            text = json.dumps(row)
            print(text, flush=True)
            sink.write(text + "\n")
            sink.flush()

        # a program is compiled once and run on both draws of the gates
        for B, S in shapes:
            args = {gate: draw(B, S, gscale, heads, d)
                    for gate, gscale in GATES.items()}
            want = {}
            for name in ["tree"] + [n for n in runs if n != "tree"]:
                if name not in ("tree", "old") and (B, S) not in few:  # noqa
                    continue
                row = {"impl": name, "rows": B, "positions": S}
                try:
                    t0 = time.perf_counter()
                    compiled = chained(forms[name]).lower(
                        *args["near1"]).compile()
                    row["compile_s"] = round(time.perf_counter() - t0, 2)
                    for gate in GATES:
                        sec, got = best_of(compiled, args[gate])
                        want.setdefault(gate, got)
                        row[f"rel_out_to_tree.{gate}"] = rel(
                            got[0], want[gate][0])
                        row[f"rel_state_to_tree.{gate}"] = rel(
                            got[1], want[gate][1])
                        if not cpu:
                            row[f"ms.{gate}"] = round(1e3 * sec / LAYERS, 4)
                except Exception as e:     # out of memory at a variant
                    row["error"] = str(e).splitlines()[0][:300]
                else:
                    if not cpu:
                        sec = min(row[f"ms.{g}"] for g in GATES) / 1e3
                        least = prefill_flops(hf, B * S) / PEAK_FLOPS
                        row.update({
                            "roofline_pct": round(100 * least / sec, 4),
                            "temp_mib": round(compiled.memory_analysis()
                                              .temp_size_in_bytes / 2 ** 20, 1),
                            "device": dev.device_kind})
                if name in runs:
                    say(row)
        # each part alone, a segment of 2048 positions
        ops = {gate: (segment_operands(gscale, 2, 128, heads, d) if cpu
                      else segment_operands(gscale))
               for gate, gscale in GATES.items()}
        every = {name: parts(*FORMS[name]) for name in FORMS}
        every["tree"] = parts()
        for name, four in every.items():
            if name not in runs:
                continue
            by_gate = {gate: part_runs(four, o) for gate, o in ops.items()}
            for part, (fn, operands, n_out) in by_gate["near1"].items():
                mine = {"stacked16": "scores", "levels16": "scores",
                        "levels1": "scores", "ends16": "scores",
                        "inverse_old": "inverse"}
                if name in mine and part != mine[name]:
                    continue     # the other three are the tree's
                row = {"impl": name, "part": part}
                compiled = chained_part(fn, n_out).lower(*operands).compile()
                for gate in GATES:
                    sec, _ = best_of(compiled, by_gate[gate][part][1])
                    if not cpu:
                        row[f"ms.{gate}"] = round(1e3 * sec / LAYERS, 4)
                if not cpu:
                    row["device"] = dev.device_kind
                say(row)


if __name__ == "__main__":
    main()
