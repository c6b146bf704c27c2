"""Time a sparse layer's prefill attention alone on the chip at the SALA cell's
shape: one chunk of 4,096 queries, 32 heads over 2 K/V heads of 128, pages of
64 on a 1,120-entry row, the chunk ending at 4k / 16k / 64k of context, in
tiles of 256 queries as ``runner._sparse_attend`` cuts it. ``slots`` is every
(query, K/V head) a virtual slot of the paged decode kernel (the form before
PR 59); ``blocks_s<a>_p<b>`` the block kernel of
``orion_tpu/ops/pallas/sparse_prefill.py`` at ``a`` pages a shared step and
``b`` a private step. That module's ``SHARED_PAGES`` / ``PRIVATE_PAGES`` come
from this script's table (PERF.md section 5).

    chiprun -- python tools/sparse_prefill_sweep.py [--only slots,blocks_s8]

The lists are drawn, not selected: the forced pages of ``SparseConfig`` (1
initial block, the 32 that end with the own) and 31 free choices uniform over
the causal blocks left, which is what seeded weights select (the
configuration's ``assumed.sparse_why``). Prints one JSON line per (context,
implementation) and keeps them in ``chiprun_out/sparse_prefill_sweep.jsonl``.
``--cpu`` is the logic check at tiny shapes (interpret mode, each form against
the gather form) and prints no time. Raises without a TPU otherwise."""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from orion_tpu.config import SparseConfig  # noqa: E402
from orion_tpu.ops import sparse  # noqa: E402
from orion_tpu.ops.pallas import sparse_prefill  # noqa: E402

PEAK_FLOPS = 197e12             # bf16, one v5e chip
HBM_BYTES_PER_S = 819e9
REPS = 6
OUT = "chiprun_out/sparse_prefill_sweep.jsonl"
# (32 pages a shared step do not fit the default scoped VMEM.)
SWEEP = [(s, p) for s in (4, 8, 16) for p in (8, 16, 32)]


def draw_lists(rng, sp, K, start, Q, table):
    """(ids, pages [1, K, Q, topk], n [1, K, Q]) of queries at ``start ..
    start + Q - 1``: every causal block while they are ``topk`` or fewer,
    else the forced ones and free choices drawn uniformly, ascending."""
    T = sp.topk
    free = T - sp.init_blocks - sp.local_blocks
    ids = np.full((K, Q, T), len(table), np.int32)
    n = np.zeros((K, Q), np.int32)
    for t0 in range(0, Q, sp.block):
        own = (start + t0) // sp.block
        rows = slice(t0, t0 + sp.block)
        if own < T:
            ids[:, rows, :own + 1] = np.arange(own + 1)
            n[:, rows] = own + 1
            continue
        first = own - sp.local_blocks + 1
        pick = rng.random((K, sp.block, first - sp.init_blocks)).argsort(-1)
        ids[:, rows] = np.concatenate([
            np.broadcast_to(np.arange(sp.init_blocks),
                            (K, sp.block, sp.init_blocks)),
            np.sort(pick[..., :free], -1) + sp.init_blocks,
            np.broadcast_to(first + np.arange(sp.local_blocks),
                            (K, sp.block, sp.local_blocks))], -1)
        n[:, rows] = T
    used = np.arange(T) < n[..., None]
    pages = np.where(used, table[np.minimum(ids, len(table) - 1)], 0)
    return (jnp.asarray(ids[None]), jnp.asarray(pages[None], jnp.int32),
            jnp.asarray(n[None]))


def forms(sp, tile):
    """name -> f(q [1, Q, N, H], pools, pages, n, pos) -> out, a chunk in
    tiles of ``tile`` queries."""
    def tiled(one):
        def run(q, kp, vp, pages, n, pos):
            Q = q.shape[1]
            cut = lambda a, ax: jnp.moveaxis(
                a.reshape(*a.shape[:ax], Q // tile, tile, *a.shape[ax + 1:]),
                ax, 0)
            out = lax.map(
                lambda xs: one(xs[0], kp, vp, *xs[1:]),
                (cut(q, 1), cut(pages, 2), cut(n, 2), cut(pos, 1)))
            return jnp.moveaxis(out, 0, 1).reshape(q.shape)
        return run

    def slots(q, kp, vp, pages, n, pos):
        return sparse.attend_pallas(q, kp, vp, pages, n, pos, layer_base=0,
                                    interpret=CPU)[0]

    def blocks(s, p):
        def one(q, kp, vp, pages, n, pos):
            kept = sparse_prefill.SHARED_PAGES, sparse_prefill.PRIVATE_PAGES
            sparse_prefill.SHARED_PAGES, sparse_prefill.PRIVATE_PAGES = s, p
            try:
                return sparse.attend_blocks(q, kp, vp, pages, pos, sp,
                                            layer_base=0, interpret=CPU)
            finally:
                (sparse_prefill.SHARED_PAGES,
                 sparse_prefill.PRIVATE_PAGES) = kept
        return one

    out = {"slots": tiled(slots)}
    out.update({f"blocks_s{s}_p{p}": tiled(blocks(s, p)) for s, p in SWEEP})
    if "--only" in sys.argv:
        keep = tuple(sys.argv[sys.argv.index("--only") + 1].split(","))
        out = {k: f for k, f in out.items() if k.startswith(keep)}
    return out


def timed(f, q, *rest):
    """Seconds a call of ``f`` inside one program of REPS calls, each call's
    q made from the last one's output as the layer scan chains them."""
    prog = jax.jit(lambda q, *rest: lax.fori_loop(
        0, REPS, lambda _, q: f(q, *rest).astype(q.dtype), q))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(prog(q, *rest))
        best = min(best, time.perf_counter() - t0)
    return best / REPS


def emit(sink, row):
    text = json.dumps(row)
    print(text, flush=True)
    sink.write(text + "\n")
    sink.flush()


def main():
    dev = jax.devices()[0]
    if not CPU and dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    if CPU:
        sp = SparseConfig(kernel=4, stride=2, block=8, init_blocks=1,
                          local_blocks=3, topk=6)
        Q, N, K, H, P, tile, dtype = 64, 4, 2, 16, 40, 32, jnp.float32
        contexts = (64, 128, 320)
    else:
        sp = SparseConfig(kernel=32, stride=16, block=64, init_blocks=1,
                          local_blocks=32, topk=64)
        Q, N, K, H, P, tile, dtype = 4096, 32, 2, 128, 1120, 256, jnp.bfloat16
        contexts = (4096, 16384, 65536)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    keys = jax.random.split(jax.random.key(59), 3)
    q = jax.random.normal(keys[0], (1, Q, N, H), dtype)
    pools = [jax.random.normal(k, ((P + 1) * K, 1, sp.block, H), dtype)
             for k in keys[1:]]
    with open(OUT, "w") as sink:
        for i, ctx in enumerate(contexts):
            # A context's draws depend on nothing that ran before it.
            rng = np.random.default_rng([59, i])
            table = (rng.permutation(P) + 1).astype(np.int32)
            start = ctx - Q
            ids, pages, n = draw_lists(rng, sp, K, start, Q, table)
            pos = jnp.arange(start, ctx, dtype=jnp.int32)[None]
            seen = np.minimum(
                np.arange(start, ctx) + 1,
                (sp.topk - 1) * sp.block + np.arange(start, ctx) % sp.block
                + 1)
            shared = sparse.split_blocks(pages, pos, sp)[1]
            hit = float(shared.sum() * sp.block) / float(n.sum())
            want = None
            for name, f in forms(sp, tile).items():
                row = {"context": ctx, "impl": name,
                       "shared_page_share_pct": round(100 * hit, 2)}
                if CPU:
                    want = (sparse.attend_xla(q, *pools, pages, ids, n, pos)
                            if want is None else want)
                    got = f(q, *pools, pages, n, pos)
                    row["rel_err"] = float(
                        jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
                    assert row["rel_err"] < 1e-5, row
                    emit(sink, row)
                    continue
                try:
                    # (Compiled, against the virtual slots' output: two
                    # orders of summation in the pools' dtype.)
                    got = jax.jit(f)(q, *pools, pages, n, pos).astype(
                        jnp.float32)
                    want = got if want is None else want
                    row["rel_to_first"] = round(float(
                        jnp.linalg.norm(got - want) / jnp.linalg.norm(want)),
                        6)
                    sec = timed(f, q, *pools, pages, n, pos)
                except Exception as e:      # a shape Mosaic refuses
                    emit(sink, {**row, "error": str(e).splitlines()[0][:200]})
                    continue
                # The model's work: 4 x H operations a visible (head, key)
                # pair (benchmarks/metrics/sala.py); the bytes of the pages
                # a (query, K/V head) lists, each once.
                flops = 4 * H * N * int(seen.sum())
                emit(sink, {
                    **row, "ms_a_layer_and_chunk": round(1e3 * sec, 3),
                    "compute_roofline_pct": round(
                        100 * flops / PEAK_FLOPS / sec, 2),
                    "listed_page_gb": round(
                        int(n.sum()) * 2 * sp.block * H * 2 / 1e9, 3),
                    "device": dev.device_kind})


CPU = "--cpu" in sys.argv

if __name__ == "__main__":
    main()
