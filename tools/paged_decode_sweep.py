"""Time the paged decode kernel alone on the chip at the two serving cells'
shapes: the block walk of ``orion_tpu/ops/pallas/paged_attention.py`` as the
engine calls it (``tree``) and over pages-per-step ``nb`` (``new_nb*``). The
module's ``BLOCK_PAGES`` comes from this script's table (PERF.md section 6,
PR 29, where the walk it replaced, one page a grid step, was last timed).

    chiprun -- python tools/paged_decode_sweep.py [--only tree,new]

Shapes: B 32, K 8, H 128, page 64; G 4 / P 40 (Mixtral), G 6 / P 76 (Laguna's
full layers), G 9 / P 76 / window 512 (its window layers). Contexts: drawn as
the cells' traffic mixes draw them, every page live, and 64 tokens.

Prints one JSON line per (shape, contexts, implementation) and keeps them in
``chiprun_out/paged_decode_sweep.jsonl``. Raises without a TPU."""

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
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from orion_tpu.ops.pallas import paged_attention as pa  # noqa: E402

B, K, H, PSZ = 32, 8, 128, 64
HBM_BYTES_PER_S = 819e9
REPS = 24
OUT = "chiprun_out/paged_decode_sweep.jsonl"
SHAPES = [                      # (tag, G, P, window, traffic mix)
    ("mixtral", 4, 40, None, "serve-batch"),
    ("laguna-full", 6, 76, None, "serve-batch-4k"),
    ("laguna-window", 9, 76, 512, "serve-batch-4k"),
]


def tree_walk(q, k_pool, v_pool, page_table, last_pos, k_new, v_new, *,
              window):
    """This checkout's public ``paged_attention``, as the engine calls it."""
    return pa.paged_attention(
        q, k_pool, v_pool, page_table, last_pos, k_new=k_new, v_new=v_new,
        window=window)


def new_walk(q, k_pool, v_pool, page_table, last_pos, k_new, v_new, *,
             window, nb):
    """The module's kernel traced at ``nb`` pages a step."""
    kept, pa.BLOCK_PAGES = pa.BLOCK_PAGES, nb
    try:
        return tree_walk(q, k_pool, v_pool, page_table, last_pos, k_new,
                         v_new, window=window)
    finally:
        pa.BLOCK_PAGES = kept


def draw_contexts(mix: str, rng) -> np.ndarray:
    """One decode step's 32 contexts under a closed-loop mix: a prompt of the
    mix's distribution plus a uniform share of an output of it."""
    with open(f"benchmarks/traffic/{mix}.json") as f:
        spec = json.load(f)

    def draw(d):
        x = d["median"] * np.exp(d["sigma"] * rng.standard_normal(B))
        return np.clip(x, d["min"], d["max"])

    return (draw(spec["prompt"])
            + rng.random(B) * draw(spec["output"])).astype(np.int32)


def timed(step, q, k_pool, v_pool, *rest):
    """Seconds per call of ``step`` inside one program of REPS calls, the
    pools carried in place and each call's q made from the last one's
    output, as the layer scan chains them."""
    def body(_, c):
        out, kp, vp = step(*c, *rest)
        return out.astype(c[0].dtype), kp, vp

    prog = jax.jit(
        lambda q, kp, vp: lax.fori_loop(0, REPS, body, (q, kp, vp)),
        donate_argnums=(1, 2))
    best = float("inf")
    for _ in range(4):
        t0 = time.perf_counter()
        q_out, k_pool, v_pool = jax.block_until_ready(prog(q, k_pool, v_pool))
        best = min(best, time.perf_counter() - t0)
    return best / REPS, k_pool, v_pool


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as sink:
        sweep(dev, sink)


def sweep(dev, sink):
    keys = jax.random.split(jax.random.key(29), 5)
    for i, (tag, G, P, window, mix) in enumerate(SHAPES):
        # A shape's draws depend on nothing that ran before it, so two
        # checkouts (or two --only lists) time the same contexts.
        rng = np.random.default_rng([29, i])
        N = K * G
        rows = B * P + 1
        q = jax.random.normal(keys[0], (B, N, H), jnp.bfloat16)
        k_pool = jax.random.normal(keys[1], (rows, K, PSZ, H), jnp.bfloat16)
        v_pool = jax.random.normal(keys[2], (rows, K, PSZ, H), jnp.bfloat16)
        k_new = jax.random.normal(keys[3], (B, K, H), jnp.bfloat16)
        v_new = jax.random.normal(keys[4], (B, K, H), jnp.bfloat16)
        page_table = jnp.asarray(
            rng.permutation(rows - 1)[: B * P].reshape(B, P) + 1, jnp.int32)
        contexts = {
            "mix": draw_contexts(mix, rng),
            "all_live": np.full(B, P * PSZ, np.int32),
            "half": np.full(B, P * PSZ // 2, np.int32),
            "one_page": np.full(B, 64, np.int32),
        }
        impls = {
            "tree": functools.partial(tree_walk, window=window),
            **{f"new_nb{nb}": functools.partial(new_walk, window=window,
                                                nb=nb)
               for nb in (2, 4, 8, 16) if hasattr(pa, "BLOCK_PAGES")},
        }
        if "--only" in sys.argv:
            keep = tuple(sys.argv[sys.argv.index("--only") + 1].split(","))
            impls = {n: f for n, f in impls.items() if n.startswith(keep)}
        for cname, ctx in contexts.items():
            last_pos = jnp.asarray(ctx - 1, jnp.int32)
            first = (np.maximum(ctx - window, 0) if window else 0 * ctx)
            tokens = int((ctx - first).sum())
            pages = int(((ctx - 1) // PSZ - first // PSZ + 1).sum())
            for name, step in impls.items():
                try:
                    sec, k_pool, v_pool = timed(
                        step, q, k_pool, v_pool, page_table, last_pos,
                        k_new, v_new)
                except Exception as e:   # a shape Mosaic refuses
                    emit(sink, {"shape": tag, "contexts": cname,
                                "impl": name,
                                "error": str(e).splitlines()[0][:200]})
                    continue
                line(sink, dev, tag, cname, name, sec, tokens, pages, 2)
        if window is None and hasattr(pa, "BLOCK_PAGES"):
            int8_lines(sink, dev, tag, rng, q, k_new, v_new, page_table,
                       rows, P)


def int8_lines(sink, dev, tag, rng, q, k_new, v_new, page_table, rows, P):
    """The int8 pool at one page a sequence and at the mix's mean (PR 21
    found int8 slower than bf16 at 64-token contexts under the old walk)."""
    kq = jnp.asarray(rng.integers(-127, 128, (rows, K, PSZ, H)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (rows, K, PSZ, H)), jnp.int8)
    for cname, n in (("one_page", 64), ("half", P * PSZ // 2)):
        scales = (jnp.full((rows, K, 128), 0.01, jnp.float32),) * 2

        def step(q, kp, vp, pt, lp, kn, vn, ks, vs):
            o = pa.paged_attention(q, kp, vp, pt, lp, k_new=kn, v_new=vn,
                                   k_scale=ks, v_scale=vs)
            return o[0], o[1], o[2]

        last_pos = jnp.full(B, n - 1, jnp.int32)
        sec, kq, vq = timed(step, q, kq, vq, page_table, last_pos, k_new,
                            v_new, *scales)
        line(sink, dev, tag, cname, "tree_int8", sec, B * n,
             B * (-(-n // PSZ)), 1)


def emit(sink, row):
    text = json.dumps(row)
    print(text, flush=True)
    sink.write(text + "\n")
    sink.flush()


def line(sink, dev, tag, cname, impl, sec, tokens, pages, itemsize):
    token_bytes = tokens * 2 * K * H * itemsize
    page_bytes = pages * PSZ * 2 * K * H * itemsize
    emit(sink, {
        "shape": tag, "contexts": cname, "impl": impl,
        "ms": round(1e3 * sec, 4), "live_pages": pages,
        "bytes_read": page_bytes,
        "roofline_pct_tokens": round(
            100 * token_bytes / HBM_BYTES_PER_S / sec, 2),
        "roofline_pct_pages": round(
            100 * page_bytes / HBM_BYTES_PER_S / sec, 2),
        "device": dev.device_kind,
    })


if __name__ == "__main__":
    main()
