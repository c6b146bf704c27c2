"""Time the grouped expert matmul at the Mixtral cell's prefill shapes on the
chip: XLA's lowering of ``lax.ragged_dot`` against the Pallas megablox ``gmm``
over a list of tilings, beside the dense bucket matmul the capacity dispatch
runs for the same tokens. The tile sizes in ``orion_tpu/ops/grouped_matmul.py``
come from this script's table (PERF.md section 6, PR 26).

    chiprun -- python tools/grouped_matmul_sweep.py

Prints one JSON line per (shape, implementation, group sizes) and keeps them
in ``chiprun_out/grouped_matmul_sweep.jsonl``. Raises without a TPU."""

from __future__ import annotations

import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm

E, TOP_K = 8, 2
TILINGS = [
    (128, 128, 128),                       # the library's default
    (128, 1024, 1024), (128, 2048, 1024), (128, 1024, 2048),
    (256, 512, 512), (256, 1024, 1024), (256, 2048, 1024),
    (256, 1024, 2048), (256, 512, 2048), (256, 4096, 512),
    (512, 512, 512), (512, 512, 1024), (512, 1024, 512),
    (512, 1024, 1024), (512, 512, 2048), (1024, 512, 1024),
]
OUT = "chiprun_out/grouped_matmul_sweep.jsonl"


def timed(fn, *args, n=8):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as sink:
        sweep(dev, sink)


def sweep(dev, sink):
    rng = np.random.default_rng(0)

    def emit(row):
        text = json.dumps(row)
        print(text, flush=True)
        sink.write(text + "\n")
        sink.flush()

    for k, n in ((4096, 14336), (14336, 4096)):
        rhs = jax.random.normal(jax.random.key(1), (E, k, n), jnp.bfloat16)
        for m in (1024, 4096, 8192):
            lhs = jax.random.normal(jax.random.key(2), (m, k), jnp.bfloat16)
            sizes = {
                "even": rng.multinomial(m, [1 / E] * E),
                "skew": rng.multinomial(m, rng.dirichlet([0.5] * E)),
                "half": rng.multinomial(m // 2, [1 / E] * E),
            }

            def line(impl, gs_name, sec, rows):
                emit({
                    "k": k, "n": n, "m": m, "impl": impl, "sizes": gs_name,
                    "ms": round(1e3 * sec, 4), "routed_rows": int(rows),
                    "tflops_routed": round(2.0 * rows * k * n / sec / 1e12, 2),
                    "device": dev.device_kind,
                })

            # What the capacity dispatch runs for the same tokens: all E
            # experts over every token (T = m / TOP_K rows each).
            xb = jax.random.normal(
                jax.random.key(3), (E, m // TOP_K, k), jnp.bfloat16)
            dense = jax.jit(lambda a, b: jnp.einsum("etk,ekn->etn", a, b))
            sec = timed(dense, xb, rhs)
            line("dense_buckets", "all", sec, E * m // TOP_K)

            rd = jax.jit(lambda a, b, g: jax.lax.ragged_dot(a, b, g))
            for name, gs in sizes.items():
                g = jnp.asarray(gs, jnp.int32)
                line("ragged_dot", name, timed(rd, lhs, rhs, g), gs.sum())
            for tiling in TILINGS:
                if m % tiling[0]:
                    continue
                f = jax.jit(lambda a, b, g, t=tiling: gmm(
                    a, b, g, preferred_element_type=jnp.bfloat16, tiling=t))
                for name, gs in sizes.items():
                    g = jnp.asarray(gs, jnp.int32)
                    try:
                        sec = timed(f, lhs, rhs, g)
                    except Exception as e:   # a tiling Mosaic refuses
                        emit({"k": k, "n": n, "m": m, "impl": f"gmm{tiling}",
                              "error": str(e).splitlines()[0][:160]})
                        break
                    line(f"gmm{tiling}", name, sec, gs.sum())


if __name__ == "__main__":
    main()
