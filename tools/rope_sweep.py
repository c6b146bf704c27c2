"""Time the rotary embedding alone on the chip, Pallas kernel against the XLA
form, over the sequence lengths a program hands it (PERF.md section 6, PR 34:
where ``ops.rope.KERNEL_MIN_SEQ`` comes from).

    chiprun -- python tools/rope_sweep.py [--batches 32,1] [--heads 8,32,40,72]
        [--lens 1,2,4,8,16,64,128,512] [--impls kernel,xla,flat]

``kernel`` is ``ops.pallas.rope.rope_pallas`` as it stands (a grid of one
batch row a step, the sequence padded to a multiple of 8), ``xla`` is
``ops.rope._rope_xla`` / ``_rope_xla_table``, ``flat`` is the same kernel
handed the ``B x S`` rows as ONE sequence (what a kernel that blocks
flattened rows would cost: the reshape is free). x is bf16 ``[B, S, N, 128]``
as ``models/transformer.qkv_proj`` holds it; heads 72 (the widest) also run
the table kernel on Laguna's YaRN / partial-rotation table, the others the
plain table at 1e6.

Each is timed in a program of chained calls (``fori_loop``; the positions
move with the loop index, so that no angle is hoisted out of it), best of
four. Alone the XLA form cannot fuse with the norm before it nor the layout
change after it as it does inside a decode program, so its time here is an
upper bound of what it costs there. Per line: us a call, the bytes of x read
and written once over that time as a share of 819 GB/s, and the rows the
kernel pads the sequence to.

Prints one JSON line each and keeps them in ``chiprun_out/rope_sweep.jsonl``.
Raises without a TPU."""

from __future__ import annotations

import itertools
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from orion_tpu.config import get_config  # noqa: E402
from orion_tpu.ops.pallas.common import round_up  # noqa: E402
from orion_tpu.ops.pallas.rope import rope_pallas  # noqa: E402
from orion_tpu.ops.rope import (  # noqa: E402
    _rope_xla, _rope_xla_table, rope_table,
)

H = 128
THETA = 1e6
PEAK_BYTES = 819e9
OUT = "chiprun_out/rope_sweep.jsonl"
# Laguna's full-attention table: half of each head rotates, YaRN frequencies.
YARN = get_config("laguna-s-2.1").model.rope_full
MAX_POS = 12287


def arg(name, default):
    if name in sys.argv:
        return sys.argv[sys.argv.index(name) + 1].split(",")
    return default


def rotations(table):
    """{impl: f(x [B, S, N, H], positions [B, S]) -> rotated x}."""
    def kernel(x, pos):
        return rope_pallas(x, pos, theta=THETA, table=table)

    def xla(x, pos):
        if table is not None:
            return _rope_xla_table(x, pos, *table)
        return _rope_xla(x, pos, THETA)

    def flat(x, pos):
        B, S, N, _ = x.shape
        return rope_pallas(x.reshape(1, B * S, N, H), pos.reshape(1, B * S),
                           theta=THETA, table=table).reshape(x.shape)

    return {"kernel": kernel, "xla": xla, "flat": flat}


def timed(fn, x, pos, reps):
    def prog(x, pos):
        def body(i, x):
            return fn(x, jnp.minimum(pos + i, MAX_POS))
        return lax.fori_loop(0, reps, body, x)

    run = jax.jit(prog).lower(x, pos).compile()
    best = float("inf")
    for _ in range(4):
        t0 = time.perf_counter()
        jax.block_until_ready(run(x, pos))
        best = min(best, time.perf_counter() - t0)
    return best / reps


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    batches = [int(b) for b in arg("--batches", ["32", "1"])]
    heads = [int(n) for n in arg("--heads", ["8", "32", "40", "72"])]
    lens = [int(s) for s in arg("--lens", "1,2,4,8,16,64,128,512".split(","))]
    impls = arg("--impls", ["kernel", "xla", "flat"])
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    cases = [(N, tname, table)
             for N in heads
             for tname, table in [("plain", None)] + (
                 [("yarn-half", rope_table(H, YARN))] if N == 72 else [])]
    with open(OUT, "a") as sink:
        for (N, tname, table), B, S in itertools.product(
                cases, batches, lens):
            fns = rotations(table)
            x = jax.random.normal(
                jax.random.key(34), (B, S, N, H), jnp.bfloat16)
            pos = jax.random.randint(
                jax.random.key(S), (B, 1), 0, MAX_POS - S
            ) + jnp.arange(S)[None, :]
            reps = max(16, min(2048, (1 << 26) // x.size))
            row = {"heads": N, "table": tname, "batch": B, "len": S,
                   "reps": reps, "kernel_rows": round_up(S, 8)}
            for impl in impls:
                try:
                    sec = timed(fns[impl], x, pos, reps)
                except Exception as e:  # a shape Mosaic refuses
                    row[impl + "_error"] = str(e).splitlines()[0][:160]
                    continue
                row[impl + "_us"] = round(1e6 * sec, 3)
                row[impl + "_hbm_pct"] = round(
                    100 * 2 * x.nbytes / PEAK_BYTES / sec, 2)
            row["device"] = dev.device_kind
            text = json.dumps(row)
            print(text, flush=True)
            sink.write(text + "\n")
            sink.flush()

if __name__ == "__main__":
    main()
