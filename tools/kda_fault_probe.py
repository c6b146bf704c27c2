#!/usr/bin/env python3
"""What of the KDA path and of the grouped router does a serving cell's output
check HOLD? Plant a fault in the program and see.

    python tools/kda_fault_probe.py --workload ling-3.0-flash.serve-reason-128 --seed N

Builds the cell's engine as the benchmark does and runs the benchmark's own
comparison (``benchmarks/kinds/serve.py``: ``probe_numbers`` and ``decide``,
under the cell's own tap, ``kinds/serve_rows.py``) on it as it is and once a
fault, each on an engine of its own (a fault is planted in traced code, so its
programs are compiled anew):

  erase   a decode step drops the erase term ``b k k^T S``: a gated SUM and
          not a delta rule (the window program and the one-step body alike,
          so the window link holds; prefill, in the chunked form, is whole);
  groups  the router ignores its groups: the top 8 of ``s + b`` over all 512
          experts, in prefill and decode alike;
  carry   the chunked prefill drops the carried state ``S_0`` at every chunk
          boundary: each chunk of 64 positions starts from zeros, and the
          state a prompt leaves is its last chunk's alone.

``--faults`` names the passes to make (default all four, ``none`` first).

Prints each pass's per-position errors by probe, the judged numbers beside
their limits and ``correct``; the last line says which faults the check saw
(exit 0 either way: this reports, it does not judge). ``tests/test_ling.py``
plants the first two on ``tiny-ling`` in float32, where both are seen; the
third is seen only where a state outlives a chunk (decays near 1:
``tests/test_kda.py``; PERF.md section 7). On the
CPU add ``--allow-cpu`` (a tiny configuration under the tests' root; no
device number is printed anywhere here)."""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import argparse
import contextlib
import gc
import os

import numpy as np

ROOT = _pathlib.Path(__file__).resolve().parent.parent
FAULTS = ("none", "erase", "groups", "carry")


@contextlib.contextmanager
def planted(fault: str):
    """The program's own functions with ``fault`` in them, while an engine
    traces its programs."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.models import moe
    from orion_tpu.ops import kda
    from orion_tpu.ops.pallas import kda as kda_kernel

    keep = (kda.kda_step, kda_kernel.kda_decode, moe._keep_groups,
            kda.kda_chunked)

    def gated_sum(state, q, k, v, g, b, active=None):
        """``kda_step`` without the erase term (value-major rows)."""
        f32 = jnp.float32
        q, k, v, g, b = (x.astype(f32) for x in (q, k, v, g, b))
        s = state * jnp.exp(g)[:, :, None, :]
        s = s + v[..., :, None] * (b[..., None] * k)[..., None, :]
        o = jnp.einsum("bhvk,bhk->bhv", s, q)
        if active is not None:
            s = jnp.where(active[:, None, None, None], s, state)
        return o, s

    def gated_sum_rows(state, q, k, v, g, b, *, layer, active=None,
                       interpret=False, name=""):
        at = (layer, 1, 0, 0, 0)
        rows = jax.lax.dynamic_slice(
            state, at, (1, q.shape[0], *state.shape[2:]))[0]
        o, new = gated_sum(rows, q, k, v, g, b, active)
        return o, jax.lax.dynamic_update_slice(state, new[None], at)

    def lost_carry(q, k, v, g, b, state=None, lengths=None, **kw):
        """``kda_chunked`` a chunk at a time, each from a zero state."""
        C = kda.CHUNK
        B, S = q.shape[:2]
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        n = -(-S // C)
        cut = lambda x: jnp.moveaxis(jnp.pad(
            x, ((0, 0), (0, n * C - S)) + ((0, 0),) * (x.ndim - 2)
        ).reshape(B, n, C, *x.shape[2:]), 1, 0)
        left = jnp.clip(lengths[None, :] - C * jnp.arange(n)[:, None], 0, C)

        def one(_, xs):
            *qkvgb, ln = xs
            return None, keep[3](*qkvgb, None, ln, **kw)

        _, (o, states) = jax.lax.scan(
            one, None, (*(cut(x) for x in (q, k, v, g, b)), left))
        o = jnp.moveaxis(o, 0, 1).reshape(B, n * C, *o.shape[3:])[:, :S]
        last = jnp.maximum(lengths - 1, 0) // C
        return o, states[last, jnp.arange(B)]

    if fault == "erase":
        kda.kda_step, kda_kernel.kda_decode = gated_sum, gated_sum_rows
    elif fault == "groups":
        moe._keep_groups = lambda scores, cfg: scores
    elif fault == "carry":
        kda.kda_chunked = lost_carry
    try:
        yield
    finally:
        (kda.kda_step, kda_kernel.kda_decode, moe._keep_groups,
         kda.kda_chunked) = keep


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--faults", default=",".join(FAULTS))
    args = ap.parse_args()
    faults = [f for f in FAULTS if f in args.faults.split(",")]
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
            ROOT / ".jax_compile_cache")

    from benchmarks.harness import device as device_lib
    from benchmarks.harness.cell import Cell
    from benchmarks.kinds import serve

    cell = Cell.find(args.workload, root=_pathlib.Path(args.root))
    dev = device_lib.require(cell.chips, allow_cpu=args.allow_cpu)
    print(f"device: {dev.platform} {dev.kind!r}", flush=True)
    verdicts = {}
    for fault in faults:
        # The cell's own tap (a kind that brings one: ``serve_rows``).
        tap = getattr(cell.kind_module(), "tapped", contextlib.nullcontext)
        with planted(fault), tap():
            _, engine = serve.build_engine(cell, args.seed)
            numbers = serve.probe_numbers(
                engine, cell.reference(), cell.config, cell.mix, args.seed)
        # The engine and its executor hold each other: drop the buffers by
        # hand, or the next engine's weights do not fit beside them.
        engine.close()
        engine.params = engine.cache = None
        del engine
        gc.collect()
        print(f"-- fault planted: {fault}", flush=True)
        per = len(numbers["err"]) // len(cell.mix["probe_prompts"])
        errs = np.asarray(numbers["err"]).reshape(-1, per)
        for n, row in zip(cell.mix["probe_prompts"], errs):
            print(f"probe {n}: median {np.median(row):.4f} max "
                  f"{row.max():.4f} positions "
                  + " ".join(f"{e:.3f}" for e in row), flush=True)
        ok, checks = serve.decide(numbers, cell.config["correct"])
        for name, value, limit in checks:
            print(f"check: {name} = {value!r} (limit {limit!r})")
        print(f"correct: {ok}", flush=True)
        verdicts[fault] = ok
    seen = [f for f in faults if f != "none" and not verdicts[f]]
    print(f"verdicts {verdicts}: the check sees {seen or 'no fault'}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
