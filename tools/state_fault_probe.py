#!/usr/bin/env python3
"""Does a serving cell's output check HOLD a per-slot state? Plant a fault and
see.

    python tools/state_fault_probe.py --workload brumby-14b.serve-longout --seed N

Builds the cell's engine as the benchmark does and runs the benchmark's own
comparison (``benchmarks/kinds/serve.py``: ``probe_numbers`` and ``decide``,
unedited) three times on it: as it is, with every decode dispatch reading
state rows of ZEROS (a decode kernel that ignores the state), and with every
fold leaving the state as it was (a fold that writes nothing, its pages freed
all the same). Prints each pass's per-position errors by probe, the judged
numbers beside their limits and ``correct``. A check that holds the state
reads ``correct`` False under both faults; the last line says which it was
(exit 0 either way: this reports, it does not judge). On the CPU add
``--allow-cpu`` (a tiny configuration under the tests' root; no device
number is printed anywhere here).

PR 33 found with it what the Brumby cell's check holds: the decode kernel's
read of the state a prefill wrote, yes (the 8192-token probe's empty tail
leaves nine decode steps to the state alone); the fold, no (the benchmark's
weights give gates of about a half, so a lost fold spoils six positions of a
probe's second window, and a median over 17 does not move): PERF.md sections 6
and 7."""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import argparse
import os

import numpy as np

ROOT = _pathlib.Path(__file__).resolve().parent.parent


def plant(engine, fault: str):
    """Wrap the executor's launch (under the harness's own ``LogitTap``,
    which wraps it again a probe): buffers are donated, so that nothing is
    held twice beside a pool that fills the chip."""
    import jax
    import jax.numpy as jnp

    run = engine._executor.run
    n_rows = engine.cache["state_len"].shape[0]
    layers = jnp.arange(engine.mcfg.n_layers) * n_rows
    # Times a zero that is an ARGUMENT: written in place into the donated
    # rows (a zeros_like is a new 4 GB buffer beside the old one).
    blind = jax.jit(lambda c, zero: {
        **c, "state": c["state"] * zero.astype(c["state"].dtype),
        "state_z": c["state_z"] * zero}, donate_argnums=0)
    put = jax.jit(lambda c, rows, S, z: {
        **c, "state": c["state"].at[rows].set(S),
        "state_z": c["state_z"].at[rows].set(z)}, donate_argnums=0)

    def faulty(path, name, *args, **kw):
        if fault == "zeros" and path == "decode":
            args = (args[0], blind(args[1], jnp.float32(0)), *args[2:])
        if fault == "fold" and path == "fold":
            rows = layers + args[1] + 1          # (cache, slot, page row)
            S, z = args[0]["state"][rows], args[0]["state_z"][rows]
            return put(run(path, name, *args, **kw), rows, S, z)
        return run(path, name, *args, **kw)

    engine._executor.run = faulty
    return run


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
            ROOT / ".jax_compile_cache")

    from benchmarks.harness import device as device_lib
    from benchmarks.harness.cell import Cell
    from benchmarks.kinds import serve

    cell = Cell.find(args.workload, root=_pathlib.Path(args.root))
    dev = device_lib.require(cell.chips, allow_cpu=args.allow_cpu)
    print(f"device: {dev.platform} {dev.kind!r}", flush=True)
    _, engine = serve.build_engine(cell, args.seed)
    verdicts = {}
    for fault in ("none", "zeros", "fold"):
        real = plant(engine, fault)
        numbers = serve.probe_numbers(
            engine, cell.reference(), cell.config, cell.mix, args.seed)
        engine._executor.run = real
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
        engine.reset_timing()
    missed = [f for f in ("zeros", "fold") if verdicts[f]]
    print(f"verdicts {verdicts}: the check "
          + ("HOLDS the state" if verdicts["none"] and not missed else
             f"does NOT hold the state: it passes {' and '.join(missed)}"
             if verdicts["none"] else "fails with no fault planted"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
