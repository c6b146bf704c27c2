#!/usr/bin/env python3
"""What of the sink, the value scale, the window and the ring does a serving
cell's output check HOLD? Plant a fault in the program and see.

    python tools/mimo_fault_probe.py --workload mimo-v2.5.serve-mixed-16k --seed N

Builds the cell's engine as the benchmark does and runs the benchmark's own
comparison (``benchmarks/kinds/serve.py``: ``probe_numbers`` and ``decide``,
under the cell's own tap, ``kinds/serve_rows.py``) on it as it is and once a
fault, each on an engine of its own (a fault is planted in traced code, so its
programs are compiled anew):

  sink     the window layers' learned sink is left out of every softmax
           (prefill and decode alike: the layer body is handed no sink);
  scale    the values are not multiplied by ``attention_value_scale``;
  ring     a slot's ring is one page short (2 pages of 64 where a window of
           128 positions spans 3): decode reads what the ring still holds;
  window   the window mask is off by one (129 positions where the model has
           128), in prefill and decode alike.

``--faults`` names the passes to make (default all five, ``none`` first).
Prints each pass's per-position errors by probe, the judged numbers beside
their limits and ``correct``; the last line says which faults the check saw
(exit 0 either way: this reports, it does not judge). ``tests/test_mimo.py``
plants the first, second and fourth on ``tiny-mimo`` in float32 through the
reference, where each is seen. On the CPU add ``--allow-cpu`` (a tiny
configuration under the tests' root; no device number is printed here)."""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import argparse
import contextlib
import dataclasses
import gc
import os

import numpy as np

ROOT = _pathlib.Path(__file__).resolve().parent.parent
FAULTS = ("none", "sink", "scale", "ring", "window")


@contextlib.contextmanager
def planted(fault: str, cell):
    """The program with ``fault`` in it, while an engine is built and traces
    its programs."""
    from orion_tpu.infer import kv_cache, runner

    keep = (runner.block, kv_cache.ring_pages, cell.program_config)

    def no_sink(x, bp, *args, **kw):
        attn = {k: v for k, v in bp["attn"].items() if k != "sink"}
        return keep[0](x, {**bp, "attn": attn}, *args, **kw)

    def model_with(**changed):
        def program_config():
            cfg = keep[2]()
            return dataclasses.replace(
                cfg, model=dataclasses.replace(cfg.model, **changed))
        return program_config

    if fault == "sink":
        runner.block = no_sink
    elif fault == "scale":
        cell.program_config = model_with(value_scale=1.0)
    elif fault == "ring":
        kv_cache.ring_pages = lambda window, psz: keep[1](window, psz) - 1
    elif fault == "window":
        cell.program_config = model_with(
            sliding_window=keep[2]().model.sliding_window + 1)
    try:
        yield
    finally:
        runner.block, kv_cache.ring_pages, cell.program_config = keep


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
        with planted(fault, cell), tap():
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
