#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` when traced), then ``checks``: each number compared, with its
limit. Exits non-zero, printing no result, without an accelerator or with
fewer chips than the cell asks for."""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()      # set-up is counted from here

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None, root: pathlib.Path = ROOT, allow_cpu: bool = False) -> int:
    """``root`` (where BENCHMARK.json is) and ``allow_cpu`` (such a run prints
    no device metric) are for the repo's own tests of the harness."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The persistent compile cache, at a fixed place inside the checkout
    # unless the environment names one. Set before jax starts.
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_compile_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from benchmarks.harness import device as device_lib
    from benchmarks.harness.cell import Cell
    from benchmarks.harness.compiles import CompileCounter

    cell = Cell.find(args.workload, root=root)
    try:
        dev = device_lib.require(cell.chips, allow_cpu=allow_cpu)
    except device_lib.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    compiles = CompileCounter()
    kind = cell.kind_module()
    out = kind.run(cell, dev, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t_process=T_PROCESS,
                   compiles=compiles)

    said = [f"check: {name} = {value!r} (limit {limit!r})"
            for name, value, limit in out.checks]
    print("\n".join(said), flush=True)
    print(f"compiles_in_window: {out.obs.get('compiles_in_window')}",
          flush=True)
    print(f"compiles in set-up: {out.obs.get('compiles_setup')} programs, "
          f"{out.obs.get('compile_s'):.2f} s (cache loads included)",
          flush=True)
    on_device = dev.platform != "cpu"
    metrics = {}
    if not on_device:
        # A CPU run (the repo's tests of the harness) gives counts only:
        # no time, rate, share or utilisation is printed under a metric's
        # name.
        sources = ("program_counter",)
    else:
        sources = ("program_counter", "program_span", "host_clock",
                   "device_trace")
    if not args.trace:
        for m in cell.end_to_end:
            if m["source"] not in sources:
                continue
            if m["name"] in out.end_to_end:
                metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            if m["source"] not in sources:
                continue
            value = cell.reader(m["name"]).read(out.obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dev.report()
    if args.trace and on_device:
        device.update(out.device_extra)
    line = {"correct": bool(out.correct), "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if args.trace and out.breakdown is not None and on_device:
        line["breakdown"] = out.breakdown
    # each number compared beside its limit, under a key of its own that
    # comes last in the line (the driver's record keeps the line's end)
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in out.checks}
    print(json.dumps(line), flush=True)
    # Each number compared beside its limit, as the last lines of standard
    # error too: of a run that is not correct the driver keeps the end of that.
    print("\n".join(said), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
