#!/usr/bin/env python3
"""What the builder of the benchmark measures with, outside ``run.py``; the
driver never calls this file.

    python3 benchmarks/tools.py sets --workload W --seconds S --runs 6 \\
        --first-seed N [--trace-first] --out chiprun_out/sets_W.jsonl
    python3 benchmarks/tools.py calibrate --workload W --first-seed N --seeds 12

``sets`` makes two sets of ``--runs`` runs of one cell, the same seeds in
both, each run a process of its own (``run.py`` as the driver calls it), and
prints every metric's quartile spread per set as PERF.md defines it.
``calibrate`` reads the numbers of a cell's output check over several seeds
in one process, for the program and for the control, so that a limit can be
set between them."""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def spread(values: list) -> float:
    """Distance between the first and third quartile over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, ValueError):
        result = {"error": proc.stderr[-2000:]}
    return {"seed": seed, "trace": trace, "rc": proc.returncode,
            "wall_s": time.monotonic() - t0, "result": result,
            "said": [l for l in lines[:-1]
                     if l.startswith(("check:", "compiles_in_window",
                                      "window:", "set-up", "positions"))]}


def sets(args) -> int:
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    seeds = [args.first_seed + i for i in range(args.runs)]
    rows = []
    with open(out, "a", encoding="utf-8") as f:
        plan = [("trace", seeds[0], 1)] if args.trace_first else []
        plan += [(k, s, 0) for k in (0, 1) for s in seeds]
        for which, seed, trace in plan:
            row = dict(one_run(args.workload, seed, args.seconds, trace),
                       set=which)
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            f.flush()
            r = row["result"]
            print(which, seed, round(row["wall_s"], 1), r.get("correct"),
                  {k: v["value"] for k, v in r.get("metrics", {}).items()},
                  [l for l in row["said"] if l.startswith("compiles")],
                  r.get("error", ""), flush=True)
    for which in (0, 1):
        mine = [r["result"] for r in rows if r["set"] == which]
        for name in sorted(mine[0].get("metrics", {})) if mine else ():
            vals = [r["metrics"][name]["value"] for r in mine]
            print(f"set {which} {name}: median {statistics.median(vals)!r} "
                  f"quartile spread {100 * spread(vals):.3f} %", flush=True)
    return 0 if all(r["rc"] == 0 and r["result"].get("correct")
                    for r in rows) else 1


def calibrate(args) -> int:
    from benchmarks.harness import device as device_lib
    from benchmarks.harness.cell import Cell

    cell = Cell.find(args.workload)
    dev = device_lib.require(cell.chips)
    kind = cell.kind_module()
    if cell.mix["kind"] == "train":
        for i in range(args.seeds):
            s = args.first_seed + i
            cfg, trainer, state = kind.build(cell, dev, s)
            batch = kind._batches(trainer, cfg, cell.mix, s)[0]
            out = kind.check_numbers(trainer, cfg, cell.reference(),
                                     cell.config, state, batch,
                                     control="int8")
            print("calibrate: " + json.dumps({"seed": s, **out}), flush=True)
            del state, trainer, batch
        return 0
    from benchmarks.reference import weights

    cfg, engine = kind.build_engine(cell, args.first_seed)
    margin_min = cell.config["correct"].get("router_margin_min", 0.0)
    for i in range(args.seeds):
        s = args.first_seed + i
        if i:
            engine.params = None
            engine.params = weights.for_cell(cell, cfg, s)
        n = kind.probe_numbers(engine, cell.reference(), cell.config,
                               cell.mix, s, control="int8")
        broken = kind.probe_numbers(
            engine, cell.reference(), cell.config,
            dict(cell.mix, probe_prompts=[cell.mix["probe_prompts"][0]]), s,
            break_link=True)
        print("calibrate: " + json.dumps({
            "seed": s, **n,
            "sound": kind.judged(n, margin_min),
            "control": kind.judged(n, margin_min, errs="control_err"),
            "link_broken": kind.judged(broken, margin_min),
        }), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="tool", required=True)
    a = sub.add_parser("sets")
    a.add_argument("--workload", required=True)
    a.add_argument("--seconds", type=float, required=True)
    a.add_argument("--runs", type=int, default=6)
    a.add_argument("--first-seed", type=int, required=True)
    a.add_argument("--trace-first", action="store_true")
    a.add_argument("--out", required=True)
    a.set_defaults(fn=sets)
    c = sub.add_parser("calibrate")
    c.add_argument("--workload", required=True)
    c.add_argument("--first-seed", type=int, required=True)
    c.add_argument("--seeds", type=int, default=12)
    c.set_defaults(fn=calibrate)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
