#!/usr/bin/env python3
"""Parent against change on one machine: the benchmark's own command, run in
turn from two trees, every result line kept.

    git archive <parent> | tar -x -C _checkout        # a directory .gitignore lists
    chiprun --timeout 3400 -- python tools/ab_pairs.py \\
        --workload mixtral-8x7b.serve-batch --pairs 3 --trace-each \\
        --out chiprun_out/pr40/mixtral

Runs P C C P ... (``--pairs`` pairs, each pair one seed of its own, drawn
large), then with ``--trace-each`` one ``--trace 1`` run a side on one more
seed. Every run is a process of its own (one process holds the chip at a
time; this launcher stays off JAX), all sharing one compile cache
(``JAX_COMPILATION_CACHE_DIR``, default ``<repo>/.jax_compile_cache``), so
that a side pays its compiles once a call. Per run: the whole output under
``<out>/<n>-<side>-<seed>[-trace].log``, the result line with its side in
``<out>/runs.jsonl``; after a traced run ``tools/step_chain_trace.py``
reads the trace it left. A run that outlasts ``--run-timeout`` seconds (1300:
the driver stops a run at 1200) is killed and counted as failed, rc 124. At
the end: per side
the median and the quartile spread of every end-to-end metric, and the
pairs' ratios. The exit code is non-zero if a run failed or was not
``correct``."""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import random
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def result_line(text: str) -> dict:
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return {}


def numbers(res: dict) -> dict:
    """The result line's metrics by name (``{"value": ..., "unit": ...}``)."""
    return {k: v["value"] for k, v in (res.get("metrics") or {}).items()
            if isinstance(v, dict) and "value" in v}


def spread(values: list) -> float:
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--pairs", type=int, default=3)
    ap.add_argument("--parent", default=str(ROOT / "_checkout"))
    ap.add_argument("--change", default=str(ROOT))
    ap.add_argument("--trace-each", action="store_true")
    ap.add_argument("--seed0", type=int, default=None)
    ap.add_argument("--out", required=True)
    ap.add_argument("--metric", default="serve_tokens_per_s")
    ap.add_argument("--run-timeout", type=float, default=1300.0)
    args = ap.parse_args()

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = bench["run_seconds"]
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   str(ROOT / ".jax_compile_cache"))
    rng = random.Random(args.seed0 if args.seed0 is not None
                        else time.time_ns())
    trees = {"parent": args.parent, "change": args.change}
    plan = []
    for i in range(args.pairs):
        seed = rng.randrange(2 ** 30, 2 ** 31)
        order = ("parent", "change") if i % 2 == 0 else ("change", "parent")
        plan += [(side, seed, 0) for side in order]
    if args.trace_each:
        seed = rng.randrange(2 ** 30, 2 ** 31)
        plan += [("parent", seed, 1), ("change", seed, 1)]

    rows, bad = [], 0
    for n, (side, seed, trace) in enumerate(plan):
        cmd = [*bench["command"], "--workload", args.workload, "--seed",
               str(seed), "--seconds", str(seconds), "--trace", str(trace)]
        t0 = time.time()
        try:
            proc = subprocess.run(cmd, cwd=trees[side], env=env, text=True,
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT,
                                  timeout=args.run_timeout)
        except subprocess.TimeoutExpired as e:
            late = e.stdout or ""
            proc = subprocess.CompletedProcess(
                cmd, 124, late if isinstance(late, str) else late.decode())
        tag = f"{n:02d}-{side}-{seed}" + ("-trace" if trace else "")
        text = proc.stdout
        if trace and proc.returncode == 0:
            more = subprocess.run(
                [sys.executable, str(ROOT / "tools" / "step_chain_trace.py"),
                 "--under", str(pathlib.Path(trees[side]) / ".bench_trace")],
                cwd=ROOT, env=env, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            text += "\n-- tools/step_chain_trace.py\n" + more.stdout
        (out / f"{tag}.log").write_text(text)
        res = result_line(proc.stdout)
        row = {"n": n, "side": side, "seed": seed, "trace": trace,
               "rc": proc.returncode, "wall_s": round(time.time() - t0, 1),
               "result": res}
        rows.append(row)
        with open(out / "runs.jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")
        nums = numbers(res)
        ok = proc.returncode == 0 and res.get("correct", True)
        bad += not ok
        print(f"{tag}: rc {proc.returncode} correct {res.get('correct')} "
              f"{args.metric} {nums.get(args.metric)} setup_s "
              f"{nums.get('setup_s')} idle "
              f"{nums.get('device_idle_pct.batch')} wall {row['wall_s']}",
              flush=True)

    untraced = [r for r in rows if not r["trace"] and r["rc"] == 0]
    for side in trees:
        mine = [numbers(r["result"]) for r in untraced if r["side"] == side]
        for metric in (args.metric, "setup_s"):
            vals = [m[metric] for m in mine if metric in m]
            if vals:
                print(f"{side} {metric}: n {len(vals)} median "
                      f"{statistics.median(vals)} min {min(vals)} max "
                      f"{max(vals)} quartile spread "
                      f"{100 * spread(vals):.3f} %")
    by_seed: dict = {}
    for r in untraced:
        by_seed.setdefault(r["seed"], {})[r["side"]] = numbers(
            r["result"]).get(args.metric)
    for seed, pair in by_seed.items():
        if pair.get("parent") and pair.get("change"):
            print(f"pair {seed}: {pair['parent']} -> {pair['change']} "
                  f"({100 * (pair['change'] / pair['parent'] - 1):+.3f} %)")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
