#!/usr/bin/env python
"""Measure pipeline-schedule bubble AND peak activation memory on the fake
8-CPU-device mesh.

Round-5 measured GPipe vs the interleaved virtual-stage schedule (the table
in PERF.md "Pipeline schedules"); this round adds the 1F1B rows (ISSUE 13)
and a ``peak_activation_bytes`` column — the 1F1B claim is memory as much
as bubble: its hand-written VJP stashes one stage-INPUT per microbatch and
re-linearizes the stage body per backward tick, so in-flight interiors are
bounded by the stage count where GPipe's jax.grad residuals grow with the
tick count.

Methodology (same as round 5): the ``plain`` base is the pp=1 layout on one
device; pipeline rows co-shard dp so every row uses all 8 fake devices
(fake devices share the host's cores, so step time tracks total EXECUTED
compute — bubbles show up as garbage-compute inflation). Every row runs in
a SUBPROCESS: the jax-0.4.x SPMD partitioner hard-aborts (F-check) on some
compositions (interleaved x dp>1 is the known one), and a subprocess turns
that into a typed ``error`` row instead of a dead bench.

A separate dp=1 parity phase pins losses BITWISE vs the pp=1 layout for
gpipe and 1f1b (co-shard rows regroup the dp loss reduction, a dp property
— so the bitwise pin runs at matched dp).

Verdict (nonzero exit on failure):
  - 1f1b step time <= interleaved at equal (pp, M) where both measured,
    and <= the measured gpipe row at equal (pp, M);
  - 1f1b peak_activation_bytes < gpipe's at equal (pp, M), and does not
    grow with M (bounded by pp, not M);
  - parity losses bitwise.

    python tools/pp_bubble_bench.py            # full table, one JSON/row
    python tools/pp_bubble_bench.py --smoke    # tier-1 twin (pp=2, tiny)
    python tools/pp_bubble_bench.py --schedule 1f1b   # filter rows
"""
from __future__ import annotations

import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))

import argparse
import json
import os
import re
import subprocess
import sys

_f = os.environ.get("XLA_FLAGS", "")
_m = re.search(r"host_platform_device_count=(\d+)", _f)
if _m is None:
    os.environ["XLA_FLAGS"] = (
        _f + " --xla_force_host_platform_device_count=8"
    ).strip()
elif _m.group(1) != "8":
    raise SystemExit(
        f"XLA_FLAGS already pins {_m.group(0)} but this bench needs 8 "
        f"fake CPU devices; unset XLA_FLAGS and rerun"
    )

# (pp, schedule, M, V) rows; dp co-shards to 8 total devices unless the
# row pins dp (the parity phase pins dp=1).
FULL_SHAPE = [
    "data.batch_size=8", "data.seq_len=128",
    "model.n_layers=8", "model.d_model=128", "model.d_ff=512",
]
SMOKE_SHAPE = [
    "data.batch_size=4", "data.seq_len=64",
    "model.n_layers=4", "model.d_model=64", "model.d_ff=128",
]


def _rows(smoke: bool, schedule: str):
    rows = []
    if smoke:
        combos = [
            (2, "gpipe", 2, 1, None),
            (2, "1f1b", 2, 1, None),
            (2, "1f1b", 4, 1, None),
            # Expected to record a typed error on jax-0.4.x boxes
            # (interleaved x dp>1 partitioner abort) — exercising exactly
            # the error path the subprocess isolation exists for.
            (2, "interleaved", 2, 2, None),
        ]
    else:
        combos = []
        for pp in (2, 4):
            combos += [(pp, "gpipe", M, 1, None) for M in (2, 4, 8)]
            combos += [(pp, "1f1b", M, 1, None) for M in (2, 4, 8)]
            combos += [
                (pp, "interleaved", M, V, None)
                for M in sorted({2, pp})
                for V in (2, 4)
                if M <= pp and 8 % (pp * V) == 0
            ]
            # dp=1 interleaved twin rows: on jax-0.4.x the dp co-shard
            # composition aborts, so the schedule's occupancy is also
            # measured on a pp-only mesh (base comparability caveat in
            # the module docstring applies — fake devices share cores).
            combos += [
                (pp, "interleaved", M, V, 1)
                for M in sorted({min(2, pp), pp})
                for V in (2,)
                if M <= pp and 8 % (pp * V) == 0
            ]
    if schedule != "all":
        combos = [c for c in combos if c[1] == schedule]
    for pp, sched, M, V, dp in combos:
        dp = dp if dp is not None else 8 // pp
        tag = f"pp{pp}-{sched}-M{M}" + (f"-V{V}" if sched == "interleaved"
                                        else "")
        if dp != 8 // pp:
            tag += f"-dp{dp}"
        rows.append({
            "layout": tag,
            "axes": {"pp": pp, "dp": dp, "pp_microbatches": M,
                     "pp_schedule": sched, "pp_virtual_stages": V},
            "pp": pp, "schedule": sched, "M": M, "V": V, "dp": dp,
        })
    return rows


def _predicted(sched: str, pp: int, M: int, V: int) -> float:
    """Ideal executed-compute inflation vs pp=1 (PERF.md arithmetic).
    GPipe/1F1B share the (M+pp-1)/M tick term; 1F1B's backward tick
    additionally re-linearizes the stage body (one extra fwd per bwd
    tick: x(2F+B)/(F+B) = 4/3 at B=2F)."""
    if sched == "interleaved":
        return (M + V * pp - 1) / (V * M)
    ticks = (M + pp - 1) / M
    return ticks * (4.0 / 3.0) if sched == "1f1b" else ticks


def run_row(spec: dict, steps: int, shape: list) -> dict:
    """Subprocess body: one measured row, one JSON line on stdout."""
    import time

    import jax

    jax.config.update("jax_platforms", "cpu")
    from orion_tpu.config import get_config
    from orion_tpu.train import Trainer

    overrides = [
        "runtime.platform=cpu",
        "train.num_steps=64", "train.log_interval=1000",
        "optimizer.warmup_steps=1",
    ] + shape + [f"parallel.{k}={v}" for k, v in spec.get("axes", {}).items()]
    t = Trainer(get_config("tiny-llama", overrides))
    out = dict(layout=spec["layout"])
    if spec.get("peak", True):
        rep = t.memory_report(assert_donation=False)
        if rep.get("available"):
            out["peak_activation_bytes"] = int(rep["temp_bytes"])
    state, _ = t.restore_or_init()
    state, m = t.train_step(state, t.global_batch(0))
    jax.block_until_ready(m["loss"])
    out["loss0"] = float(jax.device_get(m["loss"]))
    t0 = time.perf_counter()
    for s in range(1, steps + 1):
        state, m = t.train_step(state, t.global_batch(s))
    jax.block_until_ready(m["loss"])
    out["ms_per_step"] = round((time.perf_counter() - t0) / steps * 1e3, 1)
    return out


def _spawn_row(spec: dict, steps: int, shape: list, timeout: int) -> dict:
    """Run one row in a subprocess; a partitioner abort (or any crash)
    becomes a typed error row instead of killing the bench."""
    cmd = [sys.executable, os.path.abspath(__file__), "--row",
           json.dumps(spec), "--steps", str(steps),
           "--shape", json.dumps(shape)]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
    except subprocess.TimeoutExpired:
        return {"layout": spec["layout"], "error": f"timeout>{timeout}s"}
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                pass
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    detail = tail[-1][:200] if tail else f"rc={proc.returncode}"
    return {"layout": spec["layout"],
            "error": f"subprocess rc={proc.returncode}: {detail}"}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="tiny tier-1 twin: pp=2 rows, 2 timed steps")
    p.add_argument("--schedule", default="all",
                   choices=["all", "gpipe", "interleaved", "1f1b"])
    p.add_argument("--steps", type=int, default=0,
                   help="timed steps per row (default 4, smoke 2)")
    p.add_argument("--row", default=None, help=argparse.SUPPRESS)
    p.add_argument("--shape", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    shape = SMOKE_SHAPE if args.smoke else FULL_SHAPE
    if args.shape:
        shape = json.loads(args.shape)
    steps = args.steps or (2 if args.smoke else 4)

    if args.row:
        print(json.dumps(run_row(json.loads(args.row), steps, shape)),
              flush=True)
        return 0

    timeout = 300 if args.smoke else 900
    plain = _spawn_row({"layout": "plain", "axes": {}}, steps, shape,
                       timeout)
    print(json.dumps(plain), flush=True)
    if "error" in plain:
        print(json.dumps({"verdict": "pp_bubble", "ok": False,
                          "reason": "plain base failed"}))
        return 1
    base_ms, base_loss = plain["ms_per_step"], plain["loss0"]

    measured: dict[tuple, dict] = {}
    for spec in _rows(args.smoke, args.schedule):
        res = _spawn_row(spec, steps, shape, timeout)
        if "error" not in res:
            res["vs_plain"] = round(res["ms_per_step"] / base_ms, 2)
            res["predicted_inflation"] = round(
                _predicted(spec["schedule"], spec["pp"], spec["M"],
                           spec["V"]), 2)
            measured[(spec["pp"], spec["schedule"], spec["M"], spec["V"],
                      spec["dp"])] = res
        print(json.dumps(res), flush=True)

    # Parity phase: losses bitwise vs the pp=1 layout at matched dp=1.
    parity_ok = True
    for sched in (["1f1b"] if args.schedule == "1f1b"
                  else ["gpipe", "1f1b"]):
        if args.schedule not in ("all", sched):
            continue
        spec = {"layout": f"parity-pp2-{sched}-M2-dp1", "peak": False,
                "axes": {"pp": 2, "dp": 1, "pp_microbatches": 2,
                         "pp_schedule": sched}}
        res = _spawn_row(spec, 1, shape, timeout)
        ok = "error" not in res and res["loss0"] == base_loss
        parity_ok = parity_ok and ok
        res["bitwise_vs_pp1"] = ok
        print(json.dumps(res), flush=True)

    # Verdict.
    problems = []
    for (pp, sched, M, V, dp), r in sorted(measured.items()):
        if sched != "1f1b":
            continue
        gp = measured.get((pp, "gpipe", M, 1, dp))
        # A compute-bound run (a real chip) may
        # legitimately measure 1f1b at its own cost model — up to 4/3
        # gpipe's executed compute (the per-bwd-tick re-linearize) — so
        # a row only fails when it is BOTH slower than gpipe and above
        # its own predicted inflation: that combination means the
        # schedule is broken, not that the box is compute-bound.
        on_model = r["vs_plain"] <= r["predicted_inflation"] * 1.15
        if gp and r["ms_per_step"] > gp["ms_per_step"] * 1.05 \
                and not on_model:
            problems.append(
                f"1f1b pp{pp} M{M} slower than gpipe AND above its "
                f"cost model ({r['ms_per_step']} vs {gp['ms_per_step']} "
                f"ms; {r['vs_plain']}x vs predicted "
                f"{r['predicted_inflation']}x)")
        if gp and "peak_activation_bytes" in r \
                and "peak_activation_bytes" in gp \
                and r["peak_activation_bytes"] >= gp["peak_activation_bytes"]:
            problems.append(
                f"1f1b pp{pp} M{M} peak bytes not below gpipe "
                f"({r['peak_activation_bytes']} vs "
                f"{gp['peak_activation_bytes']})")
        for (pp2, sched2, M2, V2, dp2), il in measured.items():
            if sched2 == "interleaved" and (pp2, M2, dp2) == (pp, M, dp) \
                    and r["ms_per_step"] > il["ms_per_step"] * 1.10 \
                    and not on_model:
                problems.append(
                    f"1f1b pp{pp} M{M} slower than interleaved V{V2} AND "
                    f"above its cost model ({r['ms_per_step']} vs "
                    f"{il['ms_per_step']} ms)")
    fb = {(pp, M): r["peak_activation_bytes"]
          for (pp, sched, M, V, dp), r in measured.items()
          if sched == "1f1b" and "peak_activation_bytes" in r}
    for pp in (2, 4):
        ms = sorted(M for (p2, M) in fb if p2 == pp)
        if len(ms) >= 2 and fb[(pp, ms[-1])] > fb[(pp, ms[0])] * 1.15:
            problems.append(
                f"1f1b pp{pp} peak bytes grew with M "
                f"({fb[(pp, ms[0])]} @M{ms[0]} -> "
                f"{fb[(pp, ms[-1])]} @M{ms[-1]})")
    if not parity_ok:
        problems.append("parity losses not bitwise vs pp=1")
    ok = not problems
    print(json.dumps({"verdict": "pp_bubble", "ok": ok,
                      "problems": problems}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
