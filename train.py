#!/usr/bin/env python
"""Training entry point (reference top-level ``train.py``, BASELINE.json:5,7).

Usage:
    python train.py --preset gpt2-125m [section.key=value ...]

Examples:
    python train.py --preset tiny train.num_steps=50          # CPU smoke
    python train.py --preset llama3-8b-dp                      # v5p-64 DDP
    python train.py --preset llama3-70b-fsdp parallel.fsdp=64  # ZeRO-3
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    parser.add_argument("--preset", default="gpt2-125m")
    parser.add_argument("--list-presets", action="store_true")
    parser.add_argument("--print-config", action="store_true")
    parser.add_argument(
        "--max-restarts", type=int, default=None,
        help="supervisor mode: restart-and-resume after failures, up to N "
             "times (resumes from the newest intact checkpoint); default "
             "from train.max_restarts",
    )
    parser.add_argument(
        "--trace", metavar="PATH", default=None,
        help="export a Chrome trace-event JSON of per-step host phases "
             "(data/dispatch/guard/ckpt) to PATH when fit ends; sugar for "
             "train.trace=true + train.trace_path=PATH (combine with "
             "train.profile_steps for a device profile over the same "
             "window)",
    )
    parser.add_argument(
        "overrides", nargs="*", help="dotted config overrides, e.g. model.n_layers=4"
    )
    args = parser.parse_args(argv)

    from orion_tpu.config import get_config, list_presets

    if args.list_presets:
        print("\n".join(list_presets()))
        return 0

    overrides = list(args.overrides)
    if args.trace is not None:
        overrides += ["train.trace=true", f"train.trace_path={args.trace}"]
    cfg = get_config(args.preset, overrides)
    if args.print_config:
        print(cfg.to_json())
        return 0

    from orion_tpu.train import Trainer

    if overrides:
        print("overrides: " + " ".join(overrides), flush=True)
    trainers = []

    def make_trainer():
        t = Trainer(cfg)
        if not trainers:
            # What the run actually landed on, up front: the backend
            # initialize() settled on and the mesh built over it.
            print("runtime: " + json.dumps(dataclasses.asdict(t.runtime)))
            print("mesh: " + json.dumps(
                {a: n for a, n in t.mesh.shape.items() if n > 1} or {"dp": 1}
            ), flush=True)
        trainers.append(t)
        return t

    max_restarts = (
        args.max_restarts if args.max_restarts is not None
        else cfg.train.max_restarts
    )
    if max_restarts > 0:
        from orion_tpu.runtime.fault import run_with_restarts

        if not cfg.checkpoint.directory or not cfg.checkpoint.restore:
            parser.error(
                "--max-restarts needs checkpoint.directory set (and "
                "checkpoint.restore=true): without it every restart would "
                "silently retrain from step 0"
            )
        # Thread the supervisor context into each attempt's step log:
        # restart count in the metrics extras, the previous attempt's
        # fault reason on the resume log line.
        last_fault = {"reason": None}

        def _on_retry(attempt, exc):
            last_fault["reason"] = f"{type(exc).__name__}: {exc}"

        history = run_with_restarts(
            lambda attempt: make_trainer().fit(
                restart_info=(attempt, last_fault["reason"])
            ),
            max_restarts=max_restarts,
            on_retry=_on_retry,
        )
    else:
        history = make_trainer().fit()
    if history:
        last = history[-1]
        mfus = [h.mfu for h in history if h.mfu is not None]
        mfu = (
            f"{sum(mfus) / len(mfus) * 100:.2f}%" if mfus
            else "not measured"
        )
        print(
            f"done: {last.step} steps, final loss {last.loss:.4f}, "
            f"mean MFU {mfu}"
        )
    print("memory: " + json.dumps(trainers[-1].device_memory))
    return 0


if __name__ == "__main__":
    sys.exit(main())
