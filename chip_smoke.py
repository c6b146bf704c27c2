#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls — the
trainer takes a few steps (``train.py``), the server answers a few requests
(``generate.py``) — at Mistral-7B's published widths (d_model 4096, 32 query /
8 kv heads x 128, d_ff 14336, vocab 32000, sliding window 4096, compiled
Pallas kernels). Only depth and layout are cut to one chip, weights are random
from a seed, data is synthetic from a seed; every override is printed by the
entry point itself. On a host with four or more chips the trainer also runs
under ``parallel.fsdp=4``.

    python chip_smoke.py          # through the chip tool; ~2 min on a chip

There is no CPU mode: each leg passes ``runtime.platform=tpu``, and without a
TPU the first leg fails within seconds, naming the platform JAX found.

This parent never imports jax or orion_tpu: a process that has touched JAX
holds the chip, so the legs run as sequential children. They share one
persistent compile cache (``JAX_COMPILATION_CACHE_DIR`` where set, else the
checkout's ``.jax_compile_cache/``). Facts come from the sinks the CLIs
already have (``train.metrics_jsonl``, ``inference.metrics_jsonl``) and the
``runtime:`` / ``memory:`` lines they print; logs and sinks land under
``chiprun_out/chip_smoke/``.

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
mean every check of every leg passed.
"""

from __future__ import annotations

import json
import math
import pathlib
import random
import re
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
PRESET = "mistral-7b-fsdp"
LEG_TIMEOUT_S = 900   # per child; the whole smoke must end inside 1200 s

TRAIN_STEPS = 8
TRAIN_COMMON = [
    "runtime.platform=tpu", "data.seq_len=8192",   # 2x the 4096 window
    "optimizer.moment_dtype=bfloat16", "optimizer.warmup_steps=2",
    f"train.num_steps={TRAIN_STEPS}", "train.log_interval=1",
]
# One chip: 3 of 32 layers = 0.92 B parameters, 6.8 GiB of train state.
TRAIN_1CHIP = ["parallel.fsdp=1", "model.n_layers=3", "data.batch_size=1"]
# Four chips, ZeRO-3: 12 layers = 2.9 B parameters, one sequence per chip.
TRAIN_FSDP4 = ["parallel.fsdp=4", "model.n_layers=12", "data.batch_size=4"]

NEW_TOKENS = 64
# Prompt lengths, unlike on purpose; the first is longer than the 4096
# window, so paged decode must skip that request's dead pages.
PROMPT_LENS = (4200, 37, 700, 1500, 96)
# 16 of 32 layers in bf16 = 7.5 GB of weights; 64 KiB of KV per token, so
# the 512-page pool is 2 GiB. nan_guard makes a non-finite logit a typed
# error outcome — the engine's own check that what comes out is finite.
SERVE = [
    "runtime.platform=tpu", "model.n_layers=16",
    "model.param_dtype=bfloat16", "inference.max_seq_len=8192",
    "inference.num_pages=512", "inference.max_batch_size=8",
    "inference.nan_guard=true",
]


class LegFailed(Exception):
    pass


# What a leg can die of: its child failed, or its output or sinks are not
# what the checks expect to parse.
LEG_ERRORS = (LegFailed, OSError, KeyError, IndexError, ValueError)


def run_leg(name: str, argv: list[str]) -> str:
    """Run one child to its end; returns its stdout. Raises LegFailed with
    the tail of its stderr on a non-zero exit or a timeout (the child is
    killed either way — nothing this script starts outlives it)."""
    log = OUT / f"{name}.log"
    t0 = time.monotonic()
    try:
        r = subprocess.run(
            [sys.executable, *argv], cwd=ROOT, capture_output=True,
            text=True, timeout=LEG_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired as e:
        raise LegFailed(f"{name}: killed after {LEG_TIMEOUT_S}s") from e
    log.write_text(r.stdout + "\n--- stderr ---\n" + r.stderr)
    if r.returncode != 0:
        tail = "\n".join(r.stderr.strip().splitlines()[-6:])
        raise LegFailed(
            f"{name}: exit code {r.returncode} after "
            f"{time.monotonic() - t0:.0f}s\n{tail}"
        )
    return r.stdout


def tagged(stdout: str, tag: str):
    """The JSON payload of the entry point's ``<tag>: {...}`` line."""
    for line in stdout.splitlines():
        if line.startswith(tag + ": "):
            return json.loads(line[len(tag) + 2:])
    raise LegFailed(f"no '{tag}:' line in the entry point's output")


def device_of(runtime: dict) -> dict:
    if runtime["platform"] != "tpu":
        raise LegFailed(f"ran on platform={runtime['platform']!r}, not tpu")
    return {
        "platform": runtime["platform"], "kind": runtime["device_kind"],
        "count": runtime["global_devices"],
    }


def say(device: dict, text: str) -> None:
    print(
        f"[platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={device['count']}] {text}", flush=True,
    )


def train_leg(name: str, layout: list[str], n_devices: int) -> dict:
    sink = OUT / f"{name}.jsonl"
    sink.unlink(missing_ok=True)
    out = run_leg(name, [
        "train.py", "--preset", PRESET, *TRAIN_COMMON, *layout,
        f"train.metrics_jsonl={sink}",
    ])
    device = device_of(tagged(out, "runtime"))
    rows = [json.loads(line) for line in sink.read_text().splitlines()]
    losses = [r["loss"] for r in rows]
    memory = tagged(out, "memory")
    in_use = [m.get("bytes_in_use", 0) for m in memory]
    checks = {
        f"{TRAIN_STEPS} steps taken": len(rows) == TRAIN_STEPS,
        "loss finite at every step": all(math.isfinite(x) for x in losses),
        "last loss below the first": bool(losses) and losses[-1] < losses[0],
        "zero compiles after step 1": all(
            r["compiles"] == 0 for r in rows[1:]
        ),
        f"state on all {n_devices} device(s)": (
            len(in_use) == n_devices and min(in_use) > 0
            and max(in_use) < 1.5 * min(in_use)
        ),
    }
    steady = sorted(r["step_time_s"] for r in rows[1:])
    say(device, (
        f"{name}: mesh={json.dumps(tagged(out, 'mesh'))} "
        f"loss {losses[0]:.4f} -> {losses[-1]:.4f} over {len(rows)} steps; "
        f"first step {rows[0]['step_time_s']:.1f}s of which "
        f"{rows[0]['compile_s']:.1f}s in {rows[0]['compiles']} compiles; "
        f"median steady step {steady[len(steady) // 2]:.3f}s; "
        f"bytes_in_use/device {in_use}; peak_bytes_in_use/device "
        f"{[m.get('peak_bytes_in_use') for m in memory]}"
    ))
    failed = [c for c, ok in checks.items() if not ok]
    if failed:
        raise LegFailed(f"{name}: failed checks: {failed}")
    return device


def serve_leg() -> dict:
    sink = OUT / "serve.jsonl"
    sink.unlink(missing_ok=True)
    rng = random.Random(0)
    prompts = [
        ",".join(str(rng.randrange(1, 32000)) for _ in range(n))
        for n in PROMPT_LENS
    ]
    argv = ["generate.py", "--preset", PRESET,
            "--max-new-tokens", str(NEW_TOKENS)]
    for p in prompts:
        argv += ["--tokens", p]
    t0 = time.monotonic()
    out = run_leg("serve", argv + SERVE + [f"inference.metrics_jsonl={sink}"])
    wall = time.monotonic() - t0
    device = device_of(tagged(out, "runtime"))
    # "request i: prompt=[...] -> generated=[...]" + " [outcome]" unless
    # the request completed.
    answers = re.findall(
        r"^request \d+: .* -> generated=\[([^\]]*)\](.*)$", out, re.M
    )
    counts = [len(g.split(",")) if g else 0 for g, _ in answers]
    stats = json.loads(sink.read_text().splitlines()[-1])
    counters = {
        k: stats.get(f"serve.{k}") for k in
        ("dispatch_faults", "dispatch_fallbacks", "failed_steps",
         "quarantined_requests")
    }
    checks = {
        f"{len(prompts)} requests answered": len(answers) == len(prompts),
        "every request completed": all(tag == "" for _, tag in answers),
        f"{NEW_TOKENS} new tokens each": counts == [NEW_TOKENS] * len(prompts),
        f"robust counters all zero {counters}": all(
            v == 0 for v in counters.values()
        ),
        "speculation not auto-disabled":
            not stats.get("serve.spec_disabled_reason"),
    }
    windows = max(stats["serve.windows"], 1)
    say(device, (
        f"serve: {len(answers)} requests (prompts {list(PROMPT_LENS)}) x "
        f"{NEW_TOKENS} new tokens in {wall:.0f}s wall, compiles included; "
        f"prefill_s {stats['serve.prefill_s']:.2f}, {stats['serve.windows']} "
        f"decode windows of {stats['serve.decode_window']}: device_s "
        f"{stats['serve.device_s'] / windows:.4f}/window, host_s "
        f"{stats['serve.host_s'] / windows:.4f}/window; peak_bytes_in_use "
        f"{stats.get('hbm.peak_bytes_in_use')}"
    ))
    failed = [c for c, ok in checks.items() if not ok]
    if failed:
        raise LegFailed(f"serve: failed checks: {failed}")
    return device


def main() -> int:
    OUT.mkdir(parents=True, exist_ok=True)
    failures = []
    try:
        device = train_leg("train", TRAIN_1CHIP, 1)
    except LEG_ERRORS as e:
        # Nothing ran on a TPU (or the trainer is broken outright): the
        # other legs would only repeat the failure.
        print(f"chip_smoke: FAILED {e}", file=sys.stderr)
        return 1
    legs = [("serve", serve_leg)]
    if device["count"] >= 4:
        legs.append(
            ("train_fsdp4", lambda: train_leg("train_fsdp4", TRAIN_FSDP4, 4))
        )
    else:
        say(device, f"train_fsdp4: not run, found {device['count']} "
                    f"device(s) and the leg needs 4")
    for name, leg in legs:
        try:
            leg()
        except LEG_ERRORS as e:
            failures.append(str(e))
            say(device, f"FAILED {e}")
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
