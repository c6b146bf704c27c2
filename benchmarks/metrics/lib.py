"""What the per-layer readers share: each reader module is a few lines that
name what it reads; the arithmetic lives here and in ``flops.py``. A reader
returns None where it finds nothing to read, and the harness then leaves the
metric out of the line."""

from __future__ import annotations

import re
from typing import Optional

from benchmarks.harness.stats import percentile


def ms_p(samples, p: float) -> Optional[float]:
    return 1e3 * percentile(samples, p) if samples else None


def idle_pct(obs) -> Optional[float]:
    tr = obs.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def compiles_in_window(obs) -> Optional[float]:
    return obs.get("compiles_in_window")


def timing_per_step_ms(obs, key: str) -> Optional[float]:
    if not obs.get("steps"):
        return None
    return 1e3 * obs["timing"][key] / obs["steps"]


def op_seconds(obs, pattern: str) -> Optional[float]:
    tr = obs.get("trace")
    if not tr:
        return None
    rx = re.compile(pattern)
    hit = [v for k, v in tr["op_s"].items() if rx.search(k)]
    return sum(hit) if hit else None


def decode_program(obs):
    """(seconds, runs) of the decode-window program in the trace.

    A program whose name carries ``decode_window`` is it (several
    fingerprints of that name, one a shape, are added up). The engine jits
    ``partial`` objects, so until the program names its jits they all carry
    the name ``jit__unknown`` and a fingerprint that changes with every
    edit; the decode program is then the ``unknown`` one that ran most often
    (one shape, every step; each prefill shape is a program of its own)."""
    tr = obs.get("trace")
    if not tr:
        return None
    named = [k for k in tr["module_n"] if "decode_window" in k]
    if named:
        return (sum(tr["module_s"][k] for k in named),
                sum(tr["module_n"][k] for k in named))
    runs = {k: n for k, n in tr["module_n"].items() if "unknown" in k}
    if not runs:
        return None
    key = max(runs, key=lambda k: (runs[k], tr["module_s"][k]))
    return tr["module_s"][key], runs[key]


def decode_step_ms(obs) -> Optional[float]:
    """Device time of the fused decode-window program per token step."""
    got = decode_program(obs)
    if got is None:
        return None
    seconds, runs = got
    return 1e3 * seconds / (runs * obs["decode_window"])
