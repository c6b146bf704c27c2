"""The program's own host spans beside the device planes of one trace.

``reduce.py`` names a device idle gap by the benchmark's three ``bench.*``
spans. The engine also enters one ``orion/<phase>`` annotation per phase of a
step (``orion_tpu.obs.PhaseClock``), on the profiler's clock. This module
reads them out of the ``.xplane.pb`` the run's own ``capture.Trace`` just
wrote and gives

- every instant of a device idle gap to the INNERMOST host span at that
  instant (``orion/decode/build`` inside ``orion/step`` inside
  ``bench.engine_step``): a gap between two programs runs through the end of
  one run span, the fetch, the emission loop, the benchmark's own spans and
  the next step's admission, and each gets its part. (Giving a whole gap to
  the span at its midpoint, as ``reduce`` does with its three spans, read
  0 s in ``bench.generate``, which is a fifth of every gap between steps.)
- each program run (an ``XLA Modules`` event) to the ``orion/*/run`` span it
  overlaps most. Not "that holds its start": the profile's device clock ran
  0.7-0.9 ms ahead of its host clock on the v5e (a decode program "starts"
  before the span that launched it), so a gap's parts are good to about
  that much too.

``load`` turns the file into the plain dict the test fixtures are stored in;
``attribute`` does the arithmetic on that dict and needs nothing of JAX. A
program without such spans (the parent of the PR that added them) gives a
trace with no ``orion/`` event: ``for_obs`` returns None and the readers
leave their metric out.
"""

from __future__ import annotations

import bisect
import glob
import os
import pathlib
from typing import Optional

from benchmarks.trace import reduce

ROOT = pathlib.Path(__file__).resolve().parents[2]
PREFIXES = ("orion/", "bench.")
OUTSIDE = "host:_outside_any_span"


def newest_trace(root: pathlib.Path = ROOT / ".bench_trace") -> Optional[str]:
    files = glob.glob(str(root / "**" / "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        m = reduce.DEVICE_PLANE.match(plane.name)
        if m:
            out["devices"][m.group(1)] = {
                line.name: [[reduce._event_name(ev, line.name),
                             int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events]
                for line in plane.lines
                if line.name in (reduce.OPS_LINE, reduce.MODULES_LINE)
            }
        elif plane.name.startswith("/host:"):
            out["host"] += [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for line in plane.lines for ev in line.events
                if ev.name.startswith(PREFIXES)
            ]
    return out


def innermost(host: list, at: int) -> str:
    """The shortest host span that holds the instant ``at``."""
    inside = [(d, name) for name, s, d in host if s <= at < s + d]
    return min(inside)[1] if inside else OUTSIDE


def attribute(events: dict) -> Optional[dict]:
    """``idle_by_span``: seconds of device idle time (the gaps between the
    merged operation intervals of the first device, as ``reduce`` takes
    them) by the innermost host span at each instant. ``run_module_s``:
    device seconds of the programs by the ``orion/*/run`` span each
    overlaps most."""
    host = events["host"]
    if not any(name.startswith("orion/") for name, _, _ in host):
        return None
    dev = events["devices"][min(events["devices"])]
    merged = reduce.union(
        (s, s + d) for _, s, d in dev.get(reduce.OPS_LINE, []) if d > 0)
    # The host's timeline, flat: between two neighbouring starts or ends
    # of spans the innermost span does not change.
    cuts = sorted({t for _, s, d in host for t in (s, s + d)})
    names = [innermost(host, (a + b) // 2) for a, b in zip(cuts, cuts[1:])]
    idle: dict = {}
    for (_, at), (end, _) in zip(merged, merged[1:]):
        i = bisect.bisect_right(cuts, at)     # cuts[i - 1] <= at < cuts[i]
        while at < end:
            upto = min(end, cuts[i]) if i < len(cuts) else end
            name = names[i - 1] if 0 < i < len(cuts) else OUTSIDE
            idle[name] = idle.get(name, 0.0) + (upto - at) / 1e9
            at, i = upto, i + 1
    runs = [(name, s, s + d) for name, s, d in host
            if name.startswith("orion/") and name.endswith("/run")]
    run_module_s: dict = {}
    for _, s, d in dev.get(reduce.MODULES_LINE, []):
        overlap, name = max(
            ((min(s + d, e1) - max(s, s1), name) for name, s1, e1 in runs),
            default=(0, OUTSIDE))
        if overlap > 0:
            run_module_s[name] = run_module_s.get(name, 0.0) + d / 1e9
    return {"idle_by_span": idle, "idle_s": sum(idle.values()),
            "run_module_s": run_module_s}


def unattributed(idle_by_span: dict) -> dict:
    """The idle time no phase of the engine accounts for: outside the engine
    (the benchmark's own spans) or in the part of ``orion/step`` that no
    child covers."""
    return {k: v for k, v in idle_by_span.items()
            if not k.startswith("orion/") or k == "orion/step"}


def engine_gap_s(timing: dict) -> Optional[float]:
    """The engine's own estimate of device idle time: the host time of its
    steps during which no dispatch program was in flight, which is every
    in-step bucket less the ``*/run`` leaves."""
    if "prefill_run_s" not in timing:
        return None
    in_step = sum(timing[k] for k in (
        "host_s", "prefill_s", "device_s", "spill_s", "restore_s",
        "page_in_s"))
    return in_step - sum(timing[k] for k in (
        "prefill_run_s", "decode_run_s", "verify_run_s", "mixed_device_s"))


_CACHE: dict = {}


def for_obs(obs: dict) -> Optional[dict]:
    """``attribute`` of this run's own trace (the newest under
    ``.bench_trace/``), once per process; prints the table of idle time by
    span the first time, above the result line."""
    if not obs.get("trace"):
        return None
    path = newest_trace()
    if path is None:
        return None
    if path not in _CACHE:
        _CACHE[path] = got = attribute(load(path))
        if got is not None:
            say(got, obs["trace"].get("timing") or {})
    return _CACHE[path]


def say(got: dict, timing: dict) -> None:
    idle, total = got["idle_by_span"], got["idle_s"] or 1e-12
    print(f"device idle time by innermost host span ({total:.4f} s in all):")
    for name, s in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"  {name:<28s} {s:9.4f} s {100 * s / total:6.1f} %")
    inside = sum(s for k, s in idle.items()
                 if k.startswith("orion/") or k == "bench.engine_step")
    in_runs = sum(s for k, s in idle.items() if k.endswith("/run"))
    gap = engine_gap_s(timing)
    if gap is not None:
        print(f"  inside bench.engine_step: {inside:.4f} s idle, {in_runs:.4f} "
              f"s of it inside */run spans (launch and wake-up); the engine's "
              f"own estimate (step time less its */run spans) {gap:.4f} s "
              f"over {timing['steps']} steps", flush=True)
