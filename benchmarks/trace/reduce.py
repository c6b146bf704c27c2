"""From a profiler trace to numbers: device busy time, idle gaps named by
what the host was doing, time per device operation and per program.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a plain dict (the
form the test fixture is stored in); ``reduce`` does the arithmetic on that
dict and needs nothing of JAX."""

from __future__ import annotations

import re
from typing import Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
BENCH_SPANS = ("bench.engine_step", "bench.observe", "bench.generate",
               "bench.train_step")
# Suffix chains XLA appends (fusion.123.remat2.clone.1): strip the remat /
# clone parts so a name survives recompilation better; keep the number.
_SUFFIX = re.compile(r"(\.(remat\d*|clone|unrolled(_\d+)?))+")


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                lines[line.name] = [
                    [_event_name(ev, line.name), int(ev.start_ns),
                     int(ev.duration_ns)]
                    for ev in line.events
                ]
            out["devices"][m.group(1)] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in BENCH_SPANS:
                        out["host"].append(
                            [ev.name, int(ev.start_ns), int(ev.duration_ns)])
    return out


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_KIND = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def short_name(text: str) -> str:
    """A device operation's stable short name. The profiler names an
    operation by its whole HLO line (``%fusion.12 = bf16[8,32]{...}
    fusion(...), kind=...``); keep the name, the kind and the first output
    shape: ``fusion.12_fusion_bf16_8_32_``."""
    name, sep, rest = text.partition(" = ")
    name = _SUFFIX.sub("", name.lstrip("%"))
    if not sep:
        return name[:96]
    shape = _SHAPE.search(rest)
    kind = _KIND.search(rest)
    parts = [name, kind.group(1) if kind else "",
             re.sub(r"[^A-Za-z0-9]+", "_", shape.group(0)) if shape else ""]
    return "_".join(x for x in parts if x)[:96]


def _event_name(ev, line_name: str) -> str:
    return short_name(ev.name) if line_name == OPS_LINE else ev.name


def union(intervals: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def leaves(events: list) -> list:
    """Events that contain no other event (a ``while`` or a ``call`` holds
    the operations of its body; counting both counts the time twice)."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, s, d) in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt[1] < s + d and nxt[1] + nxt[2] <= s + d \
                and d > 0:
            continue
        out.append((name, s, d))
    return out


def _top(pairs: dict, n: int = 10) -> list:
    return [[k, v] for k, v in
            sorted(pairs.items(), key=lambda kv: -kv[1])[:n]]


def reduce(events: dict, window_s: float) -> dict:
    devices = events["devices"]
    if not devices:
        raise RuntimeError("the trace holds no device plane")
    busy, op_s, module_s, module_n = [], {}, {}, {}
    gaps_by = {}
    host = sorted(events["host"], key=lambda e: e[1])
    for dev_id in sorted(devices):
        ops = devices[dev_id].get(OPS_LINE, [])
        merged = union((s, s + d) for _, s, d in ops if d > 0)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, s, d in leaves(ops):
            op_s[name] = op_s.get(name, 0.0) + d / 1e9 / len(devices)
        # A program is its name AND its fingerprint: the engine's programs
        # all carry the name ``jit__unknown`` and differ only there.
        for name, s, d in devices[dev_id].get(MODULES_LINE, []):
            module_s[name] = module_s.get(name, 0.0) + d / 1e9 / len(devices)
            module_n[name] = module_n.get(name, 0) + 1 / len(devices)
        if dev_id == min(devices):
            for (_, e0), (s1, _) in zip(merged, merged[1:]):
                mid = (e0 + s1) // 2
                span = next((n for n, s, d in host if s <= mid < s + d),
                            "host:_outside_any_bench_span")
                gaps_by[span] = gaps_by.get(span, 0.0) + (s1 - e0) / 1e9
    busy_s = sum(busy) / len(busy)
    if busy_s <= 0:
        raise RuntimeError("no operation ran on the device in the trace")
    return {
        "busy_s": busy_s, "window_s": window_s,
        "op_s": op_s, "module_s": module_s, "module_n": module_n,
        "breakdown": {"device_ops": _top(op_s), "idle_gaps": _top(gaps_by)},
    }
