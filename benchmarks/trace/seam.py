"""The device's idle time split at the dispatch seam, with no clock offset in
it.

Every program the engine runs goes through two calls, and since ISSUE 56 each
is a host span of its own: ``orion/<path>/launch`` around the program's call
and ``orion/<path>/wait`` around ``block_until_ready``. The device runs
programs in launch order, so launches pair with the ``jit_orion_*`` program
runs of the device plane by ORDER, and a gap between program k's end and
program k+1's start, both on the DEVICE's clock, is

    (wait returns - program k ends)          wake-up         [two clocks]
  + (launch k+1 begins - wait returns)       host_between    [host clock only]
  + (program k+1 starts - launch k+1 begins) launch latency  [two clocks]

The middle term lies wholly on the host's clock and splits by the innermost
host span at each instant (``host_spans.innermost``'s rule); the two outer
terms' SUM is ``gap - host_between``, in which the clocks' offset cancels:
the ``seam``, what a dispatch ahead removes outright. The same pairs bracket
the offset itself (device clock minus host clock): a program cannot start
before its launch began (``hi``), nor a wait return before its program ended
(``lo``).

``split`` does the arithmetic on the dict ``host_spans.load`` returns and
needs nothing of JAX. A program without the spans (the parent of the PR that
added them) gives None, and the readers leave their metrics out.
``python3 -m benchmarks.trace.seam [<trace.xplane.pb>]`` prints the table for
the newest trace under ``.bench_trace/``.
"""

from __future__ import annotations

import bisect
import re
import sys
from typing import Optional

from benchmarks.trace import host_spans, reduce

# The programs a path's launch may have started (``DispatchExecutor.run``'s
# ``path`` -> the jit's name, ``orion_tpu/obs/parts.py:PROGRAM_NAMES``; a
# block model's ``denoise`` program goes under ``decode``).
LAUNCHES = {
    "prefill": ("orion_prefill",),
    "decode": ("orion_decode_window", "orion_denoise_block"),
    "verify": ("orion_verify",),
    "mixed": ("orion_mixed",),
    "mixed_verify": ("orion_mixed_verify",),
    "fold": ("orion_fold",),
}
OWN = "jit_orion_"
_SEAM = re.compile(r"^orion/(\w+)/(launch|wait)$")
_MODULE = re.compile(r"^jit_(\w+?)(\(\d+\))?$")
TRIM = 4            # launches or runs a trace may hold without their half
NEGATIVE_NS = 50_000


def _program(module: str) -> str:
    m = _MODULE.match(module)
    return m.group(1) if m else module


def _measure(merged: list, starts: list, a: int, b: int) -> int:
    """Nanoseconds of the merged operation intervals inside [a, b)."""
    total, i = 0, max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(merged) and merged[i][0] < b:
        total += max(0, min(merged[i][1], b) - max(merged[i][0], a))
        i += 1
    return total


def _covered(launches: list, seam: list) -> list:
    """For each launch of ``launches`` [(begin, path)], the return of the
    first wait that covers it: its own, or that of a program launched after
    it (the device runs programs in launch order: a fold's wait is the
    window's). ``seam``: every launch and wait span, in time order. None
    where no wait of the trace covers it."""
    index = {begin: k for k, (begin, _) in enumerate(launches)}
    covered: list = [None] * len(launches)
    newest: dict = {}
    for begin, end, path, kind in seam:
        if kind == "launch":
            if begin in index:
                newest[path] = index[begin]
            continue
        k = newest.get(path)
        while k is not None and k >= 0 and covered[k] is None:
            covered[k] = end
            k -= 1
    return covered


def _bracket(launches: list, runs: list, covered: list):
    """(lo, hi) of (device clock - host clock) in ns: no program started
    before its launch began, no wait returned before its program ended."""
    hi = min(start - begin for (begin, _), (start, _, _) in zip(launches, runs))
    late = [end - at for (_, end, _), at in zip(runs, covered) if at is not None]
    return (max(late) if late else None), hi


def _pair(launches: list, runs: list, seam: list):
    """``launches`` [(begin, path)] and ``runs`` [(start, end, module)], both
    in time order, trimmed to whole pairs: up to ``TRIM`` of the longer
    list's entries are dropped at its two ends (a trace that opens or closes
    mid-dispatch), in the way that pairs every launch with a program its
    path launches and contradicts no causality (the narrowest bracket, if
    several do). Returns (launches, runs, covered, lo, hi), or a sentence."""
    extra = len(launches) - len(runs)
    counts = f"{len(launches)} launch spans against {len(runs)} program runs"
    if abs(extra) > TRIM:
        return f"{counts}: more than {TRIM} apart"
    fits = []
    for head in range(abs(extra) + 1):
        ls, rs = launches, runs
        if extra > 0:
            ls = launches[head:len(launches) - (extra - head)]
        elif extra < 0:
            rs = runs[head:len(runs) + extra + head]
        if not ls or not all(_program(mod) in LAUNCHES.get(path, ())
                             for (_, path), (_, _, mod) in zip(ls, rs)):
            continue
        covered = _covered(ls, seam)
        lo, hi = _bracket(ls, rs, covered)
        if extra == 0 or lo is None or lo <= hi:
            fits.append((hi - (lo if lo is not None else hi),
                         (ls, rs, covered, lo, hi)))
    if not fits:
        return (f"{counts}, and no trim of {abs(extra)} pairs every launch "
                f"with a program its path launches under one clock offset")
    return min(fits, key=lambda f: f[0])[1]


def split(events: dict, say_why=print) -> Optional[dict]:
    """The first device's idle time between and inside the engine's own
    program runs (seconds, and counts):

    - ``seam_s``: the gaps less ``host_between``: wake-up and launch latency;
    - ``host_in_step`` / ``host_outside``: ``host_between`` by the innermost
      host span at each instant, inside ``orion/step`` and outside it;
    - ``inside_s``: idle time INSIDE program runs (a run's duration less the
      union of the operations within it);
    - ``gaps``: {kind: [count, seconds]} for gaps after a wait with nothing
      queued and gaps inside a chain; ``others``: seconds of programs that
      are not the engine's own inside a gap, by name;
    - ``lo_ns`` / ``hi_ns``: the bracket of (device clock - host clock);
    - ``span_s`` / ``busy_s``: first run's start to last run's end, and the
      operations' union within it (``span_s - busy_s`` is the sum of the
      four parts, exactly).

    None (with a sentence through ``say_why``) where the trace has no launch
    span or the spans cannot be paired with the program runs; a pairing that
    contradicts causality raises."""
    host = events["host"]
    seam = sorted((s, s + d, m.group(1), m.group(2)) for name, s, d in host
                  for m in [_SEAM.match(name)] if m)
    if not any(kind == "launch" for *_, kind in seam):
        return None
    dev = events["devices"][min(events["devices"])]
    modules = sorted((s, s + d, name)
                     for name, s, d in dev.get(reduce.MODULES_LINE, []))
    got = _pair([(b, path) for b, _, path, kind in seam if kind == "launch"],
                [m for m in modules if m[2].startswith(OWN)], seam)
    if isinstance(got, str):
        say_why(f"seam: no split of the idle time: {got}")
        return None
    launches, runs, covered, lo, hi = got
    if len(runs) < 2:
        say_why("seam: no split of the idle time: fewer than two whole "
                "dispatches in the trace")
        return None
    if lo is not None and lo > hi:
        raise RuntimeError(
            f"seam: a pairing error: a wait returned {-lo} ns (host - device) "
            f"after its program ended where a program started {hi} ns "
            f"(device - host) after its launch began: no clock offset fits "
            f"both")
    foreign = [m for m in modules if not m[2].startswith(OWN)]
    merged = reduce.union(
        (s, s + d) for _, s, d in dev.get(reduce.OPS_LINE, []) if d > 0)
    starts = [s for s, _ in merged]
    cuts = sorted({t for _, s, d in host for t in (s, s + d)})
    names = [host_spans.innermost(host, (a + b) // 2)
             for a, b in zip(cuts, cuts[1:])]

    def by_span(a: int, b: int, into: dict, scale: float) -> None:
        i = bisect.bisect_right(cuts, a)
        while a < b:
            upto = min(b, cuts[i]) if i < len(cuts) else b
            name = names[i - 1] if 0 < i < len(cuts) else host_spans.OUTSIDE
            into[name] = into.get(name, 0.0) + scale * (upto - a) / 1e9
            a, i = upto, i + 1

    out = {"seam_s": 0.0, "inside_s": 0.0, "host_in_step": {},
           "host_outside": {}, "others": {},
           "gaps": {"after_wait": [0, 0.0], "in_chain": [0, 0.0]}}
    for k, (start, end, module) in enumerate(runs):
        out["inside_s"] += (end - start - _measure(merged, starts, start, end)) / 1e9
        if k + 1 == len(runs):
            break
        nxt = runs[k + 1][0]
        if nxt < end:
            raise RuntimeError(
                f"seam: {runs[k + 1][2]} starts {end - nxt} ns before "
                f"{module} ends: program runs overlap on one device")
        other = _measure(merged, starts, end, nxt)
        idle = nxt - end - other
        for s, e, name in foreign:
            if end <= s < nxt:
                out["others"][name] = out["others"].get(name, 0.0) + (e - s) / 1e9
        between, a, b = 0, covered[k], launches[k + 1][0]
        if a is not None and a < b:
            between = b - a
        kind = "after_wait" if between else "in_chain"
        out["gaps"][kind][0] += 1
        out["gaps"][kind][1] += idle / 1e9
        if between > idle + NEGATIVE_NS and not other:
            raise RuntimeError(
                f"seam: a pairing error: the host spent {between} ns between "
                f"the wait that covered {module} (device end {end}) and the "
                f"launch of {runs[k + 1][2]} (device start {nxt}), in a "
                f"device gap of {idle} ns idle")
        scale = min(between, idle) / between if between else 0.0
        if between:
            into: dict = {}
            by_span(a, b, into, scale)
            for name, s in into.items():
                side = ("host_in_step" if name.startswith("orion/")
                        else "host_outside")
                out[side][name] = out[side].get(name, 0.0) + s
        out["seam_s"] += (idle - scale * between) / 1e9
    out["lo_ns"], out["hi_ns"] = lo, hi
    out["runs"] = len(runs)
    out["span_s"] = (runs[-1][1] - runs[0][0]) / 1e9
    out["busy_s"] = _measure(merged, starts, runs[0][0], runs[-1][1]) / 1e9
    out["host_in_step_s"] = sum(out["host_in_step"].values())
    out["host_outside_s"] = sum(out["host_outside"].values())
    return out


_CACHE: dict = {}


def for_obs(obs: dict) -> Optional[dict]:
    """``split`` of this run's own trace (the newest under ``.bench_trace/``),
    once per process; prints its table the first time, above the result
    line."""
    tr = obs.get("trace")
    if not tr:
        return None
    path = host_spans.newest_trace()
    if path is None:
        return None
    if path not in _CACHE:
        _CACHE[path] = got = split(host_spans.load(path))
        if got is not None:
            say(got, tr)
    return _CACHE[path]


def per_step_ms(obs: dict, key: str) -> Optional[float]:
    """One of ``split``'s sums over the traced segment's engine steps."""
    got = for_obs(obs)
    steps = ((obs.get("trace") or {}).get("timing") or {}).get("steps")
    if got is None or not steps:
        return None
    return 1e3 * got[key] / steps


def say(got: dict, trace: Optional[dict] = None) -> None:
    between = got["seam_s"] + got["host_in_step_s"] + got["host_outside_s"]
    total = between + got["inside_s"]
    print(f"device idle time at the dispatch seam, {got['runs']} runs of the "
          f"engine's own programs over {got['span_s']:.4f} s "
          f"({total:.4f} s idle: {between:.4f} between programs, "
          f"{got['inside_s']:.4f} inside them):")
    for kind, what in (("after_wait", "after a wait with nothing queued"),
                       ("in_chain", "inside a chain")):
        n, s = got["gaps"][kind]
        print(f"  gaps {what:<34s} {n:5d} {s:9.4f} s")
    print(f"  seam (wake-up + launch latency)            {got['seam_s']:9.4f} s")
    for side, what in (("host_in_step", "engine host, inside orion/step"),
                       ("host_outside", "front end, outside orion/step")):
        print(f"  {what:<42s} {got[side + '_s']:9.4f} s")
        for name, s in sorted(got[side].items(), key=lambda kv: -kv[1]):
            print(f"    {name:<40s} {s:9.4f} s")
    for name, s in sorted(got["others"].items(), key=lambda kv: -kv[1]):
        print(f"  not the engine's own, inside a gap: {name} {s:.6f} s")
    if trace and trace.get("window_s"):
        whole = trace["window_s"] - trace["busy_s"]
        print(f"  the segment's idle time by busy_s / window_s {whole:.4f} s; "
              f"its two edges (before the first run, after the last) "
              f"{whole - total:.4f} s")
        steps = (trace.get("timing") or {}).get("steps")
        if steps:
            print(f"  a step ({steps} steps): seam "
                  f"{1e3 * got['seam_s'] / steps:.3f} ms, engine host "
                  f"{1e3 * got['host_in_step_s'] / steps:.3f}, front end "
                  f"{1e3 * got['host_outside_s'] / steps:.3f}, inside programs "
                  f"{1e3 * got['inside_s'] / steps:.3f}")
    lo, hi = got["lo_ns"], got["hi_ns"]
    print(f"  clock bracket (device - host): [{(lo or 0) / 1e3:.1f}, "
          f"{hi / 1e3:.1f}] us, width {(hi - (lo or hi)) / 1e3:.1f} us (the "
          f"least launch latency plus the least wake-up): host_spans' idle "
          f"table is good to this much", flush=True)


if __name__ == "__main__":
    path = sys.argv[1] if sys.argv[1:] else host_spans.newest_trace()
    if path is None:
        sys.exit("no trace under .bench_trace/")
    got = split(host_spans.load(path))
    if got is None:
        sys.exit(f"{path}: no orion/*/launch span that pairs with a program")
    say(got)
