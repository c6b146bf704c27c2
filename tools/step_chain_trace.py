#!/usr/bin/env python3
"""What separates a step's prefill from its decode window on the device's
own timeline (ISSUE 40): reads a ``--trace 1`` run's profile (default: the
newest under ``.bench_trace/``) and prints, for every ``jit_orion_prefill``
run, the program that started next, how long after the prefill's end, and
every program between the prefill and the next ``jit_orion_decode_window``.

    python3 tools/step_chain_trace.py [<trace.xplane.pb> | --under <dir>]

A chained step reads a few tens of microseconds and nothing in between; a
step that kept the older order reads the host's round trips (the wake-up,
the sampler's programs, ``decode/build``). Parses the profile on the CPU
too: copy a trace to ``chiprun_out/`` to read it here. Device events only:
no clock offset between host and device enters. Last, per prefill program
(one a shape) its runs and its mean device time: two sides' tables side by
side say whether a program itself got slower or the segment caught other
shapes."""

from __future__ import annotations

import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def gaps(modules: list) -> list:
    """[(gap_ns to the next program, its name, [names before the next
    decode window])] for each prefill run of ``modules`` ([name, start,
    duration], in any order)."""
    mods = sorted(modules, key=lambda m: m[1])
    out = []
    for i, (name, start, dur) in enumerate(mods):
        if "orion_prefill" not in name or i + 1 == len(mods):
            continue
        between = []
        for nxt, _, _ in mods[i + 1:]:
            if "decode_window" in nxt:
                break
            between.append(nxt)
        out.append((mods[i + 1][1] - (start + dur), mods[i + 1][0], between))
    return out


def main() -> int:
    from benchmarks.trace import host_spans, reduce

    argv = sys.argv[1:]
    if argv[:1] == ["--under"]:
        path = host_spans.newest_trace(pathlib.Path(argv[1]))
    else:
        path = argv[0] if argv else host_spans.newest_trace()
    if path is None:
        print("no trace under .bench_trace/")
        return 1
    events = host_spans.load(path)
    dev = events["devices"][min(events["devices"])]
    modules = dev.get(reduce.MODULES_LINE, [])
    got = gaps(modules)
    if not got:
        print(f"{path}: no jit_orion_prefill run")
        return 1
    chained = [g for g, nxt, between in got
               if "decode_window" in nxt and not between]
    print(f"{path}\n{len(got)} prefill runs; {len(chained)} followed at once "
          f"by their decode window")
    if chained:
        us = sorted(g / 1e3 for g in chained)
        print(f"  prefill end -> window start, us: least {us[0]:.1f} median "
              f"{statistics.median(us):.1f} most {us[-1]:.1f}")
    for gap, nxt, between in got:
        if "decode_window" not in nxt or between:
            print(f"  {gap / 1e3:10.1f} us to {nxt}; before the window: "
                  f"{between}")
    by_program: dict = {}
    for name, _, dur in modules:
        if "orion_prefill" in name:
            by_program.setdefault(name, []).append(dur / 1e6)
    print("prefill programs, mean ms x runs, by mean:")
    for name, ms in sorted(by_program.items(),
                           key=lambda kv: statistics.mean(kv[1])):
        print(f"  {statistics.mean(ms):9.3f} x {len(ms):2d}  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
