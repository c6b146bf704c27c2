#!/usr/bin/env python
"""Render a tracer export / flight-recorder dump as a terminal timeline
summary (ISSUEs 9 + 14; the serving-side companion of
profile_report.py).

Accepts any artifact the obs layer writes:

  - a Chrome trace-event JSON (``inference.trace_path`` /
    ``train.trace_path`` / ``engine.export_trace``),
  - a MERGED fleet trace (``Router.close()`` / ``Router.export_trace``:
    one process per source — router + replica-k), or
  - a flight-recorder dump (``inference.flight_dir`` /
    ``train.flight_dir`` auto-dumps on degradation triggers).

Reports: span groups by total and SELF time (a span's time that no span
nested in it covers, so the shares partition the timeline), the split of
an engine step into its ``orion/<phase>`` spans, the top individual
spans, a per-request TTFT breakdown (submit -> admit queue
wait vs admit -> first-token compute, from the lifecycle instants), and —
for flight dumps — the fault-adjacent event window that explains why the
dump exists. Merged traces additionally get the FLEET view: per-replica
span-share diff, the breaker/failover event timeline, per-request
correlated tracks (one request's journey across router + replicas, keyed
on the ``tid`` trace id), and the SLO burn panel. A trace whose ring
overflowed (``metadata.dropped_events`` > 0) is flagged as TRUNCATED
instead of silently rendering a hole.

    python tools/obs_report.py /tmp/serve_trace.json
    python tools/obs_report.py /tmp/fleet/trace.json        # merged
    python tools/obs_report.py /tmp/flight/flight_nan_quarantine_*.json
    python tools/obs_report.py --compare base_trace.json new_trace.json
"""

from __future__ import annotations

import argparse
import collections
import json
import sys


def load(path: str):
    """Normalize either artifact into (spans, instants, meta, procs):
    spans [(name, t_start_s, dur_s, tags, pid)], instants
    [(name, t_s, tags, pid)], meta {} for plain traces / the dump header
    for flight dumps / the export metadata for traces that carry it,
    procs {pid: process_name} from the trace's metadata events."""
    with open(path) as f:
        doc = json.load(f)
    spans, instants = [], []
    procs: dict[int, str] = {}
    if isinstance(doc, dict) and "spans" in doc and "reason" in doc:
        # Flight-recorder dump: times are monotonic seconds.
        for e in doc["spans"]:
            tags = e.get("tags", {})
            if e["kind"] == "span":
                spans.append(
                    (e["name"], e["t_start"], e["t_end"] - e["t_start"],
                     tags, 0)
                )
            else:
                instants.append((e["name"], e["t_start"], tags, 0))
        meta = {k: doc.get(k) for k in
                ("reason", "wall_time", "context", "events", "metrics")}
        return spans, instants, meta, procs
    events = doc.get("traceEvents", doc) if isinstance(doc, dict) else doc
    meta = doc.get("metadata", {}) if isinstance(doc, dict) else {}
    for e in events:
        ph = e.get("ph")
        tags = e.get("args", {})
        pid = e.get("pid", 0)
        if ph == "M":
            if e.get("name") == "process_name":
                procs[pid] = tags.get("name", f"pid{pid}")
        elif ph == "X":
            spans.append(
                (e["name"], e["ts"] / 1e6, e.get("dur", 0) / 1e6, tags,
                 pid)
            )
        elif ph == "i":
            instants.append((e["name"], e["ts"] / 1e6, tags, pid))
    return spans, instants, meta, procs


def print_truncation(meta, procs) -> None:
    """Flag a ring-overflow-truncated timeline (ISSUE 14 satellite): the
    export is the most recent window only, and every absence before its
    first event means 'evicted', not 'did not happen'."""
    dropped = meta.get("dropped_events") or 0
    if not dropped:
        return
    print(f"  *** TRUNCATED TIMELINE: {dropped} events dropped by ring "
          f"overflow (raise trace_ring) — earliest activity is missing,"
          f" not absent ***")
    for name, p in (meta.get("processes") or {}).items():
        if p.get("dropped"):
            print(f"      {name}: {p['dropped']} dropped")


def group_spans(spans):
    """name -> dict(count, total_s, self_s, max_s). ``self_s`` is the time
    of a group's spans that no span nested inside them (same process)
    covers: the engine's phases nest (orion/step > orion/admit >
    orion/prefill/run), so only self times add up to the timeline."""
    groups: dict = collections.defaultdict(
        lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0, "max_s": 0.0}
    )
    by_pid: dict = collections.defaultdict(list)
    for name, t, dur, _tags, pid in spans:
        g = groups[name]
        g["count"] += 1
        g["total_s"] += dur
        g["self_s"] += dur
        g["max_s"] = max(g["max_s"], dur)
        by_pid[pid].append((t, -dur, name))
    for rows in by_pid.values():
        open_spans: list = []            # (end, name), innermost last
        for t, neg, name in sorted(rows):
            while open_spans and open_spans[-1][0] <= t:
                open_spans.pop()
            if open_spans and t - neg <= open_spans[-1][0] + 1e-9:
                groups[open_spans[-1][1]]["self_s"] += neg
            open_spans.append((t - neg, name))
    return dict(groups)


def print_groups(groups, top: int) -> None:
    total = sum(g["self_s"] for g in groups.values()) or 1e-12
    print(f"{'span group':<28s} {'count':>7s} {'total':>9s} {'mean':>9s} "
          f"{'max':>9s} {'self':>9s} {'share':>7s}")
    ranked = sorted(
        groups.items(), key=lambda kv: kv[1]["self_s"], reverse=True
    )
    for name, g in ranked[:top]:
        mean = g["total_s"] / g["count"]
        print(f"{name:<28s} {g['count']:>7d} {g['total_s'] * 1e3:>8.1f}ms "
              f"{mean * 1e3:>8.2f}ms {g['max_s'] * 1e3:>8.2f}ms "
              f"{g['self_s'] * 1e3:>8.1f}ms "
              f"{g['self_s'] / total * 100:>6.1f}%")


def print_step_split(groups) -> None:
    """The engine's step, phase by phase: each ``orion/<phase>``'s self
    time per ``orion/step`` (README "Observability" has the list)."""
    steps = groups.get("orion/step", {}).get("count", 0)
    if not steps:
        return
    phases = {n: dict(g) for n, g in groups.items()
              if n.startswith("orion/")}
    total = sum(g["self_s"] for g in phases.values()) or 1e-12
    # The executor's launch and wait leaves (ISSUE 56) are shown under
    # their ``<path>/run`` parent, whose row keeps what it read before
    # they existed; a dump from before them has none and renders as ever.
    leaves: dict = collections.defaultdict(list)
    for name in [n for n in phases if n.endswith(("/launch", "/wait"))]:
        run = name.rsplit("/", 1)[0] + "/run"
        if run in phases:
            leaf = phases.pop(name)
            phases[run]["self_s"] += leaf["self_s"]
            leaves[run].append((name.rsplit("/", 1)[1], leaf["self_s"]))
    print(f"\nengine step split ({steps} steps; self time per step):")
    for name, g in sorted(
        phases.items(), key=lambda kv: kv[1]["self_s"], reverse=True
    ):
        what = "(uncovered)" if name == "orion/step" else ""
        print(f"  {name:<26s} {g['self_s'] / steps * 1e3:>9.3f}ms "
              f"{g['self_s'] / total * 100:>6.1f}%  {what}")
        for leaf, self_s in sorted(leaves[name]):
            print(f"    of it {leaf:<18s} {self_s / steps * 1e3:>9.3f}ms")


def print_slowest(spans, top: int) -> None:
    print(f"\nslowest {min(top, len(spans))} individual spans:")
    for name, t, dur, tags, _pid in sorted(
        spans, key=lambda s: s[2], reverse=True
    )[:top]:
        extra = " ".join(
            f"{k}={v}" for k, v in tags.items() if k in ("step", "rid")
        )
        print(f"  {dur * 1e3:>9.2f}ms  {name:<24s} {extra}")


def ttft_breakdown(instants, top: int) -> None:
    """Per-request lifecycle: submit -> admit (queue wait) -> first_token
    (prefill/compute) -> outcome, from the engine's lifecycle instants."""
    by_rid: dict = collections.defaultdict(dict)
    for name, t, tags, _pid in instants:
        rid = tags.get("rid")
        if rid is None:
            continue
        if name in ("submit", "admit", "first_token"):
            by_rid[rid].setdefault(name, t)   # first occurrence wins
        elif name == "outcome":
            by_rid[rid]["outcome"] = tags.get("outcome", "?")
            by_rid[rid]["tokens"] = tags.get("tokens", 0)
    if not by_rid:
        return
    print(f"\nper-request TTFT breakdown ({len(by_rid)} requests):")
    print(f"  {'rid':>5s} {'queue':>9s} {'compute':>9s} {'ttft':>9s} "
          f"{'tokens':>7s}  outcome")
    rows = []
    for rid, ev in by_rid.items():
        sub, adm, first = (
            ev.get("submit"), ev.get("admit"), ev.get("first_token")
        )
        ttft = (first - sub) if (first is not None and sub is not None) \
            else None
        rows.append((ttft if ttft is not None else -1.0, rid, sub, adm,
                     first, ev))
    for ttft, rid, sub, adm, first, ev in sorted(rows, reverse=True)[:top]:
        fmt = lambda a, b: (
            f"{(b - a) * 1e3:>8.2f}ms" if a is not None and b is not None
            else f"{'-':>9s}"
        )
        print(f"  {rid:>5d} {fmt(sub, adm)} {fmt(adm, first)} "
              f"{fmt(sub, first)} {ev.get('tokens', 0):>7} "
              f" {ev.get('outcome', '(live)')}")


# ---------------------------------------------------------------------------
# Fleet view (merged traces; ISSUE 14)
# ---------------------------------------------------------------------------

FLEET_EVENTS = ("break", "probe", "recover", "retry", "slo_breach")


def print_fleet_shares(spans, procs, top: int) -> None:
    """Per-replica span-share diff: one column per process, rows = span
    groups ranked by fleet-total time — where each replica's time went,
    side by side (a replica grinding 80% verify while its peers decode
    is visible in one glance)."""
    pids = sorted(procs)
    per: dict[int, dict] = {
        pid: collections.defaultdict(float) for pid in pids
    }
    totals: dict[int, float] = {pid: 0.0 for pid in pids}
    fleet: dict = collections.defaultdict(float)
    for name, _t, dur, _tags, pid in spans:
        if pid not in per:
            continue
        per[pid][name] += dur
        totals[pid] += dur
        fleet[name] += dur
    cols = [procs[pid][:12] for pid in pids]
    print("\nper-process span shares (fleet diff):")
    print(f"{'span group':<24s} " +
          " ".join(f"{c:>12s}" for c in cols))
    ranked = sorted(fleet.items(), key=lambda kv: kv[1], reverse=True)
    for name, _total in ranked[:top]:
        cells = []
        for pid in pids:
            t = totals[pid]
            share = per[pid][name] / t * 100 if t > 0 else 0.0
            cells.append(f"{share:>11.1f}%" if per[pid][name] else
                         f"{'-':>12s}")
        print(f"{name:<24s} " + " ".join(cells))
    print(f"{'total span time':<24s} " + " ".join(
        f"{totals[pid] * 1e3:>10.1f}ms" for pid in pids
    ))


def print_fleet_timeline(instants, procs, tail: int) -> None:
    """Breaker state transitions, failover re-queues and SLO breaches in
    one time-ordered stream — the fleet's incident log, drawn from the
    same instants the request tracks carry."""
    rows = [
        (t, name, tags, pid) for name, t, tags, pid in instants
        if name in FLEET_EVENTS
    ]
    if not rows:
        return
    t0 = min(t for _n, t, _tg, _p in instants) if instants else 0.0
    print(f"\nfleet events ({len(rows)}; breaker/failover/SLO):")
    # Sort on time only: a timestamp tie must not fall through to dict
    # comparison (tags) and TypeError a report.
    for t, name, tags, pid in sorted(rows, key=lambda r: r[0])[-tail:]:
        if name == "retry":
            detail = (f"rid={tags.get('rid')} attempt={tags.get('attempt')}"
                      f" backoff={tags.get('backoff_steps')} "
                      f"({str(tags.get('reason', ''))[:40]})")
        elif name == "slo_breach":
            detail = (f"{tags.get('objective')} burn={tags.get('burn')} "
                      f"events={tags.get('events')} "
                      f"worst={tags.get('worst_ms')}ms")
        else:
            detail = " ".join(
                f"{k}={v}" for k, v in tags.items()
                if k in ("replica", "reason", "killed")
            )
        print(f"  +{(t - t0) * 1e3:>9.1f}ms  {name:<12s} "
              f"[{procs.get(pid, pid)}]  {detail}")


def print_request_tracks(instants, procs, top: int) -> None:
    """Correlated per-request tracks: every lifecycle/routing instant
    carrying the same ``tid`` trace id, across ALL processes, rendered
    as one journey line — a failover reads route -> admit -> retry ->
    route -> ... -> outcome with the replica names inline."""
    by_tid: dict = collections.defaultdict(list)
    for name, t, tags, pid in instants:
        tid = tags.get("tid")
        if tid is None:
            continue
        by_tid[tid].append((t, name, tags, pid))
    if not by_tid:
        return
    # Failover'd (retried) tracks first — they are what a postmortem
    # reads — then by event count.
    def key(item):
        tid, evs = item
        retried = max(
            (tg.get("retried", 0) or 0) for _t, _n, tg, _p in evs
        )
        return (-retried, -len(evs), tid)

    ranked = sorted(by_tid.items(), key=key)
    print(f"\nrequest tracks ({len(by_tid)} correlated tids; "
          f"retried first):")
    for tid, evs in ranked[:top]:
        evs.sort(key=lambda e: e[0])   # time only — tags are dicts
        t0 = evs[0][0]
        hops = []
        for t, name, tags, pid in evs:
            where = procs.get(pid, str(pid))
            label = name
            if name == "route":
                label = f"route->r{tags.get('replica')}"
            elif name == "outcome":
                label = f"outcome={tags.get('outcome')}"
            if tags.get("retried"):
                label += f"(retry{tags['retried']})"
            hops.append(f"{label}@{where}+{(t - t0) * 1e3:.0f}ms")
        print(f"  tid {tid}: " + " -> ".join(hops))


def print_slo_panel(instants, meta) -> None:
    """SLO burn panel: breach instants from the timeline (the router
    emits one per judged-over-budget window) or, for flight dumps, the
    slo.* gauges in the metrics snapshot."""
    breaches = [
        (t, tags) for name, t, tags, _pid in instants
        if name == "slo_breach"
    ]
    gauges = {
        k: v for k, v in (meta.get("metrics") or {}).items()
        if k.startswith("slo.")
    }
    if not breaches and not gauges:
        return
    print("\nSLO burn panel:")
    if breaches:
        by_obj: dict = collections.defaultdict(list)
        for _t, tags in breaches:
            by_obj[tags.get("objective", "?")].append(tags)
        for obj, rows in sorted(by_obj.items()):
            worst = max(float(r.get("burn", 0) or 0) for r in rows)
            print(f"  {obj:<16s} breaches={len(rows)} "
                  f"worst_burn={worst:.2f}x "
                  f"(target {rows[-1].get('target_ms')}ms, "
                  f"goal {rows[-1].get('goal')})")
    else:
        print("  no slo_breach events in this window")
    for k in sorted(gauges):
        print(f"  {k} = {gauges[k]}")


def print_fault_window(meta, tail: int = 12) -> None:
    print(f"\nflight dump: reason={meta['reason']} at {meta['wall_time']}")
    if meta.get("context"):
        print(f"  context: {json.dumps(meta['context'])}")
    events = meta.get("events") or []
    if events:
        print(f"  last {min(tail, len(events))} recorder events:")
        for e in events[-tail:]:
            fields = {k: v for k, v in e.items() if k not in ("t", "kind")}
            print(f"    t={e['t']:.3f}  {e['kind']:<18s} "
                  f"{json.dumps(fields) if fields else ''}")
    metrics = meta.get("metrics") or {}
    faults = {
        k: v for k, v in metrics.items()
        if any(s in k for s in ("fault", "failed", "stalled", "quarantined",
                                "shed", "expired", "rollback", "anomalous",
                                "breach"))
        and v not in (0, 0.0, "")
    }
    if faults:
        print("  nonzero fault counters at dump time:")
        for k in sorted(faults):
            print(f"    {k} = {faults[k]}")


def compare(path_a: str, path_b: str, top: int) -> int:
    ga = group_spans(load(path_a)[0])
    gb = group_spans(load(path_b)[0])
    ta = sum(g["self_s"] for g in ga.values()) or 1e-12
    tb = sum(g["self_s"] for g in gb.values()) or 1e-12
    names = set(ga) | set(gb)
    rows = []
    for n in names:
        sa = ga.get(n, {"self_s": 0.0})["self_s"] / ta
        sb = gb.get(n, {"self_s": 0.0})["self_s"] / tb
        rows.append((abs(sb - sa), n, sa, sb))
    print(f"span-share diff: A={path_a}  B={path_b}")
    print(f"{'span group':<28s} {'A share':>8s} {'B share':>8s} "
          f"{'delta':>8s}")
    for _d, n, sa, sb in sorted(rows, reverse=True)[:top]:
        print(f"{n:<28s} {sa * 100:>7.1f}% {sb * 100:>7.1f}% "
              f"{(sb - sa) * 100:>+7.1f}%")
    print(f"\ntotal span time: A {ta * 1e3:.1f}ms -> B {tb * 1e3:.1f}ms "
          f"({tb / ta:.2f}x)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("paths", nargs="+",
                    help="trace JSON (plain or merged) or flight dump "
                         "(2 with --compare)")
    ap.add_argument("--compare", action="store_true",
                    help="diff span shares between two artifacts")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)

    if args.compare:
        if len(args.paths) != 2:
            print("--compare needs exactly two paths", file=sys.stderr)
            return 2
        return compare(args.paths[0], args.paths[1], args.top)
    if len(args.paths) != 1:
        print("one artifact at a time (or --compare A B)", file=sys.stderr)
        return 2
    spans, instants, meta, procs = load(args.paths[0])
    fleet = len(procs) > 1
    kind = "merged fleet trace" if fleet else "trace"
    print(f"{args.paths[0]}: {kind}, {len(spans)} spans, "
          f"{len(instants)} instants"
          + (f", {len(procs)} processes "
             f"({', '.join(procs[p] for p in sorted(procs))})"
             if fleet else ""))
    print_truncation(meta, procs)
    if meta.get("reason"):
        print_fault_window(meta)
    if spans:
        print("\nspan groups by self time:")
        groups = group_spans(spans)
        print_groups(groups, args.top)
        print_step_split(groups)
        print_slowest(spans, min(args.top, 10))
    if fleet:
        print_fleet_shares(spans, procs, args.top)
        print_fleet_timeline(instants, procs, tail=2 * args.top)
        print_request_tracks(instants, procs, args.top)
        print_slo_panel(instants, meta)
    else:
        ttft_breakdown(instants, args.top)
        if meta.get("reason"):
            # Flight dumps carry the tracer window (which may hold
            # slo_breach instants) and the registry snapshot's slo.*
            # gauges — render the burn panel for them too.
            print_slo_panel(instants, meta)
    return 0


if __name__ == "__main__":
    sys.exit(main())
