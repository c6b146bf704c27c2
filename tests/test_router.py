"""Multi-replica router tier (ISSUE 12): scheduler/executor split
equivalence, prefix-affinity placement, health circuit breaker +
half-open recovery, and the failover matrix (kill / stall / poison /
all-down) — every episode ending with exactly one typed outcome per
request, completed greedy streams byte-identical to an uninterrupted
single-engine run, and survivor page pools exactly accounted.
"""

import json
import pathlib
import subprocess
import sys

import jax
import pytest

from orion_tpu.config import get_config
from orion_tpu.infer import InferenceEngine, Router
from orion_tpu.models import init_params
from orion_tpu.runtime.fault import FaultInjector, FaultSpec

slow = pytest.mark.slow

INFER = [
    "inference.max_seq_len=128",
    "inference.page_size=16",
    "inference.num_pages=32",
    "inference.max_batch_size=4",
    "inference.prefill_chunk=16",
    "inference.max_new_tokens=8",
    "inference.decode_window=1",
]
MIX = [
    [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8],
    [5, 3, 9, 250, 17],
    [7, 7, 7],
    [1, 2, 3, 4],
    [9, 9, 2, 1],
]
# Deterministic failover scheduling in tests: no backoff jitter.
RTR = ["router.retry_backoff_jitter=0"]


@pytest.fixture(scope="module")
def tiny():
    """(params, fault-free greedy reference outputs for MIX)."""
    cfg = get_config("tiny-llama", INFER)
    params = init_params(cfg.model, jax.random.key(0))
    ref = InferenceEngine(cfg, params).generate(MIX, 8)
    return params, ref


def _router(params, extra=(), inj=None):
    cfg = get_config("tiny-llama", INFER + RTR + list(extra))
    return Router(cfg, params, fault_injector=inj)


def _drive(router, reqs):
    """Step to quiescence; asserts every surfaced request surfaces ONCE
    (no duplicates) and every submitted request ends typed (no silent
    drops). Returns {rid: outcome-count}."""
    surfaced: dict[int, int] = {}
    while router.has_work():
        for rr in router.step():
            surfaced[rr.rid] = surfaced.get(rr.rid, 0) + 1
    assert all(c == 1 for c in surfaced.values()), surfaced
    assert sorted(surfaced) == sorted(r.rid for r in reqs), surfaced
    assert all(r.done for r in reqs)
    return surfaced


# ---------------------------------------------------------------------------
# Pass-through equivalence (the tentpole's bitwise pin)
# ---------------------------------------------------------------------------


def test_single_replica_passthrough_byte_identical(tiny):
    """router.replicas=1 is the engine behind a pass-through: greedy
    streams byte-identical, zero retries/breaks, pool accounted."""
    params, ref = tiny
    r = _router(params)
    assert r.generate(MIX, 8) == ref
    t = r.reset_timing()
    assert t["routed"] == len(MIX) and t["retries"] == 0
    assert t["breaks"] == 0 and t["replicas"] == 1
    r.handles[0].engine.assert_page_accounting()
    r.close()


def test_two_replicas_fan_out_byte_identical(tiny):
    """Load-balanced fan-out across 2 replicas never changes any
    request's tokens (the engine batching invariant, fleet-wide)."""
    params, ref = tiny
    r = _router(params, ["router.replicas=2"])
    assert r.generate(MIX, 8) == ref
    # Least-loaded placement actually spread the work.
    placed = {h.idx: h.engine.step_no for h in r.handles}
    assert all(v > 0 for v in placed.values()), placed
    for h in r.handles:
        h.engine.assert_page_accounting()
    r.close()


def test_stream_across_replicas_incremental(tiny):
    """Router stream(): every request's incremental yields concatenate to
    the reference stream; zero-token terminals announce once."""
    params, ref = tiny
    r = _router(params, ["router.replicas=2"])
    got: dict[int, list] = {}
    for rid, toks in r.stream(MIX, 8):
        got.setdefault(rid, []).extend(toks)
    assert [got[rid] for rid in sorted(got)] == ref
    r.close()


# ---------------------------------------------------------------------------
# Prefix-affinity placement (ISSUE 12 satellite)
# ---------------------------------------------------------------------------


def test_prefix_affinity_and_load_placement(tiny):
    """Two replicas with DISJOINT radix trees: a warm-prefix request
    lands on the replica holding its match (engine-level cache hit
    proves the pages were really there), and a cold request lands on the
    least-loaded replica — read off the registry gauges, not ad-hoc
    counters."""
    params, _ = tiny
    warm_a = list(range(1, 17))          # one full page each
    warm_b = list(range(101, 117))
    r = _router(params, [
        "router.replicas=2",
        "inference.prefix_cache=true",
        "router.affinity_min_tokens=16",
    ])
    # Disjoint warm-up: submitted together, least-loaded placement puts
    # prime A on replica 0 and prime B on replica 1; each donates its
    # prefix to ITS OWN tree on completion.
    pa = r.submit_request(warm_a + [40], 2)
    pb = r.submit_request(warm_b + [41], 2)
    _drive(r, [pa, pb])
    assert (pa.replica, pb.replica) == (0, 1)
    assert r.handles[0].engine.prefix_match_tokens(warm_a + [1]) == 16
    assert r.handles[1].engine.prefix_match_tokens(warm_b + [1]) == 16
    assert r.handles[0].engine.prefix_match_tokens(warm_b + [1]) == 0
    r.reset_timing()

    # Warm requests pin to the replica holding their match.
    qa = r.submit_request(warm_a + [60, 61, 62], 4)
    qb = r.submit_request(warm_b + [70, 71, 72], 4)
    assert (qa.replica, qb.replica) == (0, 1)
    t = r.reset_timing()
    assert t["affinity_routes"] == 2 and t["cold_routes"] == 0
    # Cold request while replica 0 is the busier one (holds qa AND a
    # fresh long request): the registry gauges (engine.waiting/active)
    # must send it to replica 1... after balancing, both replicas hold
    # one request; tip replica 0 with one more.
    extra = r.submit_request(warm_a + [80, 81, 82], 8)
    assert extra.replica == 0
    cold = r.submit_request([42, 43, 44, 45, 46], 4)
    assert cold.replica == 1
    t = r.reset_timing()
    assert t["cold_routes"] >= 1
    _drive(r, [qa, qb, extra, cold])
    # The warm placements were real cache hits on their replicas.
    assert r.handles[0].engine.prefix_stats.hits >= 2
    assert r.handles[1].engine.prefix_stats.hits >= 1
    for h in r.handles:
        h.engine.assert_page_accounting()
    r.close()


def test_prefix_peek_is_read_only(tiny):
    """The affinity probe (PrefixCache.peek) takes no locks and bumps no
    LRU stamps: evictable accounting and the locked-page split are
    untouched by any number of probes."""
    params, _ = tiny
    r = _router(params, ["inference.prefix_cache=true"])
    eng = r.handles[0].engine
    p = r.submit_request(list(range(1, 17)) + [40], 2)
    _drive(r, [p])
    cache = eng._pcache
    before = (cache.evictable_pages(), cache.locked_pages,
              cache.total_pages)
    for _ in range(5):
        assert eng.prefix_match_tokens(list(range(1, 17)) + [9]) == 16
    assert (cache.evictable_pages(), cache.locked_pages,
            cache.total_pages) == before
    r.close()


# ---------------------------------------------------------------------------
# Failover matrix
# ---------------------------------------------------------------------------


def test_replica_kill_mid_decode_failover(tiny):
    """The chaos pin: 3 replicas, replica 0 killed mid-decode. Every
    in-flight request on the dead replica ends in exactly one typed
    outcome (retried-then-completed here), greedy streams everywhere are
    byte-identical to an uninterrupted run, survivors' pools account,
    and the router decisions land in the trace with the `retried` tag."""
    params, ref = tiny
    inj = FaultInjector([FaultSpec("replica_kill", step=3, replica=0)])
    r = _router(
        params, ["router.replicas=3", "inference.trace=true"], inj=inj
    )
    reqs = [r.submit_request(p, 8) for p in MIX]
    on_r0 = [rr for rr in reqs if rr.replica == 0]
    assert on_r0, "placement spread nothing onto replica 0"
    _drive(r, reqs)
    assert inj.fired == [("replica_kill", 3, None)]
    for i, rr in enumerate(reqs):
        assert rr.outcome == "completed"
        assert list(rr.generated) == ref[i]
    assert all(rr.retries >= 1 for rr in on_r0)
    assert all(rr.replica != 0 for rr in on_r0)
    t = r.reset_timing()
    assert t["kills"] == 1 and t["breaks"] == 1
    assert t["retries"] >= len(on_r0)
    assert t["replicas_dead"] == 1
    for h in r.handles[1:]:
        h.engine.assert_page_accounting()
    # Router decisions in the trace: route/break/retry, and exactly one
    # outcome instant per request carrying the retried tag.
    names = [e[1] for e in r._tracer.events()]
    assert "break" in names and "retry" in names and "route" in names
    outcomes = [
        e for e in r._tracer.events() if e[1] == "outcome"
    ]
    assert len(outcomes) == len(reqs)
    by_rid = {e[4]["rid"]: e[4] for e in outcomes}
    assert all(by_rid[rr.rid]["retried"] == rr.retries for rr in reqs)
    r.close()


def test_all_replicas_down_sheds_typed(tiny):
    """Kill the whole fleet: queued and in-flight requests SHED with a
    typed outcome (never hang, never silently drop), and a post-mortem
    submit sheds immediately."""
    params, _ = tiny
    inj = FaultInjector([
        FaultSpec("replica_kill", step=2, replica=0),
        FaultSpec("replica_kill", step=2, replica=1),
    ])
    r = _router(params, ["router.replicas=2"], inj=inj)
    reqs = [r.submit_request(p, 8) for p in MIX[:3]]
    _drive(r, reqs)
    assert all(rr.outcome == "shed" for rr in reqs)
    late = r.submit_request([1, 2, 3], 4)
    assert late.outcome == "shed"       # typed, immediate, no hang
    surfaced = r.step()
    assert late in surfaced
    t = r.reset_timing()
    assert t["kills"] == 2 and t["router_shed"] == len(reqs) + 1
    r.close()


def test_retry_budget_exhausted_sheds(tiny):
    """router.retry_budget=0: a killed replica's in-flight work sheds
    typed instead of retrying; survivors complete byte-identically."""
    params, ref = tiny
    inj = FaultInjector([FaultSpec("replica_kill", step=3, replica=0)])
    r = _router(
        params, ["router.replicas=2", "router.retry_budget=0"], inj=inj
    )
    reqs = [r.submit_request(p, 8) for p in MIX[:4]]
    on_r0 = [rr for rr in reqs if rr.replica == 0]
    _drive(r, reqs)
    for i, rr in enumerate(reqs):
        if rr in on_r0:
            assert rr.outcome == "shed" and rr.retries == 0
        else:
            assert rr.outcome == "completed"
            assert list(rr.generated) == ref[i]
    r.close()


def test_circuit_breaker_soft_trip_and_half_open_recovery(tiny):
    """A replica whose steps keep failing (injected dispatch faults on
    its own engine, xla path: no fallback) trips the breaker via the
    health sweep — its request fails over and completes byte-identically
    — then the breaker goes HALF_OPEN after probe_after_steps and a
    completed probe request CLOSES it."""
    params, ref = tiny
    r = _router(params, [
        "router.replicas=2",
        "router.break_failed_steps=2",
        "router.probe_after_steps=3",
        "inference.max_step_faults=6",
    ])
    # Replica 0's first two engine steps fail every dispatch path.
    r.handles[0].injector.specs += [
        FaultSpec("dispatch", step=0), FaultSpec("dispatch", step=1),
    ]
    a = r.submit_request(MIX[0], 8)
    b = r.submit_request(MIX[1], 8)
    assert (a.replica, b.replica) == (0, 1)
    probe = None
    while r.has_work() or probe is None:
        r.step()
        if probe is None and r.handles[0].state == "half_open":
            # Replica 1 is still busy with a/b, replica 0 is idle and
            # probing: the next request must route there as the probe.
            probe = r.submit_request(MIX[2], 8)
            assert probe.replica == 0
    assert a.outcome == "completed" and a.retries == 1
    assert list(a.generated) == ref[0]
    assert b.outcome == "completed" and list(b.generated) == ref[1]
    assert probe.outcome == "completed"
    assert list(probe.generated) == ref[2]
    assert r.handles[0].state == "closed"
    t = r.reset_timing()
    assert t["breaks"] == 1 and t["probes"] == 1 and t["recoveries"] == 1
    assert t["kills"] == 0
    for h in r.handles:
        h.engine.assert_page_accounting()
    r.close()


def test_replica_stall_trips_watchdog_break(tiny):
    """replica_stall flows through the REAL path: forwarded into the
    engine's injector, the stalled dispatch trips the engine watchdog,
    the health sweep reads the stalled-step delta and breaks the
    replica; its work fails over and completes byte-identically."""
    params, ref = tiny
    inj = FaultInjector([
        FaultSpec("replica_stall", step=2, replica=0, stall_s=0.35),
    ])
    r = _router(params, [
        "router.replicas=2",
        "inference.watchdog_timeout_s=0.1",
    ], inj=inj)
    reqs = [r.submit_request(p, 8) for p in MIX[:2]]
    _drive(r, reqs)
    assert inj.fired == [("replica_stall", 2, None)]
    assert r.handles[0].engine.robust.stalled_steps >= 1 or (
        r.handles[0].seen["stalled"] >= 1
    )
    t = r.reset_timing()
    assert t["breaks"] >= 1 and t["kills"] == 0
    for i, rr in enumerate(reqs):
        assert rr.outcome == "completed"
        assert list(rr.generated) == ref[i]
    r.close()


def test_replica_poison_quarantine_storm_breaks(tiny):
    """replica_poison -> engine NaN quarantine (nan_guard) -> the router
    health sweep sees the quarantine delta and breaks the replica. The
    poisoned victim keeps its typed error outcome (request-scoped
    poison is not retried); co-tenants fail over and complete
    byte-identically; neighbors elsewhere never notice."""
    params, ref = tiny
    inj = FaultInjector([
        FaultSpec("replica_poison", step=2, replica=0),
    ])
    r = _router(params, [
        "router.replicas=2",
        "inference.nan_guard=true",
        "router.break_quarantined=1",
    ], inj=inj)
    reqs = [r.submit_request(p, 8) for p in MIX[:4]]
    on_r0 = [rr for rr in reqs if rr.replica == 0]
    _drive(r, reqs)
    victims = [rr for rr in reqs if rr.outcome == "error:nan"]
    assert len(victims) == 1 and victims[0] in on_r0
    for i, rr in enumerate(reqs):
        if rr is victims[0]:
            continue
        assert rr.outcome == "completed"
        assert list(rr.generated) == ref[i]
    t = r.reset_timing()
    assert t["breaks"] == 1
    r.close()


def test_router_drain_finishes_in_flight_sheds_queued(tiny):
    """Fleet drain: in-flight requests finish with their tokens; a
    request still waiting at the ROUTER (every breaker open) sheds
    typed; drain is idempotent."""
    params, ref = tiny
    r = _router(params, ["router.replicas=2"])
    reqs = [r.submit_request(p, 8) for p in MIX[:2]]
    r.step()
    drained = r.drain()
    assert {rr.rid for rr in drained} == {rr.rid for rr in reqs}
    for i, rr in enumerate(reqs):
        assert rr.outcome == "completed"
        assert list(rr.generated) == ref[i]
    assert r.drain() == []
    late = r.submit_request([3, 2, 1], 4)
    assert late.outcome == "shed"
    r.close()


# ---------------------------------------------------------------------------
# Fleet observability plane (ISSUE 14)
# ---------------------------------------------------------------------------


def test_merged_trace_three_replicas_failover(tiny, tmp_path):
    """The fleet-correlation pin: 3 replicas, replica 0 killed
    mid-decode, inference.trace_path set. The MERGED timeline written at
    close() contains the router + all three replica processes; every
    router rid has exactly ONE router-track outcome instant; every
    failover'd request's lifecycle instants appear on BOTH replicas'
    tracks (same tid) with the ``retried`` tag on the re-placed attempt
    — submit -> outcome on the survivor; per-replica namespaced traces
    exist for the live replicas (the killed one models a dead process:
    ring merged, file never written); and tokens are byte-identical to
    the trace-OFF fleet (recording must not perturb serving)."""
    params, ref = tiny
    path = tmp_path / "trace.json"
    inj = FaultInjector([FaultSpec("replica_kill", step=3, replica=0)])
    r = _router(
        params,
        ["router.replicas=3", f"inference.trace_path={path}"],
        inj=inj,
    )
    reqs = [r.submit_request(p, 8) for p in MIX]
    on_r0 = [rr for rr in reqs if rr.replica == 0]
    assert on_r0
    _drive(r, reqs)
    for i, rr in enumerate(reqs):
        assert rr.outcome == "completed"
        assert list(rr.generated) == ref[i]     # trace-on == trace-off
    r.close()

    doc = json.loads(path.read_text())
    procs = {
        e["pid"]: e["args"]["name"]
        for e in doc["traceEvents"]
        if e.get("ph") == "M" and e["name"] == "process_name"
    }
    assert sorted(procs.values()) == [
        "replica-0", "replica-1", "replica-2", "router",
    ]
    router_pid = next(p for p, n in procs.items() if n == "router")
    rep_pids = set(procs) - {router_pid}
    evs = [e for e in doc["traceEvents"] if e.get("ph") in ("i", "X")]
    # Every replica contributed spans (the killed one ran to the kill).
    spans_by_pid = {p: 0 for p in procs}
    for e in evs:
        if e["ph"] == "X":
            spans_by_pid[e["pid"]] += 1
    assert all(spans_by_pid[p] >= 1 for p in rep_pids), spans_by_pid
    # Exactly one router outcome instant per rid, tagged with retries.
    outs = [
        e for e in evs
        if e["pid"] == router_pid and e["name"] == "outcome"
    ]
    by_rid = {}
    for e in outs:
        by_rid.setdefault(e["args"]["rid"], []).append(e["args"])
    assert sorted(by_rid) == sorted(rr.rid for rr in reqs)
    assert all(len(v) == 1 for v in by_rid.values())
    assert all(
        by_rid[rr.rid][0]["retried"] == rr.retries for rr in reqs
    )
    # Failover'd requests: same tid on >= 2 replica tracks, the second
    # attempt's instants (incl. the survivor outcome) carry `retried`.
    tracks: dict = {}
    retried_out = set()
    for e in evs:
        a = e.get("args", {})
        if e["pid"] in rep_pids and "tid" in a:
            tracks.setdefault(a["tid"], set()).add(e["pid"])
            if a.get("retried") and e["name"] == "outcome":
                retried_out.add(a["tid"])
    for rr in on_r0:
        assert rr.retries >= 1
        assert len(tracks[rr.rid]) >= 2, (rr.rid, tracks)
        assert rr.rid in retried_out
    # Dispatch spans carry the tids they computed for.
    dspans = [
        e for e in evs
        if e["ph"] == "X" and e["name"].startswith("orion/")
        and e["name"].endswith("/run")
    ]
    assert any(e["args"].get("tids") for e in dspans)
    # Namespaced per-replica traces: live replicas wrote theirs at
    # close(); the killed replica (a dead process) never did.
    assert not (tmp_path / "trace.replica-0.json").exists()
    for k in (1, 2):
        rep = json.loads((tmp_path / f"trace.replica-{k}.json").read_text())
        assert any(e.get("ph") == "X" for e in rep["traceEvents"])


def test_replica_stall_pins_slo_breach(tiny, tmp_path):
    """The ISSUE 14 acceptance pin: an injected replica_stall drives the
    step loop past the ITL objective -> the windowed burn rate breaches
    -> a typed slo_breach lands in the flight recorder (note + dump),
    the tracer, the registry gauges and RouterStats. The same fleet
    uncontended (no stall) judges >= 1 window with ZERO breaches."""
    params, ref = tiny
    slo = [
        "router.replicas=2",
        "inference.watchdog_timeout_s=0.1",
        "slo.itl_ms=50",
        "slo.window_s=0.2",
        "slo.goal=0.9",
        f"inference.flight_dir={tmp_path / 'flight'}",
        "inference.trace=true",
    ]
    inj = FaultInjector([
        FaultSpec("replica_stall", step=2, replica=0, stall_s=0.4),
    ])
    r = _router(params, slo, inj=inj)
    reqs = [r.submit_request(p, 8) for p in MIX[:2]]
    _drive(r, reqs)
    r.close()
    for i, rr in enumerate(reqs):       # serving itself survived intact
        assert rr.outcome == "completed"
        assert list(rr.generated) == ref[i]
    g = r._slo.metrics()
    assert g["breaches"] >= 1 and g["windows"] >= 1
    # (burn_itl_all is the LAST judged window's burn — post-failover
    # healthy windows legitimately drive it back to 0; the breach-window
    # burn is pinned via the dump context below.)
    assert r.registry.snapshot(sections=("slo",))["slo.breaches"] >= 1
    dumps = list((tmp_path / "flight").glob("flight_slo_breach_*.json"))
    assert dumps, "slo_breach flight dump missing"
    doc = json.loads(dumps[0].read_text())
    assert doc["context"]["metric"] == "itl"
    assert float(doc["context"]["burn"]) > 1.0
    assert any(ev[1] == "slo_breach" for ev in r._tracer.events())

    # Uncontended twin: windows judged, zero breaches (no false alarms).
    r2 = _router(params, [
        "router.replicas=2", "slo.itl_ms=50", "slo.window_s=0.2",
        "slo.goal=0.9",
    ])
    reqs2 = [r2.submit_request(p, 8) for p in MIX[:2]]
    _drive(r2, reqs2)
    r2.close()
    g2 = r2._slo.metrics()
    assert g2["windows"] >= 1 and g2["breaches"] == 0
    assert r2.stats.slo_breaches == 0


def test_breaker_note_carries_routing_decisions(tiny, tmp_path):
    """Breaker-trip postmortems answer 'why was traffic there': the
    router_break flight note carries the last K routing decisions —
    replica, match_tokens, and the load gauges read at placement."""
    params, _ = tiny
    inj = FaultInjector([FaultSpec("replica_kill", step=3, replica=0)])
    r = _router(params, [
        "router.replicas=2",
        "router.decision_log=4",
        f"inference.flight_dir={tmp_path / 'flight'}",
    ], inj=inj)
    reqs = [r.submit_request(p, 8) for p in MIX]
    _drive(r, reqs)
    breaks = [
        e for e in r._flight._events if e["kind"] == "router_break"
    ]
    assert len(breaks) == 1
    routes = breaks[0]["recent_routes"]
    assert 1 <= len(routes) <= 4            # ring bound = decision_log
    for d in routes:
        assert {"rid", "replica", "match_tokens", "queued", "occupancy",
                "itl_proxy_s", "affinity", "retried",
                "step"} <= set(d)
    # The kill's failover re-placements landed AFTER the break, so the
    # note's window shows the pre-break placement picture.
    assert any(d["replica"] == 0 for d in routes)
    r.close()


# ---------------------------------------------------------------------------
# tools/router_bench.py --smoke (the tier-1 chaos-pin wiring)
# ---------------------------------------------------------------------------


def test_router_bench_smoke():
    """tools/router_bench.py --smoke: the acceptance pin — 3 replicas,
    kill-one-mid-decode; exactly one typed outcome per request (zero
    duplicates/drops), survivor greedy streams byte-identical to an
    uninterrupted run, throughput recovered to >= 2/3 baseline within
    the bound, and prefix affinity actually used. Fleet obs (ISSUE 14):
    the chaos run's MERGED trace exists, parses, holds >= 1 span per
    replica with rid-correlated failover tracks, and the uncontended
    baseline judged >= 1 SLO window with zero breaches."""
    root = pathlib.Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "router_bench.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    verdict = lines[-1]
    assert verdict["verdict"] is True, lines
    assert verdict["chaos_killed_inflight"] >= 1, lines
    assert verdict["chaos_retries"] >= 1, lines
    assert verdict["recovery_steps"] is not None, lines
    assert verdict["merged_trace_written"] is True, lines
    assert verdict["merged_spans_per_replica"] is True, lines
    assert verdict["merged_one_outcome_per_rid"] is True, lines
    assert verdict["merged_failover_on_two_tracks"] is True, lines
    assert verdict["merged_retried_tag_present"] is True, lines
    assert verdict["slo_windows_judged"] is True, lines
    assert verdict["baseline_slo_zero_breaches"] is True, lines
    by_mode = {d["mode"]: d for d in lines[:-1]}
    assert by_mode["chaos"]["router"]["kills"] == 1
    assert by_mode["baseline"]["router"]["affinity_routes"] > 0
