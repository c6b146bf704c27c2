"""Observability layer (ISSUE 9): span tracer, flight recorder, metrics
registry, and their engine/trainer wiring.

Acceptance pins:
  - tracing OFF leaves engine behavior identical (token-identical run) and
    ON exports a Chrome trace whose spans cover every dispatch and whose
    instants cover every request outcome;
  - an injected fault (FaultInjector) produces a flight-recorder dump
    containing the fault-adjacent span window;
  - LatencyStats percentile math is exact on known inputs (the collector
    previously shipped untested);
  - registry snapshot/reset semantics survive reset_timing's drain.
"""

from __future__ import annotations

import glob
import json

import jax
import numpy as np
import pytest

from orion_tpu.config import get_config
from orion_tpu.metrics import LatencyStats
from orion_tpu.obs import (
    NULL_TRACER,
    FlightRecorder,
    MetricsRegistry,
    Tracer,
)

BASE = [
    "model.max_seq_len=256",
    "inference.max_seq_len=256",
    "inference.page_size=16",
    "inference.num_pages=32",
    "inference.max_batch_size=4",
    "inference.prefill_chunk=16",
    "inference.decode_window=2",
]


def make_engine(extra=(), params=None, injector=None, seed=0):
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models import init_params

    cfg = get_config("tiny-llama", BASE + list(extra))
    if params is None:
        params = init_params(cfg.model, jax.random.key(0))
    return InferenceEngine(
        cfg, params, seed=seed, fault_injector=injector
    ), params


# ---------------------------------------------------------------------------
# Tracer primitive
# ---------------------------------------------------------------------------


def test_tracer_spans_instants_and_ring_bound(tmp_path):
    tr = Tracer(capacity=4)
    with tr.span("a", step=1):
        pass
    tr.instant("mark", rid=7)
    evs = tr.events()
    assert [e[1] for e in evs] == ["a", "mark"]
    kind, name, t0, t1, tags = evs[0]
    assert kind == "span" and t1 >= t0 and tags == {"step": 1}
    assert evs[1][0] == "instant" and evs[1][4] == {"rid": 7}
    # Ring bound: capacity 4 keeps only the newest 4.
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 4
    assert tr.events()[-1][1] == "e9"
    # Chrome export round-trips and marks spans "X" with a duration.
    path = tmp_path / "t.json"
    n = tr.export_chrome(str(path))
    doc = json.loads(path.read_text())
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert n == len(evs) == 4
    assert all(e["ph"] == "i" for e in evs)   # only instants survived


def test_tracer_span_records_on_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert [e[1] for e in tr.events()] == ["boom"]


def test_null_tracer_is_inert(tmp_path):
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.span("a"):
        pass
    NULL_TRACER.instant("b")
    NULL_TRACER.record_span("c", 0.0, 1.0)
    assert NULL_TRACER.events() == []
    assert NULL_TRACER.export_chrome(str(tmp_path / "x.json")) == 0
    assert not (tmp_path / "x.json").exists()


# ---------------------------------------------------------------------------
# LatencyStats percentile math (satellite: previously untested)
# ---------------------------------------------------------------------------


def test_latency_percentile_exact_ranks():
    st = LatencyStats()
    for v in (0.040, 0.010, 0.030, 0.020):   # unsorted on purpose
        st.record(v)
    # Nearest-rank on n=4: rank = ceil(p/100 * 4).
    assert st.percentile(25) == 0.010
    assert st.percentile(50) == 0.020
    assert st.percentile(75) == 0.030
    assert st.percentile(95) == 0.040
    assert st.percentile(100) == 0.040
    assert st.percentile(0) == 0.010   # clamps to the first rank
    s = st.summary()
    assert s["count"] == 4 and s["max"] == 0.040
    assert s["mean"] == pytest.approx(0.025)
    assert s["p50"] == 0.020 and s["p99"] == 0.040


def test_latency_percentile_edge_cases():
    empty = LatencyStats()
    assert empty.percentile(50) == 0.0
    assert empty.summary() == {
        "count": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0,
        "max": 0.0,
    }
    single = LatencyStats()
    single.record(0.5)
    for p in (0, 1, 50, 99, 100):
        assert single.percentile(p) == 0.5
    # n=100: p99 is the 99th rank (index 98), not the max.
    many = LatencyStats(samples=[float(i) for i in range(1, 101)])
    assert many.percentile(99) == 99.0
    assert many.percentile(50) == 50.0
    assert many.percentile(1) == 1.0


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_registry_snapshot_and_exporters(tmp_path):
    reg = MetricsRegistry()
    reg.register("a", lambda: {"x": 1, "y": 2.5, "name": "str"})
    reg.register("b", lambda: {"z": True})
    snap = reg.snapshot()
    assert snap == {"a.x": 1, "a.y": 2.5, "a.name": "str", "b.z": True}
    assert reg.snapshot(sections=("b",)) == {"b.z": True}
    with pytest.raises(ValueError):
        reg.register("bad name", lambda: {})
    # A raising provider degrades to an error key, never raises through.
    reg.register("c", lambda: 1 / 0)
    assert "c.error" in reg.snapshot()
    reg.unregister("c")
    # Prometheus textfile: numeric samples only, sanitized names.
    prom = tmp_path / "m.prom"
    n = reg.export_prometheus(str(prom))
    lines = prom.read_text().splitlines()
    assert n == len(lines) == 3   # a.name is a string -> skipped
    assert "orion_a_x 1" in lines
    assert "orion_b_z 1" in lines
    # JSONL: one row per call, ts + snapshot.
    jl = tmp_path / "m.jsonl"
    reg.export_jsonl(str(jl))
    reg.export_jsonl(str(jl))
    rows = [json.loads(x) for x in jl.read_text().splitlines()]
    assert len(rows) == 2 and rows[0]["a.x"] == 1 and "ts" in rows[1]


def test_engine_registry_survives_reset_timing(tmp_path):
    jsonl = tmp_path / "serve.jsonl"
    prom = tmp_path / "serve.prom"
    eng, _ = make_engine([
        f"inference.metrics_jsonl={jsonl}",
        f"inference.metrics_prom={prom}",
    ])
    eng.generate([[1, 2, 3], [4, 5, 6, 7]], 6)
    snap = eng.registry.snapshot()
    assert snap["engine.steps"] > 0
    assert snap["pool.num_pages"] == 32
    assert 0.0 <= snap["pool.occupancy"] <= 1.0
    t = eng.reset_timing()
    assert t["steps"] > 0
    # Drain-and-zero: the registry's lazy providers now read the NEW
    # window (zeroed counters), not a stale snapshot of the old objects.
    snap2 = eng.registry.snapshot()
    assert snap2["engine.steps"] == 0
    assert snap2["robust.shed_requests"] == 0
    # The exporters rode the drain point: one JSONL row per reset_timing,
    # prom textfile rewritten, both carrying the DRAINED window.
    rows = [json.loads(x) for x in jsonl.read_text().splitlines()]
    assert len(rows) == 1
    assert rows[0]["serve.steps"] == t["steps"]
    assert any(line.startswith("orion_serve_steps ")
               for line in prom.read_text().splitlines())
    # Another drain appends another row.
    eng.generate([[9, 9]], 2)
    eng.reset_timing()
    assert len(jsonl.read_text().splitlines()) == 2
    # close() flushes the tail window exactly once (idempotent: a second
    # close must not append a spurious all-zero row).
    eng.close()
    eng.close()
    assert len(jsonl.read_text().splitlines()) == 3


# ---------------------------------------------------------------------------
# Engine tracing: off == today, on == full lifecycle coverage
# ---------------------------------------------------------------------------


def test_trace_off_identical_and_trace_covers_lifecycle(tmp_path):
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
    eng, params = make_engine()
    plain = eng.generate(prompts, 6)
    assert eng._tracer is NULL_TRACER    # off by default: null everywhere

    path = tmp_path / "serve_trace.json"
    eng2, _ = make_engine(
        ["inference.trace=true", f"inference.trace_path={path}"],
        params=params,
    )
    traced = eng2.generate(prompts, 6)
    assert traced == plain               # tracing never changes tokens
    t = eng2.reset_timing()
    eng2.close()                         # exports inference.trace_path

    doc = json.loads(path.read_text())
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    inst = [e for e in evs if e["ph"] == "i"]
    # Every dispatch has two spans of its name, its launch and its wait
    # (the plain step queues the window between the prefill's two): the
    # prefill burst + the window of every decode step; every step has an
    # "orion/step" span.
    dispatch = [e for e in spans if e["name"].endswith("/run")]
    assert sum(
        1 for e in dispatch if e["name"] == "orion/prefill/run"
    ) == 2 * t["prefill_dispatches"] >= 2
    n_decode = sum(1 for e in dispatch if e["name"] == "orion/decode/run")
    assert n_decode == 2 * t["windows"]
    assert sum(1 for e in spans if e["name"] == "orion/step") == t["steps"]
    assert all(e["dur"] >= 0 for e in spans)
    # Full request lifecycle: submit -> admit -> first_token -> outcome,
    # once per request, tagged with rid and the typed outcome.
    for name in ("submit", "admit", "first_token"):
        assert sum(1 for e in inst if e["name"] == name) == len(prompts), name
    outcomes = [e for e in inst if e["name"] == "outcome"]
    assert len(outcomes) == len(prompts)
    assert {e["args"]["outcome"] for e in outcomes} == {"completed"}
    assert {e["args"]["rid"] for e in outcomes} == {0, 1, 2}


def test_trace_path_alone_implies_recording(tmp_path):
    """A configured export target must never silently produce nothing:
    inference.trace_path implies recording even with `trace` off."""
    path = tmp_path / "t.json"
    eng, _ = make_engine([f"inference.trace_path={path}"])
    assert eng._tracer.enabled
    eng.generate([[1, 2, 3]], 2)
    eng.close()
    doc = json.loads(path.read_text())
    assert any(e.get("name") == "outcome" for e in doc["traceEvents"])


def test_trace_tags_typed_outcomes_and_deadline(tmp_path):
    """Expired and shed requests carry their typed outcome in the trace."""
    path = tmp_path / "tr.json"
    eng, _ = make_engine([
        "inference.trace=true", f"inference.trace_path={path}",
        "inference.queue_limit=1",
    ])
    eng.submit([1, 2, 3], 4, deadline_s=1e-4)   # expires before step 1
    import time

    time.sleep(0.01)
    while eng.has_work():
        eng.step()
    eng.close()
    doc = json.loads(path.read_text())
    out = [e["args"]["outcome"] for e in doc["traceEvents"]
           if e.get("name") == "outcome"]
    assert out == ["expired"]


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_flight_dump_on_injected_nan_fault(tmp_path):
    """The acceptance pin: an injected fault produces a flight-recorder
    dump containing the fault-adjacent span window."""
    from orion_tpu.runtime.fault import FaultInjector, FaultSpec

    inj = FaultInjector(specs=[FaultSpec("nan", step=2)])
    eng, _ = make_engine(
        ["inference.nan_guard=true", "inference.trace=true",
         f"inference.flight_dir={tmp_path}"],
        injector=inj,
    )
    reqs = [eng.submit_request([1, 2, 3], 8),
            eng.submit_request([4, 5, 6, 7], 8)]
    while eng.has_work():
        eng.step()
    assert inj.fired == [("nan", 2, None)]
    assert sorted(r.outcome for r in reqs) == ["completed", "error:nan"]
    dumps = glob.glob(str(tmp_path / "flight_nan_quarantine_*.json"))
    assert len(dumps) == 1
    doc = json.loads(open(dumps[0]).read())
    assert doc["reason"] == "nan_quarantine"
    assert doc["context"]["step"] == 2
    # Fault-adjacent span window: the dispatches leading up to the
    # quarantine are in the dump.
    span_names = {s["name"] for s in doc["spans"] if s["kind"] == "span"}
    assert any(
        n.startswith("orion/") and n.endswith("/run") for n in span_names
    )
    # The injected fault itself was stamped into the event ring (the
    # FaultInjector on_fire observer).
    assert any(e["kind"] == "injected_fault" for e in doc["events"])
    # Postmortem metrics snapshot shows the quarantine.
    assert doc["metrics"]["robust.quarantined_requests"] == 1


def test_flight_dump_on_max_step_faults(tmp_path):
    from orion_tpu.runtime.fault import (
        DispatchFault, FaultInjector, FaultSpec,
    )

    inj = FaultInjector(specs=[
        FaultSpec("dispatch", step=s, path="decode") for s in range(1, 3)
    ])
    eng, _ = make_engine(
        ["inference.max_step_faults=2", "inference.dispatch_fallback=false",
         f"inference.flight_dir={tmp_path}"],
        injector=inj,
    )
    eng.submit([1, 2, 3], 8)
    eng.step()   # prefill step
    eng.step()   # decode fault 1/2 (contained)
    with pytest.raises(DispatchFault):
        eng.step()   # decode fault 2/2 -> re-raise + dump
    dumps = glob.glob(str(tmp_path / "flight_max_step_faults_*.json"))
    assert len(dumps) == 1
    doc = json.loads(open(dumps[0]).read())
    assert doc["context"]["consecutive"] == 2
    failed = [e for e in doc["events"] if e["kind"] == "failed_step"]
    assert len(failed) == 2   # both contained episodes are in the ring


def test_flight_recorder_unit(tmp_path):
    tr = Tracer()
    fr = FlightRecorder(tr, str(tmp_path), capacity=3,
                        snapshot=lambda: {"g.x": 1})
    with tr.span("work"):
        pass
    for i in range(5):
        fr.note("evt", i=i)
    p = fr.dump("unit_test", why="test")
    assert fr.dumps == [p]
    doc = json.loads(open(p).read())
    assert doc["reason"] == "unit_test"
    assert doc["context"] == {"why": "test"}
    assert [e["i"] for e in doc["events"]] == [2, 3, 4]   # ring bound 3
    assert doc["metrics"] == {"g.x": 1}
    # The tracer span made it into the dumped window, with both notes'
    # instants (note() mirrors into the tracer).
    assert {s["name"] for s in doc["spans"]} == {"work", "evt"}
    # Throttle: a repeat of the same reason inside min_interval_s is
    # suppressed (counted, not written) — a per-step trigger must not
    # stream dumps during a long incident; a different reason still dumps.
    assert fr.dump("unit_test") is None
    assert fr.throttled == 1
    assert fr.dump("other_reason") is not None
    assert len(fr.dumps) == 2


# ---------------------------------------------------------------------------
# obs_report renderer
# ---------------------------------------------------------------------------


def test_obs_report_renders_trace_and_dump(tmp_path, capsys):
    import tools.obs_report as obs_report

    path = tmp_path / "serve_trace.json"
    eng, params = make_engine(
        ["inference.trace=true", f"inference.trace_path={path}"]
    )
    eng.generate([[1, 2, 3], [4, 5, 6, 7]], 6)
    eng.close()
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "span groups by self time" in out
    assert "engine step split" in out and "orion/decode/emit" in out
    assert "orion/decode/run" in out
    # the executor's launch and wait leaves: rows of the group table, and in
    # the step split shown under their run parent
    split = out[out.index("engine step split"):out.index("slowest")]
    assert "orion/decode/launch" in out and "orion/decode/launch" not in split
    assert "of it launch" in split and "of it wait" in split
    assert "per-request TTFT breakdown" in out
    assert "completed" in out

    # Flight-dump rendering (fault window section).
    tr = Tracer()
    fr = FlightRecorder(tr, str(tmp_path), snapshot=lambda: {
        "robust.failed_steps": 3, "engine.steps": 9,
    })
    with tr.span("orion/decode/run", step=1):
        pass
    fr.note("dispatch_fault", path="decode", step=1)
    p = fr.dump("watchdog_stall")
    assert obs_report.main([p]) == 0
    out = capsys.readouterr().out
    assert "reason=watchdog_stall" in out
    assert "dispatch_fault" in out
    assert "robust.failed_steps" in out

    # --compare diffs two artifacts.
    assert obs_report.main(["--compare", str(path), p]) == 0
    assert "span-share diff" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Ring overflow accounting + merged fleet export (ISSUE 14)
# ---------------------------------------------------------------------------


def test_tracer_overflow_counted_and_exported(tmp_path):
    """Ring overflow is no longer silent: dropped events are counted,
    surface in the registry-style metrics() gauges and in the export's
    metadata block, and clear() resets them with the ring."""
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert tr.dropped == 6
    assert tr.metrics() == {"events": 4, "capacity": 4, "dropped": 6}
    path = tmp_path / "t.json"
    tr.export_chrome(str(path))
    meta = json.loads(path.read_text())["metadata"]
    assert meta["dropped_events"] == 6
    assert meta["ring_capacity"] == 4
    assert "clock_base_monotonic_s" in meta
    tr.clear()
    assert tr.dropped == 0 and tr.events() == []
    # Refilling below capacity drops nothing.
    tr.instant("x")
    assert tr.dropped == 0


def test_engine_trace_registry_section(tmp_path):
    """The engine registers the trace-ring gauges only when tracing is
    on — the obs-off snapshot keys (and thus the Prometheus row set)
    are unchanged."""
    eng, params = make_engine(["inference.trace=true",
                               "inference.trace_ring=8"])
    eng.generate([[1, 2, 3]], 4)
    snap = eng.registry.snapshot(sections=("trace",))
    assert snap["trace.capacity"] == 8
    assert snap["trace.dropped"] > 0      # tiny ring overflowed
    eng.close()
    off, _ = make_engine(params=params)
    assert "trace" not in off.registry.sections()
    off.close()


def test_merge_chrome_shared_clock(tmp_path):
    """merge_chrome: one process per source, events re-based onto the
    EARLIEST tracer's clock (per-process monotonic offsets reconciled),
    process_name metadata per pid, per-process drop counts in the
    metadata block; a NullTracer source contributes an empty process."""
    import time as _time

    from orion_tpu.obs import merge_chrome

    t1 = Tracer()
    t1.instant("a", rid=1)
    _time.sleep(0.02)
    t2 = Tracer()                 # constructed later: positive offset
    t2.instant("b", rid=2)
    path = tmp_path / "merged.json"
    n = merge_chrome(str(path), [
        ("router", t1), ("replica-0", t2), ("replica-1", NULL_TRACER),
    ])
    assert n == 2
    doc = json.loads(path.read_text())
    procs = {
        e["pid"]: e["args"]["name"]
        for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert procs == {0: "router", 1: "replica-0", 2: "replica-1"}
    evs = {
        e["name"]: e for e in doc["traceEvents"] if e["ph"] == "i"
    }
    # Shared clock: t2's event happened AFTER t1's on the merged axis,
    # even though both are "early" relative to their own tracer's t0.
    assert evs["b"]["ts"] > evs["a"]["ts"]
    assert evs["a"]["pid"] == 0 and evs["b"]["pid"] == 1
    meta = doc["metadata"]
    assert meta["merged"] is True
    assert meta["processes"]["replica-0"]["clock_offset_us"] > 0
    assert meta["processes"]["replica-1"]["events"] == 0


def test_obs_report_flags_truncation_and_fleet(tmp_path, capsys):
    """obs_report on a merged trace: flags ring truncation instead of
    rendering a hole, renders the per-process share table, the fleet
    event timeline, correlated request tracks, and the SLO burn panel."""
    import tools.obs_report as obs_report

    from orion_tpu.obs import merge_chrome

    rt = Tracer(capacity=4)       # will overflow -> truncation flag
    for i in range(6):
        rt.instant("route", rid=i, tid=i, replica=0)
    rt.instant("retry", rid=5, tid=5, attempt=1, backoff_steps=1,
               reason="replica 0: killed")
    rt.instant("slo_breach", objective="itl_all", burn=3.2, events=10,
               worst_ms=410.0, target_ms=50.0, goal=0.9)
    rt.instant("outcome", rid=5, tid=5, outcome="completed", retried=1)
    rep = Tracer()
    with rep.span("orion/decode/run", step=0):
        pass
    rep.instant("admit", rid=0, tid=5, retried=1, slot=0)
    path = tmp_path / "merged.json"
    merge_chrome(str(path), [("router", rt), ("replica-0", rep)])
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "merged fleet trace" in out
    assert "TRUNCATED TIMELINE" in out and "dropped" in out
    assert "per-process span shares" in out
    assert "fleet events" in out and "slo_breach" in out
    assert "request tracks" in out
    assert "retry1" in out            # the retried hop is tagged
    assert "SLO burn panel" in out and "itl_all" in out
    # A plain single-process trace renders WITHOUT the fleet sections.
    solo = tmp_path / "solo.json"
    rep.export_chrome(str(solo))
    assert obs_report.main([str(solo)]) == 0
    out = capsys.readouterr().out
    assert "merged" not in out and "per-process span shares" not in out


# ---------------------------------------------------------------------------
# Trainer tracing + rollback trigger
# ---------------------------------------------------------------------------


def test_trainer_trace_phases(tmp_path):
    from orion_tpu.train import Trainer

    path = tmp_path / "train_trace.json"
    cfg = get_config("tiny", [
        "train.num_steps=3", "train.trace=true",
        f"train.trace_path={path}",
        f"checkpoint.directory={tmp_path / 'ckpt'}",
    ])
    hist = Trainer(cfg).fit()
    assert len(hist) == 3
    doc = json.loads(path.read_text())
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    for phase in ("data", "dispatch", "ckpt", "train_step"):
        assert names.count(phase) == 3, (phase, names)
    # The per-train-step phases nest inside the step span (timeline
    # sanity: dispatch duration <= train_step duration at each step).
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    by_step = {}
    for e in spans:
        by_step.setdefault(e["args"].get("step"), {})[e["name"]] = e
    for step, d in by_step.items():
        assert d["dispatch"]["dur"] <= d["train_step"]["dur"] + 1e3


def test_trainer_rollback_flight_dump(tmp_path):
    """The PR 7 trigger: anomaly auto-rollback writes a postmortem."""
    from orion_tpu.runtime.fault import FaultInjector, FaultSpec
    from orion_tpu.train import Trainer

    inj = FaultInjector(
        specs=[FaultSpec("nan", step=2, path="train")]
    )
    cfg = get_config("tiny", [
        "train.num_steps=4", "train.anomaly_guard=true",
        "train.anomaly_limit=1",
        f"train.flight_dir={tmp_path / 'flight'}",
        f"checkpoint.directory={tmp_path / 'ckpt'}",
        "checkpoint.save_interval_steps=1",
    ])
    t = Trainer(cfg, fault_injector=inj)
    hist = t.fit()
    assert t.robustness.rollbacks == 1
    dumps = glob.glob(str(tmp_path / "flight" / "flight_anomaly_rollback_*"))
    assert len(dumps) == 1
    doc = json.loads(open(dumps[0]).read())
    assert doc["context"]["failed_step"] == 2
    assert doc["metrics"]["robust.rollbacks"] == 1
    # The injected train fault was stamped into the event ring.
    assert any(e["kind"] == "injected_fault" for e in doc["events"])
    # The anomalous step's span window includes its CLOSED train_step
    # span (recorded before the rollback's `continue`, so the step that
    # triggered the rollback is not a hole in the timeline).
    steps_spanned = [
        s for s in doc["spans"]
        if s["name"] == "train_step" and s.get("tags", {}).get("anomalous")
    ]
    assert steps_spanned, [s["name"] for s in doc["spans"]]
