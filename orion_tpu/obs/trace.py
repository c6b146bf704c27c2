"""Low-overhead host-side span tracer (ISSUE 9 tentpole).

A monotonic-clock ring buffer of spans and instant events. Design
constraints, in order:

  1. **~Zero cost when disabled.** Callers hold a ``NULL_TRACER`` whose
     every method is a no-op returning a shared null context manager — no
     clock reads, no allocation, no branch beyond the attribute lookup.
     Compiled programs are never touched in either mode: the tracer is
     pure host-side bookkeeping around dispatches, not inside them.
  2. **Bounded.** The ring is a ``deque(maxlen=capacity)``; a serving
     engine that runs for a week holds the most recent ``capacity``
     events, which is exactly what the flight recorder wants to dump when
     something degrades.
  3. **Profiler-aligned.** ``PhaseClock`` (the engine's step phases)
     always enters ``jax.profiler.TraceAnnotation("orion/<phase>")`` and
     ``step_annotation()`` wraps ``StepTraceAnnotation``, so host spans
     land in the SAME xprof timeline as a concurrently captured device
     trace, under the name the ring and the docs use.

Export is Chrome trace-event JSON (``export_chrome``), loadable in
Perfetto / ``chrome://tracing``; timestamps are microseconds relative to
tracer construction. ``merge_chrome`` (ISSUE 14) merges N tracers —
the router's plus one per replica engine — into ONE timeline on a
shared clock: ring events carry absolute ``time.monotonic()`` stamps,
so reconciling per-tracer construction offsets is a single re-base
against the earliest tracer, and each source becomes its own Perfetto
process (``pid`` + ``process_name`` metadata).
"""

from __future__ import annotations

import json
import time
from collections import deque
from typing import Any, Optional

import jax

# Event tuples in the ring: (kind, name, t_start, t_end, tags) with kind
# "span" (t_end > t_start) or "instant" (t_end == t_start). Times are
# time.monotonic() seconds — wall-clock jumps (NTP) must never produce
# negative spans in a postmortem artifact.
Event = tuple[str, str, float, float, dict]


class _NullCtx:
    """Shared reusable no-op context manager (the disabled-tracer span)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


class NullTracer:
    """The disabled tracer: every call is a no-op. One shared instance
    (``NULL_TRACER``) serves every disabled engine/trainer, so the
    tracing-off host path is today's code plus one attribute lookup and
    a no-op ``with`` per dispatch."""

    enabled = False
    dropped = 0
    capacity = 0

    def span(self, name: str, **tags) -> _NullCtx:
        return _NULL_CTX

    def instant(self, name: str, **tags) -> None:
        return None

    def record_span(self, name: str, t_start: float, t_end: float,
                    **tags) -> None:
        return None

    def step_annotation(self, name: str, step: int) -> _NullCtx:
        return _NULL_CTX

    def events(self) -> list[Event]:
        return []

    def export_chrome(self, path: str) -> int:
        return 0

    def clear(self) -> None:
        return None


NULL_TRACER = NullTracer()


class _Span:
    """One live span: context manager that stamps monotonic start/end and
    appends to the owning tracer's ring on exit (exit always records —
    a span interrupted by an exception is exactly the span a postmortem
    wants to see)."""

    __slots__ = ("_tracer", "name", "tags", "t0", "t1")

    def __init__(self, tracer: "Tracer", name: str, tags: dict):
        self._tracer = tracer
        self.name = name
        self.tags = tags
        self.t0 = 0.0
        self.t1 = 0.0

    def __enter__(self) -> "_Span":
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.monotonic()
        self._tracer._append(
            ("span", self.name, self.t0, self.t1, self.tags)
        )
        return False

    @property
    def elapsed(self) -> float:
        return self.t1 - self.t0


class Tracer:
    """The enabled tracer: bounded ring of spans + instants.

    Thread-notes: ``deque.append`` is atomic under the GIL and the
    watchdog/async-checkpoint threads only ever ``instant()``, so no lock
    is needed on the hot path; ``events()`` snapshots with ``list()``.
    The ``dropped`` overflow counter's check-then-append pair is not
    atomic, so concurrent appends at the ring boundary can undercount by
    a few — acceptable for a truncation FLAG (zero stays exactly zero:
    no append ever drops before the ring is full).
    """

    enabled = True

    def __init__(self, capacity: int = 16384):
        if capacity < 1:
            raise ValueError(f"tracer capacity={capacity} must be >= 1")
        self.capacity = capacity
        self._ring: deque[Event] = deque(maxlen=capacity)
        self.t0 = time.monotonic()
        # Ring-overflow accounting (ISSUE 14 satellite): a deque(maxlen)
        # silently evicts the oldest event on overflow, which means a
        # long run's export is a TRUNCATED timeline — count evictions so
        # the registry can gauge it and obs_report can flag the export
        # instead of rendering a hole as if nothing happened.
        self.dropped = 0

    # -- recording ---------------------------------------------------------

    def _append(self, event: Event) -> None:
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(event)

    def span(self, name: str, **tags) -> _Span:
        """Context manager recording a [enter, exit) span."""
        return _Span(self, name, tags)

    def instant(self, name: str, **tags) -> None:
        t = time.monotonic()
        self._append(("instant", name, t, t, tags))

    def record_span(self, name: str, t_start: float, t_end: float,
                    **tags) -> None:
        """Append an already-measured span (times on the time.monotonic
        clock) — for call sites that measured it themselves (PhaseClock,
        the trainer's whole-step span)."""
        self._append(("span", name, t_start, t_end, tags))

    def step_annotation(self, name: str, step: int):
        """``jax.profiler.StepTraceAnnotation`` context: marks a train
        step boundary in the device profile, so xprof's step view lines
        up with the host spans recorded around the same dispatch."""
        return jax.profiler.StepTraceAnnotation(name, step_num=step)

    # -- reading / export --------------------------------------------------

    def events(self) -> list[Event]:
        """Snapshot of the ring, oldest first."""
        return list(self._ring)

    def clear(self) -> None:
        self._ring.clear()
        self.dropped = 0

    def metrics(self) -> dict[str, int]:
        """Ring gauges for the metrics registry ("trace" section): event
        count, capacity and the overflow-drop counter — a nonzero
        ``dropped`` means any export from this ring is a truncated
        timeline."""
        return {
            "events": len(self._ring),
            "capacity": self.capacity,
            "dropped": self.dropped,
        }

    def export_chrome(self, path: str) -> int:
        """Write the ring as Chrome trace-event JSON (Perfetto /
        chrome://tracing loadable); returns the number of events written.
        Spans are "X" (complete) events, instants "i"; ``ts``/``dur`` are
        microseconds relative to tracer construction; tags ride ``args``.
        The top-level ``metadata`` block carries the monotonic clock base
        (so merged/compared exports can reconcile offsets) and the
        ring-overflow drop count (so consumers can flag truncation).
        """
        evs: list[dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
             "args": {"name": "orion-tpu host"}},
        ]
        evs.extend(_chrome_events(self.events(), self.t0, pid=0))
        meta = {
            "clock_base_monotonic_s": self.t0,
            "dropped_events": self.dropped,
            "ring_capacity": self.capacity,
        }
        _write_chrome(path, evs, meta)
        return len(evs) - 1  # metadata event excluded


class _Phase:
    """One live phase of a ``PhaseClock`` (see there)."""

    __slots__ = ("_clock", "name", "_keys", "_ann", "tags", "t0", "t1",
                 "_children")

    def __init__(self, clock: "PhaseClock", name: str):
        self._clock = clock
        self.name = clock.names[name]
        self._keys = clock.keys[name]
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self.tags: Optional[dict] = None
        self.t0 = self.t1 = self._children = 0.0

    def __enter__(self) -> "_Phase":
        clock = self._clock
        if clock.tracer.enabled:
            self.tags = clock.tags()
        clock.stack.append(self)
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = t1 = time.monotonic()
        self._ann.__exit__(exc_type, exc, tb)
        clock = self._clock
        clock.stack.pop()
        covered = self._children
        if self._keys and exc_type is None:
            covered = t1 - self.t0
            own = covered - self._children
            buckets = clock.buckets()
            for key in self._keys:
                buckets[key] += own
        if clock.stack:
            clock.stack[-1]._children += covered
        if self.tags is not None:
            clock.tracer.record_span(self.name, self.t0, t1, **self.tags)
        return False


class PhaseClock:
    """The ONE span primitive for the phases of an owner's step (the
    serving engine's ``orion/<phase>`` spans). ``clock(phase)`` is a
    context manager that

      - always adds the host time it covered (``time.monotonic``, the
        ring's clock) to the phase's buckets: ``keys[phase]`` names the
        entries of ``buckets()`` it feeds. A phase books its SELF time —
        what no nested phase booked — so the buckets of one step
        partition it exactly, however the phases nest. A phase that
        raises books nothing and one with no keys never does (a retry
        marker): their time stays with the enclosing phase;
      - always enters ``jax.profiler.TraceAnnotation("orion/<phase>")``:
        under a profiler session the span lands on the profiler's clock
        beside the device planes, without one it costs well under a
        microsecond;
      - with the tracer enabled, appends the span to the ring with
        ``tags()`` (built at entry, and only then).

    The set of phases is the fixed ``keys`` table (an unknown phase is a
    KeyError): nothing per token, per request or per slot opens a span.
    """

    def __init__(self, tracer, keys: dict, buckets, tags):
        self.tracer = tracer
        self.keys = keys
        self.names = {phase: "orion/" + phase for phase in keys}
        self.buckets = buckets
        self.tags = tags
        self.stack: list = []

    def __call__(self, phase: str) -> _Phase:
        return _Phase(self, phase)


def _chrome_events(
    events: list[Event], base: float, pid: int
) -> list[dict[str, Any]]:
    """Ring events as Chrome trace-event dicts: ``ts``/``dur`` in
    microseconds re-based against ``base`` (a monotonic-clock origin),
    under process id ``pid``. Shared by the single-tracer export and the
    multi-source merge, so both emit identical event shapes."""
    out: list[dict[str, Any]] = []
    for kind, name, t_start, t_end, tags in events:
        ev: dict[str, Any] = {
            "name": name,
            "ts": (t_start - base) * 1e6,
            "pid": pid,
            "tid": 0,
            "args": dict(tags),
        }
        if kind == "span":
            ev["ph"] = "X"
            ev["dur"] = (t_end - t_start) * 1e6
        else:
            ev["ph"] = "i"
            ev["s"] = "t"
        out.append(ev)
    return out


def _write_chrome(path: str, evs: list, meta: dict) -> None:
    # tmp + atomic rename, like every other obs artifact writer: a
    # poller watching trace_path (or a mid-write crash) must never see
    # a torn multi-MB JSON. default=str: a non-primitive tag value
    # degrades to its repr, never TypeErrors a shutdown-path export.
    import os

    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(
            {"traceEvents": evs, "displayTimeUnit": "ms",
             "metadata": meta},
            f, default=str,
        )
    os.replace(tmp, path)


def merge_chrome(
    path: str, sources: list[tuple[str, Any]]
) -> int:
    """Merge N tracers into ONE Perfetto timeline (ISSUE 14 tentpole):
    ``sources`` is ``[(name, tracer)]`` — e.g. the router's tracer plus
    one per replica engine. Each source becomes its own Perfetto process
    (``pid`` = source index, ``process_name``/``thread_name`` metadata =
    the source name); every event is re-based onto the SHARED clock (the
    earliest tracer's construction origin — ring events carry absolute
    ``time.monotonic()`` stamps, so per-tracer offsets reconcile by
    subtraction, no cross-process clock sync needed for in-process
    replicas). Disabled (Null) tracers contribute an empty process, so
    the process list always names the whole fleet. Returns the number of
    events written (metadata rows excluded); the top-level ``metadata``
    block carries per-process event/drop counts so a truncated replica
    ring is visible in the artifact itself."""
    enabled = [tr for _, tr in sources if tr.enabled]
    base = min((tr.t0 for tr in enabled), default=0.0)
    evs: list[dict[str, Any]] = []
    procs: dict[str, Any] = {}
    total = 0
    for pid, (name, tr) in enumerate(sources):
        evs.append({"name": "process_name", "ph": "M", "pid": pid,
                    "tid": 0, "args": {"name": name}})
        evs.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": 0, "args": {"name": name}})
        rows = _chrome_events(tr.events(), base, pid=pid)
        evs.extend(rows)
        total += len(rows)
        procs[name] = {
            "pid": pid,
            "events": len(rows),
            "dropped": tr.dropped,
            "clock_offset_us": (
                (tr.t0 - base) * 1e6 if tr.enabled else None
            ),
        }
    meta = {
        "merged": True,
        "clock_base_monotonic_s": base,
        "dropped_events": sum(tr.dropped for _, tr in sources),
        "processes": procs,
    }
    _write_chrome(path, evs, meta)
    return total


def merge_chrome_safe(
    path: Optional[str], sources: list[tuple[str, Any]]
) -> int:
    """``merge_chrome`` under the shared shutdown-path error contract
    (the fleet analog of ``export_chrome_safe``): no-op when no path is
    configured or every source is disabled; a write failure is logged,
    never raised. Returns events written."""
    import logging

    log = logging.getLogger("orion_tpu.obs")
    if not path or not any(tr.enabled for _, tr in sources):
        return 0
    try:
        n = merge_chrome(path, sources)
        log.info(
            "merged %d trace events from %d processes to %s "
            "(load in Perfetto)", n, len(sources), path,
        )
        return n
    except OSError as e:
        log.error("merged trace export to %s failed: %s", path, e)
        return 0


def namespaced_path(path: str, tag: str) -> str:
    """Per-replica sink path: insert ``tag`` before the extension —
    ``("/tmp/trace.json", "replica-0")`` -> ``/tmp/trace.replica-0.json``
    — so N replicas exporting the "same" configured target never clobber
    one file (ISSUE 14; PR 11 stripped replica targets instead)."""
    import os

    root, ext = os.path.splitext(path)
    return f"{root}.{tag}{ext}" if ext else f"{path}.{tag}"


def export_chrome_safe(tracer, path: Optional[str]) -> int:
    """Chrome export with the shared error contract (engine.close and
    Trainer.fit both end with this): no-op when tracing is off or no path
    is configured, and an export failure is logged, never raised — a full
    disk must not fail a clean shutdown. Returns events written."""
    import logging

    log = logging.getLogger("orion_tpu.obs")
    if not path or not tracer.enabled:
        return 0
    try:
        n = tracer.export_chrome(path)
        log.info("exported %d trace events to %s (load in Perfetto)",
                 n, path)
        return n
    except OSError as e:
        log.error("trace export to %s failed: %s", path, e)
        return 0


def serialize_events(events: list[Event]) -> list[dict[str, Any]]:
    """Ring events as JSON-ready dicts (the flight-recorder dump format;
    times stay monotonic seconds so dump consumers can window on them)."""
    return [
        {"kind": kind, "name": name, "t_start": t_start, "t_end": t_end,
         "dur_ms": (t_end - t_start) * 1e3, **({"tags": tags} if tags else {})}
        for kind, name, t_start, t_end, tags in events
    ]
