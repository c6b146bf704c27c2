"""Failure detection, elastic recovery and fault injection — shared by the
training and serving stacks (SURVEY.md §6 "Failure detection / elastic
recovery / fault injection").

Promoted from ``orion_tpu.train.fault`` (whose deprecation shim is now
removed): the serving engine needs exactly the same machinery the trainer
grew — preemption flagging for SIGTERM drains, a stall watchdog around
the step loop, and an inject-and-assert-recovery test pattern — so the
module lives with the runtime.

TPU-native mapping of the reference's torchelastic-class machinery:

  - ``PreemptionHandler`` — TPU pods are preempted with SIGTERM; the handler
    flips a flag that the trainer (step boundary -> final checkpoint) and
    the serving entry point (stop admission -> drain live requests) both
    check; signal delivery itself only sets the flag.
  - ``run_with_restarts`` — the in-process supervisor loop: rebuild the
    trainer and resume from the latest checkpoint after a recoverable
    failure.
  - ``Watchdog`` — step-progress heartbeat; a hung collective or a wedged
    dispatch trips the callback after ``timeout_s`` without a heartbeat.
    Training uses action="abort" (a hung collective is unrecoverable
    in-process); the serving engine uses the default flag-only callback so
    a stalled step fails the STEP, never the process.
  - ``FaultInjector`` — the shared injection harness. Serving
    (InferenceEngine(..., fault_injector=...)): dispatch exceptions, NaN
    logits (page poisoning), page-pool exhaustion and artificial step
    stalls, each at a configured engine step. Training (ISSUE 8;
    Trainer(..., fault_injector=...) consults the same ``take()`` with
    path="train"): "dispatch" raises before the compiled step runs (feeds
    run_with_restarts), "nan" routes the step through a poisoned loss so
    REAL NaNs flow through the real backward into every grad leaf (the
    anomaly guard's quarry), and "partial_write" tears the checkpoint
    commit (an array file is truncated after its manifest checksum was
    recorded — restore must detect and fall back). The legacy
    train.inject_fault_at_step hook remains — same closing-the-loop idea:
    tests crash a real run and assert recovery.
"""

from __future__ import annotations

import logging
import signal
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence, Type

log = logging.getLogger("orion_tpu.fault")


class Preempted(RuntimeError):
    """Raised by the trainer after a preemption-triggered final save."""


class InjectedFault(RuntimeError):
    """A FaultInjector-scheduled dispatch exception (serving tests)."""


class DispatchFault(RuntimeError):
    """A serving dispatch failed on every available path (primary and, when
    one exists, the XLA reference fallback). Carries the coarse dispatch
    ``path`` name so the engine's degradation ladder can react per path
    (e.g. repeated "verify" faults auto-disable speculation)."""

    def __init__(self, path: str, detail: str = ""):
        super().__init__(f"{path} dispatch failed{': ' + detail if detail else ''}")
        self.path = path


class PreemptionHandler:
    """Installs SIGTERM/SIGINT-compatible preemption flagging.

    Usage: ``with PreemptionHandler() as h: ... if h.preempted: save+exit``.
    Signal delivery only sets a flag — all real work (checkpoint save, or
    the serving engine's admission-stop + drain) happens synchronously at a
    step boundary, where the state is consistent.

    Idempotent on re-entry: a nested ``__enter__`` keeps the ORIGINAL
    previous dispositions (it must not record its own handler as "prior"),
    and ``__exit__`` restores them exactly once.
    """

    def __init__(self, signals: Sequence[int] = (signal.SIGTERM,)):
        self.signals = tuple(signals)
        self._flag = threading.Event()
        self._prev: dict[int, object] = {}

    @property
    def preempted(self) -> bool:
        return self._flag.is_set()

    def _on_signal(self, signum, frame):
        log.warning("received signal %d: preemption flagged", signum)
        self._flag.set()

    def __enter__(self) -> "PreemptionHandler":
        for s in self.signals:
            if s in self._prev:
                continue  # double-enter: the first entry's prior handler wins
            try:
                self._prev[s] = signal.signal(s, self._on_signal)
            except ValueError:
                # Not the main thread (e.g. under some test runners): fall
                # back to manual .trigger() only.
                log.debug("cannot install handler for signal %d", s)
        return self

    def trigger(self) -> None:
        """Manually flag preemption (tests / external schedulers)."""
        self._flag.set()

    def __exit__(self, *exc) -> None:
        for s, prev in self._prev.items():
            signal.signal(s, prev)
        self._prev.clear()


def run_with_restarts(
    make_and_fit: Callable[[int], object],
    *,
    max_restarts: int = 3,
    retry_on: tuple[Type[BaseException], ...] = (Exception,),
    non_retryable: tuple[Type[BaseException], ...] = (ValueError, TypeError),
    backoff_s: float = 0.0,
    on_retry: Optional[Callable[[int, BaseException], None]] = None,
) -> object:
    """Supervisor loop: call ``make_and_fit(attempt)``, restarting on failure.

    ``make_and_fit`` must rebuild its world from scratch (config -> Trainer
    -> restore_or_init -> fit) so every attempt resumes from the newest
    checkpoint. KeyboardInterrupt and Preempted always propagate — those are
    orderly shutdowns, not failures — as do ``non_retryable`` types
    (config/typo errors are deterministic; retrying them wastes compute).

    ``on_retry(attempt, exc)`` fires before each restart with the attempt
    number about to run and the exception that killed the previous one —
    the hook train.py uses to thread the restart count and last fault
    reason into the next attempt's step log.
    """
    attempt = 0
    while True:
        try:
            return make_and_fit(attempt)
        except (KeyboardInterrupt, Preempted):
            raise
        except non_retryable:
            raise
        except retry_on as e:
            attempt += 1
            if attempt > max_restarts:
                log.error("giving up after %d restarts", max_restarts)
                raise
            log.warning(
                "attempt %d failed (%s: %s); restarting (%d/%d)",
                attempt - 1, type(e).__name__, e, attempt, max_restarts,
            )
            if on_retry is not None:
                on_retry(attempt, e)
            if backoff_s:
                time.sleep(backoff_s)


class Watchdog:
    """Detects a stalled step loop (hung collective / dead host / wedged
    dispatch).

    The step loop calls ``heartbeat()`` once per completed step; once armed,
    if no heartbeat arrives within ``timeout_s``, ``on_stall`` fires
    (default: log loudly). The watchdog ARMS AT THE FIRST HEARTBEAT — the
    first step's jit compile is unbounded and must not trip a false "hung
    collective" alarm. The monitor is a DAEMON thread and never blocks the
    loop or process exit. ``timeout_s=None`` constructs a disabled no-op
    watchdog.

    Lifecycle: either the context-manager form or explicit
    ``start()``/``stop()`` (the serving engine owns one across many
    ``step()`` calls and has no scope to ``with`` over). Both are
    idempotent — a double start spawns no second thread, a double stop is a
    no-op — and a stopped watchdog can be started again.
    """

    def __init__(
        self,
        timeout_s: Optional[float],
        on_stall: Optional[Callable[[float], None]] = None,
        poll_s: Optional[float] = None,
        action: str = "log",
    ):
        if action not in ("log", "abort"):
            raise ValueError(f"unknown watchdog action {action!r}")
        self.timeout_s = timeout_s
        if on_stall is not None:
            self.on_stall = on_stall
        elif action == "abort":
            self.on_stall = self._abort_on_stall
        else:
            self.on_stall = self._default_on_stall
        self._poll_s = (
            poll_s if poll_s is not None
            else min((timeout_s or 40.0) / 4, 10.0)
        )
        self._last: Optional[float] = None   # None until armed
        self._stop = threading.Event()
        self._fired = False
        self._thread: Optional[threading.Thread] = None

    @staticmethod
    def _default_on_stall(elapsed: float) -> None:
        log.error(
            "watchdog: no step completed for %.1fs — suspect hung "
            "collective or dead peer host", elapsed,
        )

    @staticmethod
    def _abort_on_stall(elapsed: float) -> None:
        """Kill the process so the (cross-process) supervisor restarts it.

        A hung collective cannot be recovered in-process — the device queue
        is wedged — so detection must feed the restart loop: SIGABRT takes
        the whole process down and the supervisor (re-run of train.py, or
        an external scheduler) resumes from the latest checkpoint.
        """
        import os

        log.error(
            "watchdog: no step completed for %.1fs — aborting for "
            "supervisor restart (hung collective / dead peer host)", elapsed,
        )
        os.kill(os.getpid(), signal.SIGABRT)

    def heartbeat(self) -> None:
        self._last = time.monotonic()
        self._fired = False

    @property
    def stalled(self) -> bool:
        return self._fired

    @property
    def armed(self) -> bool:
        """True once the first heartbeat has arrived (the stall timer only
        runs from then — first-compile time never counts)."""
        return self._last is not None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _run(self) -> None:
        while not self._stop.wait(self._poll_s):
            if self._last is None:
                continue  # not armed: first step still compiling
            elapsed = time.monotonic() - self._last
            if elapsed > self.timeout_s and not self._fired:
                self._fired = True
                try:
                    self.on_stall(elapsed)
                # orion: allow[fault-except] a broken stall observer must not kill the watchdog thread it reports through
                except Exception:
                    log.exception("watchdog on_stall callback failed")

    def start(self) -> "Watchdog":
        """Spawn the monitor thread (idempotent; no-op when disabled)."""
        if self.timeout_s is None or self.running:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="orion-watchdog", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        """Stop and join the monitor thread (idempotent)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


# ---------------------------------------------------------------------------
# Serving-path fault injection (InferenceEngine(..., fault_injector=...))
# ---------------------------------------------------------------------------


@dataclass
class FaultSpec:
    """One scheduled fault.

    ``kind``:
      - "dispatch": raise InjectedFault instead of running the jit program
        (fired BEFORE the call, so engine/cache state is untouched and the
        XLA-fallback retry exercises the real degradation path).
      - "execute":  raise InjectedFault where the engine WAITS for a
        dispatch's results (executor.wait): what an execute-time device
        error does, which asynchronous dispatch defers from the launch to
        the first wait, after the program was queued and whatever the
        engine queued behind it.
      - "nan":      poison the victim request's newest private KV page with
        NaN before the step's dispatch — real NaNs flow through the real
        attention into that slot's logits (requires inference.nan_guard for
        the engine to detect and quarantine).
      - "pool":     the next page allocation this step raises MemoryError,
        as a genuinely exhausted pool would.
      - "stall":    sleep ``stall_s`` inside the dispatch path (trips the
        engine watchdog when stall_s > inference.watchdog_timeout_s).
      - "restore":  the next host-tier restore this step raises
        InjectedFault INSIDE the copy envelope — after the fresh device
        pages were allocated and the in-flight host refs taken —
        exercising the envelope's full unwind (both pools balanced, tree
        markers unpromoted, typed DispatchFault fails the step).
      - "migration": the next KV-page migration envelope (ISSUE 20;
        ``step`` is the ROUTER step number) raises InjectedFault inside
        the gather/convert/scatter copy — after the source gather but
        before the destination admission commits — exercising the
        whole-or-requeued guarantee: the request must end wholly on the
        decode replica or re-queued on a surviving prefill replica with
        a typed ``retried`` outcome, never half a context. ``path``
        optionally restricts to one envelope stage ("gather" |
        "scatter").

    Training-path kinds (Trainer(..., fault_injector=...); ``step`` is the
    trainer step, ``path`` is "train"):
      - "dispatch": raise InjectedFault before the compiled train step runs
        (state untouched; a supervisor restart resumes from the newest
        checkpoint).
      - "nan":      run this step through the poisoned-loss variant of the
        SAME compiled program family — loss multiplied by NaN inside the
        differentiated function, so every grad leaf comes out NaN through
        the real backward (requires train.anomaly_guard for the step to be
        skipped instead of poisoning the params forever).
      - "partial_write": tear the checkpoint commit at this step (the
        CheckpointManager consumes it with path="ckpt") — one array file
        is truncated AFTER its checksum landed in the manifest, then the
        rename commits anyway, modeling post-rename data loss; restore
        must checksum-detect it, quarantine, and fall back.

    Replica-scoped kinds (ISSUE 12; the multi-replica Router consumes
    these with ``step`` = the ROUTER step number, and ``replica``
    selecting the victim):
      - "replica_kill":  the replica's process dies — the router never
        steps that engine again; its in-flight AND engine-queued requests
        fail over to survivors under the retry budget. Modeled as sudden
        death: nothing on the dead replica is cancelled or drained.
      - "replica_stall": forward a "stall" spec (``stall_s``) into the
        replica engine's own injector at its next step — the engine
        watchdog flags it and the router's health sweep sees the stalled
        step, exercising the soft-break path end to end.
      - "replica_poison": forward a "nan" spec into the replica engine's
        injector — with inference.nan_guard the quarantine storm shows up
        in the router's health sweep as ``quarantined`` deltas.

    ``step`` is the engine step number (``InferenceEngine.step_no``) to fire
    at — or the router step for replica-scoped kinds; ``path`` optionally
    restricts dispatch/execute/stall faults to one coarse dispatch path
    ("prefill" | "decode" | "verify" | "mixed" | "mixed_verify" |
    "train"); ``rid`` optionally selects the nan victim (default: the
    oldest active request); ``replica`` selects the replica-scoped
    victim. ``count`` fires the spec that many times.
    """

    kind: str
    step: int
    path: Optional[str] = None
    rid: Optional[int] = None
    stall_s: float = 0.0
    count: int = 1
    replica: Optional[int] = None

    REPLICA_KINDS = ("replica_kill", "replica_stall", "replica_poison")

    def __post_init__(self):
        if self.kind not in (
            "dispatch", "execute", "nan", "pool", "stall", "partial_write",
            "restore", "migration",
        ) + self.REPLICA_KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}")
        if self.count < 1:
            raise ValueError(f"count must be >= 1, got {self.count}")
        if self.kind in self.REPLICA_KINDS and (
            self.replica is None or self.replica < 0
        ):
            raise ValueError(
                f"{self.kind} needs replica=<index>, got {self.replica}"
            )


@dataclass
class FaultInjector:
    """Deterministic fault schedule for the serving engine.

    The engine consults ``take(kind, step, path)`` at each injection point;
    a matching spec is consumed (its ``count`` decrements) and recorded in
    ``fired`` so tests can assert the episode actually happened. The
    injector never mutates engine state itself — every fault manifests
    through the same code path a real failure would take.

    ``on_fire(kind, step, path)`` is an optional observer invoked whenever
    a spec is consumed: the engine/trainer wire it to the flight recorder
    (orion_tpu/obs) so every injected fault is stamped into the postmortem
    ring alongside the real fault events it provokes.
    """

    specs: list = field(default_factory=list)
    fired: list = field(default_factory=list)
    on_fire: Optional[Callable[[str, int, Optional[str]], None]] = None

    def take(
        self, kind: str, step: int, path: Optional[str] = None
    ) -> Optional[FaultSpec]:
        for s in self.specs:
            if (
                s.kind == kind
                and s.step == step
                and s.count > 0
                and (s.path is None or path is None or s.path == path)
            ):
                s.count -= 1
                self.fired.append((kind, step, path))
                if self.on_fire is not None:
                    try:
                        self.on_fire(kind, step, path)
                    # orion: allow[fault-except] a broken flight-recorder observer must not change WHICH faults fire
                    except Exception:
                        log.exception("FaultInjector on_fire observer failed")
                return s
        return None
