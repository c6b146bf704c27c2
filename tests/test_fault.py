"""Fault-tier tests (SURVEY.md §5-6) for BOTH stacks sharing
orion_tpu/runtime/fault.py:

  - training: preemption -> clean save -> lossless resume; supervisor
    restarts; stall watchdog (the original tier, Trainer-heavy cases
    marked slow per the tier-1 budget convention);
  - serving (ISSUE 6): deadlines/cancellation, bounded-queue shedding,
    fault injection (dispatch, pool, NaN, stall) and the graceful-
    degradation ladder — every episode ends with the engine completing
    the remaining requests byte-identically to a fault-free run, and the
    page pool exactly accounted (assert_page_accounting).

Fast engine cases run in tier-1; heavy kernel/feature compositions
(pallas x int8 x SWA x chunked x fault) are `slow`.
"""

import os
import signal
import time

import jax
import numpy as np
import pytest

from orion_tpu.config import get_config
from orion_tpu.infer import InferenceEngine
from orion_tpu.models import init_params
from orion_tpu.runtime.fault import (
    DispatchFault,
    FaultInjector,
    FaultSpec,
    Preempted,
    PreemptionHandler,
    Watchdog,
    run_with_restarts,
)
from orion_tpu.train import Trainer
from orion_tpu.train.trainer import FaultInjected

slow = pytest.mark.slow


# ---------------------------------------------------------------------------
# Training stack (the original fault tier)
# ---------------------------------------------------------------------------


def _cfg(tmp_path=None, extra=()):
    overrides = [
        "runtime.platform=cpu", "train.num_steps=60",
        "train.log_interval=1000", "optimizer.warmup_steps=5",
    ]
    if tmp_path is not None:
        overrides += [
            f"checkpoint.directory={tmp_path}/ckpt",
            "checkpoint.save_interval_steps=10",
        ]
    return get_config("tiny", list(overrides) + list(extra))


@slow
def test_preemption_mid_run_saves_and_resumes(tmp_path):
    """Preemption mid-run -> checkpoint at the interrupted step -> resume
    reproduces the uninterrupted loss trajectory."""
    full = Trainer(_cfg()).fit()

    cfg = _cfg(tmp_path)
    trainer = Trainer(cfg)

    class CountdownHandler(PreemptionHandler):
        """Flags preemption at the trainer's 25th step-boundary check —
        deterministic, no wall-clock race against compile time."""

        def __init__(self, after_checks: int):
            super().__init__()
            self._checks_left = after_checks

        @property
        def preempted(self) -> bool:
            self._checks_left -= 1
            if self._checks_left <= 0:
                self._flag.set()
            return self._flag.is_set()

    handler = CountdownHandler(after_checks=25)
    with pytest.raises(Preempted):
        with handler:
            trainer.fit(preemption_handler=handler)
    stop_step = trainer.ckpt.latest_step()
    assert stop_step == 25, stop_step

    resumed = Trainer(_cfg(tmp_path)).fit()
    assert resumed[0].step == stop_step + 1
    full_by_step = {m.step: m.loss for m in full}
    for m in resumed:
        np.testing.assert_allclose(m.loss, full_by_step[m.step], rtol=1e-6)


def test_preemption_handler_catches_sigterm():
    with PreemptionHandler() as h:
        assert not h.preempted
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(50):          # delivery is asynchronous
            if h.preempted:
                break
            time.sleep(0.01)
        assert h.preempted
    # previous disposition restored on exit
    assert signal.getsignal(signal.SIGTERM) != h._on_signal


def test_preemption_handler_double_enter_restores_original():
    """Regression (ISSUE 6 Watchdog/handler hardening): a nested
    __enter__ must keep the ORIGINAL prior disposition — recording its
    own handler as "prior" would make __exit__ leave the process wired
    to a dead handler object."""
    prev = signal.getsignal(signal.SIGTERM)
    h = PreemptionHandler()
    with h:
        installed = signal.getsignal(signal.SIGTERM)
        h.__enter__()    # double-enter: must not re-record "prior"
        assert signal.getsignal(signal.SIGTERM) == installed
        assert h._prev[signal.SIGTERM] == prev
    assert signal.getsignal(signal.SIGTERM) == prev


@slow
def test_run_with_restarts_resumes_after_fault(tmp_path):
    """The supervisor loop retries a crashed run; the retry resumes from the
    crash checkpoint rather than step 0."""
    attempts = []
    # The fault hook fires once per (ckpt dir, step), so the same config is
    # reused across attempts — exactly how train.py --max-restarts runs.
    extra = ("train.inject_fault_at_step=30",)

    def make_and_fit(attempt):
        attempts.append(attempt)
        return Trainer(_cfg(tmp_path, extra)).fit()

    hist = run_with_restarts(make_and_fit, max_restarts=2)
    assert attempts == [0, 1]
    assert hist[0].step > 20          # resumed, not from scratch
    assert hist[-1].step == 60


def test_run_with_restarts_gives_up():
    def always_fail(attempt):
        raise FaultInjected("boom")

    with pytest.raises(FaultInjected):
        run_with_restarts(always_fail, max_restarts=2)


def test_run_with_restarts_preemption_propagates():
    def preempted(attempt):
        raise Preempted("pod reclaimed")

    with pytest.raises(Preempted):
        run_with_restarts(preempted, max_restarts=5)


def test_watchdog_detects_stall_and_recovers():
    fired = []
    with Watchdog(timeout_s=0.2, on_stall=fired.append, poll_s=0.05) as wd:
        time.sleep(0.5)
        assert not wd.stalled       # unarmed during (unbounded) first compile
        wd.heartbeat()              # first step completes: armed
        time.sleep(0.5)
        assert wd.stalled and len(fired) == 1
        wd.heartbeat()              # progress resumes
        assert not wd.stalled
        time.sleep(0.1)
        assert len(fired) == 1      # no re-fire while fresh
    assert not wd.running


def test_watchdog_abort_action_signals_process(monkeypatch):
    """action='abort' closes the recovery loop: on stall the watchdog
    SIGABRTs the process so the supervisor restart resumes from the
    checkpoint (a hung collective is unrecoverable in-process)."""
    import signal as _signal

    kills = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: kills.append((pid, sig)))
    with Watchdog(timeout_s=0.2, poll_s=0.05, action="abort") as wd:
        wd.heartbeat()
        time.sleep(0.5)
        assert wd.stalled
    assert kills == [(os.getpid(), _signal.SIGABRT)]


def test_watchdog_rejects_unknown_action():
    with pytest.raises(ValueError, match="action"):
        Watchdog(timeout_s=1.0, action="explode")


def test_watchdog_idempotent_daemon_lifecycle():
    """Regression (ISSUE 6 hardening): start() twice spawns ONE daemon
    thread, stop() twice is a no-op, and a stopped watchdog restarts —
    the serving engine owns one across many step() calls with no `with`
    scope, so the explicit lifecycle must be safe to drive redundantly."""
    wd = Watchdog(timeout_s=30.0, poll_s=0.05)
    assert not wd.running and not wd.armed
    wd.start()
    t1 = wd._thread
    assert wd.running and t1.daemon
    wd.start()                       # idempotent: same thread
    assert wd._thread is t1
    wd.stop()
    assert not wd.running
    wd.stop()                        # idempotent
    wd.start()                       # restartable
    assert wd.running and wd._thread is not t1
    wd.stop()
    # disabled watchdog: start is a no-op
    off = Watchdog(timeout_s=None).start()
    assert not off.running
    off.stop()


def test_run_with_restarts_config_errors_not_retried():
    attempts = []

    def bad_config(attempt):
        attempts.append(attempt)
        raise ValueError("n_layers not divisible by pp")

    with pytest.raises(ValueError):
        run_with_restarts(bad_config, max_restarts=5)
    assert attempts == [0]          # deterministic errors fail fast


def test_watchdog_quiet_under_heartbeats():
    fired = []
    with Watchdog(timeout_s=0.3, on_stall=fired.append, poll_s=0.05) as wd:
        for _ in range(6):
            time.sleep(0.05)
            wd.heartbeat()
    assert not fired and not wd.stalled


@slow
def test_trainer_watchdog_wired(tmp_path, caplog):
    """train.watchdog_timeout_s installs the watchdog around the fit loop
    (quiet for a healthy run)."""
    cfg = _cfg(extra=("train.num_steps=10", "train.watchdog_timeout_s=30",))
    hist = Trainer(cfg).fit()
    assert len(hist) == 10


# ---------------------------------------------------------------------------
# Serving stack (ISSUE 6): engine fault injection + degradation ladder
# ---------------------------------------------------------------------------

INFER = [
    "inference.max_seq_len=128",
    "inference.page_size=16",
    "inference.num_pages=32",
    "inference.max_batch_size=4",
    "inference.prefill_chunk=16",
    "inference.max_new_tokens=8",
    "inference.decode_window=1",
]
# The n-gram proposer drafts on MIX[1] from its 12th token on (as in
# test_spec_decode): see SPEC_TOKENS below.
REP = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]
MIX = [REP, [5, 3, 9, 250, 17], [7, 7, 7]]
SPEC = ["inference.speculative=true", "inference.speculate_tokens=4"]


@pytest.fixture(scope="module")
def tiny():
    """(params, fault-free greedy reference outputs for MIX)."""
    cfg = get_config("tiny-llama", INFER)
    params = init_params(cfg.model, jax.random.key(0))
    ref = InferenceEngine(cfg, params).generate(MIX, 8)
    return params, ref


# The XLA-fallback rung is an opt-in (inference.dispatch_fallback defaults
# off so a kernel that never compiled cannot pass as a slower, green run);
# the tests that exercise the rung ask for it.
FALLBACK = ["inference.dispatch_fallback=true"]


def _engine(params, extra=(), inj=None):
    cfg = get_config("tiny-llama", INFER + list(extra))
    return InferenceEngine(cfg, params, fault_injector=inj)


def _drain_outcomes(eng):
    done = {}
    while eng.has_work():
        for r in eng.step():
            done[r.rid] = r
    return done


def test_injected_dispatch_fault_contained(tiny):
    """xla path (no fallback rung): an injected decode-dispatch fault
    fails the STEP — counted, state untouched — and the engine completes
    every request byte-identically to the fault-free run."""
    params, ref = tiny
    inj = FaultInjector([FaultSpec("dispatch", step=2, path="decode")])
    eng = _engine(params, inj=inj)
    assert eng.generate(MIX, 8) == ref
    t = eng.reset_timing()
    assert t["failed_steps"] == 1 and t["dispatch_faults"] == 1
    assert inj.fired == [("dispatch", 2, "decode")]
    eng.assert_page_accounting()


def test_injected_prefill_fault_unwinds_admission(tiny):
    """A prefill-dispatch fault unwinds the burst's admissions (slots and
    pages released, NOTHING donated — no KV was written) and the requeued
    requests re-prefill next step, byte-identically."""
    params, ref = tiny
    inj = FaultInjector([FaultSpec("dispatch", step=0, path="prefill")])
    eng = _engine(params, inj=inj)
    assert eng.generate(MIX, 8) == ref
    t = eng.reset_timing()
    assert t["failed_steps"] == 1
    eng.assert_page_accounting()


def _launches(eng):
    """(step, path) of every dispatch the engine launches from now on."""
    seen, real = [], eng._executor.run

    def run(path, name, *args, **kwargs):
        seen.append((eng.step_no, path))
        return real(path, name, *args, **kwargs)

    eng._executor.run = run
    return seen


def test_prefill_launch_fault_unwinds_before_any_window(tiny):
    """The plain step queues its window behind its prefill (ISSUE 40): a
    fault at the prefill's LAUNCH still unwinds the burst before any
    window is launched."""
    params, ref = tiny
    inj = FaultInjector([FaultSpec("dispatch", step=0, path="prefill")])
    eng = _engine(params, inj=inj)
    seen = _launches(eng)
    for p in MIX:
        eng.submit(p, 8)
    eng.step()
    assert seen == [(0, "prefill")]
    assert eng.slots.count(None) == len(eng.slots) and len(eng.waiting) == 3
    eng.assert_page_accounting()
    done = _drain_outcomes(eng)
    assert [done[i].generated for i in range(3)] == ref
    t = eng.reset_timing()
    assert t["failed_steps"] == 1 and t["chained_steps"] == 1
    eng.assert_page_accounting()


def test_prefill_wait_fault_unwinds_and_drops_the_queued_window(tiny):
    """An execute-time fault surfaces where the prefill is WAITED for,
    after the window was queued behind it: the burst's admissions are
    unwound, the window's results dropped, the step fails with the engine
    consistent, and the re-prefill is byte-identical."""
    params, ref = tiny
    inj = FaultInjector([FaultSpec("execute", step=0, path="prefill")])
    eng = _engine(params, inj=inj)
    seen = _launches(eng)
    reqs = [eng.submit_request(p, 8) for p in MIX]
    key = np.asarray(eng._key).tolist()
    eng.step()
    assert seen == [(0, "prefill"), (0, "decode")]
    assert inj.fired == [("execute", 0, "prefill")]
    assert eng.slots.count(None) == len(eng.slots) and len(eng.waiting) == 3
    assert all(not r.generated for r in reqs)
    assert not eng.seq_lens.any() and not eng.last_token.any()
    assert np.asarray(eng._key).tolist() == key     # no sampling event
    eng.assert_page_accounting()
    done = _drain_outcomes(eng)
    assert [done[i].generated for i in range(3)] == ref
    t = eng.reset_timing()
    assert t["failed_steps"] == 1 and t["dispatch_faults"] == 1
    assert t["chained_steps"] == 2      # the failed step's, and the retry's
    eng.assert_page_accounting()


@pytest.mark.parametrize("kind", ["dispatch", "execute"])
def test_decode_fault_behind_an_unwaited_prefill_keeps_first_tokens(
        tiny, kind):
    """The window fails at its launch (the prefill in flight is then
    waited for and its first tokens emitted) or at its wait (they were
    emitted while it ran): either way the step fails with the prefill's
    work kept, and the slots decode the lost positions again."""
    params, ref = tiny
    inj = FaultInjector([FaultSpec(kind, step=0, path="decode")])
    eng = _engine(params, inj=inj)
    reqs = [eng.submit_request(p, 8) for p in MIX]
    eng.step()
    assert [len(r.generated) for r in reqs] == [1, 1, 1]
    assert [r.generated[0] for r in reqs] == [g[0] for g in ref]
    assert eng.slots.count(None) == len(eng.slots) - 3
    eng.assert_page_accounting()
    done = _drain_outcomes(eng)
    assert [done[i].generated for i in range(3)] == ref
    t = eng.reset_timing()
    assert t["failed_steps"] == 1 and t["prefill_dispatches"] == 1
    assert t["chained_steps"] == (1 if kind == "execute" else 0)
    eng.assert_page_accounting()


def _seam_marks(spans):
    """(instant, launch's number: negative at a wait's return) of the ring's
    launch, wait and fallback spans: a launch is made at its span's start, a
    wait returns at its end, a fallback attempt is both in one span."""
    marks = []
    for _, name, t0, t1, tags in spans:
        kind = name.rsplit("/", 1)[-1]
        if kind in ("launch", "fallback"):
            marks.append((t0, tags["seq"]))
        if kind in ("wait", "fallback"):
            marks.append((t1, -tags["seq"]))
    return sorted(marks)


def _unqueued_from(spans):
    """The intervals in which nothing was queued, replayed from the ring."""
    launched, idle_since, gaps = 0, None, []
    for at, seq in _seam_marks(spans):
        if seq > 0:
            if idle_since is not None:
                gaps.append(at - idle_since)
            launched, idle_since = seq, None
        elif -seq == launched and idle_since is None:
            idle_since = at
    return gaps


def test_dispatch_fallback_xla_reference(tiny):
    """Degradation ladder rung 1: with kernels=pallas a failed dispatch
    retries once on the XLA reference path — same step, no failed step,
    byte-identical output."""
    params, _ = tiny
    pall = ["model.kernels=pallas_interpret", "inference.trace=true"] + FALLBACK
    sound = _engine(params, pall)
    ref = sound.generate(MIX, 8)
    inj = FaultInjector([FaultSpec("dispatch", step=2, path="decode")])
    eng = _engine(params, pall, inj=inj)
    assert eng.generate(MIX, 8) == ref
    spans = [e for e in eng.tracer.events() if e[0] == "span"]
    t = eng.reset_timing()
    assert t["dispatch_fallbacks"] == 1 and t["failed_steps"] == 0
    eng.assert_page_accounting()
    # The seam's counters (ISSUE 56): the attempt that ran is a launch like
    # any other, waited for where it ran, and its time on the device (the
    # fallback program's compile with it) is not time with nothing queued.
    assert t["launches"] == t["waits"] == sound.reset_timing()["launches"]
    assert eng._executor.in_flight == 0
    (attempt,) = (e for e in spans if e[1] == "orion/decode/fallback")
    assert attempt[4]["program"] == "orion_decode_window"
    gaps = _unqueued_from(spans)
    assert t["unqueued_s"] == pytest.approx(sum(gaps), abs=1e-9)
    assert t["unqueued_max_s"] == pytest.approx(max(gaps), abs=1e-9)


@slow   # tier-1 budget, round 11: knob variant of the fallback path;
#         the fallback-on rung is tier-1 (test_dispatch_fallback_xla_reference)
def test_dispatch_fallback_disabled_fails_step(tiny):
    """inference.dispatch_fallback=false turns the same episode into a
    contained failed step instead of a fallback."""
    params, _ = tiny
    pall = ["model.kernels=pallas_interpret"]
    ref = _engine(params, pall).generate(MIX, 8)
    inj = FaultInjector([FaultSpec("dispatch", step=2, path="decode")])
    eng = _engine(
        params, pall + ["inference.dispatch_fallback=false"], inj=inj
    )
    assert eng.generate(MIX, 8) == ref
    t = eng.reset_timing()
    assert t["dispatch_fallbacks"] == 0 and t["failed_steps"] == 1


def test_persistent_fault_reraises(tiny):
    """max_step_faults consecutive failed steps is no longer transient:
    the engine re-raises instead of spinning forever."""
    params, _ = tiny
    inj = FaultInjector(
        [FaultSpec("dispatch", step=s, count=10) for s in range(20)]
    )
    eng = _engine(params, ["inference.max_step_faults=2"], inj=inj)
    for p in MIX:
        eng.submit(p, 8)
    with pytest.raises(DispatchFault):
        while eng.has_work():
            eng.step()
    t = eng.reset_timing()
    assert t["failed_steps"] == 2


def test_pool_fault_at_admit_defers(tiny):
    """Injected page-pool exhaustion during admission defers the request
    (un-claimed, still queued) instead of crashing; output exact."""
    params, ref = tiny
    inj = FaultInjector([FaultSpec("pool", step=0)])
    eng = _engine(params, inj=inj)
    assert eng.generate(MIX, 8) == ref
    t = eng.reset_timing()
    assert t["pool_faults"] == 1 and inj.fired
    eng.assert_page_accounting()


def test_pool_fault_at_grow_fails_step(tiny):
    """Injected exhaustion at decode-window page growth fails the step
    (pages stay owned, state consistent) and the retry completes."""
    params, ref = tiny
    # REP is 11 tokens; growth allocates when the write position crosses
    # into page 2 at seq_len 16 — engine step 5 (prefill step emits token
    # 1, each decode step one more).
    inj = FaultInjector([FaultSpec("pool", step=5)])
    eng = _engine(params, inj=inj)
    assert eng.generate(MIX, 8) == ref
    t = eng.reset_timing()
    assert inj.fired == [("pool", 5, None)]
    assert t["pool_faults"] == 1 and t["failed_steps"] == 1
    eng.assert_page_accounting()


def test_nan_quarantine_neighbors_exact(tiny):
    """A NaN-poisoned slot is quarantined: that request errors with a
    typed outcome, its pages are scrubbed and released with NO prefix
    donation, and every neighbor's output is byte-identical to the
    fault-free run. Guard ON with no fault stays byte-identical too."""
    params, ref = tiny
    guard = ["inference.nan_guard=true"]
    assert _engine(params, guard).generate(MIX, 8) == ref

    inj = FaultInjector([FaultSpec("nan", step=2)])
    eng = _engine(params, guard, inj=inj)
    rids = [eng.submit(p, 8) for p in MIX]
    done = _drain_outcomes(eng)
    t = eng.reset_timing()
    assert t["quarantined_requests"] == 1
    victims = [r for r in rids if done[r].outcome == "error:nan"]
    assert len(victims) == 1
    for i, rid in enumerate(rids):
        if rid not in victims:
            assert done[rid].outcome == "completed"
            assert done[rid].generated == ref[i]
    eng.assert_page_accounting()


@slow   # tier-1 budget, round 11: documentation-grade variant; the
#         guard-on quarantine path is tier-1 (test_nan_quarantine_neighbors_exact)
def test_nan_without_guard_documented_passthrough(tiny):
    """Guard OFF: the injected NaN flows into that slot's sampled tokens
    (garbage-in) but the ENGINE survives, completes, and accounts pages —
    the knob only buys detection, never stability."""
    params, ref = tiny
    inj = FaultInjector([FaultSpec("nan", step=2)])
    eng = _engine(params, inj=inj)
    out = eng.generate(MIX, 8)
    assert [len(o) for o in out] == [len(o) for o in ref]
    t = eng.reset_timing()
    assert t["quarantined_requests"] == 0
    eng.assert_page_accounting()


def test_deadline_expiry_mid_decode_and_waiting(tiny):
    """Deadlines lapse on an ACTIVE request mid-decode and on one still
    WAITING in the queue: both reap at the next step boundary — typed
    "expired", partial tokens kept for the active one, pages donated/
    released exactly as preemption does — and the surviving neighbor
    completes byte-identically."""
    params, ref = tiny
    eng = _engine(params, ["inference.max_batch_size=1"])
    r_dead = eng.submit_request(REP, 120, deadline_s=0.25)   # admits
    r_wait = eng.submit_request([5, 5, 5], 8, deadline_s=0.05)
    r_live = eng.submit_request(MIX[1], 8)
    eng.step()                      # admit r_dead + first tokens
    assert len(r_dead.generated) >= 1
    time.sleep(0.3)                 # both deadlines lapse
    done = _drain_outcomes(eng)
    assert done[r_dead.rid].outcome == "expired"
    assert 0 < len(r_dead.generated) < 120
    assert done[r_wait.rid].outcome == "expired"
    assert r_wait.generated == []   # expired before ever admitted
    assert done[r_live.rid].outcome == "completed"
    assert r_live.generated == ref[1]
    t = eng.reset_timing()
    assert t["expired_requests"] == 2
    eng.assert_page_accounting()


@slow   # tier-1 budget, round 11: chunked engine compile; the active-
#         and waiting-expiry paths stay tier-1 in the test above
def test_deadline_expiry_mid_prefill(tiny):
    """Expiry hits a chunked request still in its prompt phase: it ends
    "expired" at a step boundary with completed chunks' pages released;
    the live neighbor completes byte-identically."""
    params, ref = tiny
    chunked = [
        "inference.chunked_prefill=true",
        "inference.prefill_chunk_tokens=16",
    ]
    cref = _engine(params, chunked).generate(MIX, 8)
    assert cref == ref              # chunked equivalence (pinned upstream)

    eng = _engine(params, chunked + ["inference.max_batch_size=1"])
    r_live = eng.submit_request(MIX[1], 8)
    # 90-token prompt = 6 chunks; deadline lapses after the first one.
    r_pre = eng.submit_request(list(range(1, 91)), 8, deadline_s=0.2)
    eng.step()
    time.sleep(0.25)
    done = _drain_outcomes(eng)
    assert done[r_pre.rid].outcome == "expired"
    assert r_pre.generated == []    # never left the prompt phase
    assert done[r_live.rid].outcome == "completed"
    assert r_live.generated == ref[1]
    t = eng.reset_timing()
    assert t["expired_requests"] == 1
    eng.assert_page_accounting()


def test_cancel_waiting_and_speculating_slot(tiny):
    """cancel(): a waiting request dies immediately; an ACTIVE one — mid
    speculation, with drafted KV provisioned past its cursor — is reaped
    at the next boundary with the rollback footprint exact (free list
    back to full once all requests leave; double-release would trip the
    accounting assert)."""
    params, ref = tiny
    eng = _engine(params, SPEC + ["inference.max_batch_size=2"])
    r_spec = eng.submit_request(REP, 24)
    r_wait = eng.submit_request([5, 5, 5], 8)
    eng.step()
    eng.step()                      # speculation in flight on REP
    assert eng.cancel(r_wait.rid) and r_wait.outcome == "cancelled"
    assert eng.cancel(r_spec.rid)
    done = _drain_outcomes(eng)
    assert done[r_spec.rid].outcome == "cancelled"
    assert not eng.cancel(r_spec.rid)       # already terminal
    assert not eng.cancel(10_000)           # unknown rid
    t = eng.reset_timing()
    assert t["cancelled_requests"] == 2
    eng.assert_page_accounting()
    assert eng.alloc.free_pages == eng.icfg.num_pages - 1


def test_queue_limit_sheds_lowest_priority(tiny):
    """Bounded admission queue: an over-limit submit sheds the lowest-
    priority / nearest-deadline / newest candidate — possibly the
    incoming request itself — with a typed outcome; accepted requests
    complete untouched."""
    params, _ = tiny
    eng = _engine(
        params, ["inference.queue_limit=2", "inference.max_batch_size=1"]
    )
    a = eng.submit_request([1, 2, 3], 8, priority=2)
    eng.step()                      # a holds the only slot
    lo = eng.submit_request([4, 5], 8, priority=0)
    hi = eng.submit_request([6, 7], 8, priority=1)
    hi2 = eng.submit_request([8, 9], 8, priority=1)   # full -> shed lo
    assert lo.outcome == "shed" and not hi.done and not hi2.done
    lo2 = eng.submit_request([1, 1], 8, priority=0)   # itself the victim
    assert lo2.outcome == "shed"
    done = _drain_outcomes(eng)
    assert {done[r.rid].outcome for r in (a, hi, hi2)} == {"completed"}
    # shed requests surface exactly once, through step(), like any other
    assert done[lo.rid].outcome == "shed"
    t = eng.reset_timing()
    assert t["shed_requests"] == 2
    eng.assert_page_accounting()


def test_priority_admission_order(tiny):
    """With one slot, a higher-priority arrival admits ahead of earlier
    lower-priority waiters; default-priority traffic keeps pure arrival
    order (the pre-robustness behavior)."""
    params, _ = tiny
    eng = _engine(params, ["inference.max_batch_size=1"])
    a = eng.submit_request([1, 2], 4)
    eng.step()
    lo = eng.submit_request([3, 4], 4, priority=0)
    hi = eng.submit_request([5, 6], 4, priority=5)
    while not a.done:
        eng.step()
    while not hi.done:
        eng.step()
    assert hi.outcome == "completed"
    assert not lo.done              # hi jumped the queue
    _drain_outcomes(eng)
    assert lo.outcome == "completed"


def test_drain_sheds_queue_finishes_live(tiny):
    """drain() (the SIGTERM path): admission stops, the wait queue sheds
    with typed outcomes, live requests FINISH (pages donated as normal
    completion), pool fully accounted; post-drain submits shed."""
    params, ref = tiny
    eng = _engine(params)
    live = eng.submit_request(REP, 8)
    eng.step()
    waiters = [eng.submit_request([9, 9, 9], 8) for _ in range(6)]
    eng.drain()
    assert live.outcome == "completed" and live.generated == ref[0]
    outs = {r.outcome for r in waiters}
    assert outs <= {"completed", "shed"} and "shed" in outs
    post = eng.submit_request([1, 2], 4)
    assert post.outcome == "shed"
    t = eng.reset_timing()
    assert t["shed_requests"] >= 1


def test_drain_finishes_preempted_requests(tiny):
    """Regression (review): a request PREEMPTED mid-drain re-enters the
    waiting queue — drain must re-admit and finish it (it is in-flight
    work), not spin forever on an admission gate. Also: queue-pressure
    shedding never victimizes a preempted request (it carries generated
    tokens; "shed" means never admitted)."""
    params, ref = tiny
    eng = _engine(params, ["inference.queue_limit=1"])
    a = eng.submit_request(REP, 8)
    eng.step()                       # admit a (queue empties)
    b = eng.submit_request(MIX[1], 8)
    eng.step()                       # admit b
    assert a.generated and b.generated
    eng._preempt(b)                  # simulate pool pressure
    # b (admitted once, priority 0) is in the queue; an over-limit burst
    # must shed around it, never it.
    c = eng.submit_request([9, 9], 8, priority=0)
    assert c.outcome == "shed" and b.outcome == ""
    drained = eng.drain()
    assert b in drained and b.outcome == "completed"
    assert b.generated == ref[1]     # resume-after-preempt exactness
    assert a.outcome == "completed" and a.generated == ref[0]
    eng.assert_page_accounting()


# The spec-fault tests need VERIFY dispatches to fault. Within the module's
# 8 tokens nothing of MIX repeats itself on the seed-0 tiny model (the
# cyclic prompt's stream does not loop), so the proposer never drafts and
# no verify runs; MIX[1]'s stream repeats from its 12th token on.
SPEC_TOKENS = 24


def _spec_dry_run(params, extra=()):
    """(fault-free greedy reference of MIX at SPEC_TOKENS, the number of
    verify steps the speculative engine ran on it with no fault planted).
    Speculation must not change the stream, and must verify at all."""
    ref = _engine(params, extra).generate(MIX, SPEC_TOKENS)
    dry = _engine(params, SPEC + list(extra))
    assert dry.generate(MIX, SPEC_TOKENS) == ref
    return ref, dry.reset_timing()["verify_steps"]


def _verify_faults():
    """A fault on every step's verify dispatch, by path name: whichever
    steps verify, each attempt faults until speculation is off."""
    return FaultInjector([
        FaultSpec("dispatch", step=s, path="verify")
        for s in range(4 * SPEC_TOKENS)
    ])


def test_spec_fault_auto_disable(tiny):
    """Degradation ladder rung 2: repeated verify-path dispatch faults
    auto-disable speculation (SpecDecodeStats.disabled_reason, carried
    across reset_timing) after exactly spec_fault_limit of them, and
    decoding continues exactly on the plain window."""
    params, _ = tiny
    limit = 2
    ref, n_verify = _spec_dry_run(params)
    assert n_verify > limit, "the workload must verify more than it faults"
    inj = _verify_faults()
    eng = _engine(params, SPEC + [f"inference.spec_fault_limit={limit}"],
                  inj=inj)
    rids = [eng.submit(p, SPEC_TOKENS) for p in MIX]
    out = {}
    while eng.has_work():
        # Off after the limit-th verify fault, not before.
        assert eng._spec_disabled == (len(inj.fired) >= limit)
        for r in eng.step():
            out[r.rid] = r.generated
    assert [out[i] for i in rids] == ref
    assert eng._spec_disabled and eng._spec_faults == limit
    t = eng.reset_timing()
    assert f"auto-disabled after {limit} verify" in t["spec_disabled_reason"]
    # disabled: no further verify attempted, none ever ran
    assert [f[2] for f in inj.fired] == ["verify"] * limit
    assert t["verify_steps"] == 0 and t["failed_steps"] == limit, t
    # the reason survives the drain (engine-lifetime state)
    assert "auto-disabled" in eng.reset_timing()["spec_disabled_reason"]
    eng.assert_page_accounting()


def test_spec_fault_disable_counts_primary_faults_under_fallback(tiny):
    """Regression (review): rung 2 must count PRIMARY verify faults even
    when every episode is absorbed by a successful XLA fallback —
    otherwise a persistently broken verify kernel pays a doomed primary
    attempt + fallback forever and spec_fault_limit is a dead knob."""
    params, _ = tiny
    pall = FALLBACK + ["model.kernels=pallas_interpret"]
    ref, n_verify = _spec_dry_run(params, pall)
    assert n_verify > 1, "the workload must verify more than it faults"
    inj = _verify_faults()
    eng = _engine(
        params, SPEC + pall + ["inference.spec_fault_limit=1"], inj=inj
    )
    assert eng.generate(MIX, SPEC_TOKENS) == ref
    assert eng._spec_disabled and eng._spec_faults == 1
    t = eng.reset_timing()
    assert "auto-disabled after 1 verify" in t["spec_disabled_reason"]
    assert len(inj.fired) == 1 and inj.fired[0][2] == "verify"
    assert t["failed_steps"] == 0       # the fault was absorbed ...
    assert t["dispatch_fallbacks"] == 1
    assert t["verify_steps"] == 1, t    # ... by the fallback's verify
    eng.assert_page_accounting()


def test_preemption_prefers_low_priority_victims(tiny):
    """Regression (review): page-pressure preemption evicts the LOWEST
    priority class first (the submit() contract), not simply the
    youngest admission."""
    params, _ = tiny
    eng = _engine(params, ["inference.max_batch_size=2"])
    lo = eng.submit_request(REP, 24, priority=0)
    eng.step()
    hi = eng.submit_request(MIX[1], 24, priority=5)
    eng.step()
    assert lo.slot is not None and hi.slot is not None
    # Starve the pool so the next window growth must preempt someone:
    # hi is YOUNGER, but lo must be the victim.
    hostage = eng.alloc.alloc(eng.alloc.free_pages)
    for _ in range(20):
        if lo.slot is None or hi.slot is None or not eng.has_work():
            break
        eng.step()
    assert hi.slot is not None, "high-priority request was preempted"
    assert lo.slot is None and not lo.done   # lo evicted, re-queued
    eng.alloc.free(hostage)
    done = _drain_outcomes(eng)
    assert done[hi.rid].outcome == "completed"
    assert done[lo.rid].outcome == "completed"   # resumed after pressure
    eng.assert_page_accounting()


def test_pool_deferred_request_is_sheddable(tiny):
    """Regression (review): an admission pool-fault deferral un-claims
    the request completely — having never run, it is NOT shed-exempt the
    way preempted (in-flight) requests are."""
    params, _ = tiny
    inj = FaultInjector([FaultSpec("pool", step=0)])
    eng = _engine(
        params, ["inference.queue_limit=1", "inference.max_batch_size=1"],
        inj=inj,
    )
    a = eng.submit_request([1, 2, 3], 8)
    eng.step()                      # pool fault: a deferred, un-claimed
    assert a.admit_seq == -1 and not eng._in_flight(a)
    b = eng.submit_request([4, 5], 8, priority=1)   # queue full: a sheds
    assert a.outcome == "shed" and not b.done
    done = _drain_outcomes(eng)
    assert done[b.rid].outcome == "completed"
    eng.assert_page_accounting()


def test_watchdog_stall_counted_not_fatal(tiny):
    """An injected stall beyond inference.watchdog_timeout_s flags the
    step as stalled (counted in reset_timing) — the process and the
    outputs survive, unlike train's action='abort'."""
    params, ref = tiny
    inj = FaultInjector([FaultSpec("stall", step=2, stall_s=0.6)])
    eng = _engine(params, ["inference.watchdog_timeout_s=0.2"], inj=inj)
    assert eng.generate(MIX, 8) == ref
    t = eng.reset_timing()
    assert t["stalled_steps"] == 1
    assert eng._watchdog.running
    eng.close()
    assert not eng._watchdog.running
    eng.close()                     # idempotent


def test_fault_config_validation():
    with pytest.raises(ValueError, match="queue_limit"):
        get_config("tiny-llama", INFER + ["inference.queue_limit=0"])
    with pytest.raises(ValueError, match="default_deadline_s"):
        get_config(
            "tiny-llama", INFER + ["inference.default_deadline_s=0"]
        )
    with pytest.raises(ValueError, match="spec_fault_limit"):
        get_config("tiny-llama", INFER + ["inference.spec_fault_limit=0"])
    with pytest.raises(ValueError, match="max_step_faults"):
        get_config("tiny-llama", INFER + ["inference.max_step_faults=0"])
    with pytest.raises(ValueError, match="watchdog_timeout_s"):
        get_config(
            "tiny-llama", INFER + ["inference.watchdog_timeout_s=-1"]
        )
    with pytest.raises(ValueError, match="kind"):
        FaultSpec("explode", step=0)
    with pytest.raises(ValueError, match="count"):
        FaultSpec("dispatch", step=0, count=0)
    cfg = get_config("tiny-llama", INFER)
    params = init_params(cfg.model, jax.random.key(0))
    eng = InferenceEngine(cfg, params)
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit([1, 2], 4, deadline_s=-1.0)


def test_dispatch_retry_loop_absorbs_flaky_fallback(tiny):
    """inference.dispatch_retries > 1 (ISSUE 12 satellite): the fallback
    retry LOOP absorbs a transiently-failing XLA fallback — here the
    first two fallback attempts raise — with the attempts counted in
    RobustnessStats and the output byte-identical."""
    params, _ = tiny
    pall = FALLBACK + [
        "model.kernels=pallas_interpret", "inference.dispatch_retries=3",
    ]
    ref = _engine(params, ["model.kernels=pallas_interpret"]).generate(MIX, 8)
    inj = FaultInjector([FaultSpec("dispatch", step=2, path="decode")])
    eng = _engine(params, pall, inj=inj)
    real = eng._executor.fallback_program
    flaky = {"left": 2}

    def failing_twice(name):
        fb = real(name)
        if fb is None:
            return None

        def wrapped(*a, **k):
            if flaky["left"] > 0:
                flaky["left"] -= 1
                raise RuntimeError("transient fallback fault")
            return fb(*a, **k)

        return wrapped

    eng._executor.fallback_program = failing_twice
    assert eng.generate(MIX, 8) == ref
    t = eng.reset_timing()
    # 1 primary fault + 2 failed fallback attempts; the 3rd succeeds.
    assert t["dispatch_faults"] == 3 and t["dispatch_retries"] == 3
    assert t["dispatch_fallbacks"] == 1 and t["failed_steps"] == 0
    eng.assert_page_accounting()


def test_dispatch_retries_zero_disables_fallback(tiny):
    """dispatch_retries=0 turns the episode into a contained failed step
    even with dispatch_fallback=true — the 0-attempt loop is the
    fallback-off path."""
    params, _ = tiny
    pall = ["model.kernels=pallas_interpret"] + FALLBACK
    ref = _engine(params, pall).generate(MIX, 8)
    inj = FaultInjector([FaultSpec("dispatch", step=2, path="decode")])
    eng = _engine(
        params, pall + ["inference.dispatch_retries=0"], inj=inj
    )
    assert eng.generate(MIX, 8) == ref
    t = eng.reset_timing()
    assert t["dispatch_fallbacks"] == 0 and t["failed_steps"] == 1
    assert t["dispatch_retries"] == 0
    with pytest.raises(ValueError, match="dispatch_retries"):
        get_config("tiny-llama", INFER + ["inference.dispatch_retries=-1"])


def test_submit_after_drain_and_close_sheds_typed(tiny):
    """Engine lifecycle edges the router leans on (ISSUE 12 satellite):
    submit() after drain() AND after close() yields a typed "shed"
    outcome that surfaces from the next step() — never a raise, never a
    request queued for a step loop that will not run."""
    params, ref = tiny
    eng = _engine(params)
    assert eng.generate(MIX[:2], 8) == ref[:2]
    eng.drain()
    late = eng.submit_request([1, 2, 3], 4)
    assert late.done and late.outcome == "shed"
    assert late in eng.step()           # surfaces exactly once
    eng.close()
    later = eng.submit_request([4, 5, 6], 4)
    assert later.done and later.outcome == "shed"
    assert later in eng.step()
    t = eng.reset_timing()
    assert t["shed_requests"] == 2
    eng.assert_page_accounting()


def test_drain_idempotent_under_concurrent_cancel(tiny):
    """drain() composes with cancel(): cancelling an active request just
    before/after the drain never double-releases or hangs; a second
    drain() is a no-op; the pool stays exactly accounted."""
    params, ref = tiny
    eng = _engine(params)
    reqs = [eng.submit_request(p, 8) for p in MIX[:3]]
    eng.step()                          # admit + first tokens
    assert eng.cancel(reqs[0].rid)
    drained = eng.drain()
    assert {r.rid for r in drained} == {r.rid for r in reqs}
    assert reqs[0].outcome == "cancelled"
    assert reqs[1].outcome == "completed"
    assert reqs[1].generated == ref[1]
    # Concurrent-cancel edge: cancel of an already-drained rid is a
    # clean no-op, and drain() again returns nothing.
    assert not eng.cancel(reqs[0].rid)
    assert eng.drain() == []
    eng.assert_page_accounting()
    eng.close()


def test_overload_bench_smoke():
    """tools/serving_latency_bench.py --overload --smoke (tier-1 wiring):
    at 2x-capacity offered load every miss is a typed shed/expiry (no
    silent drops, no crash), sheds are all lowest-priority, and no
    accepted request overruns its deadline by more than one step."""
    import json
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "serving_latency_bench.py"),
         "--overload", "--smoke"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    verdict = lines[-1]
    assert verdict["no_silent_drops"] is True, lines
    assert verdict["all_typed"] is True, lines
    assert verdict["sheds_lowest_priority_only"] is True, lines
    assert verdict["deadline_overrun_bounded"] is True, lines
    by_mode = {d["mode"]: d for d in lines[:-1]}
    ov = by_mode["overload"]
    assert ov["shed_rate"] > 0 and ov["outcomes"]["completed"] > 0, lines


# ---------------------------------------------------------------------------
# Heavy fault compositions (full tier)
# ---------------------------------------------------------------------------


@slow
def test_fault_composition_chunked_spec_nan_quarantine(tiny):
    """chunked prefill x speculation x NaN quarantine: the poisoned
    decode-phase slot errors out of a MIXED step while a prompt is mid
    chunk; neighbors byte-identical to the fault-free chunked run."""
    params, ref = tiny
    extra = SPEC + [
        "inference.chunked_prefill=true",
        "inference.prefill_chunk_tokens=16",
        "inference.nan_guard=true",
    ]
    assert _engine(params, extra).generate(MIX, 8) == ref
    inj = FaultInjector([FaultSpec("nan", step=2)])
    eng = _engine(params, extra, inj=inj)
    rids = [eng.submit(p, 8) for p in MIX]
    done = _drain_outcomes(eng)
    victims = [r for r in rids if done[r].outcome == "error:nan"]
    assert len(victims) == 1
    for i, rid in enumerate(rids):
        if rid not in victims:
            assert done[rid].generated == ref[i]
    eng.assert_page_accounting()


@slow
def test_fault_composition_int8_pallas_fallback(tiny):
    """kv_quant=int8 on the pallas path: the XLA fallback's quantized
    pool writes are bitwise the kernel's (the round-5 scale fix), so a
    mid-stream fallback step changes NOTHING downstream."""
    params, _ = tiny
    extra = FALLBACK + [
        "model.kernels=pallas_interpret", "inference.kv_quant=int8",
    ]
    ref = _engine(params, extra).generate(MIX, 8)
    inj = FaultInjector([
        FaultSpec("dispatch", step=2, path="decode"),
        FaultSpec("dispatch", step=4, path="decode"),
    ])
    eng = _engine(params, extra, inj=inj)
    assert eng.generate(MIX, 8) == ref
    t = eng.reset_timing()
    assert t["dispatch_fallbacks"] == 2 and t["failed_steps"] == 0
    eng.assert_page_accounting()


@slow
def test_fault_composition_swa_expiry_and_fallback(tiny):
    """Sliding-window model: deadline expiry mid-decode releases the
    rolled page layout cleanly, and a pallas fault falls back byte-
    identically with the window mask intact."""
    params, _ = tiny
    swa = ["model.sliding_window=20"]
    ref = _engine(params, swa).generate(MIX, 8)
    # expiry under SWA
    eng = _engine(params, swa)
    r_dead = eng.submit_request(REP, 120, deadline_s=0.25)
    r_live = eng.submit_request(MIX[1], 8)
    eng.step()
    time.sleep(0.3)
    done = _drain_outcomes(eng)
    assert done[r_dead.rid].outcome == "expired"
    assert done[r_live.rid].generated == ref[1]
    eng.assert_page_accounting()
    # fallback under SWA + pallas
    pall = swa + FALLBACK + ["model.kernels=pallas_interpret"]
    pref = _engine(params, pall).generate(MIX, 8)
    assert pref == ref
    inj = FaultInjector([FaultSpec("dispatch", step=3, path="decode")])
    eng = _engine(params, pall, inj=inj)
    assert eng.generate(MIX, 8) == ref
    assert eng.reset_timing()["dispatch_fallbacks"] == 1


@slow
def test_fault_composition_spec_verify_fallback(tiny):
    """The ragged Pallas verify path falls back to the XLA verify body on
    an injected fault — acceptance decisions, rollback footprint and
    greedy output all unchanged."""
    params, ref = tiny
    pall = SPEC + FALLBACK + ["model.kernels=pallas_interpret"]
    assert _engine(params, pall).generate(MIX, 8) == ref
    inj = FaultInjector([FaultSpec("dispatch", step=2, path="verify")])
    eng = _engine(params, pall, inj=inj)
    assert eng.generate(MIX, 8) == ref
    t = eng.reset_timing()
    assert t["failed_steps"] == 0
    assert eng._spec_disabled is False
    eng.assert_page_accounting()
