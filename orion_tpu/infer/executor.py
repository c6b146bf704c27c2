"""Dispatch executor of the serving engine (ISSUE 12 tentpole split).

The other half of the scheduler/executor split (see infer/scheduler.py):
this module owns the *device-facing* machinery the engine delegates to —
the jitted dispatch-program factory (primary and XLA-fallback builds
share one code path so they can never drift), and the per-dispatch
fault-tolerance envelope: injection points, the degradation-ladder
fallback retry loop (``inference.dispatch_retries`` attempts with
jittered backoff between them — ISSUE 12 satellite), and the
DispatchFault contract the engine's failed-step containment consumes.
The envelope has two halves (ISSUE 40): ``run`` launches a program and
``wait`` waits for it, so that a caller can queue the next program on
the device between the two; both end in the same ladder. The seam times
itself (ISSUE 56): each half is a leaf phase of its own
(``orion/<path>/launch`` around the program's call, ``orion/<path>/wait``
around ``block_until_ready``), and the executor numbers its launches, so
that it knows what is in flight and books the host time in which nothing
is queued (``unqueued_s`` and its siblings in ``reset_timing()``).

The executor holds a back-reference to its engine rather than copies of
the engine's mutable state (robust stats, injector, tracer): those
objects are swapped by ``reset_timing``/lifecycle paths and the envelope
must always read the live ones.
"""

from __future__ import annotations

import dataclasses
import logging
import random
import time
from functools import partial
from typing import Any, Optional

import jax

from orion_tpu.infer.runner import (
    decode_window,
    denoise_block,
    fold_step,
    mixed_step,
    mixed_verify_step,
    prefill_step,
    verify_step,
)
from orion_tpu.obs.parts import PROGRAM_NAMES
from orion_tpu.runtime.fault import DispatchFault, InjectedFault

log = logging.getLogger("orion_tpu.infer")


def named_program(fn, stem: str, **kw):
    """``partial(fn, **kw)`` under a name: ``jax.jit`` names a program after
    the function it is given (a ``partial`` has no name of its own, and its
    module is ``jit__unknown``), so this one's module is ``jit_orion_<stem>``
    in the compiled text and in a device profile. Still a ``partial``: its
    parameters keep the names ``fn`` gives them."""
    program = partial(fn, **kw)
    program.__name__ = program.__qualname__ = PROGRAM_NAMES[stem]
    return program


def _program(name: str) -> str:
    """The jit's name of dispatch ``name`` (``decode_defaults`` ->
    ``orion_decode_window``): a launch or wait span's ``program`` tag."""
    return PROGRAM_NAMES[name.removesuffix("_defaults")]


class DispatchExecutor:
    """Owns the engine's dispatch programs and the fault envelope around
    every device call (previously ``InferenceEngine._jit_program`` /
    ``_fallback_program`` / ``_run_dispatch``, relocated verbatim plus
    the configurable-retry satellite)."""

    PROGRAM_FNS = {
        "prefill": prefill_step,
        "decode": decode_window,
        "mixed": mixed_step,
        "verify": verify_step,
        "mixed_verify": mixed_verify_step,
        "fold": fold_step,
        "denoise": denoise_block,
    }

    def __init__(self, engine):
        self.eng = engine
        # XLA reference programs, built lazily per dispatch name the first
        # time a Pallas dispatch fails (inference.dispatch_fallback, an
        # opt-in: by default a failed dispatch fails the step).
        self._xla_fallbacks: dict[str, Any] = {}
        # Backoff jitter source. Fixed seed so a replayed fault episode
        # sleeps the same schedule; sleep durations never touch tokens,
        # so this is log-determinism, not output-determinism.
        self._rng = random.Random(0)
        # What the last prefill returned between its logits and its cache,
        # all on the device: the greedy picks [nb], the step's last tokens
        # [B] with the picks at their slots, the key after one sampling
        # event, and the count of expert rows on held experts (only a
        # model that holds a share of its experts has one) with, where the
        # dispatch bounds those rows, the layer-dispatches that passed the
        # bound.
        self.picks = self.last_token = self.key = None
        self.held_rows = self.held_overflows = None
        # What is in flight: launches are numbered, the device runs them
        # in that order, so a wait retires its own launch and every
        # earlier one (a fold is launched and never waited for: the
        # window's wait is its wait). ``_newest[path]`` is the number of
        # the path's newest launch, ``_retired`` the newest number a wait
        # has covered. Engine-lifetime state: reset_timing leaves it.
        self._launched = self._retired = 0
        self._newest: dict[str, int] = {}
        # The instant (time.monotonic, the ring's and the buckets' clock)
        # since which nothing is queued; None while a program is in flight.
        # An engine that has launched nothing has nothing to be late with:
        # None.
        self._idle_since: Optional[float] = None

    def jit_program(self, name: str, mcfg, mesh):
        """Build one jitted dispatch program. ``name`` is a coarse path
        stem optionally suffixed "_defaults" (python-scalar sampling params
        bound as trace-time constants — the sort-free greedy
        specialization). The SAME factory builds the XLA fallback programs
        (kernels="xla", mesh=None), so the two paths share every static
        binding and can never drift."""
        icfg = self.eng.icfg
        is_default = name.endswith("_defaults")
        stem = name[: -len("_defaults")] if is_default else name
        fn = self.PROGRAM_FNS[stem]
        if stem == "fold":
            # No weights, no sampling: (cache, slot, page-table row).
            return jax.jit(named_program(fn, stem, cfg=mcfg, mesh=mesh),
                           donate_argnums=(0,))
        if stem == "prefill":
            kw: dict[str, Any] = dict(cfg=mcfg, mesh=mesh)
        else:
            kw = dict(
                cfg=mcfg, max_seq_len=icfg.max_seq_len, mesh=mesh,
                nan_guard=self.eng._guard,
            )
        if stem == "denoise":
            # The schedule is the engine's, for every request alike.
            kw.update(steps=icfg.denoising_steps, remasking=icfg.remasking,
                      threshold=icfg.confidence_threshold)
        if stem in ("prefill", "mixed", "mixed_verify"):
            # Blockwise paged-flash prefill (inference.paged_prefill):
            # resolved against THIS build's kernels — the XLA fallback
            # build (kernels="xla") ignores it inside runner._prefill_ctx, so
            # the reference body stays the degradation-ladder rung.
            kw["paged_prefill"] = icfg.paged_prefill
        if is_default:
            kw.update(
                temperature=icfg.temperature,
                top_k=icfg.top_k,
                top_p=icfg.top_p,
            )
        program = jax.jit(
            named_program(fn, stem, **kw), donate_argnums=(1,),
            # The decode window's length, since its keys are derived inside
            # the program from the engine's one key.
            static_argnames=("window",) if stem == "decode" else None,
        )
        if stem != "prefill":
            return program
        import jax.numpy as jnp

        eng = self.eng

        def prefill(params, cache, tokens, lengths, pages, pre_lens,
                    pre_pages, state_rows=None, slots=None, last_token=None,
                    key=None):
            """(logits, cache) of the prefill program; what it returns
            between the two is parked on the executor (``picks``,
            ``last_token``, ``key``, ``held_rows``, ``held_overflows``) for
            the engine to take
            up. What only the engine can say (the state row and the slot of
            each row, the step's last tokens, its key) a caller that says
            nothing (a warm-up) gets as placeholders of the same shapes and
            dtypes, so that it compiles the program the engine runs."""
            nb = tokens.shape[0]
            if state_rows is None and (
                    mcfg.is_retention or mcfg.has_kda
                    or mcfg.has_window_ring or mcfg.resumes_prefill):
                state_rows = jnp.zeros((nb,), jnp.int32)    # the scratch row
            if slots is None:
                # Out of range: the scatter of the picks drops every row.
                slots = jnp.full((nb,), eng.max_batch, jnp.int32)
            if last_token is None:
                last_token = jnp.zeros((eng.max_batch,), jnp.int32)
            if key is None:
                key = eng._key
            logits, self.picks, self.last_token, self.key, *held, cache = (
                program(params, cache, tokens, lengths, pages, pre_lens,
                        pre_pages, state_rows, slots, last_token, key))
            if held:
                self.held_rows, self.held_overflows = (*held, None)[:2]
            return logits, cache

        prefill.program = program
        return prefill

    def fallback_program(self, name: str):
        """The XLA reference program for ``name`` (degradation ladder rung
        1), or None when no fallback applies — the primary already runs
        XLA, or inference.dispatch_fallback is off / retry count 0. Built
        lazily on the first fault and cached; mesh=None because the XLA
        ops partition from the params' shardings alone."""
        from orion_tpu.ops._dispatch import resolve_impl

        eng = self.eng
        if not eng.icfg.dispatch_fallback or eng.icfg.dispatch_retries < 1:
            return None
        if not resolve_impl(eng.mcfg.kernels)[0]:
            return None
        fb = self._xla_fallbacks.get(name)
        if fb is None:
            mcfg_xla = dataclasses.replace(eng.mcfg, kernels="xla")
            fb = self.jit_program(name, mcfg_xla, None)
            self._xla_fallbacks[name] = fb
        return fb

    def _backoff(self, attempt: int) -> None:
        """Jittered exponential backoff between fallback attempts
        (inference.dispatch_retry_backoff_s; 0.0 = today's immediate
        retry). Full jitter on the upper half keeps a fleet of replicas
        retrying a shared transient from re-colliding in lockstep."""
        base = self.eng.icfg.dispatch_retry_backoff_s
        if base <= 0.0:
            return
        time.sleep(base * (2 ** attempt) * (0.5 + 0.5 * self._rng.random()))

    def run(self, path: str, name: str, *args, **kwargs):
        """LAUNCH one device dispatch under the fault-tolerance envelope and
        return its results without waiting for them (``wait`` is the other
        half): the injection points (stall sleeps; dispatch exceptions
        raised BEFORE the primary call, so engine/cache state is untouched
        and retry is sound), then the call, whose trace / compile /
        lowering failures (the dominant Pallas fault class) surface here.
        Only with ``inference.dispatch_fallback`` on, ANY failure is
        retried up to ``inference.dispatch_retries`` times on the XLA
        reference path, jittered backoff between attempts (``_recover``,
        which waits for each attempt's results). Raises DispatchFault(path)
        when every path is exhausted (at once, with the fallback off): the
        engine fails the step, not the process.

        The device runs the program while the host goes on: what a caller
        queues between ``run`` and ``wait`` (the plain step queues its
        decode window behind its prefill) starts on the device the moment
        this program ends. A caller with nothing to queue calls the two
        back to back (``engine._run_dispatch``)."""
        eng = self.eng
        inj = eng._injector
        if inj is not None:
            st = inj.take("stall", eng.step_no, path)
            if st is not None:
                log.warning(
                    "injected %.2fs stall in %s dispatch (step %d)",
                    st.stall_s, path, eng.step_no,
                )
                time.sleep(st.stall_s)
        try:
            if inj is not None and (
                inj.take("dispatch", eng.step_no, path) is not None
            ):
                raise InjectedFault(
                    f"injected {path} dispatch fault (step {eng.step_no})"
                )
            # The call alone: the caller's ``orion/<path>/run`` phase
            # (engine._phase) holds its uploads too. A call that raises
            # numbers no launch and leaves the unqueued interval open.
            with eng._phase(path + "/launch") as span:
                out = getattr(eng, "_" + name)(*args, **kwargs)
                self._launch(path, name, span)
            return out
        # orion: allow[fault-except] the fault envelope exists to contain ANY dispatch failure (DispatchFault re-raise in _recover)
        except Exception as e:
            return self._recover(path, name, e, args, kwargs)

    def wait(self, path: str, name: str, out, *args, **kwargs):
        """WAIT for the results ``out`` that ``run(path, name, *args,
        **kwargs)`` launched (or for the part of them the caller still
        owns: a cache already handed on to the next program is that
        program's to wait for), and return them. Execute-time device errors
        (async dispatch defers them to the first fetch) surface HERE, inside
        the same envelope and the same fallback ladder, instead of crashing
        the caller's device_get; an injected ``execute`` fault fires here
        too. An EXECUTE-time failure may already have consumed the donated
        cache buffer, in which case the fallback double-faults and the
        episode is contained as a failed step. Where the ladder recovers,
        the results returned are the fallback's, not ``out``."""
        eng = self.eng
        try:
            if eng._injector is not None and (
                eng._injector.take("execute", eng.step_no, path) is not None
            ):
                raise InjectedFault(
                    f"injected {path} execute fault (step {eng.step_no})"
                )
            with eng._phase(path + "/wait") as span:
                if span.tags is not None:
                    span.tags.update(program=_program(name),
                                     seq=self._newest.get(path, 0))
                # orion: allow[host-sync] THE envelope sync point, once a program and after everything the device needs has been queued: execute-time faults must surface here, not at the caller's fetch
                jax.block_until_ready(out)
            self._retire(self._newest.get(path, 0), span.t1)
            return out
        # orion: allow[fault-except] the fault envelope exists to contain ANY dispatch failure (DispatchFault re-raise in _recover)
        except Exception as e:
            # What was waited for has left the device, in an error (an
            # injected one is taken for the device's).
            self._retire(self._newest.get(path, 0), time.monotonic())
            return self._recover(path, name, e, args, kwargs)

    # -- what is in flight (ISSUE 56) --------------------------------------

    @property
    def in_flight(self) -> int:
        """Launches no wait has covered yet."""
        return self._launched - self._retired

    def unqueued_until(self, now: float) -> float:
        """Host seconds with nothing queued up to ``now``: what
        ``unqueued_s`` holds and, where nothing is queued now, the open
        interval so far. The engine reads it at a step's two edges
        (``unqueued_in_step_s``)."""
        booked = self.eng.timing["unqueued_s"]
        if self._idle_since is None:
            return booked
        return booked + (now - self._idle_since)

    def _launch(self, path: str, name: str, span) -> None:
        """Number the launch that ``span`` (its ``orion/<path>/launch``
        phase, or a fallback attempt's, still open) made. Where nothing was queued, the interval
        ends at the span's START: the call's own host time is the
        ``*_launch_s`` leaf."""
        t = self.eng.timing
        if self._idle_since is not None:
            gap = span.t0 - self._idle_since
            t["unqueued_s"] += gap
            if gap > t["unqueued_max_s"]:
                t["unqueued_max_s"] = gap
            self._idle_since = None
        self._launched += 1
        self._newest[path] = self._launched
        t["launches"] += 1
        if span.tags is not None:
            span.tags.update(program=_program(name), seq=self._launched)

    def _retire(self, upto: int, at: float) -> None:
        """A wait that returned at ``at`` covered every launch numbered
        ``upto`` or lower."""
        if upto <= self._retired:
            return
        self.eng.timing["waits"] += upto - self._retired
        self._retired = upto
        if upto == self._launched:
            self._idle_since = at

    def _recover(self, path: str, name: str, e: Exception, args, kwargs):
        """A dispatch failed, at its launch or at its wait: count it, then
        the degradation ladder (module docstring). Returns a fallback
        attempt's results, waited for, or raises DispatchFault."""
        eng = self.eng
        eng.robust.dispatch_faults += 1
        eng._flight_note(
            "dispatch_fault", path=path,
            error=f"{type(e).__name__}: {e}",
        )
        if path in ("verify", "mixed_verify"):
            # Degradation ladder rung 2 counts PRIMARY verify faults
            # here — before the fallback — so a persistently broken
            # verify kernel disables speculation even when every
            # episode is absorbed by a successful XLA retry (otherwise
            # the engine would pay a doomed primary attempt + fallback
            # on every verify step forever).
            eng._note_spec_fault(e)
        fb = self.fallback_program(name)
        if fb is None:
            raise DispatchFault(
                path, f"{type(e).__name__}: {e}"
            ) from e
        last: Exception = e
        for attempt in range(eng.icfg.dispatch_retries):
            self._backoff(attempt)
            eng.robust.dispatch_retries += 1
            log.warning(
                "%s dispatch failed (%s: %s); retry %d/%d on the XLA "
                "reference path", path, type(last).__name__, last,
                attempt + 1, eng.icfg.dispatch_retries,
            )
            before = self._launched
            try:
                with eng._phase(path + "/fallback") as span:
                    out = fb(*args, **kwargs)
                    # A launch like any other: numbered, and where nothing
                    # was queued the interval ends at the attempt's start.
                    self._launch(path, name, span)
                    # orion: allow[host-sync] fallback attempts must surface their own execute-time faults inside the retry loop
                    jax.block_until_ready(out)
            # orion: allow[fault-except] retry-ladder rung: a failed fallback attempt feeds the next retry, then DispatchFault
            except Exception as e2:
                if self._launched > before:
                    # Launched, and left the device in an error.
                    self._retire(self._launched, time.monotonic())
                eng.robust.dispatch_faults += 1
                last = e2
                continue
            eng.robust.dispatch_fallbacks += 1
            eng._flight_note("dispatch_fallback", path=path)
            # Waited for, behind everything launched before it: nothing is
            # queued from the attempt's end on.
            self._retire(self._launched, span.t1)
            return out
        raise DispatchFault(
            path, f"xla fallback failed too: {last}"
        ) from last
