"""Device-side debug assertions for manual shard_map regions.

``runtime.checkify`` (the sanitizer story's main tool, SANITIZERS.md)
cannot cross manually-sharded regions — precisely the sp / sorted_a2a /
grad-quant code where an out-of-bounds routing or paging index would be
hardest to debug (it surfaces as NaNs or silent drops). This module is the
complement (SURVEY.md §6 "Race detection / sanitizers", VERDICT r4 weak
#7): ``device_assert`` lowers to a ``jax.debug.callback`` that raises
host-side the moment a predicate fails ON DEVICE, and it works inside
``shard_map`` (callbacks run per shard).

Gated by ``model.debug_asserts`` at every call site: when the flag is off
the call is a Python no-op — nothing enters the jaxpr, so production
programs are unchanged.

``inject(site)`` force-fails a named assert site (test hook, mirroring
runtime/fault.py's fault-injection style): it validates that an assert is
actually wired into a given layout's compiled program, complementing the
true-corruption tests that monkeypatch router outputs.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

_INJECTED: set[str] = set()


class DeviceAssertionError(AssertionError):
    """Raised host-side when a device_assert predicate fails."""


def inject(site: str) -> None:
    """Force the named assert site to fail (test hook).

    TRACE-TIME ONLY: the injection is read while the enclosing program is
    being traced (``if site in _INJECTED`` inside device_assert runs at
    trace time), so it only takes effect for programs compiled AFTER this
    call. Injecting after a jit cache is warm (the program already
    compiled) is a silent no-op — tests must inject BEFORE the first call
    of the program under test, or clear the jit cache. This is by design:
    the hook validates that an assert is actually wired into a given
    layout's compiled program, not that a cached program re-reads host
    state.
    """
    _INJECTED.add(site)


def clear_injected() -> None:
    _INJECTED.clear()


# Failure records appended by the (async) debug-callback thread and drained
# by raise_if_failed on the scheduler thread — guarded by a lock so a
# failure landing mid-drain is never dropped.
_failures: list[str] = []
_failures_lock = threading.Lock()


def device_assert(enabled: bool, pred: jax.Array, site: str, msg: str) -> None:
    """Assert ``pred`` (a scalar boolean on device) when ``enabled``.

    ``enabled`` must be a static Python bool (the config flag): when False,
    nothing is traced. The callback RECORDS the failure host-side (raising
    inside an async-dispatched callback aborts the runtime — observed as a
    fatal interpreter error under donated train steps); the trainer/engine
    call ``raise_if_failed()`` at their per-step host sync points, which is
    where the loud failure surfaces. Works inside jit and shard_map,
    compiled or interpreted.
    """
    if not enabled:
        return
    if site in _INJECTED:
        pred = jnp.logical_and(pred, False)

    def _check(ok, _site=site, _msg=msg):
        if not bool(ok):
            rec = f"device_assert[{_site}]: {_msg}"
            with _failures_lock:
                _failures.append(rec)
            import logging

            logging.getLogger("orion_tpu.asserts").error(rec)

    jax.debug.callback(_check, jnp.asarray(pred).all())


def raise_if_failed() -> None:
    """Raise DeviceAssertionError if any device_assert has fired since the
    last call. Call sites: Trainer.train_step / InferenceEngine.step (the
    per-step host sync points). Drains the record either way — the swap
    happens atomically under the callback lock, so a failure appended by
    the async callback thread between snapshot and clear can't be lost."""
    with _failures_lock:
        if not _failures:
            return
        recs = list(_failures)
        _failures.clear()
    raise DeviceAssertionError("; ".join(recs))
