"""Counts the XLA programs this process builds, from JAX's own monitoring
event (one ``backend_compile_duration`` per new program, persistent-cache
hits included: a hit is still a program the steady state should not ask
for). The mechanism of ``orion_tpu.metrics.CompileCounter``, kept here so
the count brackets the window whatever the program does with its own."""

from __future__ import annotations

from typing import Any

import jax

_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == _EVENT:
            self.count += 1
            self.seconds += duration

    def take(self) -> tuple[int, float]:
        out = (self.count, self.seconds)
        self.count, self.seconds = 0, 0.0
        return out
