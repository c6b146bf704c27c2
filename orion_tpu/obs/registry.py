"""Named-snapshot metrics registry + Prometheus/JSONL exporters (ISSUE 9).

Before this module, serving/training counters lived in seven ad-hoc Stats
dataclasses (metrics.py) drained through ``reset_timing`` / MetricsLogger
extras, with no export surface and no gauges (pool occupancy, live HBM).
The registry unifies them behind one API:

    reg = MetricsRegistry()
    reg.register("prefix", lambda: engine.prefix_stats.as_timing())
    reg.register("pool", engine_pool_provider)
    reg.snapshot()                      # {"prefix.hits": 3, "pool.free_pages": 12, ...}
    reg.export_prometheus("/run/metrics/orion.prom")
    reg.export_jsonl("/var/log/orion_metrics.jsonl")

Providers are zero-arg callables returning flat mappings; they are read
lazily at snapshot time, so registering costs nothing on the hot path and
a provider reading live engine state always reports the CURRENT window —
``reset_timing``'s drain-and-zero semantics are unchanged, the registry
just reads whichever stats object is live right now.

The engine and trainer each own a registry (``engine.registry`` /
``trainer.registry``); the bench tools emit a standard ``"metrics"`` block
built from it (``bench_metrics_block``), so every bench JSON line carries
a comparable counter set across rounds.
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import Any, Callable, Mapping, Optional, Sequence

import jax

Provider = Callable[[], Mapping[str, Any]]

_NAME_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")
_PROM_SANITIZE = re.compile(r"[^a-zA-Z0-9_]")

# JSONL time-series stamps must be wall-clock (cross-host comparable) but
# may never step backwards within a process — an NTP slew mid-run would
# reorder the series a dashboard diffs. Anchor the wall clock once and
# advance it on the monotonic clock (obs clock discipline, tools/lint.py
# `clock` rule).
_T0_WALL = time.time()  # orion: allow[clock] one-off wall anchor; stamps advance monotonically from it
_T0_MONO = time.monotonic()


def _wall_now() -> float:
    """Monotonic-within-process wall-clock seconds."""
    return _T0_WALL + (time.monotonic() - _T0_MONO)


def live_hbm_metrics(device: jax.Device) -> dict[str, int]:
    """Live device-memory gauges of ``device`` (one the caller's arrays
    live on — the mesh's, not whatever ``jax.devices()[0]`` is) from the
    backend allocator, or {} when the backend exposes none (CPU test
    runs). Keys follow the backend's own naming (bytes_in_use /
    peak_bytes_in_use / bytes_limit)."""
    stats = device.memory_stats()
    if not stats:
        return {}
    out = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "largest_alloc_size"):
        if key in stats:
            out[key] = int(stats[key])
    return out


class MetricsRegistry:
    """Named sections of lazily-read metric providers."""

    def __init__(self):
        self._providers: dict[str, Provider] = {}

    def register(self, name: str, provider: Provider) -> None:
        """Register (or replace) the provider for a section. Section names
        are identifier-shaped; snapshot keys are ``section.key``."""
        if not _NAME_RE.match(name):
            raise ValueError(f"bad metrics section name {name!r}")
        self._providers[name] = provider

    def unregister(self, name: str) -> None:
        self._providers.pop(name, None)

    def sections(self) -> list[str]:
        return sorted(self._providers)

    def snapshot(
        self, sections: Optional[Sequence[str]] = None
    ) -> dict[str, Any]:
        """One flat name-spaced read of every (or the named) section(s).
        A provider that raises contributes a ``<section>.error`` string
        instead of taking the caller down — metrics reads run inside
        serving loops and postmortem dumps."""
        out: dict[str, Any] = {}
        names = self.sections() if sections is None else sections
        for name in names:
            fn = self._providers.get(name)
            if fn is None:
                continue
            try:
                vals = fn() or {}
            except Exception as e:
                out[f"{name}.error"] = f"{type(e).__name__}: {e}"
                continue
            for k, v in vals.items():
                out[f"{name}.{k}"] = v
        return out

    # -- exporters ---------------------------------------------------------

    def export_prometheus(
        self,
        path: str,
        prefix: str = "orion",
        snapshot: Optional[Mapping[str, Any]] = None,
    ) -> int:
        """Write the snapshot as a Prometheus textfile (node_exporter
        textfile-collector format: ``<prefix>_<flattened_key> <value>``),
        atomically (tmp + rename — the collector must never read a torn
        file). Non-numeric values are skipped (Prometheus has no string
        samples); returns the number of samples written."""
        snap = self.snapshot() if snapshot is None else snapshot
        lines = []
        for key in sorted(snap):
            v = snap[key]
            if isinstance(v, bool):
                v = int(v)
            if not isinstance(v, (int, float)):
                continue
            metric = f"{prefix}_{_PROM_SANITIZE.sub('_', key)}"
            lines.append(f"{metric} {float(v):.17g}")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write("\n".join(lines) + ("\n" if lines else ""))
        os.replace(tmp, path)
        return len(lines)

    def export_jsonl(
        self,
        path: str,
        snapshot: Optional[Mapping[str, Any]] = None,
    ) -> dict[str, Any]:
        """Append one time-series row ({"ts": unix_seconds, **snapshot})
        to a JSONL file; returns the row. The serving engine calls this
        from ``reset_timing`` when ``inference.metrics_jsonl`` is set, so
        every drain window becomes one comparable row. The stamp is the
        monotonic-anchored wall clock (``_wall_now``): comparable across
        hosts, never backwards within the process."""
        row = {"ts": _wall_now()}
        row.update(self.snapshot() if snapshot is None else snapshot)
        with open(path, "a") as f:
            f.write(json.dumps(row, default=str) + "\n")
        return row


def bench_metrics_block(
    engine, timing: Optional[Mapping[str, Any]] = None
) -> dict[str, Any]:
    """The standard ``"metrics"`` block for tools/*_bench.py JSON lines:
    the engine registry's gauge sections (pool occupancy, live HBM) plus a
    drained ``reset_timing`` window, name-spaced ``serve.*`` like registry
    snapshots. Pass ``timing`` when the bench already drained the window
    itself (reset_timing zeroes — draining twice would report zeros)."""
    block = engine.registry.snapshot(sections=("pool", "hbm"))
    src = timing if timing is not None else engine.reset_timing()
    block.update({f"serve.{k}": v for k, v in src.items()})
    return block
