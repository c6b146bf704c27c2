"""Capture a profiler trace of part of a run, inside the checkout."""

from __future__ import annotations

import glob
import pathlib
import shutil
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


class Trace:
    def __init__(self, cell):
        self.dir = ROOT / ".bench_trace" / cell.name
        self.t0 = self.t1 = 0.0

    def __enter__(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        # No Python call tracing: it slows the host that drives the device.
        # TraceAnnotation spans (host tracer) stay.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        import jax

        self.t1 = time.monotonic()
        jax.profiler.stop_trace()

    def reduced(self, dev) -> dict:
        from benchmarks.trace import reduce

        files = sorted(glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        events = reduce.load_xplane(files[-1])
        return reduce.reduce(events, self.t1 - self.t0)
