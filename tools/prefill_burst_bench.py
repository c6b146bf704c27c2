#!/usr/bin/env python
"""Mixed-length admission-burst latency (VERDICT r3 item 7 'measure').

Submits a burst of prompts whose lengths span several prefill buckets and
times the single engine step that admits + prefills them all. The ragged
single-dispatch prefill (segment-skip flash blocks) should beat the
per-bucket dispatch pattern roughly by (dispatch overhead x extra buckets)
plus the padded-blocks compute, which grows with length spread.

    python tools/prefill_burst_bench.py          # on-chip numbers
    python tools/prefill_burst_bench.py --cpu    # tiny-shape logic check

Output: one JSON line per burst shape.
"""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import json
import sys
import time

import jax
import numpy as np


def main() -> int:
    cpu = "--cpu" in sys.argv[1:]
    if cpu:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        print(f"FAIL: no TPU backend (default backend is "
              f"{jax.default_backend()!r}); use --cpu for the logic check")
        return 1

    from orion_tpu.config import get_config
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models import init_params

    if cpu:
        preset, overrides = "tiny-llama", [
            "inference.max_seq_len=128", "inference.page_size=16",
            "inference.num_pages=64", "inference.max_batch_size=8",
            "inference.prefill_chunk=16", "inference.max_new_tokens=4",
        ]
        bursts = {"uniform": [14] * 4, "mixed": [3, 14, 30, 60]}
    else:
        preset, overrides = "llama-1b-bench", [
            "model.param_dtype=bfloat16",
            "inference.max_seq_len=2048", "inference.page_size=64",
            "inference.num_pages=1024", "inference.max_batch_size=16",
            "inference.prefill_chunk=256", "inference.max_new_tokens=4",
        ]
        bursts = {
            "uniform": [250] * 8,
            "mixed": [40, 120, 250, 400, 700, 1000, 1500, 2000],
        }

    cfg = get_config(preset, overrides)
    params = init_params(cfg.model, jax.random.key(0))
    rng = np.random.default_rng(0)

    for name, lengths in bursts.items():
        # One engine per burst shape; an identical warm burst first (the
        # prefill jit cache lives on the engine), drained before timing.
        eng = InferenceEngine(cfg, params)
        for timed in (False, True):
            for n in lengths:
                eng.submit(
                    rng.integers(1, cfg.model.vocab_size, n).tolist(), 2
                )
            eng.reset_timing()
            t0 = time.perf_counter()
            eng.step()           # admission + ONE ragged prefill dispatch
            dt = time.perf_counter() - t0
            t = eng.reset_timing()   # the admit step only
            while eng.has_work():
                eng.step()       # drain so the next burst admits cleanly
        from orion_tpu.obs import bench_metrics_block

        print(json.dumps({
            "burst": name,
            "lengths": lengths,
            "admit_ms": round(dt * 1e3, 2),
            # Round 5 split the prefill dispatch->first-token span out of
            # host_s into its own bucket: for an admit step prefill_ms IS
            # the burst cost this bench measures; host_ms is scheduler
            # overhead only.
            "prefill_ms": round(t["prefill_s"] * 1e3, 2),
            "device_ms": round(t["device_s"] * 1e3, 2),
            "host_ms": round(t["host_s"] * 1e3, 2),
            "tokens": int(sum(lengths)),
            # Standard bench metrics block (ISSUE 9): registry gauges +
            # the admit-step reset_timing window.
            "metrics": bench_metrics_block(eng, timing=t),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
