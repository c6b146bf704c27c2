#!/usr/bin/env python
"""Prefix-cache TTFT benchmark: cold vs warm prefill under shared-prefix
traffic (ISSUE 1 'measure'), plus the tiered-cache capacity sweep
(ISSUE 18).

Default mode serves a batch of prompts of which a fraction share a long
common prefix (the system-prompt pattern), once against a cold engine and
once against an engine whose radix tree was warmed by a single pathfinder
request carrying the shared prefix. The admit-step prefill span (engine
reset_timing ``prefill_s`` — dispatch through first-token fetch, i.e.
TTFT's compute term) is the headline: warm sharing should cut it roughly
by the shared fraction times the prefix/prompt length ratio, and the
hit-rate / cached-token counters confirm the cache did the work.

``--capacity-sweep`` measures the host tier (inference.host_tier_bytes)
across shrinking HBM pools: per pool size, the admit-step TTFT of the
same shared-prefix burst under three cache states — device-warm (radix
tree holds the prefix in HBM), host-warm (the prefix was demoted via
``offload_prefix_cache``, the hit pays one batched h2d restore), and
recompute (cache cleared, full prefill) — with the per-phase hit/restore
counters and the REAL d2h/h2d bandwidth the copy spans measured (the
constants PERF.md's break-even arithmetic wants). The final JSON line is
a verdict asserting warm < host < recompute strictly at every pool size
on ``ttft_ms`` — the admit-step COMPUTE span, prefill_s + restore_s,
TTFT's compute term (the wall-clock ``admit_ms`` rides along but is
scheduler noise at smoke shapes, where the phases differ by ~1 ms); the
exit code is nonzero on any inversion. That order is the chip's to show:
the tier-1 wiring (tests/test_host_tier.py) judges each phase's counts
(tokens prefilled, tokens cached, pages restored), not the CPU's clocks.

    python tools/prefix_cache_bench.py                    # on-chip
    python tools/prefix_cache_bench.py --smoke            # CPU check
    python tools/prefix_cache_bench.py --capacity-sweep [--smoke]

Output: one JSON line per (shared_fraction, phase) / per (pool, phase),
verdict line last in sweep mode.
"""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import json
import sys
import time

import jax
import numpy as np


def _drain(eng):
    while eng.has_work():
        eng.step()


def _sweep_phase(eng, phase, shared, prompts):
    """Run ONE capacity-sweep phase measurement and return
    (admit_ms, window, offload_window_or_None).

    recompute: cleared cache, full prefill. warm: pathfinder-seeded radix
    tree, tail-only prefill from HBM. host: pathfinder-seeded tree demoted
    wholesale via offload_prefix_cache, so the hit pays the batched h2d
    restore before the tail prefill.
    """
    eng.clear_prefix_cache()
    t_off = None
    if phase != "recompute":
        eng.submit(shared, 2)
        _drain(eng)
    if phase == "host":
        eng.reset_timing()       # discard the pathfinder window
        eng.offload_prefix_cache()
        t_off = eng.reset_timing()   # spill_s + evicted_to_host only
    else:
        eng.reset_timing()
    for p in prompts:
        eng.submit(p, 2)
    t0 = time.perf_counter()
    eng.step()                   # admission burst: prefill == TTFT compute
    admit_ms = (time.perf_counter() - t0) * 1e3
    t = eng.reset_timing()
    _drain(eng)
    return admit_ms, t, t_off


def capacity_sweep(smoke: bool) -> int:
    """ISSUE 18: device-warm vs host-warm vs recompute TTFT across HBM
    pool sizes, with measured d2h/h2d bandwidth from the copy spans.
    Exit 1 unless warm < host < recompute strictly at every pool size.
    """
    from orion_tpu.config import get_config
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models import init_params
    from orion_tpu.obs import bench_metrics_block

    if smoke:
        preset, base = "tiny-llama", [
            "inference.max_seq_len=128", "inference.page_size=16",
            "inference.max_batch_size=8", "inference.prefill_chunk=16",
            "inference.max_new_tokens=4",
            "inference.host_tier_bytes=1048576",
        ]
        n_req, prefix_len, tail_len = 3, 96, 16
        pools = (64, 32)
    else:
        preset, base = "llama-1b-bench", [
            "model.param_dtype=bfloat16",
            "inference.max_seq_len=2048", "inference.page_size=64",
            "inference.max_batch_size=16", "inference.prefill_chunk=256",
            "inference.max_new_tokens=4",
            "inference.host_tier_bytes=268435456",
        ]
        n_req, prefix_len, tail_len = 8, 1024, 128
        pools = (1024, 512)
    # The verdict wants the host phase to RESTORE, deterministically:
    # pin break-even to zero so the measurement itself (not the knob's
    # estimate of it) decides whether the tier pays.
    base = base + [
        "inference.prefix_cache=true", "inference.host_tier_min_tokens=0",
    ]

    cfg0 = get_config(preset, base)
    params = init_params(cfg0.model, jax.random.key(0))
    rng = np.random.default_rng(0)
    V = cfg0.model.vocab_size
    shared = rng.integers(1, V, prefix_len).tolist()
    prompts = [
        shared + rng.integers(1, V, tail_len).tolist() for _ in range(n_req)
    ]

    phases = ("recompute", "host", "warm")
    rows, ok = [], True
    for pool in pools:
        cfg = get_config(preset, base + [f"inference.num_pages={pool}"])
        eng = InferenceEngine(cfg, params)
        # Un-timed pass over every phase first: compiles the cold-prefill,
        # warm tail-group, and gather/scatter restore programs at the
        # measured shapes (the jit caches live on the engine).
        for phase in phases:
            _sweep_phase(eng, phase, shared, prompts)
        best = {}
        for phase in phases:
            runs = [_sweep_phase(eng, phase, shared, prompts)
                    for _ in range(3)]
            # Best repeat by the COMPUTE span (prefill + restore): the
            # verdict metric. Wall admit_ms is informational — at smoke
            # shapes it is dominated by scheduler noise.
            admit_ms, t, t_off = min(
                runs, key=lambda r: r[1]["prefill_s"] + r[1]["restore_s"]
            )
            row = {
                "phase": phase,
                "num_pages": pool,
                "requests": n_req,
                "prefix_tokens": prefix_len,
                "ttft_ms": round(
                    (t["prefill_s"] + t["restore_s"]) * 1e3, 2),
                "admit_ms": round(admit_ms, 2),
                "prefill_ms": round(t["prefill_s"] * 1e3, 2),
                "prefill_tokens": int(t.get("prefill_tokens", 0)),
                "prefix_hits": int(t.get("prefix_hits", 0)),
                "cached_tokens": int(t.get("cached_tokens", 0)),
                "host_hits": int(t.get("host_hits", 0)),
                "host_restored_pages": int(t.get("host_restored_pages", 0)),
                "metrics": bench_metrics_block(eng, timing=t),
            }
            if phase == "host":
                pb = eng._host_pool.page_bytes
                demoted = int(t_off.get("evicted_to_host", 0))
                restored = row["host_restored_pages"]
                spill_s = float(t_off.get("spill_s", 0.0))
                restore_s = float(t.get("restore_s", 0.0))
                row["spill_ms"] = round(spill_s * 1e3, 2)
                row["restore_ms"] = round(restore_s * 1e3, 2)
                # The PERF.md break-even constants, measured for real.
                if spill_s > 0:
                    row["d2h_gbps"] = round(demoted * pb / spill_s / 1e9, 3)
                if restore_s > 0:
                    row["h2d_gbps"] = round(
                        restored * pb / restore_s / 1e9, 3)
            best[phase] = row
            print(json.dumps(row))
        rows.append(best)
        if best["host"]["host_restored_pages"] == 0:
            ok = False
        if not (best["warm"]["ttft_ms"] < best["host"]["ttft_ms"]
                < best["recompute"]["ttft_ms"]):
            ok = False
    print(json.dumps({
        "verdict": "ok" if ok else "inverted",
        "ordering": "warm < host < recompute",
        "pools": list(pools),
        "ttft_ms": {
            str(pool): {ph: best[ph]["ttft_ms"] for ph in phases}
            for pool, best in zip(pools, rows)
        },
    }))
    return 0 if ok else 1


def main() -> int:
    smoke = "--smoke" in sys.argv[1:] or "--cpu" in sys.argv[1:]
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        print(f"FAIL: no TPU backend (default backend is "
              f"{jax.default_backend()!r}); use --smoke for the CPU logic check")
        return 1
    if "--capacity-sweep" in sys.argv[1:]:
        return capacity_sweep(smoke)

    from orion_tpu.config import get_config
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models import init_params

    if smoke:
        preset, overrides = "tiny-llama", [
            "inference.max_seq_len=128", "inference.page_size=16",
            "inference.num_pages=64", "inference.max_batch_size=8",
            "inference.prefill_chunk=16", "inference.max_new_tokens=4",
        ]
        n_req, prefix_len, tail_len = 4, 48, 8
    else:
        preset, overrides = "llama-1b-bench", [
            "model.param_dtype=bfloat16",
            "inference.max_seq_len=2048", "inference.page_size=64",
            "inference.num_pages=1024", "inference.max_batch_size=16",
            "inference.prefill_chunk=256", "inference.max_new_tokens=4",
        ]
        n_req, prefix_len, tail_len = 8, 1024, 128
    warm_overrides = overrides + ["inference.prefix_cache=true"]

    cfg_cold = get_config(preset, overrides)
    cfg_warm = get_config(preset, warm_overrides)
    params = init_params(cfg_cold.model, jax.random.key(0))
    rng = np.random.default_rng(0)
    V = cfg_cold.model.vocab_size
    shared = rng.integers(1, V, prefix_len).tolist()

    for frac in (0.0, 0.5, 0.9):
        n_shared = round(frac * n_req)
        prompts = []
        for i in range(n_req):
            tail = rng.integers(1, V, tail_len).tolist()
            head = (
                shared if i < n_shared
                else rng.integers(1, V, prefix_len).tolist()
            )
            prompts.append(head + tail)

        for phase, cfg in (("cold", cfg_cold), ("warm", cfg_warm)):
            eng = InferenceEngine(cfg, params)
            # Compile pass at the measured shapes, drained before timing
            # (the jit caches live on the engine). Cache empty -> this
            # compiles the COLD prefill programs.
            for p in prompts:
                eng.submit(p, 2)
            eng.step()
            _drain(eng)
            if phase == "warm":
                # Rehearsal under the measurement's exact cache state
                # (pathfinder-only: ONE prior request carrying the shared
                # prefix, the system-prompt steady state) compiles the
                # warm-path prefill programs at the measured group shapes;
                # then reset to that same state for the timed pass.
                for _ in range(2):
                    eng.clear_prefix_cache()
                    eng.submit(shared, 2)
                    _drain(eng)
                    for p in prompts:
                        eng.submit(p, 2)
                    eng.step()
                    _drain(eng)
                eng.clear_prefix_cache()
                eng.submit(shared, 2)
                _drain(eng)
            eng.reset_timing()
            for p in prompts:
                eng.submit(p, 2)
            t0 = time.perf_counter()
            eng.step()           # admission burst: prefill == TTFT compute
            admit_ms = (time.perf_counter() - t0) * 1e3
            t = eng.reset_timing()
            _drain(eng)
            from orion_tpu.obs import bench_metrics_block

            print(json.dumps({
                "phase": phase,
                "shared_frac": frac,
                "requests": n_req,
                "prefix_tokens": prefix_len,
                "admit_ms": round(admit_ms, 2),
                "prefill_ms": round(t["prefill_s"] * 1e3, 2),
                "prefix_hits": int(t.get("prefix_hits", 0)),
                "cached_tokens": int(t.get("cached_tokens", 0)),
                "hit_rate": round(float(t.get("prefix_hit_rate", 0.0)), 3),
                # Standard bench metrics block (ISSUE 9): registry gauges
                # + the admit-step reset_timing window.
                "metrics": bench_metrics_block(eng, timing=t),
            }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
