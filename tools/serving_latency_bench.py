#!/usr/bin/env python
"""Serving latency under prompt bursts: TTFT/ITL percentiles, chunked
prefill vs whole-prompt prefill (ISSUE 2 'measure').

Scenario: a few short-prompt requests decode steadily; mid-stream, a
long-prompt request arrives. With whole-prompt prefill the admission runs
the full quadratic prefill before the next decode window — every in-flight
request observes that stall as one giant inter-token gap. With
``inference.chunked_prefill`` the engine runs mixed steps (one decode token
per live slot + at most ``prefill_chunk_tokens`` of prompt tail per
dispatch), so the worst stall any decode observes is bounded by the chunk
budget.

Reported per mode (one JSON line each): ITL percentiles (p50/p95/p99/max)
over every accepted decode token of the short requests, TTFT of the long
request, the engine's chunk/waste counters, and the largest prefill
dispatch observed while decodes were live (the structural no-head-of-line
check). A final JSON line compares the two runs.

    python tools/serving_latency_bench.py          # on-chip numbers
    python tools/serving_latency_bench.py --smoke  # tiny CPU logic check

``--overload`` (ISSUE 6 robustness): a 2x-capacity offered burst in two
priority classes against a bounded admission queue with per-request
deadlines. Reports typed-outcome accounting (completed/shed/expired —
no silent drops), shed rate and shed priorities, accepted-request
TTFT/ITL percentiles vs an uncontended run, and the worst deadline
overrun in steps (expiry reaping bounds it at ~1 by construction).

``--structured`` (ISSUE 16): mixed grammar-constrained + free-form
traffic; structured requests run as their own SLO class and the
per-class objectives are judged via ``obs.SLOMonitor`` (burn rates in
the JSON line); the verdict re-validates every constrained output
against its FSM and reports the forced-run draft tally.
"""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import json
import sys
import time

import jax
import numpy as np


def _run_scenario(eng, shorts, long_prompt, short_new, long_new, warm_tokens):
    """Serve the interference scenario once; returns the measurement dict.

    ``warm_tokens``: how many tokens each short request decodes before the
    long prompt is injected (so its prefill provably lands mid-decode).
    """
    from orion_tpu.metrics import LatencyStats
    from orion_tpu.obs import bench_metrics_block

    # Structural probe: the widest whole-prompt prefill dispatch issued
    # while at least one admitted request was decoding (chunked mode never
    # issues one — chunks ride the mixed step, whose prompt-side width is
    # the budget by construction).
    live_widths = []
    orig_prefill = eng._prefill

    def counting(*args):
        if any(
            r is not None and not r.done and not r.prefill_pending
            for r in eng.slots
        ):
            live_widths.append(int(args[2].shape[1]))
        return orig_prefill(*args)

    eng._prefill = counting
    itl = LatencyStats()
    max_chunk_step_tokens = 0
    totals: dict = {}
    eng.reset_timing()

    t_run0 = time.perf_counter()
    rids = [eng.submit(p, short_new) for p in shorts]
    reqs = {r.rid: r for r in eng.waiting}
    last_accept = {}
    seen = {rid: 0 for rid in rids}
    long_rid, t_long_submit, t_long_first = None, None, None
    steps = 0
    while eng.has_work():
        if long_rid is None and all(
            len(reqs[rid].generated) >= warm_tokens for rid in rids
        ):
            long_rid = eng.submit(long_prompt, long_new)
            long_req = eng.waiting[-1]
            t_long_submit = time.perf_counter()
        eng.step()
        steps += 1
        now = time.perf_counter()
        t = eng.reset_timing()
        max_chunk_step_tokens = max(max_chunk_step_tokens, t["chunk_tokens"])
        for k, v in t.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                # Counters sum across the per-step drains; snapshot/ratio
                # keys (decode_window, hit/acceptance rates, tokens-per-
                # verify) keep the last nonzero value — summing a rate
                # across hundreds of drains would report nonsense.
                if k == "decode_window" or k.endswith("_rate") \
                        or k.endswith("per_verify"):
                    totals[k] = v if v else totals.get(k, 0)
                else:
                    totals[k] = totals.get(k, 0) + v
        for rid in rids:
            n = len(reqs[rid].generated)
            if n > seen[rid]:
                if rid in last_accept:
                    # One ITL sample per accepted token; a W-token window
                    # yields one gap + W-1 zero-gaps, which is exactly how
                    # a streaming consumer experiences it.
                    gap = now - last_accept[rid]
                    itl.record(gap)
                    for _ in range(n - seen[rid] - 1):
                        itl.record(0.0)
                last_accept[rid] = now
                seen[rid] = n
        if (
            long_rid is not None and t_long_first is None
            and len(long_req.generated) > 0
        ):
            t_long_first = now
    wall_s = time.perf_counter() - t_run0
    s = itl.summary()
    return {
        "itl_p50_ms": round(s["p50"] * 1e3, 3),
        "itl_p95_ms": round(s["p95"] * 1e3, 3),
        "itl_p99_ms": round(s["p99"] * 1e3, 3),
        "itl_max_ms": round(s["max"] * 1e3, 3),
        "itl_samples": s["count"],
        "ttft_long_ms": round((t_long_first - t_long_submit) * 1e3, 3),
        "max_live_prefill_dispatch_tokens": max(live_widths, default=0),
        "max_chunk_tokens_per_step": max_chunk_step_tokens,
        "steps": steps,
        "wall_s": round(wall_s, 4),
        "steps_per_s": round(steps / wall_s, 2) if wall_s > 0 else None,
        # Standard bench metrics block (ISSUE 9): registry gauges + the
        # summed reset_timing counters of the measured run.
        "metrics": bench_metrics_block(eng, timing=totals),
    }


def _serve_outcomes(eng, subs, deadline_s):
    """Submit every (prompt, priority, new_tokens) up front — the offered
    burst — then step the engine dry. Returns per-request records (typed
    outcome, TTFT, ITL gaps, deadline overrun) and the per-step wall
    times; every submitted request is accounted for (no silent drops)."""
    recs = []
    for sub in subs:
        prompt, prio, new = sub[:3]
        # Optional 4th element: a ConstraintSpec (--structured traffic).
        constraint = sub[3] if len(sub) > 3 else None
        req = eng.submit_request(
            prompt, new, priority=prio, deadline_s=deadline_s,
            constraint=constraint,
        )
        recs.append({
            "req": req, "priority": prio,
            "submit": time.perf_counter(),
            "first": None, "last": None, "seen": 0, "gaps": [],
            "end_mono": None,
        })
    by_rid = {r["req"].rid: r for r in recs}
    step_times = []
    while eng.has_work():
        ts = time.perf_counter()
        finished = eng.step()
        now = time.perf_counter()
        step_times.append(now - ts)
        for r in recs:
            n = len(r["req"].generated)
            if n > r["seen"]:
                if r["first"] is None:
                    r["first"] = now
                else:
                    r["gaps"].append(now - r["last"])
                    for _ in range(n - r["seen"] - 1):
                        r["gaps"].append(0.0)
                r["last"] = now
                r["seen"] = n
        t_mono = time.monotonic()
        for req in finished:
            if req.rid in by_rid:
                by_rid[req.rid]["end_mono"] = t_mono
    return recs, step_times


def _overload_summary(recs, step_times, mode, slo_cfg=None):
    """Aggregate one overload run: typed-outcome counts, accepted-request
    TTFT/ITL percentiles, shed priorities and the worst deadline overrun
    measured in steps (expiry reaping at step boundaries bounds it at ~1
    by construction — the structural no-silent-miss check)."""
    from orion_tpu.metrics import LatencyStats

    outcomes = {}
    for r in recs:
        outcomes[r["req"].outcome] = outcomes.get(r["req"].outcome, 0) + 1
    ttft, itl = LatencyStats(), LatencyStats()
    for r in recs:
        if r["req"].outcome != "completed":
            continue
        if r["first"] is not None:
            ttft.record(r["first"] - r["submit"])
        for g in r["gaps"]:
            itl.record(g)
    max_step = max(step_times) if step_times else 0.0
    med_step = sorted(step_times)[len(step_times) // 2] if step_times else 0.0
    # Deadline overrun of every request that HELD a slot to completion:
    # a completed request that ran past its deadline would have been
    # reaped as "expired" at the first boundary after it, so the overrun
    # can never exceed the ONE step that spanned the deadline — measure
    # it rather than assert it. The bound is checked in SECONDS against
    # the run's own longest step (which may be a jit compile); the
    # steps-denominated figure uses the MEDIAN (steady-state) step so a
    # multi-second compile step cannot deflate a real overrun.
    overrun_s = 0.0
    for r in recs:
        if r["req"].outcome == "completed" and r["end_mono"] is not None:
            dl = r["req"].deadline
            if dl is not None and r["end_mono"] > dl:
                overrun_s = max(overrun_s, r["end_mono"] - dl)
    ts, is_ = ttft.summary(), itl.summary()
    offered = len(recs)
    n_shed = outcomes.get("shed", 0)
    # Per-priority-class TTFT/ITL percentiles (ISSUE 9 satellite; seeds
    # the ROADMAP multi-tenant SLO item): one registry section per class,
    # snapshotted into the JSON line — the named-snapshot API the engine's
    # future per-class accounting will feed directly.
    from orion_tpu.obs import MetricsRegistry

    reg = MetricsRegistry()
    for prio in sorted({r["priority"] for r in recs}):
        # Section names are identifier-shaped; negative classes spell the
        # sign out ("classneg1") instead of crashing register().
        section = f"class{prio}" if prio >= 0 else f"classneg{-prio}"
        cttft, citl = LatencyStats(), LatencyStats()
        n_done = n_offered = 0
        for r in recs:
            if r["priority"] != prio:
                continue
            n_offered += 1
            if r["req"].outcome != "completed":
                continue
            n_done += 1
            if r["first"] is not None:
                cttft.record(r["first"] - r["submit"])
            for g in r["gaps"]:
                citl.record(g)

        def provider(t=cttft, i=citl, done=n_done, off=n_offered):
            tsum, isum = t.summary(), i.summary()
            return {
                "offered": off,
                "completed": done,
                "ttft_p50_ms": round(tsum["p50"] * 1e3, 3),
                "ttft_p99_ms": round(tsum["p99"] * 1e3, 3),
                "itl_p50_ms": round(isum["p50"] * 1e3, 3),
                "itl_p99_ms": round(isum["p99"] * 1e3, 3),
            }

        reg.register(section, provider)
    # SLO judgment over the same per-class collectors (ISSUE 14: the
    # PR 8 per-class percentiles finally judged against objectives, not
    # just reported): replay completed-request TTFT/ITL through an
    # SLOMonitor built from cfg.slo and force-close one window — burn
    # rates + breach counts ride the JSON line next to the percentiles.
    slo_block = None
    if slo_cfg is not None and slo_cfg.enabled:
        from orion_tpu.obs import SLOMonitor

        mon = SLOMonitor.from_config(slo_cfg)
        for r in recs:
            if r["req"].outcome != "completed":
                continue
            if r["first"] is not None:
                mon.observe(
                    "ttft", r["priority"], r["first"] - r["submit"], 0.0
                )
            for g in r["gaps"]:
                mon.observe("itl", r["priority"], g, 0.0)
        mon.sweep(0.0, force=True)
        slo_block = {
            "breaches": mon.breaches,
            **{k: v for k, v in mon.metrics().items()
               if k.startswith("burn_")},
        }
    return {
        "slo": slo_block,
        "per_class": reg.snapshot(),
        "mode": mode,
        "offered": offered,
        "outcomes": outcomes,
        "shed_rate": round(n_shed / offered, 4) if offered else 0.0,
        "shed_priorities": sorted(
            {r["priority"] for r in recs if r["req"].outcome == "shed"}
        ),
        "ttft_p50_ms": round(ts["p50"] * 1e3, 3),
        "ttft_p99_ms": round(ts["p99"] * 1e3, 3),
        "itl_p50_ms": round(is_["p50"] * 1e3, 3),
        "itl_p99_ms": round(is_["p99"] * 1e3, 3),
        "itl_samples": is_["count"],
        "max_deadline_overrun_s": round(overrun_s, 3),
        "max_deadline_overrun_steps": round(
            overrun_s / max(med_step, 1e-9), 2
        ),
        "max_step_s": round(max_step, 3),
        "steps": len(step_times),
    }


def overload_main(smoke: bool) -> int:
    """--overload: 2x-capacity offered load against a bounded queue with
    two priority classes; one JSON line per mode (uncontended / overload)
    plus a verdict line. The overload engine must DEGRADE — typed sheds
    of the lowest class, feasible deadlines kept — never crash or
    silently drop."""
    from orion_tpu.config import get_config
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models import init_params

    if smoke:
        preset, base = "tiny-llama", [
            "model.max_seq_len=1024",
            "inference.max_seq_len=1024", "inference.page_size=64",
            "inference.num_pages=48", "inference.max_batch_size=4",
            "inference.prefill_chunk=64", "inference.decode_window=1",
            # Per-class SLO objective (obs/slo.py): judge the high
            # class's tail against a generous CPU-smoke bar — the pin is
            # that the judgment RUNS and a healthy run burns zero budget,
            # not a latency bar for a smoke with jit compiles in it.
            "slo.per_class=1:ttft=120000,itl=60000",
        ]
        prompt_len, new_tokens, deadline_s = 8, 24, 60.0
    else:
        preset, base = "llama-1b-bench", [
            "model.param_dtype=bfloat16",
            "inference.max_seq_len=2048", "inference.page_size=64",
            "inference.num_pages=1024", "inference.max_batch_size=8",
            "inference.prefill_chunk=256", "inference.decode_window=1",
            # On-chip bar for the high class (the ROADMAP multi-tenant
            # SLO: priority 1 = interactive traffic).
            "slo.per_class=1:ttft=2000,itl=100",
        ]
        prompt_len, new_tokens, deadline_s = 32, 128, 120.0

    cfg = get_config(preset, base)
    B = cfg.inference.max_batch_size
    # Offered = 2x the slot capacity (B high + B low, interleaved) in one
    # burst; the queue is bounded at B, so the overload MUST shed the
    # surplus — and the priority/deadline victim rule sheds exactly the
    # low class, leaving the accepted set identical to the uncontended
    # run's (the clean SLO comparison).
    qcfg = get_config(preset, base + [
        f"inference.queue_limit={B}",
    ])
    rng = np.random.default_rng(0)
    V = cfg.model.vocab_size
    mk = lambda: rng.integers(1, V, prompt_len).tolist()
    params = init_params(cfg.model, jax.random.key(0))

    results = {}
    for mode in ("uncontended", "overload"):
        c = cfg if mode == "uncontended" else qcfg
        eng = InferenceEngine(c, params)
        if mode == "uncontended":
            subs = [(mk(), 1, new_tokens) for _ in range(B)]
        else:
            # interleave hi/lo so the bounded queue always holds both
            # classes when the shed decision fires
            subs = []
            for _ in range(B):
                subs.append((mk(), 1, new_tokens))
                subs.append((mk(), 0, new_tokens))
        # Compile pass at the serving shapes, then the timed pass.
        _serve_outcomes(eng, [(mk(), 1, 4)], deadline_s)
        recs, step_times = _serve_outcomes(eng, subs, deadline_s)
        eng.assert_page_accounting()
        r = _overload_summary(recs, step_times, mode, slo_cfg=c.slo)
        t = eng.reset_timing()
        r["engine_shed"] = t["shed_requests"]
        r["engine_expired"] = t["expired_requests"]
        from orion_tpu.obs import bench_metrics_block

        r["metrics"] = bench_metrics_block(eng, timing=t)
        results[mode] = r
        print(json.dumps(r))
    un, ov = results["uncontended"], results["overload"]
    acc = {
        k: v for k, v in ov["outcomes"].items()
        if k not in ("shed", "expired")
    }
    verdict = {
        # Structural: every offered request carries exactly one typed
        # outcome; the surplus shed, and only from the lowest class.
        "no_silent_drops": sum(ov["outcomes"].values()) == ov["offered"],
        "all_typed": set(ov["outcomes"]) <= {"completed", "shed", "expired"},
        "sheds_lowest_priority_only": ov["shed_priorities"] in ([], [0]),
        # Reap-at-boundary structural bound: an overrun can never exceed
        # the one (possibly compile-length) step spanning the deadline.
        "deadline_overrun_bounded":
            ov["max_deadline_overrun_s"] <= ov["max_step_s"] + 1e-3,
        "accepted_completed": sum(acc.values()),
        # SLO: accepted-request tail latency under 2x offered load vs the
        # uncontended run (the acceptance bar is 1.10 on-chip; CPU smoke
        # wall clocks are noisy, so the smoke asserts structure only).
        "ttft_p99_ratio": round(
            ov["ttft_p99_ms"] / un["ttft_p99_ms"], 4
        ) if un["ttft_p99_ms"] else None,
        "itl_p99_ratio": round(
            ov["itl_p99_ms"] / un["itl_p99_ms"], 4
        ) if un["itl_p99_ms"] else None,
        # SLO burn (obs/slo.py): the high class's judged breach count per
        # mode — shedding the LOW class is exactly how the hi-class
        # objective survives 2x offered load.
        "slo_breaches_uncontended": (un.get("slo") or {}).get("breaches"),
        "slo_breaches_overload": (ov.get("slo") or {}).get("breaches"),
    }
    print(json.dumps(verdict))
    return 0


def structured_main(smoke: bool) -> int:
    """--structured (ISSUE 16): mixed structured + free-form traffic.
    Constrained (JSON-schema, grammar-masked) requests run as their own
    SLO class alongside free-form decodes, and the per-class objectives
    are JUDGED via obs.SLOMonitor — structured traffic trades raw ITL
    for validity and forced-run speedup, so it gets its own bar instead
    of silently burning the interactive class's budget. One JSON line
    per mode (freeform-only / mixed) plus a verdict line: every
    constrained output re-validates against the FSM, forced-run draft
    tokens were produced, and the structured class's SLO judgment ran."""
    from orion_tpu.config import get_config
    from orion_tpu.constrain import (
        ConstraintSpec, ConstraintState, compile_constraint,
    )
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models import init_params
    from orion_tpu.obs import bench_metrics_block

    if smoke:
        preset, base = "tiny-llama", [
            "inference.max_seq_len=128", "inference.page_size=16",
            "inference.num_pages=32", "inference.max_batch_size=4",
            "inference.prefill_chunk=16", "inference.decode_window=1",
            "inference.constrained=true", "inference.speculative=true",
            # Structured traffic is SLO class 2; free-form interactive
            # stays class 1. CPU-smoke bars are generous — the pin is
            # that the per-class judgment RUNS and a healthy run burns
            # zero budget, not a wall-clock bar with jit compiles in it.
            "slo.per_class=2:ttft=120000,itl=60000;"
            "1:ttft=120000,itl=60000",
        ]
        prompt_len, new_tokens, deadline_s = 6, 24, 60.0
    else:
        preset, base = "llama-1b-bench", [
            "model.param_dtype=bfloat16",
            "inference.max_seq_len=2048", "inference.page_size=64",
            "inference.num_pages=1024", "inference.max_batch_size=8",
            "inference.prefill_chunk=256", "inference.decode_window=1",
            "inference.constrained=true", "inference.speculative=true",
            # On-chip bars: structured (class 2) tolerates a higher TTFT
            # (constraint compile on first sight) for the masked-decode
            # validity guarantee; interactive (class 1) keeps its bar.
            "slo.per_class=2:ttft=3000,itl=120;1:ttft=2000,itl=100",
        ]
        prompt_len, new_tokens, deadline_s = 32, 96, 120.0

    cfg = get_config(preset, base)
    B = cfg.inference.max_batch_size
    rng = np.random.default_rng(0)
    V = cfg.model.vocab_size
    mk = lambda: rng.integers(1, min(V, 256), prompt_len).tolist()
    schema = (
        '{"type": "object", "properties": {'
        '"ok": {"type": "boolean"}, "n": {"type": "integer"}}}'
    )
    spec = ConstraintSpec(json_schema=schema)
    params = init_params(cfg.model, jax.random.key(0))

    results = {}
    for mode in ("freeform", "mixed"):
        eng = InferenceEngine(cfg, params)
        if mode == "freeform":
            subs = [(mk(), 1, new_tokens) for _ in range(B)]
        else:
            # Half structured (class 2), half free-form (class 1),
            # interleaved so both classes share every batch.
            subs = []
            for i in range(B):
                if i % 2 == 0:
                    subs.append((mk(), 2, new_tokens, spec))
                else:
                    subs.append((mk(), 1, new_tokens))
        # Compile pass at the serving shapes (constrained + free rows),
        # then the timed pass on the same engine.
        _serve_outcomes(
            eng, [(mk(), 2, 4, spec), (mk(), 1, 4)], deadline_s
        )
        eng.reset_timing()
        recs, step_times = _serve_outcomes(eng, subs, deadline_s)
        eng.assert_page_accounting()
        r = _overload_summary(recs, step_times, mode, slo_cfg=cfg.slo)
        t = eng.reset_timing()
        r["metrics"] = bench_metrics_block(eng, timing=t)
        r["constrain"] = {
            k: v for k, v in t.items() if k.startswith("constrain_")
        }
        # Validity audit: every structured output must re-walk its FSM
        # (prefix-legal always; fully accepted when it closed the
        # grammar before hitting its token budget).
        dfa, _ = compile_constraint(spec, V)
        valid = True
        for rec in recs:
            req = rec["req"]
            if req.constraint is None:
                continue
            body = [
                tk for tk in req.generated if tk != eng.eos_id
            ]
            c = ConstraintState(dfa, eng.eos_id)
            if not c.sync(body):
                valid = False
        r["constrained_outputs_fsm_legal"] = valid
        results[mode] = r
        print(json.dumps(r))
    free, mixed = results["freeform"], results["mixed"]
    cs = mixed["constrain"]
    verdict = {
        "all_completed": (
            mixed["outcomes"].get("completed", 0) == mixed["offered"]
        ),
        "constrained_outputs_fsm_legal":
            mixed["constrained_outputs_fsm_legal"],
        # Forced-run amplification: single-choice FSM states produced
        # free draft tokens, and every one of them was accepted.
        "forced_run_tokens": cs.get("constrain_forced_drafted", 0),
        "forced_all_accepted": (
            cs.get("constrain_forced_accepted", 0)
            == cs.get("constrain_forced_drafted", 0)
        ),
        # The structured class was actually JUDGED: its burn-rate gauges
        # exist in the SLO block (class 2 keys), and a healthy smoke
        # burns zero budget in both classes.
        "structured_class_judged": any(
            k.startswith("burn_") and k.endswith("_c2")
            for k in (mixed.get("slo") or {})
        ),
        "slo_breaches_mixed": (mixed.get("slo") or {}).get("breaches"),
        "itl_p99_ratio_mixed_vs_freeform": round(
            mixed["itl_p99_ms"] / free["itl_p99_ms"], 4
        ) if free["itl_p99_ms"] else None,
    }
    print(json.dumps(verdict))
    return 0


def main() -> int:
    smoke = "--smoke" in sys.argv[1:] or "--cpu" in sys.argv[1:]
    # --trace: run the same scenario with the span tracer ON — the
    # steps_per_s / wall_s delta vs a plain run IS the tracer-overhead
    # measurement (PERF.md "Tracer overhead").
    trace = "--trace" in sys.argv[1:]
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        print(f"FAIL: no TPU backend (default backend is "
              f"{jax.default_backend()!r}); use --smoke for the CPU logic check")
        return 1
    if "--overload" in sys.argv[1:]:
        return overload_main(smoke)
    if "--structured" in sys.argv[1:]:
        return structured_main(smoke)

    from orion_tpu.config import get_config
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models import init_params

    if smoke:
        preset, base = "tiny-llama", [
            "model.max_seq_len=1024",
            "inference.max_seq_len=1024", "inference.page_size=64",
            "inference.num_pages=48", "inference.max_batch_size=4",
            "inference.prefill_chunk=64", "inference.decode_window=1",
        ]
        budget, long_len, short_len = 64, 640, 8
        n_short, short_new, long_new, warm = 2, 40, 4, 4
    else:
        preset, base = "llama-1b-bench", [
            "model.param_dtype=bfloat16",
            "inference.max_seq_len=2048", "inference.page_size=64",
            "inference.num_pages=1024", "inference.max_batch_size=8",
            "inference.prefill_chunk=256", "inference.decode_window=1",
        ]
        budget, long_len, short_len = 256, 1536, 32
        n_short, short_new, long_new, warm = 4, 128, 8, 8

    if trace:
        base = base + ["inference.trace=true"]
    rng = np.random.default_rng(0)
    cfg_cold = get_config(preset, base)
    cfg_chunk = get_config(preset, base + [
        "inference.chunked_prefill=true",
        f"inference.prefill_chunk_tokens={budget}",
    ])
    V = cfg_cold.model.vocab_size
    shorts = [rng.integers(1, V, short_len).tolist() for _ in range(n_short)]
    long_prompt = rng.integers(1, V, long_len).tolist()
    params = init_params(cfg_cold.model, jax.random.key(0))

    results = {}
    for mode, cfg in (("unchunked", cfg_cold), ("chunked", cfg_chunk)):
        eng = InferenceEngine(cfg, params)
        # Compile pass at the measured shapes (jit caches live on the
        # engine), then the timed pass on the same engine.
        _run_scenario(eng, shorts, long_prompt, short_new, long_new, warm)
        r = _run_scenario(eng, shorts, long_prompt, short_new, long_new,
                          warm)
        r["mode"] = mode
        r["trace"] = trace
        r["prefill_chunk_tokens"] = budget if mode == "chunked" else None
        results[mode] = r
        print(json.dumps(r))
    cold, chunk = results["unchunked"], results["chunked"]
    verdict = {
        # Structural head-of-line check: the chunked engine issued NO
        # whole-prompt prefill dispatch while decodes were live, and no
        # mixed step carried more prompt tokens than the budget.
        "stall_bounded": (
            chunk["max_live_prefill_dispatch_tokens"] == 0
            and 0 < chunk["max_chunk_tokens_per_step"] <= budget
        ),
        "unchunked_live_prefill_tokens":
            cold["max_live_prefill_dispatch_tokens"],
        "chunked_p99_below_unchunked":
            chunk["itl_p99_ms"] < cold["itl_p99_ms"],
        "itl_p99_ratio": round(
            chunk["itl_p99_ms"] / cold["itl_p99_ms"], 4
        ) if cold["itl_p99_ms"] else None,
    }
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
