#!/usr/bin/env python
"""Speculative decoding bench: chain vs TREE drafting, looping vs
non-looping workloads, and the verify KERNEL PATH (xla scatter+gather vs
the multi-query ragged paged-attention Pallas kernel) vs the
non-speculative engine (ISSUE 3 'measure', ISSUE 5 kernel-path column,
ISSUE 11 tree columns).

Two workloads, because the two drafting modes win in different regimes:

  - looping: prompts whose greedy continuations cycle — the canonical
    single-path speculative win (the n-gram proposer drafts the loop).
    Tree drafting must DEGENERATE here: one candidate, chain-shaped
    tree, tokens-per-verify-dispatch >= the single-path mode's.
  - nonloop: low self-repetition prompts with AMBIGUOUS n-gram
    continuations (the same suffix recurs with different followers) —
    single-path drafting must bet on the most recent match and stalls;
    tree drafting carries the alternatives as verified branches, which
    is where the acceptance uplift is measured (not asserted in prose).

Each speculative mode runs on BOTH kernel settings so the kernel win is
measured; one JSON line per (workload, mode, verify_path) with ITL
percentiles, per-step device/host ms, and the speculation counters. The
final verdict line pins greedy byte-identity per (workload, kernel path,
mode) and the tree-vs-chain acceptance/throughput columns.

    python tools/spec_decode_bench.py          # on-chip numbers
    python tools/spec_decode_bench.py --smoke  # tiny CPU logic check
                                               # (pallas via interpreter)
"""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import json
import random
import sys
import time

import jax


def _ambig_prompts(n, lo, hi, seed0=6, reps=4):
    """Non-looping prompts with planted ambiguous continuations: the
    (a, b) bigram recurs with a DIFFERENT follower each time, so the
    n-gram proposer always has several plausible continuations and a
    single path must bet on one."""
    out = []
    for i in range(n):
        r = random.Random(seed0 + i)
        a, b = r.randrange(lo, hi), r.randrange(lo, hi)
        p = [r.randrange(lo, hi) for _ in range(4)]
        for _ in range(reps):
            p += [a, b, r.randrange(lo, hi), r.randrange(lo, hi)]
        out.append(p + [a, b])
    return out


def _run(eng, prompts, max_new):
    """Drain the workload once; per-token ITL + spec counters."""
    from orion_tpu.metrics import LatencyStats

    itl = LatencyStats()
    eng.reset_timing()
    rids = [eng.submit(p, max_new) for p in prompts]
    reqs = {r.rid: r for r in eng.waiting}
    seen = {rid: 0 for rid in rids}
    last = {}
    t0 = time.perf_counter()
    while eng.has_work():
        eng.step()
        now = time.perf_counter()
        for rid in rids:
            n = len(reqs[rid].generated)
            if n > seen[rid]:
                if rid in last:
                    # One gap per engine step + zero-gaps for the extra
                    # tokens the step emitted — how a streaming consumer
                    # experiences a multi-token acceptance.
                    itl.record(now - last[rid])
                    for _ in range(n - seen[rid] - 1):
                        itl.record(0.0)
                last[rid] = now
                seen[rid] = n
    wall = time.perf_counter() - t0
    t = eng.reset_timing()
    s = itl.summary()
    steps = max(t["steps"], 1)
    out = {
        "itl_p50_ms": round(s["p50"] * 1e3, 3),
        "itl_p95_ms": round(s["p95"] * 1e3, 3),
        "itl_p99_ms": round(s["p99"] * 1e3, 3),
        "wall_s": round(wall, 3),
        "tokens": sum(len(reqs[rid].generated) for rid in rids),
        "steps": t["steps"],
        # decode_window=1: one dispatch per step, so for the speculative
        # modes these are the per-VERIFY device/host costs.
        "dev_ms_per_step": round(t["device_s"] / steps * 1e3, 3),
        "host_ms_per_step": round(t["host_s"] / steps * 1e3, 3),
    }
    for key in ("spec_drafted", "spec_accepted", "spec_rolled_back",
                "spec_acceptance_rate", "verify_steps",
                "verify_slot_steps", "spec_tokens_per_verify",
                "spec_gated_steps", "spec_tree_nodes",
                "spec_tree_branch_nodes", "spec_compactions",
                "spec_compacted_tokens"):
        if key in t:
            out[key] = round(t[key], 4) if isinstance(t[key], float) \
                else t[key]
    if "verify_slot_steps" in t:
        # Accepted DRAFT tokens per per-slot verify opportunity: the
        # acceptance column the tree-vs-chain comparison reads (the raw
        # acceptance_rate divides by drafted NODES, which a tree has
        # more of by construction).
        out["accept_per_slot_step"] = round(
            t["spec_accepted"] / max(t["verify_slot_steps"], 1), 4
        )
    from orion_tpu.obs import bench_metrics_block

    # Standard bench metrics block (ISSUE 9): registry gauges + the
    # drained reset_timing window of the measured run.
    out["metrics"] = bench_metrics_block(eng, timing=t)
    return out, {rid: list(reqs[rid].generated) for rid in rids}


def main() -> int:
    smoke = "--smoke" in sys.argv[1:] or "--cpu" in sys.argv[1:]
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        print(f"FAIL: no TPU backend (default backend is "
              f"{jax.default_backend()!r}); use --smoke for the CPU logic check")
        return 1

    from orion_tpu.config import get_config
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models import init_params

    if smoke:
        preset, base = "tiny-llama", [
            "inference.max_seq_len=128", "inference.page_size=16",
            "inference.num_pages=32", "inference.max_batch_size=4",
            "inference.prefill_chunk=16", "inference.decode_window=1",
        ]
        speculate, tree_width, max_new = 4, 3, 40
        # Self-repetitive workload: short cyclic prompts whose greedy
        # continuations loop on the fixed-seed tiny model, so the n-gram
        # proposer has real structure to draft from.
        looping = [
            [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8],
            [5, 6, 5, 6, 5, 6, 5, 6, 5],
            [11, 12, 13, 11, 12, 13, 11, 12, 13, 11, 12],
        ]
        nonloop = _ambig_prompts(3, 2, 200)
    else:
        preset, base = "llama-1b-bench", [
            "model.param_dtype=bfloat16",
            "inference.max_seq_len=2048", "inference.page_size=64",
            "inference.num_pages=1024", "inference.max_batch_size=8",
            "inference.prefill_chunk=256", "inference.decode_window=1",
        ]
        speculate, tree_width, max_new = 6, 4, 256
        looping = [
            ([17 + i, 91 + i, 203 + i, 44 + i] * 64)[:240]
            for i in range(4)
        ]
        nonloop = _ambig_prompts(4, 2, 32000, reps=16)

    chain_ov = [
        "inference.speculative=true",
        f"inference.speculate_tokens={speculate}",
    ]
    tree_ov = chain_ov + [f"inference.spec_tree_width={tree_width}"]
    # Both kernel paths: the "pallas" rows run the compiled Mosaic kernels
    # on the chip and, under --smoke, ask for the Pallas interpreter by
    # name. Greedy streams are comparable only WITHIN a kernel path (the
    # xla and pallas attention algorithms round differently), so each
    # spec mode gets its own baseline. The nonloop workload reuses the
    # SAME engines (same programs — only the requests change).
    modes = []
    for path in ("xla", "pallas"):
        impl = "pallas_interpret" if smoke and path == "pallas" else path
        kern = [f"model.kernels={impl}"]
        modes.append((f"baseline_{path}", path,
                      get_config(preset, base + kern)))
        modes.append((f"speculative_{path}", path,
                      get_config(preset, base + kern + chain_ov)))
        modes.append((f"tree_{path}", path,
                      get_config(preset, base + kern + tree_ov)))
    params = init_params(modes[0][2].model, jax.random.key(0))

    workloads = [("looping", looping), ("nonloop", nonloop)]
    results, tokens = {}, {}
    for mode, path, cfg in modes:
        eng = InferenceEngine(cfg, params)
        for wname, prompts in workloads:
            if wname == "nonloop" and path == "pallas":
                # The nonloop tree-vs-chain comparison is a DRAFTING
                # property; one kernel path measures it (the pallas
                # identity is pinned on the looping workload).
                continue
            _run(eng, prompts, max_new)      # compile pass, same shapes
            r, toks = _run(eng, prompts, max_new)
            r["mode"] = mode
            r["workload"] = wname
            r["verify_path"] = path
            r["speculate_tokens"] = (
                None if mode.startswith("baseline") else speculate
            )
            r["spec_tree_width"] = (
                tree_width if mode.startswith("tree") else
                (1 if mode.startswith("speculative") else None)
            )
            results[(wname, mode)] = r
            tokens[(wname, mode)] = toks
            print(json.dumps(r))

    lp = {m: results[("looping", m)] for m, _, _ in modes}
    spec_x, spec_p = lp["speculative_xla"], lp["speculative_pallas"]
    tree_x, tree_p = lp["tree_xla"], lp["tree_pallas"]
    base_x = lp["baseline_xla"]
    nl_chain = results[("nonloop", "speculative_xla")]
    nl_tree = results[("nonloop", "tree_xla")]
    verdict = {
        # Greedy speculative output must be byte-identical to the
        # non-speculative engine's (exact argmax acceptance), per kernel
        # path and per drafting mode — the tree entries are the ISSUE 11
        # acceptance criterion, the pallas ones ISSUE 5's.
        "greedy_identical": tokens[("looping", "baseline_xla")]
        == tokens[("looping", "speculative_xla")],
        "pallas_greedy_identical": tokens[("looping", "baseline_pallas")]
        == tokens[("looping", "speculative_pallas")],
        "tree_greedy_identical": tokens[("looping", "baseline_xla")]
        == tokens[("looping", "tree_xla")],
        "tree_pallas_greedy_identical":
        tokens[("looping", "baseline_pallas")]
        == tokens[("looping", "tree_pallas")],
        "nonloop_tree_greedy_identical":
        tokens[("nonloop", "baseline_xla")]
        == tokens[("nonloop", "tree_xla")],
        # The amortization the speculation bought: emitted decode tokens
        # per per-slot verify dispatch (1.0 = speculation bought
        # nothing). On the LOOPING workload the tree must not lose to
        # the chain (it degenerates to it).
        "spec_tokens_per_verify": spec_x.get("spec_tokens_per_verify", 0.0),
        "tree_tokens_per_verify": tree_x.get("spec_tokens_per_verify", 0.0),
        "acceptance_rate": spec_x.get("spec_acceptance_rate", 0.0),
        # The tree-vs-chain columns on the NON-LOOPING workload: accepted
        # draft tokens per per-slot verify opportunity (the uplift the
        # ROADMAP names), tokens/dispatch, and ITL.
        "nonloop_accept_per_slot": {
            "chain": nl_chain.get("accept_per_slot_step", 0.0),
            "tree": nl_tree.get("accept_per_slot_step", 0.0),
        },
        "nonloop_tree_uplift": round(
            nl_tree.get("accept_per_slot_step", 0.0)
            - nl_chain.get("accept_per_slot_step", 0.0), 4
        ),
        "nonloop_tokens_per_verify": {
            "chain": nl_chain.get("spec_tokens_per_verify", 0.0),
            "tree": nl_tree.get("spec_tokens_per_verify", 0.0),
        },
        "nonloop_itl_p50_ms": {
            "chain": nl_chain["itl_p50_ms"], "tree": nl_tree["itl_p50_ms"],
        },
        "itl_p50_ratio": round(
            spec_x["itl_p50_ms"] / base_x["itl_p50_ms"], 4
        ) if base_x["itl_p50_ms"] else None,
        "steps_ratio": round(spec_x["steps"] / base_x["steps"], 4)
        if base_x["steps"] else None,
        # The kernel-path win per verify dispatch (meaningful on-chip;
        # interpreter timings under --smoke are not device costs).
        "verify_dev_ms": {"xla": spec_x["dev_ms_per_step"],
                          "pallas": spec_p["dev_ms_per_step"]},
        "tree_verify_dev_ms": {"xla": tree_x["dev_ms_per_step"],
                               "pallas": tree_p["dev_ms_per_step"]},
        "pallas_dev_ratio": round(
            spec_p["dev_ms_per_step"] / spec_x["dev_ms_per_step"], 4
        ) if spec_x["dev_ms_per_step"] else None,
    }
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
