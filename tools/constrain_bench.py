#!/usr/bin/env python
"""Grammar-constrained decoding bench: FSM logit masks as speculation
amplifiers — constrained vs unconstrained acceptance on a structured
(JSON-schema) workload (ISSUE 16 'measure').

The claim under test: a grammar does not just make outputs valid, it
makes speculation CHEAPER. Wherever the token DFA admits exactly one
continuation (JSON punctuation, key names, ``true``/``false`` literals)
the masked target probability of that token is exactly 1.0, so drafting
it costs nothing and it is accepted with certainty under both greedy
argmax and rejection sampling. The n-gram proposer, by contrast, has to
EARN its acceptance from workload self-similarity — on low-repetition
prompts it mostly stalls.

Four modes over the same prompts (greedy, so acceptance is exact):

  - freeform_spec:     n-gram chain speculation, no constraint — the
                       unconstrained acceptance the verdict compares
                       against.
  - constrained_greedy: n-gram proposer OFF — but grammar-forced runs
                       still ride the verify program as drafts (they
                       come from the FSM, not the proposer), so even
                       "speculation-free" constrained decoding
                       multi-emits through punctuation runs.
  - constrained_spec:  forced single-choice runs drafted for free, then
                       FSM-filtered n-gram extension on the ambiguous
                       tail.
  - constrained_tree:  ambiguous FSM states become branch points of a
                       token tree (``spec_decode.build_tree``), so the
                       verify dispatch carries the grammar's
                       alternatives instead of betting on one.

Constraints operate on the byte-level tokenizer contract (token id ==
byte; ids >= 256 are illegal in every state), matching generate.py's
``--json-schema``/``--regex`` flags. Every constrained output is
re-walked through a freshly compiled DFA — validity is audited, not
assumed. One JSON line per mode; the verdict line last pins
forced-run tokens > 0, forced acceptance == 1.0, and constrained
acceptance >= unconstrained.

    python tools/constrain_bench.py          # on-chip numbers
    python tools/constrain_bench.py --smoke  # tiny CPU logic check
"""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import json
import sys
import time

import jax
import numpy as np

SCHEMA = (
    '{"type": "object", "properties": {'
    '"ok": {"type": "boolean"}, "n": {"type": "integer"}}}'
)


def _run(eng, prompts, max_new, spec):
    """Drain the workload once; ITL + spec/constrain counters."""
    from orion_tpu.metrics import LatencyStats

    itl = LatencyStats()
    eng.reset_timing()
    reqs = [eng.submit_request(p, max_new, constraint=spec)
            for p in prompts]
    seen = [0] * len(reqs)
    last = [None] * len(reqs)
    t0 = time.perf_counter()
    while eng.has_work():
        eng.step()
        now = time.perf_counter()
        for i, r in enumerate(reqs):
            n = len(r.generated)
            if n > seen[i]:
                if last[i] is not None:
                    itl.record(now - last[i])
                    for _ in range(n - seen[i] - 1):
                        itl.record(0.0)
                last[i] = now
                seen[i] = n
    wall = time.perf_counter() - t0
    t = eng.reset_timing()
    s = itl.summary()
    steps = max(t["steps"], 1)
    out = {
        "itl_p50_ms": round(s["p50"] * 1e3, 3),
        "itl_p99_ms": round(s["p99"] * 1e3, 3),
        "wall_s": round(wall, 3),
        "tokens": sum(len(r.generated) for r in reqs),
        "steps": t["steps"],
        "dev_ms_per_step": round(t["device_s"] / steps * 1e3, 3),
        "host_ms_per_step": round(t["host_s"] / steps * 1e3, 3),
        "outcomes": sorted({r.outcome for r in reqs}),
    }
    for key in ("spec_drafted", "spec_accepted", "spec_acceptance_rate",
                "verify_steps", "verify_slot_steps",
                "spec_tokens_per_verify", "spec_tree_nodes",
                "constrain_requests", "constrain_compiles",
                "constrain_compile_hits", "constrain_compile_s",
                "constrain_advance_s", "constrain_masked_steps",
                "constrain_masked_rows", "constrain_forced_drafted",
                "constrain_forced_accepted", "constrain_branch_points",
                "constrain_completed", "constrain_dead_ends"):
        if key in t:
            out[key] = round(t[key], 4) if isinstance(t[key], float) \
                else t[key]
    from orion_tpu.obs import bench_metrics_block

    out["metrics"] = bench_metrics_block(eng, timing=t)
    return out, [list(r.generated) for r in reqs]


def _fsm_legal(outputs, spec, vocab_size, eos_id):
    """Audit: re-walk every output through a FRESH DFA compile."""
    from orion_tpu.constrain import compile_constraint
    from orion_tpu.constrain.dfa import ConstraintState

    dfa, _ = compile_constraint(spec, vocab_size)
    for toks in outputs:
        body = toks[:-1] if (toks and toks[-1] == eos_id) else toks
        c = ConstraintState(dfa, eos_id)
        if not c.sync(body):
            return False
    return True


def main() -> int:
    smoke = "--smoke" in sys.argv[1:] or "--cpu" in sys.argv[1:]
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        print(f"FAIL: no TPU backend (default backend is "
              f"{jax.default_backend()!r}); use --smoke for the CPU logic check")
        return 1

    from orion_tpu.config import get_config
    from orion_tpu.constrain import ConstraintSpec
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models import init_params

    if smoke:
        preset, base = "tiny-llama", [
            "inference.max_seq_len=128", "inference.page_size=16",
            "inference.num_pages=32", "inference.max_batch_size=4",
            "inference.prefill_chunk=16", "inference.decode_window=1",
        ]
        speculate, tree_width, max_new, prompt_len = 4, 3, 24, 6
    else:
        preset, base = "llama-1b-bench", [
            "model.param_dtype=bfloat16",
            "inference.max_seq_len=2048", "inference.page_size=64",
            "inference.num_pages=1024", "inference.max_batch_size=8",
            "inference.prefill_chunk=256", "inference.decode_window=1",
        ]
        speculate, tree_width, max_new, prompt_len = 6, 4, 96, 32

    spec_ov = ["inference.speculative=true",
               f"inference.speculate_tokens={speculate}"]
    con_ov = ["inference.constrained=true"]
    modes = [
        ("freeform_spec", get_config(preset, base + spec_ov), False),
        ("constrained_greedy", get_config(preset, base + con_ov), True),
        ("constrained_spec",
         get_config(preset, base + spec_ov + con_ov), True),
        ("constrained_tree",
         get_config(preset, base + spec_ov + con_ov
                    + [f"inference.spec_tree_width={tree_width}"]), True),
    ]
    params = init_params(modes[0][1].model, jax.random.key(0))
    cspec = ConstraintSpec(json_schema=SCHEMA)

    # Low-repetition prompts: the n-gram proposer gets no planted
    # structure, so freeform acceptance is what random self-overlap
    # buys — the regime where the grammar's forced runs matter most.
    rng = np.random.default_rng(16)
    V = modes[0][1].model.vocab_size
    prompts = [rng.integers(1, min(V, 256), prompt_len).tolist()
               for _ in range(3)]

    results, outputs = {}, {}
    for mode, cfg, constrained in modes:
        eng = InferenceEngine(cfg, params)
        spec = cspec if constrained else None
        _run(eng, prompts, max_new, spec)        # compile pass
        r, toks = _run(eng, prompts, max_new, spec)
        r["mode"] = mode
        r["constrained"] = constrained
        if constrained:
            r["fsm_legal"] = _fsm_legal(
                toks, cspec, cfg.model.vocab_size, eng.eos_id
            )
        results[mode] = r
        outputs[mode] = toks
        print(json.dumps(r))
        eng.close()

    free = results["freeform_spec"]
    cspec_r = results["constrained_spec"]
    ctree_r = results["constrained_tree"]
    forced = cspec_r.get("constrain_forced_drafted", 0)
    verdict = {
        # Validity is audited by re-walking outputs through a fresh
        # compile, per constrained mode.
        "constrained_outputs_fsm_legal": all(
            results[m]["fsm_legal"] for m in
            ("constrained_greedy", "constrained_spec", "constrained_tree")
        ),
        # The amplification claim: forced runs exist and NEVER miss
        # (masked target prob is exactly 1.0 on a single-choice state).
        "forced_run_tokens": forced,
        "forced_all_accepted": forced > 0 and
        cspec_r.get("constrain_forced_accepted", 0) == forced,
        "acceptance": {
            "freeform": free.get("spec_acceptance_rate", 0.0),
            "constrained": cspec_r.get("spec_acceptance_rate", 0.0),
            "tree": ctree_r.get("spec_acceptance_rate", 0.0),
        },
        "constrained_acceptance_ge_freeform":
        cspec_r.get("spec_acceptance_rate", 0.0)
        >= free.get("spec_acceptance_rate", 0.0),
        "tokens_per_verify": {
            "freeform": free.get("spec_tokens_per_verify", 0.0),
            "constrained": cspec_r.get("spec_tokens_per_verify", 0.0),
            "tree": ctree_r.get("spec_tokens_per_verify", 0.0),
        },
        # Grammar branch points actually fed build_tree in tree mode.
        "tree_branch_points": ctree_r.get("constrain_branch_points", 0),
        # Second engine onward compiles nothing: the module-level DFA
        # cache is shared across engines and requests.
        "dfa_cache_hits": cspec_r.get("constrain_compile_hits", 0)
        + ctree_r.get("constrain_compile_hits", 0),
        "no_dead_ends": all(
            results[m].get("constrain_dead_ends", 0) == 0 for m in
            ("constrained_greedy", "constrained_spec", "constrained_tree")
        ),
        "constrained_greedy_itl_p50_ratio": round(
            results["constrained_greedy"]["itl_p50_ms"]
            / free["itl_p50_ms"], 4
        ) if free["itl_p50_ms"] else None,
    }
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
