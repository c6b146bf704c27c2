#!/usr/bin/env python
"""Generation entry point (reference ``inference/generate.py``,
BASELINE.json:11): continuous-batching inference over the paged KV cache.

Usage:
    python generate.py --preset tiny-llama --tokens "5,3,9" [--tokens "..."]
    python generate.py --preset gpt2-125m --prompt "hello" --byte-tokenizer

Prompts are token-id lists (``--tokens``, repeatable — each becomes one
request, served concurrently) or raw text under the byte tokenizer (demo
path; real deployments bring their own tokenizer). Parameters come from the
checkpoint directory if configured (checkpoint.directory=...), else random
init — which still exercises the full engine, scheduler and cache path.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from functools import partial


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    parser.add_argument("--preset", default="tiny-llama")
    parser.add_argument("--tokens", action="append", default=[],
                        help="comma-separated token ids (one per request)")
    parser.add_argument("--prompt", action="append", default=[],
                        help="text prompt, encoded with --byte-tokenizer")
    parser.add_argument("--byte-tokenizer", action="store_true",
                        help="encode --prompt as UTF-8 bytes (vocab >= 256)")
    parser.add_argument("--max-new-tokens", type=int, default=None)
    parser.add_argument("--eos-id", type=int, default=None)
    parser.add_argument("--stream", action="store_true",
                        help="print tokens incrementally as they decode")
    parser.add_argument("--temperature", type=float, default=None,
                        help="sampling temperature (0 = greedy); sugar for "
                             "inference.temperature")
    parser.add_argument("--top-k", type=int, default=None,
                        help="top-k sampling filter (0 disables)")
    parser.add_argument("--top-p", type=float, default=None,
                        help="nucleus sampling threshold in (0, 1]")
    parser.add_argument("--chunked-prefill", action="store_true",
                        help="bound decode stalls under prompt bursts: "
                             "split prompt prefill into page-aligned "
                             "chunks mixed into each decode step; sugar "
                             "for inference.chunked_prefill=true (budget "
                             "via inference.prefill_chunk_tokens=N)")
    parser.add_argument("--speculate", type=int, default=None, metavar="N",
                        help="speculative decoding: draft up to N tokens "
                             "per step by prompt-lookup (n-gram) and "
                             "verify them in one dispatch; greedy output "
                             "is byte-identical, sampled output keeps its "
                             "distribution; sugar for "
                             "inference.speculative=true + "
                             "inference.speculate_tokens=N")
    parser.add_argument("--regex", default=None, metavar="PATTERN",
                        help="grammar-constrained decoding: every request "
                             "emits only tokens the regex's FSM admits "
                             "(byte-level patterns over the byte "
                             "tokenizer); forced single-choice runs ride "
                             "the verify path as free drafts; sugar for "
                             "inference.constrained=true + a per-request "
                             "ConstraintSpec (mutually exclusive with "
                             "--json-schema)")
    parser.add_argument("--json-schema", default=None, metavar="FILE",
                        help="grammar-constrained decoding from a JSON "
                             "Schema file: the schema compiles to a "
                             "regex, then to the same token-level FSM "
                             "(mutually exclusive with --regex)")
    parser.add_argument("--spec-tree", type=int, default=None, metavar="W",
                        help="token-TREE speculation: draft up to W "
                             "distinct n-gram continuations per step and "
                             "verify the whole branch tree in one "
                             "dispatch (requires --speculate); sugar for "
                             "inference.spec_tree_width=W")
    parser.add_argument("--trace", metavar="PATH", default=None,
                        help="export a Chrome trace-event JSON of the "
                             "serve to PATH (request-lifecycle spans + "
                             "per-dispatch timing; load in Perfetto); "
                             "sugar for inference.trace=true + "
                             "inference.trace_path=PATH. With --replicas "
                             "N, PATH is the MERGED fleet timeline "
                             "(router + every replica on a shared clock) "
                             "and each replica also exports its own "
                             "trace.replica-k.json alongside")
    parser.add_argument("--replicas", type=int, default=None, metavar="N",
                        help="multi-replica serving: run N engine "
                             "replicas behind the health-checked router "
                             "(prefix-affinity placement, circuit-break "
                             "failover); sugar for router.replicas=N")
    parser.add_argument("--flight-dir", metavar="DIR", default=None,
                        help="flight-recorder postmortem dumps: on a "
                             "degradation trigger (watchdog stall, step "
                             "faults, NaN quarantine, spec auto-disable) "
                             "write the fault-adjacent span window to "
                             "DIR; sugar for inference.flight_dir=DIR "
                             "(render with tools/obs_report.py)")
    parser.add_argument(
        "overrides", nargs="*", help="dotted config overrides"
    )
    args = parser.parse_args(argv)

    import jax

    from orion_tpu.ckpt import CheckpointManager
    from orion_tpu.config import get_config
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models import init_params
    from orion_tpu.runtime import initialize

    # Same contract as engine.submit's per-request validation — the CLI
    # must not smuggle out-of-range values in through config overrides.
    if args.temperature is not None and args.temperature < 0.0:
        raise SystemExit(f"--temperature must be >= 0, got {args.temperature}")
    if args.top_k is not None and args.top_k < 0:
        raise SystemExit(f"--top-k must be >= 0, got {args.top_k}")
    if args.top_p is not None and not 0.0 < args.top_p <= 1.0:
        raise SystemExit(f"--top-p must be in (0, 1], got {args.top_p}")
    overrides = list(args.overrides)
    for flag, key in ((args.temperature, "inference.temperature"),
                      (args.top_k, "inference.top_k"),
                      (args.top_p, "inference.top_p")):
        if flag is not None:
            overrides.append(f"{key}={flag}")
    if args.chunked_prefill:
        overrides.append("inference.chunked_prefill=true")
    if args.speculate is not None:
        if args.speculate < 1:
            raise SystemExit(f"--speculate must be >= 1, got {args.speculate}")
        overrides.append("inference.speculative=true")
        overrides.append(f"inference.speculate_tokens={args.speculate}")
    if args.spec_tree is not None:
        if args.speculate is None:
            raise SystemExit("--spec-tree requires --speculate N")
        if args.spec_tree < 1:
            raise SystemExit(
                f"--spec-tree must be >= 1, got {args.spec_tree}"
            )
        overrides.append(f"inference.spec_tree_width={args.spec_tree}")
    if args.trace is not None:
        overrides.append("inference.trace=true")
        overrides.append(f"inference.trace_path={args.trace}")
    if args.flight_dir is not None:
        overrides.append(f"inference.flight_dir={args.flight_dir}")
    if args.replicas is not None:
        if args.replicas < 1:
            raise SystemExit(f"--replicas must be >= 1, got {args.replicas}")
        overrides.append(f"router.replicas={args.replicas}")
    constraint = None
    if args.regex is not None and args.json_schema is not None:
        raise SystemExit(
            "--regex and --json-schema are mutually exclusive (one "
            "grammar per request)"
        )
    if args.regex is not None or args.json_schema is not None:
        from orion_tpu.constrain import ConstraintError, ConstraintSpec, \
            compile_regex

        try:
            if args.regex is not None:
                constraint = ConstraintSpec(regex=args.regex)
            else:
                try:
                    with open(args.json_schema, encoding="utf-8") as f:
                        schema_text = f.read()
                except OSError as e:
                    raise SystemExit(
                        f"--json-schema {args.json_schema}: {e}"
                    )
                constraint = ConstraintSpec(json_schema=schema_text)
            # Surface malformed patterns/schemas as CLI errors, before
            # the engine builds (the engine would raise the same
            # ConstraintError at submit). pattern() parses the schema
            # frontend; compile_regex parses the regex itself.
            compile_regex(constraint.pattern())
        except ConstraintError as e:
            raise SystemExit(f"invalid constraint: {e}")
        overrides.append("inference.constrained=true")
    cfg = get_config(args.preset, overrides)
    if overrides:
        print("overrides: " + " ".join(overrides), flush=True)
    # What the run actually landed on, up front.
    print("runtime: " + json.dumps(
        dataclasses.asdict(initialize(cfg.runtime))
    ), flush=True)

    prompts: list[list[int]] = []
    for spec in args.tokens:
        prompts.append([int(t) for t in spec.split(",")])
    for text in args.prompt:
        if not args.byte_tokenizer:
            raise SystemExit("--prompt requires --byte-tokenizer")
        if cfg.model.vocab_size < 256:
            raise SystemExit("byte tokenizer needs vocab_size >= 256")
        prompts.append(list(text.encode("utf-8")))
    if not prompts:
        prompts = [[1, 2, 3, 4]]

    # Jitted, so each matrix is drawn, scaled and cast in one fused program
    # instead of op-by-op with full-size temporaries — a 7B-wide stack in
    # bf16 must initialize inside one chip's memory.
    params = jax.jit(partial(init_params, cfg.model))(
        jax.random.key(cfg.train.seed)
    )
    if cfg.checkpoint.directory:
        # Trainer checkpoints hold the full train state; restore through the
        # SHARDED abstract state (NamedShardings attached), so a 70B-class
        # checkpoint reads directly into its mesh layout instead of
        # materializing host-side (a shapes-only eval_shape restore would
        # host-OOM at the sizes this CLI advertises).
        from orion_tpu.train.trainer import abstract_train_state

        restored = CheckpointManager(
            cfg.checkpoint.directory, cfg.checkpoint
        ).restore_latest(abstract_train_state(cfg))
        if restored is not None:
            params = restored[0]["params"]
            print(f"restored checkpoint step {restored[1]}")
            # Drop the rest of the train state (optimizer moments are 2x
            # the params) before the engine possibly quantizes.
            del restored

    from orion_tpu.runtime.fault import PreemptionHandler

    if cfg.router.replicas > 1:
        # Multi-replica serving (README "Scale-out serving"): the router
        # mirrors the engine's scheduler face — submit_request/step/
        # has_work/drain/close — so the loop below drives either.
        from orion_tpu.infer import Router

        engine = Router(cfg, params, eos_id=args.eos_id)
    else:
        engine = InferenceEngine(cfg, params, eos_id=args.eos_id)
    # The engine owns (a possibly int8-quantized copy of) the params from
    # here; keeping this reference alive would pin the full-precision
    # masters in device memory for the whole serving loop.
    del params
    # Graceful shutdown (README "Robustness"): SIGTERM only flips a flag;
    # at the next step boundary the engine stops admission, sheds the wait
    # queue with typed outcomes, FINISHES every live request — donating
    # their pages to the prefix cache exactly as normal completion does —
    # and this process leaves through the normal exit path (non-zero if a
    # queued request was shed) instead of dying mid-dispatch.
    with PreemptionHandler() as handler:
        reqs = [
            engine.submit_request(
                p, args.max_new_tokens, constraint=constraint
            )
            for p in prompts
        ]
        emitted = [0] * len(reqs)
        while engine.has_work():
            if handler.preempted:
                print("SIGTERM: draining (admission stopped, live "
                      "requests finishing)", flush=True)
                engine.drain()
                break
            engine.step()
            if args.stream:
                for req, n in zip(reqs, emitted):
                    if len(req.generated) > n:
                        print(f"request {req.rid} += {req.generated[n:]}",
                              flush=True)
                # High-water mark, never reset: a router failover swaps
                # the attempt and generated shrinks while the survivor
                # regenerates — already-printed tokens must not reprint.
                emitted = [
                    max(n, len(r.generated))
                    for n, r in zip(emitted, reqs)
                ]
    engine.close()
    if args.trace:
        # Re-export explicitly so the success message reflects THIS run
        # (a stale file from a previous serve must not mask a failure).
        # On a Router this is the MERGED fleet timeline: router + every
        # replica ring on a shared clock (per-replica namespaced traces
        # were written by each live replica's close() above).
        try:
            n = engine.export_trace(args.trace)
            fleet = " (merged fleet timeline)" if (
                cfg.router.replicas > 1
            ) else ""
            print(f"trace written to {args.trace}{fleet}: {n} events "
                  f"(open in Perfetto, or run "
                  f"tools/obs_report.py {args.trace})")
        except OSError as e:
            print(f"trace export to {args.trace} failed: {e}",
                  file=sys.stderr)
    for i, (prompt, req) in enumerate(zip(prompts, reqs)):
        out = req.generated
        tag = "" if req.outcome == "completed" else f" [{req.outcome}]"
        print(f"request {i}: prompt={prompt} -> generated={out}{tag}")
        if args.byte_tokenizer:
            print(f"  text: {bytes(t % 256 for t in out).decode('utf-8', 'replace')!r}")
    # A request that ended in a typed error, a shed or an expiry is a
    # failed run, not a tag on a green one.
    failed = [
        (i, r.outcome) for i, r in enumerate(reqs) if r.outcome != "completed"
    ]
    if failed:
        print(f"FAILED: {len(failed)} of {len(reqs)} requests did not "
              f"complete: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
