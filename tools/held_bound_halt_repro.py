#!/usr/bin/env python3
"""The halt that keeps ``moe.bounds_held_rows`` at a quarter: Ling's engine
prefill with the bounded grouped dispatch FORCED into its layer scan, under a
watchdog (PR 52, from PR 51's uncommitted repro).

    chiprun --timeout 600 -- python tools/held_bound_halt_repro.py
    python tools/held_bound_halt_repro.py --cpu        # logic check, tiny-ling

Builds the engine of ``ling-3.0-flash.serve-reason-128`` as the benchmark
does, patches the rule (here, in this process: the package has no switch) so
that every layer that holds a share of its experts takes
``moe._bounded_rows`` at ``moe.held_row_bound`` rows a pass (Ling's 128 of
512: a half of k x T, which the rule refuses), and dispatches the engine's
prefill program at each named shape, ``<rows>x<bucket>:<first|all>`` (one
real position a row, the warm-up's block, or every position), twice. A
``faulthandler`` watchdog dumps every thread's stack and exits 1 where a
shape outlasts ``--stall`` seconds (100; a first dispatch compiles for
25-40 s).

What PR 51's builder saw on the v5e (its git-ignored chiprun_out/pr51r/):
with the overflow passes under ``lax.while_loop`` inside the layer scan, the
program at 4x1024:first never returned (repro_engine.err,
force_full_then_first.err); the same with the passes as a ``fori_loop`` of
``cond``s (force_foricond.err); with the bound at every row, one pass in line
and no loop, it ran in 0.15 s (force_noloop.err); the LAYER alone, scanned or
not, ran with the loop (repro_scan.err, repro_static.err). With PR 52's
form (this tree's ``_bounded_rows``) the same: the control returned in 0.14 s
and the forced form did not return in 100 s (PERF.md section 7, "since
PR 52"). ``--no-force`` runs the tree's own rule (the parent's programs for
this cell) as the control. The PR that widens the rule to a quarter or a
half starts here."""

import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import argparse
import faulthandler
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CELL = "ling-3.0-flash.serve-reason-128"
T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[repro {time.monotonic() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def arm(seconds: float) -> None:
    faulthandler.dump_traceback_later(seconds, exit=True, file=sys.stderr)


def build(cpu: bool):
    """(config, engine): the cell's, as the benchmark builds it, or
    tiny-ling's with 4 of its 16 experts held for the logic check."""
    if not cpu:
        from benchmarks.harness.cell import Cell
        from benchmarks.kinds import serve

        return serve.build_engine(Cell.find(CELL), 1991526525)
    import jax

    from orion_tpu.config import get_config
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models.transformer import init_params

    cfg = get_config("tiny-ling", [
        "runtime.platform=cpu", "model.n_experts=4",
        "model.capacity_factor=64"])     # a block of [2, 64] goes grouped
    return cfg, InferenceEngine(
        cfg, init_params(cfg.model, jax.random.key(5)), seed=0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("shapes", nargs="*",
                    help="<rows>x<bucket>:<first|all> (default 4x1024:first)")
    ap.add_argument("--no-force", action="store_true",
                    help="the tree's own rule: the control")
    ap.add_argument("--stall", type=float, default=100.0)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        print(f"FAIL: no TPU backend (default backend is "
              f"{jax.default_backend()!r}); use --cpu for the logic check")
        return 1
    import jax.numpy as jnp

    from orion_tpu.models import moe as moe_lib

    if not args.no_force:
        moe_lib.bounds_held_rows = (
            lambda cfg, tokens: cfg.holds_expert_share)
    arm(3 * args.stall)
    cfg, engine = build(args.cpu)
    jax.block_until_ready(engine.params)
    icfg, m = cfg.inference, cfg.model
    say(f"engine built; {m.n_experts} of {m.resolved_router_width} experts "
        f"held, bounded form {'as the rule says' if args.no_force else 'forced'}")
    for spec in args.shapes or ["2x64:first" if args.cpu else "4x1024:first"]:
        shape, which = spec.split(":")
        nb, s_pad = map(int, shape.split("x"))
        n = 1 if which == "first" else s_pad
        say(f"prefill {spec}: bound {moe_lib.held_row_bound(m, nb * s_pad)} "
            f"of {m.n_experts_per_token * nb * s_pad} rows a pass")
        for turn in ("first", "again"):
            arm(args.stall)
            t = time.monotonic()
            logits, engine.cache = engine._run_dispatch(
                "prefill", "prefill", engine.params, engine.cache,
                jnp.zeros((nb, s_pad), jnp.int32),
                jnp.full((nb,), n, jnp.int32),
                jnp.zeros((nb, s_pad // icfg.page_size), jnp.int32),
                jnp.zeros((nb,), jnp.int32), jnp.zeros((nb, 0), jnp.int32))
            jax.block_until_ready(logits)
            ex = engine._executor
            say(f"prefill {spec} {turn}: {time.monotonic() - t:.3f}s, held "
                f"rows {int(ex.held_rows)}, dispatches past the bound "
                f"{None if ex.held_overflows is None else int(ex.held_overflows)}")
    faulthandler.cancel_dump_traceback_later()
    say("every shape returned")
    return 0


if __name__ == "__main__":
    sys.exit(main())
