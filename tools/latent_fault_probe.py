#!/usr/bin/env python3
"""What of the latent path does a serving cell's output check HOLD? Plant a
fault in the program and see.

    python tools/latent_fault_probe.py --workload glm-4.7-flash.serve-longctx --seed N

Builds the cell's engine as the benchmark does and runs the benchmark's own
comparison (``benchmarks/kinds/serve.py``: ``probe_numbers`` and ``decide``,
unedited) on it as it is and once a fault, each on an engine of its own (a
fault is planted in traced code, so its programs are compiled anew):

  rope   the decode window's absorbed query loses its rotary part: a decode
         that drops ``q_rope . k_pe`` (prefill, in the expanded form, is
         left whole);
  bias   the router's gates are taken from ``s + b``, the selection bias in
         them, in prefill and decode alike.

Prints each pass's per-position errors by probe, the judged numbers beside
their limits and ``correct``; the last line says which faults the check saw
(exit 0 either way: this reports, it does not judge). ``tests/test_glm.py``
plants the same two on ``tiny-glm`` with peaked attention, where both are
seen; under the benchmark's N(0, 0.02) draw a score's spread is about 0.3 and
attention is close to a mean of the values, so what the chip's comparison
sees of ``rope`` is this tool's to say (PERF.md section 6, PR 36). On the CPU
add ``--allow-cpu`` (a tiny configuration under the tests' root; no device
number is printed anywhere here)."""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import argparse
import contextlib
import gc
import os

import numpy as np

ROOT = _pathlib.Path(__file__).resolve().parent.parent
FAULTS = ("none", "rope", "bias")


@contextlib.contextmanager
def planted(fault: str):
    """The program's own functions with ``fault`` in them, while an engine
    traces its programs."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.infer import runner
    from orion_tpu.models import moe

    absorb, topk = runner.latent_absorb, moe._router_topk

    def without_rope(q, wkv_b, cfg):
        return absorb(q, wkv_b, cfg).at[..., cfg.kv_lora_rank:].set(0)

    def gates_hold_the_bias(x, router_w, cfg, bias=None):
        probs, _, idx = topk(x, router_w, cfg, bias)
        s = jax.nn.sigmoid(jnp.einsum(
            "bsd,de->bse", x, router_w,
            preferred_element_type=jnp.float32)) + bias.astype(jnp.float32)
        g = jnp.take_along_axis(s, idx, axis=-1)
        return probs, cfg.router_scale * g / g.sum(-1, keepdims=True), idx

    if fault == "rope":
        runner.latent_absorb = without_rope
    elif fault == "bias":
        moe._router_topk = gates_hold_the_bias
    try:
        yield
    finally:
        runner.latent_absorb, moe._router_topk = absorb, topk


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
            ROOT / ".jax_compile_cache")

    from benchmarks.harness import device as device_lib
    from benchmarks.harness.cell import Cell
    from benchmarks.kinds import serve

    cell = Cell.find(args.workload, root=_pathlib.Path(args.root))
    dev = device_lib.require(cell.chips, allow_cpu=args.allow_cpu)
    print(f"device: {dev.platform} {dev.kind!r}", flush=True)
    verdicts = {}
    for fault in FAULTS:
        with planted(fault):
            _, engine = serve.build_engine(cell, args.seed)
            numbers = serve.probe_numbers(
                engine, cell.reference(), cell.config, cell.mix, args.seed)
        # The engine and its executor hold each other: drop the buffers by
        # hand, or the next engine's weights do not fit beside them.
        engine.close()
        engine.params = engine.cache = None
        del engine
        gc.collect()
        print(f"-- fault planted: {fault}", flush=True)
        per = len(numbers["err"]) // len(cell.mix["probe_prompts"])
        errs = np.asarray(numbers["err"]).reshape(-1, per)
        for n, row in zip(cell.mix["probe_prompts"], errs):
            print(f"probe {n}: median {np.median(row):.4f} max "
                  f"{row.max():.4f} positions "
                  + " ".join(f"{e:.3f}" for e in row), flush=True)
        ok, checks = serve.decide(numbers, cell.config["correct"])
        for name, value, limit in checks:
            print(f"check: {name} = {value!r} (limit {limit!r})")
        print(f"correct: {ok}", flush=True)
        verdicts[fault] = ok
    seen = [f for f in FAULTS[1:] if not verdicts[f]]
    print(f"verdicts {verdicts}: the check sees {seen or 'neither fault'}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
