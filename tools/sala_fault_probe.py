#!/usr/bin/env python3
"""What of block selection and of the lightning state does a serving cell's
output check HOLD? Plant a fault in the program and see.

    python tools/sala_fault_probe.py --workload minicpm-sala.serve-longdoc-64k --seed N

Builds the cell's engine as the benchmark does and runs the benchmark's own
comparison (``benchmarks/kinds/serve_chunks.py``: ``probe_numbers`` and
``decide``) on it as it is and once a fault, each on an engine of its own (a
fault is planted in traced code, so its programs are compiled anew):

  no_window  a selection without the forced local window: block 0 and the
             query's own block alone are forced, the other 62 are chosen by
             score (prefill and decode alike, so the window link holds; the
             reference's selection keeps the window, so the program's free
             choices score under the reference's cutoff: the regret sees it
             where logits under seeded weights may not);
  no_carry   the lightning state is not carried across a chunk boundary:
             every chunk of a prompt starts its lightning layers from zeros
             (a prompt of one chunk is whole; the decode window is whole);
  per_head   the selection is made on the FIRST head's scores of a K/V
             group, not on the group's sum;
  unpooled   a block's score is its own first kernel's alone (no max over
             the five kernels that overlap it).

``--faults`` names the passes to make (default ``none,no_window,no_carry``).
``--control`` adds the int8 control to the first pass (the reference at that
precision in the program's place, which has to fail).

Prints each pass's per-position errors and regrets by probe, the judged
numbers beside their limits and ``correct``; the last line says which faults
the check saw (exit 0 either way: this reports, it does not judge).
``tests/test_sala.py`` plants all four on ``tiny-sala`` in float32 under
peaked weights. On the CPU add ``--allow-cpu`` (a tiny configuration under
the tests' root; no device number is printed anywhere here)."""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import argparse
import contextlib
import gc
import os

import numpy as np

ROOT = _pathlib.Path(__file__).resolve().parent.parent
FAULTS = ("none", "no_window", "no_carry", "per_head", "unpooled")


@contextlib.contextmanager
def planted(fault: str):
    """The program's own functions with ``fault`` in them, while an engine
    traces its programs."""
    import jax.numpy as jnp

    from orion_tpu.ops import lightning, sparse

    keep = (sparse.forced_blocks, sparse.block_scores,
            lightning.lightning_chunked)

    def own_block_alone(pos, n_blocks, sp):
        b = jnp.arange(n_blocks)
        own = (pos // sp.block)[..., None]
        return (b <= own) & ((b < sp.init_blocks) | (b == own))

    def first_head(q, ck, pos, sp):
        N, K = q.shape[2], ck.shape[2]
        lead = q.reshape(*q.shape[:2], K, N // K, q.shape[-1])[:, :, :, :1]
        return keep[1](lead.reshape(*q.shape[:2], K, q.shape[-1]), ck, pos,
                       sp)

    def own_kernel(q, ck, pos, sp):
        """A block's score from its own FIRST kernel alone: no pooling."""
        kpp = sparse.kernels_per_page(sp)
        B, Q, N, H = q.shape
        K = ck.shape[2]
        z = jnp.einsum("bqkgh,bjkh->bkgqj", q.reshape(B, Q, K, N // K, H),
                       ck, preferred_element_type=jnp.float32) * H ** -0.5
        seen = (sp.stride * jnp.arange(ck.shape[1]) + sp.kernel - 1)[
            None, None] <= pos[:, :, None]
        z = jnp.where(seen[:, None, None], z, -jnp.inf)
        m = z.max(-1, keepdims=True)
        e = jnp.exp(z - jnp.where(jnp.isfinite(m), m, 0.0))
        total = e.sum(-1, keepdims=True)
        r = (e / jnp.where(total == 0.0, 1.0, total)).sum(2)
        return jnp.where(seen[:, None], r, -jnp.inf)[..., ::kpp]

    def zero_state(q, k, v, state=None, lengths=None, **kw):
        return keep[2](q, k, v, None, lengths, **kw)

    if fault == "no_window":
        sparse.forced_blocks = own_block_alone
    elif fault == "per_head":
        sparse.block_scores = first_head
    elif fault == "unpooled":
        sparse.block_scores = own_kernel
    elif fault == "no_carry":
        lightning.lightning_chunked = zero_state
    try:
        yield
    finally:
        (sparse.forced_blocks, sparse.block_scores,
         lightning.lightning_chunked) = keep


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--faults", default="none,no_window,no_carry")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    faults = [f for f in FAULTS if f in args.faults.split(",")]
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
            ROOT / ".jax_compile_cache")

    from benchmarks.harness import device as device_lib
    from benchmarks.harness.cell import Cell

    cell = Cell.find(args.workload, root=_pathlib.Path(args.root))
    kind = cell.kind_module()
    dev = device_lib.require(cell.chips, allow_cpu=args.allow_cpu)
    print(f"device: {dev.platform} {dev.kind!r}", flush=True)
    verdicts = {}
    for fault in faults:
        with planted(fault):
            _, engine = kind.build_engine(cell, args.seed)
            numbers = kind.probe_numbers(
                engine, cell.reference(), cell.config, cell.mix, args.seed,
                control="int8" if args.control and fault == "none" else None)
        if dev.platform != "cpu":
            peak = engine.device.memory_stats().get("peak_bytes_in_use")
            print(f"peak_bytes_in_use after the probes: {peak}", flush=True)
        # The engine and its executor hold each other: drop the buffers by
        # hand, or the next engine's weights do not fit beside them.
        engine.close()
        engine.params = engine.cache = None
        del engine
        gc.collect()
        print(f"-- fault planted: {fault}", flush=True)
        per = len(numbers["err"]) // len(cell.mix["probe_prompts"])
        for name in ("err", "control_err", "regret"):
            if not numbers[name]:
                continue
            rows = np.asarray(numbers[name]).reshape(-1, per)
            for n, row in zip(cell.mix["probe_prompts"], rows):
                print(f"probe {n} {name}: median {np.median(row):.5f} max "
                      f"{row.max():.5f} positions "
                      + " ".join(f"{e:.4f}" for e in row), flush=True)
        print(f"window_kv_rel_err {numbers['window_kv_rel_err']} "
              f"window_token_gap {numbers['window_token_gap']}", flush=True)
        if numbers["control_err"]:
            print("control judged: " + repr(kind.judged(
                numbers, 0.0, errs="control_err")), flush=True)
        ok, checks = kind.decide(numbers, cell.config["correct"])
        for name, value, limit in checks:
            print(f"check: {name} = {value!r} (limit {limit!r})")
        print(f"correct: {ok}", flush=True)
        verdicts[fault] = ok
    seen = [f for f in faults if f != "none" and not verdicts[f]]
    print(f"verdicts {verdicts}: the check sees {seen or 'no fault'}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
