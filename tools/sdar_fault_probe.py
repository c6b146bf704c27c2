#!/usr/bin/env python3
"""What of generation by blocks does a serving cell's output check HOLD?
Plant a fault in the program and see.

    python tools/sdar_fault_probe.py --workload sdar-30b-a3b.serve-blocks-1k --seed N

Builds the cell's engine as the benchmark does and runs the benchmark's own
comparison (``benchmarks/kinds/serve_blocks.py``: ``probe_numbers`` and
``decide``) on it as it is and once a fault, each on an engine of its own (a
fault is planted in traced code, so its programs are compiled anew):

  causal    a causal mask INSIDE the block: the block program's queries see
            the new rows at or before their own and not the later ones (the
            W-query kernel's chain path in place of full ancestor words);
  prefill   the prefill's mask one block short: a row sees the blocks before
            its own and not its own (``q_offset = -block``);
  commit    the commit forward left out: the rows the last denoising forward
            wrote (undecided positions fed as the mask token) are kept;
  qk_norm   no norm over the query and key heads;
  shift     a shifted head: the logits read at a row are those of the row
            before it, among the rows the head is given (every position in
            the check's body and the program's first forward, a slot's
            undecided ones after it);
  least     the static rule decides the LEAST confident positions.

``--faults`` names the passes to make (default all seven, ``none`` first);
``--numbers`` prints what each pass's verdict was judged from, a position.
Prints each pass's judged numbers beside their limits and ``correct``; the
last line says which faults the check saw (exit 0 either way: this reports,
it does not judge). On the CPU add ``--allow-cpu`` (a tiny configuration under
the tests' root; no device number is printed here)."""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import argparse
import contextlib
import dataclasses
import gc
import json
import os

ROOT = _pathlib.Path(__file__).resolve().parent.parent
FAULTS = ("none", "causal", "prefill", "commit", "qk_norm", "shift", "least")


@contextlib.contextmanager
def planted(fault: str, cell):
    """The program with ``fault`` in it, while an engine is built and traces
    its programs."""
    import jax.numpy as jnp

    from orion_tpu.infer import executor, runner

    keep = {name: getattr(runner, name) for name in (
        "_block_ctx", "attention", "_block_hidden", "_block_logits",
        "choose_positions")}
    keep_config = cell.program_config
    keep_program = executor.DispatchExecutor.PROGRAM_FNS["denoise"]

    def chain_ctx(cache, seq_lens, page_table, active, max_seq_len, cfg):
        L = cfg.block_length
        return runner._paged_ctx(
            cache, seq_lens, jnp.full(seq_lens.shape, L, jnp.int32),
            page_table, active, L, max_seq_len, cfg, name="block_paged")

    def short_attention(*args, block=0, **kw):
        return keep["attention"](
            *args, block=block, **{**kw, "q_offset": -block})

    state = {"on": False, "calls": 0, "commit": 0}

    def no_commit_program(*args, steps=1, **kw):
        state.update(on=True, calls=0, commit=steps + 1)
        try:
            return keep_program(*args, steps=steps, **kw)
        finally:
            state["on"] = False

    def hidden(params, cache, fed, ctx, cfg, mesh):
        if state["on"]:
            state["calls"] += 1
            # the denoising forwards one by one, then the commit
            if state["calls"] == state["commit"]:
                return None, dict(cache)
        return keep["_block_hidden"](params, cache, fed, ctx, cfg, mesh)

    def shifted(params, x, cfg, mesh):
        x = jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)
        return keep["_block_logits"](params, x, cfg, mesh)

    def least(conf, *rest):
        return keep["choose_positions"](1.0 - conf, *rest)

    if fault == "causal":
        runner._block_ctx = chain_ctx
    elif fault == "prefill":
        runner.attention = short_attention
    elif fault == "commit":
        runner._block_hidden = hidden
        executor.DispatchExecutor.PROGRAM_FNS["denoise"] = no_commit_program
    elif fault == "qk_norm":
        def program_config():
            cfg = keep_config()
            return dataclasses.replace(
                cfg, model=dataclasses.replace(cfg.model, qk_norm=False))
        cell.program_config = program_config
    elif fault == "shift":
        runner._block_logits = shifted
    elif fault == "least":
        runner.choose_positions = least
    try:
        yield
    finally:
        for name, fn in keep.items():
            setattr(runner, name, fn)
        cell.program_config = keep_config
        executor.DispatchExecutor.PROGRAM_FNS["denoise"] = keep_program


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--numbers", action="store_true",
                    help="print every compared position's numbers too")
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
        with planted(fault, cell):
            _, engine = kind.build_engine(cell, args.seed)
            numbers = kind.probe_numbers(
                engine, cell.reference(), cell.config, cell.mix, args.seed)
        # The engine and its executor hold each other: drop the buffers by
        # hand, or the next engine's weights do not fit beside them.
        engine.close()
        engine.params = engine.cache = None
        del engine
        gc.collect()
        print(f"-- fault planted: {fault}", flush=True)
        if args.numbers:
            print("numbers: " + json.dumps({"seed": args.seed, **numbers}))
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
