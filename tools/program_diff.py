#!/usr/bin/env python3
"""Are two trees' programs the same programs? Compiles, for a v5e that is
described and not attached (no chip, no chip time), what the benchmark's
cells run, writes each program's text with everything that names a source
line taken out, and compares two such dumps:

    python tools/program_diff.py dump <tree> <out-dir>   # once per tree
    python tools/program_diff.py compare <dir-a> <dir-b>

``dump`` writes, per serving cell of BENCHMARK.json, the optimized HLO of
``decode_window`` (of ``denoise_block`` for a model that generates by blocks)
and of the cell's widest and narrowest prefill shape, and
for each training cell (the four-chip one on the described 2x2) the lowered
and the optimized text of the donated train step. Taken out: op metadata
(scope paths with it), the module's name, ``loc(...)``, the compiled text's tables
of files / functions / stack frames, and the debug locations inside every
Mosaic kernel body (each body is replaced by a hash of its text without
them; a call site's stack is in there).

``compare`` prints, per file, ``same`` (byte-equal), ``same-renumbered``
(equal once every %name is renamed by its order of first appearance: the
same instructions, operands, shapes and layouts in the same scheduled
order, under other numbers from the compiler's passes) or ``DIFFERENT``
with the count of differing lines, and exits non-zero on the last.

A refactor that claims equal programs shows it here before the chip does
(PERF.md §6, PR 30). The instruction names in the compiled text are the
ledger's (``breakdown.device_ops``: ``rope.20``, ``fusion.372`` ...), so a
dump also says what an operation the ledger names IS (PR 34). One process
at a time: libtpu holds a lock. Nothing here is a measurement."""

from __future__ import annotations

import base64
import hashlib
import importlib
import os
import pathlib
import pkgutil
import re
import sys
from functools import partial


def _jitted(stem, fn, **kw):
    """What ``infer/executor.jit_program`` jits for ``stem``: the tree's own
    named partial where it has one (the module is then ``jit_orion_<stem>``),
    the bare ``partial`` of a tree from before the programs had names."""
    from orion_tpu.infer import executor

    named = getattr(executor, "named_program", None)
    return named(fn, stem, **kw) if named else partial(fn, **kw)


def _strip(text: str) -> str:
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True      # stable_mosaic
    seen: dict[str, str] = {}

    def body(m):
        b64 = m.group(2)
        if b64 not in seen:
            with ctx:
                asm = ir.Module.parse(base64.b64decode(b64)).operation.get_asm(
                    enable_debug_info=False)
            seen[b64] = ("MOSAIC:" + hashlib.sha256(asm.encode()).hexdigest()[:16]
                         + f":{len(asm)}")
        return m.group(1) + seen[b64]

    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    # The module's name (``HloModule jit_<name>``, ``module @jit_<name>``,
    # ``jit(<name>)`` in a location): a name is no instruction.
    text = re.sub(r"^(HloModule |module @)jit_\w+", r"\1jit_", text, flags=re.M)
    text = re.sub(r"jit\(\w+\)", "jit()", text)
    text = re.sub(r" loc\(.*?\)$|^#loc.*$|loc\(#loc\d+\)", "", text, flags=re.M)
    text = re.sub(r'(body\\22: \\22|"body": ?")([A-Za-z0-9+/=]+)', body, text)
    return re.sub(r"^FileNames\n.*?(?=^\S*%|^ENTRY|^HloModule)", "", text,
                  flags=re.M | re.S, count=1)


def dump(tree: str, out: str) -> int:
    root, outp = os.path.abspath(tree), pathlib.Path(out).resolve()
    os.chdir(root)
    sys.path.insert(0, root)
    outp.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import orion_tpu
    import orion_tpu.ops.pallas as pallas_pkg

    assert os.path.abspath(orion_tpu.__file__).startswith(root), (
        f"orion_tpu came from {orion_tpu.__file__}, not from {root}")
    # The default backend is the CPU here, where the program refuses compiled
    # kernels; these programs are compiled for the described chip.
    for m in pkgutil.iter_modules(pallas_pkg.__path__):
        mod = importlib.import_module(f"orion_tpu.ops.pallas.{m.name}")
        if hasattr(mod, "resolve_interpret"):
            mod.resolve_interpret = bool

    from benchmarks.harness.cell import BENCH, Cell, load_benchmark
    from benchmarks.traffic.generator import load_mix

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def save(name, text):
        (outp / name).write_text(_strip(text))
        print("wrote", name, flush=True)

    def abstract(tree_):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree_)

    def serve_cell(name):
        from benchmarks.kinds import serve
        from benchmarks.reference import weights
        from orion_tpu.infer import runner
        from orion_tpu.infer.kv_cache import init_cache, pages_per_seq

        cell = Cell.find(name)
        cfg = cell.program_config()
        mcfg, icfg = cfg.model, cfg.inference
        spec = cell.reference().param_spec(cell.config)
        params = abstract(jax.eval_shape(lambda: weights._draw(
            spec, mcfg.n_layers, jnp.dtype(mcfg.param_dtype),
            jax.random.key(0))))
        cache = abstract(jax.eval_shape(lambda: init_cache(mcfg, icfg)))
        i32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32, sharding=one_chip)
        B, W = icfg.max_batch_size, icfg.decode_window
        # The programs as the engine launches them (ISSUE 40: the window's
        # keys derived inside it from one key, the first tokens picked and
        # scattered inside the prefill); a tree from before that takes W
        # keys and seven arguments.
        import inspect

        chained = "window" in inspect.signature(
            runner.decode_window).parameters
        key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        u32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.uint32,
                                               sharding=one_chip)
        if getattr(mcfg, "block_length", 0):
            # A model that generates by blocks runs the block program where
            # the others run the decode window.
            L = mcfg.block_length
            block = jax.jit(_jitted(
                "denoise", runner.denoise_block, cfg=mcfg,
                max_seq_len=icfg.max_seq_len, mesh=None, nan_guard=False,
                steps=icfg.denoising_steps, remasking=icfg.remasking,
                threshold=icfg.confidence_threshold,
                temperature=icfg.temperature, top_k=icfg.top_k,
                top_p=icfg.top_p), donate_argnums=(1,))
            save(f"{name}.denoise_block.compiled.txt", block.lower(
                params, cache, i32(B, L), i32(B), i32(B),
                i32(B, pages_per_seq(icfg)),
                jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip),
                u32(*key.shape)).compile().as_text())
        else:
            decode = jax.jit(_jitted(
                "decode", runner.decode_window, cfg=mcfg,
                max_seq_len=icfg.max_seq_len, mesh=None, nan_guard=False,
                temperature=icfg.temperature, top_k=icfg.top_k,
                top_p=icfg.top_p, **({"window": W} if chained else {})),
                donate_argnums=(1,))
            keys = u32(*key.shape) if chained else jax.ShapeDtypeStruct(
                (W,), jax.random.key(0).dtype, sharding=one_chip)
            save(f"{name}.decode_window.compiled.txt", decode.lower(
                params, cache, i32(B), i32(B), i32(B, pages_per_seq(icfg)),
                jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip), keys,
            ).compile().as_text())
        # A kind that closes its own set of shapes says so (``serve_chunks``:
        # one row of a chunk and of its tail buckets, each resuming over the
        # slot's whole page-table row).
        todo = getattr(cell.kind_module(), "cell_prefill_shapes",
                       serve.cell_prefill_shapes)(cell, icfg)
        resumed = (pages_per_seq(icfg)
                   if getattr(mcfg, "resumes_prefill", False) else 0)
        size = lambda s: (s[0] * s[1], s[0])
        prefill = jax.jit(_jitted(
            "prefill", runner.prefill_step, cfg=mcfg, mesh=None,
            paged_prefill=icfg.paged_prefill), donate_argnums=(1,))
        for nb, s_pad in sorted({max(todo, key=size), min(todo, key=size)}):
            extra = (i32(nb) if mcfg.is_retention else None, i32(nb),
                     i32(B), u32(*key.shape)) if chained else ()
            save(f"{name}.prefill_{nb}x{s_pad}.compiled.txt", prefill.lower(
                params, cache, i32(nb, s_pad), i32(nb),
                i32(nb, s_pad // icfg.page_size), i32(nb), i32(nb, resumed),
                *extra,
            ).compile().as_text())

    def train_cell(name):
        from orion_tpu.parallel.sharding import batch_sharding
        from orion_tpu.runtime.mesh import build_mesh
        from orion_tpu.train.optimizer import make_schedule
        from orion_tpu.train.trainer import (
            abstract_train_state, make_train_step, state_shardings)

        cfg = Cell.find(name).program_config()
        mesh = build_mesh(
            cfg.parallel, devices=topo.devices[:cfg.parallel.num_devices])
        state = abstract_train_state(cfg, state_shardings(cfg, mesh))
        step = make_train_step(
            cfg, make_schedule(cfg.optimizer, cfg.train.num_steps), mesh)
        tok = jax.ShapeDtypeStruct(
            (cfg.data.batch_size, cfg.data.seq_len), jnp.int32,
            sharding=batch_sharding(mesh))
        lowered = jax.jit(step, donate_argnums=(0,)).lower(
            state, {"inputs": tok, "targets": tok})
        save(f"{name}.train_step.lowered.txt", lowered.as_text())
        save(f"{name}.train_step.compiled.txt", lowered.compile().as_text())

    for w in load_benchmark()["workloads"]:
        # ``serve`` and the kinds that are ``serve`` under another tap
        # (``serve_rows``: the same engine, the same programs)
        if load_mix(w["traffic"], BENCH / "traffic")["kind"].startswith(
                "serve"):
            serve_cell(w["name"])
        else:
            train_cell(w["name"])
    return 0


def _renumber(text: str) -> str:
    names: dict[str, str] = {}
    return re.sub(r"%[A-Za-z_][\w.\-]*",
                  lambda m: names.setdefault(m.group(0), f"%n{len(names)}"),
                  text)


def compare(a: str, b: str) -> int:
    worst = 0
    pa, pb = pathlib.Path(a), pathlib.Path(b)
    for fa in sorted(pa.glob("*.txt")):
        fb = pb / fa.name
        if not fb.exists():
            print(f"MISSING          {fa.name}")
            worst = 1
            continue
        ta, tb = fa.read_text(), fb.read_text()
        if ta == tb:
            print(f"same             {fa.name}")
        elif _renumber(ta) == _renumber(tb):
            print(f"same-renumbered  {fa.name}")
        else:
            la, lb = _renumber(ta).splitlines(), _renumber(tb).splitlines()
            n = sum(x != y for x, y in zip(la, lb)) + abs(len(la) - len(lb))
            print(f"DIFFERENT        {fa.name}: {n} of {len(la)} lines")
            worst = 1
    return worst


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] in ("dump", "compare"):
        sys.exit({"dump": dump, "compare": compare}[sys.argv[1]](*sys.argv[2:]))
    sys.exit(__doc__)
