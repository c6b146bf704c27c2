"""Time the grouped expert matmul alone at the benchmark cells' own call
shapes on the chip: the Pallas megablox ``gmm`` over row tiles 32 / 64 / 128
/ 256 / 512 and the n-tiles that divide n, beside XLA's ``lax.ragged_dot``.
The rule ``orion_tpu/ops/grouped_matmul._tiles(m, G, k, n)`` cites this
script's table (PERF.md section 6: PR 26 at Mixtral's widths, PR 55 at every
cell's).

    chiprun -- python tools/grouped_matmul_sweep.py [--only sdar,mixtral,...]
    python tools/grouped_matmul_sweep.py --shapes     # the calls, no chip
    python tools/grouped_matmul_sweep.py --tables     # the kept lines' tables

A cell's calls come from its two files: G experts held of W the router
chooses among, top-k, (k, n) = (D, F) for ``w_in`` / ``w_gate`` and (F, D)
for ``w_out``, m = the sorted rows ``moe_mlp_grouped`` hands the kernel for
each shape of ``serve.cell_prefill_shapes`` (``held_row_bound`` where the
bounded form runs) and, for a model that generates by blocks, for a block
forward of every slot. Of the prefill shapes it keeps up to four under
``ROW_TILE`` rows a group and the first at or over it. Rows are
REAL for the share of the router's width the chip holds (the rest sort behind
the last group and are never visited), drawn over the groups evenly (a
multinomial) and skewed (a tenth of the experts take half the rows: a trained
router is not even).

Prints one JSON line per (cell, matrix, m, tiling, draw) and keeps them in
``chiprun_out/grouped_matmul_sweep.<cells>.jsonl`` (a call's outputs are
merged into ``chiprun_out/`` file by file: one name would be overwritten by
the next call); at the end, per (cell, matrix, m),
a table of ms a call by (tm, tn) with the tree's own tiles marked, and each
call's best against the tree's. ``floor`` is the bytes' floor: the weights of
the groups that hold rows, the real rows in and out, over 819 GB/s; ``peak``
the real rows' products over 197 TFLOP/s. Timing needs a TPU."""

from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

ROW_TILES = (32, 64, 128, 256, 512)
OUT = "chiprun_out/grouped_matmul_sweep.{}.jsonl"    # a file a set of cells
HBM_BYTES_PER_S, PEAK_FLOPS = 819e9, 197e12      # TPU v5e (PERF.md section 3)


def cell_calls(short: str) -> list[dict]:
    """The grouped matmul calls of the cell whose name starts with ``short``."""
    from benchmarks.harness.cell import Cell, load_benchmark
    from benchmarks.kinds import serve
    from orion_tpu.models import moe
    from orion_tpu.ops.grouped_matmul import ROW_TILE

    name = next(w["name"] for w in load_benchmark()["workloads"]
                if w["name"].startswith(short))
    cell = Cell.find(name)
    cfg = cell.program_config()
    mc, ic = cfg.model, cfg.inference
    G, W, top = mc.n_experts, mc.resolved_router_width, mc.n_experts_per_token
    D, F = mc.d_model, mc.resolved_moe_d_ff

    def rows(tokens):        # sorted rows handed over, and how many are real
        if moe.bounds_held_rows(mc, tokens):
            m = moe.held_row_bound(mc, tokens)
            return m, m // 2
        return top * tokens, top * tokens * G // W

    ms = {}
    for nb, s in serve.cell_prefill_shapes(cell, ic):
        ms.setdefault(rows(nb * s), f"prefill {nb}x{s}")
    order = sorted(ms)
    small = [r for r in order if r[0] < G * ROW_TILE]
    if len(small) > 4:
        small = [small[round(i * (len(small) - 1) / 3)] for i in range(4)]
    keep = small + [r for r in order if r[0] >= G * ROW_TILE][:1]
    picked = [(r, ms[r]) for r in keep]
    if getattr(mc, "block_length", 0):
        B, L = ic.max_batch_size, mc.block_length
        picked.insert(0, (rows(B * L), f"block forward {B}x{L}"))
    return [{"cell": short, "matrix": mat, "G": G, "k": k, "n": n, "m": m,
             "real": real, "what": what}
            for mat, k, n in (("w_in", D, F), ("w_out", F, D))
            for (m, real), what in picked]


def n_tiles(tm: int, tk: int, n: int, tree_tn: int) -> list[int]:
    """The tree's n-tile and the two widest multiples of 128 that divide
    ``n`` and fit the scoped VMEM beside ``(tm, tk)``."""
    from orion_tpu.ops.grouped_matmul import VMEM_BYTES, tile_vmem_bytes

    fit = [t for t in range(n, 127, -128)
           if n % t == 0 and tile_vmem_bytes(tm, tk, t) <= VMEM_BYTES]
    return sorted({tree_tn, *fit[:2]}, reverse=True)


def draws(rng, G: int, real: int) -> dict:
    hot = rng.choice(G, -(-G // 10), replace=False)
    p = [0.5 / (G - len(hot))] * G
    for g in hot:
        p[g] = 0.5 / len(hot)
    return {"even": rng.multinomial(real, [1 / G] * G),
            "skew": rng.multinomial(real, p)}


def timed(fn, *args, iters: int = 20, repeats: int = 3) -> float:
    """Seconds a call, device bound: ``iters`` calls in flight, one wait."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def sweep(calls: list[dict], sink) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from orion_tpu.ops.grouped_matmul import _tiles

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    rng = np.random.default_rng(0)
    lines = []

    def emit(row):
        text = json.dumps(row)
        print(text, flush=True)
        sink.write(text + "\n")
        sink.flush()
        lines.append(row)

    ragged = jax.jit(jax.lax.ragged_dot)
    rhs = jnp.zeros((0, 0, 0))
    for c in calls:
        G, k, n, m, real = c["G"], c["k"], c["n"], c["m"], c["real"]
        if rhs.shape != (G, k, n):        # one weight bank on the device
            rhs = jax.random.normal(
                jax.random.key(1), (G, k, n), jnp.bfloat16)
        lhs = jax.random.normal(jax.random.key(2), (m, k), jnp.bfloat16)
        sizes = {d: jnp.asarray(g, jnp.int32)
                 for d, g in draws(rng, G, real).items()}
        tree = _tiles(m, G, k, n)

        def line(impl, tiling, draw, sec):
            held = int((sizes[draw] > 0).sum())
            floor = (held * k * n + real * (k + n)) * 2 / HBM_BYTES_PER_S
            emit({**c, "impl": impl, "tiling": tiling, "draw": draw,
                  "tree": tiling == list(tree), "ms": round(1e3 * sec, 4),
                  "floor_pct": round(100 * floor / sec, 1),
                  "peak_pct": round(
                      100 * 2.0 * real * k * n / PEAK_FLOPS / sec, 1),
                  "device": dev.device_kind})

        for draw, g in sizes.items():
            line("ragged_dot", None, draw, timed(ragged, lhs, rhs, g))
        for tm in ROW_TILES:
            a = jnp.pad(lhs, ((0, -m % tm), (0, 0)))
            for tn in n_tiles(tm, tree[1], n, tree[2]):
                tiling = (tm, tree[1], tn)
                f = jax.jit(lambda a, b, g, t=tiling: gmm(
                    a, b, g, preferred_element_type=jnp.bfloat16, tiling=t))
                for draw, g in sizes.items():
                    try:
                        sec = timed(f, a, rhs, g)
                    except Exception as e:       # a tiling Mosaic refuses
                        emit({**c, "impl": "gmm", "tiling": list(tiling),
                              "error": str(e).splitlines()[0][:160]})
                        break
                    line("gmm", list(tiling), draw, sec)
    return lines


def tables(lines: list[dict]) -> None:
    """Per call: ms by (tm, tn), even / skewed, the tree's tiles starred."""
    calls = {}
    for r in lines:
        if "ms" in r:
            calls.setdefault(
                (r["cell"], r["matrix"], r["m"], r["what"]), []).append(r)
    for (cell, mat, m, what), rs in calls.items():
        c = rs[0]
        print(f"\n{cell} {mat} [{c['k']}, {c['n']}] G {c['G']} m {m} "
              f"({m // c['G']} rows a group, {c['real']} real; {what}): "
              f"ms a call, even / skewed (share of the bytes' floor, even)")
        cell_of = {}
        for r in rs:
            key = (tuple(r["tiling"]) if r["tiling"] else None)
            cell_of.setdefault(key, {})[r["draw"]] = r
        rd = cell_of.pop(None)
        print(f"  ragged_dot {rd['even']['ms']:.3f} / {rd['skew']['ms']:.3f}")
        for tiling, d in sorted(cell_of.items()):
            e, s = d["even"], d.get("skew", d["even"])
            print(f"  {'*' if e['tree'] else ' '} tm {tiling[0]:>3} tk "
                  f"{tiling[1]} tn {tiling[2]:>4}: {e['ms']:.3f} / "
                  f"{s['ms']:.3f}  ({e['floor_pct']} % floor, "
                  f"{e['peak_pct']} % peak)")
        mean = lambda d: (d["even"]["ms"] + d.get("skew", d["even"])["ms"]) / 2
        best = min(cell_of, key=lambda t: mean(cell_of[t]))
        tree = [t for t, d in cell_of.items() if d["even"]["tree"]]
        if tree:
            print(f"  best {best}: {mean(cell_of[best]):.3f} ms against the "
                  f"tree's {tree[0]} {mean(cell_of[tree[0]]):.3f} = "
                  f"{mean(cell_of[best]) / mean(cell_of[tree[0]]):.3f}x")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="sdar,laguna,ling,mixtral,glm,mimo",
                    help="cells, by the start of their names")
    ap.add_argument("--shapes", action="store_true",
                    help="print the calls and the tree's tiles; no chip")
    ap.add_argument("--tables", action="store_true",
                    help="print the tables of the lines kept in "
                    + OUT.format("*"))
    args = ap.parse_args()
    if args.tables:
        lines = [json.loads(text) for kept in sorted(glob.glob(OUT.format("*")))
                 for text in open(kept)]
        tables([r for r in lines if r["cell"] in args.only.split(",")])
        return 0
    calls = [c for short in args.only.split(",") for c in cell_calls(short)]
    if args.shapes:
        from orion_tpu.ops.grouped_matmul import _tiles

        for c in calls:
            print(json.dumps({**c, "rows_a_group": c["m"] // c["G"],
                              "tiles": _tiles(c["m"], c["G"], c["k"], c["n"])}))
        return 0
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT.format(args.only.replace(",", "-")), "w") as sink:
        tables(sweep(calls, sink))
    return 0


if __name__ == "__main__":
    sys.exit(main())
