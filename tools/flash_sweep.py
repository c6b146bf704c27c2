"""Time the three flash attention kernels alone on the chip at the cells'
shapes, with one cost put back at a time (PERF.md section 6, PR 32).

    chiprun -- python tools/flash_sweep.py [--only train,laguna,mixtral,mimo]
        [--kernels fwd,dq,dkv] [--impls tree,b512x512,dead,dead+f32]

Runs on any checkout that has ``ops/pallas/flash_attention.py`` (copy it into
an unpacked parent to time two commits in one call): ``tree`` is the module
as it stands at its default blocks, ``b<q>x<kv>`` the same at other blocks.
On a module with a live-block grid (PR 32 on) the ablations switch one cause
each, by patching the module here, so that it carries no switch of its own:
``dead`` visits every block of a row and skips the dead ones in the body (the
grid step and the DMA stay), ``f32`` feeds float32 inputs (the operands'
dtype is the inputs'), ``nomask`` leaves the mask step out (wrong results;
what a free mask would time). Join them with ``+``.

Shapes (bf16, H 128, K 8): the train cells' [1, 32, 8192] under window 4096,
forward and both backward kernels; Laguna's prefill layers (72 heads under
window 512, 48 heads under none) and Mixtral's (32 heads), rows x length of
the warmed prefill shapes with a padded burst's segment ids (id 0 = padding),
forward only. Each kernel is timed in a program of chained calls, best of
four. Per line: ms a call, the share of 197 TFLOP/s that the matmuls the
kernel runs take over the pairs that attend, blocks full / visited / minimum
(blocks with one attending pair, from the dense mask) for one head, and the
seconds to trace and lower one instance in a fresh cache.

The group ``mimo-window`` (PR 53; ``--only mimo``) is MiMo's window layer:
window 128 under a sink, 64 / 8 heads, keys 192 and values 128 wide, rows x
length 1 x 16384, 2 x 8192 and 8 x 2048 with a ragged burst's segment ids,
forward only. ``b<n>x<n>`` is the plain walk at those blocks, ``band<C>`` the
band form (``_fold_bands``) at chunks of C query rows: where
``flash_attention.BAND_CHUNK`` comes from. ``fwd`` times the kernel alone;
``call`` the whole wrapper from the model's ``[B, S, N, H]`` layout, with the
transposes, the key padding to 256 lanes and the bands it builds (one program
a call, dispatched back to back: a loop would let XLA hoist them). A program
of the call alone takes q in the layout the device keeps a parameter in, and
the band form pays one or two passes more over q for that than the plain walk
(PERF.md §6 PR 53); behind the rotary kernel, as in a prefill program, both
take the same two. So ``fwd`` settles the chunk and a traced prefill the gain.

Prints one JSON line each and keeps them in ``chiprun_out/flash_sweep.jsonl``.
Raises without a TPU."""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

fa = importlib.import_module("orion_tpu.ops.pallas.flash_attention")

K, H = 8, 128
PEAK_FLOPS = 197e12
OUT = "chiprun_out/flash_sweep.jsonl"
TAG = os.environ.get("FLASH_SWEEP_TAG", "")   # names the checkout in a line
# matmuls a kernel runs per (q, k) pair, 2 H flops each
UNITS = {"fwd": 2, "dq": 3, "dkv": 4}
# (group, N, window, [(rows, length)], kernels, reps)
SHAPES = [
    ("train", 32, 4096, [(1, 8192)], ("fwd", "dq", "dkv"), 8),
    ("laguna-window", 72, 512, [(8, 512), (2, 2048), (1, 4096)], ("fwd",), 16),
    ("laguna-full", 48, None, [(8, 512), (2, 2048), (1, 4096)], ("fwd",), 16),
    ("mixtral", 32, None, [(8, 512), (2, 2048), (1, 4096)], ("fwd",), 16),
]
BLOCKS = [(256, 256), (512, 512), (1024, 1024), (512, 1024), (1024, 512)]
ABLATIONS = ("dead", "f32", "nomask")
# MiMo's window layer (PR 53): keys wider than values under a sink, prefill
# buckets of 2048, so it has a walk of its own (``sweep_mimo``).
MIMO = dict(group="mimo-window", N=64, K=8, Hk=192, Hv=128, window=128,
            sizes=[(1, 16384), (2, 8192), (8, 2048)], bucket=2048, reps=8)


def burst_segments(rows: int, length: int, rng, bucket: int = 512) -> np.ndarray:
    """A padded burst: each row's prompt fills between a bucket less and the
    whole length; the rest is id 0."""
    real = rng.integers(max(length - bucket + 1, 64), length + 1, size=rows)
    real[0] = length                       # the row that set the bucket
    return (np.arange(length)[None, :] < real[:, None]).astype(np.int32)


def dense_mask(S: int, window, seg) -> np.ndarray:
    """[rows, S, S] bool: the pairs that attend (padding rows attend none)."""
    d = np.arange(S)[:, None] - np.arange(S)[None, :]
    m = d >= 0
    if window is not None:
        m &= d < window
    if seg is None:
        return m[None]
    return m[None] & (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] > 0)


def min_blocks(mask: np.ndarray, bq: int, bk: int) -> float:
    """Blocks a head of one sequence cannot do without (mean over rows)."""
    R, S, _ = mask.shape
    nq, nk = -(-S // bq), -(-S // bk)
    pad = np.zeros((R, nq * bq, nk * bk), bool)
    pad[:, :S, :S] = mask
    return float(pad.reshape(R, nq, bq, nk, bk).any(axis=(2, 4)).sum() / R)


def _dead_step(st, rng, outer, j, n_inner):
    """``fa._step`` over the whole row: the parent's walk."""
    if not fa._static_range(st):
        return j, True
    lo, cnt = rng(st, outer, n_inner)
    return j, (j >= lo) & (j < lo + cnt)


PATCHES = {
    "dead": {"_step": _dead_step,
             "_steps": lambda st, rng, n_outer, n_inner: n_inner},
    "nomask": {"_block_mask": lambda *a, **k: None},
    "f32": {},
    # the band form (PR 53) at another chunk of query rows
    **{f"band{c}": {"BAND_CHUNK": c} for c in (256, 512, 1024)},
}


@contextlib.contextmanager
def ablation(names):
    """The module with the named causes switched, until the block ends.
    ``timed`` clears jax's caches before it traces, so no earlier trace of
    the same statics is reused."""
    kept = {}
    for name in names:
        for attr, fn in PATCHES[name].items():
            kept.setdefault(attr, getattr(fa, attr))
            setattr(fa, attr, fn)
    try:
        yield
    finally:
        for attr, fn in kept.items():
            setattr(fa, attr, fn)


def statics(q, k, v, seg, window, blocks, f32):
    bq, bk = blocks or (None, None)
    if f32:
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    st, *arrays, _ = fa._prep(
        q, k, v, seg, seg, True, None, 0, bq, bk, False,
        window=window, seg_pad_zero=seg is not None)
    return st, arrays


def programs(st, arrays, reps):
    """{kernel: (jitted program of ``reps`` chained calls, its arguments)}.
    A backward program keeps one of the two kernels: XLA drops the call
    whose results nothing reads."""
    qt, kt, vt, qseg, kseg, qpos, kpos = arrays

    def fwd(q):
        def body(_, q):
            return fa._fwd_call(st, q, kt, vt, qseg, kseg, qpos, kpos)[0]
        return lax.fori_loop(0, reps, body, q)

    o, lse = jax.jit(lambda q: fa._fwd_call(
        st, q, kt, vt, qseg, kseg, qpos, kpos))(qt)

    def bwd(which):
        def prog(do):
            def body(_, do):
                dq, dk, dv = fa._bwd_call(
                    st, qt, kt, vt, qseg, kseg, o, lse, do, qpos=qpos,
                    kpos=kpos)
                if which == "dq":
                    return do + (dq * 0).astype(do.dtype)
                dkv = (dk + dv).sum(axis=1, keepdims=True)[:, :, : do.shape[2]]
                return do + (dkv * 0).astype(do.dtype)
            return lax.fori_loop(0, reps, body, do)
        return prog

    return {"fwd": (fwd, qt), "dq": (bwd("dq"), o), "dkv": (bwd("dkv"), o)}


def timed(prog, arg, reps, calls=1):
    """(seconds a repetition, seconds to lower) of ``prog(arg)``, which holds
    ``reps`` repetitions; best of four trials of ``calls`` dispatches back to
    back (more than one: a whole wrapper a program, whose preparation a loop
    would let XLA hoist out)."""
    jax.clear_caches()
    t0 = time.perf_counter()
    lowered = jax.jit(prog).lower(arg)
    lower_s = time.perf_counter() - t0
    run = lowered.compile()
    best = float("inf")
    for _ in range(4):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = run(arg)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / (reps * calls), lower_s


def emit(sink, row):
    text = json.dumps(row)
    print(text, flush=True)
    sink.write(text + "\n")
    sink.flush()


def sweep(dev, sink, only):
    new = hasattr(fa, "block_counts")
    keys = jax.random.split(jax.random.key(32), 3)
    for gi, (group, N, window, sizes, kernels, reps) in enumerate(SHAPES):
        if only and not group.startswith(only):
            continue
        for rows, S in sizes:
            rng = np.random.default_rng([32, gi, rows])
            seg_np = None if group == "train" else burst_segments(rows, S, rng)
            seg = None if seg_np is None else jnp.asarray(seg_np)
            mask = dense_mask(S, window, seg_np)
            pairs = float(mask.sum()) * N / mask.shape[0] * rows
            q = jax.random.normal(keys[0], (rows, S, N, H), jnp.bfloat16)
            k = jax.random.normal(keys[1], (rows, S, K, H), jnp.bfloat16)
            v = jax.random.normal(keys[2], (rows, S, K, H), jnp.bfloat16)
            impls = [("tree", None, None)]
            impls += [(f"b{a}x{b}", (a, b), None) for a, b in BLOCKS
                      if a <= S and (group == "train" or a == b)]
            if new:
                impls += [(a, None, a) for a in ABLATIONS]
            if "--impls" in sys.argv:
                # names from the list above, or ablations joined by "+"
                named = dict((i[0], i) for i in impls)
                impls = [named.get(a, (a, None, a)) for a in
                         sys.argv[sys.argv.index("--impls") + 1].split(",")]
            if "--kernels" in sys.argv:
                kernels = tuple(
                    sys.argv[sys.argv.index("--kernels") + 1].split(","))
            for name, blocks, ablate in impls:
                causes = ablate.split("+") if ablate else []
                with ablation(causes):
                    st, arrays = statics(q, k, v, seg, window, blocks,
                                         "f32" in causes)
                    nq = arrays[0].shape[2] // st.block_q
                    nk = arrays[1].shape[2] // st.block_kv
                    counts = {"full": nq * nk}
                    if new:
                        counts = fa.block_counts(st, nq, nk, seg is not None)
                    counts["minimum"] = min_blocks(
                        mask, st.block_q, st.block_kv)
                    progs = programs(st, arrays, reps)
                    for kern in kernels:
                        row = {"tree": TAG, "shape": group, "rows": rows,
                               "len": S, "heads": N, "window": window,
                               "impl": name, "kernel": kern,
                               "blocks": [st.block_q, st.block_kv], **counts}
                        try:
                            sec, lower_s = timed(*progs[kern], reps)
                        except Exception as e:   # a shape Mosaic refuses
                            row["error"] = str(e).splitlines()[0][:200]
                            emit(sink, row)
                            continue
                        flops = UNITS[kern] * 2 * H * pairs
                        emit(sink, {**row, "ms": round(1e3 * sec, 4),
                                    "mxu_pct": round(
                                        100 * flops / PEAK_FLOPS / sec, 2),
                                    "lower_s": round(lower_s, 3),
                                    "device": dev.device_kind})


def kernel_operands(call, args):
    """(statics, operands) that the wrapper hands ``_fwd_call`` for
    ``call(args)``: the call is run eagerly with the kernel's entry swapped
    for a recorder, so the sweep repeats none of the wrapper's preparation."""
    seen = []

    def record(st, q, k, v, *rest):
        seen.append((st, (q, k, v, *rest)))
        return jnp.zeros((*q.shape[:3], v.shape[3]), q.dtype)

    kept, fa._flash_forward_only = fa._flash_forward_only, record
    try:
        call(args)
    finally:
        fa._flash_forward_only = kept
    return seen[0]


def sweep_mimo(dev, sink):
    """The plain walk at three block sizes against the band form at three
    chunk sizes, kernel alone (``fwd``) and whole wrapper (``call``)."""
    m = MIMO
    N, W, reps = m["N"], m["window"], m["reps"]
    keys = jax.random.split(jax.random.key(53), 4)
    impls = [("tree", None)]
    impls += [(f"b{b}x{b}", (b, b)) for b in (1024, 512, 256)]
    if hasattr(fa, "BAND_CHUNK"):
        impls += [(f"band{c}", None) for c in (256, 512, 1024)]
    if "--impls" in sys.argv:
        named = sys.argv[sys.argv.index("--impls") + 1].split(",")
        impls = [i for i in impls if i[0] in named]
    for rows, S in m["sizes"]:
        rng = np.random.default_rng([53, rows])
        seg_np = burst_segments(rows, S, rng, m["bucket"])
        n = seg_np.sum(axis=1)
        # the pairs the window keeps of each row's real positions
        pairs = float((W * (W + 1) // 2 + (n - W) * W).sum()) * N
        flops = pairs * (m["Hk"] + m["Hv"]) * 2
        seg = jnp.asarray(seg_np)
        q = jax.random.normal(keys[0], (rows, S, N, m["Hk"]), jnp.bfloat16)
        k = jax.random.normal(keys[1], (rows, S, m["K"], m["Hk"]), jnp.bfloat16)
        v = jax.random.normal(keys[2], (rows, S, m["K"], m["Hv"]), jnp.bfloat16)
        b = jax.random.uniform(keys[3], (N,), jnp.float32, 0.0, 4.0)
        for name, blocks in impls:
            bq, bk = blocks or (None, None)

            def call(args):
                q, k, v, seg, b = args
                return fa.flash_attention(
                    q, k, v, window=W, q_segment_ids=seg, kv_segment_ids=seg,
                    seg_pad_zero=True, sink=b, block_q=bq, block_kv=bk)

            with ablation([name] if name in PATCHES else []):
                st, ops = kernel_operands(call, (q, k, v, seg, b))
                folds = ops[0].shape[0] // rows
                counts = fa.block_counts(
                    st, ops[0].shape[2] // st.block_q,
                    ops[1].shape[2] // st.block_kv, True)
                row = {"tree": TAG, "shape": m["group"], "rows": rows,
                       "len": S, "heads": N, "window": W, "impl": name,
                       "blocks": [st.block_q, st.block_kv],
                       "steps": counts["steps"] * folds,
                       "pairs_visited_a_position": round(
                           counts["visited"] * folds * st.block_q
                           * st.block_kv / S, 1)}

                def alone(ops):
                    # the sink rows carry the loop: q and o differ in width
                    def body(_, sink_rows):
                        o = fa._fwd_call(st, *ops[:-1], sink_rows)[0]
                        return sink_rows + o[0, 0, 0, 0].astype(
                            sink_rows.dtype) * 0
                    return lax.fori_loop(0, reps, body, ops[-1])

                for kern, run in (
                        ("fwd", lambda: timed(alone, ops, reps)),
                        ("call", lambda: timed(
                            call, (q, k, v, seg, b), 1, calls=reps))):
                    try:
                        sec, lower_s = run()
                    except Exception as e:   # a shape Mosaic refuses
                        emit(sink, {**row, "kernel": kern, "error":
                                    str(e).splitlines()[0][:200]})
                        continue
                    emit(sink, {**row, "kernel": kern,
                                "ms": round(1e3 * sec, 4),
                                "mxu_pct": round(
                                    100 * flops / PEAK_FLOPS / sec, 2),
                                "lower_s": round(lower_s, 3),
                                "device": dev.device_kind})


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    only = None
    if "--only" in sys.argv:
        only = tuple(sys.argv[sys.argv.index("--only") + 1].split(","))
    with open(OUT, "a") as sink:
        sweep(dev, sink, only)
        if not only or MIMO["group"].startswith(only):
            sweep_mimo(dev, sink)


if __name__ == "__main__":
    main()
