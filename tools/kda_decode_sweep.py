"""Time the KDA decode kernel alone on the chip at the Ling cell's shapes
(state ``[8, 129, 32, 128, 128]`` float32, 128 live slots, 32 heads of a
128 x 128 tile) against what its bytes allow: ``2 x 2 MiB x live slots /
819 GB/s`` a call. ``ops/pallas/kda.py``'s block of heads and its head loop
come from this script's table (PERF.md section 6, PR 46); the variants that
lost are kept HERE for the comparison and nowhere else.

    chiprun -- python tools/kda_decode_sweep.py [--only tree,copy,...]

``tree`` is this checkout's public ``kda_decode``. ``variant`` is one kernel
on the automatic pipeline with four switches: the heads a grid step carries
(``hb``), what a head does (``body``: ``copy`` is ``so = s * exp(g)`` and
nothing else, i.e. the pipeline and the grid alone; ``eye`` is the kernel
as PR 41 wrote it, ``v`` and ``o`` changing hands with their column form
through an identity mask in every head; ``xchg`` turns a group of eight
heads' ``v`` once and gathers their ``o`` columns to turn them back once,
``xchg_where`` gathers them by a select, ``math`` is ``xchg`` without its
two transposes), how the small operands arrive (``block``: five
``(1, hb, d)`` blocks a grid step; ``slot``: five ``(1, N, d)`` blocks
fetched once a slot; ``packed``: one ``[B, 5, N, d]`` operand the caller
stacks, the stack timed with it), whether the heads are unrolled or a
``fori_loop`` over groups of eight (``loop``), and the state operand's
buffers (3 through ``pl.Buffered``: this JAX refuses it). ``manual`` keeps
the state in HBM and moves it by hand through a ring of ``nb`` buffers with
``depth`` reads ahead (more copies in flight than the pipeline's one read
and one write); ``nodma`` is the same with no copy started: the vector work
alone. ``phased`` never reads while it writes: K slots' rows in, then out.

Prints one JSON line a variant and keeps them in
``chiprun_out/kda_decode_sweep.jsonl``. Raises without a TPU;
``--compile-only`` compiles every variant for a described v5e (no chip, no
time: what Mosaic refuses shows here)."""

from __future__ import annotations

import functools
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

LAYERS, SLOTS, N, D = 8, 128, 32, 128
KDA_LAYERS = 7
HBM_BYTES_PER_S = 819e9
REPS = 56
OUT = "chiprun_out/kda_decode_sweep.jsonl"
GROUP = 8                       # heads a sublane tile of the operands holds


def _eye(d):
    """The identity mask a row and its column form change hands through."""
    return (lax.broadcasted_iota(jnp.int32, (d, d), 0)
            == lax.broadcasted_iota(jnp.int32, (d, d), 1)).astype(jnp.float32)


def _head(m, a, k, kb, q, v_col):
    """One head on the vector unit, ``m`` [dv, dk] value-major, the rows
    [1, dk], ``v_col`` [dv, 1] -> (m', o_col [dv, 1])."""
    m = m * a
    r = (m * k).sum(-1, keepdims=True)
    m = m + (v_col - r) * kb
    return m, (m * q).sum(-1, keepdims=True)


def _kernel(hb, body, ops, loop, rows_ref, layer_ref, act_ref, *refs):
    del rows_ref, layer_ref
    if ops == "packed":
        p_ref, s_ref, o_ref, so_ref = refs[:4]
        q_ref, k_ref, kb_ref, g_ref, v_ref = (p_ref.at[0, i] for i in range(5))
        scratch = refs[4:]
        row = lambda ref, at: ref[at, :]
    else:
        q_ref, k_ref, kb_ref, g_ref, v_ref, s_ref, o_ref, so_ref = refs[:8]
        scratch = refs[8:]
        row = lambda ref, at: ref[0, at, :]
    b, h = pl.program_id(0), pl.program_id(1)
    dv = s_ref.shape[-2]
    gb = GROUP if hb % GROUP == 0 else hb
    # where this block's first head sits in the small operands' blocks
    base = 0 if ops == "block" else h * hb

    @pl.when(act_ref[b] == 0)
    def _dead():
        so_ref[...] = s_ref[...]
        if ops == "block":
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        else:
            o_ref[0, pl.ds(base, hb), :] = jnp.zeros((hb, dv), o_ref.dtype)

    def group(j):
        """Heads j * gb .. of this block; ``j`` may be traced."""
        at = base + j * gb
        if gb == GROUP and not isinstance(at, int):
            at = pl.multiple_of(at, GROUP)
        sl = pl.ds(at, gb)
        q8, k8, kb8, v8 = (row(r, sl) for r in (q_ref, k_ref, kb_ref, v_ref))
        a8 = jnp.exp(row(g_ref, sl))
        if body == "copy":
            for i in range(gb):
                so_ref[0, 0, j * gb + i] = s_ref[0, 0, j * gb + i] * a8[i:i + 1]
            o_ref[0, sl, :] = jnp.zeros((gb, dv), o_ref.dtype)
            return
        if body == "eye":
            eye = _eye(dv)
            for i in range(gb):
                r1 = lambda x: x[i:i + 1]
                v_col = (eye * r1(v8)).sum(-1, keepdims=True)
                m, o_col = _head(s_ref[0, 0, j * gb + i], r1(a8), r1(k8),
                                 r1(kb8), r1(q8), v_col)
                so_ref[0, 0, j * gb + i] = m
                o_ref[0, pl.ds(at + i, 1), :] = (eye * o_col).sum(
                    0, keepdims=True)
            return
        # "xchg": the group's v turned once, its o columns turned back once
        # ("math" is the same without the two turns: what they cost; its
        # output is not the recurrence's)
        vt_ref, ot_ref = scratch
        if body != "math":
            vt_ref[...] = jnp.concatenate(
                [v8, jnp.zeros((dv - gb, dv), jnp.float32)], 0).T
        lane = lax.broadcasted_iota(jnp.int32, (dv, dv), 1)
        acc = jnp.zeros((dv, dv), jnp.float32)
        for i in range(gb):
            r1 = lambda x: x[i:i + 1]
            m, o_col = _head(s_ref[0, 0, j * gb + i], r1(a8), r1(k8),
                             r1(kb8), r1(q8), vt_ref[:, i:i + 1])
            so_ref[0, 0, j * gb + i] = m
            if body == "xchg_where":
                acc = jnp.where(lane == i, o_col, acc)
            else:
                ot_ref[:, i:i + 1] = o_col
        if body == "xchg_where":
            o_ref[0, sl, :] = acc.T[:gb]
        elif body == "math":
            o_ref[0, sl, :] = ot_ref[:gb, :]
        else:
            o_ref[0, sl, :] = ot_ref[...].T[:gb]

    @pl.when(act_ref[b] != 0)
    def _live():
        if loop == "unroll":
            for j in range(hb // gb):
                group(j)
        else:
            lax.fori_loop(0, hb // gb, lambda j, c: (group(j), c)[1], 0)


@functools.partial(jax.jit, static_argnames=(
    "hb", "body", "ops", "loop", "bufs"))
def variant(state, q, k, kb, g, v, layer, active, *, hb, body, ops, loop,
            bufs):
    B, n, dk = q.shape
    dv = v.shape[-1]
    act = active.astype(jnp.int32)
    prefetch = [jnp.where(act > 0, jnp.arange(1, B + 1, dtype=jnp.int32), 0),
                layer, act]
    if ops == "block":
        vec = lambda d: pl.BlockSpec(
            (1, hb, d), lambda b, h, rows, layer, act: (b, h, 0))
    else:
        vec = lambda d: pl.BlockSpec(
            (1, n, d), lambda b, h, rows, layer, act: (b, 0, 0))
    mode = {} if bufs == 2 else {"pipeline_mode": pl.Buffered(bufs)}
    index = lambda b, h, rows, layer, act: (
        layer[0], rows[b], h * act[b], 0, 0)
    st_in = pl.BlockSpec((1, 1, hb, dv, dk), index, **mode)
    st_out = pl.BlockSpec((1, 1, hb, dv, dk), index)
    if ops == "packed":
        small = [jnp.stack([q, k, kb, g, v], 1)]
        small_specs = [pl.BlockSpec(
            (1, 5, n, dk), lambda b, h, rows, layer, act: (b, 0, 0, 0))]
    else:
        small = [q, k, kb, g, v]
        small_specs = [vec(dk), vec(dk), vec(dk), vec(dk), vec(dv)]
    block_bytes = hb * dv * dk * 4
    o, state = pl.pallas_call(
        functools.partial(_kernel, hb, body, ops, loop),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, n // hb),
            in_specs=[*small_specs, st_in],
            out_specs=[vec(dv), st_out],
            scratch_shapes=([pltpu.VMEM((dv, dv), jnp.float32)] * 2
                            if body.startswith(("xchg", "math")) else []),
        ),
        out_shape=[jax.ShapeDtypeStruct((B, n, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={len(prefetch) + len(small): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(bufs + 2) * block_bytes + 8 * 2 ** 20),
        name="kda_decode",
    )(*prefetch, *small, state)
    return o, state


def _manual_kernel(hb, nb, depth, body, dma, rows_ref, layer_ref, act_ref,
                   nxt_ref, rank_ref, q_ref, k_ref, kb_ref, g_ref, v_ref,
                   s_hbm, o_ref, so_hbm, buf, sem_in, sem_out):
    """The state stays in HBM and moves by hand: a ring of ``nb`` buffers
    of ``hb`` heads, ``depth`` reads ahead of the head being worked on
    (across slots: ``nxt`` is the next live slot), a buffer's write-back
    waited for only when the ring comes round to it. A dead slot moves
    nothing. Without ``dma`` no copy is started or waited for: the vector
    work alone, on whatever the buffers hold."""
    b = pl.program_id(0)
    n, dv = q_ref.shape[1], v_ref.shape[2]
    C = n // hb
    lyr = layer_ref[0]
    t0 = rank_ref[b] * C

    class _Idle:
        start = wait = staticmethod(lambda: None)

    def rd(slot, c, t):
        return pltpu.make_async_copy(
            s_hbm.at[lyr, rows_ref[slot], pl.ds(c * hb, hb)],
            buf.at[t % nb], sem_in.at[t % nb]) if dma else _Idle

    def wr(slot, c, t):
        return pltpu.make_async_copy(
            buf.at[t % nb],
            so_hbm.at[lyr, rows_ref[slot], pl.ds(c * hb, hb)],
            sem_out.at[t % nb]) if dma else _Idle

    @pl.when(act_ref[b] == 0)
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(act_ref[b] != 0)
    def _live():
        @pl.when(rank_ref[b] == 0)
        def _prime():
            for c in range(depth):
                rd(b, c, c).start()

        eye = _eye(dv)
        for c in range(C):
            t = t0 + c
            ahead, slot = (c + depth, b) if c + depth < C else (
                c + depth - C, nxt_ref[b])

            @pl.when(slot >= 0)
            def _fetch():
                @pl.when(t + depth >= nb)
                def _reuse():
                    wr(b, 0, t + depth).wait()
                rd(slot, ahead, t + depth).start()

            rd(b, c, t).wait()
            at = t % nb
            for i in range(hb):
                row = lambda ref: ref[0, c * hb + i:c * hb + i + 1, :]
                a = jnp.exp(row(g_ref))
                if body == "copy":
                    buf[at, i] = buf[at, i] * a
                    continue
                v_col = (eye * row(v_ref)).sum(-1, keepdims=True)
                m, o_col = _head(buf[at, i], a, row(k_ref), row(kb_ref),
                                 row(q_ref), v_col)
                buf[at, i] = m
                o_ref[0, c * hb + i:c * hb + i + 1, :] = (eye * o_col).sum(
                    0, keepdims=True)
            if body == "copy":
                o_ref[0, c * hb:(c + 1) * hb, :] = jnp.zeros((hb, dv),
                                                             o_ref.dtype)
            wr(b, c, t).start()

        @pl.when(nxt_ref[b] < 0)
        def _drain():
            for i in range(nb):
                @pl.when(t0 + C > i)
                def _():
                    wr(b, 0, t0 + C - 1 - i).wait()


@functools.partial(jax.jit, static_argnames=(
    "hb", "nb", "depth", "body", "dma"))
def manual(state, q, k, kb, g, v, layer, active, *, hb, nb, depth, body,
           dma=True):
    B, n, dk = q.shape
    dv = v.shape[-1]
    assert depth <= n // hb and depth < nb
    act = active.astype(jnp.int32)
    ids = jnp.arange(B, dtype=jnp.int32)
    # the next live slot behind each slot (-1: none), by a reversed running
    # minimum over the live slots' own indices
    later = jnp.where(act > 0, ids, B)
    nxt = lax.cummin(jnp.concatenate([later[1:], jnp.full((1,), B)]),
                     reverse=True)
    prefetch = [jnp.where(act > 0, ids + 1, 0), layer, act,
                jnp.where(nxt >= B, -1, nxt).astype(jnp.int32),
                (jnp.cumsum(act) - act).astype(jnp.int32)]
    vec = lambda d: pl.BlockSpec((1, n, d), lambda b, *_: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    o, state = pl.pallas_call(
        functools.partial(_manual_kernel, hb, nb, depth, body, dma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B,),
            in_specs=[vec(dk), vec(dk), vec(dk), vec(dk), vec(dv), hbm],
            out_specs=[vec(dv), hbm],
            scratch_shapes=[pltpu.VMEM((nb, hb, dv, dk), jnp.float32),
                            pltpu.SemaphoreType.DMA((nb,)),
                            pltpu.SemaphoreType.DMA((nb,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, n, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={len(prefetch) + 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=nb * hb * dv * dk * 4 + 8 * 2 ** 20),
        name="kda_decode",
    )(*prefetch, q, k, kb, g, v, state)
    return o, state


def _phased_kernel(K, compute, rows_ref, layer_ref, g_ref, s_hbm, so_hbm, buf, sem):
    """Reads and writes in PHASES (``copy`` body, nothing overlapped): K
    slots' rows in, the decay, the K rows out. What the memory gives a
    kernel that never reads while it writes."""
    B, n = g_ref.shape[:2]
    lyr = layer_ref[0]

    def batch(j, c):
        row = lambda i: rows_ref[j * K + i]
        for i in range(K):
            pltpu.make_async_copy(
                s_hbm.at[lyr, row(i)], buf.at[i], sem.at[i]).start()
        for i in range(K):
            pltpu.make_async_copy(
                s_hbm.at[lyr, row(i)], buf.at[i], sem.at[i]).wait()

        def slot(i, c):
            a = jnp.exp(g_ref[j * K + i])
            for h in range(n):
                buf[i, h] = buf[i, h] * a[h:h + 1]
            return c

        if compute:
            lax.fori_loop(0, K, slot, 0)
        for i in range(K):
            pltpu.make_async_copy(
                buf.at[i], so_hbm.at[lyr, row(i)], sem.at[i]).start()
        for i in range(K):
            pltpu.make_async_copy(
                buf.at[i], so_hbm.at[lyr, row(i)], sem.at[i]).wait()
        return c

    lax.fori_loop(0, B // K, batch, 0)


@functools.partial(jax.jit, static_argnames=("K", "compute"))
def phased(state, q, k, kb, g, v, layer, active, *, K, compute):
    B, n, dk = q.shape
    dv = v.shape[-1]
    prefetch = [jnp.arange(1, B + 1, dtype=jnp.int32), layer]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    state = pl.pallas_call(
        functools.partial(_phased_kernel, K, compute),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(1,),
            in_specs=[pl.BlockSpec((B, n, dk), lambda i, *_: (0, 0, 0)), hbm],
            out_specs=hbm,
            scratch_shapes=[pltpu.VMEM((K, n, dv, dk), jnp.float32),
                            pltpu.SemaphoreType.DMA((K,))],
        ),
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        input_output_aliases={len(prefetch) + 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=K * n * dv * dk * 4 + 16 * 2 ** 20),
        name="kda_decode",
    )(*prefetch, g, state)
    return jnp.zeros((B, n, dv), jnp.float32), state


def tree(state, q, k, kb, g, v, layer, active):
    """This checkout's public ``kda_decode``, as the runner calls it (``kb``
    is the caller's ``k * b``: the module multiplies again by ones)."""
    from orion_tpu.ops.pallas.kda import kda_decode

    del kb
    return kda_decode(state, q, k, v, g, jnp.ones(q.shape[:2], jnp.float32),
                      layer=layer[0], active=active)


def block_heads(name: str) -> int:
    """The heads one block of the state holds under ``name``."""
    if "hb" in name:
        return int(name.split("hb")[1].split("_")[0])
    import orion_tpu.ops.pallas.kda as module

    if hasattr(module, "head_block"):
        return module.head_block(N, D, D)
    return module.HEAD_BLOCK            # PR 41's constant, in a parent's tree


def variants() -> dict:
    v = lambda **kw: functools.partial(
        variant, **{"ops": "block", "loop": "unroll", "bufs": 2, **kw})
    out = {"tree": tree}
    for hb in (8, 16, 32):
        out[f"copy_hb{hb}"] = v(hb=hb, body="copy")
        out[f"eye_hb{hb}"] = v(hb=hb, body="eye")
        out[f"xchg_hb{hb}"] = v(hb=hb, body="xchg")
    out["math_hb32"] = v(hb=32, body="math")
    out["xchg_where_hb32"] = v(hb=32, body="xchg_where")
    out["copy_hb8_slot"] = v(hb=8, body="copy", ops="slot", loop="fori")
    out["eye_hb8_slot"] = v(hb=8, body="eye", ops="slot", loop="fori")
    out["xchg_hb8_slot"] = v(hb=8, body="xchg", ops="slot", loop="fori")
    out["copy_hb32_packed"] = v(hb=32, body="copy", ops="packed")
    out["xchg_hb32_packed"] = v(hb=32, body="xchg", ops="packed")
    out["eye_hb32_fori"] = v(hb=32, body="eye", loop="fori")
    out["xchg_hb32_fori"] = v(hb=32, body="xchg", loop="fori")
    out["xchg_hb16_fori"] = v(hb=16, body="xchg", loop="fori")
    for hb, nb, depth in ((8, 4, 2), (8, 8, 4), (8, 8, 2), (4, 8, 4),
                          (4, 16, 8), (16, 4, 2), (32, 2, 1), (32, 3, 1),
                          (2, 16, 8)):
        for body in ("copy", "eye"):
            out[f"manual_{body}_hb{hb}_nb{nb}_d{depth}"] = functools.partial(
                manual, hb=hb, nb=nb, depth=depth, body=body)
    for body in ("copy", "eye"):
        out[f"nodma_{body}_hb8"] = functools.partial(
            manual, hb=8, nb=4, depth=2, body=body, dma=False)
    for K in (1, 4, 8, 16, 32):
        out[f"phased_copy_hb32_K{K}"] = functools.partial(
            phased, K=K, compute=True)
        out[f"phased_copy_nomath_hb32_K{K}"] = functools.partial(
            phased, K=K, compute=False)
    out["copy_hb32_bufs3"] = v(hb=32, body="copy", bufs=3)
    if "--only" in sys.argv:
        keep = tuple(sys.argv[sys.argv.index("--only") + 1].split(","))
        out = {n: f for n, f in out.items() if n.startswith(keep)}
    return out


def program(step):
    """REPS calls in one program, the state carried in place, each call's
    ``v`` the last one's output and its layer the next of the seven, as the
    layer scan chains them."""
    def body(i, c, q, k, b, g, active):
        state, v = c
        o, state = step(state, q, k, k * b[..., None], g, v,
                        (i % KDA_LAYERS).reshape(1).astype(jnp.int32), active)
        return state, o

    return jax.jit(
        lambda state, v, *rest: lax.fori_loop(
            0, REPS, functools.partial(
                body, q=rest[0], k=rest[1], b=rest[2], g=rest[3],
                active=rest[4]), (state, v)),
        donate_argnums=(0,))


def abstract(device):
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(device)
    f = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=sh)
    return (f(LAYERS, SLOTS + 1, N, D, D), f(SLOTS, N, D), f(SLOTS, N, D),
            f(SLOTS, N, D), f(SLOTS, N), f(SLOTS, N, D),
            f(SLOTS, dt=jnp.bool_))


def compile_only():
    """Every variant compiled for a described v5e: Mosaic's refusals and
    the compile seconds, no time of the kernel."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    import orion_tpu.ops.pallas.kda as module

    # the default backend here is the CPU; the tree's kernel compiles anyway
    module.resolve_interpret = bool
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    args = abstract(topo.devices[0])
    for name, step in variants().items():
        t0 = time.perf_counter()
        try:
            program(step).lower(*args).compile()
            print(json.dumps({"impl": name, "compile_s": round(
                time.perf_counter() - t0, 2)}), flush=True)
        except Exception as e:          # what Mosaic refuses
            print(json.dumps({"impl": name, "error": str(e)[:600]}),
                  flush=True)


def main():
    if "--compile-only" in sys.argv:
        return compile_only()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    ks = jax.random.split(jax.random.key(46), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (SLOTS, N, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (SLOTS, N, D)))
    v = jax.random.normal(ks[2], (SLOTS, N, D))
    g = -0.02 * jax.nn.sigmoid(jax.random.normal(ks[3], (SLOTS, N, D)))
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (SLOTS, N)))
    active = jnp.ones((SLOTS,), bool)
    least = 2 * N * D * D * 4 * SLOTS / HBM_BYTES_PER_S
    want = None
    with open(OUT, "a" if "--append" in sys.argv else "w") as sink:
        for name, step in variants().items():
            state = 0.1 * jax.random.normal(
                ks[5], (LAYERS, SLOTS + 1, N, D, D), jnp.float32)
            prog = program(step)
            row = {"impl": name}
            try:
                t0 = time.perf_counter()
                compiled = prog.lower(state, v, q, k, b, g, active).compile()
                row["compile_s"] = round(time.perf_counter() - t0, 2)
                best = float("inf")
                for _ in range(4):
                    t0 = time.perf_counter()
                    state, o = jax.block_until_ready(
                        compiled(state, v, q, k, b, g, active))
                    best = min(best, time.perf_counter() - t0)
            except Exception as e:       # a shape Mosaic refuses
                row["error"] = str(e).splitlines()[0][:300]
            else:
                sec = best / REPS
                # every variant ran the same 4 x REPS steps from one state:
                # a full body's output is the tree's (copy's is not)
                probe = float(jnp.abs(o).mean())
                if want is None and "copy" not in name:
                    want = probe
                row.update({
                    "ms": round(1e3 * sec, 4),
                    "least_ms": round(1e3 * least, 4),
                    "roofline_pct": round(100 * least / sec, 2),
                    "us_a_block": round(
                        1e6 * sec / (SLOTS * N // block_heads(name)), 3),
                    "mean_abs_o": probe, "mean_abs_o_first": want,
                    "device": dev.device_kind})
            del state
            text = json.dumps(row)
            print(text, flush=True)
            sink.write(text + "\n")
            sink.flush()


if __name__ == "__main__":
    main()
