"""Pipeline parallelism over the ``pp`` mesh axis.

The reference pipelines layers across devices with NCCL p2p activation
transfers and a microbatch schedule (SURVEY.md §3 "PP"; PAPERS.md:7). The
TPU-native formulation here is SPMD, not MPMD: the stacked per-layer params
[L, ...] are sharded contiguously over ``pp`` (rule "layers" -> "pp", so each
device owns L/pp stage layers), and a ``shard_map`` that is *manual over pp
only* runs the classic GPipe fill/drain schedule — each tick every stage
applies its layers to its current microbatch and ``ppermute``s the activation
one hop down the ring. All other mesh axes (dp/fsdp/tp/sp) stay in XLA's
auto-sharding mode inside the pipeline body, so pipeline composes with data,
ZeRO-3, tensor and sequence sharding without any manual collectives.

Schedule notes: with M microbatches over S stages the bubble fraction is
(S-1)/(M+S-1) — raise ``parallel.pp_microbatches`` to amortize. Bubble ticks
compute on garbage and are masked out (uniform SPMD control flow beats a
per-stage cond that would have to carry collectives). Three schedules:

GPIPE (``pp_schedule='gpipe'``): the classic fill/drain. Backward is just
``jax.grad`` through the scan: ppermute transposes into the reverse-direction
ring, giving the synchronous GPipe backward schedule. The forward scan's
autodiff residuals grow with the TICK count — every per-layer interior of
every tick (bubble ticks included, whose garbage compute still gets stashed)
stays live from the forward pass until its backward tick, so peak activation
memory scales with M (or with remat='full', M+S-1 boundary carries plus
1.33x executed FLOPs).

1F1B (``pp_schedule='1f1b'``; PAPERS.md 2412.14374 schedule family): the
hand-written pipeline VJP the round-3 note said this would need (jax.grad
through a schedule that reorders fwd/bwd ticks does not fall out of a scan).
The forward tick loop stashes exactly ONE [mb, S, D] stage-INPUT per real
microbatch (M slots — no garbage-tick stash, no per-layer interiors); the
custom-vjp backward runs the reverse-direction ring: each tick re-linearizes
the stage body at its stashed input (``jax.vjp`` inside the tick — the
recompute lives and dies within one tick) and ppermutes the input-cotangent
UP the ring while parameter cotangents accumulate per stage. Peak in-flight
interior activations are therefore ONE stage body per device — bounded by
the stage count, never by M — and the boundary stash is M·(B/M) = B rows
total, also M-independent. The loss lives outside the pipelined region, so
its cotangent only exists after every microbatch has drained: the classic
steady-state "one forward, one backward per tick" interleaving of fwd and
bwd of the SAME optimizer step collapses to fwd-phase-then-bwd-phase here
(same tick count, T = M+S-1 each way); what 1F1B contributes in this
formulation is its stash discipline. Cost model per backward tick:
relinearize (F) + pullback (B) — GPipe's remat='full' pays the same FLOPs
while stashing M+S-1 carries incl. bubbles; GPipe's remat='none' skips the
relinearize but stashes every interior of every tick. Bitwise: forward is
tick-for-tick GPipe's, and backward contributions accumulate in the same
reverse-microbatch order jax.grad's transposed scan uses, so losses AND
grads are bitwise-equal to the GPipe path (pinned,
tests/test_pipeline_1f1b.py).

The INTERLEAVED (Megatron virtual-pipeline-class) schedule attacks the
bubble where raising M cannot: each device owns V non-contiguous layer
chunks (chunk c on device c mod pp), ticks advance at CHUNK granularity,
and a microbatch laps the device ring V times. Per-batch overhead drops
from GPipe's (M+pp-1)/M to (M+V*pp-1)/(V*M): at M=pp, V=4 that is
~1.25x vs GPipe's ~2x — and, crucially, V raises utilization WITHOUT
shrinking the microbatch, so it composes with small global batches where
GPipe's only lever (more, smaller microbatches) starves the MXU.
Scheduling constraint: M <= pp keeps at most ONE of a device's V chunks
active per tick, which is what lets the schedule stay a uniform SPMD scan
that ``jax.grad`` differentiates (the reverse scan IS the interleaved
backward). Cost: the round-robin chunk layout is a one-gather-per-step
resharding of the stage params (volume comparable to the param
all-gather every ZeRO-3 step already pays). Select via
``parallel.pp_schedule='interleaved'`` + ``parallel.pp_virtual_stages``.

Measured (round 5, tools/pp_bubble_bench.py, 8-fake-CPU-device mesh,
8-layer model, uncontended rows; step time vs the pp=1 layout):
pp=2 interleaved M=2,V=4 -> 1.14x (predicted 1.12x); pp=4 GPipe
M=2/4/8 -> 2.75x/1.78x/1.33x (predicted 2.5/1.75/1.38 — the model
tracks); pp=4 interleaved M=4,V=2 -> 1.12x, i.e. BETTER occupancy than
GPipe at M=8 while using half the microbatches (2x the per-microbatch
MXU shape) — exactly the regime the schedule exists for.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

BlockFn = Callable[[jax.Array, Any], Tuple[jax.Array, jax.Array]]


def _make_hop(npp: int, axis: str, wrap: bool = False):
    """``hop(x, reverse=False)``: one ring hop along ``axis`` — a
    ``lax.ppermute`` (whose transpose is the reverse permute). ``wrap``
    closes the ring (the interleaved schedule's lap link)."""
    if wrap:
        fperm = [(i, (i + 1) % npp) for i in range(npp)]
        rperm = [((i + 1) % npp, i) for i in range(npp)]
    else:
        fperm = [(i, i + 1) for i in range(npp - 1)]
        rperm = [(i + 1, i) for i in range(npp - 1)]

    def hop(x, reverse: bool = False):
        return lax.ppermute(x, axis, rperm if reverse else fperm)

    return hop


def validate_row_state(row_state: Any, batch: int, num_microbatches: int):
    """Normalize per-row state for microbatch slicing.

    The non-pp block_fn accepts row-state leaves with a broadcast [1, ...]
    leading dim; pipelining slices leaves to [M, B/M, ...], so lift the
    broadcast to the full batch up front and reject any other leading dim
    loudly instead of dying in an opaque reshape."""
    def _leaf(a):
        a = jnp.asarray(a)
        if a.ndim >= 1 and a.shape[0] == 1 and batch != 1:
            return jnp.broadcast_to(a, (batch,) + a.shape[1:])
        if a.ndim < 1 or a.shape[0] != batch:
            raise ValueError(
                f"pipeline row_state leaf has shape {a.shape}: leading dim "
                f"must equal the batch ({batch}) — or 1 to broadcast — so "
                f"it can be sliced into {num_microbatches} microbatches"
            )
        return a

    return jax.tree.map(_leaf, row_state)


def pipeline_forward(
    x: jax.Array,                 # [B, S, D] (batch auto-sharded on dp/fsdp)
    blocks: Any,                  # stacked per-layer params, leaves [L, ...]
    block_fn: BlockFn,            # (x [b,S,D], layer_params[, row_state])
    mesh: Mesh,
    *,
    axis: str = "pp",
    num_microbatches: int = 1,
    schedule: str = "gpipe",
    virtual_stages: int = 1,
    row_state: Any = None,        # pytree of [B, ...] per-row arrays
) -> tuple[jax.Array, jax.Array]:
    """Run the layer stack as a GPipe pipeline; returns (x_out, aux_sum).

    Requirements (validated by the trainer): L % pp == 0, B % M == 0.

    ``row_state`` carries per-row batch state (packed segment_ids, custom
    positions) through microbatching: leaves are [B, ...] arrays sliced to
    [M, mb, ...], and each tick's stage LOOKS UP its active microbatch's
    slice by index — row state never rides the ppermute ring (it is a
    static input, unlike the activation). With row_state, ``block_fn`` is
    called as ``block_fn(x, layer_params, rs)``.

    ``schedule='interleaved'`` runs the virtual-stage schedule (module
    docstring): ``virtual_stages`` chunks per device, M <= pp required.
    ``schedule='1f1b'`` runs the hand-written-VJP schedule (module
    docstring): stage-input stash bounded by the stage count, explicit
    reverse-ring backward; bitwise-equal losses and grads to 'gpipe'.
    """
    if schedule not in ("gpipe", "interleaved", "1f1b"):
        raise ValueError(
            f"unknown pp_schedule {schedule!r}; expected 'gpipe', "
            f"'interleaved' or '1f1b'"
        )

    def call(c, bp, rs):
        return block_fn(c, bp) if row_state is None else block_fn(c, bp, rs)

    pp = mesh.shape.get(axis, 1)
    if pp == 1:
        def scan_fn(c, bp):
            y, aux = call(c, bp, row_state)
            return y, aux
        x, aux = lax.scan(scan_fn, x, blocks)
        return x, aux.sum()

    B, S, D = x.shape
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by pp_microbatches {M}")
    L = jax.tree.leaves(blocks)[0].shape[0]
    if L % pp:
        raise ValueError(f"n_layers {L} not divisible by pp {pp}")

    row_state = validate_row_state(row_state, B, M)
    rs_mb = jax.tree.map(
        lambda a: a.reshape(M, B // M, *a.shape[1:]), row_state
    )
    if schedule == "interleaved":
        return _interleaved_pipeline(
            x, blocks, call, mesh, axis, M, virtual_stages, rs_mb
        )
    if schedule == "1f1b":
        return _pipeline_1f1b(x, blocks, call, mesh, axis, M, rs_mb)
    mb = B // M

    # [L, ...] -> [pp, L/pp, ...]: contiguous stage chunks, so this reshape
    # is local for params sharded "layers" -> "pp".
    staged = jax.tree.map(
        lambda a: a.reshape(pp, L // pp, *a.shape[1:]), blocks
    )
    x_mb = x.reshape(M, mb, S, D)

    def local(x_mb, staged, rs_mb):
        stage_params = jax.tree.map(lambda a: a[0], staged)  # [L/pp, ...]
        stage = lax.axis_index(axis)
        is_last = stage == pp - 1
        T = M + pp - 1
        hop = _make_hop(pp, axis)

        def run_stage(c, rs):
            def scan_fn(h, bp):
                y, aux = call(h, bp, rs)
                return y, aux
            y, aux = lax.scan(scan_fn, c, stage_params)
            return y, aux.sum()

        def tick(carry, t):
            state, outputs, aux_acc = carry
            inject = x_mb[jnp.clip(t, 0, M - 1)]
            cur = jnp.where(stage == 0, inject, state)
            # Row state is looked up by this stage's active microbatch
            # index (t - stage) — static input, never on the ring.
            rs = jax.tree.map(
                lambda a: a[jnp.clip(t - stage, 0, M - 1)], rs_mb
            )
            # Bubble ticks run on garbage and are masked below: uniform
            # control flow keeps the auto-axis collectives unconditional.
            out, aux_t = run_stage(cur, rs)
            active = (t >= stage) & (t - stage < M)
            aux_acc = aux_acc + jnp.where(active, aux_t, 0.0)
            out_idx = jnp.clip(t - (pp - 1), 0, M - 1)
            outputs = outputs.at[out_idx].set(
                jnp.where(is_last & active, out, outputs[out_idx])
            )
            state = hop(out)
            return (state, outputs, aux_acc), None

        # The carries become device-varying over pp after the first tick, so
        # their (replicated-zero) initial values must be cast to varying.
        carry0 = jax.tree.map(
            lambda a: lax.pcast(a, (axis,), to="varying"),
            (
                jnp.zeros_like(x_mb[0]),
                jnp.zeros_like(x_mb),
                jnp.zeros((), jnp.float32),
            ),
        )
        (_, outputs, aux_acc), _ = lax.scan(tick, carry0, jnp.arange(T))
        # Only the last stage holds real outputs; broadcast them (and the
        # per-stage aux partial sums) to every stage. Per-layer aux values
        # are batch means (e.g. the MoE balance loss), so average over the M
        # microbatches to match the single-batch scan semantics.
        outputs = lax.psum(
            jnp.where(is_last, outputs, jnp.zeros_like(outputs)), axis
        )
        aux = lax.psum(aux_acc, axis) / M
        return outputs, aux

    outputs, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axis), jax.tree.map(lambda _: P(), rs_mb)),
        out_specs=(P(), P()),
        axis_names={axis},
        check_vma=False,
    )(x_mb, staged, rs_mb)
    return outputs.reshape(B, S, D), aux


def _zero_cotangent(a):
    """Cotangent for a non-differentiated pipeline input: float zeros for
    float leaves, float0 for integer leaves (row-state positions /
    segment_ids — the custom-vjp contract for int primals)."""
    a = jnp.asarray(a)
    if jnp.issubdtype(a.dtype, jnp.floating):
        return jnp.zeros_like(a)
    return np.zeros(a.shape, jax.dtypes.float0)


def _pipeline_1f1b(
    x: jax.Array,
    blocks: Any,
    call,                  # call(x, layer_params, rs) -> (y, aux)
    mesh: Mesh,
    axis: str,
    M: int,
    rs_mb: Any = None,     # row-state leaves [M, mb, ...] (see caller)
) -> tuple[jax.Array, jax.Array]:
    """The 1F1B schedule as a hand-written pipeline VJP (module docstring).

    Forward: GPipe's fill/drain tick loop, additionally saving each
    stage's INPUT activation per real microbatch into an [M, mb, S, D]
    per-device stash (masked writes — bubble ticks never stash garbage).
    Backward (``jax.custom_vjp``): a reverse-direction tick loop of the
    same length; tick u at stage s re-linearizes the stage body at the
    stashed input of microbatch M-1-(u-(pp-1-s)) via ``jax.vjp`` (the
    recompute is transient within the tick — no interior ever crosses a
    tick boundary), accumulates the parameter cotangent, and ppermutes
    the input-cotangent one hop UP the ring. Losses and grads are
    bitwise-equal to the 'gpipe' schedule: the forward is tick-for-tick
    identical and the backward accumulates per-stage contributions in
    the same reverse-microbatch order as jax.grad's transposed scan
    (masked-zero bubble contributions are exact +0.0 either way).
    """
    pp = mesh.shape[axis]
    B, S, D = x.shape
    L = jax.tree.leaves(blocks)[0].shape[0]
    if L % pp:
        raise ValueError(f"n_layers {L} not divisible by pp {pp}")
    mb = B // M

    staged = jax.tree.map(
        lambda a: a.reshape(pp, L // pp, *a.shape[1:]), blocks
    )
    x_mb = x.reshape(M, mb, S, D)
    rs_specs = jax.tree.map(lambda _: P(), rs_mb)

    def run_stage(c, sp, rs):
        def scan_fn(h, bp):
            y, aux = call(h, bp, rs)
            return y, aux

        y, aux = lax.scan(scan_fn, c, sp)
        return y, aux.sum()

    def make_fwd_local(with_stash: bool):
        """The forward tick loop; ``with_stash`` statically selects
        whether the stage-input stash is carried and returned (the VJP
        forward needs it; the no-grad primal skips its writes and
        footprint entirely — GPipe's forward cost exactly)."""
        def fwd_local(x_mb, staged, rs_mb):
            stage_params = jax.tree.map(lambda a: a[0], staged)
            stage = lax.axis_index(axis)
            is_last = stage == pp - 1
            T = M + pp - 1
            hop = _make_hop(pp, axis)
            ts = jnp.arange(T)
            # Per-tick reads of the replicated inputs happen HERE,
            # outside the scan: the injected microbatch stream and this
            # stage's row-state slices ride in as scan xs instead of
            # being indexed inside the loop body.
            injects = x_mb[jnp.clip(ts, 0, M - 1)]
            rs_seq = jax.tree.map(
                lambda a: a[jnp.clip(ts - stage, 0, M - 1)], rs_mb
            )

            def tick(carry, xs):
                t, inject, rs = xs
                if with_stash:
                    state, outputs, stash, aux_acc = carry
                else:
                    state, outputs, aux_acc = carry
                cur = jnp.where(stage == 0, inject, state)
                midx = jnp.clip(t - stage, 0, M - 1)
                active = (t >= stage) & (t - stage < M)
                if with_stash:
                    # The 1F1B stash: this stage's input for microbatch
                    # midx — the backward's re-linearization point.
                    # Masked so bubble ticks can't clobber a real slot.
                    stash = stash.at[midx].set(
                        jnp.where(active, cur, stash[midx])
                    )
                out, aux_t = run_stage(cur, stage_params, rs)
                aux_acc = aux_acc + jnp.where(active, aux_t, 0.0)
                out_idx = jnp.clip(t - (pp - 1), 0, M - 1)
                outputs = outputs.at[out_idx].set(
                    jnp.where(is_last & active, out, outputs[out_idx])
                )
                state = hop(out)
                carry = (
                    (state, outputs, stash, aux_acc) if with_stash
                    else (state, outputs, aux_acc)
                )
                return carry, None

            init = [
                jnp.zeros_like(x_mb[0]),
                jnp.zeros_like(x_mb),
                jnp.zeros((), jnp.float32),
            ]
            if with_stash:
                init.insert(2, jnp.zeros_like(x_mb))  # stage-input stash
            carry0 = jax.tree.map(
                lambda a: lax.pcast(a, (axis,), to="varying"), tuple(init)
            )
            carry, _ = lax.scan(tick, carry0, (ts, injects, rs_seq))
            outputs, aux_acc = carry[1], carry[-1]
            outputs = lax.psum(
                jnp.where(is_last, outputs, jnp.zeros_like(outputs)), axis
            )
            aux = lax.psum(aux_acc, axis) / M
            if with_stash:
                return outputs, aux, carry[2]
            return outputs, aux

        return fwd_local

    fwd_sm = jax.shard_map(
        make_fwd_local(True),
        mesh=mesh,
        in_specs=(P(), P(axis), rs_specs),
        out_specs=(P(), P(), P(axis)),
        axis_names={axis},
        check_vma=False,
    )
    fwd_nostash_sm = jax.shard_map(
        make_fwd_local(False),
        mesh=mesh,
        in_specs=(P(), P(axis), rs_specs),
        out_specs=(P(), P()),
        axis_names={axis},
        check_vma=False,
    )

    def bwd_local(g_out, g_aux, stash, staged, rs_mb):
        stage_params = jax.tree.map(lambda a: a[0], staged)
        stage = lax.axis_index(axis)
        is_last = stage == pp - 1
        is_first = stage == 0
        T = M + pp - 1
        hop = _make_hop(pp, axis)
        us = jnp.arange(T)
        # The last stage injects output-cotangents, microbatch M-1 first
        # (the reverse of emission order); pre-gathered outside the scan
        # like the forward's injects, and this stage's row-state slices
        # for its backward microbatch schedule likewise.
        g_seq = g_out[jnp.clip(M - 1 - us, 0, M - 1)]
        rs_seq = jax.tree.map(
            lambda a: a[jnp.clip(M - 1 - (us - (pp - 1 - stage)),
                                 0, M - 1)],
            rs_mb,
        )
        # d(aux)/d(aux_t) = 1/M for every active (stage, microbatch) tick
        # (fwd: aux = psum(sum_t aux_t) / M).
        gaux_term = (g_aux / M).astype(jnp.float32)

        def tick(carry, xs):
            u, ginj, rs = xs
            gstate, dparams, dx = carry
            d = u - (pp - 1 - stage)
            active = (d >= 0) & (d < M)
            midx = jnp.clip(M - 1 - d, 0, M - 1)
            gcur = jnp.where(is_last, ginj, gstate)
            a_in = stash[midx]
            # Re-linearize the stage body at its stashed input: the
            # recompute (and every interior it briefly materializes)
            # lives entirely within this tick.
            _, pull = jax.vjp(
                lambda a_, p_: run_stage(a_, p_, rs), a_in, stage_params
            )
            da, dp = pull((gcur, gaux_term))
            da = jnp.where(active, da, jnp.zeros_like(da))
            dparams = jax.tree.map(
                lambda acc, g: acc + jnp.where(active, g,
                                               jnp.zeros_like(g)),
                dparams, dp,
            )
            dx = dx.at[midx].set(
                jnp.where(is_first & active, da, dx[midx])
            )
            gstate = hop(da, reverse=True)
            return (gstate, dparams, dx), None

        zero_dp = jax.tree.map(jnp.zeros_like, stage_params)
        carry0 = jax.tree.map(
            lambda a: lax.pcast(a, (axis,), to="varying"),
            (jnp.zeros_like(g_out[0]), zero_dp, jnp.zeros_like(g_out)),
        )
        (_, dparams, dx), _ = lax.scan(
            tick, carry0, (us, g_seq, rs_seq)
        )
        dx = lax.psum(
            jnp.where(is_first, dx, jnp.zeros_like(dx)), axis
        )
        # Re-lead with the stage dim so the out_spec P(axis) reassembles
        # the [pp, L/pp, ...] staged layout.
        dparams = jax.tree.map(lambda g: g[None], dparams)
        return dx, dparams

    bwd_sm = jax.shard_map(
        bwd_local,
        mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), rs_specs),
        out_specs=(P(), jax.tree.map(lambda _: P(axis), staged)),
        axis_names={axis},
        check_vma=False,
    )

    @jax.custom_vjp
    def run(x_mb, staged, rs_mb):
        # The no-grad primal (eval / forward-only callers): no stash
        # writes, no stash footprint — GPipe's forward, tick for tick.
        return fwd_nostash_sm(x_mb, staged, rs_mb)

    def run_fwd(x_mb, staged, rs_mb):
        outputs, aux, stash = fwd_sm(x_mb, staged, rs_mb)
        return (outputs, aux), (stash, staged, rs_mb)

    def run_bwd(res, ct):
        stash, staged, rs_mb = res
        g_out, g_aux = ct
        dx, dstaged = bwd_sm(g_out, g_aux, stash, staged, rs_mb)
        return dx, dstaged, jax.tree.map(_zero_cotangent, rs_mb)

    run.defvjp(run_fwd, run_bwd)
    outputs, aux = run(x_mb, staged, rs_mb)
    return outputs.reshape(B, S, D), aux


def _interleaved_pipeline(
    x: jax.Array,
    blocks: Any,
    call,                  # call(x, layer_params, rs) -> (y, aux)
    mesh: Mesh,
    axis: str,
    M: int,
    V: int,
    rs_mb: Any = None,     # row-state leaves [M, mb, ...] (see caller)
) -> tuple[jax.Array, jax.Array]:
    """Virtual-stage (interleaved) schedule: chunk c of V*pp lives on device
    c mod pp; tick t runs chunk s on microbatch t-s; ppermute is the full
    ring (the wrap link carries a microbatch into its next lap). M <= pp
    keeps exactly one of a device's V chunks active per tick, so the
    schedule is a uniform SPMD scan and ``jax.grad`` of it IS the
    interleaved backward. See the module docstring for the bubble math.
    """
    pp = mesh.shape[axis]
    B, S, D = x.shape
    L = jax.tree.leaves(blocks)[0].shape[0]
    if V < 1:
        raise ValueError(f"pp_virtual_stages={V} must be >= 1")
    if L % (V * pp):
        raise ValueError(
            f"n_layers {L} not divisible by pp*pp_virtual_stages "
            f"({pp}*{V})"
        )
    if M > pp:
        raise ValueError(
            f"interleaved schedule needs pp_microbatches ({M}) <= pp "
            f"({pp}): a device may only have one active chunk per tick; "
            f"raise pp_virtual_stages (not M) to amortize the bubble"
        )
    mb = B // M
    Lc = L // (V * pp)

    # Round-robin chunk layout: device d owns chunks {j*pp + d}. The
    # stacked params are sharded contiguously on the layer dim, so this
    # static gather is a per-step resharding of the stage params (cost ~
    # one ZeRO-3 param all-gather; see module docstring).
    perm = jnp.asarray(
        [
            (j * pp + d) * Lc + i
            for d in range(pp)
            for j in range(V)
            for i in range(Lc)
        ],
        jnp.int32,
    )
    staged = jax.tree.map(
        lambda a: jnp.take(a, perm, axis=0).reshape(
            pp, V, Lc, *a.shape[1:]
        ),
        blocks,
    )
    x_mb = x.reshape(M, mb, S, D)

    def local(x_mb, staged, rs_mb):
        chunks = jax.tree.map(lambda a: a[0], staged)   # [V, Lc, ...]
        stage = lax.axis_index(axis)
        T = M + V * pp - 1
        hop = _make_hop(pp, axis, wrap=True)
        is_last = stage == pp - 1

        def run_chunk(c, j, rs):
            cp = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, j, 0, keepdims=False),
                chunks,
            )

            def scan_fn(h, bp):
                y, aux = call(h, bp, rs)
                return y, aux

            y, aux = lax.scan(scan_fn, c, cp)
            return y, aux.sum()

        def tick(carry, t):
            state, outputs, aux_acc = carry
            dt = t - stage
            j = jnp.clip(dt // pp, 0, V - 1)        # this device's chunk lap
            active = (dt >= 0) & (dt % pp < M) & (dt // pp < V)
            # Chunk 0 (device 0, lap 0) injects fresh microbatches; every
            # other (device, lap) consumes the ring.
            inject = x_mb[jnp.clip(t, 0, M - 1)]
            cur = jnp.where((stage == 0) & (t < M), inject, state)
            # Active microbatch index: dt mod pp (lap-invariant); row
            # state is a static lookup, never on the ring.
            rs = jax.tree.map(
                lambda a: a[jnp.clip(dt % pp, 0, M - 1)], rs_mb
            )
            out, aux_t = run_chunk(cur, j, rs)
            aux_acc = aux_acc + jnp.where(active, aux_t, 0.0)
            # The final chunk (device pp-1, lap V-1) emits mb m at tick
            # t = m + V*pp - 1.
            out_idx = jnp.clip(t - (V * pp - 1), 0, M - 1)
            emit = is_last & active & (j == V - 1)
            outputs = outputs.at[out_idx].set(
                jnp.where(emit, out, outputs[out_idx])
            )
            state = hop(out)
            return (state, outputs, aux_acc), None

        carry0 = jax.tree.map(
            lambda a: lax.pcast(a, (axis,), to="varying"),
            (
                jnp.zeros_like(x_mb[0]),
                jnp.zeros_like(x_mb),
                jnp.zeros((), jnp.float32),
            ),
        )
        (_, outputs, aux_acc), _ = lax.scan(tick, carry0, jnp.arange(T))
        outputs = lax.psum(
            jnp.where(is_last, outputs, jnp.zeros_like(outputs)), axis
        )
        aux = lax.psum(aux_acc, axis) / M
        return outputs, aux

    outputs, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axis), jax.tree.map(lambda _: P(), rs_mb)),
        out_specs=(P(), P()),
        axis_names={axis},
        check_vma=False,
    )(x_mb, staged, rs_mb)
    return outputs.reshape(B, S, D), aux
