"""Mixture-of-experts layer: capacity-based top-k dispatch, and a dropless
grouped dispatch where the capacity one provably drops nothing.

Covers the reference's Mixtral 8x7B workload (BASELINE.json:10, "expert-
parallel all-to-all"). Everything is static-shaped for XLA. The three
capacity dispatch modes (``model.moe_dispatch``) give every expert a bucket of
``moe_capacity`` rows per batch row and drop what overflows it
(Switch-style); they have identical semantics where their drop rules coincide
(see each docstring):

  - **einsum** — dispatch/combine are einsums against a static-capacity
    one-hot tensor; expert parallelism is purely a sharding choice (expert
    weight axis on ``ep``; XLA inserts the all-to-all at the dispatch/
    combine boundaries). Simple and robust, but the one-hot contractions
    cost ~2*S*(E*C)*D extra matmul FLOPs per layer (~12 % of expert FLOPs
    at Mixtral shapes) and materialize a [B,S,E,C] float tensor.
  - **sorted** — the ragged dispatch: integer routing (cumsum positions),
    tokens scattered into [E, C] capacity buckets by index, batched
    expert matmuls on the bucketed activations, combine by gather. The
    TPU-static equivalent of "argsort tokens by expert + segment-sliced
    expert matmuls": no one-hot contractions, no [B,S,E,C] tensor —
    dispatch cost drops from matmul FLOPs to pure memory movement.
    Sharding stays SPMD-automatic, so it composes like einsum.
  - **sorted_a2a** — the sorted dispatch inside an explicit ``shard_map``
    over ``ep`` with ``lax.all_to_all`` moving capacity buckets to the
    expert owners (the literal NCCL-a2a structure of the reference,
    BASELINE.json:10). Tokens are routed per ep-local sequence slice, so
    overflow drops are per-slice rather than global-priority.

A bucket costs its full capacity whether filled or not: at the only dropless
capacity (``capacity_factor = E / k``, C = S) that is all E experts over every
position, E / k times the routed work. So within the sorted modes
``moe_dispatch`` takes the **grouped** path (``moe_mlp_grouped``) wherever
``takes_grouped_path`` holds — the capacity is dropless, no ``ep`` axis is
live, and the routed rows plus tile rounding are fewer than the bucket rows —
all three known at trace time from shapes, config and mesh: the k*T routed
assignments are sorted by expert and multiplied by grouped matmuls
(``ops.grouped_matmul``), padded positions of a prefill block not routed at
all. Prefill blocks of a dropless model go grouped; decode-sized blocks, a
capacity that drops (training at 1.25) and expert-parallel layouts keep their
buckets. ``einsum`` mode is never rerouted (it is the plain form the others
are tested against).

At ONE position a row (``[B, 1, D]``: the decode step) a bucket is one row
deep, a position's k choices are k different experts and nothing overflows:
an expert's bucket of a batch row holds that row or nothing. So
``moe_mlp_sorted`` builds no bucket tensor there: the experts' input is the
block itself, broadcast over the experts (``_broadcast_dispatch``), the same
expert matmuls run over the same E x B rows and the combine gathers the k
rows a position's router chose, as it does from buckets. A block of more
positions (a verify block, training) is scattered into buckets as before.

Aux load-balancing loss follows Switch/Mixtral: E * sum_e f_e * p_e.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from orion_tpu.config import ModelConfig


def moe_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    """An expert's bucket: its even share of the assignments (over ALL the
    experts the router chooses among, held here or not) times the factor."""
    cap = int(cfg.capacity_factor * tokens_per_group * cfg.n_experts_per_token
              / cfg.resolved_router_width)
    return max(cap, 1)


def _held(idx: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """(index among the experts held here, whether the chosen expert is one
    of them) of router choices ``idx``. A layer that holds every expert
    holds them all at their own index."""
    if not cfg.holds_expert_share:
        return idx, jnp.ones(idx.shape, bool)
    local = idx - cfg.expert_offset
    return local, (local >= 0) & (local < cfg.n_experts)


@jax.named_scope("router")
def _router_topk(
    x: jax.Array, router_w: jax.Array, cfg: ModelConfig,
    bias: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shared router head: (probs [B,S,E] f32, gate [B,S,k] f32 renormalized,
    idx [B,S,k] int32). ONE function with two scorings
    (``model.router_score``): a softmax over the experts, or each logit's
    sigmoid with the top-k chosen on score + ``bias`` [E] (``moe.router_bias``,
    under ``model.router_bias``) and the gates the scores WITHOUT it. With
    ``model.n_group`` > 1 the choice is group-limited: the experts in
    n_group groups of equal size, a group's score the sum of its two largest
    (score + bias), the best ``model.topk_group`` groups kept and the top-k
    taken inside them (the others' scores set to 0 before it, as the
    published code does; the gates still read the scores themselves).

    Top-k is argsort + a one-hot product rather than ``lax.top_k`` +
    gather: identical values/indices (verified in tests), negligible cost
    at router width E, and — unlike top_k and gather's scatter transpose —
    it survives checkify's index-check rewrite in this jax version, so
    ``runtime.checkify`` keeps its FULL check set on MoE models too.
    """
    E = router_w.shape[-1]      # the router's width is the router's own
    logits = jnp.einsum(
        "bsd,de->bse", x, router_w, preferred_element_type=jnp.float32
    )
    if cfg.router_score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        chosen_on = probs if bias is None else probs + bias.astype(probs.dtype)
    else:
        chosen_on = probs = jax.nn.softmax(logits, axis=-1)
    if cfg.n_group > 1:
        chosen_on = _keep_groups(chosen_on, cfg)
    idx = jnp.argsort(-chosen_on, axis=-1)[
        ..., : cfg.n_experts_per_token
    ].astype(jnp.int32)
    onehot = jax.nn.one_hot(idx, E, dtype=probs.dtype)   # [B,S,k,E]
    gate = (probs[..., None, :] * onehot).sum(-1)        # scatter-free gather
    if cfg.router_score == "sigmoid":
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
    else:
        gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)  # renormalize
    if cfg.router_scale != 1.0:
        gate = gate * cfg.router_scale
    # remat="names" (models/transformer.REMAT_SAVE_NAMES) saves the gates:
    # [B,S,k] f32 is near-free to store and pins the softmax/argsort chain
    # every dispatch mode's backward needs. No-op under other policies.
    gate = checkpoint_name(gate, "moe_router_gate")
    if cfg.router_score == "sigmoid":
        # What the load-balance statistics read: the scores as shares.
        probs = probs / probs.sum(-1, keepdims=True)
    return probs, gate, idx


def _keep_groups(scores: jax.Array, cfg: ModelConfig) -> jax.Array:
    """``scores`` [B, S, E] with every expert outside the best
    ``topk_group`` of ``n_group`` groups set to 0 (a group's score: the sum
    of its two largest; argsort + one-hot as in ``_router_topk``)."""
    G = cfg.n_group
    grouped = scores.reshape(*scores.shape[:-1], G, -1)       # [B, S, G, E/G]
    best2 = -jnp.sort(-grouped, axis=-1)[..., :2].sum(-1)     # [B, S, G]
    kept = jnp.argsort(-best2, axis=-1)[..., : cfg.topk_group]
    keep = jax.nn.one_hot(kept, G, dtype=scores.dtype).sum(-2)  # [B, S, G]
    return jnp.where(keep[..., None] > 0, grouped, 0.0).reshape(scores.shape)


@jax.named_scope("router")
def _aux_stats(
    probs: jax.Array, idx: jax.Array, cfg: ModelConfig
) -> tuple[jax.Array, jax.Array]:
    """Per-expert (assignment fraction [E], mean router prob [E]) — the two
    token-mean statistics of the Switch load-balance loss. Token means
    compose across equal-sized shards by plain averaging, so sharded
    callers pmean these BEFORE taking the product (the loss is bilinear in
    the stats, not linear in per-shard losses)."""
    E, k = probs.shape[-1], cfg.n_experts_per_token
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [B,S,k,E]
    frac = onehot.sum(axis=2).mean(axis=(0, 1)) / k
    mean_prob = probs.mean(axis=(0, 1))
    return frac, mean_prob


@jax.named_scope("router")
def _aux_loss(probs: jax.Array, idx: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Switch eq. 4 load-balance loss: E * sum_e fraction_e * mean-prob_e."""
    frac, mean_prob = _aux_stats(probs, idx, cfg)
    return probs.shape[-1] * jnp.sum(frac * mean_prob)


def route(
    x: jax.Array, router_w: jax.Array, cfg: ModelConfig,
    bias: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Router: returns (dispatch [B,S,E,C], combine [B,S,E,C], aux_loss)."""
    B, S, _ = x.shape
    E, k = cfg.n_experts, cfg.n_experts_per_token
    C = moe_capacity(cfg, S)

    probs, gate, idx = _router_topk(x, router_w, cfg, bias)

    with jax.named_scope("dispatch"):
        # Slot-major priority: all slot-0 (top-1) choices claim capacity
        # before any slot-1 choice, matching Switch-Transformer semantics.
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [B,S,k,E]
        prio = onehot.transpose(0, 2, 1, 3).reshape(B, k * S, E)  # [B,k*S,E]
        pos = jnp.cumsum(prio, axis=1) - prio  # position within expert
        keep = (pos < C).astype(jnp.float32) * prio
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
        disp_flat = keep[..., None] * pos_oh  # [B,k*S,E,C]
        disp = disp_flat.reshape(B, k, S, E, C).sum(axis=1)  # [B,S,E,C]

        gate_slot = gate.transpose(0, 2, 1).reshape(B, k, S)[..., None, None]
        comb = (
            disp_flat.reshape(B, k, S, E, C) * gate_slot
        ).sum(axis=1)  # [B,S,E,C]

    return disp, comb, _aux_loss(probs, idx, cfg)


def moe_mlp(
    x: jax.Array, params: dict[str, Any], cfg: ModelConfig
) -> tuple[jax.Array, jax.Array]:
    """MoE feed-forward. x: [B,S,D] -> ([B,S,D], aux_loss).

    params: router [D,E]; w_in, w_gate [E,D,F]; w_out [E,F,D].
    Expert-parallel: shard the leading E axis of w_* (and the E axis of the
    einsum operands) on the ``ep`` mesh axis.
    """
    dtype = x.dtype
    disp, comb, aux = route(
        x, params["router"], cfg, params.get("router_bias"))
    with jax.named_scope("dispatch"):
        disp = disp.astype(dtype)
        comb = comb.astype(dtype)
        # Dispatch: [B,S,E,C] x [B,S,D] -> (E,B,C,D) capacity buckets.
        xin = jnp.einsum("bsec,bsd->ebcd", disp, x)
    out = _expert_ffn(xin, params, cfg)
    with jax.named_scope("dispatch"):
        y = jnp.einsum("bsec,ebcd->bsd", comb, out)
    return y, aux.astype(jnp.float32)


def route_indices(
    x: jax.Array, router_w: jax.Array, cfg: ModelConfig,
    bias: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Integer routing for the sorted dispatch.

    Returns (idx [B,S,k] int32 expert per assignment, gate [B,S,k] f32,
    pos [B,S,k] int32 position within the expert's capacity, keep [B,S,k]
    bool, aux_stats — see _aux_stats; callers combine shard stats before
    forming the loss). Drop semantics are IDENTICAL to ``route``: slot-major
    priority (every top-1 claim beats any top-2 claim), first-come within a
    slot, capacity C per expert per batch row — the int32 cumsum here and
    route()'s float one-hot cumsum count the same stream.
    """
    B, S, _ = x.shape
    E, k = cfg.resolved_router_width, cfg.n_experts_per_token
    C = moe_capacity(cfg, S)

    probs, gate, idx = _router_topk(x, router_w, cfg, bias)

    with jax.named_scope("dispatch"):
        # Slot-major assignment stream [B, k*S]: all slot-0 choices precede
        # any slot-1 choice (matches route()'s prio layout).
        idx_km = idx.transpose(0, 2, 1).reshape(B, k * S)
        onehot = jax.nn.one_hot(idx_km, E, dtype=jnp.int32)  # [B, kS, E]
        pos_all = jnp.cumsum(onehot, axis=1) - onehot        # count before me
        pos_km = jnp.take_along_axis(
            pos_all, idx_km[..., None], axis=-1
        )[..., 0]                                            # [B, kS]
        pos = pos_km.reshape(B, k, S).transpose(0, 2, 1)     # [B, S, k]
        keep = pos < C
    # Sanitizer hook (SURVEY.md §6): routing indices feed scatter/gather —
    # and, on the a2a path, a cross-device all_to_all — INSIDE shard_map
    # regions where checkify cannot reach; an OOB here otherwise surfaces
    # as silent drops or NaNs. No-op unless model.debug_asserts.
    from orion_tpu.runtime.asserts import device_assert

    device_assert(
        cfg.debug_asserts,
        (idx >= 0).all() & (idx < E).all(),
        "moe_route_idx",
        f"router expert index outside [0, {E})",
    )
    # pos is a count-before-me over the [B, kS] assignment stream, so the
    # genuine invariant is 0 <= pos < k*S (NOT pos < C, which is what
    # ``keep`` is defined as and would be a tautology): corruption of the
    # cumsum math or of idx skews positions outside the stream bound.
    device_assert(
        cfg.debug_asserts,
        (pos >= 0).all() & (pos < k * S).all(),
        "moe_route_pos",
        f"capacity position outside the assignment-stream bound [0, {k * S})",
    )
    return idx, gate, pos, keep, _aux_stats(probs, idx, cfg)


@jax.named_scope("experts")
def _expert_ffn(xin: jax.Array, params: dict[str, Any], cfg: ModelConfig
                ) -> jax.Array:
    """Batched expert feed-forward on capacity buckets. xin: [E, B, C, D]."""
    h_in = jnp.einsum("ebcd,edf->ebcf", xin, params["w_in"])
    if cfg.is_gated_mlp:
        from orion_tpu.models.transformer import _gate_act

        h_gate = jnp.einsum("ebcd,edf->ebcf", xin, params["w_gate"])
        h = _gate_act(cfg)(h_gate) * h_in
    else:
        h = jax.nn.gelu(h_in)
    return jnp.einsum("ebcf,efd->ebcd", h, params["w_out"])


@jax.named_scope("dispatch")
def _scatter_dispatch(x, idx, pos, keep, E, C):
    """Tokens -> capacity buckets by index. x: [B,S,D] -> [E, B, C, D].

    Dropped assignments land in a trash row (C) that is sliced off; kept
    (expert, pos) pairs are unique per batch row, so the scatter-add never
    actually collides and its gradient is the plain gather transpose.

    Called for blocks of MORE than one position a row by ``moe_mlp_sorted``
    (a verify block, training at a capacity that drops) and for every block
    by ``moe_mlp_sorted_a2a`` (its buckets are what the all-to-all moves);
    one position a row is ``_broadcast_dispatch``'s.
    """
    B, S, D = x.shape
    k = idx.shape[-1]
    b_ix = jnp.broadcast_to(jnp.arange(B)[:, None, None], (B, S, k))
    pos_c = jnp.where(keep, pos, C)
    xin = jnp.zeros((B, E, C + 1, D), x.dtype)
    xv = jnp.broadcast_to(x[:, :, None, :], (B, S, k, D))
    xin = xin.at[b_ix, idx, pos_c].add(xv, mode="drop")
    return xin[:, :, :C].transpose(1, 0, 2, 3)               # [E, B, C, D]


@jax.named_scope("dispatch")
def _broadcast_dispatch(x, E):
    """``_scatter_dispatch`` at one position a row. x: [B,1,D] -> [E, B, 1, D].

    The capacity is 1, a position's k choices are k different experts, so
    ``pos`` is 0 and nothing is dropped: the bucket of (expert, batch row)
    held that row or zeros. Every bucket gets the row: what an expert makes
    of a row nobody routed to it is computed as it was from zeros (the
    matmuls run over all E x B rows either way) and is never gathered, so
    neither it nor its cotangent (the gather's transpose leaves it zero)
    reaches a result. No zeros, no scatter-add, no re-laid bucket tensor.
    """
    B, _, D = x.shape
    return jnp.broadcast_to(x[None], (E, B, 1, D))


@jax.named_scope("dispatch")
def _gather_combine(out, idx, pos, keep, gate, dtype):
    """Inverse of _scatter_dispatch: per-assignment gather + gate-weighted
    sum over the k slots. out: [E, B, C, D] -> [B, S, D]."""
    B = out.shape[1]
    S, k = idx.shape[1], idx.shape[2]
    out_b = out.transpose(1, 0, 2, 3)                        # [B, E, C, D]
    b_ix = jnp.broadcast_to(jnp.arange(B)[:, None, None], (B, S, k))
    pos_cl = jnp.minimum(pos, out.shape[2] - 1)
    got = out_b[b_ix, idx, pos_cl]                           # [B, S, k, D]
    w = (gate * keep.astype(gate.dtype)).astype(dtype)
    return jnp.einsum("bskd,bsk->bsd", got.astype(dtype), w)


def moe_mlp_sorted(
    x: jax.Array, params: dict[str, Any], cfg: ModelConfig
) -> tuple[jax.Array, jax.Array]:
    """The ragged (sort-class) dispatch: einsum-free, same drop semantics as
    ``moe_mlp``. Sharding is SPMD-automatic (expert axis of the weights and
    the [E, ...] buckets shard on ``ep``), so it composes with every other
    axis exactly like the einsum path."""
    dtype = x.dtype
    E, C = cfg.n_experts, moe_capacity(cfg, x.shape[1])
    idx, gate, pos, keep, (frac, mp) = route_indices(
        x, params["router"], cfg, params.get("router_bias"))
    if cfg.holds_expert_share:
        # Assignments to experts held elsewhere are dropped here (their
        # bucket position was counted per expert, so the held ones keep
        # theirs); the gates stay normalised over all k chosen.
        with jax.named_scope("dispatch"):
            idx, held = _held(idx, cfg)
            keep = keep & held
            idx = jnp.clip(idx, 0, E - 1)
    if x.shape[1] == 1:
        xin = _broadcast_dispatch(x, E)     # a bucket IS the row: no scatter
    else:
        xin = _scatter_dispatch(x, idx, pos, keep, E, C)
    out = _expert_ffn(xin, params, cfg)
    y = _gather_combine(out, idx, pos, keep, gate, dtype)
    with jax.named_scope("router"):
        aux = cfg.resolved_router_width * jnp.sum(frac * mp)
    return y, aux.astype(jnp.float32)


def moe_mlp_sorted_a2a(
    x: jax.Array,
    params: dict[str, Any],
    cfg: ModelConfig,
    mesh,
    *,
    batch_axes: tuple = ("dp", "fsdp"),
) -> tuple[jax.Array, jax.Array]:
    """Sorted dispatch with an EXPLICIT expert all-to-all over the ``ep``
    mesh axis (the reference's NCCL-a2a structure, BASELINE.json:10).

    Inside a ``shard_map``, each device routes its own sequence slice
    (S/ep tokens) into per-expert capacity buckets, one tiled
    ``lax.all_to_all`` hands every bucket to its expert's owner, the owner
    runs the batched expert FFN over its ep*C_loc-deep buckets, and the
    inverse all-to-all returns outputs for local combine. Capacity is per
    slice (C_loc = capacity(S/ep)), so total per-expert capacity matches
    the einsum path but overflow drops are per-slice rather than global
    slot-major — identical results whenever nothing overflows.

    Composes with dp/fsdp (batch axes pass through), tp (weights' F
    axis), and pp: inside the pipeline's pp-manual region this shard_map
    NESTS, bound to the context abstract mesh (see below).
    """
    sp_ax = cfg.sequence_axis or "sp"
    ep = mesh.shape.get("ep", 1)
    if ep == 1:
        return moe_mlp_sorted(x, params, cfg)
    # Inside the pipeline's shard_map (manual over pp) a nested shard_map
    # must bind the CONTEXT abstract mesh (ops._dispatch.manual_context).
    # The ep/tp/sp/batch axes this dispatch goes manual over are still Auto
    # in that context, so sorted_a2a composes with pp; per-microbatch token
    # slices only shrink C_loc, the same per-slice drop semantics as any
    # batch sharding.
    from orion_tpu.ops._dispatch import manual_context

    mesh, _ = manual_context(mesh)
    E = cfg.n_experts
    if E % ep:
        raise ValueError(f"n_experts {E} not divisible by ep={ep}")
    if x.shape[1] % (mesh.shape.get(sp_ax, 1) * ep):
        raise ValueError(
            f"seq len {x.shape[1]} not divisible by sp*ep for the a2a "
            f"token slicing"
        )

    has_gate = "w_gate" in params
    if "router_bias" in params or cfg.n_group > 1:
        raise ValueError(
            "model.router_bias / model.n_group are not carried into "
            "moe_dispatch=sorted_a2a's shard_map: use moe_dispatch=sorted")

    def body(x_loc, router_w, w_in, w_out, *gate_w):
        p_loc = {"w_in": w_in, "w_out": w_out}
        if has_gate:
            p_loc["w_gate"] = gate_w[0]
        C_loc = moe_capacity(cfg, x_loc.shape[1])
        idx, gate, pos, keep, (frac, mp) = route_indices(
            x_loc, router_w, cfg)
        xin = _scatter_dispatch(x_loc, idx, pos, keep, E, C_loc)
        # [E, B_loc, C_loc, D] -> [E/ep, B_loc, ep*C_loc, D]: bucket j of
        # expert e travels to e's owner; owners see every slice's bucket.
        with jax.named_scope("dispatch"):
            xin = lax.all_to_all(
                xin, "ep", split_axis=0, concat_axis=2, tiled=True)
        out = _expert_ffn(xin, p_loc, cfg)
        # The F axis of the expert weights is tp-sharded, so the w_out
        # contraction leaves each tp shard holding a partial sum: reduce
        # over tp BEFORE the inverse a2a (megatron row-parallel pattern).
        with jax.named_scope("dispatch"):
            if mesh.shape.get("tp", 1) > 1:
                out = lax.psum(out, "tp")
            out = lax.all_to_all(
                out, "ep", split_axis=2, concat_axis=0, tiled=True)
        y = _gather_combine(out, idx, pos, keep, gate, x_loc.dtype)
        # Combine the aux STATS across equal-sized token/batch shards, then
        # form the bilinear loss — this reproduces the global-token aux
        # exactly (a pmean of per-shard losses would not: the loss is a
        # product of two token means). tp shards carry identical values.
        axes = ("dp", "fsdp", "ep", sp_ax, "tp")
        with jax.named_scope("router"):
            frac = lax.pmean(frac, axis_name=axes)
            mp = lax.pmean(mp, axis_name=axes)
            aux = E * jnp.sum(frac * mp)
        return y, aux

    x_spec = P(batch_axes, (sp_ax, "ep"), None)
    in_specs = [
        x_spec,
        P(None, None),                 # router replicated
        P("ep", None, "tp"),           # w_in  [E, D, F]
        P("ep", "tp", None),           # w_out [E, F, D]
    ]
    args = [x, params["router"], params["w_in"], params["w_out"]]
    if has_gate:
        in_specs.append(P("ep", None, "tp"))   # w_gate [E, D, F]
        args.append(params["w_gate"])
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    y, aux = mapped(*args)
    return y, aux.astype(jnp.float32)


def _bucket_rows(cfg: ModelConfig, B: int, S: int) -> int:
    """Expert-matmul rows the capacity dispatches compute for a [B, S, D]
    block: every expert's bucket at full capacity, filled or not."""
    return cfg.n_experts * B * moe_capacity(cfg, S)


def takes_grouped_path(cfg: ModelConfig, B: int, S: int, mesh=None) -> bool:
    """The rule by which ``moe_dispatch`` leaves the capacity buckets of its
    sorted modes for ``moe_mlp_grouped`` (``einsum`` mode never does: it is
    the plain form the others are tested against); every term is known at
    trace time.

    (a) ``moe_capacity(cfg, S) >= S``: an expert's bucket holds a whole row,
        so the capacity dispatch provably drops nothing and both paths
        define the same function;
    (b) no live ``ep`` axis: the expert-parallel layouts keep their buckets
        (static shapes for the all-to-all);
    (c) the grouped matmul visits fewer MXU rows than the buckets hold, its
        tile rounding counted at the worst case (every expert's group ends
        inside a tile of the widest row tile, ``ROW_TILE``, whatever tile
        the call's shape then gets): ``k*T + E*tm < E*B*C``. Decode
        (``[32, 1, D]``: 64 + 8*tm against 256) stays on the buckets, where
        all E experts' weights are read whichever path runs; a bucket there
        is one row deep and holds the batch row itself or nothing, so
        ``moe_mlp_sorted`` hands the experts the block broadcast over them
        and scatters nothing (``_broadcast_dispatch``).
    """
    from orion_tpu.ops.grouped_matmul import ROW_TILE

    if cfg.moe_dispatch == "einsum" or moe_capacity(cfg, S) < S:
        return False
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        return False
    E, k = cfg.n_experts, cfg.n_experts_per_token
    return k * B * S + E * ROW_TILE < _bucket_rows(cfg, B, S)


def expert_rows(cfg: ModelConfig, B: int, S: int,
                n_valid: Optional[int] = None, mesh=None) -> int:
    """Expert-matmul rows per MoE layer that ``moe_dispatch`` computes for a
    [B, S, D] block of which ``n_valid`` positions (all, when None) are real:
    the routed assignments ``k * n_valid`` on the grouped path (its tile
    rounding, at most ``E * tm`` rows a matmul, depends on the routing and
    is NOT counted), all ``E * B * C`` bucket rows on the capacity paths."""
    if takes_grouped_path(cfg, B, S, mesh):
        n = B * S if n_valid is None else n_valid
        return cfg.n_experts_per_token * n
    return _bucket_rows(cfg, B, S)


def held_row_bound(cfg: ModelConfig, tokens: int) -> int:
    """How many sorted rows ONE pass of the bounded grouped dispatch builds,
    multiplies and combines for a block of ``tokens`` positions in a layer
    that holds ``n_experts`` of the ``router_width`` experts its router
    chooses among: twice the share's expectation ``k * tokens * n_experts /
    router_width``, rounded up to the grouped matmul's row tile, and never
    more than the ``k * tokens`` assignments there are.

    Twice: the held count is a sum over thousands of choices, so a router
    that spreads its load stays within a few per cent of the expectation,
    and the room is for one that favours the experts held here. More room
    moves that many more rows in EVERY block (the form costs what it moves:
    PR 51's layer timings in ``bounds_held_rows``), while a block that passes
    the bound costs one further pass and loses nothing (``_bounded_rows``).
    The prefill program counts those blocks
    (``engine.timing["prefill_held_bound_overflows"]``)."""
    from orion_tpu.ops.grouped_matmul import ROW_TILE

    kt = cfg.n_experts_per_token * tokens
    twice = -(-2 * kt * cfg.n_experts // cfg.resolved_router_width)
    return min(-(-twice // ROW_TILE) * ROW_TILE, kt)


def bounds_held_rows(cfg: ModelConfig, tokens: int) -> bool:
    """The rule by which ``moe_mlp_grouped`` moves ``held_row_bound`` rows a
    pass and not all ``k * tokens``: the layer holds a share of its experts
    and the bound is at most a QUARTER of the assignments, both known at
    trace time. Measured on the v5e, one layer's dispatch and experts, the
    bound against the whole (builder, PR 51; ``tools/moe_dispatch_bench.py
    --prefill`` draws it again): at an eighth 23.6 -> 8.2 ms, at a half 10.3
    -> 11.6 ms, at the whole 12.4 -> 14.2 ms. The bounded form pays a
    scatter-add into float32 token rows where the whole pays an un-sort and
    a product over k, so it wins only where it moves far fewer rows. A layer
    that holds every expert has nothing to bound."""
    return (cfg.holds_expert_share
            and 4 * held_row_bound(cfg, tokens)
            <= cfg.n_experts_per_token * tokens)


@jax.custom_vjp
def _forward_only(y: jax.Array) -> jax.Array:
    """The identity, with a reverse mode that says why there is none."""
    return y


def _forward_only_fwd(y):
    raise NotImplementedError(
        "moe_mlp_grouped bounds the rows of a layer that holds a small "
        "share of its experts (moe.bounds_held_rows) and computes what "
        "passes the bound under lax.while_loop, which has no reverse mode: "
        "a held share is a serving layout (model.router_width > "
        "model.n_experts); differentiate a model that holds every expert")


_forward_only.defvjp(_forward_only_fwd, lambda *_: None)


def _bounded_rows(x2, order, group_sizes, gate, R: int, experts):
    """The grouped dispatch of ``x2`` [T, D] over the first rows of the
    sorted assignments alone, ``R`` (``held_row_bound``) at a time: the
    held assignments sort first, so one pass of R rows takes them all
    wherever they are no more than R, and the gather, the expert matmuls
    (``experts(rows, sizes)``) and the select are [R, .] and never
    [k T, .]. The combine adds each gated result row onto its token (a
    scatter-add over ``order // k`` in float32, what the whole form's
    product over k accumulates in). Returns y [T, D] float32.

    Nothing is dropped: the first pass is traced in line, and while held
    rows remain behind it (a skewed router) further passes of R rows run
    under ``lax.while_loop``, each over its own window of the same sort, an
    expert's group cut at the window's ends. A row's result does not depend
    on which rows share its matmul, so the passes add up to what the whole
    computes.

    ``lax.while_loop`` has no reverse mode, and a held share exists only
    in serving (``moe_dispatch`` refuses it under ``ep``): ``jax.grad``
    through a bounded layer raises ``_forward_only``'s sentence."""
    T, D = x2.shape
    k = order.shape[0] // T
    with jax.named_scope("dispatch"):
        ends = jnp.cumsum(group_sizes)
        n_held = ends[-1]
        order = jnp.pad(order, (0, -order.shape[0] % R))
        gate = gate.reshape(T * k).astype(x2.dtype)

    def one(lo, y):
        with jax.named_scope("dispatch"):
            rows = lax.dynamic_slice(order, (lo,), (R,))
            tok = rows // k
            sizes = (jnp.clip(ends, lo, lo + R)
                     - jnp.clip(ends - group_sizes, lo, lo + R))
            xs = x2[tok]                                     # [R, D]
        out = experts(xs, sizes)                             # [R, D]
        with jax.named_scope("dispatch"):
            # Rows behind the last group are never computed (the kernel
            # leaves them uninitialised): the select keeps them out.
            live = (lo + jnp.arange(R) < n_held)[:, None]
            z = jnp.where(live, out, 0).astype(jnp.float32) * (
                gate[rows].astype(jnp.float32)[:, None])
            return y.at[tok].add(z)

    y = one(0, jnp.zeros((T, D), jnp.float32))
    _, y = lax.while_loop(
        lambda c: c[0] < n_held,
        lambda c: (c[0] + R, one(c[0], c[1])), (jnp.int32(R), y))
    return _forward_only(y)


def moe_mlp_grouped(
    x: jax.Array,
    params: dict[str, Any],
    cfg: ModelConfig,
    valid: Optional[jax.Array] = None,
    mesh=None,
    layer_stack: Optional[tuple[dict[str, Any], jax.Array]] = None,
) -> tuple[jax.Array, jax.Array]:
    """The dropless dispatch: only the routed (token, expert) assignments
    are multiplied. The block is flattened to T = B*S tokens, its k*T
    assignments stable-sorted by expert, and the three expert matmuls run
    as grouped matmuls over the sorted rows (``ops.grouped_matmul``), so an
    expert costs the rows the router gave it and not a capacity bucket.
    Same mathematics as ``moe_mlp_sorted`` where that drops nothing: a
    row's result does not depend on which rows share its matmul.

    ``valid`` [B, S] bool marks the real positions of a padded block
    (prefill): assignments of the others sort behind the last group, are in
    no ``group_sizes`` entry, and their output is zero.

    ``layer_stack`` = (the layer-stacked MoE weights [L, E, ...], this
    layer's index) lets the matmuls read the expert matrices out of the
    stack in place (``ops.grouped_matmul``'s ``layer``); ``params`` then
    serves the router, and any matrix the stack holds in another dtype.

    Where the layer holds a small share of its experts (``bounds_held_rows``)
    only the share's rows are gathered, multiplied and combined
    (``_bounded_rows``): the rest of the k*T sort behind them under key E
    and were never multiplied, yet the whole form gathers, selects and
    un-sorts them too.
    """
    from orion_tpu.ops.grouped_matmul import grouped_matmul

    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.n_experts_per_token
    T = B * S
    probs, gate, idx = _router_topk(
        x, params["router"], cfg, params.get("router_bias"))

    partial = valid is not None or cfg.holds_expert_share
    with jax.named_scope("dispatch"):
        # Assignment a = t*k + j (token t, slot j); invalid ones, and those
        # to experts held elsewhere, get key E.
        key, held = (a.reshape(T * k) for a in _held(idx, cfg))
        gate = gate.reshape(T, k)
        if valid is not None:
            held = held & jnp.repeat(valid.reshape(T), k)
        if partial:
            key = jnp.where(held, key, E)
            gate = gate * held.reshape(T, k).astype(gate.dtype)
        order = jnp.argsort(key, stable=True)                # [kT]
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(E, dtype=key.dtype), axis=0,
            dtype=jnp.int32)                                 # [E]

    def gmm(a, name, sizes, contract_tp=False):
        w, layer = params[name], None
        if layer_stack is not None and layer_stack[0][name].dtype == a.dtype:
            w, layer = layer_stack[0][name], layer_stack[1]
        return grouped_matmul(a, w, sizes, impl=cfg.kernels, mesh=mesh,
                              contract_tp=contract_tp, layer=layer)

    @jax.named_scope("experts")
    def experts(xs, sizes):
        h_in = gmm(xs, "w_in", sizes)
        if cfg.is_gated_mlp:
            from orion_tpu.models.transformer import _gate_act

            h = _gate_act(cfg)(gmm(xs, "w_gate", sizes)) * h_in
        else:
            h = jax.nn.gelu(h_in)
        return gmm(h, "w_out", sizes, contract_tp=True)

    if bounds_held_rows(cfg, T):
        y = _bounded_rows(x.reshape(T, D), order, group_sizes, gate,
                          held_row_bound(cfg, T), experts)
        return (y.astype(x.dtype).reshape(B, S, D),
                _aux_loss(probs, idx, cfg).astype(jnp.float32))
    with jax.named_scope("dispatch"):
        xs = x.reshape(T, D)[order // k]                     # [kT, D]
        if partial:
            # Rows behind the last group are never computed (the kernel
            # leaves them uninitialised); the selects keep what is there out
            # of the result and out of the cotangents.
            live = (jnp.arange(T * k) < group_sizes.sum())[:, None]
            xs = jnp.where(live, xs, 0)
    out = experts(xs, group_sizes)                           # [kT, D]
    with jax.named_scope("dispatch"):
        if partial:
            out = jnp.where(live, out, 0)
        out = out[jnp.argsort(order)].reshape(T, k, D)       # un-sort
        y = jnp.einsum("tkd,tk->td", out, gate.astype(x.dtype))
    return y.reshape(B, S, D), _aux_loss(probs, idx, cfg).astype(jnp.float32)


def moe_dispatch(
    x: jax.Array,
    params: dict[str, Any],
    cfg: ModelConfig,
    mesh=None,
    valid: Optional[jax.Array] = None,
    layer_stack: Optional[tuple[dict[str, Any], jax.Array]] = None,
) -> tuple[jax.Array, jax.Array]:
    """Entry point: select the dispatch per ``cfg.moe_dispatch``, and within
    the sorted modes the dropless grouped path where ``takes_grouped_path``
    admits it. ``valid`` [B, S] (real positions of a padded block) and
    ``layer_stack`` (see ``moe_mlp_grouped``) are read by the grouped path
    alone; the capacity paths route every position."""
    mode = cfg.moe_dispatch
    if mode not in ("einsum", "sorted", "sorted_a2a"):
        raise ValueError(f"unknown model.moe_dispatch={mode!r}")
    if cfg.holds_expert_share and (mode != "sorted" or (
            mesh is not None and mesh.shape.get("ep", 1) > 1)):
        raise ValueError(
            "a layer that holds a share of its experts (model.router_width "
            "> model.n_experts) is computed by moe_dispatch=sorted with no "
            "ep axis: the share IS this device's part of the experts")
    if takes_grouped_path(cfg, x.shape[0], x.shape[1], mesh):
        y, aux = moe_mlp_grouped(x, params, cfg, valid, mesh, layer_stack)
    elif mode == "einsum":
        y, aux = moe_mlp(x, params, cfg)
    elif mode == "sorted_a2a" and mesh is not None:
        y, aux = moe_mlp_sorted_a2a(x, params, cfg, mesh)  # ep == 1: sorted
    else:
        y, aux = moe_mlp_sorted(x, params, cfg)
    if "shared" in params:
        shared = _shared_expert(x, params["shared"], cfg)
        with jax.named_scope("shared"):
            y = y + shared
    return y, aux


@jax.named_scope("shared")
def _shared_expert(x: jax.Array, p: dict[str, Any], cfg: ModelConfig
                   ) -> jax.Array:
    """The shared expert, a gated feed-forward every token takes, added
    UNGATED beside the routed ones (assumed: the config names no gate on
    it). ONE function, so that the published modelling code corrects it in
    one place."""
    from orion_tpu.models.transformer import _gate_act

    h = _gate_act(cfg)(jnp.einsum("bsd,df->bsf", x, p["w_gate"])) * (
        jnp.einsum("bsd,df->bsf", x, p["w_in"]))
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"])


def held_rows(x: jax.Array, router_w: jax.Array, cfg: ModelConfig,
              valid: Optional[jax.Array] = None,
              bias: Optional[jax.Array] = None) -> jax.Array:
    """How many of the block's routed (token, expert) assignments fall on
    experts held here (int32 scalar): the rows this layer's expert matmuls
    have to compute. ``valid`` [B, S] as in ``moe_dispatch``. The same
    router head as the dispatch, so XLA computes it once."""
    idx = _router_topk(x, router_w, cfg, bias)[2]
    with jax.named_scope("router"):
        _, held = _held(idx, cfg)
        if valid is not None:
            held = held & valid[..., None]
        return held.sum(dtype=jnp.int32)
