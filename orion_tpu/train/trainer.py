"""The training step loop (reference ``orion.trainer`` equivalent).

Design (SURVEY.md §4 stack A): control crosses host->device once per step —
batch feed in, metric scalars out. Everything else (forward, backward, grad
accumulation, clipping, AdamW update, the DDP psum / ZeRO-3 gathers / TP and
EP collectives implied by the sharding rules) is one jit-compiled XLA program
with donated buffers. Fault injection and preemption-safe resume hook in at
the step boundary (SURVEY.md §6 "Failure detection").
"""

from __future__ import annotations

import logging
import os
import time
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from orion_tpu import metrics as metrics_lib
from orion_tpu.ckpt import CheckpointManager
from orion_tpu.config import Config
from orion_tpu.data import make_loader
from orion_tpu.models import init_params, loss_fn, param_logical_axes
from orion_tpu.parallel import (
    batch_sharding,
    param_shardings,
    zero1_shardings,
)
from orion_tpu.runtime import build_mesh, initialize
from orion_tpu.train.optimizer import (
    Zero1Plan,
    apply_updates,
    global_norm,
    init_opt_state,
    make_schedule,
    tree_all_finite,
)

log = logging.getLogger("orion_tpu.train")

TrainState = dict[str, Any]


class FaultInjected(RuntimeError):
    """Raised by the --inject_fault_at_step test hook (SURVEY.md §6)."""


class RollbackFailed(RuntimeError):
    """Auto-rollback (train.anomaly_limit consecutive anomalies) found no
    intact checkpoint to restore. Retryable by run_with_restarts only in
    the sense that a supervisor restart re-inits from scratch."""


# Each injected fault fires once per (checkpoint dir, step) per process, so
# a supervisor restart that resumes from *before* the fault step does not
# crash again on the same hook — mimicking a transient failure.
_FIRED_FAULTS: set = set()


def zero1_master_split(cfg: Config) -> bool:
    """Whether ZeRO-1 carries a separate dp-sharded master copy.

    Two reasons to split:

    - mixed precision (``param_dtype != dtype``): ``state['params']``
      holds the cast-down working copy the forward reads and
      ``opt['master']`` the sharded full-precision source of truth;
    - a quantized all-gather leg (``zero1_quantize=int8|ag_int8``): the
      gathered params are an int8 round-trip, and WITHOUT a master the
      owner's own shard would re-enter the next update quantized — a
      per-step error random walk that compounds over a long run. With
      the master split the update always reads the exact master shards
      and params are a bounded ONE-step quantization of them (and stay
      bit-identical across replicas, since every device — owner
      included — takes the same gathered bytes).

    Otherwise the params ARE the masters and stay replicated — a separate
    copy would cost memory, not save it."""
    if not cfg.train.zero1:
        return False
    if jnp.dtype(cfg.model.param_dtype) != jnp.dtype(cfg.model.dtype):
        return True
    return cfg.train.zero1_quantize in ("int8", "ag_int8")


def make_zero1_plan(cfg: Config, mesh) -> Optional[Zero1Plan]:
    """The per-leaf ZeRO-1 update-sharding plan (train.zero1), or None."""
    if not cfg.train.zero1:
        return None
    logical = param_logical_axes(cfg.model)
    shapes = jax.eval_shape(
        lambda: init_params(cfg.model, jax.random.key(0))
    )
    zshard, dims = zero1_shardings(mesh, logical, shapes)
    return Zero1Plan(
        axis="dp",
        dims=dims,
        state_shardings=zshard,
        param_shardings=param_shardings(mesh, logical),
        quantize=cfg.train.zero1_quantize,
    )


def init_train_state(cfg: Config, key: jax.Array) -> TrainState:
    params = init_params(cfg.model, key)
    opt = init_opt_state(
        params, cfg.optimizer, master=zero1_master_split(cfg)
    )
    if zero1_master_split(cfg):
        wdt = jnp.dtype(cfg.model.dtype)
        params = jax.tree.map(lambda p: p.astype(wdt), params)
    return {
        "params": params,
        "opt": opt,
        "step": jnp.zeros((), jnp.int32),
    }


def state_shardings(
    cfg: Config, mesh, zero1_plan: Optional[Zero1Plan] = None
) -> TrainState:
    """NamedShardings for the full train state: ZeRO-3 by construction —
    moments share the params' shardings, scalars are replicated. With
    train.zero1 the moments (and the master copy, when split) instead take
    the dp-sharded weight-update layout (parallel.sharding.zero1_shardings)
    so each replica physically holds 1/dp of the optimizer state.
    ``zero1_plan`` lets a caller that already built the plan (the Trainer)
    reuse its layout trees instead of re-tracing the abstract init."""
    if zero1_plan is None:
        zero1_plan = make_zero1_plan(cfg, mesh)
    if zero1_plan is not None:
        pshard = zero1_plan.param_shardings
        mshard = zero1_plan.state_shardings
    else:
        pshard = param_shardings(mesh, param_logical_axes(cfg.model))
        mshard = pshard
    repl = NamedSharding(mesh, P())
    opt = {"mu": mshard, "nu": mshard, "count": repl}
    if zero1_master_split(cfg):
        opt["master"] = mshard
    return {
        "params": pshard,
        "opt": opt,
        "step": repl,
    }


def abstract_train_state(cfg: Config, shardings=None) -> TrainState:
    """ShapeDtypeStructs (with NamedShardings) of the full train state.

    The sharding-aware restore template: Orbax reads each leaf directly into
    its mesh layout instead of materializing host-side (a 70B state would
    host-OOM otherwise). Free function so non-training consumers (e.g. the
    serving CLI restoring params from a trainer checkpoint) don't need a
    Trainer; ``shardings`` defaults to the production rules on a fresh mesh.
    """
    if shardings is None:
        mesh = build_mesh(cfg.parallel, platform=cfg.runtime.platform)
        shardings = state_shardings(cfg, mesh)
    key = jax.random.key(cfg.train.seed)
    shapes = jax.eval_shape(lambda: init_train_state(cfg, key))
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes,
        shardings,
    )


def _require_unmasked_dp_batch(batch, knob: str) -> None:
    """Shared guard for the manual-over-dp paths (grad_quant_bits and the
    quantized zero1 wire legs): the combined ce+moe gradient cannot be
    re-weighted by per-shard valid-token counts after the fact, so a
    uniform pmean would bias shards with few valid tokens. Masked /
    packed batches need the exact (XLA-inserted) reduction."""
    if "loss_mask" in batch:
        raise ValueError(
            f"{knob} does not support loss_mask batches: dp shards with "
            f"unequal valid-token counts need token-weighted reduction; "
            f"use the full-precision automatic path"
        )


def _dp_mean_metrics(loss, aux):
    """Reduce per-shard loss/aux across dp inside a manual region: means
    everywhere except token counts, which accumulate."""
    from jax import lax as _lax

    loss = _lax.pmean(loss, "dp")
    aux = {
        k: _lax.psum(v, "dp") if k == "tokens" else _lax.pmean(v, "dp")
        for k, v in aux.items()
    }
    return loss, aux


def make_train_step(
    cfg: Config,
    schedule: Callable[[jax.Array], jax.Array],
    mesh: Any = None,
    poison: bool = False,
    zero1: Optional[Zero1Plan] = None,
) -> Callable[..., tuple[TrainState, dict[str, jax.Array]]]:
    """Build the compiled per-step function.

    With ``train.anomaly_guard`` the returned callable takes a third
    ``norm_limit`` scalar (the host-maintained spike threshold) and folds a
    donation-safe all-finite + global-norm-spike check into the program:
    an anomalous step selects the PRE-step params/optimizer back out
    bit-identically and reports ``anomaly``/``nonfinite``/``spike`` flags
    in the step metrics. Guard off returns exactly the pre-guard two-arg
    program (trace bit-identical — no finiteness ops are ever staged).

    ``poison=True`` builds the fault-injection variant: the loss is
    multiplied by NaN INSIDE the differentiated function, so real NaNs
    flow through the real backward into every grad leaf (the trainer
    dispatches one step through this program when a FaultInjector "nan"
    spec fires).
    """
    mcfg = cfg.model
    accum = cfg.train.grad_accum
    gdt = (
        jnp.dtype(cfg.train.grad_dtype)
        if cfg.train.grad_dtype is not None else None
    )
    if poison:
        def _loss_fn(p, mb, m, mesh_):
            loss, aux = loss_fn(p, mb, m, mesh_)
            return loss * jnp.float32(jnp.nan), aux
    else:
        _loss_fn = loss_fn

    def _value_and_grad(params, mb):
        """value_and_grad of the loss; under train.grad_dtype the grads are
        taken wrt a downcast param tree, so every stacked per-layer grad
        buffer (the scan-stash traffic, PERF.md) carries that dtype. The
        optimizer upcasts per leaf; with grad_accum the accumulator tree
        stays f32 (zeros_like(params) + bf16 promotes), so only the
        per-microbatch gradient signal is rounded."""
        if gdt is not None:
            params = jax.tree.map(
                lambda p: p.astype(gdt)
                if jnp.issubdtype(p.dtype, jnp.floating) else p,
                params,
            )
        return jax.value_and_grad(_loss_fn, has_aux=True)(
            params, mb, mcfg, mesh
        )

    def loss_and_grads(params, batch):
        if accum == 1:
            (loss, aux), grads = _value_and_grad(params, batch)
            return loss, aux, grads

        # batch leaves are [A, b, S]; scan over microbatches, summing grads.
        def micro(carry, mb):
            acc_grads, acc_loss, acc_aux = carry
            (loss, aux), grads = _value_and_grad(params, mb)
            acc_grads = jax.tree.map(jnp.add, acc_grads, grads)
            acc_loss = acc_loss + loss
            acc_aux = jax.tree.map(jnp.add, acc_aux, aux)
            return (acc_grads, acc_loss, acc_aux), None

        zero_grads = jax.tree.map(jnp.zeros_like, params)
        micro0 = jax.tree.map(lambda v: v[0], batch)
        aux_shapes = jax.eval_shape(
            lambda p, b: loss_fn(p, b, mcfg, mesh)[1], params, micro0
        )
        zero_aux = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), aux_shapes
        )
        (grads, loss, aux), _ = jax.lax.scan(
            micro, (zero_grads, jnp.zeros(()), zero_aux), batch
        )
        inv = 1.0 / accum
        grads = jax.tree.map(lambda g: g * inv, grads)
        # Means over microbatches, except token counts which accumulate.
        aux = {
            k: v if k == "tokens" else v * inv for k, v in aux.items()
        }
        return loss * inv, aux, grads

    quant_bits = cfg.train.grad_quant_bits
    if quant_bits:
        # Int8-wire DP gradient reduction (EQuARX-class; comm/quantized.py).
        # Grads are computed per-dp-shard inside a shard_map manual over dp
        # only, reduced with quantized collectives, and returned replicated.
        # Pure DP is required: with the other axes at 1 the model forward
        # contains no cross-device collectives of its own, so the manual dp
        # region is self-contained.
        from orion_tpu.comm.quantized import quantized_all_reduce

        if quant_bits != 8:
            raise ValueError(f"grad_quant_bits={quant_bits}; only 8 works")
        others = {
            k: v
            for k, v in (mesh.shape.items() if mesh is not None else [])
            if k != "dp" and v > 1
        }
        if others:
            raise ValueError(
                f"grad_quant_bits needs pure DP; mesh has {others}"
            )

        def reduced_loss_and_grads(params, batch):
            _require_unmasked_dp_batch(batch, "train.grad_quant_bits")

            def body(params, batch):
                loss, aux, grads = loss_and_grads(params, batch)
                grads = jax.tree.map(
                    lambda g: quantized_all_reduce(g, "dp", mean=True), grads
                )
                loss, aux = _dp_mean_metrics(loss, aux)
                return loss, aux, grads

            bspec = P(None, "dp") if accum > 1 else P("dp")
            return jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(
                    jax.tree.map(lambda _: P(), params),
                    jax.tree.map(lambda _: bspec, batch),
                ),
                out_specs=(P(), P(), P()),
                check_vma=False,
            )(params, batch)

        grads_fn = reduced_loss_and_grads
    else:
        grads_fn = loss_and_grads

    manual_zero1 = zero1 is not None and zero1.manual
    if manual_zero1:
        # The quantized-wire ZeRO-1 path (train.zero1_quantize): the whole
        # fwd/bwd + sharded update runs manual over dp, so the gradient
        # exchange is the PARTIAL per-replica grads (the reduce-scatter
        # leg quantizes real wire traffic, not an already-psum'd copy) and
        # the updated params return through the explicit all-gather leg.
        # Pure DP is required (Trainer validates): with the other axes at
        # 1 the model forward contains no collectives of its own.
        from jax import lax as _lax

        zspec = jax.tree.map(lambda s: s.spec, zero1.state_shardings)
        opt_spec: dict = {"mu": zspec, "nu": zspec, "count": P()}
        if zero1_master_split(cfg):
            opt_spec["master"] = zspec
        bspec = P(None, "dp") if accum > 1 else P("dp")

        def _manual_body(params, opt, batch, lr, want_finite):
            loss, aux, grads = loss_and_grads(params, batch)
            if want_finite:
                # Checked on the LOCAL partial grads: the int8 wire leg
                # would round a NaN away before a post-reduce check saw
                # it. psum-of-bools == n <=> every replica finite.
                fin = jnp.logical_and(
                    jnp.isfinite(loss), tree_all_finite(grads)
                )
                fin = _lax.psum(
                    fin.astype(jnp.int32), "dp"
                ) >= _lax.axis_size("dp")
            else:
                fin = jnp.bool_(True)
            new_params, new_opt, m = apply_updates(
                params, grads, opt, cfg.optimizer, lr, zero1=zero1
            )
            loss, aux = _dp_mean_metrics(loss, aux)
            return loss, aux, new_params, new_opt, m["grad_norm"], fin

        def manual_update(state, batch, lr, want_finite):
            _require_unmasked_dp_batch(batch, "train.zero1_quantize")
            return jax.shard_map(
                lambda p, o, b, lr_: _manual_body(
                    p, o, b, lr_, want_finite
                ),
                mesh=mesh,
                in_specs=(P(), opt_spec, bspec, P()),
                out_specs=(P(), P(), P(), opt_spec, P(), P()),
                check_vma=False,
            )(state["params"], state["opt"], batch, lr)

    def train_step(state: TrainState, batch):
        params = state["params"]
        lr = schedule(state["opt"]["count"]).astype(jnp.float32)
        if manual_zero1:
            with jax.named_scope("fwd_bwd_zero1"):
                loss, aux, new_params, new_opt, gnorm, _ = manual_update(
                    state, batch, lr, False
                )
        else:
            with jax.named_scope("fwd_bwd"):
                loss, aux, grads = grads_fn(params, batch)
            with jax.named_scope("optimizer"):
                new_params, new_opt, opt_metrics = apply_updates(
                    params, grads, state["opt"], cfg.optimizer, lr,
                    zero1=zero1,
                )
            gnorm = opt_metrics["grad_norm"]
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        step_metrics = {
            "loss": loss,
            "ce_loss": aux["ce_loss"],
            "moe_aux": aux["moe_aux"],
            "grad_norm": gnorm,
            "lr": lr,
        }
        return new_state, step_metrics

    if not cfg.train.anomaly_guard:
        return train_step

    def guarded_step(state: TrainState, batch, norm_limit):
        """train_step + the gradient anomaly guard (ISSUE 8).

        Donation-safe skip: the old params/moments are read BEFORE the
        update and selected back per leaf when the step is anomalous, so
        a skipped step's outputs are byte-identical to the pre-step state
        even with the inputs donated (XLA still aliases the buffers —
        shapes/dtypes match — and `where` reads happen before writes).
        The schedule count only advances on applied steps, mirroring
        standard skip-nonfinite optimizers: a skipped batch neither moves
        the params nor burns an LR-schedule position.
        """
        params = state["params"]
        lr = schedule(state["opt"]["count"]).astype(jnp.float32)
        if manual_zero1:
            with jax.named_scope("fwd_bwd_zero1"):
                (loss, aux, new_params, new_opt, gnorm,
                 finite) = manual_update(state, batch, lr, True)
            with jax.named_scope("anomaly_guard"):
                spike = jnp.logical_and(finite, gnorm > norm_limit)
                ok = jnp.logical_and(finite, jnp.logical_not(spike))
            keep = lambda new, old: jnp.where(ok, new, old)
            new_state = {
                "params": jax.tree.map(keep, new_params, params),
                "opt": jax.tree.map(keep, new_opt, state["opt"]),
                "step": state["step"] + 1,
            }
            f32 = jnp.float32
            return new_state, {
                "loss": loss,
                "ce_loss": aux["ce_loss"],
                "moe_aux": aux["moe_aux"],
                "grad_norm": gnorm,
                "lr": lr,
                "anomaly": jnp.logical_not(ok).astype(f32),
                "nonfinite": jnp.logical_not(finite).astype(f32),
                "spike": spike.astype(f32),
            }
        with jax.named_scope("fwd_bwd"):
            loss, aux, grads = grads_fn(params, batch)
        if zero1 is not None:
            # Pin the guard's norm (and the clip below, via gnorm=) to the
            # baseline's replicated grad layout — the bitwise-parity rule
            # apply_updates applies when it computes the norm itself.
            grads = jax.lax.with_sharding_constraint(
                grads, zero1.param_shardings
            )
        with jax.named_scope("anomaly_guard"):
            gnorm = global_norm(grads)
            finite = jnp.logical_and(
                jnp.isfinite(loss), tree_all_finite(grads)
            )
            spike = jnp.logical_and(finite, gnorm > norm_limit)
            ok = jnp.logical_and(finite, jnp.logical_not(spike))
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = apply_updates(
                params, grads, state["opt"], cfg.optimizer, lr,
                gnorm=gnorm, zero1=zero1,
            )
        keep = lambda new, old: jnp.where(ok, new, old)
        new_state = {
            "params": jax.tree.map(keep, new_params, params),
            "opt": jax.tree.map(keep, new_opt, state["opt"]),
            "step": state["step"] + 1,
        }
        f32 = jnp.float32
        step_metrics = {
            "loss": loss,
            "ce_loss": aux["ce_loss"],
            "moe_aux": aux["moe_aux"],
            "grad_norm": gnorm,
            "lr": lr,
            "anomaly": jnp.logical_not(ok).astype(f32),
            "nonfinite": jnp.logical_not(finite).astype(f32),
            "spike": spike.astype(f32),
        }
        return new_state, step_metrics

    return guarded_step


class Trainer:
    """Builds the distributed runtime and runs the fit loop.

    Call stack mirror of the reference train path (SURVEY.md §4 stack A):
    runtime.init -> mesh -> loader -> sharded model init or checkpoint
    restore -> jit train_step -> loop.
    """

    def __init__(self, cfg: Config, fault_injector: Optional[Any] = None):
        import dataclasses as _dc

        self.fault_injector = fault_injector
        if cfg.runtime.checkify and cfg.train.anomaly_guard:
            raise ValueError(
                "train.anomaly_guard handles non-finite steps by skipping "
                "them in-program; runtime.checkify raises host-side on the "
                "same condition — pick one"
            )
        if cfg.model.weight_quant is not None:
            raise ValueError(
                "model.weight_quant is a serving-only knob (the engine "
                "quantizes at init); training runs full-precision masters"
            )
        if cfg.train.zero1:
            if cfg.parallel.dp < 2:
                raise ValueError(
                    "train.zero1 needs parallel.dp > 1: the optimizer "
                    "state shards 1/dp across the dp axis"
                )
            if cfg.train.grad_quant_bits:
                raise ValueError(
                    "train.zero1 replaces the dp gradient all-reduce with "
                    "a reduce-scatter, so train.grad_quant_bits has no "
                    "collective left to quantize; use train.zero1_quantize"
                )
            if cfg.train.zero1_quantize:
                if cfg.parallel.pp > 1:
                    # Named separately from the generic pure-DP check:
                    # the full-precision zero1 path DOES compose with pp
                    # (stage-local dp via sharding constraints), so this
                    # is the one zero1 combo that stays rejected — the
                    # int8 wire legs run shard_map manual over dp, and
                    # nesting that inside the pipeline's pp-manual
                    # region is unproven.
                    raise ValueError(
                        "train.zero1_quantize is rejected under "
                        "parallel.pp: the int8 wire legs run manual "
                        "over dp and cannot nest inside the pipeline's "
                        "pp shard_map; use full-precision train.zero1 "
                        "(composes with pp) or drop pp"
                    )
                others = {
                    k: v for k, v in cfg.parallel.axis_sizes.items()
                    if k != "dp" and v > 1
                }
                if others:
                    raise ValueError(
                        f"train.zero1_quantize needs pure DP (the wire "
                        f"legs run manual over dp); mesh has {others}"
                    )
        elif cfg.train.zero1_quantize:
            raise ValueError(
                "train.zero1_quantize without train.zero1 has no "
                "ZeRO-1 collective legs to quantize"
            )
        if cfg.train.remat != "inherit" or cfg.train.remat_offload:
            # train.remat / train.remat_offload are the training-side
            # spelling of the remat policy: fold them into the model config
            # (the source of truth the forward pass reads), so checkpoints
            # and serving configs keep their own model.remat. An explicit
            # train.remat=none arrives as None (the override parser's
            # spelling) and ModelConfig.__post_init__ normalizes it — it
            # must DISABLE remat, not fall back to model.remat. An explicit
            # train.remat to a NON-names policy takes the offload decision
            # wholesale (model.remat_offload is dropped, not OR'd in), so
            # overriding an offload-configured checkpoint to dots/full does
            # not dead-end on the offload-requires-names check — but an
            # explicit train.remat=names keeps a configured
            # model.remat_offload (OR), so restating the canonical spelling
            # cannot silently move the stash back into HBM.
            explicit = cfg.train.remat != "inherit"
            drop_model_offload = explicit and cfg.train.remat != "names"
            cfg = _dc.replace(
                cfg,
                model=_dc.replace(
                    cfg.model,
                    remat=(cfg.train.remat if explicit
                           else cfg.model.remat),
                    remat_offload=(
                        cfg.train.remat_offload if drop_model_offload
                        else (cfg.train.remat_offload
                              or cfg.model.remat_offload)
                    ),
                ),
            )
        # Validate the remat-policy coupling (offload requires "names") and
        # the scan-unit split NOW, with config vocabulary — not as a trace-
        # time error out of the middle of the forward pass.
        from orion_tpu.models.transformer import remat_policy

        remat_policy(cfg.model)
        if cfg.model.scan_layers and cfg.model.n_layers % cfg.model.scan_unit:
            raise ValueError(
                f"model.n_layers={cfg.model.n_layers} must be divisible by "
                f"the layer-scan unit {cfg.model.scan_unit} "
                f"(model.scan_group={cfg.model.scan_group}"
                + (f" x pattern={cfg.model.window_pattern}"
                   if cfg.model.window_pattern else "") + ")"
            )
        if cfg.model.scan_group > 1 and not cfg.model.scan_layers:
            raise ValueError(
                "model.scan_group > 1 requires model.scan_layers=true "
                "(grouping is a property of the layer scan)"
            )
        if (
            cfg.parallel.pp_virtual_stages != 1
            and cfg.parallel.pp_schedule != "interleaved"
        ):
            # Checked regardless of pp: at pp=1 the setting would otherwise
            # be silently ignored — the exact no-op it exists to reject.
            raise ValueError(
                "pp_virtual_stages > 1 requires pp_schedule=interleaved"
            )
        if cfg.parallel.pp > 1:
            # Route the layer stack through the pipeline over pp
            # (parallel.pipeline); params/opt shard "layers" -> pp by rule.
            pp, M = cfg.parallel.pp, cfg.parallel.pp_microbatches
            micro = cfg.data.batch_size // max(cfg.train.grad_accum, 1)
            # The pipeline unit is the layer-scan unit: scan_group
            # homogeneous layers times the window pattern (Gemma-family
            # models group local/global layers). Same source of truth as
            # the forward pass (ModelConfig.scan_unit), so scan_group
            # composes with pp instead of being rejected.
            unit = cfg.model.scan_unit
            n_units, rem = divmod(cfg.model.n_layers, unit)
            if rem or n_units % pp:
                raise ValueError(
                    f"model.n_layers={cfg.model.n_layers} must split into "
                    f"scan units of {unit} (scan_group="
                    f"{cfg.model.scan_group} x pattern="
                    f"{cfg.model.window_pattern or 1}) divisible by "
                    f"parallel.pp={pp}"
                )
            if M < 1 or micro % M:
                raise ValueError(
                    f"per-step batch {micro} must be divisible by "
                    f"pp_microbatches={M}"
                )
            if not cfg.model.scan_layers:
                raise ValueError("parallel.pp > 1 requires model.scan_layers")
            sched = cfg.parallel.pp_schedule
            V = cfg.parallel.pp_virtual_stages
            if sched == "interleaved":
                if n_units % (pp * V):
                    raise ValueError(
                        f"model.n_layers={cfg.model.n_layers} gives "
                        f"{n_units} pipeline units (scan unit {unit}); "
                        f"must be divisible by pp*pp_virtual_stages "
                        f"({pp}*{V})"
                    )
                if M > pp:
                    raise ValueError(
                        f"pp_schedule=interleaved needs pp_microbatches "
                        f"({M}) <= pp ({pp}); raise pp_virtual_stages to "
                        f"amortize the bubble instead"
                    )
            cfg = _dc.replace(
                cfg,
                model=_dc.replace(
                    cfg.model, pipeline_axis="pp", pp_microbatches=M,
                    pp_schedule=sched, pp_virtual_stages=V,
                ),
            )
        if cfg.parallel.sp > 1:
            # Route attention through ring/Ulysses over the sp axis
            # (parallel.sequence); all other layers are pointwise over the
            # sequence and stay sequence-sharded via the "seq" rule.
            if cfg.data.seq_len % cfg.parallel.sp:
                raise ValueError(
                    f"data.seq_len={cfg.data.seq_len} must be divisible by "
                    f"parallel.sp={cfg.parallel.sp}"
                )
            if cfg.parallel.sequence_method == "ulysses":
                sp_tp = cfg.parallel.sp * cfg.parallel.tp
                if cfg.model.n_heads % sp_tp:
                    raise ValueError(
                        f"ulysses needs model.n_heads={cfg.model.n_heads} "
                        f"divisible by sp*tp={sp_tp}"
                    )
                kv = cfg.model.n_kv_heads
                if kv < sp_tp and sp_tp % kv == 0:
                    # GQA KV replication (the only sub-divisible shape
                    # sequence.py accepts): the head<->seq all_to_all moves
                    # whole heads, so kv_heads replicate up to sp*tp — that
                    # inflates KV comm volume by sp*tp/kv_heads vs ring's
                    # exact O(S/sp) KV rotation. Warn and quantify so the
                    # config author can switch (parallel.sequence_method).
                    log.warning(
                        "ulysses with GQA (kv_heads=%d < sp*tp=%d) "
                        "replicates KV heads: %dx KV all_to_all volume. "
                        "parallel.sequence_method='ring' (or "
                        "'ring_striped') avoids the inflation for this "
                        "config.",
                        kv, sp_tp, sp_tp // kv,
                    )
            cfg = _dc.replace(
                cfg,
                model=_dc.replace(
                    cfg.model,
                    sequence_axis="sp",
                    sequence_method=cfg.parallel.sequence_method,
                ),
            )
        self.cfg = cfg
        if cfg.data.batch_size % max(cfg.train.grad_accum, 1):
            raise ValueError(
                f"grad_accum={cfg.train.grad_accum} must divide global batch "
                f"{cfg.data.batch_size}"
            )
        micro = cfg.data.batch_size // max(cfg.train.grad_accum, 1)
        dpf = cfg.parallel.dp * cfg.parallel.fsdp
        if micro % dpf:
            raise ValueError(
                f"per-step batch {micro} (data.batch_size="
                f"{cfg.data.batch_size} / grad_accum="
                f"{max(cfg.train.grad_accum, 1)}) must be divisible by "
                f"dp*fsdp={dpf}"
            )
        self.runtime = initialize(cfg.runtime)
        self.mesh = build_mesh(cfg.parallel, platform=cfg.runtime.platform)
        # Plan first, shardings from it: both need the same abstract init
        # trace; building the plan once avoids paying it twice.
        self._zero1 = make_zero1_plan(self.cfg, self.mesh)
        self.shardings = state_shardings(
            cfg, self.mesh, zero1_plan=self._zero1
        )
        self.batch_shard = self._batch_sharding()
        self.loader = make_loader(cfg.data, cfg.model.vocab_size)
        schedule = make_schedule(cfg.optimizer, cfg.train.num_steps)
        self._schedule = schedule
        base_step = make_train_step(
            self.cfg, schedule, self.mesh, zero1=self._zero1
        )
        if cfg.runtime.checkify:
            # Sanitizer mode (SURVEY.md §6, SANITIZERS.md): functionalized
            # device-side nan/inf + index-OOB checks; the error pytree is
            # fetched and thrown host-side after every step.
            from jax.experimental import checkify as _checkify

            # checkify's error plumbing does not compose with manual
            # shard_map regions in this jax version (the error pytree's
            # shapes diverge across the manual boundary) — fail loudly
            # with the reason instead of a cryptic trace-time TypeError.
            manual = []
            if cfg.parallel.sp > 1:
                manual.append("parallel.sp>1 (ring/Ulysses shard_map)")
            if cfg.parallel.pp > 1:
                manual.append("parallel.pp>1 (pipeline shard_map)")
            if (cfg.model.is_moe and cfg.parallel.ep > 1
                    and cfg.model.moe_dispatch == "sorted_a2a"):
                manual.append("moe_dispatch=sorted_a2a (explicit ep a2a)")
            if cfg.train.grad_quant_bits:
                manual.append("train.grad_quant_bits (dp shard_map)")
            if cfg.train.zero1_quantize:
                manual.append(
                    "train.zero1_quantize (dp shard_map wire legs)"
                )
            if manual:
                raise ValueError(
                    "runtime.checkify does not compose with manual "
                    f"shard_map regions ({', '.join(manual)}); use "
                    "runtime.debug_nans, or check the step on an "
                    "SPMD-automatic layout (dp/fsdp/tp/ep-sorted)"
                )
            # Full check set: float (nan/inf) AND index (out-of-bounds)
            # checks. Two rewrites make this possible on this jax version:
            # the loss's target gather routes through a custom VJP whose
            # backward is a one-hot product, not a scatter
            # (models/transformer._gather_target), and the MoE router's
            # top-k is argsort + one-hot product (models/moe._router_topk)
            # — checkify's index rewrite crashes on gather's scatter
            # transpose and on lax.top_k, which previously forced
            # float_checks-only here.
            checked = jax.jit(
                _checkify.checkify(base_step, errors=_checkify.all_checks),
                donate_argnums=(0,),
            )

            def _checked_step(state, batch):
                err, out = checked(state, batch)
                _checkify.check_error(err)
                return out

            self._jit_step = checked
            self.train_step = _checked_step
        else:
            self._jit_step = jax.jit(base_step, donate_argnums=(0,))
            self.train_step = self._jit_step
        if cfg.model.debug_asserts:
            # Manual-region sanitizer (runtime/asserts.py): device_assert
            # callbacks RECORD failures (raising inside an async callback
            # aborts the runtime); surface them loudly at this per-step
            # host sync point. The block_until_ready forces the step's
            # callbacks to have run before we check.
            from orion_tpu.runtime import asserts as _asserts

            inner_step = self.train_step

            def _asserted_step(*args):
                out = inner_step(*args)
                jax.block_until_ready(out[1])
                # Output readiness does not order the async callback
                # thread; the barrier does — without it a failure could
                # surface a step late (or never, on the final step).
                jax.effects_barrier()
                _asserts.raise_if_failed()
                return out

            self.train_step = _asserted_step
        self.eval_loader = None
        self._eval_batches = None
        if cfg.train.eval_interval:
            eval_data = _dc.replace(
                cfg.data,
                path=cfg.data.eval_path or cfg.data.path,
                shuffle_seed=cfg.data.eval_seed,
            )
            self.eval_loader = make_loader(eval_data, cfg.model.vocab_size)
            mcfg, mesh = self.cfg.model, self.mesh
            self.eval_step = jax.jit(
                lambda params, batch: loss_fn(params, batch, mcfg, mesh)[1][
                    "ce_loss"
                ]
            )
        self.ckpt: Optional[CheckpointManager] = None
        if cfg.checkpoint.directory:
            self.ckpt = CheckpointManager(
                cfg.checkpoint.directory, cfg.checkpoint,
                fault_injector=fault_injector,
            )
        # Anomaly-guard host state (persisted in the checkpoint manifest so
        # resume reproduces the exact skip decisions) + robustness counters.
        self._gnorm_ema: Optional[float] = None
        self._anomaly_run = 0
        self._poison_jit = None
        self.robustness = metrics_lib.TrainRobustnessStats()
        # The PRNG key the run was seeded with, recorded in every manifest
        # (pillar 2: a resumed run must be able to prove it continues the
        # same key lineage).
        self._prng_key_data = [
            int(x) for x in np.ravel(
                jax.random.key_data(jax.random.key(cfg.train.seed))
            )
        ]
        # data.batch_size is the global batch per optimizer step; grad_accum
        # only splits it into microbatches and must not inflate throughput.
        tokens_per_step = cfg.data.batch_size * cfg.data.seq_len
        self.metrics = metrics_lib.MetricsLogger(
            flops_per_token=cfg.model.flops_per_token(cfg.data.seq_len),
            num_devices=self.mesh.size,
            device=self.mesh.devices.flat[0],
            jsonl_path=cfg.train.metrics_jsonl,
            log_interval=cfg.train.log_interval,
        )
        self.tokens_per_step = tokens_per_step
        self.device_memory: list[dict] = []   # filled at the end of fit()
        # -- Observability (orion_tpu/obs; README "Observability") ---------
        # Registry always exists (lazy provider reads — no hot-path cost);
        # tracer/flight only when train.trace / train.flight_dir ask, so
        # the untraced fit loop is byte-identical to the pre-obs one.
        from orion_tpu.obs import MetricsRegistry, init_obs, live_hbm_metrics

        self.registry = MetricsRegistry()
        self.registry.register(
            "robust", lambda: self.robustness.as_timing()
        )
        self.registry.register("train", self._last_step_metrics)
        self.registry.register(
            "hbm", partial(live_hbm_metrics, self.mesh.local_devices[0])
        )
        self._tracer, self._flight = init_obs(
            trace=cfg.train.trace,
            trace_ring=cfg.train.trace_ring,
            flight_dir=cfg.train.flight_dir,
            trace_path=cfg.train.trace_path,
            snapshot=self.registry.snapshot,
            injector=fault_injector,
        )

    def _last_step_metrics(self) -> dict:
        """Registry provider: the newest StepMetrics row (the same dict
        the JSONL sink writes), or {} before the first step."""
        h = self.metrics.history
        return h[-1].to_dict() if h else {}

    def _flight_dump(self, reason: str, **context) -> None:
        """Write a flight-recorder postmortem (no-op without
        train.flight_dir); best-effort like the engine's
        (FlightRecorder.try_dump)."""
        if self._flight is not None:
            self._flight.try_dump(reason, **context)

    def _batch_sharding(self) -> NamedSharding:
        shard = batch_sharding(self.mesh)
        if self.cfg.train.grad_accum > 1:
            # Microbatch axis leads and is unsharded: [A, b, S].
            return NamedSharding(self.mesh, P(None, *shard.spec))
        return shard

    # -- state ------------------------------------------------------------

    def init_state(self) -> TrainState:
        key = jax.random.key(self.cfg.train.seed)
        init = lambda: init_train_state(self.cfg, key)
        return jax.jit(init, out_shardings=self.shardings)()

    def abstract_state(self) -> TrainState:
        return abstract_train_state(self.cfg, shardings=self.shardings)

    def memory_report(self, assert_donation: bool = True) -> dict:
        """AOT-compile the jitted train step and report XLA's compiled
        memory analysis — the ground truth for "does this remat policy fit"
        (temp bytes = activations + workspace) and for whether the donated
        master-param/optimizer-state buffers were actually reused.

        All state accounting is PER CHIP (``sharding.shard_shape``), so a
        dp-sharded layout (train.zero1) shows its 1/dp master+moment
        shrink directly; ``by_category`` breaks the per-chip bytes into
        params / grads / master / moments / activations (grads and
        activations are estimates: the effective grad dtype over the param
        layout, and XLA's temp bytes — activations + workspace + transient
        grads — respectively).

        With ``assert_donation`` (default), raise if any donated state
        bytes failed to alias into the outputs: an un-aliased master/
        moment buffer silently DOUBLES its footprint for the step, which
        is exactly the headroom that decides whether remat=names fits at
        bench batch 8 (PERF.md). The check compares per-chip donated bytes
        against the per-executable alias size, so it covers sharded
        layouts too; multi-PROCESS runs still skip it (this process only
        sees its own executable). (Not called from the hot path: the AOT
        executable is separate from jit's own cache, so this costs one
        extra compile.)
        """
        import math

        state = self.abstract_state()
        # Specs from the REAL assembled global batch (one materialization,
        # trivial next to the AOT compile): on multi-process runs the
        # host-local batch is only this process's shard, and lowering with
        # its shape would analyze a program the hot path never runs.
        batch = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            self.global_batch(0),
        )
        args = (state, batch)
        if self.cfg.train.anomaly_guard:
            # The guarded program takes the host-fed spike threshold too.
            args = (*args, jax.ShapeDtypeStruct((), jnp.float32))
        compiled = self._jit_step.lower(*args).compile()
        ma = compiled.memory_analysis()

        def _nbytes(leaf):
            return math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize

        def _chip_nbytes(leaf, dtype=None):
            """Per-device bytes: the leaf's local shard (replicated dims
            count in full on every chip). ``dtype`` overrides the leaf's
            (the grads estimate prices the param layout at grad dtype)."""
            sharding = getattr(leaf, "sharding", None)
            shape = (
                sharding.shard_shape(leaf.shape)
                if sharding is not None else leaf.shape
            )
            dt = jnp.dtype(dtype if dtype is not None else leaf.dtype)
            return math.prod(shape) * dt.itemsize

        def _chip_tree(tree):
            return sum(_chip_nbytes(x) for x in jax.tree.leaves(tree))

        donated = sum(_nbytes(leaf) for leaf in jax.tree.leaves(state))
        donated_chip = _chip_tree(state)
        opt = state["opt"]
        gdt = jnp.dtype(
            self.cfg.train.grad_dtype
            if self.cfg.train.grad_dtype is not None
            else jax.tree.leaves(state["params"])[0].dtype
        )
        by_category = {
            "params": _chip_tree(state["params"]),
            "grads": sum(
                _chip_nbytes(p, gdt)
                for p in jax.tree.leaves(state["params"])
            ),
            "master": _chip_tree(opt["master"]) if "master" in opt else 0,
            "moments": _chip_tree(opt["mu"]) + _chip_tree(opt["nu"]),
        }
        report = {
            "donated_state_bytes": donated,
            "donated_bytes_per_chip": donated_chip,
            "by_category": by_category,
            "available": ma is not None,
        }
        if jax.process_count() > 1:
            assert_donation = False
            report["note"] = (
                "multi-process run: this process's executable only covers "
                "its own devices; donation assertion skipped"
            )
        if ma is not None:
            report.update(
                argument_bytes=int(ma.argument_size_in_bytes),
                output_bytes=int(ma.output_size_in_bytes),
                temp_bytes=int(ma.temp_size_in_bytes),
                alias_bytes=int(ma.alias_size_in_bytes),
                unaliased_donated_bytes=max(
                    0, donated_chip - int(ma.alias_size_in_bytes)
                ),
            )
            by_category["activations"] = int(ma.temp_size_in_bytes)
            if assert_donation and report["unaliased_donated_bytes"] > 0:
                raise RuntimeError(
                    f"train-step donation leaked a copy: "
                    f"{report['unaliased_donated_bytes']} of "
                    f"{donated_chip} donated per-chip state bytes were "
                    f"not aliased into the outputs "
                    f"(alias_size={report['alias_bytes']}); check for "
                    f"dtype/sharding mismatches between old and new "
                    f"state leaves"
                )
        return report

    def restore_or_init(self) -> tuple[TrainState, int]:
        if self.ckpt is not None and self.cfg.checkpoint.restore:
            restored = self.ckpt.restore_latest(self.abstract_state())
            self.robustness.corrupt_checkpoints += len(self.ckpt.quarantined)
            if restored is not None:
                state, step = restored
                self._apply_restore_extra(self.ckpt.last_restore_extra)
                return state, step
        return self.init_state(), 0

    def _apply_restore_extra(self, extra: Optional[dict]) -> None:
        """Rehydrate the host-side resume state the manifest carried:
        data-loader cursor, anomaly-guard EMA/run, PRNG-lineage check."""
        if not extra:
            return
        if extra.get("loader"):
            loader_state = dict(extra["loader"])
            # The manifest-level stream-format check already warned on a
            # mismatch; don't let load_state_dict repeat it.
            loader_state.pop("stream_format", None)
            self.loader.load_state_dict(loader_state)
        if "gnorm_ema" in extra:
            self._gnorm_ema = extra["gnorm_ema"]
        self._anomaly_run = int(extra.get("anomaly_run") or 0)
        key = extra.get("prng_key")
        if key is not None and list(key) != self._prng_key_data:
            log.warning(
                "checkpoint was written under a different train.seed PRNG "
                "key (%s vs %s): any key-derived randomness diverges from "
                "the original run", key, self._prng_key_data,
            )

    def _ckpt_extra(self) -> dict:
        extra = {
            "loader": self.loader.state_dict(),
            "train_seed": self.cfg.train.seed,
            "prng_key": self._prng_key_data,
        }
        if self.cfg.train.anomaly_guard:
            extra["gnorm_ema"] = self._gnorm_ema
            extra["anomaly_run"] = self._anomaly_run
        return extra

    def _spike_limit(self) -> np.float32:
        """The norm threshold fed to the guarded step: factor x the
        running EMA, or +inf while no reference exists (first steps, or
        spike checking disabled — finiteness is still checked)."""
        factor = self.cfg.train.anomaly_spike_factor
        if factor is None or not self._gnorm_ema:
            return np.float32(np.inf)
        return np.float32(factor * self._gnorm_ema)

    def _poison_variant(self):
        """The FaultInjector "nan" step program, compiled on first use
        (same config/schedule family; the loss is NaN-poisoned inside the
        differentiated function, so every grad leaf comes out NaN through
        the real backward)."""
        if self._poison_jit is None:
            self._poison_jit = jax.jit(
                make_train_step(
                    self.cfg, self._schedule, self.mesh, poison=True,
                    zero1=self._zero1,
                ),
                donate_argnums=(0,),
            )
        return self._poison_jit

    def _rollback(self, failed_step: int) -> tuple[TrainState, int]:
        """Auto-rollback after train.anomaly_limit consecutive anomalies:
        restore the newest intact checkpoint and fast-forward the data
        cursor past the poisoned batch window, so the replayed optimizer
        steps draw fresh batches instead of the poison. Idempotent under
        repetition — every episode skips further."""
        stats = self.robustness
        stats.rollbacks += 1
        stats.last_fault_reason = (
            f"anomaly_rollback: {self._anomaly_run} consecutive anomalous "
            f"steps ending at step {failed_step}"
        )
        # Postmortem BEFORE the restore mutates loader/EMA state: the dump
        # captures the poisoned window as the rollback saw it.
        self._flight_dump(
            "anomaly_rollback", failed_step=failed_step,
            anomaly_run=self._anomaly_run,
        )
        if self.ckpt is None:
            raise RollbackFailed(
                f"{self._anomaly_run} consecutive anomalous steps at step "
                f"{failed_step} and no checkpoint.directory to roll back to"
            )
        restored = self.ckpt.restore_latest(self.abstract_state())
        stats.corrupt_checkpoints += len(self.ckpt.quarantined)
        if restored is None:
            raise RollbackFailed(
                f"{self._anomaly_run} consecutive anomalous steps at step "
                f"{failed_step} and no intact checkpoint to roll back to"
            )
        state, good_step = restored
        extra = self.ckpt.last_restore_extra or {}
        loader_state = dict(extra.get("loader") or {})
        # Defensive clamp: if the newest intact checkpoint is somehow AHEAD
        # of the failed step (a later-step checkpoint resurfacing after a
        # transient validation failure), replay starts past the poison
        # already — never ask the cursor to rewind.
        skip = max((failed_step + 1) - good_step, 0)
        self.loader.load_state_dict(loader_state)
        self.loader.skip_batches(skip)
        stats.skipped_batches += skip
        self._gnorm_ema = extra.get("gnorm_ema")
        self._anomaly_run = 0
        # Persist the advanced cursor AT the restored step immediately: a
        # crash before the next periodic save would otherwise resume with
        # the old cursor, replay the poison, and have to roll back again.
        self.ckpt.save(
            good_step, state, force=True, overwrite=True,
            extra=self._ckpt_extra(),
        )
        log.warning(
            "auto-rollback: restored step %d, skipping the %d-batch poison "
            "window (data cursor offset now %d)",
            good_step, skip, self.loader.offset,
        )
        return state, good_step

    # -- data -------------------------------------------------------------

    def _host_batch(self, step: int) -> dict:
        """The host-side batch exactly as the train step receives it
        (grad_accum microbatch axis applied). Shared by the hot path and
        memory_report, so the AOT-analyzed shapes cannot drift from the
        shapes the real step runs."""
        host = dict(self.loader.batch_at(step))
        accum = self.cfg.train.grad_accum
        if accum > 1:
            host = {
                k: v.reshape(accum, v.shape[0] // accum, *v.shape[1:])
                for k, v in host.items()
            }
        return host

    def global_batch(self, step: int) -> Any:
        return jax.tree.map(
            lambda v: jax.make_array_from_process_local_data(
                self.batch_shard, v
            ),
            self._host_batch(step),
        )

    def evaluate(self, params: Any) -> float:
        """Mean held-out CE loss over the fixed eval batch set (the same
        (seed, step) batches every call, so curves are comparable).

        The batch set never changes, so the device arrays are built once
        and reused across eval points (they are tiny next to model state).
        """
        assert self.eval_loader is not None, "set train.eval_interval"
        if self._eval_batches is None:
            shard = batch_sharding(self.mesh)
            self._eval_batches = [
                jax.tree.map(
                    lambda v: jax.make_array_from_process_local_data(
                        shard, v
                    ),
                    dict(self.eval_loader.batch_at(i)),
                )
                for i in range(self.cfg.train.eval_batches)
            ]
        total = 0.0
        for batch in self._eval_batches:
            total += float(jax.device_get(self.eval_step(params, batch)))
        return total / max(len(self._eval_batches), 1)

    # -- loop -------------------------------------------------------------

    def fit(
        self,
        state: Optional[TrainState] = None,
        preemption_handler: Optional[Any] = None,
        restart_info: Optional[tuple] = None,
    ) -> list:
        """Run the step loop from the restored (or given) state.

        ``restart_info=(attempt, reason)`` threads the supervisor context
        (run_with_restarts) into the step log: the restart count rides the
        metrics extras, the previous attempt's fault reason the log line.
        """
        from orion_tpu.runtime.fault import (
            InjectedFault, Preempted, PreemptionHandler, Watchdog,
        )
        import contextlib

        cfg = self.cfg
        stats = self.robustness
        if restart_info is not None:
            attempt, reason = restart_info
            stats.restarts = int(attempt)
            if reason:
                stats.last_fault_reason = str(reason)
            if attempt:
                log.warning(
                    "supervisor restart %d: resuming after %s",
                    attempt, reason or "unknown fault",
                )
        if state is None:
            state, start = self.restore_or_init()
        else:
            start = int(jax.device_get(state["step"]))
        guard = cfg.train.anomaly_guard
        injector = self.fault_injector
        profile = cfg.train.profile_steps
        watch = metrics_lib.Stopwatch()
        compiles = metrics_lib.CompileCounter()
        tracing = False
        # After an auto-rollback the replayed trajectory differs from the
        # one the existing checkpoints captured; overwrite them up to the
        # rollback point so a crash mid-replay resumes the NEW trajectory.
        overwrite_until = -1
        try:
          with contextlib.ExitStack() as stack:
            # An externally-managed handler (tests, schedulers) is used
            # as-is; otherwise install our own for the duration of the loop.
            preempt = (
                preemption_handler
                if preemption_handler is not None
                else stack.enter_context(PreemptionHandler())
            )
            # Disabled no-op when watchdog_timeout_s is None.
            watchdog = stack.enter_context(
                Watchdog(cfg.train.watchdog_timeout_s,
                         action=cfg.train.watchdog_action)
            )
            step = start
            while step < cfg.train.num_steps:
                if cfg.train.inject_fault_at_step == step:
                    key = (cfg.checkpoint.directory, step)
                    if key not in _FIRED_FAULTS:
                        _FIRED_FAULTS.add(key)
                        raise FaultInjected(f"injected fault at step {step}")
                if injector is not None \
                        and injector.take("dispatch", step, "train"):
                    raise InjectedFault(
                        f"injected train dispatch fault at step {step}"
                    )
                if profile and step == profile[0]:
                    jax.profiler.start_trace(cfg.train.profile_dir)
                    tracing = True
                s0 = time.monotonic() if self._tracer.enabled else 0.0
                with self._tracer.span("data", step=step):
                    batch = self.global_batch(step)
                step_fn = self.train_step
                if injector is not None \
                        and injector.take("nan", step, "train") is not None:
                    log.warning(
                        "fault injection: NaN-poisoned train step %d", step
                    )
                    step_fn = self._poison_variant()
                # StepTraceAnnotation marks the step boundary in a device
                # profile captured over the same window (profile_steps),
                # so xprof's step view lines up with the host spans; the
                # dispatch span covers compiled-step call + metric fetch.
                with self._tracer.step_annotation("train", step), \
                        self._tracer.span("dispatch", step=step):
                    if guard:
                        state, m = step_fn(state, batch, self._spike_limit())
                    else:
                        state, m = step_fn(state, batch)
                    m = jax.device_get(m)
                dt = watch.lap(sync_on=m["loss"])
                watchdog.heartbeat()
                n_compiled, compile_s = compiles.take()
                extras = {
                    "ce_loss": float(m["ce_loss"]),
                    "moe_aux": float(m["moe_aux"]),
                    # XLA programs built since the previous row: the first
                    # step's is the compile; a steady step's must be 0.
                    "compiles": n_compiled,
                    "compile_s": compile_s,
                }
                anomalous = bool(guard and m["anomaly"] > 0)
                if guard:
                    with self._tracer.span("guard", step=step):
                        extras["anomaly"] = float(m["anomaly"])
                        if anomalous:
                            stats.anomalous_steps += 1
                            stats.nonfinite_steps += int(m["nonfinite"] > 0)
                            stats.spike_steps += int(m["spike"] > 0)
                            self._anomaly_run += 1
                            log.warning(
                                "anomalous step %d skipped (%s; grad_norm "
                                "%.3g; run %d/%d)", step,
                                "non-finite" if m["nonfinite"] > 0
                                else "norm spike",
                                float(m["grad_norm"]), self._anomaly_run,
                                cfg.train.anomaly_limit,
                            )
                        else:
                            self._anomaly_run = 0
                            beta = cfg.train.anomaly_ema_beta
                            g = float(m["grad_norm"])
                            self._gnorm_ema = (
                                g if self._gnorm_ema is None
                                else beta * self._gnorm_ema + (1 - beta) * g
                            )
                if stats.restarts or stats.rollbacks or stats.anomalous_steps:
                    extras.update(stats.as_extras())
                eval_iv = cfg.train.eval_interval
                if eval_iv and (step + 1) % eval_iv == 0:
                    extras["eval_loss"] = self.evaluate(state["params"])
                    log.info(
                        "eval at step %d: loss %.4f",
                        step + 1,
                        extras["eval_loss"],
                    )
                    watch.lap()  # keep eval time out of the next step's MFU
                self.metrics.record(
                    step=step + 1,
                    loss=m["loss"],
                    tokens=self.tokens_per_step,
                    step_time_s=dt,
                    grad_norm=m["grad_norm"],
                    learning_rate=m["lr"],
                    **extras,
                )
                if tracing and step + 1 >= profile[1]:
                    jax.profiler.stop_trace()
                    tracing = False
                if cfg.train.metrics_prom and \
                        (step + 1) % max(cfg.train.log_interval, 1) == 0:
                    try:
                        self.registry.export_prometheus(
                            cfg.train.metrics_prom
                        )
                    except OSError as e:
                        log.error("metrics_prom export failed: %s", e)
                if anomalous \
                        and self._anomaly_run >= cfg.train.anomaly_limit:
                    if self._tracer.enabled:
                        # Close the step span BEFORE the rollback's
                        # `continue` — the anomalous step a postmortem
                        # inspects must not be a hole in the timeline.
                        self._tracer.record_span(
                            "train_step", s0, time.monotonic(), step=step,
                            anomalous=True,
                        )
                    state, step = self._rollback(step)
                    overwrite_until = self._overwrite_from(step)
                    watch.lap()   # rollback time out of the next step's MFU
                    continue
                if self.ckpt is not None:
                    # ckpt span: async saves enqueue here (the host-side
                    # snapshot copy), sync saves block — either cost lands
                    # in this phase of the timeline.
                    with self._tracer.span("ckpt", step=step):
                        self.ckpt.save(
                            step + 1, state, extra=self._ckpt_extra(),
                            overwrite=step + 1 <= overwrite_until,
                        )
                if self._tracer.enabled:
                    self._tracer.record_span(
                        "train_step", s0, time.monotonic(), step=step,
                        anomalous=anomalous,
                    )
                if preempt.preempted:
                    # Step boundary: state is consistent. Persist and stop
                    # cleanly; the supervisor restart resumes losslessly.
                    # The emergency save queues BEHIND any in-flight async
                    # save (single writer queue) and wait() drains both
                    # inside the grace window.
                    if self.ckpt is not None and cfg.train.emergency_ckpt:
                        if self.ckpt.save(
                            step + 1, state, force=True,
                            extra=self._ckpt_extra(),
                            overwrite=step + 1 <= overwrite_until,
                        ):
                            stats.emergency_saves += 1
                        self.ckpt.wait()
                    raise Preempted(f"preempted after step {step + 1}")
                step += 1
            if self.ckpt is not None:
                self.ckpt.save(
                    cfg.train.num_steps, state, force=True,
                    extra=self._ckpt_extra(),
                )
            # Allocator state of this process's mesh devices while the
            # train state is still live (it is dropped when fit returns):
            # on a multi-chip mesh each device should hold a comparable
            # share.
            from orion_tpu.obs import live_hbm_metrics

            self.device_memory = [
                {"id": d.id, **live_hbm_metrics(d)}
                for d in self.mesh.local_devices
            ]
            return self.metrics.history
        except (KeyboardInterrupt, FaultInjected, InjectedFault):
            # Preemption-safe path: persist the newest complete state, then
            # re-raise so a supervisor can restart and restore_or_init.
            # If the interrupt landed inside train_step, `state` is the
            # donated (deleted) input — in that case the last periodic
            # checkpoint stands and at most one step is lost.
            if self.ckpt is not None and cfg.train.emergency_ckpt:
                try:
                    at_step = int(jax.device_get(state["step"]))
                    if self.ckpt.save(
                        at_step, state, force=True, extra=self._ckpt_extra(),
                        # Inside a rollback-replay window the committed
                        # checkpoint at this step captured the ABANDONED
                        # trajectory; the emergency save must replace it or
                        # the restart resumes the wrong stream.
                        overwrite=at_step <= overwrite_until,
                    ):
                        stats.emergency_saves += 1
                except RuntimeError:
                    log.warning(
                        "state was donated mid-step; relying on last "
                        "periodic checkpoint"
                    )
                self.ckpt.wait()
            raise
        finally:
            compiles.close()
            if tracing:
                jax.profiler.stop_trace()
            if self.ckpt is not None:
                self.ckpt.wait()
            self.metrics.close()
            from orion_tpu.obs import export_chrome_safe

            export_chrome_safe(self._tracer, cfg.train.trace_path)
            if cfg.train.metrics_prom:
                try:
                    self.registry.export_prometheus(cfg.train.metrics_prom)
                except OSError as e:
                    log.error("metrics_prom export failed: %s", e)

    def _overwrite_from(self, good_step: int) -> int:
        """Newest committed step at rollback time: checkpoints in
        (good_step, newest] captured the abandoned trajectory and are
        overwritten as the replay passes them."""
        if self.ckpt is None:
            return -1
        latest = self.ckpt.latest_step()
        return latest if latest is not None else -1
