"""Declarative contracts over compiled orion programs (ISSUE 15 tentpole).

Every compiled-program invariant the stack depends on used to live as a
one-off HLO pin inside some test file: donation fully aliased
(``Trainer.memory_report``), guard-off traces free of finiteness ops
(test_train_fault), the grouped scan's stacked-DUS shrink
(test_scan_remat), ZeRO-1's reduce-scatter/all-gather pair (test_zero1).
This module centralizes them as a *contract registry*: each contract
names a program builder (the train step at a parallel layout; an engine
dispatch program per kernel path) and a tuple of predicates over the
compiled artifact. ``tools/contract_check.py`` sweeps contracts across a
layout grid in subprocesses; tests call :func:`check` directly and prove
every predicate live with injected violations (tests/test_contracts.py).

Three artifact views, all static (no program is ever executed):

- **jaxpr** (``jax.jit(f).trace``): primitive census — host callbacks,
  finiteness ops, dtype-upcast sites (counted per *staged* site, so a
  scanned layer body counts once, not per layer);
- **StableHLO** (``lower().as_text()``): textual matchers — f64 tensors,
  custom_call targets, the executed-stacked-DUS counter;
- **optimized HLO** (``compile().as_text()`` + ``memory_analysis()``):
  what XLA actually scheduled — collective inventory (SPMD partitioning
  inserts collectives only at compile time) and donation aliasing.
"""

from __future__ import annotations

import math
import re
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ProgramArtifact", "Contract", "ContractResult", "Violation",
    "CONTRACTS", "check", "check_artifact", "artifact_from_fn",
    "build_program", "iter_eqns", "primitive_census", "count_bf16_upcasts",
    "collective_census", "executed_stacked_dus", "donation_report",
    "smoke_contracts", "grid_contracts",
]


class ContractError(RuntimeError):
    """A contract could not be evaluated (bad layout / missing program) —
    distinct from a contract *violation*."""


# ---------------------------------------------------------------------------
# Artifact
# ---------------------------------------------------------------------------


@dataclass
class ProgramArtifact:
    """Lazy views over one lowered program.

    ``lowered``/``traced`` come from the builder; the StableHLO text,
    compiled executable, optimized-HLO text and memory analysis are
    derived on first use (compiling is the expensive step — predicates
    that only need the trace never pay for it). Tests can also construct
    artifacts directly from raw text (``stablehlo=``/``optimized=``) to
    exercise a matcher on synthetic input.
    """

    name: str
    lowered: Any = None
    traced: Any = None            # jax Traced (jaxpr access), optional
    donated: tuple = ()           # abstract donated input leaves
    meta: dict = field(default_factory=dict)
    stablehlo_text: Optional[str] = None
    optimized_text: Optional[str] = None
    _compiled: Any = None

    @property
    def jaxpr(self):
        if self.traced is None:
            return None
        return self.traced.jaxpr

    @property
    def stablehlo(self) -> str:
        if self.stablehlo_text is None:
            if self.lowered is None:
                raise ContractError(f"{self.name}: no lowered module")
            self.stablehlo_text = self.lowered.as_text()
        return self.stablehlo_text

    def compiled(self):
        if self._compiled is None:
            if self.lowered is None:
                raise ContractError(f"{self.name}: no lowered module")
            self._compiled = self.lowered.compile()
        return self._compiled

    @property
    def optimized_hlo(self) -> str:
        if self.optimized_text is None:
            self.optimized_text = self.compiled().as_text()
        return self.optimized_text

    def memory_analysis(self):
        return self.compiled().memory_analysis()


def artifact_from_fn(
    name: str, fn, *args, donate_argnums: tuple = (), **jit_kw
) -> ProgramArtifact:
    """Build an artifact from a plain callable — the injected-violation
    fixture path (tests) and ad-hoc matcher runs."""
    jitted = jax.jit(fn, donate_argnums=donate_argnums, **jit_kw)
    donated = tuple(
        leaf
        for i in donate_argnums
        for leaf in jax.tree.leaves(args[i])
    )
    return ProgramArtifact(
        name=name,
        lowered=jitted.lower(*args),
        traced=_try_trace(jitted, args),
        donated=donated,
    )


def _try_trace(jitted, args, kwargs=None):
    """jaxpr access is best-effort: every predicate that walks the jaxpr
    falls back to a text matcher when tracing is unavailable (older jit
    wrappers, checkify closures)."""
    try:
        return jitted.trace(*args, **(kwargs or {}))
    except Exception:
        return None


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------


def _open_jaxpr(x):
    """The open jaxpr behind ``x`` (a jaxpr, or the closed form that wraps
    one in ``.jaxpr``), or None for any other equation parameter. Duck-
    typed on ``.eqns``: the classes' import path has moved between jax
    releases, their shape has not."""
    inner = getattr(x, "jaxpr", x)
    return inner if hasattr(inner, "eqns") else None


def _sub_jaxprs(eqn) -> Iterator:
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            sub = _open_jaxpr(x)
            if sub is not None:
                yield sub


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first over every equation, descending into sub-jaxprs
    (scan/while/cond/pjit bodies) — a census over *staged sites*, not
    executions: a scanned layer body contributes each primitive once."""
    for eqn in _open_jaxpr(jaxpr).eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def primitive_census(jaxpr) -> Counter:
    return Counter(eqn.primitive.name for eqn in iter_eqns(jaxpr))


def count_bf16_upcasts(jaxpr) -> int:
    """Staged ``convert_element_type`` sites bf16 -> f32 — the silent-
    upcast budget (each is a whitelisted site: norms compute in f32,
    logits/loss promote; anything beyond the budget is a new full-width
    f32 activation sneaking into a bf16 model)."""
    n = 0
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        inv = eqn.invars[0]
        out = eqn.outvars[0]
        if (
            getattr(inv, "aval", None) is not None
            and inv.aval.dtype == jnp.bfloat16
            and out.aval.dtype == jnp.float32
        ):
            n += 1
    return n


_HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
})
# StableHLO fallback: jax lowers every host callback flavor to a
# custom_call against the cpu/tpu callback runtime.
_CALLBACK_RE = re.compile(
    r"custom_call\s+@(xla_python_cpu_callback\w*|xla_ffi_python_cpu_callback"
    r"\w*|xla_python_gpu_callback\w*|tpu_callback\w*)"
)


# ---------------------------------------------------------------------------
# HLO matchers
# ---------------------------------------------------------------------------

# A scan writing per-iteration slices lowers to a while whose body does one
# dynamic_update_slice of a [1, ...]-leading update into a [trip, ...]-
# leading buffer (migrated from tests/test_scan_remat.py — ISSUE 15).
_DUS_RE = re.compile(
    r"stablehlo\.dynamic_update_slice[^\n]*:\s*"
    r"\(tensor<(\d+)x[^>]*>,\s*tensor<(\d+)x"
)


def executed_stacked_dus(stablehlo_text: str) -> int:
    """Executed stacked-buffer DUS writes in a lowered module: each
    unit-leading update into a [trip_count, ...] buffer EXECUTES
    trip_count slice writes — exactly the fwd stash + bwd stacked-grad
    traffic the grouped scan (model.scan_group) shrinks G-fold."""
    total = 0
    for m in _DUS_RE.finditer(stablehlo_text):
        target_lead, update_lead = int(m.group(1)), int(m.group(2))
        if update_lead == 1 and target_lead > 1:
            total += target_lead
    return total


COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)
# Optimized-HLO instruction form: `%name = ty[...] all-gather(...)`, or the
# async `-start(` pair whose result is a TUPLE type with spaces
# (`%s = (f32[1,8], f32[8,8]) all-gather-start(...)`); `-done(` carries no
# new collective (the trailing `\(` rejects it: after the op name a done
# line continues `-done(`).
_COLL_RE = re.compile(
    r"=\s+(?:\([^)]*\)|\S+)\s+(" + "|".join(COLLECTIVE_OPS)
    + r")(?:-start)?\("
)


def collective_census(optimized_hlo: str) -> dict[str, int]:
    """Count scheduled collective instructions per op kind — what the SPMD
    partitioner actually inserted (StableHLO carries only sharding
    annotations; collectives exist after compile)."""
    census = {op: 0 for op in COLLECTIVE_OPS}
    for m in _COLL_RE.finditer(optimized_hlo):
        census[m.group(1)] += 1
    return census


_F64_RE = re.compile(r"tensor<(?:\d+x)*f64>|xf64[>x]|\bf64\[")


def _leaf_chip_bytes(leaf) -> int:
    """Per-device bytes of one abstract leaf (replicated dims count in
    full — the same accounting as Trainer.memory_report)."""
    sharding = getattr(leaf, "sharding", None)
    shape = (
        sharding.shard_shape(leaf.shape) if sharding is not None
        else leaf.shape
    )
    return math.prod(shape) * jnp.dtype(leaf.dtype).itemsize


def donation_report(artifact: ProgramArtifact) -> dict:
    """Donated-vs-aliased accounting off XLA's compiled memory analysis.
    A donated buffer that failed to alias silently DOUBLES its footprint
    for the step — the exact headroom regression class memory_report
    guards in the trainer, generalized to any program."""
    ma = artifact.memory_analysis()
    donated = sum(_leaf_chip_bytes(leaf) for leaf in artifact.donated)
    report = {"donated_bytes": donated, "available": ma is not None}
    if ma is not None:
        report["alias_bytes"] = int(ma.alias_size_in_bytes)
        report["leaked_bytes"] = max(
            0, donated - int(ma.alias_size_in_bytes)
        )
    return report


# ---------------------------------------------------------------------------
# Predicates
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Violation:
    contract: str
    predicate: str
    detail: str

    def __str__(self):
        return f"{self.contract}/{self.predicate}: {self.detail}"


@dataclass(frozen=True)
class Predicate:
    """A named check over one artifact; returns a list of violation
    detail strings (empty = holds)."""

    name: str
    fn: Callable[[ProgramArtifact], list]

    def __call__(self, artifact: ProgramArtifact) -> list:
        return self.fn(artifact)


def predicate(name: str):
    def wrap(fn) -> Predicate:
        return Predicate(name, fn)
    return wrap


@predicate("no_f64")
def no_f64(art: ProgramArtifact) -> list:
    """No float64 anywhere: an f64 tensor on TPU software-emulates (and on
    any backend doubles bytes) — always an accidental promotion here."""
    if art.jaxpr is not None:
        hits = sorted({
            str(v.aval.dtype)
            for eqn in iter_eqns(art.jaxpr)
            for v in eqn.outvars
            if getattr(v, "aval", None) is not None
            and getattr(v.aval, "dtype", None) is not None
            and v.aval.dtype == jnp.float64
        })
        if hits:
            return [f"float64 values staged in jaxpr ({len(hits)} dtypes)"]
        return []
    m = _F64_RE.search(art.stablehlo)
    return [f"f64 tensor in StableHLO: ...{m.group(0)}..."] if m else []


@predicate("no_host_callbacks")
def no_host_callbacks(art: ProgramArtifact) -> list:
    """No host callbacks staged: a pure/debug/io callback in a dispatch
    program is a per-step host round-trip (and a donation barrier) —
    only model.debug_asserts may stage them, and it is off here."""
    out = []
    if art.jaxpr is not None:
        census = primitive_census(art.jaxpr)
        prims = sorted(_HOST_CALLBACK_PRIMS & set(census))
        if prims:
            out.append(f"host-callback primitives staged: {prims}")
    m = _CALLBACK_RE.search(art.stablehlo)
    if m and not out:
        out.append(f"host-callback custom_call in StableHLO: @{m.group(1)}")
    return out


def _finiteness_staged(art: ProgramArtifact) -> bool:
    if art.jaxpr is not None:
        return "is_finite" in primitive_census(art.jaxpr)
    txt = art.stablehlo
    return "is_finite" in txt or "is-finite" in txt


@predicate("no_finiteness_ops")
def no_finiteness_ops(art: ProgramArtifact) -> list:
    """Guard-off purity: with nan_guard / anomaly_guard off, the compiled
    program must be the pre-guard trace — zero is_finite ops (the PR 6/7
    bit-identical-when-off promise, migrated from test_train_fault)."""
    if _finiteness_staged(art):
        return ["is_finite ops staged in a guard-off program"]
    return []


@predicate("finiteness_staged")
def finiteness_staged(art: ProgramArtifact) -> list:
    """Positive control: the guard-ON program must actually stage the
    finiteness check (a contract that can only pass vacuously is dead)."""
    if not _finiteness_staged(art):
        return ["guard on, but no is_finite ops staged"]
    return []


@predicate("donation_complete")
def donation_complete(art: ProgramArtifact) -> list:
    """Every donated input byte aliases into an output buffer."""
    rep = donation_report(art)
    if not rep["available"]:
        return ["memory_analysis unavailable on this backend"]
    if rep["donated_bytes"] == 0:
        return ["nothing donated: donation contract is vacuous here"]
    if rep["leaked_bytes"] > 0:
        return [
            f"donation leaked {rep['leaked_bytes']} of "
            f"{rep['donated_bytes']} donated per-chip bytes "
            f"(alias_size={rep['alias_bytes']})"
        ]
    return []


def n_param_leaves(art: ProgramArtifact) -> int:
    """Weight-leaf count of the artifact's model — the per-leaf unit the
    CPU emitter schedules collectives at (no combiner pass: one grad
    all-reduce / ZeRO-1 all-gather per leaf; on-chip XLA combines them,
    so bands expressed in leaves hold on both backends)."""
    from orion_tpu.models import init_params

    cfg = art.meta["cfg"]
    shapes = jax.eval_shape(
        lambda: init_params(cfg.model, jax.random.key(0))
    )
    return len(jax.tree.leaves(shapes))


def collective_inventory(**expect) -> Predicate:
    """Pin the scheduled collective census. ``expect`` maps op name
    (underscored: ``all_gather=1``) to an exact int, a ``(lo, hi)``
    inclusive band, or a callable(artifact) -> int | (lo, hi) for
    layout-derived bounds; unnamed ops are unconstrained."""

    spec = {k.replace("_", "-"): v for k, v in expect.items()}
    unknown = set(spec) - set(COLLECTIVE_OPS)
    if unknown:
        raise ValueError(f"unknown collective ops: {sorted(unknown)}")

    def fn(art: ProgramArtifact) -> list:
        census = collective_census(art.optimized_hlo)
        out = []
        for op, want in spec.items():
            got = census[op]
            if callable(want):
                want = want(art)
            lo, hi = want if isinstance(want, tuple) else (want, want)
            if not (lo <= got <= hi):
                out.append(
                    f"{op} count {got} outside expected "
                    f"[{lo}, {hi}] (census: " + ", ".join(
                        f"{k}={v}" for k, v in census.items() if v
                    ) + ")"
                )
        return out

    return Predicate("collective_inventory", fn)


def dtype_whitelist_budget(art: ProgramArtifact) -> int:
    """Whitelisted staged bf16->f32 convert sites for the tiny-llama
    train step, as a function of layout: ~16 per layer staged in the
    scan body (norm x2 / rotary / softmax / residual-boundary mirrors),
    +4 per layer under a remat policy (the bwd body re-stages the fwd's
    converts), +5 fixed (final norm, logits, loss, schedule), +2 slack.
    Measured exact across scan_group x remat combos
    (tests/test_contracts.py pins the fit)."""
    mcfg = art.meta["cfg"].model
    unit = mcfg.scan_unit if mcfg.scan_layers else mcfg.n_layers
    remat_extra = 4 * unit if mcfg.remat != "none" else 0
    return 5 + 16 * unit + remat_extra + 2


def bf16_upcast_budget(budget) -> Predicate:
    """Dtype discipline: at most ``budget`` (int, or callable(artifact)
    -> int for layout-derived budgets) staged bf16->f32 convert sites —
    the norm/master/logits whitelist. A new full-width f32 activation in
    a bf16 model shows up as a budget overrun."""

    def fn(art: ProgramArtifact) -> list:
        if art.jaxpr is None:
            return ["no jaxpr available for upcast census"]
        b = budget(art) if callable(budget) else budget
        n = count_bf16_upcasts(art.jaxpr)
        if n > b:
            return [
                f"{n} staged bf16->f32 convert sites exceed the "
                f"whitelist budget {b}"
            ]
        return []

    return Predicate("bf16_upcast_budget", fn)


def output_sharded_over(getter: Callable[[Any], Any], axis: str,
                        what: str) -> Predicate:
    """The compiled executable's output shardings place ``what`` over
    ``axis`` — the artifact-level form of test_zero1's physical-sharding
    pin (the memory lever IS the sharding)."""

    def fn(art: ProgramArtifact) -> list:
        try:
            out_sh = art.compiled().output_shardings
        except Exception as e:  # pragma: no cover - jax-version dependent
            return [f"output_shardings unavailable: {type(e).__name__}"]
        leaves = jax.tree.leaves(
            getter(out_sh),
            is_leaf=lambda x: hasattr(x, "spec"),
        )
        if not leaves:
            return [f"{what}: no output sharding leaves found"]
        bad = sum(
            1 for s in leaves
            if axis not in jax.tree.leaves(tuple(s.spec))
        )
        if bad:
            return [
                f"{what}: {bad}/{len(leaves)} output leaves not sharded "
                f"over '{axis}'"
            ]
        return []

    return Predicate("output_sharded_over", fn)


# ---------------------------------------------------------------------------
# Program builders
# ---------------------------------------------------------------------------

# Small enough to lower/compile in seconds on the fake-device CPU mesh,
# big enough that every structural feature (scan, GQA, norms) is staged.
TRAIN_BASE = (
    "runtime.platform=cpu",
    "train.num_steps=4",
    "train.log_interval=1000",
    "optimizer.warmup_steps=1",
)
ENGINE_BASE = (
    "inference.max_seq_len=128",
    "inference.page_size=16",
    "inference.num_pages=32",
    "inference.max_batch_size=4",
    "inference.prefill_chunk=16",
    "inference.max_new_tokens=8",
)

ENGINE_PROGRAMS = (
    "prefill", "decode", "decode_defaults", "mixed", "mixed_defaults",
    "verify", "verify_defaults", "mixed_verify", "mixed_verify_defaults",
    # The grammar-masked verify specialization (inference.constrained):
    # the same _verify_defaults program called with a legal_mask —
    # switching None -> array is a distinct jit specialization, so the
    # masked trace gets its own contract row.
    "verify_masked",
    # The KV-page migration envelope halves (ISSUE 20): the batched
    # gather (export — a pure pool read, NO donation) and the batched
    # scatter (import — donates the destination cache). One dispatch per
    # page batch by construction; the contracts pin that neither half
    # smuggles in host callbacks, f64, finiteness ops or collectives.
    "migrate_gather", "migrate_scatter",
)


def build_train_step(
    overrides: Sequence[str] = (), preset: str = "tiny-llama"
) -> ProgramArtifact:
    """Lower the Trainer's jitted step at a layout — abstract state/batch
    exactly as the hot path runs them (the memory_report shapes)."""
    from orion_tpu.config import get_config
    from orion_tpu.train.trainer import Trainer

    cfg = get_config(preset, list(TRAIN_BASE) + list(overrides))
    t = Trainer(cfg)
    state = t.abstract_state()
    batch = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding),
        t.global_batch(0),
    )
    args: tuple = (state, batch)
    if t.cfg.train.anomaly_guard:
        args = (*args, jax.ShapeDtypeStruct((), jnp.float32))
    return ProgramArtifact(
        name="train_step",
        lowered=t._jit_step.lower(*args),
        traced=_try_trace(t._jit_step, args),
        donated=tuple(jax.tree.leaves(state)),
        meta={"cfg": t.cfg, "mesh": t.mesh},
    )


def _tp_shard_params(cfg, params, tp: int):
    from orion_tpu.config import ParallelConfig
    from orion_tpu.models.transformer import param_logical_axes
    from orion_tpu.parallel.sharding import param_shardings
    from orion_tpu.runtime import build_mesh

    mesh = build_mesh(
        ParallelConfig(tp=tp), devices=jax.devices("cpu")[:tp]
    )
    return jax.device_put(
        params, param_shardings(mesh, param_logical_axes(cfg.model))
    )


def build_engine_program(
    program: str,
    overrides: Sequence[str] = (),
    preset: str = "tiny-llama",
    tp: int = 0,
) -> ProgramArtifact:
    """Lower one engine dispatch program with inputs shaped exactly as the
    engine's call sites assemble them (engine._decode_window_all /
    _prefill_burst / _verify_all / _mixed_decode). The arrays are zeros —
    lowering only cares about shape/dtype — and the cache is the donated
    tree (executor donate_argnums=(1,)). ``tp > 1`` serves tp-sharded
    params over a fake tp mesh (the xla path partitions from the params'
    shardings alone)."""
    from orion_tpu.config import get_config
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models import init_params

    if program not in ENGINE_PROGRAMS:
        raise ContractError(
            f"unknown engine program {program!r}; have {ENGINE_PROGRAMS}"
        )
    cfg = get_config(preset, list(ENGINE_BASE) + list(overrides))
    params = init_params(cfg.model, jax.random.key(0))
    if tp > 1:
        params = _tp_shard_params(cfg, params, tp)
    eng = InferenceEngine(cfg, params)
    if tp > 1:
        # Steady-state cache layout: on the xla tp path XLA shards the
        # pool over kv heads from the first dispatch on (the same
        # P(None, 'tp') the pallas path places explicitly). Donating the
        # day-0 unsharded cache would measure a one-off reshard, not the
        # hot loop — the contract checks the program the engine re-runs.
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = next(iter(jax.tree.leaves(params))).sharding.mesh
        spec = {"k": P(None, "tp"), "v": P(None, "tp"),
                "k_scale": P(None, "tp"), "v_scale": P(None, "tp")}
        eng.cache = {
            name: jax.device_put(arr, NamedSharding(mesh, spec[name]))
            for name, arr in eng.cache.items()
        }
    jitted, args, kwargs = _engine_call(eng, program)
    return ProgramArtifact(
        name=f"engine_{program}",
        lowered=jitted.lower(*args, **kwargs),
        traced=_try_trace(jitted, args, kwargs),
        donated=tuple(
            jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding),
                eng.cache,
            ).values()
        ),
        meta={"cfg": cfg, "engine_cfg": eng.icfg, "program": program},
    )


def _engine_call(eng, program: str):
    """Mirror the engine's dispatch-arg assembly for each program (shape/
    dtype only; values are zeros). Drift is loud: a signature change makes
    the lower() here fail, which is the contract run failing."""
    i32, f32 = np.int32, np.float32
    B, pps = eng.max_batch, eng.pages_per_seq
    zB = np.zeros(B, i32)
    mask = np.zeros(B, bool)
    pt = np.zeros((B, pps), i32)
    sampling = (np.zeros(B, f32), np.zeros(B, i32), np.ones(B, f32))
    key = eng._key

    if program in ("decode", "decode_defaults"):
        # The engine's one key; the window's length is static.
        common = (eng.params, eng.cache, zB, zB, pt, mask, key)
        extra = sampling if program == "decode" else ()
        return (getattr(eng, "_" + program), common + extra,
                {"window": eng.decode_window})

    if program in ("migrate_gather", "migrate_scatter"):
        # The migration copy envelope (ISSUE 20): pow2-padded page-id
        # batches, exactly as export_migration_pages / import_pages
        # assemble them. Gather reads the pool (no donation); scatter
        # donates the destination cache (executor donate_argnums=(0,)).
        pages = np.zeros(8, i32)
        if program == "migrate_gather":
            return eng._gather_pages, (eng.cache, pages), {}
        L = eng.mcfg.n_layers
        blocks = {
            name: np.zeros((8, L) + arr.shape[1:], arr.dtype)
            for name, arr in eng.cache.items()
        }
        return eng._scatter_pages, (eng.cache, pages, blocks), {}

    if program == "prefill":
        S = eng.icfg.prefill_chunk
        nb = 2
        args = (
            eng.params, eng.cache,
            np.zeros((nb, S), i32), np.ones(nb, i32),
            np.zeros((nb, S // eng.psz), i32),
            np.zeros(nb, i32), np.zeros((nb, 0), i32),
            # No state rows (a K/V model); each row's slot, the step's
            # last tokens and the engine's key.
            None, np.zeros(nb, i32), zB, key,
        )
        return eng._prefill.program, args, {}

    if program in ("verify", "verify_defaults", "verify_masked"):
        if getattr(eng, "_verify", None) is None:
            raise ContractError(
                "verify programs need inference.speculative=true or "
                "inference.constrained=true in the contract overrides"
            )
        W2 = eng.icfg.speculate_tokens + 1
        common = (
            eng.params, eng.cache, np.zeros((B, W2), i32), zB,
            np.ones(B, i32), pt, mask, key,
        )
        extra = sampling if program == "verify" else ()
        if program == "verify_masked":
            # The masked specialization: all-True rows shape the trace
            # exactly as the engine's host-built FSM masks do.
            kwargs = {
                "legal_mask": np.ones(
                    (B, W2, eng.mcfg.vocab_size), bool
                ),
            }
            return eng._verify_defaults, common, kwargs
        return getattr(eng, "_" + program), common + extra, {}

    # mixed / mixed_verify: one-page chunk rows (the chunk width is a
    # static arg — any page-multiple width traces the same program family).
    if not eng.chunked:
        raise ContractError(
            "mixed programs need inference.chunked_prefill=true in the "
            "contract overrides"
        )
    S = eng.psz
    chunk = (
        np.zeros((1, S), i32), np.ones(1, i32),
        np.zeros((1, S // eng.psz), i32),
        np.zeros(1, i32), np.zeros((1, 0), i32),
    )
    if program in ("mixed", "mixed_defaults"):
        common = (eng.params, eng.cache, zB, zB, pt, mask, key) + chunk
        extra = sampling if program == "mixed" else ()
        return getattr(eng, "_" + program), common + extra, {}

    if getattr(eng, "_mixed_verify", None) is None:
        raise ContractError(
            "mixed_verify programs need inference.speculative=true (or "
            "inference.constrained=true) AND "
            "inference.chunked_prefill=true in the contract overrides"
        )
    W2 = eng.icfg.speculate_tokens + 1
    common = (
        eng.params, eng.cache, np.zeros((B, W2), i32), zB,
        np.ones(B, i32), pt, mask, key,
    ) + chunk
    extra = sampling if program == "mixed_verify" else ()
    return getattr(eng, "_" + program), common + extra, {}


def build_program(
    program: str, overrides: Sequence[str] = (), **kw
) -> ProgramArtifact:
    """The registry's single builder entry point: ``"train"`` or an
    engine program name."""
    if program == "train":
        return build_train_step(overrides, **kw)
    return build_engine_program(program, overrides, **kw)


# ---------------------------------------------------------------------------
# Contract registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Contract:
    """One declarative contract: a program at a layout plus predicates.

    ``smoke`` marks the cpu-viable fast set (tools/contract_check.py
    --smoke, wired into tier-1); the full grid adds layout compositions
    on top via extra overrides. ``devices`` is the fake-device floor the
    layout needs (the sweeper skips rows the host cannot fake)."""

    name: str
    program: str
    overrides: tuple = ()
    predicates: tuple = ()
    smoke: bool = False
    devices: int = 1
    tp: int = 0
    doc: str = ""


@dataclass
class ContractResult:
    name: str
    ok: bool
    violations: list
    seconds: float
    notes: dict = field(default_factory=dict)

    def as_row(self) -> dict:
        return {
            "contract": self.name,
            "ok": self.ok,
            "violations": [str(v) for v in self.violations],
            "seconds": round(self.seconds, 2),
            **self.notes,
        }


def _registry() -> dict[str, Contract]:
    C: dict[str, Contract] = {}

    def add(name, program, overrides=(), predicates=(), **kw):
        C[name] = Contract(
            name=name, program=program, overrides=tuple(overrides),
            predicates=tuple(predicates), **kw
        )

    # -- train step -------------------------------------------------------
    add(
        "train_hygiene", "train",
        predicates=(no_f64, no_host_callbacks, no_finiteness_ops,
                    donation_complete),
        smoke=True,
        doc="baseline train step: no f64 promotion, no host callbacks "
            "(debug_asserts off), guard-off purity (zero is_finite — the "
            "test_train_fault pin), donation fully aliased "
            "(memory_report's failure class, PR 4/9)",
    )
    add(
        "train_guard_staged", "train",
        overrides=("train.anomaly_guard=true",),
        predicates=(finiteness_staged, donation_complete),
        smoke=True,
        doc="positive control: anomaly_guard=on really stages the "
            "finiteness check AND keeps the donation-safe skip aliased",
    )
    add(
        "train_dtype_discipline", "train",
        overrides=("model.dtype=bfloat16",),
        predicates=(bf16_upcast_budget(dtype_whitelist_budget), no_f64),
        smoke=True,
        doc="bf16 activations stay bf16: staged f32 upcast sites bounded "
            "by the norm/master/logits whitelist",
    )
    add(
        "zero1_collectives", "train",
        overrides=("parallel.dp=8", "data.batch_size=8",
                   "train.zero1=true"),
        predicates=(
            # ONE RS/AG pair per weight-update leaf and nothing more: the
            # updated-param AG leg gathers each leaf exactly once (2x
            # would be a doubled wire bill), the grad reduction costs at
            # most one reduce-scatter-or-all-reduce per leaf (XLA's CPU
            # emitter spells RS as AR + local slice) plus the fused
            # loss/metric scalars — and no ring/a2a traffic at all.
            collective_inventory(
                all_gather=lambda a: (1, n_param_leaves(a)),
                reduce_scatter=lambda a: (0, n_param_leaves(a)),
                all_reduce=lambda a: (0, n_param_leaves(a) + 3),
                collective_permute=0, all_to_all=0,
            ),
            output_sharded_over(
                lambda out: out[0]["opt"]["mu"], "dp", "adam mu moments"
            ),
            donation_complete,
        ),
        smoke=True,
        devices=8,
        doc="ZeRO-1 step: one RS/AG pair per update leaf over dp "
            "(PAPERS.md 2004.13336) and the moments physically "
            "dp-sharded (the test_zero1 pin, artifact-level)",
    )
    add(
        "dp_baseline_collectives", "train",
        overrides=("parallel.dp=8", "data.batch_size=8"),
        predicates=(
            # Plain DP: the grad all-reduce only — ANY all-gather or
            # reduce-scatter here means some state silently stopped
            # being replicated (the footprint regression zero1 makes on
            # purpose and nothing else may).
            collective_inventory(
                all_gather=0, reduce_scatter=0,
                all_reduce=lambda a: (1, n_param_leaves(a) + 3),
                collective_permute=0, all_to_all=0,
            ),
            donation_complete,
        ),
        smoke=True,
        devices=8,
        doc="plain dp=8 step: grads all-reduce only; no gathers/scatters "
            "(state stays replicated)",
    )

    def _pp_hop_band(art: ProgramArtifact) -> tuple:
        """Staged ring-hop band for a pipeline step: the tick loops are
        lax.scan'd, so each loop body stages its hop once (fwd + bwd =
        2), and XLA may unroll up to one per tick — the band is
        [2, 2*(M+pp-1)]. Zero means the ring is GONE (stages stopped
        talking); above means a schedule staged extra hops per tick."""
        p = art.meta["cfg"].parallel
        return (2, 2 * (p.pp_microbatches + p.pp - 1))

    add(
        "pp_ring_hops", "train",
        overrides=("parallel.pp=2", "parallel.pp_microbatches=2",
                   "model.scan_layers=true", "model.n_layers=2",
                   "data.batch_size=4"),
        predicates=(
            # Ring hops only: point-to-point traffic spelled as
            # collective-permute (lax.ppermute). An all-gather here is
            # the failure mode where a stage gathers the whole activation
            # stack instead of ring-hopping its slice; all-reduce belongs
            # to the metric scalars only.
            collective_inventory(
                all_gather=0, all_to_all=0, reduce_scatter=0,
                collective_permute=_pp_hop_band,
            ),
            donation_complete,
        ),
        devices=2,
        doc="pp=2 pipeline step: ring-hop count per tick bounded "
            "(2..2*(M+pp-1) staged collective-permutes), no stage-gather "
            "all-gathers",
    )

    # -- engine programs --------------------------------------------------
    eng_hygiene = (no_f64, no_host_callbacks, no_finiteness_ops,
                   donation_complete)
    add(
        "decode_hygiene", "decode_defaults",
        predicates=eng_hygiene, smoke=True,
        doc="fused decode window (greedy-defaults path): guard-off "
            "purity, no callbacks, cache donation aliased",
    )
    add(
        "decode_guard_staged", "decode_defaults",
        overrides=("inference.nan_guard=true",),
        predicates=(finiteness_staged, donation_complete), smoke=True,
        doc="positive control: nan_guard=on decode stages is_finite and "
            "still donates the cache",
    )
    add(
        "prefill_hygiene", "prefill",
        predicates=(no_f64, no_host_callbacks, donation_complete),
        smoke=True,
        doc="batched prefill: no callbacks/f64, cache donation aliased",
    )
    add(
        "verify_hygiene", "verify_defaults",
        overrides=("inference.speculative=true",),
        predicates=eng_hygiene,
        doc="speculative verify dispatch: hygiene + cache donation",
    )
    add(
        "constrained_verify_hygiene", "verify_masked",
        overrides=("inference.constrained=true",),
        predicates=eng_hygiene + (
            # Constrained programs may not grow a wire bill: a single-
            # replica masked verify schedules ZERO collectives, exactly
            # like its unmasked twin — the FSM mask is a pure elementwise
            # where() on the logits.
            collective_inventory(
                all_gather=0, reduce_scatter=0, all_reduce=0,
                collective_permute=0, all_to_all=0,
            ),
        ),
        doc="grammar-masked verify specialization "
            "(inference.constrained): the FSM legal_mask composes into "
            "the verify program with no host callbacks, no f64, no "
            "finiteness ops, zero collectives, cache still donated",
    )
    add(
        "mixed_hygiene", "mixed_defaults",
        overrides=("inference.chunked_prefill=true",),
        predicates=eng_hygiene,
        doc="mixed decode+chunk dispatch: hygiene + cache donation",
    )
    add(
        "long_prefill_hygiene", "mixed_defaults",
        overrides=("inference.chunked_prefill=true",
                   "inference.long_context=true",
                   "inference.host_tier_bytes=1048576",
                   "model.sliding_window=32"),
        predicates=eng_hygiene + (
            # The page walk is scalar metadata, not communication: a
            # single-replica long-context mixed program schedules ZERO
            # collectives, exactly like its short-context twin.
            collective_inventory(
                all_gather=0, reduce_scatter=0, all_reduce=0,
                collective_permute=0, all_to_all=0,
            ),
        ),
        smoke=True,
        doc="long-context serving (ISSUE 19): the mixed chunk+decode "
            "program under long_context + SWA gains no host callbacks, "
            "d2h copies, finiteness ops or collectives from the "
            "per-request paging machinery — demote/restore copies live "
            "in their own dispatches, never in the compiled step; cache "
            "donation still aliased",
    )
    add(
        "mixed_verify_hygiene", "mixed_verify_defaults",
        overrides=("inference.chunked_prefill=true",
                   "inference.speculative=true"),
        predicates=eng_hygiene,
        doc="mixed verify dispatch: hygiene + cache donation",
    )
    add(
        "decode_sampled_hygiene", "decode",
        predicates=eng_hygiene,
        doc="per-request-sampling decode path: same hygiene as defaults",
    )
    add(
        "host_tier_decode_hygiene", "decode_defaults",
        overrides=("inference.prefix_cache=true",
                   "inference.host_tier_bytes=1048576"),
        predicates=eng_hygiene, smoke=True,
        doc="host-tier-enabled decode (ISSUE 18): the tiered cache is "
            "pure host machinery — the compiled decode program gains no "
            "host callbacks or d2h copies, cache donation still aliased "
            "(eviction/restore copies live in their own dispatches, "
            "never on the decode hot path)",
    )
    add(
        "host_tier_verify_hygiene", "verify_defaults",
        overrides=("inference.prefix_cache=true",
                   "inference.host_tier_bytes=1048576",
                   "inference.speculative=true"),
        predicates=eng_hygiene,
        doc="host-tier x speculation: the verify dispatch is equally "
            "untouched by the tier (no callbacks, donation complete)",
    )
    # Zero-collective pin shared by both migration envelope halves: a
    # single-replica page copy is pure pool traffic — ONE dispatch per
    # pow2-padded page batch with no collective fan-out (a per-page
    # dispatch blowup would show up as N gathers in the bench, but a
    # collective sneaking into the copy program would show up HERE).
    _mig_no_collectives = collective_inventory(
        all_gather=0, reduce_scatter=0, all_reduce=0,
        collective_permute=0, all_to_all=0,
    )
    add(
        "migration_hygiene", "migrate_gather",
        predicates=(no_f64, no_host_callbacks, no_finiteness_ops,
                    _mig_no_collectives),
        smoke=True,
        doc="migration export half (ISSUE 20): the batched page gather "
            "feeding a prefill->decode handoff stages no host callbacks/"
            "f64/finiteness ops and zero collectives per page batch. "
            "Deliberately NO donation predicate: the gather is a pure "
            "pool read (the source request keeps serving if the handoff "
            "dies), so nothing is donated by design",
    )
    add(
        "migration_scatter_hygiene", "migrate_scatter",
        predicates=eng_hygiene + (_mig_no_collectives,),
        smoke=True,
        doc="migration import half (ISSUE 20): the batched page scatter "
            "admitting migrated KV into the decode replica's pool — same "
            "hygiene, zero collectives, and the destination cache "
            "donation fully aliased (a leaked alias would double the "
            "decode pool for the copy step)",
    )
    add(
        "tp_decode_collectives", "decode_defaults",
        tp=2, devices=2,
        predicates=(
            # tp decode: row-parallel matmul partials all-reduce; nothing
            # may all-gather a weight matrix (that would serialize tp's
            # whole memory win). The logits unembed may gather the [B, V]
            # activation — bounded, not a param gather.
            collective_inventory(all_gather=(0, 2)),
            no_finiteness_ops, donation_complete,
        ),
        doc="tp=2-sharded decode: no unexpected all-gathers (params stay "
            "sharded; only bounded activation gathers allowed)",
    )
    return C


CONTRACTS: dict[str, Contract] = _registry()


def smoke_contracts() -> list[str]:
    return [c.name for c in CONTRACTS.values() if c.smoke]


def grid_contracts() -> list[str]:
    return list(CONTRACTS)


def check_artifact(
    artifact: ProgramArtifact,
    predicates: Sequence[Predicate],
    contract_name: str = "adhoc",
) -> list:
    """Run predicates over one artifact; returns Violations."""
    out = []
    for pred in predicates:
        for detail in pred(artifact):
            out.append(Violation(contract_name, pred.name, detail))
    return out


def check(
    name: str, extra_overrides: Sequence[str] = ()
) -> ContractResult:
    """Evaluate one registered contract (optionally at a layout variant
    layered on top of its base overrides)."""
    if name not in CONTRACTS:
        raise ContractError(
            f"unknown contract {name!r}; have {sorted(CONTRACTS)}"
        )
    c = CONTRACTS[name]
    t0 = time.perf_counter()
    artifact = build_program(
        c.program, tuple(c.overrides) + tuple(extra_overrides),
        **({"tp": c.tp} if c.tp else {}),
    )
    violations = check_artifact(artifact, c.predicates, name)
    return ContractResult(
        name=name,
        ok=not violations,
        violations=violations,
        seconds=time.perf_counter() - t0,
        notes={"program": c.program,
               "overrides": list(c.overrides) + list(extra_overrides)},
    )
