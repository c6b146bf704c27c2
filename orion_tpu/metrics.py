"""Step metrics, throughput and MFU accounting, structured logging.

The judged metric is tokens/sec/chip + MFU for Llama-3-8B (BASELINE.json:2);
this module owns that math (SURVEY.md §6 "Metrics / logging"): MFU = achieved
model FLOP/s ÷ (chips × peak bf16 FLOP/s), with model FLOPs from the
6·N·tokens estimate plus the attention term (ModelConfig.flops_per_token).
Sinks: console, JSONL, and in-memory history for tests. The Stats
dataclasses below double as metrics-registry providers (orion_tpu/obs/
registry.py): their as_timing()/summary() dicts are what the registry
snapshots and the Prometheus/JSONL exporters serialize.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks: the denominators of MFU and MBU."""

    bf16_flops: float        # dense bf16 FLOP/s
    hbm_bytes_per_s: float   # HBM bandwidth
    source: str


_CLOUD_TPU_DOCS = "Google Cloud TPU documentation, system architecture"

# The ONE peaks table, keyed by the exact ``device_kind`` string JAX
# reports. A TPU kind that is not here is an error, never a default: add
# it with its source.
DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "TPU v4": DevicePeaks(275e12, 1200e9, _CLOUD_TPU_DOCS + " (TPU v4)"),
    "TPU v5 lite": DevicePeaks(197e12, 819e9, _CLOUD_TPU_DOCS + " (TPU v5e)"),
    "TPU v5p": DevicePeaks(459e12, 2765e9, _CLOUD_TPU_DOCS + " (TPU v5p)"),
    "TPU v6 lite": DevicePeaks(918e12, 1640e9, _CLOUD_TPU_DOCS + " (TPU v6e)"),
}


def device_peaks(device: jax.Device) -> Optional[DevicePeaks]:
    """Peaks of ``device``; None on a platform with no published peak (CPU
    runs report utilization as "not measured", never against a nominal
    number). An unknown TPU kind raises."""
    if device.platform != "tpu":
        return None
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind={device.device_kind!r}; add it to "
            f"orion_tpu.metrics.DEVICE_PEAKS with its source (known: "
            f"{sorted(DEVICE_PEAKS)})"
        ) from None


@dataclass
class StepMetrics:
    step: int
    loss: float
    grad_norm: float = 0.0
    learning_rate: float = 0.0
    step_time_s: float = 0.0
    tokens: int = 0
    tokens_per_sec: float = 0.0
    tokens_per_sec_per_device: float = 0.0
    model_flops: float = 0.0
    # None = not measured: the device has no published peak (a CPU run).
    mfu: Optional[float] = None
    extras: dict[str, float] = field(default_factory=dict)

    def to_dict(self) -> dict[str, Any]:
        d = {
            "step": self.step,
            "loss": self.loss,
            "grad_norm": self.grad_norm,
            "lr": self.learning_rate,
            "step_time_s": self.step_time_s,
            "tokens": self.tokens,
            "tokens_per_sec": self.tokens_per_sec,
            "tokens_per_sec_per_device": self.tokens_per_sec_per_device,
            "mfu": self.mfu,
        }
        d.update(self.extras)
        return d


@dataclass
class PrefixCacheStats:
    """Serving-side prefix-cache counters (SURVEY.md §6 metrics).

    Owned by InferenceEngine and surfaced through ``reset_timing`` (the
    serving metrics drain point, like the device/host split): hits/misses
    count admissions, cached_tokens the prompt tokens served from shared
    pages instead of prefill FLOPs, evicted/inserted/cow pages the pool
    churn the cache itself causes.

    Host-tier counters (inference.host_tier_bytes > 0):
    ``evicted_to_host`` pages demoted to host RAM instead of discarded (a
    subset of ``evicted_pages``), ``host_hits`` admissions that restored a
    host-resident path, ``host_restored_pages`` the pages those restores
    copied back, ``host_recompute_skips`` host-resident matches the
    break-even gate (or a full host pool / restore failure) sent to
    recompute instead.
    """

    hits: int = 0
    misses: int = 0
    cached_tokens: int = 0
    inserted_pages: int = 0
    evicted_pages: int = 0
    cow_pages: int = 0
    evicted_to_host: int = 0
    host_hits: int = 0
    host_restored_pages: int = 0
    host_recompute_skips: int = 0
    # Per-request paging (inference.long_context): pages a live request
    # demoted to host slots (residency cap / preempt-to-host) and pages
    # restored ahead of the dispatch that reads them. Distinct from the
    # tree's evicted_to_host/host_restored_pages: these carry
    # engine-owned refs and never transit the radix tree.
    request_paged_out: int = 0
    request_paged_in: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def as_timing(self) -> dict[str, float]:
        """Flatten into the engine's reset_timing dict."""
        return {
            "prefix_hits": self.hits,
            "prefix_misses": self.misses,
            "prefix_hit_rate": self.hit_rate,
            "cached_tokens": self.cached_tokens,
            "inserted_pages": self.inserted_pages,
            "evicted_pages": self.evicted_pages,
            "cow_pages": self.cow_pages,
            "evicted_to_host": self.evicted_to_host,
            "host_hits": self.host_hits,
            "host_restored_pages": self.host_restored_pages,
            "host_recompute_skips": self.host_recompute_skips,
            "request_paged_out": self.request_paged_out,
            "request_paged_in": self.request_paged_in,
        }


@dataclass
class SpecDecodeStats:
    """Speculative-decoding counters (inference.speculative), owned by
    InferenceEngine and drained through ``reset_timing``.

    ``drafted``/``accepted``/``rolled_back`` count DRAFT tokens (proposed /
    matched-and-emitted / rejected-and-rewound; rolled_back == drafted -
    accepted by construction). ``verify_steps`` counts verify dispatches,
    ``verify_slot_steps`` (verify dispatches x live decode slots) the
    per-slot dispatch opportunities, and ``emitted`` every token a verify
    step emitted (accepted drafts + the per-slot bonus/correction token) —
    so ``emitted / verify_slot_steps`` is the decode tokens-per-dispatch
    the speculation bought (1.0 means it bought nothing). ``gated_steps``
    counts steps where SOMETHING drafted but fewer slots than
    ``inference.spec_min_draft_slots``, so the engine ran the plain
    decode window instead of a whole-batch verify step (the
    draft-density gate; drafts discarded there are not in ``drafted``)."""

    drafted: int = 0
    accepted: int = 0
    rolled_back: int = 0
    emitted: int = 0
    verify_steps: int = 0
    verify_slot_steps: int = 0
    gated_steps: int = 0
    # Token-tree speculation (inference.spec_tree_width > 1):
    # ``tree_nodes`` counts drafted tree nodes (a subset of ``drafted``),
    # ``tree_branch_nodes`` the nodes OUTSIDE the primary chain (the
    # extra breadth a single-path draft could not carry),
    # ``compactions``/``compacted_tokens`` the KV-compaction dispatches
    # and moved tokens when an accepted path was not the primary chain
    # (zero on chain-shaped traffic — the layout is already contiguous).
    tree_nodes: int = 0
    tree_branch_nodes: int = 0
    compactions: int = 0
    compacted_tokens: int = 0
    # Why the engine auto-disabled speculation (degradation ladder: repeated
    # verify-path dispatch faults), or None while speculation is live.
    # Carried across reset_timing drains — disablement is engine-lifetime
    # state, not a per-window counter.
    disabled_reason: Optional[str] = None

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0

    @property
    def tokens_per_verify(self) -> float:
        if not self.verify_slot_steps:
            return 0.0
        return self.emitted / self.verify_slot_steps

    def as_timing(self) -> dict[str, float]:
        """Flatten into the engine's reset_timing dict."""
        return {
            "spec_drafted": self.drafted,
            "spec_accepted": self.accepted,
            "spec_rolled_back": self.rolled_back,
            "spec_emitted": self.emitted,
            "spec_acceptance_rate": self.acceptance_rate,
            "verify_steps": self.verify_steps,
            "verify_slot_steps": self.verify_slot_steps,
            "spec_tokens_per_verify": self.tokens_per_verify,
            "spec_gated_steps": self.gated_steps,
            "spec_tree_nodes": self.tree_nodes,
            "spec_tree_branch_nodes": self.tree_branch_nodes,
            "spec_compactions": self.compactions,
            "spec_compacted_tokens": self.compacted_tokens,
            "spec_disabled_reason": self.disabled_reason or "",
        }


@dataclass
class ConstraintStats:
    """Grammar-constrained-decoding counters (inference.constrained;
    ISSUE 16), owned by InferenceEngine and drained through
    ``reset_timing`` like the speculation stats.

    Compile side: ``compiles``/``compile_hits`` count constraint-DFA
    compilations requested at submit and the memo-cache hits among them
    (``compile_s`` is the cumulative MISS cost — hits are free by
    construction). Runtime side: ``masked_rows`` counts logits rows a
    legal-token mask was applied to (per slot per dispatch position),
    ``masked_steps`` the engine steps that carried at least one
    constrained row, ``advance_s`` the cumulative host-side FSM-advance
    time. Speculation coupling: ``forced_drafted``/``forced_accepted``
    count draft tokens emitted from single-legal-continuation FSM states
    (the free drafts — accepted/drafted should sit at ~1.0),
    ``branch_points`` tree branch-outs taken at ambiguous FSM states.
    Terminals: ``completed`` constraints satisfied to acceptance,
    ``dead_ends`` runtime walks into a state no vocab token leaves
    (typed quarantine, neighbors unaffected).
    """

    requests: int = 0
    compiles: int = 0
    compile_hits: int = 0
    compile_s: float = 0.0
    advance_s: float = 0.0
    masked_steps: int = 0
    masked_rows: int = 0
    forced_drafted: int = 0
    forced_accepted: int = 0
    branch_points: int = 0
    completed: int = 0
    dead_ends: int = 0

    @property
    def forced_acceptance_rate(self) -> float:
        if not self.forced_drafted:
            return 0.0
        return self.forced_accepted / self.forced_drafted

    def as_timing(self) -> dict[str, float]:
        """Flatten into the engine's reset_timing dict."""
        return {
            "constrain_requests": self.requests,
            "constrain_compiles": self.compiles,
            "constrain_compile_hits": self.compile_hits,
            "constrain_compile_s": self.compile_s,
            "constrain_advance_s": self.advance_s,
            "constrain_masked_steps": self.masked_steps,
            "constrain_masked_rows": self.masked_rows,
            "constrain_forced_drafted": self.forced_drafted,
            "constrain_forced_accepted": self.forced_accepted,
            "constrain_forced_acceptance_rate":
                self.forced_acceptance_rate,
            "constrain_branch_points": self.branch_points,
            "constrain_completed": self.completed,
            "constrain_dead_ends": self.dead_ends,
        }


@dataclass
class RobustnessStats:
    """Fault-tolerance counters (ISSUE 6), owned by InferenceEngine and
    drained through ``reset_timing`` like the cache/speculation stats.

    Request outcomes: ``shed`` (bounded-queue overload or drain — never
    admitted), ``expired`` (deadline passed; reaped at a step boundary),
    ``cancelled`` (cancel(rid)), ``quarantined`` (non-finite logits; the
    request errored, neighbors unaffected). Every terminal request carries
    exactly one typed outcome — there are no silent drops.

    Fault episodes: ``dispatch_faults`` counts dispatch attempts that
    raised (injected or real), ``dispatch_retries`` the XLA-fallback
    retry attempts started (``inference.dispatch_retries`` per episode),
    ``dispatch_fallbacks`` the retries that SUCCEEDED on the XLA
    reference path, ``failed_steps`` engine steps abandoned after every
    path failed (the engine continues; state untouched),
    ``stalled_steps`` steps the watchdog flagged as stalled, and
    ``pool_faults`` page-allocation failures absorbed at admit/grow.
    ``shed_context`` counts the "shed:context_too_long" subset of
    ``shed`` — requests the long-context feasibility check refused with
    a typed outcome instead of a raw raise (inference.long_context).
    """

    shed: int = 0
    shed_context: int = 0
    expired: int = 0
    cancelled: int = 0
    quarantined: int = 0
    dispatch_faults: int = 0
    dispatch_retries: int = 0
    dispatch_fallbacks: int = 0
    failed_steps: int = 0
    stalled_steps: int = 0
    pool_faults: int = 0

    def as_timing(self) -> dict[str, float]:
        """Flatten into the engine's reset_timing dict."""
        return {
            "shed_requests": self.shed,
            "shed_context_requests": self.shed_context,
            "expired_requests": self.expired,
            "cancelled_requests": self.cancelled,
            "quarantined_requests": self.quarantined,
            "dispatch_faults": self.dispatch_faults,
            "dispatch_retries": self.dispatch_retries,
            "dispatch_fallbacks": self.dispatch_fallbacks,
            "failed_steps": self.failed_steps,
            "stalled_steps": self.stalled_steps,
            "pool_faults": self.pool_faults,
        }


@dataclass
class RouterStats:
    """Multi-replica router counters (infer/router.py; ISSUE 12), the
    router-level twin of ``RobustnessStats`` — drained through
    ``Router.reset_timing`` and registered as the ``router`` section of
    the router's metrics registry.

    Placement: ``routed`` counts engine placements (including failover
    re-placements and half-open probes), split into ``affinity_routes``
    (longest radix match >= router.affinity_min_tokens pinned the
    replica) and ``cold_routes`` (no usable match — least-loaded replica
    by registry gauges). Failover: ``retries`` counts re-queues of
    in-flight requests off a dead/broken replica, ``router_shed``
    requests the ROUTER shed (retry budget exhausted, or no survivors) —
    engine-level sheds stay in the engine's own stats. Breaker:
    ``breaks`` OPEN trips (health sweep or a step() escalation),
    ``kills`` the replica_kill subset, ``probes`` OPEN->HALF_OPEN
    transitions, ``recoveries`` probes that closed the breaker. SLO
    (ISSUE 14): ``slo_breaches`` counts typed ``slo_breach`` events the
    burn-rate monitor (obs/slo.py) fired this window. Disaggregation
    (ISSUE 20): ``migrations`` counts completed prefill->decode KV-page
    handoffs, ``migrations_failed`` envelopes that faulted (gather/
    convert/scatter), ``migrations_requeued`` the subset whose request
    was re-queued on a prefill replica with a ``retried`` tag (the rest
    of the failures stayed resident on their source replica).
    """

    routed: int = 0
    affinity_routes: int = 0
    cold_routes: int = 0
    retries: int = 0
    router_shed: int = 0
    breaks: int = 0
    kills: int = 0
    probes: int = 0
    recoveries: int = 0
    slo_breaches: int = 0
    migrations: int = 0
    migrations_failed: int = 0
    migrations_requeued: int = 0

    def as_timing(self) -> dict[str, float]:
        return {
            "routed": self.routed,
            "affinity_routes": self.affinity_routes,
            "cold_routes": self.cold_routes,
            "retries": self.retries,
            "router_shed": self.router_shed,
            "breaks": self.breaks,
            "kills": self.kills,
            "probes": self.probes,
            "recoveries": self.recoveries,
            "slo_breaches": self.slo_breaches,
            "migrations": self.migrations,
            "migrations_failed": self.migrations_failed,
            "migrations_requeued": self.migrations_requeued,
        }


@dataclass
class TrainRobustnessStats:
    """Training-side fault-tolerance counters (ISSUE 8), owned by the
    Trainer — the twin of the serving engine's ``RobustnessStats``.

    ``anomalous_steps`` counts compiled-step skips by the gradient anomaly
    guard (``train.anomaly_guard``), split into ``nonfinite_steps`` (NaN/Inf
    in the loss or any grad leaf) and ``spike_steps`` (finite but the global
    grad norm exceeded ``train.anomaly_spike_factor`` x the running EMA); a
    skipped step leaves params/optimizer bit-identical to pre-step.
    ``rollbacks`` counts auto-rollback episodes (``train.anomaly_limit``
    consecutive anomalies -> restore newest intact checkpoint + skip the
    poisoned batch window), ``skipped_batches`` the data-cursor fast-forward
    those episodes applied. ``emergency_saves`` counts preemption/crash
    force-saves, ``corrupt_checkpoints`` the checkpoints restore quarantined
    with a typed reason before finding an intact one, ``restarts`` the
    supervisor attempt number this fit is running under
    (``run_with_restarts``), and ``last_fault_reason`` why the previous
    attempt died (carried into the step log).
    """

    anomalous_steps: int = 0
    nonfinite_steps: int = 0
    spike_steps: int = 0
    rollbacks: int = 0
    skipped_batches: int = 0
    emergency_saves: int = 0
    corrupt_checkpoints: int = 0
    restarts: int = 0
    last_fault_reason: Optional[str] = None

    def as_extras(self) -> dict[str, float]:
        """Flatten into MetricsLogger extras (floats only; the reason
        string rides the log line, not the JSONL row)."""
        return {
            "anomalous_steps": float(self.anomalous_steps),
            "rollbacks": float(self.rollbacks),
            "restarts": float(self.restarts),
        }

    def as_timing(self) -> dict[str, Any]:
        """The FULL counter set, for the metrics registry / Prometheus
        export (as_extras keeps its lean step-log subset)."""
        return {
            "anomalous_steps": self.anomalous_steps,
            "nonfinite_steps": self.nonfinite_steps,
            "spike_steps": self.spike_steps,
            "rollbacks": self.rollbacks,
            "skipped_batches": self.skipped_batches,
            "emergency_saves": self.emergency_saves,
            "corrupt_checkpoints": self.corrupt_checkpoints,
            "restarts": self.restarts,
            "last_fault_reason": self.last_fault_reason or "",
        }


@dataclass
class LatencyStats:
    """Streaming latency collector for the serving benches (SURVEY.md §6
    metrics): record per-event wall times (TTFT, inter-token gaps), report
    percentiles. The serving SLO quantities — p50/p99 ITL under prompt
    bursts, max decode stall — are wall-clock host-side measurements, so
    they live with the bench driver (tools/serving_latency_bench.py), not
    inside the engine; the engine exposes the counters (reset_timing) this
    class turns into a distribution summary."""

    samples: list[float] = field(default_factory=list)

    def record(self, seconds: float) -> None:
        self.samples.append(float(seconds))

    def percentile(self, p: float) -> float:
        """Nearest-rank percentile (p in [0, 100]); 0.0 when empty."""
        if not self.samples:
            return 0.0
        s = sorted(self.samples)
        rank = max(int(-(-p / 100.0 * len(s) // 1)) - 1, 0)  # ceil - 1
        return s[min(rank, len(s) - 1)]

    def summary(self) -> dict[str, float]:
        n = len(self.samples)
        return {
            "count": n,
            "mean": sum(self.samples) / n if n else 0.0,
            "p50": self.percentile(50),
            "p95": self.percentile(95),
            "p99": self.percentile(99),
            "max": max(self.samples) if n else 0.0,
        }


class MetricsLogger:
    """Accumulates per-step metrics; writes console lines and optional JSONL."""

    def __init__(
        self,
        flops_per_token: float,
        num_devices: int,
        device: jax.Device,
        jsonl_path: Optional[str] = None,
        log_interval: int = 10,
    ):
        """``device`` is one of the mesh's devices (they are homogeneous):
        its kind picks the MFU peak (``device_peaks``)."""
        self.flops_per_token = flops_per_token
        self.num_devices = max(num_devices, 1)
        peaks = device_peaks(device)
        self.peak_flops = peaks.bf16_flops if peaks is not None else None
        self.jsonl_path = jsonl_path
        self.log_interval = max(log_interval, 1)
        self.history: list[StepMetrics] = []
        self._jsonl_file = None
        if jsonl_path:
            self._jsonl_file = open(jsonl_path, "a")

    def record(
        self,
        step: int,
        loss: float,
        tokens: int,
        step_time_s: float,
        grad_norm: float = 0.0,
        learning_rate: float = 0.0,
        **extras: float,
    ) -> StepMetrics:
        tps = tokens / step_time_s if step_time_s > 0 else 0.0
        model_flops = self.flops_per_token * tokens
        achieved = model_flops / step_time_s if step_time_s > 0 else 0.0
        mfu = (
            achieved / (self.num_devices * self.peak_flops)
            if self.peak_flops else None
        )
        m = StepMetrics(
            step=step,
            loss=float(loss),
            grad_norm=float(grad_norm),
            learning_rate=float(learning_rate),
            step_time_s=step_time_s,
            tokens=tokens,
            tokens_per_sec=tps,
            tokens_per_sec_per_device=tps / self.num_devices,
            model_flops=model_flops,
            mfu=mfu,
            extras=dict(extras),
        )
        self.history.append(m)
        if self._jsonl_file is not None:
            self._jsonl_file.write(json.dumps(m.to_dict()) + "\n")
            self._jsonl_file.flush()
        if step % self.log_interval == 0 or "eval_loss" in extras:
            line = (
                f"step {step:>6d}  loss {m.loss:8.4f}  "
                f"gnorm {m.grad_norm:7.3f}  lr {m.learning_rate:.2e}  "
                f"{m.step_time_s * 1e3:7.1f} ms/step  "
                f"{m.tokens_per_sec_per_device:9.0f} tok/s/dev  "
                + (
                    f"MFU {m.mfu * 100:5.2f}%" if m.mfu is not None
                    else "MFU not measured"
                )
            )
            if "eval_loss" in extras:
                line += f"  eval {extras['eval_loss']:8.4f}"
            print(line)
        return m

    def close(self) -> None:
        if self._jsonl_file is not None:
            self._jsonl_file.close()
            self._jsonl_file = None


class CompileCounter:
    """Counts the XLA programs this process builds, from JAX's own
    monitoring events (one ``backend_compile_duration`` per new program,
    persistent-cache hits included — a hit is still a program the steady
    state should not be asking for). The trainer stamps the per-step
    delta into its metrics rows so "zero compiles after step 1" is a fact
    a reader of the JSONL can check. ``close()`` unregisters."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == self._EVENT:
            self.count += 1
            self.seconds += duration

    def take(self) -> tuple[int, float]:
        """(programs, seconds) since the last take; resets both."""
        out = (self.count, self.seconds)
        self.count, self.seconds = 0, 0.0
        return out

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


class Stopwatch:
    """Wall-clock timer for step timing (blocks on device completion)."""

    def __init__(self):
        self._t = time.perf_counter()

    def lap(self, sync_on: Any = None) -> float:
        if sync_on is not None:
            jax.block_until_ready(sync_on)
        now = time.perf_counter()
        dt = now - self._t
        self._t = now
        return dt
