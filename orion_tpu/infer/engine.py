"""Continuous-batching inference engine (host-side scheduler).

Mirrors the reference's ``inference/generate.py`` serving loop
(BASELINE.json:11; SURVEY.md §4 stack B): an admission/scheduler loop on the
host drives two jit programs — per-prompt prefill (bucketed static lengths)
and whole-batch decode (fully static shapes). Requests join mid-flight as
slots and KV pages free up; batching never changes any request's tokens
(checked by the equivalence tests in tests/test_infer.py).

ISSUE 12 split the single class into a scheduler face and an executor:
the request lifecycle + admission-queue policy live in
``infer/scheduler.py`` (Request, AdmissionQueue), the dispatch programs +
fault envelope in ``infer/executor.py`` (DispatchExecutor), and this
class composes them — byte-identical programs and streams to the
pre-split engine. ``infer/router.py`` fans requests across N of these
engines as replicas, reading the scheduler face (typed outcomes,
registry gauges, ``prefix_match_tokens``) and nothing deeper.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import logging
import math
import time
from functools import lru_cache, partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu.config import Config
from orion_tpu.infer.executor import DispatchExecutor
from orion_tpu.infer.kv_cache import (
    LIGHTNING_STATE,
    RING_K,
    RING_V,
    HostPagePool,
    PageAllocator,
    copy_page,
    gather_pages,
    host_page_bytes,
    host_tier_break_even_tokens,
    init_cache,
    pages_per_seq,
    poison_page,
    rollback_pages,
    scatter_pages,
    scrub_pages,
)
from orion_tpu.infer.runner import undecided_bounds
from orion_tpu.infer.scheduler import AdmissionQueue, Request, in_flight
from orion_tpu.infer.sampling import sample
from orion_tpu.metrics import (
    ConstraintStats,
    PrefixCacheStats,
    RobustnessStats,
    SpecDecodeStats,
)
from orion_tpu.models.moe import expert_rows
from orion_tpu.obs import (
    MetricsRegistry,
    PhaseClock,
    export_chrome_safe,
    init_obs,
    live_hbm_metrics,
)
from orion_tpu.runtime.fault import (
    DispatchFault,
    FaultInjector,
    InjectedFault,
    Watchdog,
)

log = logging.getLogger("orion_tpu.infer")


@dataclasses.dataclass
class _Burst:
    """A prefill dispatch that was launched and not waited for yet."""

    reqs: list             # the burst's requests, row by row
    args: tuple            # what was launched: the wait's ladder runs it again
    logits: jax.Array      # as launched, perhaps still running (its cache
    #                        is the engine's, and the next program's)
    picked: bool           # the first tokens are the program's greedy picks
    key: jax.Array         # the engine's key before the launch
    ends: frozenset        # rids whose budget the host knows to end at
    #                        their first token: no decode window takes them


@lru_cache(maxsize=None)
def _gather_pages_jit(n_layers: int, num_pages: int):
    """Process-wide jitted batched page gather, keyed by pool geometry:
    fleet replicas in one process (infer.Router) share the compiled
    executables instead of each engine re-compiling its own — a
    migration's scatter compile on a decode replica would otherwise land
    in that replica's serving clock."""
    return jax.jit(
        partial(gather_pages, n_layers=n_layers, num_pages=num_pages),
    )


@lru_cache(maxsize=None)
def _scatter_pages_jit(n_layers: int, num_pages: int):
    return jax.jit(
        partial(scatter_pages, n_layers=n_layers, num_pages=num_pages),
        donate_argnums=(0,),
    )


def _detect_tp_mesh(params: Any, axis: str = "tp"):
    """The params' mesh, iff they are sharded over a ``tp`` axis of size > 1.

    The engine is mesh-agnostic for the dense math (XLA partitions the
    einsums from the params' shardings alone), but the Pallas kernels are
    opaque to the SPMD partitioner and need an explicit head-sharded
    shard_map — which needs the mesh. Detecting it from the params keeps
    the public engine API unchanged: shard the params, get sharded serving.
    """
    for leaf in jax.tree.leaves(params):
        s = getattr(leaf, "sharding", None)
        if (
            isinstance(s, jax.sharding.NamedSharding)
            and s.mesh.shape.get(axis, 1) > 1
        ):
            return s.mesh
    return None


class InferenceEngine:
    """Paged-KV continuous-batching engine over a single model replica.

    Multi-chip serving shards the same programs over a mesh (the params'
    shardings decide); the scheduler below is mesh-agnostic.
    """

    def __init__(
        self,
        cfg: Config,
        params: Any,
        *,
        eos_id: Optional[int] = None,
        seed: int = 0,
        fault_injector: Optional[FaultInjector] = None,
    ):
        self.cfg = cfg
        self.mcfg = cfg.model
        self.icfg = cfg.inference
        # What a model's architecture cannot be served with yet: ONE list,
        # refused by name. Layers of different shapes (a layer plan) run
        # through the same one layer body on every path and the runner's
        # verify step agrees with its decode window on such a model
        # (tests/test_laguna.py); what is missing is a comparison of THESE
        # paths at the engine and a benchmark cell that runs them (ROADMAP
        # R6). One plan model's prompts do enter in chunks, back to back
        # inside a step (model.mixer_types, below): chunk rows BESIDE decode
        # rows in one dispatch (inference.chunked_prefill) stay refused for
        # every plan model. A power-retention model keeps a state row a slot, which
        # nothing can snapshot, share or roll back yet (ROADMAP R9): no
        # cached prefix or host tier, no resuming a prompt mid-way, no
        # drafts to reject, and its tail pages are not quantised.
        refused, why = [], []
        if self.mcfg.layer_plan is not None:
            why.append(
                "has layers of different shapes (model.layer_types / "
                "n_heads_per_layer / n_dense_layers)")
            refused += [
                ("inference.speculative", self.icfg.speculative),
                ("inference.constrained", self.icfg.constrained),
                ("inference.chunked_prefill", self.icfg.chunked_prefill),
                ("model.weight_quant", self.mcfg.weight_quant),
            ]
        # What reads K and V of heads out of pages, which a cache of
        # another kind is not served with.
        kv_only = [
            ("inference.prefix_cache", self.icfg.prefix_cache),
            ("inference.host_tier_bytes", self.icfg.host_tier_bytes),
            ("inference.long_context", self.icfg.long_context),
            ("inference.speculative", self.icfg.speculative),
            ("inference.constrained", self.icfg.constrained),
            ("inference.chunked_prefill", self.icfg.chunked_prefill),
            ("inference.kv_quant", self.icfg.kv_quant),
            ("model.weight_quant", self.mcfg.weight_quant),
        ]
        if self.mcfg.is_retention:
            why.append(
                "keeps a fixed-size state a request "
                "(model.attention=power_retention)")
            refused += kv_only
        if self.mcfg.has_latent:
            # A latent cache holds one compressed row a position, which the
            # prefix gather, the chunk rows, the host tier's paging, the
            # int8 pools and the verify kernel do not read yet.
            why.append(
                "caches one compressed row a position "
                "(model.kv_lora_rank)")
            refused += kv_only
        if self.mcfg.has_kda:
            # A KDA layer keeps a state row and a convolution's tail a
            # slot and no page: no snapshot of the rows at a page boundary
            # (a cached prefix, a chunk to resume from, a host tier to page
            # to), no rollback of a step that advanced them (drafts), no
            # int8 form of them.
            why.append(
                "keeps a state row a request and no page in its KDA layers "
                "(model.attention=kda)")
            refused += kv_only
        if self.mcfg.resumes_prefill:
            # Sparse layers read the pages a query selects through their
            # compressed keys, lightning layers keep a state row a slot. A
            # prompt DOES enter in chunks that resume from both (back to
            # back, inside one step: _prefill_bucket), which is all of
            # chunking this model has: the mixed step's chunk rows attend
            # through the dense prefix gather, which selects nothing and
            # carries no state (inference.chunked_prefill). Nothing
            # snapshots a state row at a page boundary (no cached prefix, no
            # host tier, no long-context paging), nothing rolls one back (no
            # drafts, no constrained drafts), the verify kernel walks every
            # page, and neither int8 form has been held to the reference
            # under a selection.
            why.append(
                "selects the pages its sparse layers read and keeps a state "
                "row a request in its lightning layers (model.mixer_types)")
            refused += kv_only
        if self.mcfg.has_window_ring:
            # Window layers keep a ring of their last positions a slot and
            # nothing behind it: a cached prefix's window rows are gone (no
            # prefix reuse, no host tier, no migration of pages), a prompt
            # cannot resume mid-way from rows a later chunk overwrote, a
            # rejected draft's write has already gone round, and the packed
            # key rows have no int8 form.
            why.append(
                "keeps its window layers' K and V in a ring a request "
                "(model.n_kv_heads_sliding)")
            refused += kv_only
        # Generation by diffusion over blocks: a block's denoising forwards
        # write rows beyond the cursor that only the block program may read
        # (no shared, spilled or migrated page may hold them), a prompt's
        # tail enters its first block (no chunk to resume from), the
        # sampler ranks positions inside the program (no draft to verify,
        # no mask a position), and neither int8 form has been held to the
        # reference under the block mask.
        blocks = bool(self.mcfg.block_length)
        if blocks:
            why.append(
                "generates by diffusion over blocks (model.block_length)")
            refused += kv_only
        off = list(dict.fromkeys(name for name, on in refused if on))
        if off:
            raise ValueError(
                f"model {self.mcfg.name!r} {' and '.join(why)} and is "
                f"served by whole-prompt prefill and the "
                f"{'block program' if blocks else 'decode window'} "
                f"only: unset {', '.join(off)}")
        if blocks and (
                self.icfg.max_seq_len % self.mcfg.block_length
                or self.icfg.page_size % self.mcfg.block_length):
            raise ValueError(
                f"inference.max_seq_len={self.icfg.max_seq_len} and "
                f"inference.page_size={self.icfg.page_size} must be "
                f"multiples of model.block_length="
                f"{self.mcfg.block_length}: a block never straddles a page "
                f"or the context's end")
        if self.mcfg.weight_quant == "int8":
            from orion_tpu.models.quantize import quantize_params

            params = quantize_params(params, self.mcfg)
        elif self.mcfg.weight_quant is not None:
            raise ValueError(
                f"unknown model.weight_quant={self.mcfg.weight_quant!r}"
            )
        self.params = params
        # The device the params live on (a router replica's, a mesh's
        # first) — not whatever jax.devices()[0] happens to be.
        self.device = min(
            jax.tree.leaves(params)[0].devices(), key=lambda d: d.id
        )
        self.eos_id = eos_id
        self.psz = self.icfg.page_size
        self.pages_per_seq = pages_per_seq(self.icfg)
        self.max_batch = self.icfg.max_batch_size
        if self.icfg.prefill_chunk % self.psz:
            raise ValueError(
                f"prefill_chunk={self.icfg.prefill_chunk} must be a "
                f"multiple of page_size={self.psz}"
            )
        self.chunked = self.icfg.chunked_prefill
        if self.chunked and (
            self.icfg.prefill_chunk_tokens < self.psz
            or self.icfg.prefill_chunk_tokens % self.psz
        ):
            raise ValueError(
                f"prefill_chunk_tokens={self.icfg.prefill_chunk_tokens} "
                f"must be a positive multiple of page_size={self.psz} "
                f"(chunks split at page granularity)"
            )
        # Long-context serving (inference.long_context; README "Long
        # context"): per-request KV paging to the host tier + lazy page
        # provisioning under chunked prefill. Cross-field checks live
        # here per the config lint rule (dotted overrides apply one
        # field at a time).
        self._long = self.icfg.long_context
        if self._long:
            if not self.chunked:
                raise ValueError(
                    "inference.long_context=true requires "
                    "inference.chunked_prefill=true (over-pool contexts "
                    "prefill through page-aligned chunks)"
                )
            if self.icfg.host_tier_bytes <= 0:
                raise ValueError(
                    "inference.long_context=true requires "
                    "inference.host_tier_bytes > 0 (per-request paging "
                    "needs somewhere to page to)"
                )

        self.cache = init_cache(self.mcfg, self.icfg)
        # Tensor-parallel serving on the Pallas path: the kernels run under
        # head-sharded shard_maps (see runner/ops), and the KV pool lives
        # sharded over kv heads — each device holds K/tp of every page, so
        # pool memory scales down with tp like the params do.
        from orion_tpu.ops._dispatch import resolve_impl

        self.mesh = (
            _detect_tp_mesh(self.params)
            if resolve_impl(self.mcfg.kernels)[0] else None
        )
        if self.mesh is not None and self.mcfg.has_latent:
            raise ValueError(
                "a latent-attention model is served on one device: its "
                "decode kernel is not run per shard yet")
        if self.mesh is not None and blocks:
            raise ValueError(
                f"model {self.mcfg.name!r} generates by diffusion over "
                f"blocks (model.block_length) and is served on one device: "
                f"the block program has not been run over a mesh yet")
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            tp = self.mesh.shape["tp"]
            if self.mcfg.n_heads % tp or self.mcfg.n_kv_heads % tp:
                raise ValueError(
                    f"Pallas serving with tp={tp} needs n_heads "
                    f"({self.mcfg.n_heads}) and n_kv_heads "
                    f"({self.mcfg.n_kv_heads}) divisible by it; lower tp "
                    f"or set model.kernels='xla'"
                )
            spec = {
                "k": P(None, "tp", None, None),
                "v": P(None, "tp", None, None),
                "k_scale": P(None, "tp", None),
                "v_scale": P(None, "tp", None),
            }
            self.cache = {
                name: jax.device_put(
                    arr, NamedSharding(self.mesh, spec[name])
                )
                for name, arr in self.cache.items()
            }
        self.alloc = PageAllocator(self.icfg.num_pages)
        # Automatic prefix caching (inference.prefix_cache): radix tree of
        # immutable refcounted KV pages over the SAME allocator — cached
        # pages are reclaimable headroom, evicted LRU under pressure.
        self._pcache = None
        self.prefix_stats = PrefixCacheStats()
        # Host-RAM second tier (inference.host_tier_bytes; README "Tiered
        # prefix cache"): LRU eviction demotes cached pages into host
        # buffers (one batched d2h per sweep) instead of discarding, and
        # a later match on a host-resident path restores them (one
        # batched h2d) — tail prefill then resumes exactly as a warm HBM
        # hit. Off (0): everything below stays None and the engine is
        # byte-identical to the untiered one.
        self._host_pool: Optional[HostPagePool] = None
        self._host_min_tokens: float = 0.0
        # Batched page-copy programs, shared by the host tier's spill/
        # restore envelopes AND cross-replica KV-page migration (ISSUE
        # 20) — built unconditionally so a tier-off prefill replica can
        # still export pages. gather is a pure pool read (no donation);
        # scatter donates the pool like every other cache-updating
        # program.
        self._gather_pages = _gather_pages_jit(
            self.mcfg.n_paged_layers, self.icfg.num_pages
        )
        self._scatter_pages = _scatter_pages_jit(
            self.mcfg.n_paged_layers, self.icfg.num_pages
        )
        if self.icfg.host_tier_bytes > 0:
            if not (self.icfg.prefix_cache or self._long):
                raise ValueError(
                    "inference.host_tier_bytes > 0 requires "
                    "inference.prefix_cache=true (the tier lives behind "
                    "the radix tree) or inference.long_context=true "
                    "(per-request paging owns its slots directly)"
                )
            pb = host_page_bytes(self.cache, self.mcfg.n_paged_layers)
            cap = self.icfg.host_tier_bytes // pb
            if cap < 1:
                raise ValueError(
                    f"inference.host_tier_bytes={self.icfg.host_tier_bytes}"
                    f" is smaller than one page's KV footprint ({pb} "
                    f"bytes); raise it or disable the tier with 0"
                )
            self._host_pool = HostPagePool(cap, page_bytes=pb)
            # Break-even gate: explicit knob wins; otherwise derive from
            # the measured constants (PERF.md "Host-tier break-even").
            # None from the arithmetic means restore NEVER wins — the
            # tier still absorbs evictions (a fleet-warm replica beats a
            # cold one at placement) but every local hit recomputes.
            if self.icfg.host_tier_min_tokens is not None:
                self._host_min_tokens = float(
                    self.icfg.host_tier_min_tokens
                )
            else:
                auto = host_tier_break_even_tokens(
                    pb, self.psz,
                    self.icfg.host_tier_h2d_gbps,
                    self.icfg.host_tier_restore_overhead_s,
                    self.icfg.host_tier_prefill_tok_s,
                )
                self._host_min_tokens = (
                    float(auto) if auto is not None else float("inf")
                )
        if self.icfg.prefix_cache:
            from orion_tpu.infer.prefix_cache import PrefixCache

            self._pcache = PrefixCache(
                self.psz, self.alloc,
                host_pool=self._host_pool,
                spill=(
                    self._spill_pages if self._host_pool is not None
                    else None
                ),
            )
        self._cow = jax.jit(
            partial(
                copy_page,
                n_layers=self.mcfg.n_paged_layers,
                num_pages=self.icfg.num_pages,
            ),
            donate_argnums=(0,),
        )
        self.page_table = np.zeros(
            (self.max_batch, self.pages_per_seq), np.int32
        )
        self.seq_lens = np.zeros(self.max_batch, np.int32)
        self.last_token = np.zeros(self.max_batch, np.int32)
        self.slots: list[Optional[Request]] = [None] * self.max_batch
        # Scheduler face (infer/scheduler.py): the wait queue carries the
        # admission-side policy (shed victim selection, deadline sweep).
        self.waiting: AdmissionQueue = AdmissionQueue()
        self._just_finished: list[Request] = []
        self._rid = itertools.count()
        self._admit_seq = itertools.count()
        # The PRNG stream: one key, on the device. A sampling event splits
        # it once; the decode window and a greedy prefill do so inside
        # their programs (key in, next key out), every other path eagerly.
        # The key's raw data (the same stream as the typed key's): a typed
        # key that crosses a program's boundary lowers with a custom call
        # around it, which a reader of the program's text would have to
        # tell from a kernel.
        self._key = jax.random.PRNGKey(seed)
        # The step's prefill between its launch and its wait (_Burst).
        self._burst: Optional[_Burst] = None
        self.preemptions = 0
        # Page-management window: with interleaved local/global layers
        # (model.layer_kinds) the GLOBAL layers read the whole
        # history, so pages never die and rolling/dead-on-arrival page
        # logic must treat the model as unwindowed; only the attention
        # masks are per-layer windowed (runner/cfg.layer_window).
        self.page_window = self.mcfg.page_window
        # A power-retention model's pages hold only a sequence's tail: the
        # positions since its last fold, a chunk ago at most
        # (runner.fold_step). fold_lens mirrors the cache's state_len.
        self._chunk = None
        if self.mcfg.is_retention:
            from orion_tpu.ops.retention import fold_chunk

            self._chunk = fold_chunk(self.mcfg.max_seq_len)
        self.fold_lens = np.zeros(self.max_batch, np.int64)
        if self._chunk is not None:
            if self._chunk % self.psz or self.icfg.decode_window > self.psz:
                raise ValueError(
                    f"the fold chunk of {self._chunk} positions "
                    f"(ops/retention.fold_chunk of model.max_seq_len) must "
                    f"be a multiple of inference.page_size={self.psz}, and "
                    f"a decode window no longer than a page")
            if self.mesh is not None:
                raise ValueError(
                    "a power-retention model is served on one device: its "
                    "kernels are not run per shard yet")
        # What a window-aware allocator would know (the counters
        # kv_dead_window_page_layers / kv_live_page_layers): how many
        # layers read only their window of a context whose pages all stay.
        # Over the layers that keep K and V in pages alone (decode_kv_*).
        self._layers_by_window = collections.Counter(
            k.window for k in self.mcfg.layer_kinds
            if k.attention == "softmax")
        # (a model whose window layers keep a ring holds no pool page for
        # them: none is dead.)
        self._window_layers = (
            0 if self.page_window is not None or self.mcfg.has_window_ring
            else sum(self._layers_by_window.values())
            - self._layers_by_window[None])
        # Decode window: the configured value for the engine's life. Page
        # provisioning and admission budget for _provision_window.
        self.decode_window = self.icfg.decode_window
        if blocks:
            # A dispatch advances a slot by one block: what page
            # provisioning and admission budget for, and what the engine
            # reports as its window.
            self.decode_window = self.mcfg.block_length
        # Lazy chunk provisioning (the over-pool admission path): only
        # meaningful with a sliding window — a full-attention chunk reads
        # its WHOLE history from the pool, so its device working set is
        # O(context) no matter how pages move (the typed
        # "shed:context_too_long" outcome covers that case instead).
        self._lazy = self._long and self.page_window is not None
        self.timing = self._zero_timing()
        # Cross-replica migration staging (ISSUE 20): requests whose KV
        # pages are arriving from a prefill replica but have not claimed
        # a slot yet. Page owners for assert_page_accounting.
        self._importing: dict[int, Request] = {}

        # -- Fault tolerance (runtime/fault.py; README "Robustness") -------
        self._injector = fault_injector
        self.robust = RobustnessStats()
        self.step_no = 0            # completed step() calls; FaultSpec.step
        self._consec_failed = 0     # consecutive failed steps (bounded)
        self._spec_faults = 0       # verify-path dispatch faults (lifetime)
        self._spec_disabled = False
        self._guard = self.icfg.nan_guard
        self.draining = False       # drain(): admission stopped
        # Executor face (infer/executor.py): the dispatch-program factory,
        # the lazily-built XLA fallbacks and the per-dispatch fault
        # envelope all live there; _jit_program/_run_dispatch delegate.
        self._executor = DispatchExecutor(self)
        # Quarantine primitives: poison is the NaN fault injection
        # (FaultSpec kind="nan"), scrub zeroes a quarantined request's
        # private pages before they return to the free list.
        self._poison = jax.jit(
            partial(
                poison_page,
                n_layers=self.mcfg.n_paged_layers,
                num_pages=self.icfg.num_pages,
            ),
            donate_argnums=(0,),
        )
        self._scrub = jax.jit(
            partial(
                scrub_pages,
                n_layers=self.mcfg.n_paged_layers,
                num_pages=self.icfg.num_pages,
            ),
            donate_argnums=(0,),
        )
        # Serving step watchdog: flags stalls (counted in reset_timing's
        # stalled_steps); never aborts the process — a stalled step fails
        # the step, not the engine (unlike train.watchdog_action="abort").
        self._watchdog: Optional[Watchdog] = None
        if self.icfg.watchdog_timeout_s is not None:
            self._watchdog = Watchdog(
                self.icfg.watchdog_timeout_s,
                on_stall=lambda elapsed: log.error(
                    "serving watchdog: step stalled for %.1fs", elapsed
                ),
            ).start()

        # -- Observability (orion_tpu/obs; README "Observability") ---------
        # Registry: always constructed (providers are lazy reads of live
        # state — zero hot-path cost); tracer/flight only when asked for,
        # so the untraced host path is byte-identical to the pre-obs
        # engine.
        self.registry = MetricsRegistry()
        self._register_metrics()
        self._tracer, self._flight = init_obs(
            trace=self.icfg.trace,
            trace_ring=self.icfg.trace_ring,
            flight_dir=self.icfg.flight_dir,
            trace_path=self.icfg.trace_path,
            snapshot=self.registry.snapshot,
            injector=self._injector,
        )
        self._register_trace_metrics()
        # The step's phases (obs.PhaseClock): host time into the
        # reset_timing buckets and a profiler annotation ALWAYS, the ring
        # only with inference.trace. buckets is a lambda because
        # reset_timing swaps self.timing.
        self._phase = PhaseClock(
            self._tracer, self._PHASE_KEYS,
            buckets=lambda: self.timing, tags=self._phase_tags,
        )
        self._ttft_seen: set[int] = set()   # rids with a first_token event
        self._closed = False

        # Per-slot sampling params (inference.* defaults; submit() can
        # override per request, vLLM-style).
        self.slot_temp = np.full(self.max_batch, self.icfg.temperature,
                                 np.float32)
        self.slot_top_k = np.full(self.max_batch, self.icfg.top_k, np.int32)
        self.slot_top_p = np.full(self.max_batch, self.icfg.top_p,
                                  np.float32)
        # Dispatch programs, built by the shared _jit_program factory (the
        # XLA-fallback degradation ladder rebuilds the same programs with
        # kernels="xla" on demand, so primary and fallback can never drift):
        #   decode           — the fused decode window; the "_defaults"
        #                      variant binds python-scalar sampling params
        #                      so sample()'s greedy short-circuit compiles
        #                      no sampling machinery (no [B, V] sort).
        #   prefill          — one specialization per (padded bucket length,
        #                      padded batch size) pair, keyed by jit.
        #   mixed            — unified mixed prefill+decode
        #                      (inference.chunked_prefill): ONE dispatch per
        #                      engine step while prompt chunks are in
        #                      flight.
        self._decode = self._jit_program("decode", self.mcfg, self.mesh)
        self._decode_defaults = self._jit_program(
            "decode_defaults", self.mcfg, self.mesh
        )
        self._prefill = self._jit_program("prefill", self.mcfg, self.mesh)
        if blocks:
            self._denoise = self._jit_program(
                "denoise", self.mcfg, self.mesh)
            self._denoise_defaults = self._jit_program(
                "denoise_defaults", self.mcfg, self.mesh)
        if self._chunk is not None:
            self._fold = self._jit_program("fold", self.mcfg, self.mesh)
        self._mixed = self._jit_program("mixed", self.mcfg, self.mesh)
        self._mixed_defaults = self._jit_program(
            "mixed_defaults", self.mcfg, self.mesh
        )
        # Fixed key for mixed steps with no live decode slot: those steps
        # must not advance the engine PRNG stream (sampled chunked-vs-
        # unchunked equivalence relies on one split per SAMPLING event,
        # not per dispatch).
        self._null_key = jax.random.PRNGKey(0)

        # Speculative decoding (inference.speculative): host-side n-gram
        # proposer (infer/spec_decode.py) + single-dispatch batched
        # verification (runner.verify_step / mixed_verify_step). The
        # verify width is STATIC at speculate_tokens+1 — per-request
        # adaptive draft lengths ride the `lens` argument, so there is
        # one jit specialization, not one per draft-length mix.
        self._spec = None
        self._tree = False          # token-tree drafting (spec_tree_width>1)
        self.spec_stats = SpecDecodeStats()
        # Grammar-constrained decoding (inference.constrained; ISSUE 16):
        # constrained slots decode through the VERIFY path — FSM forced
        # runs are free drafts and per-position legal masks are
        # host-precomputable along a known draft, while the fused
        # multi-token decode window cannot carry them (the next mask
        # depends on the device-side sample). So the verify programs are
        # built for `speculative OR constrained`; the draft budget is
        # speculate_tokens either way (one static verify width).
        self.constrained = self.icfg.constrained
        self.constraint_stats = ConstraintStats()
        # Forced-run bookkeeping for the CURRENT verify step: slot ->
        # number of leading draft tokens that were FSM-forced (the
        # guaranteed-accept prefix); consumed by the acceptance walks.
        self._constraint_forced: dict[int, int] = {}
        need_verify = self.icfg.speculative or self.constrained
        if need_verify and resolve_impl(self.mcfg.kernels)[0]:
            # Pallas verify path: reject a verify width the ragged
            # paged-attention kernel cannot hold in VMEM at engine
            # init — a config error naming the knob, instead of a
            # Mosaic allocation failure mid-serving.
            from orion_tpu.ops.pallas.ragged_paged_attention import (
                check_verify_fit,
            )

            # Per-SHARD head counts: under tp the kernel runs inside
            # a head-sharded shard_map with K/tp kv heads per device
            # (divisibility already validated above), so the fit is
            # per shard — whole-model counts would reject configs
            # that actually fit.
            tp = self.mesh.shape["tp"] if self.mesh is not None else 1
            check_verify_fit(
                self.icfg.speculate_tokens + 1,
                n_heads=self.mcfg.n_heads // tp,
                n_kv_heads=self.mcfg.n_kv_heads // tp,
                head_dim=self.mcfg.resolved_head_dim,
                page_size=self.psz,
                kv_quant=self.icfg.kv_quant,
                dtype_itemsize=jnp.dtype(self.mcfg.dtype).itemsize,
            )
        if (self.icfg.paged_prefill and not self.mcfg.has_latent
                and not self.mcfg.has_window_ring
                and resolve_impl(self.mcfg.kernels)[0]):
            # Same init-time VMEM gate for the paged-flash prefill
            # kernel: its blocks are page-sized (one page of queries x
            # the GQA group), so the failure mode is a too-large
            # page_size, named here instead of a Mosaic OOM mid-chunk.
            # (A latent model, and one whose window layers keep a ring,
            # prefill whole prompts: no row of theirs ever reaches that
            # kernel.)
            from orion_tpu.ops.pallas.paged_flash_prefill import (
                check_prefill_fit,
            )

            tp = self.mesh.shape["tp"] if self.mesh is not None else 1
            check_prefill_fit(
                n_heads=self.mcfg.n_heads // tp,
                n_kv_heads=self.mcfg.n_kv_heads // tp,
                head_dim=self.mcfg.resolved_head_dim,
                page_size=self.psz,
                kv_quant=self.icfg.kv_quant,
                dtype_itemsize=jnp.dtype(self.mcfg.dtype).itemsize,
            )
        if self.icfg.speculative:
            from orion_tpu.infer.spec_decode import NgramProposer

            if self.icfg.spec_min_draft_slots < 1:
                raise ValueError(
                    f"inference.spec_min_draft_slots="
                    f"{self.icfg.spec_min_draft_slots} must be >= 1"
                )
            if self.icfg.spec_tree_width > self.icfg.speculate_tokens:
                raise ValueError(
                    f"inference.spec_tree_width="
                    f"{self.icfg.spec_tree_width} exceeds "
                    f"speculate_tokens={self.icfg.speculate_tokens}: a "
                    f"tree of w branches needs at least w nodes"
                )
            if (
                self.icfg.spec_tree_width > 1
                and self.icfg.speculate_tokens + 1 > 31
            ):
                raise ValueError(
                    f"tree speculation packs the per-column ancestor mask "
                    f"into int32 words: speculate_tokens="
                    f"{self.icfg.speculate_tokens} needs "
                    f"{self.icfg.speculate_tokens + 1} columns > the "
                    f"31-bit budget; lower inference.speculate_tokens or "
                    f"set spec_tree_width=1"
                )
            self._spec = NgramProposer(
                speculate_tokens=self.icfg.speculate_tokens,
                max_n=self.icfg.spec_ngram_max,
                min_n=self.icfg.spec_ngram_min,
                tree_width=self.icfg.spec_tree_width,
            )
            # Token trees (inference.spec_tree_width > 1): the accepted
            # root-path may live at non-contiguous verify columns; this
            # program moves its KV into cursor-contiguous slots before
            # the losing branches roll back (kv_cache.compact_draft_kv).
            self._tree = self.icfg.spec_tree_width > 1
            if self._tree:
                from orion_tpu.infer.kv_cache import compact_draft_kv

                self._compact = jax.jit(
                    partial(
                        compact_draft_kv,
                        n_layers=self.mcfg.n_layers,
                        num_pages=self.icfg.num_pages,
                    ),
                    donate_argnums=(0,),
                )
        if need_verify:
            self._verify = self._jit_program("verify", self.mcfg, self.mesh)
            self._verify_defaults = self._jit_program(
                "verify_defaults", self.mcfg, self.mesh
            )
            if self.chunked:
                self._mixed_verify = self._jit_program(
                    "mixed_verify", self.mcfg, self.mesh
                )
                self._mixed_verify_defaults = self._jit_program(
                    "mixed_verify_defaults", self.mcfg, self.mesh
                )

    # -- observability (orion_tpu/obs) ------------------------------------

    def _register_metrics(self) -> None:
        """Wire the engine's live state into the metrics registry: the
        per-window counters (timing/prefix/spec/robust — the same objects
        reset_timing drains, read lazily so the registry always reports
        the CURRENT window) plus the gauges the old reset_timing surface
        never had: pool/prefix-tree occupancy and live HBM."""
        reg = self.registry
        reg.register("engine", lambda: {
            **self.timing,
            "decode_window": self.decode_window,
            "step_no": self.step_no,
            "waiting": len(self.waiting),
            "active": sum(
                1 for r in self.slots if r is not None and not r.done
            ),
            "preemptions": self.preemptions,
        })
        reg.register("robust", lambda: self.robust.as_timing())
        if self.icfg.prefix_cache:
            reg.register("prefix", lambda: self.prefix_stats.as_timing())
        if self.icfg.speculative:
            reg.register("spec", lambda: self.spec_stats.as_timing())
        if self.icfg.constrained:
            reg.register(
                "constrain", lambda: self.constraint_stats.as_timing()
            )
        reg.register("pool", self._pool_metrics)
        reg.register("hbm", partial(live_hbm_metrics, self.device))

    def _register_trace_metrics(self) -> None:
        """Ring-occupancy gauges ("trace" section: events/capacity/
        dropped), registered only when tracing is actually on — the
        obs-off snapshot (and thus the Prometheus/JSONL row set) stays
        byte-identical to the pre-obs engine. A nonzero ``dropped`` means
        any export from this ring is a truncated timeline (ISSUE 14
        satellite; obs_report flags it)."""
        if self._tracer.enabled:
            self.registry.register("trace", self._tracer.metrics)

    @staticmethod
    def _trace_ctx(req: Request) -> dict:
        """Correlation tags for a lifecycle instant: ``tid`` (the fleet
        trace id — the router's request id when routed, the engine rid on
        a bare engine) plus ``retried=attempt`` on failover re-placements
        (attempt > 0), so a failed-over request's instants on BOTH
        replicas' tracks carry the same tid and the retry is visible in
        the merged timeline."""
        tid = req.trace_id if req.trace_id is not None else req.rid
        if req.attempt:
            return {"tid": tid, "retried": req.attempt}
        return {"tid": tid}

    def _pool_metrics(self) -> dict:
        """Page-pool and radix-tree occupancy gauges. ``occupancy`` counts
        the usable pool (page 0 is the reserved scratch page); cached
        pages are reclaimable headroom but still occupied."""
        n = self.icfg.num_pages
        usable = max(n - 1, 1)
        free = self.alloc.free_pages
        out = {
            "num_pages": n,
            "free_pages": free,
            "occupancy": (usable - free) / usable,
        }
        if self._pcache is not None:
            # total_pages is the incrementally-maintained count of what
            # held_pages() would walk-and-yield: O(1), which matters now
            # that the router reads this gauge per placement candidate
            # (the walk equivalence is covered by assert_page_accounting,
            # which sums the real held_pages against the allocator).
            out["cached_pages"] = self._pcache.total_pages
            out["evictable_pages"] = self._pcache.evictable_pages()
        if self._host_pool is not None:
            # Host-tier occupancy (inference.host_tier_bytes): slots held
            # minus free over capacity; host_pages is the tree's marker
            # count (== capacity - free_slots while only the tree and
            # in-flight restores hold slots).
            hp = self._host_pool
            out["host_capacity"] = hp.capacity
            out["host_free_slots"] = hp.free_slots
            if self._pcache is not None:
                out["host_pages"] = self._pcache.host_pages
            out["host_occupancy"] = (
                (hp.capacity - hp.free_slots) / hp.capacity
            )
            if self._long:
                # Residency gauges (inference.long_context): host slots
                # held by live REQUESTS (engine-owned refs, not tree
                # markers) over the tier's capacity.
                held = sum(
                    len(r.host_pages)
                    for r in itertools.chain(self.slots, self.waiting)
                    if r is not None
                )
                out["request_host_pages"] = held
                out["residency_occupancy"] = held / hp.capacity
        return out

    # The fixed set of ``orion/<phase>`` spans and the reset_timing() keys
    # each one's SELF time feeds (obs.PhaseClock). The first key is the
    # phase's own leaf; the rest are the sums the router's ITL proxy and
    # the benchmark read (host_s, prefill_s, device_s, decode_device_s),
    # which are therefore sums of leaves by construction, never separate
    # measurements. A phase that raises books nothing: a failed dispatch's
    # time stays with its parent and ends in host_s. The fallback phases
    # mark an XLA retry inside ``<path>/run`` and book nothing of their own.
    # The launch and wait phases are the executor's (ISSUE 56), entered once
    # each a dispatch inside the caller's ``<path>/run``: a phase books SELF
    # time, so each feeds its own leaf and then every key its run parent
    # feeds, and the parent's keys read what they read without the children.
    _PHASE_KEYS = {
        "step": ("step_self_s", "host_s"),
        "reap": ("reap_s", "host_s"),
        "admit": ("admit_s", "host_s"),
        "prefill/build": ("prefill_build_s", "host_s"),
        "prefill/run": ("prefill_run_s", "prefill_s"),
        "prefill/sample": ("prefill_sample_s", "prefill_s"),
        "decode/build": ("decode_build_s", "host_s"),
        "decode/run": ("decode_run_s", "decode_device_s", "device_s"),
        "decode/fetch": ("decode_fetch_s", "decode_device_s", "device_s"),
        "decode/emit": ("emit_s", "host_s"),
        "verify/run": ("verify_run_s", "decode_device_s", "device_s"),
        "compact": ("compact_s", "decode_device_s", "device_s"),
        "fold/run": ("fold_s", "decode_device_s", "device_s"),
        "mixed/run": ("mixed_device_s", "device_s"),
        "mixed_verify/run": ("mixed_device_s", "device_s"),
        "spill": ("spill_s",),
        "restore": ("restore_s",),
        "page_in": ("page_in_s",),
        "migrate_out": ("migrate_out_s",),
        "migrate_in": ("migrate_in_s",),
        "prefill/launch": ("prefill_launch_s", "prefill_run_s", "prefill_s"),
        "prefill/wait": ("prefill_wait_s", "prefill_run_s", "prefill_s"),
        "decode/launch": ("decode_launch_s", "decode_run_s",
                          "decode_device_s", "device_s"),
        "decode/wait": ("decode_wait_s", "decode_run_s",
                        "decode_device_s", "device_s"),
        "verify/launch": ("verify_launch_s", "verify_run_s",
                          "decode_device_s", "device_s"),
        "verify/wait": ("verify_wait_s", "verify_run_s",
                        "decode_device_s", "device_s"),
        "fold/launch": ("fold_launch_s", "fold_s",
                        "decode_device_s", "device_s"),
        "mixed/launch": ("mixed_launch_s", "mixed_device_s", "device_s"),
        "mixed/wait": ("mixed_wait_s", "mixed_device_s", "device_s"),
        "mixed_verify/launch": ("mixed_launch_s", "mixed_device_s",
                                "device_s"),
        "mixed_verify/wait": ("mixed_wait_s", "mixed_device_s", "device_s"),
        "prefill/fallback": (),
        "fold/fallback": (),
        "decode/fallback": (),
        "verify/fallback": (),
        "mixed/fallback": (),
        "mixed_verify/fallback": (),
    }

    def _phase_tags(self) -> dict:
        """Ring tags of a phase span (built only with the tracer on): the
        step that caused it and the trace ids of every live slot (ISSUE
        14), so a request's correlated track in the merged timeline
        includes the work that advanced it, not just its lifecycle
        instants."""
        return {
            "step": self.step_no,
            "tids": [
                r.trace_id if r.trace_id is not None else r.rid
                for r in self.slots if r is not None and not r.done
            ],
        }

    def _flight_dump(self, reason: str, **context) -> None:
        """Write a flight-recorder postmortem (no-op without
        inference.flight_dir); best-effort — a failed dump degrades the
        artifact, never the engine (FlightRecorder.try_dump)."""
        if self._flight is not None:
            self._flight.try_dump(reason, step=self.step_no, **context)

    def _flight_note(self, kind: str, **fields) -> None:
        """Stamp one event into the postmortem ring (no-op without
        inference.flight_dir) — the single guard every fault path shares."""
        if self._flight is not None:
            self._flight.note(kind, step=self.step_no, **fields)

    def export_trace(self, path: str) -> int:
        """Export the span ring as Chrome trace-event JSON (Perfetto);
        returns the number of events written (0 when tracing is off)."""
        return self._tracer.export_chrome(path)

    @property
    def tracer(self):
        """The engine's span tracer (NULL_TRACER when obs is off) — the
        router reads it to merge this replica's ring into the fleet
        timeline (obs.merge_chrome)."""
        return self._tracer

    # -- dispatch + degradation ladder (infer/executor.py) ----------------

    def _jit_program(self, name: str, mcfg, mesh):
        """Delegate to the executor's program factory (the one factory
        both primary and XLA-fallback builds share)."""
        return self._executor.jit_program(name, mcfg, mesh)

    def _fallback_program(self, name: str):
        return self._executor.fallback_program(name)

    def _run_dispatch(self, path: str, name: str, *args, **kwargs):
        """Run one device dispatch under the executor's fault-tolerance
        envelope (injection points, XLA-fallback retry ladder with
        ``inference.dispatch_retries`` jittered-backoff attempts); raises
        DispatchFault when every path is exhausted — the engine fails the
        step, not the process. Launch and wait, back to back: only the
        plain step queues anything between the two (step)."""
        out = self._executor.run(path, name, *args, **kwargs)
        return self._executor.wait(path, name, out, *args, **kwargs)

    def _note_spec_fault(self, e: Exception) -> None:
        """Degradation ladder rung 2: count a verify-path PRIMARY dispatch
        fault (whether or not the XLA fallback then absorbed it); past
        inference.spec_fault_limit, speculation auto-disables for the
        engine's lifetime (SpecDecodeStats.disabled_reason) and decoding
        continues on the plain window."""
        self._spec_faults += 1
        log.warning(
            "speculative verify dispatch fault %d/%d: %s",
            self._spec_faults, self.icfg.spec_fault_limit, e,
        )
        if (
            self._spec_faults >= self.icfg.spec_fault_limit
            and not self._spec_disabled
        ):
            self._spec_disabled = True
            self.spec_stats.disabled_reason = (
                f"auto-disabled after {self._spec_faults} verify "
                f"dispatch faults"
            )
            log.error(
                "speculative decoding %s", self.spec_stats.disabled_reason
            )
            self._flight_dump(
                "spec_auto_disable", spec_faults=self._spec_faults
            )

    def _maybe_inject_nan(self) -> None:
        """FaultSpec kind="nan": poison the victim's newest attended
        PRIVATE page with NaN. The poison flows through the real attention
        into exactly that slot's logits (no other slot reads its pages);
        the nan_guard quarantine is then exercised end-to-end."""
        inj = self._injector
        if inj is None:
            return
        spec = inj.take("nan", self.step_no)
        if spec is None:
            return
        cands = [
            r for r in self.slots
            if r is not None and not r.done
            and (spec.rid is None or r.rid == spec.rid)
        ]
        if not cands:
            log.warning("nan injection at step %d found no victim",
                        self.step_no)
            return
        req = min(cands, key=lambda r: r.admit_seq)
        # Walk back from the cursor's page: the newest written position is
        # always attended, and shared (refcount > 1) prefix pages must stay
        # clean — they are other requests' data.
        pos = max(int(self.seq_lens[req.slot]) - 1, 0)
        for i in range(min(pos // self.psz, len(req.pages) - 1), -1, -1):
            p = req.pages[i]
            if p is not None and self.alloc.refcount(p) == 1:
                log.warning(
                    "injecting NaN into page %d of request %d (step %d)",
                    p, req.rid, self.step_no,
                )
                self.cache = self._poison(self.cache, jnp.int32(p))
                return
        log.warning("nan injection: request %d has no private page",
                    req.rid)

    def _quarantine(self, req: Request, reason: str) -> None:
        """Contain a poisoned slot: the request errors with a typed
        outcome, its private pages are SCRUBBED (stale NaNs must not leak
        to the page's next tenant) and released with NO prefix-cache
        donation; neighbors never read its pages, so their outputs stay
        byte-identical to a fault-free run."""
        log.error("quarantining request %d (%s)", req.rid, reason)
        priv = [
            p for p in req.pages
            if p is not None and self.alloc.refcount(p) == 1
        ]
        if priv:
            pad = priv + [0] * (self.pages_per_seq - len(priv))
            self.cache = self._scrub(
                self.cache, jnp.asarray(pad, jnp.int32)
            )
        req.done = True
        req.outcome = f"error:{reason}"
        self.robust.quarantined += 1
        self._teardown_slot(req, 0)   # n_cached=0: donate nothing
        self._just_finished.append(req)
        self._flight_dump(f"{reason}_quarantine", rid=req.rid)

    def _reap_expired(self) -> None:
        """Step-boundary deadline sweep: expired requests — waiting or
        active, mid-prefill or mid-decode — terminate with outcome
        "expired"; active ones release pages with prefix-cache donation
        exactly as preemption does (the _reap path)."""
        now = time.monotonic()
        for r in self.waiting.sweep_expired(now):
            r.done = True
            r.outcome = "expired"
            self.robust.expired += 1
            self._drop_host_pages(r)
            self._just_finished.append(r)
        for r in self.slots:
            if (
                r is not None and not r.done
                and r.deadline is not None and now >= r.deadline
            ):
                log.info("request %d deadline expired (slot %d)",
                         r.rid, r.slot)
                r.done = True
                r.outcome = "expired"
                self.robust.expired += 1

    # -- public API --------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: Optional[int] = None,
        *,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        deadline_s: Optional[float] = None,
        priority: int = 0,
        constraint: Optional[Any] = None,
    ) -> int:
        """Queue a request; returns its id.

        ``deadline_s`` (seconds from now; default
        inference.default_deadline_s) bounds the request's life: once past
        it, the request is reaped at the next step boundary with outcome
        "expired". ``priority`` (higher = more important) orders admission,
        page-pressure preemption (low classes evict first) and overload
        shedding. With inference.queue_limit set, an over-limit submit
        SHEDS the lowest-priority / nearest-deadline / newest candidate —
        possibly this very request — with outcome "shed" instead of
        queueing unboundedly; the shed request still surfaces from the
        next step().

        ``constraint`` (a ``orion_tpu.constrain.ConstraintSpec``) asks
        for grammar-constrained output: the emission is guaranteed to
        match the spec's regex / JSON schema token-for-token. Needs
        ``inference.constrained=true`` (the flag builds the verify
        programs constrained slots decode through); the spec compiles at
        submit (memoized across requests by constraint hash) and a
        pattern this vocab can never satisfy raises here, typed.

        Note: any non-None sampling override switches the WHOLE decode batch
        to the sort-based sampling program (a [B, V] sort per token for every
        co-scheduled slot, plus a one-time second decode compile) until no
        overriding request remains active — overrides cost throughput for the
        batch, not just this request. Greedy-default traffic stays on the
        sort-free specialized program.
        """
        return self.submit_request(
            prompt, max_new_tokens, temperature=temperature, top_k=top_k,
            top_p=top_p, deadline_s=deadline_s, priority=priority,
            constraint=constraint,
        ).rid

    def submit_request(
        self,
        prompt: Sequence[int],
        max_new_tokens: Optional[int] = None,
        *,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        deadline_s: Optional[float] = None,
        priority: int = 0,
        trace_id: Optional[int] = None,
        attempt: int = 0,
        constraint: Optional[Any] = None,
    ) -> Request:
        """submit() returning the live Request object instead of its id —
        the CLI/bench/driver surface: callers poll ``.generated`` for
        incremental tokens and read the typed ``.outcome`` at the end.
        Same arguments and validation as submit(). ``trace_id`` /
        ``attempt`` are the fleet trace context (ISSUE 14): the router
        stamps its request id and failover attempt number here so this
        replica's lifecycle instants correlate in the merged timeline;
        bare-engine callers leave them defaulted (tid falls back to the
        engine rid)."""
        if not len(prompt):
            raise ValueError("empty prompt")
        if temperature is not None and temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k is not None and not 0 <= top_k <= self.mcfg.vocab_size:
            raise ValueError(
                f"top_k must be in [0, vocab_size={self.mcfg.vocab_size}], "
                f"got {top_k} (0 disables the top-k filter)"
            )
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        cstate = None
        if constraint is not None:
            # Cross-field check lives here per the config lint rule
            # (dotted overrides apply one field at a time).
            if not self.constrained:
                raise ValueError(
                    "constraint= needs inference.constrained=true (the "
                    "flag builds the verify programs constrained slots "
                    "decode through)"
                )
            from orion_tpu.constrain import (
                ConstraintSpec,
                ConstraintState,
                compile_constraint,
            )

            if not isinstance(constraint, ConstraintSpec):
                raise ValueError(
                    f"constraint must be a ConstraintSpec, got "
                    f"{type(constraint).__name__}"
                )
            t0 = time.perf_counter()
            dfa, hit = compile_constraint(
                constraint, self.mcfg.vocab_size,
                max_states=self.icfg.constraint_max_states,
                cache_size=self.icfg.constraint_cache,
            )
            cs = self.constraint_stats
            cs.requests += 1
            cs.compiles += 1
            if hit:
                cs.compile_hits += 1
            else:
                cs.compile_s += time.perf_counter() - t0
            cstate = ConstraintState(dfa, self.eos_id)
        # Normalize overrides equal to the engine defaults back to None: a
        # request that explicitly passes the default values is sampling-
        # identical to one passing nothing, and must not push the batch onto
        # the sort-based decode program.
        if temperature is not None and temperature == self.icfg.temperature:
            temperature = None
        if top_k is not None and top_k == self.icfg.top_k:
            top_k = None
        if top_p is not None and top_p == self.icfg.top_p:
            top_p = None
        limit = self.icfg.max_seq_len
        if len(prompt) >= limit:
            raise ValueError(f"prompt length {len(prompt)} >= max_seq_len {limit}")
        max_new = (
            max_new_tokens
            if max_new_tokens is not None
            else self.icfg.max_new_tokens
        )
        # The pool must be able to hold this request ALONE at its largest
        # footprint (preemption can always shrink the batch to one, and a
        # grown request re-prefills at its context's bucket length) plus one
        # spare page — this makes mid-decode pool exhaustion unreachable for
        # admitted requests. The footprint includes the decode window's
        # pre-provisioned pages: the device may write up to W-1 positions
        # past the host's final accepted token (see runner.decode_window).
        max_context = min(len(prompt) + max(max_new, 0), limit)
        # Worst admission demand over every context the request could
        # (re-)prefill at — with a sliding window the peak sits at a
        # prefill-bucket bottom, not at max_context (see
        # _worst_admission_need).
        needed = self._worst_admission_need(len(prompt), max_context)
        usable = self.icfg.num_pages - 1
        shed_kind = None
        if needed > usable:
            if self._lazy and self._long_admission_need() <= usable:
                # Over-pool long context (inference.long_context + SWA +
                # chunked prefill): the LAZY working set fits — pages
                # materialize per chunk and die behind the window, so the
                # pool never holds the O(context) footprint at once.
                pass
            elif self._long:
                # Long-context mode refuses infeasible work with a TYPED
                # outcome instead of a raw raise: the caller/router sees
                # "shed:context_too_long" surface from step() exactly
                # like an overload shed (RobustnessStats.shed_context).
                shed_kind = "context_too_long"
            else:
                raise ValueError(
                    f"request needs up to {needed} KV pages but the pool "
                    f"only has {usable}; raise inference.num_pages or "
                    f"lower max_new_tokens"
                )
        if deadline_s is None:
            deadline_s = self.icfg.default_deadline_s
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        req = Request(
            rid=next(self._rid),
            prompt=list(map(int, prompt)),
            max_new_tokens=max_new,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            priority=int(priority),
            deadline=(
                time.monotonic() + deadline_s
                if deadline_s is not None else None
            ),
            trace_id=trace_id,
            attempt=int(attempt),
            constraint=cstate,
        )
        if self._tracer.enabled:
            self._tracer.instant(
                "submit", rid=req.rid, priority=req.priority,
                prompt_tokens=len(req.prompt),
                max_new_tokens=req.max_new_tokens,
                deadline_s=deadline_s, **self._trace_ctx(req),
            )
        if shed_kind is not None:
            self._shed(
                req,
                f"context needs up to {needed} KV pages, pool has "
                f"{usable} and the lazy working set does not fit",
                kind=shed_kind,
            )
            return req
        if self.draining:
            # Admission is stopped (SIGTERM drain): typed shed, never
            # queued — the caller still sees the request surface.
            self._shed(req, "draining")
            return req
        qlim = self.icfg.queue_limit
        if qlim is not None and len(self.waiting) >= qlim:
            # Overload: shed the least defensible candidate — lowest
            # priority first, then the nearest (most infeasible) deadline,
            # then the newest arrival — which may be the incoming request.
            # In-flight requests (admitted once, or carrying generated
            # tokens — see _in_flight) are never victims: "shed" means
            # never admitted (RobustnessStats contract).
            victim = self.waiting.shed_victim(req)
            self._shed(victim, f"queue full ({qlim})")
            if victim is not req:
                self.waiting.remove(victim)
                self.waiting.append(req)
            return req
        self.waiting.append(req)
        return req

    # In-flight test (scheduler face): admitted at least once, or carrying
    # generated tokens — exempt from overload shedding, finished (not
    # shed) by drain(). See infer/scheduler.py.
    _in_flight = staticmethod(in_flight)

    def _shed(
        self, req: Request, why: str, kind: Optional[str] = None
    ) -> None:
        log.warning("shedding request %d (priority %d): %s",
                    req.rid, req.priority, why)
        req.done = True
        req.outcome = "shed" if kind is None else f"shed:{kind}"
        self.robust.shed += 1
        if kind == "context_too_long":
            self.robust.shed_context += 1
        self._drop_host_pages(req)
        self._just_finished.append(req)

    def cancel(self, rid: int) -> bool:
        """Cancel a request by id; returns False when it is unknown or
        already terminal. A waiting request terminates immediately; an
        active one is reaped at the next step boundary — pages released,
        full pages donated to the prefix cache, any speculative
        provisioning rolled back — exactly like a finished request."""
        for i, r in enumerate(self.waiting):
            if r.rid == rid:
                del self.waiting[i]
                r.done = True
                r.outcome = "cancelled"
                self.robust.cancelled += 1
                self._drop_host_pages(r)
                self._just_finished.append(r)
                return True
        for r in self.slots:
            if r is not None and r.rid == rid and not r.done:
                r.done = True
                r.outcome = "cancelled"
                self.robust.cancelled += 1
                return True
        return False

    def step(self) -> list[Request]:
        """Admit + prefill new requests, then run one decode WINDOW
        (``self.decode_window`` fused token steps, one host round-trip)
        for all active slots; returns the requests that finished.

        A plain step (no chunked, mixed, speculative or verify dispatch)
        queues its programs on the device before it waits for any: the
        prefill is LAUNCHED (``prefill/run``: uploads and launch; a greedy
        burst's first tokens are picked inside the program and scattered
        into the step's ``last_token`` there), the decode window is built
        while it runs (``decode/build``; a retention model's folds are
        launched without a wait) and LAUNCHED behind it on the prefill's
        own ``last_token`` and key (``decode/run``), then the host WAITS
        for the prefill (``prefill/run`` again), fetches the picks and
        emits the first tokens (``prefill/sample``), and WAITS for the
        window (``decode/run`` again) and fetches its ``[W, B]`` tokens
        (``decode/fetch``). The step keeps the older order, each program
        waited for before the next is built, where it can see that it has
        to: a burst that samples or is constrained (the host picks from
        the logits), more than one prefill dispatch a step, or a window
        whose pages the free list cannot provision (reclaiming might
        re-queue a request whose first token is not on the host yet).

        The step is one ``orion/step`` phase whose children (reap, admit,
        prefill/*, decode/*, ...) book their host time into ``timing``
        (see reset_timing and _PHASE_KEYS): the split between waiting on
        the device, prefill, and each part of the host's own work — the
        observability needed to tune the decode window, and the serve
        path, from data rather than assertion.
        """
        with self._phase("step") as span:
            idle0 = self._executor.unqueued_until(span.t0)
            if self._watchdog is not None and self._watchdog.armed:
                # Refresh at step START so idle gaps between caller-driven
                # steps never read as stalls — only time INSIDE a step
                # does. Arming stays with the step-END heartbeat
                # (Watchdog's first-completed-step contract): the first
                # step's unbounded jit compile must not trip a false stall.
                self._watchdog.heartbeat()
            with self._phase("reap"):
                self._reap_expired()
                # Reap expired/cancelled slots BEFORE admission so their
                # pages are already donated/free when this step's
                # admission pass budgets.
                self._reap()
            try:
                if self.waiting:
                    with self._phase("admit"):
                        self._admit()
                self._maybe_inject_nan()
                mixed = self.chunked and any(
                    r is not None and r.prefill_pending and not r.done
                    for r in self.slots
                )
                decoded = (
                    self._mixed_decode() if mixed else self._decode_all()
                )
                self._consec_failed = 0
            except (DispatchFault, MemoryError) as e:
                # Every dispatch path failed (or the page allocator did,
                # at grow time): the step is abandoned with engine state
                # consistent — injected dispatch faults fire before the
                # device call, prefill faults unwind their admissions,
                # grow faults leave pages owned — so fail the step, not
                # the process. A persistent fault is not transient:
                # re-raise after max_step_faults consecutive losses.
                if isinstance(e, MemoryError):
                    self.robust.pool_faults += 1
                if self._burst is not None:
                    # Between a prefill's launch and its wait (the window's
                    # provisioning or launch failed): the prefill itself
                    # ran, so its first tokens are kept.
                    try:
                        self._finish_prefill()
                    except DispatchFault as e2:
                        log.error("the launched prefill failed too: %s", e2)
                self.robust.failed_steps += 1
                self._consec_failed += 1
                log.error(
                    "engine step %d failed (%s); continuing (%d/%d "
                    "consecutive)",
                    self.step_no, e, self._consec_failed,
                    self.icfg.max_step_faults,
                )
                self._flight_note(
                    "failed_step", consecutive=self._consec_failed,
                    error=f"{type(e).__name__}: {e}",
                )
                if self._consec_failed >= self.icfg.max_step_faults:
                    self._flight_dump(
                        "max_step_faults",
                        consecutive=self._consec_failed, error=str(e),
                    )
                    raise
                decoded = False
            self.timing["steps"] += 1
            if decoded:
                self.timing["windows"] += 1
            if self.mcfg.debug_asserts:
                from orion_tpu.runtime.asserts import raise_if_failed

                # The token fetch synced the device work, but not the
                # async callback thread — the barrier orders it before
                # the check.
                jax.effects_barrier()
                raise_if_failed()
            if self._watchdog is not None:
                if self._watchdog.stalled:
                    # The watchdog fired DURING this step (a wedged/slow
                    # dispatch): the step is marked stalled and counted;
                    # the process carries on, deadline expiry handles the
                    # SLO consequences at the next boundary.
                    self.robust.stalled_steps += 1
                    self._flight_dump(
                        "watchdog_stall",
                        step_wall_s=time.monotonic() - span.t0,
                    )
                self._watchdog.heartbeat()
            if span.tags is not None:
                # Request-lifecycle instants, swept at the step boundary
                # where every token-emitting path has already run:
                # first_token fires once per request (TTFT), outcome
                # exactly once at the end. The wait queue is in the sweep
                # too: a request preempted in the very step that produced
                # its first token sits there, and skipping it would stamp
                # its TTFT steps late.
                for r in itertools.chain(
                    self.slots, self.waiting, self._just_finished
                ):
                    if (
                        r is not None and r.generated
                        and r.rid not in self._ttft_seen
                    ):
                        self._ttft_seen.add(r.rid)
                        self._tracer.instant(
                            "first_token", rid=r.rid, step=self.step_no,
                            **self._trace_ctx(r),
                        )
                for r in self._just_finished:
                    self._ttft_seen.discard(r.rid)
                    self._tracer.instant(
                        "outcome", rid=r.rid, outcome=r.outcome,
                        tokens=len(r.generated), step=self.step_no,
                        **self._trace_ctx(r),
                    )
                span.tags["decoded"] = bool(decoded)
        self.timing["unqueued_in_step_s"] += (
            self._executor.unqueued_until(span.t1) - idle0)
        self.step_no += 1
        done, self._just_finished = self._just_finished, []
        return done

    @staticmethod
    def _zero_timing() -> dict:
        return {
            "device_s": 0.0, "host_s": 0.0, "prefill_s": 0.0,
            # The leaves of a step (one per bucketed phase of
            # _PHASE_KEYS; every other *_s key here is a sum of leaves):
            #   host_s    == reap_s + admit_s + prefill_build_s
            #                + decode_build_s + emit_s + step_self_s
            #   prefill_s == prefill_run_s + prefill_sample_s
            #   decode_device_s == decode_run_s + decode_fetch_s
            #                      + verify_run_s + compact_s
            # admit_s is _admit's SELF time (its prefill phases nest in
            # it), step_self_s the part of a step no child covers.
            "reap_s": 0.0, "admit_s": 0.0, "prefill_build_s": 0.0,
            "prefill_run_s": 0.0, "prefill_sample_s": 0.0,
            "decode_build_s": 0.0, "decode_run_s": 0.0,
            "decode_fetch_s": 0.0, "emit_s": 0.0, "step_self_s": 0.0,
            "verify_run_s": 0.0, "compact_s": 0.0,
            # The dispatch seam (executor.run / wait, ISSUE 56). Inside each
            # ``<path>/run`` the program's call and the wait for it are
            # leaves of their own, and feed their parent's keys too:
            #   prefill_run_s == its uploads + prefill_launch_s
            #                    + prefill_wait_s
            # and so for decode, verify, fold (fold_s: launched, never
            # waited for) and mixed (mixed_device_s, which also holds the
            # fetch; mixed_verify books under the same two leaves).
            "prefill_launch_s": 0.0, "prefill_wait_s": 0.0,
            "decode_launch_s": 0.0, "decode_wait_s": 0.0,
            "verify_launch_s": 0.0, "verify_wait_s": 0.0,
            "fold_launch_s": 0.0,
            "mixed_launch_s": 0.0, "mixed_wait_s": 0.0,
            # Launches, and the launches a wait covered (its own and every
            # earlier one: the device runs them in order, and a fold's wait
            # is the window's), so launches - waits over a window is what it
            # left in flight. unqueued_s: host seconds with NOTHING queued on
            # the device, from the return of the wait that covered the
            # newest launch to the start of the next launch (booked when it
            # ends, to the window open then); unqueued_in_step_s the part of
            # it inside ``orion/step`` (the rest is the caller's), booked by
            # step() at its end from the executor's reading at its two
            # edges, so an interval still open there is in it already;
            # unqueued_max_s the longest single interval: a stall, by its
            # size. Always on, traced or not.
            "launches": 0, "waits": 0, "unqueued_s": 0.0,
            "unqueued_in_step_s": 0.0, "unqueued_max_s": 0.0,
            # A power-retention model (all 0 for a K/V model): fold_s the
            # fold dispatches at the start of a decode window (``folds`` of
            # them: one a slot whose tail holds a complete chunk); per token
            # step and layer, the live slots' state rows the decode kernel
            # read (decode_state_slot_layers), those of them that hold
            # nothing yet (no fold so far: decode_state_empty_slot_layers)
            # and the tail positions it read, the new token among them
            # (decode_tail_token_layers); prefill_retention_units counts a
            # real prompt position of index t as min(2 (t + 1), D) a layer,
            # D = H (H + 1) / 2: the cheaper of the quadratic and the
            # recurrent form for its query, in units of 2 x H x query heads
            # operations. Host arithmetic on lengths, no device value read.
            "fold_s": 0.0, "folds": 0,
            "decode_state_slot_layers": 0,
            "decode_state_empty_slot_layers": 0,
            "decode_tail_token_layers": 0,
            "prefill_retention_units": 0,
            # A latent-attention model (all 0 for a K/V model, whose
            # decode_kv_* are 0 for this one): per token step, the live
            # slots' cached positions summed over the layers, each one
            # latent row the decode kernel reads
            # (decode_latent_token_layers); S (S + 1) / 2 a real prompt and
            # layer, the query-key pairs the expanded causal attention of a
            # prefill computes (prefill_attn_pairs); and, summed at each
            # decode window, the pool bytes the live slots' pages hold over
            # all layers and their cached tokens (latent_live_page_bytes /
            # latent_live_tokens: bytes a token and layer is their ratio
            # over the layers). Host arithmetic on lengths.
            "decode_latent_token_layers": 0, "prefill_attn_pairs": 0,
            "latent_live_page_bytes": 0, "latent_live_tokens": 0,
            # A model with KDA layers (all 0 for any other; its latent
            # counters above are over its latent layers alone): per token
            # step, the live slots x the KDA layers, each one state row the
            # decode kernel reads and writes (decode_kda_slot_layers); the
            # real prompt positions x the KDA layers a prefill's chunked
            # form computes (prefill_kda_token_layers); and, summed at each
            # decode window, the bytes of the live slots' state and
            # convolution rows (kda_live_state_bytes; over
            # latent_live_tokens with latent_live_page_bytes: what a cached
            # token costs). Host arithmetic on lengths.
            "decode_kda_slot_layers": 0, "prefill_kda_token_layers": 0,
            "kda_live_state_bytes": 0,
            # A model of sparse and lightning layers (all 0 for any other).
            # Per token step, summed over the live slots, the sparse layers
            # and their K/V heads: the keys the selected pages hold at or
            # before the new position, which is what the decode kernel
            # attends and reads (decode_sparse_visible_keys), and the keys
            # in context (decode_sparse_context_keys: the first over it is
            # the share a query sees). Over a prefill's real positions, the
            # sparse layers and the QUERY heads: the (head, key) pairs the
            # selections leave visible (prefill_sparse_visible_pairs); over
            # them, the sparse layers and the K/V heads, the pages a query
            # lists (prefill_sparse_selected_pages) and those of them that
            # every query of its block lists, which the prefill kernel walks
            # once a block: the forced pages, and every page while there is
            # nothing to choose (prefill_sparse_shared_pages). The
            # live slots x the lightning layers a token step, each one state
            # row read and written (decode_lightning_slot_layers), and the
            # real prompt positions x the lightning layers the chunked form
            # computes (prefill_lightning_token_layers). Summed at each
            # decode window: the bytes of the pages the live slots hold (K,
            # V and compressed keys: sala_live_page_bytes), of their state
            # rows (lightning_live_state_bytes) and their cached positions
            # (sala_live_tokens). Host arithmetic on lengths: a selection
            # takes min(causal blocks, topk) pages whatever it picks.
            "decode_sparse_visible_keys": 0, "decode_sparse_context_keys": 0,
            "prefill_sparse_visible_pairs": 0,
            "prefill_sparse_selected_pages": 0,
            "prefill_sparse_shared_pages": 0,
            "decode_lightning_slot_layers": 0,
            "prefill_lightning_token_layers": 0,
            "sala_live_page_bytes": 0, "lightning_live_state_bytes": 0,
            "sala_live_tokens": 0,
            # Prefill sizing: prefill_tokens counts the real prompt
            # positions the prefill dispatches computed (prefix-cached
            # positions excluded), prefill_pad_tokens the rest of each
            # dispatched [rows -> power of two] x [largest bucket] block.
            "prefill_dispatches": 0, "prefill_tokens": 0,
            "prefill_pad_tokens": 0,
            # Of the prefill dispatches, those whose first tokens were the
            # program's own greedy picks (no sampler program, no logits
            # read on the host); of the steps, those whose decode window
            # was launched before its prefill's picks were fetched.
            "prefill_picks_in_program": 0, "chained_steps": 0,
            # Expert-matmul rows per MoE layer those dispatches computed
            # (models/moe.expert_rows: k per routed position on the
            # dropless grouped path, E per dispatched position on the
            # capacity buckets); 0 for a dense model.
            "prefill_expert_rows": 0,
            # Of those rows, summed over the sparse layers, the ones on
            # experts held here, counted by the prefill program itself
            # (runner.HELD_ROWS); 0 unless model.router_width says the
            # device holds a share.
            "prefill_held_expert_rows": 0,
            # Of those layers' dispatches, the ones whose held rows passed
            # the bound of one pass (models/moe.held_row_bound) and took a
            # further pass; counted by the same program, and 0 wherever the
            # dispatch bounds nothing (moe.bounds_held_rows).
            "prefill_held_bound_overflows": 0,
            # What the paged decode kernel had to read: over every token
            # step of every decode window, the live slots' context
            # lengths (bounded by the sliding window where there is one).
            "decode_kv_tokens": 0,
            # The same summed over the layers, each by its own kind: a
            # full layer reads a slot's length, a window layer its window
            # at most.
            "decode_kv_token_layers": 0,
            # What the kernel's block walk copies for them: whole pages,
            # from the page of a window's first position to the page that
            # takes the new token. x page_size over decode_kv_token_layers
            # is the page rounding no walk of whole pages avoids.
            "decode_kv_pages_read": 0,
            # At each decode window, over the live slots: pages x layers
            # the pool holds for them, and of those the ones lying wholly
            # behind a window layer's window (a model that mixes window
            # and full layers keeps every page for every layer).
            "kv_live_page_layers": 0, "kv_dead_window_page_layers": 0,
            # A model whose window layers keep a ring a slot beside the
            # full layers' pages (model.n_kv_heads_sliding), at each decode
            # window over the live slots: the positions the full layers
            # hold for them and those positions' bytes there, and the bytes
            # of the pages the pool holds for them (kv_full_page_bytes_held:
            # whole pages, out to the end of the prompt's bucket and the
            # window ahead); the bytes, over all window layers, of the
            # positions a window layer still holds of them (a ring's reach
            # at most).
            # decode_kv_token_layers by the leaves walked (the two kinds
            # differ in their K/V heads, so in bytes a position). Its
            # prefill's prefill_attn_pairs: the (query, key) pairs each
            # layer's own mask keeps of a real prompt, summed over the layers.
            "kv_full_positions_live": 0, "kv_full_bytes_live": 0,
            "kv_full_page_bytes_held": 0, "kv_window_bytes_held": 0,
            "decode_kv_token_layers_full": 0, "decode_kv_token_layers_ring": 0,
            # A model that generates by diffusion over blocks (all 0 for
            # any other; a block program counts as a window, and holds
            # inference.denoising_steps + 1 forwards): live slots x forwards
            # (block_slot_forwards: x the block length, the positions they
            # fed) and of those positions the ones fed as the mask token,
            # from the program's own record of the forward that decided
            # each (block_positions_undecided_fed); tokens emitted
            # (tokens_committed); and, summed over slots and forwards, the
            # cached positions a forward's attention read, the block's own
            # among them (block_kv_positions_read). The rows the head and
            # the choice were given (block_head_rows: a live slot's
            # runner.undecided_bounds, summed over the denoising forwards)
            # and of those the ones that decided a token, again from the
            # program's record (block_head_rows_decided; the rest waited
            # for a later forward or filled up a prompt's tail). Host
            # arithmetic on lengths, no device value read.
            "block_slot_forwards": 0, "block_positions_undecided_fed": 0,
            "tokens_committed": 0, "block_kv_positions_read": 0,
            "block_head_rows": 0, "block_head_rows_decided": 0,
            # Per-phase device split (ISSUE 20 load-gauge satellite):
            # decode_device_s covers pure decode-phase dispatches
            # (decode windows, verify, draft compaction) and pairs with
            # decode_slot_steps for a phase-pure ITL proxy;
            # mixed_device_s covers chunk-carrying mixed dispatches
            # whose wall time fuses prompt and decode work.
            # device_s == decode_device_s + mixed_device_s, unchanged.
            "decode_device_s": 0.0, "mixed_device_s": 0.0,
            "windows": 0, "steps": 0,
            # Decode-waste accounting: slot_steps counts (active slot x
            # inner decode step) work the device performed; wasted_steps
            # the share discarded because the slot finished mid-window.
            # decode_slot_steps is the pure decode-window/verify subset
            # (mixed steps' decode rows excluded, matching
            # decode_device_s's numerator).
            "slot_steps": 0, "wasted_steps": 0, "decode_slot_steps": 0,
            # Chunked-prefill accounting: mixed_steps counts unified
            # dispatches, chunk_tokens the real prompt tokens they carried,
            # chunk_pad_tokens the padded-out chunk positions (the chunk-
            # side waste analog of wasted_steps — budget tuning reads both
            # instead of guessing).
            "mixed_steps": 0, "prefill_chunks": 0,
            "chunk_tokens": 0, "chunk_pad_tokens": 0,
            # Host-tier copy time: spill_s wraps the batched d2h of each
            # eviction sweep, restore_s the batched h2d of each restore
            # (inference.host_tier_bytes; both 0.0 with the tier off).
            # page_in_s is the per-request paging h2d (inference.
            # long_context): restores of a live request's own host-
            # resident pages ahead of the dispatch that reads them.
            "spill_s": 0.0, "restore_s": 0.0, "page_in_s": 0.0,
            # Cross-replica KV migration copy time (ISSUE 20): the
            # batched gather on the export side / scatter on the import
            # side. Both run OUTSIDE step() (router-driven) and flush
            # directly, like offload_prefix_cache's spill span.
            "migrate_out_s": 0.0, "migrate_in_s": 0.0,
        }

    def reset_timing(self) -> dict:
        """Return and zero the accumulated step timing split: device_s
        (decode dispatch -> token fetch, including mixed chunk+decode
        dispatches), prefill_s (admission bursts: uploads, dispatch,
        first-token sample), host_s (scheduler remainder), the leaf
        ``<phase>_s`` keys those three are sums of (_zero_timing), the
        prefill_dispatches/prefill_tokens/prefill_pad_tokens/
        prefill_picks_in_program/chained_steps/
        prefill_expert_rows/prefill_held_expert_rows/
        prefill_held_bound_overflows, decode_kv_tokens/
        decode_kv_token_layers/decode_kv_pages_read and kv_live_page_layers/
        kv_dead_window_page_layers sizing counters,
        windows/steps counters, the slot_steps/wasted_steps
        decode-waste tally, the mixed_steps/prefill_chunks/chunk_tokens/
        chunk_pad_tokens chunked-prefill tally, the decode_window (a
        snapshot, not zeroed), with
        inference.prefix_cache the prefix-cache counters
        (prefix_hits/misses/hit_rate, cached_tokens, inserted/evicted/cow
        pages), and with inference.speculative the speculation counters
        (spec_drafted/accepted/rolled_back/emitted, spec_acceptance_rate,
        verify_steps, verify_slot_steps, spec_tokens_per_verify, and
        spec_gated_steps — steps the draft-density gate sent back to the
        plain window), and with inference.constrained the grammar
        counters (constrain_* — compiles/cache hits, masked dispatch
        volume, forced-run draft/accept tally, completions/dead ends)."""
        out, self.timing = self.timing, self._zero_timing()
        out["decode_window"] = self.decode_window
        # prefix_stats also carries the per-request paging counters
        # (request_paged_out/in), which exist without a prefix tree.
        if self._pcache is not None or self._long:
            out.update(self.prefix_stats.as_timing())
            self.prefix_stats = PrefixCacheStats()
        if self._spec is not None:
            out.update(self.spec_stats.as_timing())
            old = self.spec_stats
            self.spec_stats = SpecDecodeStats(
                # Disablement is engine-lifetime state, not a window
                # counter: the reason survives the drain.
                disabled_reason=old.disabled_reason,
            )
        if self.constrained:
            # Constrained-decoding counters (metrics.ConstraintStats):
            # compiles/cache hits, masked dispatch volume, and the
            # forced-run draft/accept tally — drained like spec_stats.
            out.update(self.constraint_stats.as_timing())
            self.constraint_stats = ConstraintStats()
        # Robustness counters (metrics.RobustnessStats): typed request
        # outcomes + fault episodes, always present.
        out.update(self.robust.as_timing())
        self.robust = RobustnessStats()
        if self.icfg.metrics_jsonl or self.icfg.metrics_prom:
            # The registry exporters ride the drain point: one JSONL
            # time-series row / one Prometheus textfile rewrite per drain
            # window, carrying the drained counters plus the live gauges.
            row = {f"serve.{k}": v for k, v in out.items()}
            row.update(self.registry.snapshot(sections=("pool", "hbm")))
            try:
                if self.icfg.metrics_jsonl:
                    self.registry.export_jsonl(
                        self.icfg.metrics_jsonl, snapshot=row
                    )
                if self.icfg.metrics_prom:
                    self.registry.export_prometheus(
                        self.icfg.metrics_prom, snapshot=row
                    )
            except OSError as e:
                log.error("metrics export failed: %s", e)
        return out

    def clear_prefix_cache(self) -> int:
        """Drop every cached prefix (idle cached pages return to the free
        list); returns the number of pages released. Live requests keep
        their shared pages through their own refs. No-op when
        inference.prefix_cache is off."""
        if self._pcache is None:
            return 0
        return self._pcache.clear()

    def has_work(self) -> bool:
        return (
            bool(self.waiting)
            or bool(self._just_finished)
            or any(r is not None for r in self.slots)
        )

    # -- router-facing scheduler face (infer/router.py) --------------------

    @property
    def consec_failed_steps(self) -> int:
        """Consecutive failed step() calls (0 after any successful step) —
        the router's primary liveness signal for this replica; the engine
        itself re-raises at inference.max_step_faults."""
        return self._consec_failed

    def prefix_match_tokens(self, context: Sequence[int]) -> int:
        """Tokens of ``context`` this replica could serve from its radix
        prefix index right now — the router's prefix-affinity placement
        signal. Read-only (PrefixCache.peek: no locks, no LRU stamps, no
        edge splits), so probing N replicas never perturbs any tree. 0
        with the prefix cache off.

        Mirrors _match_prefix's USABILITY gates, not just its cap: a
        match below prefix_cache_min_pages, or shallower than the SWA
        dead-page boundary, is one admission would reject — advertising
        it would affinity-pin placements that then prefill cold."""
        if self._pcache is None:
            return 0
        cap = len(context) // self.psz
        if self.page_window is not None:
            # Mirror _match_prefix's SWA cap: a full-context match is
            # never usable there, so do not advertise it.
            cap = (len(context) - 1) // self.psz
        pages, host, first_host = self._pcache.peek_tiered(context, cap)
        if host and (
            self._host_pool is None
            or host * self.psz < self._host_min_tokens
        ):
            # Host-resident span admission would send to recompute (gate
            # below threshold, or a stale tier with no pool): advertise
            # only the usable device prefix. Above the threshold the FULL
            # match advertises — a host-warm replica must beat a cold one
            # at placement even though its hit pays one h2d.
            pages = first_host
        if pages < max(self.icfg.prefix_cache_min_pages, 1):
            return 0
        if self.page_window is not None and (
            pages < self._first_live_page(len(context))
        ):
            return 0
        return pages * self.psz

    def drain(self) -> list[Request]:
        """Graceful shutdown (the SIGTERM path, wired in generate.py via
        PreemptionHandler): stop admission, shed the wait queue with typed
        outcomes, finish every LIVE request — donating their pages to the
        prefix cache exactly as normal completion does — and return every
        request that terminated during the drain. Leaves the pool fully
        accounted (assert_page_accounting)."""
        self.draining = True
        keep: AdmissionQueue = AdmissionQueue()
        while self.waiting:
            r = self.waiting.popleft()
            if in_flight(r):
                # Preempted back into the queue after running: in-flight
                # work the drain contract finishes, not sheds.
                keep.append(r)
            else:
                self._shed(r, "draining")
        self.waiting = keep
        drained: list[Request] = []
        while self.has_work():
            drained.extend(self.step())
        self.assert_page_accounting()
        return drained

    def close(self) -> None:
        """Stop the serving watchdog thread, flush the metrics exporters
        and export the Chrome trace when inference.trace_path is set.
        Idempotent: the flush/export half runs once — a second close must
        not append a spurious all-zero row to the metrics time series.

        Admission stops permanently: a submit() after close() yields a
        typed "shed" outcome exactly like one after drain() — it must
        never queue work no step loop will ever run (ISSUE 12 lifecycle
        hardening; the router leans on this when retiring replicas)."""
        self.draining = True
        if not self._closed:
            self._closed = True
            if self.icfg.metrics_jsonl or self.icfg.metrics_prom:
                # Final drain so a short-lived serve (the CLI path, which
                # never calls reset_timing itself) still flushes its tail
                # window through the exporters.
                self.reset_timing()
            export_chrome_safe(self._tracer, self.icfg.trace_path)
        if self._watchdog is not None:
            self._watchdog.stop()

    def assert_page_accounting(self) -> None:
        """The drain-time pool invariant (bugfix-sweep guard for the shared
        release path): every pool page's allocator refcount equals its live
        owner count — one per page mapped by a request plus one per
        prefix-cache node holding it — and the free list holds exactly the
        rest. A double-release or leak in ANY teardown path (reap, preempt,
        expiry, cancel, quarantine, shed) trips this immediately."""
        n = self.icfg.num_pages
        refs = [0] * n
        owners = [r for r in self.slots if r is not None]
        owners += list(self.waiting) + list(self._just_finished)
        owners += list(self._importing.values())
        for req in owners:
            for p in req.pages:
                if p is not None:
                    refs[p] += 1
        if self._pcache is not None:
            for p in self._pcache.held_pages():
                refs[p] += 1
        actual = [self.alloc.refcount(p) for p in range(n)]
        bad = [
            (p, refs[p], actual[p])
            for p in range(1, n) if refs[p] != actual[p]
        ]
        assert not bad, (
            f"page refcount mismatch (page, owners, refcount): {bad[:8]}"
        )
        live = sum(1 for p in range(1, n) if refs[p] > 0)
        assert self.alloc.free_pages == n - 1 - live, (
            f"free-list size {self.alloc.free_pages} != "
            f"{n - 1 - live} (pool {n}, live {live})"
        )
        if self._host_pool is not None:
            # Host-tier half of the invariant: at a quiescent point host
            # slots are owned by the tree's HostPage markers plus live
            # requests' host_pages maps (inference.long_context — one
            # ENGINE-owned ref each; in-flight restore refs exist only
            # inside the restore envelope), so each held slot's refcount
            # is its owner count and the free list holds exactly the rest.
            hp = self._host_pool
            hrefs = [0] * hp.capacity
            tlive = 0
            if self._pcache is not None:
                for h in self._pcache.held_host_pages():
                    hrefs[h] += 1
                tlive = sum(1 for h in range(hp.capacity) if hrefs[h] > 0)
            for req in owners:
                for h in req.host_pages.values():
                    hrefs[h] += 1
            hbad = [
                (h, hrefs[h], hp.refcount(h))
                for h in range(hp.capacity) if hrefs[h] != hp.refcount(h)
            ]
            assert not hbad, (
                f"host slot refcount mismatch (slot, owners, refcount): "
                f"{hbad[:8]}"
            )
            hlive = sum(1 for h in range(hp.capacity) if hrefs[h] > 0)
            assert hp.free_slots == hp.capacity - hlive, (
                f"host free-list size {hp.free_slots} != "
                f"{hp.capacity - hlive} (capacity {hp.capacity}, "
                f"live {hlive})"
            )
            if self._pcache is not None:
                assert self._pcache.host_pages == tlive, (
                    f"host_pages counter {self._pcache.host_pages} != "
                    f"walked marker count {tlive}"
                )

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: Optional[int] = None,
    ) -> list[list[int]]:
        """Convenience drain loop: returns generated tokens per prompt, in
        submission order."""
        rids = [self.submit(p, max_new_tokens) for p in prompts]
        results: dict[int, list[int]] = {}
        while self.has_work():
            for req in self.step():
                results[req.rid] = req.generated
        return [results[rid] for rid in rids]

    def stream(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: Optional[int] = None,
    ):
        """Incremental drain loop: yields ``(rid, new_tokens)`` as tokens
        are accepted, one tuple per advanced request per engine step.

        Granularity is the engine step (``inference.decode_window`` fused
        token steps per host round-trip): lowering the window trades
        latency-to-first-yield against throughput. Requests still waiting
        for pool admission simply yield nothing until admitted.
        """
        reqs = [self.submit_request(p, max_new_tokens) for p in prompts]
        emitted = [0] * len(reqs)
        pending = set(range(len(reqs)))
        while pending:
            self.step()
            for i in sorted(pending):
                req = reqs[i]
                if len(req.generated) > emitted[i]:
                    yield req.rid, req.generated[emitted[i]:]
                    emitted[i] = len(req.generated)
                if req.done and emitted[i] == len(req.generated):
                    if emitted[i] == 0:
                        # Zero-token completion (e.g. max_new_tokens=0
                        # scoring): still announce the rid exactly once so
                        # consumers see every request they submitted.
                        yield req.rid, []
                    pending.discard(i)

    # -- scheduler internals ----------------------------------------------

    def _bucket_len(self, n: int) -> int:
        chunk = self.icfg.prefill_chunk
        return min(-(-n // chunk) * chunk, self.icfg.max_seq_len)

    def _admission_need(self, context_len: int) -> tuple[int, int, int]:
        """(n_pages, first_live, need): the pool demand of admitting a
        request whose context is ``context_len`` tokens.

        ``need`` covers the prefill's real (live) pages plus the first
        decode window's pre-provisioning — the exact check _admit applies;
        submit() maxes it over every context the request could re-prefill
        at so the pool-holds-this-request-alone invariant stays true.

        Chunked prefill allocates EVERY logical page (first_live = 0, even
        under SWA): a later chunk's queries read window-distant positions
        from the POOL (the prefix-page gather), so pages behind the
        window of the full context are still live for the chunks that
        attend them; _roll_window frees them as the chunk cursor — not
        the whole prompt — advances. Chunked SWA admission is therefore
        O(context) pages, traded for the bounded ITL.
        """
        n_pages = self._bucket_len(context_len) // self.psz
        first_live = (
            0 if self.chunked else self._first_live_page(context_len)
        )
        n_real = n_pages - first_live
        last = min(
            context_len + self._provision_window - 1,
            self.icfg.max_seq_len - 1,
        )
        first_window = min(last // self.psz + 1, self.pages_per_seq)
        # +1 spare on both branches: mid-decode pool exhaustion must stay
        # unreachable for a request the pool holds alone.
        need = max(n_real + 1, first_window - first_live + 1)
        return n_pages, first_live, need

    def _worst_admission_need(self, min_ctx: int, max_ctx: int) -> int:
        """Max admission need over every context in [min_ctx, max_ctx].

        Exact vectorized sweep: with a sliding window the demand is not
        monotone in context (bucket size is a step function while the
        dead-page count advances every page_size tokens), and the peak
        sits at a prefill-bucket bottom — not at max_ctx, where a
        candidate-point check would look.
        """
        icfg = self.icfg
        W, Wd, psz = self.page_window, self._provision_window, self.psz
        ctxs = np.arange(min_ctx, max_ctx + 1, dtype=np.int64)
        chunk = icfg.prefill_chunk
        bucket = np.minimum(-(-ctxs // chunk) * chunk, icfg.max_seq_len)
        first_live = (
            np.maximum(ctxs - W + 1, 0) // psz
            if W is not None and not self.chunked
            else np.zeros_like(ctxs)
        )
        if self._chunk is not None:
            first_live = ctxs // self._chunk * self._chunk // psz
        n_real = bucket // psz - first_live
        last = np.minimum(ctxs + Wd - 1, icfg.max_seq_len - 1)
        first_window = np.minimum(last // psz + 1, self.pages_per_seq)
        need = np.maximum(n_real + 1, first_window - first_live + 1)
        return int(need.max())

    def _available(self) -> int:
        """Pool headroom the scheduler may count on: free pages plus every
        cached page no live request has pinned — the cache is reclaimable
        headroom, not a separate budget (one pool, one invariant)."""
        ev = self._pcache.evictable_pages() if self._pcache is not None else 0
        return self.alloc.free_pages + ev

    def _alloc_pages(self, n: int) -> list[int]:
        """Allocate n pages, evicting LRU prefix-cache pages as needed.

        EVERY engine page allocation routes through here — it is the
        injection point for FaultSpec kind="pool" (a simulated allocator
        exhaustion), which the admit path absorbs by deferring the request
        and the grow path by failing the step, never the process."""
        if self._injector is not None and (
            self._injector.take("pool", self.step_no) is not None
        ):
            raise MemoryError(
                f"injected pool exhaustion (step {self.step_no})"
            )
        short = n - self.alloc.free_pages
        if short > 0 and self._pcache is not None:
            self.prefix_stats.evicted_pages += self._pcache.evict(short)
        return self.alloc.alloc(n)

    # -- host tier (inference.host_tier_bytes; README "Tiered prefix
    #    cache"): the two batched copy envelopes + the break-even gate ---

    def _spill_pages(
        self, pages: list[int], *, tree: bool = True
    ) -> Optional[list[int]]:
        """PrefixCache's spill callback: copy the victim pages' KV bytes
        (every cache array — int8 scale pools ride along) into host
        slots. ONE batched d2h serves the whole eviction sweep: one
        gather dispatch over all victims, one device_get. Returns the
        host slot ids (one engine-owned ref each, which demote hands to
        the tree), or None when the tier cannot take them — the caller
        falls back to discarding, so a spill failure degrades the cache,
        never the step."""
        hp = self._host_pool
        try:
            hids = hp.alloc(len(pages))
        except MemoryError:
            return None
        n = len(pages)
        npad = 1 << (n - 1).bit_length()
        padded = np.zeros(npad, np.int32)
        padded[:n] = pages
        try:
            with self._phase("spill"):
                blocks = self._gather_pages(self.cache, jnp.asarray(padded))
                # orion: allow[host-sync] the ONE batched d2h per eviction sweep — the host copy IS the operation
                blocks = jax.device_get(blocks)
        # orion: allow[fault-except] spill envelope: ANY copy failure degrades to discard eviction, never a failed step
        except Exception as e:
            hp.free(hids)
            self.robust.dispatch_faults += 1
            self._flight_note(
                "dispatch_fault", path="spill",
                error=f"{type(e).__name__}: {e}",
            )
            log.error("host-tier spill failed (%s); discarding instead", e)
            return None
        hp.store(hids, blocks, n)
        if tree:
            # tree=False is the per-request paging caller (_page_out /
            # _preempt_to_host): those slots never transit the radix
            # tree, so they count as request_paged_out, not
            # evicted_to_host.
            self.prefix_stats.evicted_to_host += n
        return hids

    def _restore_pages(self, pages: list, node, host_idx: list[int]) -> None:
        """Restore a matched path's host-resident entries into fresh pool
        pages with ONE batched h2d, then promote the tree markers to the
        new device ids — after which the caller maps the match exactly as
        a warm HBM hit. Runs under the match's lock (the path cannot
        mutate) with one engine ref per host slot in flight (the slots
        cannot be reclaimed).

        Failure containment: pool exhaustion while allocating the fresh
        pages propagates as MemoryError (the admission path defers, as
        any warm admission does); a fault inside the copy envelope —
        injected (FaultSpec kind="restore") or real — unwinds BOTH sides
        completely (fresh pages freed, in-flight refs dropped, tree
        markers untouched and unpromoted) and raises a typed
        DispatchFault: a torn restore can never leave a half-promoted
        path or leak a page on either tier."""
        hp = self._host_pool
        hids = [pages[i].hid for i in host_idx]
        for h in hids:
            hp.retain(h)
        n = len(hids)
        try:
            fresh = self._alloc_pages(n)
        except MemoryError:
            hp.free(hids)
            raise
        try:
            if self._injector is not None and (
                self._injector.take("restore", self.step_no) is not None
            ):
                raise InjectedFault(
                    f"injected restore fault (step {self.step_no})"
                )
            npad = 1 << (n - 1).bit_length()
            padded = np.zeros(npad, np.int32)
            padded[:n] = fresh
            blocks = hp.load(hids)
            if npad > n:
                blocks = {
                    k: np.concatenate(
                        [v, np.zeros((npad - n,) + v.shape[1:], v.dtype)]
                    )
                    for k, v in blocks.items()
                }
            with self._phase("restore"):
                self.cache = self._scatter_pages(
                    self.cache, jnp.asarray(padded),
                    {k: jnp.asarray(v) for k, v in blocks.items()},
                )
                # orion: allow[host-sync] the ONE batched h2d per restore — a torn copy must surface BEFORE any marker promotes
                jax.block_until_ready(self.cache)
        # orion: allow[fault-except] restore envelope: unwind both tiers fully, typed DispatchFault, no torn pages
        except Exception as e:
            self.alloc.free(fresh)
            hp.free(hids)
            self.robust.dispatch_faults += 1
            self._flight_note(
                "dispatch_fault", path="restore",
                error=f"{type(e).__name__}: {e}",
            )
            raise DispatchFault(
                "restore", f"{type(e).__name__}: {e}"
            ) from e
        self._pcache.promote_path(node, dict(zip(host_idx, fresh)))
        hp.free(hids)
        for i, p in zip(host_idx, fresh):
            pages[i] = p
        self.prefix_stats.host_hits += 1
        self.prefix_stats.host_restored_pages += n

    def _resolve_host_match(self, context, cap: int, pages: list, node):
        """A match() result containing host-resident entries is not yet
        mappable: either restore the whole match (break-even says the h2d
        beats recomputing the host span) or re-match truncated at the
        FIRST host entry (prefill needs a contiguous device prefix —
        entries past a gap are unusable even if device-resident). The
        binary choice is exact: restores are all-or-prefix, and the gate
        compares the host span's token count against the measured
        threshold."""
        host_idx = [
            i for i, p in enumerate(pages) if not isinstance(p, int)
        ]
        if (
            self._host_pool is not None
            and len(host_idx) * self.psz >= self._host_min_tokens
        ):
            try:
                self._restore_pages(pages, node, host_idx)
                return pages, node
            except MemoryError as e:
                # Pool too tight for the restore right now: fall back to
                # the device prefix rather than deferring the admission —
                # recompute always works.
                log.warning(
                    "host-tier restore deferred to recompute (%s)", e
                )
            except DispatchFault:
                # The envelope unwound both pools; balance the match
                # lock too before the typed fault fails the step —
                # retry re-matches from scratch.
                self._pcache.unlock(node)
                raise
        self.prefix_stats.host_recompute_skips += 1
        self._pcache.unlock(node)
        first_host = host_idx[0]
        if first_host == 0:
            return [], None
        return self._pcache.match(context, first_host)

    def offload_prefix_cache(self) -> int:
        """Demote every evictable device-resident cached page to the host
        tier (one batched d2h) — the fleet warm-start control: a replica
        about to scale down / hand off its traffic parks its working set
        in host RAM, and the router's affinity probe still advertises the
        prefixes, so the replica wins placement over a cold one and
        restores on first hit. Also the bench's phase control
        (tools/prefix_cache_bench.py --capacity-sweep). Returns device
        pages demoted; 0 with the tier (or the cache) off."""
        if self._pcache is None or self._host_pool is None:
            return 0
        n = self._pcache.demote(self._pcache.evictable_pages())
        self.prefix_stats.evicted_pages += n
        return n

    def _match_prefix(self, context: list[int]):
        """(n_match, pages, node): longest usable cached prefix of
        ``context``, page-granular, LOCKED against eviction (the caller
        owns the unlock). Always leaves at least the final token to
        recompute — a full-page-multiple full match is allowed (the COW
        admission path recomputes the last token via decode)."""
        if self._pcache is None:
            return 0, [], None
        cap = len(context) // self.psz
        if self.page_window is not None:
            # SWA: never take the COW full-match path, and only accept
            # matches at least as deep as the cold dead-page boundary —
            # a shallower match would have to ALLOCATE live prefix pages
            # for the tail prefill to read, pages a cold admission never
            # materializes, breaking the pool-holds-this-request-alone
            # accounting submit() checked against.
            cap = (len(context) - 1) // self.psz
        pages, node = self._pcache.match(context, cap)
        if node is not None and any(not isinstance(p, int) for p in pages):
            # Host-resident entries in the match: restore them (break-
            # even permitting) or fall back to the pure-device prefix.
            # Either way `pages` below holds only mappable device ids.
            pages, node = self._resolve_host_match(
                context, cap, pages, node
            )
        n_match = len(pages)
        ok = n_match >= max(self.icfg.prefix_cache_min_pages, 1)
        if ok and self.page_window is not None:
            ok = n_match >= self._first_live_page(len(context))
        if not ok:
            if node is not None:
                self._pcache.unlock(node)
            return 0, [], None
        return n_match, pages, node

    def _admission_need_warm(
        self, context_len: int, n_match: int, full: bool
    ) -> tuple[int, int, int, int]:
        """(n_pages, first_live, n_alloc, need) for a prefix-matched
        admission: ``n_alloc`` fresh pool pages (the uncached tail — exact
        page count, no bucket padding — or the single COW page on a full
        match), ``need`` the same live-prefill + first-decode-window
        demand _admission_need computes for cold admissions. Always
        <= the cold need submit() validated the pool against."""
        psz = self.psz
        if full:
            # Whole context cached: decode restarts at position len-1,
            # rewriting the final token's KV slot in a COW'd private copy
            # of the last matched page.
            n_pages, n_alloc = n_match, 1
            last = min(
                context_len - 1 + self._provision_window - 1,
                self.icfg.max_seq_len - 1,
            )
        else:
            n_pages = -(-context_len // psz)
            n_alloc = n_pages - n_match
            last = min(
                context_len + self._provision_window - 1,
                self.icfg.max_seq_len - 1,
            )
        first_window = min(last // psz + 1, self.pages_per_seq)
        first_live = (
            self._first_live_page(n_match * psz) if not full else 0
        )
        need = max(n_alloc + 1, n_alloc + first_window - n_pages + 1)
        return n_pages, first_live, n_alloc, need

    @property
    def _provision_window(self) -> int:
        """The decode window the pool must budget for (admission/submit
        check against this). With speculation on, at least
        speculate_tokens+1: a verify step writes draft KV that far past
        the cursor, and its page provisioning must never preempt a request
        admission promised to hold."""
        base = self.decode_window
        if self.icfg.speculative:
            base = max(base, self.icfg.speculate_tokens + 1)
        return base

    def _first_live_page(self, context_len: int) -> int:
        """First logical page a sequence at ``context_len`` can still read.

        With sliding-window attention the next decode query (position
        ``context_len``) attends kv positions > context_len - window; pages
        wholly before that are dead — never allocated at admission, and
        freed as the window rolls past them (_roll_window). 0 without SWA.
        """
        if self._chunk is not None:
            # Everything below the last complete chunk is in the state.
            return context_len // self._chunk * self._chunk // self.psz
        W = self.page_window
        if W is None:
            return 0
        return max(context_len - W + 1, 0) // self.psz

    def _roll_window(self) -> None:
        """Return dead pages (behind the sliding window) to the pool.

        The decode mask and the paged kernel's index clamp both exclude
        them, so a windowed sequence's steady-state footprint is
        O(window), not O(context). Freed logical slots keep a None
        placeholder so page indices stay position-aligned; their table
        entries point at scratch page 0 (never read). A power-retention
        model's dead pages are those behind its slot's last fold."""
        if self.page_window is None and self._chunk is None:
            return
        for req in self.slots:
            if req is None or req.slot is None:
                continue
            first = min(
                int(self.fold_lens[req.slot]) // self.psz
                if self._chunk is not None else
                self._first_live_page(int(self.seq_lens[req.slot])),
                len(req.pages),
            )
            if first <= req.freed_until:
                continue  # nothing newly dead since the last pass
            dead = [
                p for p in req.pages[req.freed_until:first] if p is not None
            ]
            for j in range(req.freed_until, first):
                req.pages[j] = None
            self.page_table[req.slot, req.freed_until:first] = 0
            req.freed_until = first
            if dead:
                self.alloc.free(dead)
            if req.host_pages:
                # SWA rolled past a host-resident page: its KV will never
                # be read again — drop the host slot instead of ever
                # paying the h2d to restore a dead page.
                rolled = [j for j in req.host_pages if j < first]
                if rolled:
                    self._host_pool.free(
                        [req.host_pages.pop(j) for j in rolled]
                    )

    # -- per-request KV paging (inference.long_context; README "Long
    #    context"): lazy chunk provisioning + host-tier demote/restore --

    def _long_admission_need(self) -> int:
        """Worst-instant pool demand of the LAZY chunked-prefill path
        (the over-pool admission bound): pages spanned by
        [cursor - W + 1, cursor + X - 1] for any page-aligned cursor —
        the live window behind plus the larger of one chunk and the
        decode provisioning window ahead — plus one page of span
        misalignment and the +1 spare every admission carries. O(window),
        independent of context length: that independence IS the
        long-context admission story (PERF.md "Long context")."""
        W = self.page_window
        X = max(self.icfg.prefill_chunk_tokens, self._provision_window)
        return (W + X - 2) // self.psz + 3

    def _drop_host_pages(self, req: Request) -> None:
        """Release every host slot a request holds (terminal paths and
        recompute-from-scratch preemption — stale KV must not occupy the
        tier)."""
        if req.host_pages:
            self._host_pool.free(list(req.host_pages.values()))
            req.host_pages.clear()
        req.host_cursor = 0

    def _page_out(self, req: Request) -> None:
        """Residency-cap demotion (inference.request_resident_pages):
        after a long request's chunk, demote its OLDEST live private
        pages beyond the cap to host slots — one batched d2h — freeing
        device pages for co-tenants between this request's turns. The
        pages come back through _page_in_request before the next chunk
        that reads them. Spill failure (full tier / copy fault) degrades
        to staying resident, never a failed step."""
        cap = self.icfg.request_resident_pages
        if not cap or not self._long or req.slot is None:
            return
        live = [
            j for j in range(req.freed_until, len(req.pages))
            if req.pages[j] is not None and j >= req.n_prefix
        ]
        excess = len(live) - cap
        if excess <= 0:
            return
        victims = live[:excess]
        pages = [req.pages[j] for j in victims]
        hids = self._spill_pages(pages, tree=False)
        if hids is None:
            return
        for j, h in zip(victims, hids):
            req.host_pages[j] = h
            req.pages[j] = None
        self.page_table[req.slot, victims] = 0
        self.alloc.free(pages)
        self.prefix_stats.request_paged_out += len(pages)
        if self._tracer.enabled:
            self._tracer.instant(
                "page_out", rid=req.rid, pages=len(pages),
                step=self.step_no, **self._trace_ctx(req),
            )

    def _page_in_request(self, req: Request) -> None:
        """Restore a live request's host-resident pages into fresh pool
        pages with ONE batched h2d, ahead of the chunk/decode dispatch
        that reads them (every still-held slot is live: _roll_window
        already dropped the rolled-dead ones).

        Failure containment mirrors _restore_pages: pool exhaustion
        propagates as MemoryError (the step fails and retries — the
        request keeps its host refs); a fault inside the copy envelope —
        injected (FaultSpec kind="restore") or real — unwinds the DEVICE
        side completely (fresh pages freed) while the HOST side keeps
        every slot, so the request stays resumable and a retry next step
        pages in from scratch. No torn page on either tier."""
        if not req.host_pages:
            return
        hp = self._host_pool
        due = sorted(req.host_pages)
        hids = [req.host_pages[j] for j in due]
        n = len(hids)
        fresh = self._alloc_pages(n)
        try:
            if self._injector is not None and (
                self._injector.take("restore", self.step_no) is not None
            ):
                raise InjectedFault(
                    f"injected restore fault (step {self.step_no})"
                )
            npad = 1 << (n - 1).bit_length()
            padded = np.zeros(npad, np.int32)
            padded[:n] = fresh
            blocks = hp.load(hids)
            if npad > n:
                blocks = {
                    k: np.concatenate(
                        [v, np.zeros((npad - n,) + v.shape[1:], v.dtype)]
                    )
                    for k, v in blocks.items()
                }
            with self._phase("page_in"):
                self.cache = self._scatter_pages(
                    self.cache, jnp.asarray(padded),
                    {k: jnp.asarray(v) for k, v in blocks.items()},
                )
                # orion: allow[host-sync] the ONE batched h2d per page-in — a torn copy must surface BEFORE any page maps
                jax.block_until_ready(self.cache)
        # orion: allow[fault-except] page-in envelope: free the fresh device pages, keep every host ref, typed DispatchFault
        except Exception as e:
            self.alloc.free(fresh)
            self.robust.dispatch_faults += 1
            self._flight_note(
                "dispatch_fault", path="page_in",
                error=f"{type(e).__name__}: {e}",
            )
            raise DispatchFault(
                "page_in", f"{type(e).__name__}: {e}"
            ) from e
        for j, p in zip(due, fresh):
            req.pages[j] = p
            del req.host_pages[j]
        hp.free(hids)
        self.page_table[req.slot, due] = fresh
        self.prefix_stats.request_paged_in += n
        if self._tracer.enabled:
            self._tracer.instant(
                "page_in", rid=req.rid, pages=n, step=self.step_no,
                **self._trace_ctx(req),
            )

    def _provision_chunk_pages(self, req: Request, k: int) -> None:
        """Lazy page materialization for the next chunk (the over-pool
        admission path allocates NOTHING up front): extend the request's
        page list to cover [cursor, cursor + k). Pool exhaustion raises
        MemoryError out of _alloc_pages — the step fails with pages
        owned, exactly the _grow_pages contract."""
        n_need = -(-(req.prefill_done + k) // self.psz)
        while len(req.pages) < n_need:
            page = self._alloc_pages(1)[0]
            self.page_table[req.slot, len(req.pages)] = page
            req.pages.append(page)

    def _preempt_to_host(self, req: Request, cursor: int) -> bool:
        """Preempt-to-host (inference.long_context): spill the victim's
        live private pages to host slots instead of discarding and
        re-prefilling from scratch — for a long request the O(context)
        chunked re-prefill is exactly the cost the tier exists to dodge.
        Gated by the same measured break-even the tree restores use
        (host_tier_min_tokens / the PERF.md arithmetic): below it,
        recompute wins and the plain preempt path runs. Returns True
        when the request left the slot host-resident."""
        if not self._long or self._host_pool is None:
            return False
        if req.n_prefix:
            # Shared prefix pages are tree-owned and immutable — the
            # radix tier already covers them; mixed ownership is not
            # worth the accounting.
            return False
        live = [
            j for j in range(req.freed_until, len(req.pages))
            if req.pages[j] is not None
        ]
        span = (len(live) + len(req.host_pages)) * self.psz
        if span < self._host_min_tokens:
            return False
        hids = None
        if live:
            hids = self._spill_pages(
                [req.pages[j] for j in live], tree=False
            )
            if hids is None:
                return False   # tier full / copy fault: plain preempt
        slot = req.slot
        if hids is not None:
            req.host_pages.update(zip(live, hids))
            self.prefix_stats.request_paged_out += len(live)
        req.host_cursor = cursor
        req.host_last_token = int(self.last_token[slot])
        self.alloc.free([req.pages[j] for j in live])
        req.pages = []
        if req.prefix_node is not None:   # unreachable (n_prefix == 0)
            self._pcache.unlock(req.prefix_node)
            req.prefix_node = None
        req.slot = None
        self.slots[slot] = None
        self.page_table[slot] = 0
        self.seq_lens[slot] = 0
        self.last_token[slot] = 0
        if self._spec is not None:
            self._spec.drop(req.rid)
        self.waiting.appendleft(req)
        if self._tracer.enabled:
            self._tracer.instant(
                "preempt_to_host", rid=req.rid, pages=len(live),
                cursor=cursor, step=self.step_no, **self._trace_ctx(req),
            )
        return True

    def _readmit_host(
        self, req: Request, slot: int, reserved: int
    ) -> Optional[int]:
        """Re-admit a host-resident request (preempt-to-host's other
        half): allocate fresh device pages for every spilled logical
        page, batched-restore them, and resume at the spill-time cursor
        — no re-prefill at all. Returns the claimed-but-unallocated page
        count (the caller's ``reserved`` delta), or None (head-of-line
        block) when the pool lacks the restore + first-window headroom;
        raises DispatchFault out of the copy envelope with the admission
        fully unwound (the request re-queues at the head, still
        host-resident, and retries next step)."""
        n = len(req.host_pages)
        last = min(
            req.host_cursor + self._provision_window - 1,
            self.icfg.max_seq_len - 1,
        )
        first_window = min(last // self.psz + 1, self.pages_per_seq)
        n_logical = max(
            max(req.host_pages) + 1 if req.host_pages else 0,
            -(-req.host_cursor // self.psz),
        )
        need = max(n + 1, n + first_window - n_logical + 1)
        if self._available() - reserved < need:
            return None
        req.slot = slot
        req.admit_seq = next(self._admit_seq)
        req.pages = [None] * n_logical
        self.slots[slot] = req
        self.page_table[slot] = 0
        try:
            self._page_in_request(req)
        except (MemoryError, DispatchFault):
            # Unwind the claim completely; host refs survive inside the
            # envelope, so the request re-queues resumable either way.
            req.pages = []
            req.slot = None
            self.slots[slot] = None
            self.waiting.appendleft(req)
            raise
        icfg = self.icfg
        self.slot_temp[slot] = (
            icfg.temperature if req.temperature is None
            else req.temperature
        )
        self.slot_top_k[slot] = (
            icfg.top_k if req.top_k is None else req.top_k
        )
        self.slot_top_p[slot] = (
            icfg.top_p if req.top_p is None else req.top_p
        )
        self.seq_lens[slot] = req.host_cursor
        self.last_token[slot] = req.host_last_token
        req.prefill_done = req.host_cursor
        req.prefill_pending = req.host_cursor < len(req.context)
        req.host_cursor = 0
        if self._tracer.enabled:
            self._tracer.instant(
                "admit", rid=req.rid, slot=slot, step=self.step_no,
                priority=req.priority, host_restored=n,
                **self._trace_ctx(req),
            )
        return need - n

    # -- cross-replica KV-page migration (ISSUE 20; infer/router.py
    #    drives these between steps for role-split fleets) ----------------
    #
    # Export half (the prefill replica): migration_ready /
    # migration_full_pages gate the handoff, export_migration_state
    # snapshots the host-side request state, export_migration_pages runs
    # the batched gather (the spill envelope's read half — int8 scale
    # pools ride the cache dict), finish_migration tears the slot down
    # WITHOUT a typed outcome once the destination committed (fleet-level
    # exactly-once surfacing moves with the request; full context pages
    # still donate to the source prefix tree on the way out).
    #
    # Import half (the decode replica): import_begin stages a Request
    # with no slot, import_pages allocates fresh pool pages and scatters
    # migrated blocks into them (the restore envelope's write half, same
    # unwind discipline), import_commit claims a slot and resumes decode
    # at the source cursor — a zero-prefill warm start, byte-identical
    # greedy continuation — and import_abort unwinds a torn handoff.
    # Staged requests are page owners (assert_page_accounting walks
    # them); a commit deferred on a full batch leaves the request WHOLLY
    # arrived, just unscheduled.

    def _active_request(self, rid: int) -> Optional[Request]:
        for r in self.slots:
            if r is not None and r.rid == rid and not r.done:
                return r
        return None

    def migration_ready(self, rid: int) -> bool:
        """Whole-request handoff can run: the prompt is fully prefilled
        and the first token sampled (both prefill paths sample it at
        prompt completion), so the destination resumes in pure decode."""
        req = self._active_request(rid)
        return (
            req is not None
            and not req.prefill_pending
            and bool(req.generated)
        )

    def migration_in_prefill(self, rid: int) -> bool:
        """The request is mid-chunked-prefill on a live slot — the
        per-chunk streaming mode (router.migrate_per_chunk) can open its
        stream and ship completed full pages ahead of the final commit."""
        req = self._active_request(rid)
        return req is not None and req.prefill_pending

    def migration_full_pages(self, rid: int) -> int:
        """Leading logical pages whose KV is final (wholly covered by the
        prefill chunk cursor / decode cursor): the per-chunk streaming
        watermark — a full page never mutates, so pages below this index
        ship once and stay valid."""
        req = self._active_request(rid)
        if req is None:
            return 0
        cursor = (
            req.prefill_done if req.prefill_pending
            else int(self.seq_lens[req.slot])
        )
        return min(cursor // self.psz, len(req.pages))

    def export_migration_state(self, rid: int) -> dict:
        """Host-side snapshot of everything the destination needs beyond
        the KV bytes: identity + sampling overrides, the decode cursor
        and in-flight token, the SWA rolling mark, and the grammar
        ``ConstraintState`` walk (pure host state — it moves with the
        request). No device work; call at commit time so the snapshot
        matches the shipped pages."""
        req = self._active_request(rid)
        if req is None:
            raise ValueError(f"no active request {rid} to export")
        if self._chunk is not None:
            raise ValueError(
                f"model {self.mcfg.name!r} keeps a state row a request "
                f"(model.attention=power_retention), which migration does "
                f"not ship yet")
        if self.mcfg.has_kda:
            raise ValueError(
                f"model {self.mcfg.name!r} keeps a state row a request and "
                f"no page in its KDA layers (model.attention=kda), which "
                f"migration, a copy of pages, does not ship")
        if self.mcfg.resumes_prefill:
            raise ValueError(
                f"model {self.mcfg.name!r} keeps a state row a request in "
                f"its lightning layers (model.mixer_types), which migration, "
                f"a copy of pages, does not ship")
        if self.mcfg.is_latent:
            raise ValueError(
                f"model {self.mcfg.name!r} caches one compressed row a "
                f"position (model.kv_lora_rank), which migration has not "
                f"been run on yet")
        if self.mcfg.block_length:
            raise ValueError(
                f"model {self.mcfg.name!r} generates by diffusion over "
                f"blocks (model.block_length), which migration has not "
                f"been run on yet")
        slot = req.slot
        return {
            "prompt": list(req.prompt),
            "generated": list(req.generated),
            "max_new_tokens": req.max_new_tokens,
            "temperature": req.temperature,
            "top_k": req.top_k,
            "top_p": req.top_p,
            "priority": req.priority,
            "deadline": req.deadline,
            "trace_id": req.trace_id,
            "attempt": req.attempt,
            "constraint": req.constraint,
            "cursor": int(self.seq_lens[slot]),
            "last_token": int(self.last_token[slot]),
            "prefill_pending": req.prefill_pending,
            "prefill_done": req.prefill_done,
            "freed_until": req.freed_until,
            "n_logical": len(req.pages),
            "page_size": self.psz,
        }

    def export_migration_pages(
        self, rid: int, start: int = 0, stop: Optional[int] = None
    ):
        """Batched gather of the live pages in logical span [start, stop)
        — ONE dispatch + the blocks as DEVICE arrays (``[npad, L, ...]``
        per cache array, int8 scale pools included), so the router can
        convert topology through ``parallel/reshard.py`` (or
        ``jax.device_get`` for the universal host hop) before the
        destination scatter. Host-tier-resident pages page in FIRST
        (restore-before-migrate): the gather needs device bytes, and the
        page-in envelope's unwind already covers its faults. Returns
        ``(live, blocks)`` with ``live`` the absolute logical indices
        gathered. The source request is untouched — gather is a pure pool
        read, so a failed handoff leaves it serving colocated."""
        req = self._active_request(rid)
        if req is None:
            raise ValueError(f"no active request {rid} to export")
        if req.host_pages:
            self._page_in_request(req)
        if stop is None:
            stop = len(req.pages)
        live = [
            j for j in range(start, min(stop, len(req.pages)))
            if req.pages[j] is not None
        ]
        if not live:
            return [], {}
        n = len(live)
        npad = 1 << (n - 1).bit_length()
        padded = np.zeros(npad, np.int32)
        padded[:n] = [req.pages[j] for j in live]
        try:
            with self._phase("migrate_out"):
                blocks = self._gather_pages(self.cache, jnp.asarray(padded))
                jax.block_until_ready(blocks)  # orion: allow[host-sync] a torn gather must surface HERE, not inside the destination scatter
        # orion: allow[fault-except] migrate-out envelope: pure read — nothing to unwind; typed DispatchFault, source request intact
        except Exception as e:
            self.robust.dispatch_faults += 1
            self._flight_note(
                "dispatch_fault", path="migrate_out",
                error=f"{type(e).__name__}: {e}",
            )
            raise DispatchFault(
                "migrate_out", f"{type(e).__name__}: {e}"
            ) from e
        return live, blocks

    def finish_migration(self, rid: int) -> None:
        """Source-side commit: the destination holds the whole request —
        tear the slot down with NO typed outcome (the request surfaces
        exactly once, from the destination), donating full context pages
        to the source prefix tree exactly like a reap would, so the
        source stays warm for affinity-matched followers."""
        req = self._active_request(rid)
        if req is None:
            return
        cursor = int(self.seq_lens[req.slot])
        self._teardown_slot(req, cursor)
        req.done = True
        self._ttft_seen.discard(req.rid)
        if self._tracer.enabled:
            self._tracer.instant(
                "migrate_out", rid=req.rid, cursor=cursor,
                step=self.step_no, **self._trace_ctx(req),
            )

    def import_begin(self, state: dict) -> int:
        """Stage an incoming migration: a Request with no slot, owning
        pages as they arrive (import_pages). Returns the engine rid the
        router uses as the stream token. Validates the ONE layout
        parameter the page copy cannot convert — page geometry; pool
        sizes, shardings and dtypes convert in transit."""
        if state["page_size"] != self.psz:
            raise ValueError(
                f"migration page_size {state['page_size']} != "
                f"destination page_size {self.psz} (page-granular copies "
                f"cannot re-chunk; match inference.page_size across roles)"
            )
        req = Request(
            rid=next(self._rid),
            prompt=list(state["prompt"]),
            max_new_tokens=state["max_new_tokens"],
            temperature=state["temperature"],
            top_k=state["top_k"],
            top_p=state["top_p"],
            priority=state["priority"],
            deadline=state["deadline"],
            trace_id=state["trace_id"],
            attempt=state["attempt"],
            constraint=state["constraint"],
        )
        self._importing[req.rid] = req
        return req.rid

    def import_pages(self, token: int, live: list, blocks: dict) -> None:
        """Scatter one batch of migrated page blocks into fresh pool
        pages at the staged request's logical indices ``live``. The write
        half of the restore envelope with the same unwind: a fault frees
        the fresh pages and raises a typed DispatchFault with the staged
        request unchanged — the router aborts or retries; no torn page
        either way."""
        req = self._importing[token]
        n = len(live)
        fresh = self._alloc_pages(n)
        try:
            npad = 1 << (n - 1).bit_length()
            padded = np.zeros(npad, np.int32)
            padded[:n] = fresh
            with self._phase("migrate_in"):
                self.cache = self._scatter_pages(
                    self.cache, jnp.asarray(padded),
                    {k: jnp.asarray(v) for k, v in blocks.items()},
                )
                jax.block_until_ready(self.cache)  # orion: allow[host-sync] the ONE sync per migrate-in batch — a torn copy must surface BEFORE the commit
        # orion: allow[fault-except] migrate-in envelope: free the fresh pages, keep the staged request, typed DispatchFault
        except Exception as e:
            self.alloc.free(fresh)
            self.robust.dispatch_faults += 1
            self._flight_note(
                "dispatch_fault", path="migrate_in",
                error=f"{type(e).__name__}: {e}",
            )
            raise DispatchFault(
                "migrate_in", f"{type(e).__name__}: {e}"
            ) from e
        if live and max(live) >= len(req.pages):
            req.pages.extend([None] * (max(live) + 1 - len(req.pages)))
        for j, p in zip(live, fresh):
            req.pages[j] = p

    def import_commit(self, token: int, state: dict) -> Optional[Request]:
        """Admit the staged request as a zero-prefill warm start: claim a
        free slot, mirror the source's page layout and cursors, resume
        decode on the in-flight token. Returns the live Request, or None
        when no slot (or no first-window page headroom) is free — the
        request stays staged, WHOLLY arrived, and the router retries the
        commit next step. Mirrors _readmit_host's slot restore exactly;
        the decode stream continues byte-identical to a colocated serve
        for greedy requests (argmax is key-independent — sampled streams
        draw from the destination engine's key lineage, the same caveat
        as the prefix cache's zero-prefill path)."""
        req = self._importing[token]
        slot = next(
            (i for i, r in enumerate(self.slots) if r is None), None
        )
        if slot is None:
            return None
        n_logical = max(state["n_logical"], len(req.pages))
        cursor = state["cursor"]
        last = min(
            cursor + self._provision_window - 1, self.icfg.max_seq_len - 1
        )
        first_window = min(last // self.psz + 1, self.pages_per_seq)
        headroom = max(first_window - n_logical, 0) + 1
        if self._available() < headroom:
            return None
        del self._importing[token]
        if len(req.pages) < n_logical:
            req.pages.extend([None] * (n_logical - len(req.pages)))
        req.generated = list(state["generated"])
        req.constraint = state["constraint"]
        req.freed_until = state["freed_until"]
        # The source's SWA window may have rolled past pages shipped
        # earlier in a per-chunk stream: they are dead at commit — free
        # them now, exactly as the source's _roll_window did.
        stale = [
            j for j in range(min(req.freed_until, len(req.pages)))
            if req.pages[j] is not None
        ]
        if stale:
            self.alloc.free([req.pages[j] for j in stale])
            for j in stale:
                req.pages[j] = None
        req.slot = slot
        req.admit_seq = next(self._admit_seq)
        self.slots[slot] = req
        icfg = self.icfg
        self.slot_temp[slot] = (
            icfg.temperature if req.temperature is None
            else req.temperature
        )
        self.slot_top_k[slot] = (
            icfg.top_k if req.top_k is None else req.top_k
        )
        self.slot_top_p[slot] = (
            icfg.top_p if req.top_p is None else req.top_p
        )
        self.page_table[slot] = 0
        self.page_table[slot, :len(req.pages)] = [
            0 if p is None else p for p in req.pages
        ]
        self.seq_lens[slot] = cursor
        self.last_token[slot] = state["last_token"]
        req.prefill_done = state["prefill_done"]
        req.prefill_pending = state["prefill_pending"]
        if self._tracer.enabled:
            self._tracer.instant(
                "migrate_in", rid=req.rid, slot=slot, cursor=cursor,
                step=self.step_no, **self._trace_ctx(req),
            )
        return req

    def import_abort(self, token: int) -> None:
        """Unwind a torn/abandoned migration stream: free every staged
        page and drop the staged request. Idempotent (a commit already
        consumed the token -> no-op), so the router's failure paths can
        call it unconditionally."""
        req = self._importing.pop(token, None)
        if req is None:
            return
        self.alloc.free([p for p in req.pages if p is not None])
        req.pages = []

    def migration_block_shardings(self) -> Optional[dict]:
        """Target shardings for migrated-in page blocks, one per cache
        array: this pool's own sharding with the leading pool-row dim
        replaced by the block batch dims (``[rows, ...] -> [n, L, ...]``)
        so `parallel/reshard.py` can move a source replica's gathered
        blocks straight onto this replica's layout — the manifest-style
        per-array redistribution, without a host bounce when source and
        destination share a platform. Returns None when any pool array
        carries no usable sharding (the router then falls back to the
        universal jax.device_get hop)."""
        out = {}
        for name, arr in self.cache.items():
            sh = getattr(arr, "sharding", None)
            if sh is None:
                return None
            if isinstance(sh, jax.sharding.NamedSharding):
                spec = jax.sharding.PartitionSpec(None, None, *sh.spec[1:])
                out[name] = jax.sharding.NamedSharding(sh.mesh, spec)
            else:
                # Single-device pool: place blocks on the same device.
                out[name] = sh
        return out

    def _admit(self) -> None:
        # Pass 1 (host): claim slots + pages for every admissible request,
        # highest priority class first, arrival order within a class
        # (with all-default priorities this IS arrival order, exactly the
        # pre-priority behavior) and head-of-line blocking on resources.
        # No draining gate here: while draining, submit() sheds on arrival
        # and drain()'s entry pass sheds queued never-started requests, so
        # anything still in the queue is in-flight work (preempted, or
        # unwound by a fault) that MUST re-admit to finish — gating it
        # would livelock the drain loop.
        admitted: list[tuple[Request, int]] = []
        # Headroom pages claimed by this burst's earlier admissions but not
        # yet allocated (they materialize in _grow_pages): without carrying
        # this across the loop, N admissions each pass the check against the
        # same free pool and the burst over-commits — _grow_pages then
        # preempts an OLDER request in the same step, discarding its
        # just-done prefill.
        reserved = 0
        while self.waiting:
            idx = max(
                range(len(self.waiting)),
                key=lambda i: (self.waiting[i].priority, -i),
            )
            req = self.waiting[idx]
            slot = next(
                (i for i, r in enumerate(self.slots) if r is None), None
            )
            if slot is None:
                break
            if req.host_pages:
                # Host-resident re-admission (preempt-to-host's other
                # half): restore the spilled pages and resume at the
                # spill-time cursor — no re-prefill. A DispatchFault out
                # of the copy envelope has already unwound the claim and
                # re-queued the request; let it fail the step.
                del self.waiting[idx]
                delta = self._readmit_host(req, slot, reserved)
                if delta is None:
                    self.waiting.insert(idx, req)
                    break   # head-of-line blocking, as below
                reserved += delta
                continue
            context = req.context
            # Prefix cache: map the longest cached prefix (shared,
            # refcount++) and prefill only the uncached tail. The matched
            # path is locked (evict-proof) from here until release.
            n_match, m_pages, m_node = self._match_prefix(context)
            full = bool(n_match) and n_match * self.psz >= len(context)
            if full:
                temp = (
                    self.icfg.temperature
                    if req.temperature is None else req.temperature
                )
                if temp != 0.0:
                    # Sampled request: the zero-prefill path would draw its
                    # first token from the decode key stream where the cold
                    # engine draws it from the prefill stream — breaking
                    # sampled cache-on/off byte-equivalence. Fall back to a
                    # one-page tail re-prefill (still n_match-1 pages
                    # shared); greedy requests keep the zero-prefill path
                    # (argmax is key-independent).
                    full = False
                    n_match = (len(context) - 1) // self.psz
                    if n_match < max(self.icfg.prefix_cache_min_pages, 1):
                        self._pcache.unlock(m_node)
                        n_match, m_pages, m_node = 0, [], None
                    else:
                        m_pages = m_pages[:n_match]
            if n_match and self._lazy and self._admission_need_warm(
                len(context), n_match, full
            )[3] > self.icfg.num_pages - 1:
                # Over-pool long request with a prefix match: the warm
                # path's eager tail allocation can NEVER fit — drop the
                # match and take the lazy cold branch below.
                self._pcache.unlock(m_node)
                n_match, m_pages, m_node = 0, [], None
                full = False
            if n_match:
                n_pages, first_live, n_alloc, need = (
                    self._admission_need_warm(len(context), n_match, full)
                )
                s_pad = self._bucket_len(len(context) - n_match * self.psz)
            else:
                # Sliding window: logical pages wholly behind the window are
                # dead on arrival (decode will never read them) — their table
                # entries point at scratch page 0 and no pool page is spent.
                # `need` also reserves the first decode window's
                # pre-provisioning: admitting on the prefill footprint alone
                # would let _grow_pages preempt the request right back out in
                # the same step when decode_window > page_size.
                n_pages, first_live, need = self._admission_need(len(context))
                n_alloc = n_pages - first_live
                s_pad = self._bucket_len(len(context))
                if self._lazy and need > self.icfg.num_pages - 1:
                    # Over-pool long-context admission (inference.
                    # long_context): the eager footprint can never fit —
                    # admit on the O(window) lazy working set instead.
                    # NO pages allocate here: chunks materialize their
                    # own (_provision_chunk_pages) and _roll_window
                    # frees behind the window, so the pool never holds
                    # the O(context) footprint at once.
                    first_live = 0
                    n_alloc = 0
                    need = self._long_admission_need()
            if self._available() - reserved < need:
                if m_node is not None:
                    self._pcache.unlock(m_node)
                break  # head-of-line blocking: keep class/arrival order
            reserved += need - n_alloc
            del self.waiting[idx]
            req.slot = slot
            req.admit_seq = next(self._admit_seq)
            req.prefix_node = m_node
            # Fresh pages allocate FIRST in every branch: _alloc_pages is
            # the only fallible op (injected/real pool exhaustion), so a
            # MemoryError here leaves nothing to unwind beyond the claim.
            try:
                if full:
                    # Whole context cached (exact page multiple): no
                    # prefill at all. Copy-on-write the final matched page
                    # — the first decode step rewrites the last token's KV
                    # slot, and shared pages are immutable — then restart
                    # decode from position len-1 with the last context
                    # token in flight.
                    cow = self._alloc_pages(1)[0]
                    self.cache = self._cow(
                        self.cache, jnp.int32(m_pages[-1]), jnp.int32(cow)
                    )
                    for p in m_pages[:-1]:
                        self.alloc.retain(p)
                    req.pages = list(m_pages[:-1]) + [cow]
                    req.n_prefix = n_match - 1
                    req.freed_until = 0
                    self.prefix_stats.hits += 1
                    self.prefix_stats.cached_tokens += len(context) - 1
                    self.prefix_stats.cow_pages += 1
                elif n_match:
                    fresh = self._alloc_pages(n_alloc)
                    live = m_pages[first_live:]
                    for p in live:
                        self.alloc.retain(p)
                    req.pages = [None] * first_live + list(live) + fresh
                    req.n_prefix = n_match
                    req.freed_until = first_live
                    self.prefix_stats.hits += 1
                    self.prefix_stats.cached_tokens += n_match * self.psz
                else:
                    req.pages = (
                        [None] * first_live + self._alloc_pages(n_alloc)
                    )
                    req.n_prefix = 0
                    req.freed_until = first_live
                    if self._pcache is not None:
                        self.prefix_stats.misses += 1
            except MemoryError as e:
                # Pool exhaustion at admit (injected, or an allocator/
                # accounting fault): un-claim and retry next step instead
                # of crashing the engine mid-admission.
                self.robust.pool_faults += 1
                log.warning(
                    "admission of request %d hit pool exhaustion (%s); "
                    "deferred", req.rid, e,
                )
                if m_node is not None:
                    self._pcache.unlock(m_node)
                req.prefix_node = None
                req.slot = None
                # Un-claim completely: admit_seq >= 0 marks in-flight work
                # (shed/drain-exempt), and this request never ran.
                req.admit_seq = -1
                self.waiting.appendleft(req)
                break
            self.slots[slot] = req
            if self._tracer.enabled:
                self._tracer.instant(
                    "admit", rid=req.rid, slot=slot, step=self.step_no,
                    priority=req.priority,
                    cached_tokens=(
                        len(context) - 1 if full
                        else n_match * self.psz
                    ),
                    **self._trace_ctx(req),
                )
            icfg = self.icfg
            self.slot_temp[slot] = (
                icfg.temperature if req.temperature is None
                else req.temperature
            )
            self.slot_top_k[slot] = (
                icfg.top_k if req.top_k is None else req.top_k
            )
            self.slot_top_p[slot] = (
                icfg.top_p if req.top_p is None else req.top_p
            )
            # len(req.pages) == n_pages on every eager branch; the lazy
            # branch admitted with NO pages (they materialize per chunk).
            self.page_table[slot, :len(req.pages)] = [
                0 if p is None else p for p in req.pages
            ]
            if full:
                self.seq_lens[slot] = len(context) - 1
                self.last_token[slot] = context[-1]
                if req.max_new_tokens <= 0:
                    # Scoring request with its whole context cached:
                    # nothing to compute; reap re-donates the pages.
                    req.done = True
            else:
                # (a block model's cursor: the prompt's whole blocks)
                self.seq_lens[slot] = len(context) - (
                    len(context) % self.mcfg.block_length
                    if self.mcfg.block_length else 0)
                admitted.append((req, s_pad))

        # Pass 2. Chunked prefill (inference.chunked_prefill): NO eager
        # prefill dispatch at all — admitted prompts only set their chunk
        # cursor (past any cached prefix) and ride the next mixed steps,
        # so a long-prompt admission can never stall in-flight decodes by
        # more than one chunk budget.
        if admitted and self.chunked:
            for req, _ in admitted:
                req.prefill_done = req.n_prefix * self.psz
                req.prefill_pending = True
                self.seq_lens[req.slot] = req.prefill_done
            return
        # Unchunked pass 2 (device). On the pallas path: ONE ragged
        # prefill dispatch for the WHOLE burst, regardless of length mix
        # (VERDICT r3 item 7) — rows pad to the burst's largest bucket,
        # but the flash kernel SKIPS blocks whose rows/columns are all
        # padding (segment id 0), so each row's attention pays ~its own
        # length (the quadratic term; the linear ops still run at the
        # shared width). On the xla path no block skip exists — a short
        # row would pay the burst-max O(S^2) attention — so keep one
        # dispatch per bucket there. Rows are padded up to a power-of-two
        # batch so jit specializations stay bounded.
        if admitted and self.mcfg.resumes_prefill:
            # One request a burst: its chunks run back to back, the last
            # of the step's last request may stay in flight.
            for i, (req, s_pad) in enumerate(admitted):
                try:
                    self._prefill_bucket(
                        [req], s_pad, chain=i == len(admitted) - 1)
                except DispatchFault:
                    for r, _ in reversed(admitted[i + 1:]):
                        self._teardown_slot(r, 0)
                        r.freed_until = 0
                        self.waiting.appendleft(r)
                    raise
        elif admitted:
            from orion_tpu.ops._dispatch import resolve_impl

            if resolve_impl(self.mcfg.kernels)[0]:
                self._prefill_bucket(
                    [r for r, _ in admitted], max(s for _, s in admitted),
                    chain=True,
                )
            else:
                by_bucket: dict[int, list[Request]] = {}
                for req, s_pad in admitted:
                    by_bucket.setdefault(s_pad, []).append(req)
                items = list(by_bucket.items())
                for bi, (s_pad, reqs) in enumerate(items):
                    try:
                        # The step's one prefill may stay in flight.
                        self._prefill_bucket(
                            reqs, s_pad, chain=len(items) == 1)
                    except DispatchFault:
                        # The faulted bucket unwound its own admissions;
                        # the not-yet-dispatched buckets are admitted but
                        # unprefilled — unwind them too before failing
                        # the step.
                        for _, later in items[bi + 1:]:
                            for r in reversed(later):
                                self._teardown_slot(r, 0)
                                r.freed_until = 0
                                self.waiting.appendleft(r)
                        raise

    def _prefill_bucket(
        self, reqs: list[Request], s_pad: int, chain: bool = False
    ) -> None:
        """Prefill a group of admitted requests in one dispatch; rows may
        be shorter than ``s_pad`` (their tail positions write to the
        scratch page and their compute blocks skip via segment ids).
        Prefix-matched rows carry only their uncached TAIL here — the
        prefix page ids ride along for the mid-sequence attention gather
        (runner.prefill_step), padded to the burst's max match (power of
        two, so jit specializations stay bounded).

        The dispatch is launched here. With ``chain`` (the step's one
        prefill) and a greedy burst it stays in flight (``self._burst``)
        for the step's decode window to be queued behind it; whoever needs
        the first tokens on the host calls ``_finish_prefill``. Any other
        burst is finished before this returns."""
        # Where the burst's rows start: behind a cached prefix, or, for a
        # model whose prefill resumes, behind the chunks launched here.
        first_page = {r.rid: r.n_prefix for r in reqs}
        if self.mcfg.resumes_prefill:
            (req,) = reqs       # (_admit hands such a model one at a time)
            try:
                done = self._prefill_earlier_chunks(req)
            except DispatchFault:
                self._unwind_burst(reqs)
                raise
            first_page[req.rid] = done // self.psz
            s_pad = self._bucket_len(len(req.context) - done)
        with self._phase("prefill/build"):
            n_pages = s_pad // self.psz
            nb = 1 << (len(reqs) - 1).bit_length()   # next power of two
            tokens = np.zeros((nb, s_pad), np.int32)
            lengths = np.ones(nb, np.int32)          # pad rows: length 1
            # pad rows: scratch page 0
            pages = np.zeros((nb, n_pages), np.int32)
            max_pre = max(r.n_prefix for r in reqs)
            p_pre = 1 << (max_pre - 1).bit_length() if max_pre > 0 else 0
            if self.mcfg.resumes_prefill:
                p_pre = self.pages_per_seq      # the slot's whole row
            pre_lens = np.zeros(nb, np.int32)
            pre_pages = np.zeros((nb, p_pre), np.int32)
            # Where each row's pick lands in the step's last tokens (a
            # padding row: out of range, which the scatter drops).
            slots = np.full(nb, self.max_batch, np.int32)
            slots[: len(reqs)] = [r.slot for r in reqs]
            # A power-retention model: the state row each row of the burst
            # owns (slot + 1; padding rows take scratch row 0).
            state_rows = (
                None if self._chunk is None and not (
                    self.mcfg.has_kda or self.mcfg.has_window_ring
                    or self.mcfg.resumes_prefill)
                else np.where(
                    slots < self.max_batch, slots + 1, 0).astype(np.int32))
            for i, req in enumerate(reqs):
                npre = first_page[req.rid]
                tail = req.context[npre * self.psz:]
                tokens[i, : len(tail)] = tail
                lengths[i] = len(tail)
                if self.mcfg.block_length:
                    # Whole blocks alone (the rest enters the first block as
                    # decided positions); a prompt shorter than one block
                    # prefills one position, written beyond the cursor.
                    lengths[i] = max(int(self.seq_lens[req.slot]), 1)
                pre_lens[i] = npre * self.psz
                if self.mcfg.resumes_prefill:
                    pre_pages[i] = self.page_table[req.slot]
                elif npre:
                    # Dead (behind-window) matched pages point at scratch
                    # 0 — behind every tail query's window, never
                    # attended.
                    pre_pages[i, :npre] = [
                        0 if p is None else p for p in req.pages[:npre]
                    ]
                # Dead (behind-window) logical pages write to scratch
                # page 0; those positions are never read back (sliding-
                # window mask). Positions past this row's own bucket
                # (shorter than the burst's) go to scratch too.
                tail_pg = req.pages[npre:]
                pages[i, : len(tail_pg)] = [
                    0 if p is None else p for p in tail_pg
                ]
            # Greedy with no legal mask, read off the requests (_admit
            # resolved None-means-default into the slot arrays): the
            # program's own argmax is then what the sampler would return.
            picked = all(self.slot_temp[r.slot] <= 0.0 for r in reqs) and not (
                self.constrained
                and any(r.constraint is not None for r in reqs))
            if self.mcfg.block_length:
                picked = True   # prefill samples nothing: no host sampler
        key = self._key
        try:
            # The uploads are part of prefill_s, as they always were.
            with self._phase("prefill/run"):
                args = (
                    self.params,
                    self.cache,
                    jnp.asarray(tokens),
                    jnp.asarray(lengths),
                    jnp.asarray(pages),
                    jnp.asarray(pre_lens),
                    jnp.asarray(pre_pages),
                    None if state_rows is None else jnp.asarray(state_rows),
                    jnp.asarray(slots),
                    # A copy, like every mirror handed to a program that is
                    # not waited for at once: an upload may alias the
                    # host's buffer, which the engine writes again.
                    jnp.asarray(self.last_token.copy()),
                    key,
                )
                out = self._executor.run("prefill", "prefill", *args)
        except DispatchFault:
            self._unwind_burst(reqs)
            raise
        logits, self.cache = out
        if picked and not self.mcfg.block_length:
            self._key = self._executor.key
            self.timing["prefill_picks_in_program"] += 1
        # Whose budget ends at the first token, as far as the host knows
        # (_maybe_finish's rule less the stop token); a block model's
        # prefill yields no token, and ends a request that asks for none.
        first = 0 if self.mcfg.block_length else 1
        ends = frozenset(
            r.rid for r in reqs
            if r.max_new_tokens - len(r.generated) <= first
            or int(self.seq_lens[r.slot]) >= self.icfg.max_seq_len)
        self._burst = _Burst(reqs, args, logits, picked, key, ends)
        real = int(lengths[: len(reqs)].sum())
        self.timing["prefill_dispatches"] += 1
        self.timing["prefill_tokens"] += real
        self.timing["prefill_pad_tokens"] += nb * s_pad - real
        if self._chunk is not None:
            from orion_tpu.ops.retention import query_units

            for i, req in enumerate(reqs):
                n = int(lengths[i])
                self.fold_lens[req.slot] = n // self._chunk * self._chunk
                self.timing["prefill_retention_units"] += (
                    self.mcfg.n_layers
                    * query_units(n, self.mcfg.resolved_head_dim))
        if self.mcfg.has_latent:
            self.timing["prefill_attn_pairs"] += (
                self.mcfg.n_layers_of("latent") * sum(
                    int(n) * (int(n) + 1) // 2 for n in lengths[: len(reqs)]))
        if self.mcfg.has_window_ring:
            for window, layers in self._layers_by_window.items():
                for n in lengths[: len(reqs)]:
                    n, w = int(n), int(n) if window is None else min(
                        int(n), window)
                    self.timing["prefill_attn_pairs"] += layers * (
                        w * (w + 1) // 2 + (n - w) * w)
        if self.mcfg.block_length:
            # The pairs the block mask keeps of a prompt's whole blocks:
            # a row sees to the end of its block, n (n + L) / 2 a layer.
            L = self.mcfg.block_length
            self.timing["prefill_attn_pairs"] += self.mcfg.n_layers * sum(
                int(n) * (int(n) + L) // 2 for n in lengths[: len(reqs)])
        if self.mcfg.has_kda:
            self.timing["prefill_kda_token_layers"] += (
                self.mcfg.n_layers_of("kda") * real)
        if self.mcfg.resumes_prefill:
            self._count_resumed_prefill(int(pre_lens[0]), real)
        if self.mcfg.is_moe:
            # Pad rows have length 1, so one position of each routes too.
            self.timing["prefill_expert_rows"] += expert_rows(
                self.mcfg, nb, s_pad, int(lengths.sum()), self.mesh)
        if not (chain and picked):
            self._finish_prefill()

    def _unwind_burst(self, reqs: list[Request]) -> None:
        """Unwind a failed prefill's admissions: their slots are claimed
        but NO KV is theirs, so tear down with nothing donated (n_cached=0
        — donating would insert garbage pages into the prefix cache) and
        re-queue at the head for the next step's re-prefill."""
        for r in reversed(reqs):
            self._teardown_slot(r, 0)
            r.freed_until = 0
            self.waiting.appendleft(r)

    def _finish_prefill(self) -> bool:
        """Wait for the prefill in flight, bring its first tokens to the
        host and emit them. True where the wait's fallback ladder ran the
        prefill again: ``self.cache`` and the key are then the fallback's,
        and whatever was queued behind the first launch is void. A fault
        unwinds the burst's admissions and leaves the key where it was
        before the launch."""
        b, self._burst = self._burst, None
        try:
            with self._phase("prefill/run"):
                # The logits alone: the cache may be a later program's by
                # now (donated to it), which waits for it in its turn.
                out = self._executor.wait(
                    "prefill", "prefill", b.logits, *b.args)
        except DispatchFault:
            self._key = b.key
            self._unwind_burst(b.reqs)
            raise
        again = out is not b.logits
        logits = b.logits
        if again:
            logits, self.cache = out
            if b.picked and not self.mcfg.block_length:
                self._key = self._executor.key
        if self.mcfg.block_length:
            for req in b.reqs:
                if req.max_new_tokens <= len(req.generated):
                    req.done = True   # prefill-only (scoring) request
            return again
        with self._phase("prefill/sample"):
            if b.picked:
                # orion: allow[host-sync] [nb] picks of a program that has ended: the prefill's ONE fetch
                firsts = np.asarray(jax.device_get(self._executor.picks))
            else:
                firsts = self._sample(logits, b.reqs)  # blocks on the fetch
            if self.mcfg.holds_expert_share:
                # The program has ended (its tokens are here): a 4-byte
                # copy, no wait.
                self.timing["prefill_held_expert_rows"] += int(
                    self._executor.held_rows)
                if self._executor.held_overflows is not None:
                    self.timing["prefill_held_bound_overflows"] += int(
                        self._executor.held_overflows)
            for i, req in enumerate(b.reqs):
                if req.done:
                    continue   # quarantined during mask build (_sample_masks)
                if req.max_new_tokens <= 0:
                    req.done = True   # prefill-only (scoring) request
                    continue
                first = int(firsts[i])
                self.last_token[req.slot] = first
                req.generated.append(first)
                self._maybe_finish(req, first)
        return again

    def _release_request(self, req: Request, n_cached: int) -> None:
        """Release a leaving request's pages. With prefix caching, the
        contiguous full pages of its context (``n_cached`` tokens hold
        valid KV) are donated to the radix tree first — on reap AND
        preempt, so a preempted request re-matches its own pages and
        re-prefills only what the cache lost. insert() retains what it
        keeps; the request then drops its own refs uniformly (shared
        pages decrement, private duplicates free)."""
        if self._pcache is not None and req.pages:
            n_full = min(n_cached // self.psz, len(req.pages))
            k = 0
            while k < n_full and req.pages[k] is not None:
                k += 1
            if k:
                self.prefix_stats.inserted_pages += self._pcache.insert(
                    req.context[: k * self.psz], req.pages[:k]
                )
        if req.prefix_node is not None:
            self._pcache.unlock(req.prefix_node)
            req.prefix_node = None
        self.alloc.free([p for p in req.pages if p is not None])
        req.pages = []
        req.n_prefix = 0
        # Host-resident pages are stale the moment the device side drops
        # (terminal exit, or a recompute-from-scratch preemption — the
        # preempt-to-host path never reaches here): release the slots.
        self._drop_host_pages(req)
        if self._spec is not None:
            # Adaptive draft-length state dies with the slot; a preempted
            # request restarts adaptation cold on re-admission.
            self._spec.drop(req.rid)

    def _teardown_slot(self, req: Request, n_cached: int) -> None:
        """The ONE slot-teardown path every exit shares: reap (completion,
        expiry, cancel), preemption and quarantine all release pages
        (donating the first ``n_cached`` tokens' full pages to the prefix
        cache via _release_request) and clear the slot's scheduler arrays
        HERE, so the pool invariant (assert_page_accounting) has a single
        code path to hold instead of three hand-rolled variants."""
        slot = req.slot
        self._release_request(req, n_cached)
        req.slot = None
        self.slots[slot] = None
        self.page_table[slot] = 0
        self.seq_lens[slot] = 0
        self.last_token[slot] = 0
        # The state row is the slot's: the next prefill into it writes it.
        self.fold_lens[slot] = 0

    def _preempt(self, req: Request) -> None:
        """Evict an active request, returning its pages; it re-enters at the
        head of the queue and resumes from its full context on re-prefill
        (cheaply, when the prefix cache kept its pages)."""
        log.info("preempting request %d (pool pressure)", req.rid)
        self.preemptions += 1
        cursor = int(self.seq_lens[req.slot])
        # Preempt-to-host (inference.long_context): for a long request
        # past the restore break-even, spill live pages to host slots and
        # resume at the cursor on re-admission — replacing the O(context)
        # recompute-from-scratch below.
        if self._preempt_to_host(req, cursor):
            return
        # Mid-prefill preemption: seq_lens is the chunk cursor, so exactly
        # the completed chunks' full pages donate to the prefix cache and
        # re-admission resumes from whatever the cache kept.
        self._teardown_slot(req, cursor)
        req.freed_until = 0
        req.prefill_pending = False
        req.prefill_done = 0
        self.waiting.appendleft(req)

    def _decodes(self, req: Optional[Request]) -> bool:
        """The next decode dispatch takes this slot: a live request that is
        not done, nor known to end at the first token of a prefill still in
        flight (which it will be the moment that token is here)."""
        return req is not None and not req.done and (
            self._burst is None or req.rid not in self._burst.ends)

    def _window_page_need(self, W: int) -> int:
        """Pages _grow_pages would have to allocate to cover ``W`` write
        positions ahead of every decoding slot."""
        need = 0
        for req in self.slots:
            if not self._decodes(req):
                continue
            pos = int(self.seq_lens[req.slot])
            last = min(pos + W - 1, self.icfg.max_seq_len - 1)
            n_need = min(last // self.psz + 1, self.pages_per_seq)
            need += max(n_need - len(req.pages), 0)
        return need

    def _grow_pages(self, window: Optional[int] = None) -> None:
        """Pre-provision every active slot with pages covering the whole
        upcoming decode window (the device writes up to W positions ahead of
        the host's view, including past mid-window EOS), preempting the
        lowest-priority youngest-admitted request under pool pressure
        (high classes and older requests keep making progress; no
        mid-decode crash). ``window`` overrides the
        span for verify steps (speculate_tokens+1 write positions per
        slot — always within _provision_window, which admission budgeted
        for)."""
        W = self.decode_window if window is None else window
        # Provisioning rank: high priority classes first, oldest first
        # within a class — so the preemption victim (the LAST ranked
        # request below) is the lowest class's youngest member, honoring
        # the submit() contract that low classes evict first. With
        # all-default priorities this is exactly the pre-priority
        # youngest-admitted order.
        by_age = sorted(
            (r for r in self.slots if self._decodes(r)),
            key=lambda r: (-r.priority, r.admit_seq),
        )
        # Batched pre-evict: compute the whole pass's page shortfall and
        # reclaim it in ONE eviction sweep, so a host-tier demotion pays
        # one batched d2h instead of one per page (the per-page evict(1)
        # below remains as the fallback for preemption-donated pages).
        # Tier-off this frees the identical LRU page set the lazy loop
        # would have, just up front.
        if self._pcache is not None:
            short = self._window_page_need(W) - self.alloc.free_pages
            if short > 0:
                self.prefix_stats.evicted_pages += self._pcache.evict(
                    short
                )
        for req in by_age:
            if req.slot is None:
                continue  # preempted earlier in this pass
            pos = int(self.seq_lens[req.slot])
            last = min(pos + W - 1, self.icfg.max_seq_len - 1)
            n_need = min(last // self.psz + 1, self.pages_per_seq)
            while req.slot is not None and len(req.pages) < n_need:
                while self.alloc.free_pages < 1:
                    # Reclaim cached pages before touching live requests:
                    # the prefix cache is headroom, not a tenant. (A
                    # preemption below may DONATE pages to the cache, which
                    # this branch then reclaims on the next iteration.)
                    if self._pcache is not None and self._pcache.evict(1):
                        self.prefix_stats.evicted_pages += 1
                        continue
                    victims = [
                        r for r in by_age
                        if r.slot is not None and r is not req
                        and r.priority <= req.priority
                    ]
                    if not victims:
                        if not any(
                            r.slot is not None and r is not req
                            for r in by_age
                        ):
                            raise MemoryError(
                                "KV pool too small for a single request; "
                                "raise inference.num_pages"
                            )
                        # Only HIGHER-priority tenants hold pages: a
                        # low-priority request must never grow at their
                        # expense — evict the requester itself instead.
                        self._preempt(req)
                        break
                    self._preempt(victims[-1])
                if req.slot is None:
                    break   # self-preempted above
                # Through _alloc_pages for the pool-fault injection point
                # (free_pages >= 1 here, so no second eviction pass runs).
                page = self._alloc_pages(1)[0]
                self.page_table[req.slot, len(req.pages)] = page
                req.pages.append(page)

    def _propose_drafts(
        self, cands: list[Request]
    ) -> Optional[dict[int, list[int]]]:
        """Host-side drafting pass (inference.speculative): an n-gram
        draft per candidate slot, keyed by slot. None when NOTHING was
        drafted — the caller falls back to the plain decode window, so a
        non-repetitive workload pays only the proposal scan. The draft
        length is capped per request by the adaptive state, the context
        window (write positions must stay below max_seq_len) and the
        request's remaining token budget (drafting past max_new_tokens
        is guaranteed rollback).

        Draft-density gate (inference.spec_min_draft_slots): a verify
        step costs every NON-drafting co-tenant its multi-step decode
        window (one host round-trip per token on that step), so when
        fewer than the threshold of live slots drafted — clamped to the
        live count, a fully-drafting batch always verifies — the step is
        gated back to the plain window (counted: spec_gated_steps). The
        discarded drafts were free to produce and are re-proposed next
        step if the repetition persists."""
        if not cands:
            return None
        self._constraint_forced = {}   # no forced prefixes on this path
        extra = (
            self._pcache.token_paths() if self._pcache is not None else ()
        )
        drafts: dict[int, list[int]] = {}
        n_drafted = 0
        for r in cands:
            if r.host_pages:
                # Long-context hold: part of this slot's KV is host-
                # resident (a page-in fault left residue), so a
                # multi-token verify would read pages the page-in pass
                # has not restored yet. Hold to a plain 1-token row this
                # step; the restore runs before dispatch and the slot
                # drafts again next step.
                drafts[r.slot] = None if self._tree else []
                continue
            pos = int(self.seq_lens[r.slot])
            limit = min(
                self.icfg.max_seq_len - 1 - pos,
                r.max_new_tokens - len(r.generated) - 1,
            )
            if limit <= 0:
                d = None if self._tree else []
            elif self._tree:
                # Token-tree drafting: up to spec_tree_width distinct
                # n-gram continuations merged into a trie (DraftTree).
                d = self._spec.propose_tree(r.rid, r.context, limit, extra)
            else:
                d = self._spec.propose(r.rid, r.context, limit, extra)
            drafts[r.slot] = d
            n_drafted += bool(d)
        if not n_drafted:
            return None
        if n_drafted < min(self.icfg.spec_min_draft_slots, len(cands)):
            self.spec_stats.gated_steps += 1
            return None
        return drafts

    def _propose_constrained_drafts(
        self, cands: list[Request]
    ) -> dict[int, Any]:
        """Drafting pass for a decode batch that contains constrained
        slots (these never ride the fused multi-token window: the next
        mask depends on the device-side sample, but along a KNOWN draft
        every per-position mask is host-precomputable — the verify
        layout). Never returns None: zero-draft constrained slots still
        verify at lens=1 — a masked single-token decode.

        Constrained slots draft their FSM FORCED RUN — single-choice
        states emit their only legal continuation, whose masked target
        probability is exactly 1.0, so acceptance is guaranteed under
        the standard rejection/greedy rule with NO new acceptance math
        (free tokens). Speculation composes: with inference.speculative
        the run extends with the n-gram continuation truncated to its
        FSM-legal prefix; in tree mode an ambiguous state after the run
        becomes a branch point — up to spec_tree_width legal tokens,
        each extended by its own forced tail, merged by
        spec_decode.build_tree. Unconstrained co-tenants draft exactly
        as _propose_drafts would (or not at all when speculation is
        off: their lens-1 rows ride the same verify dispatch)."""
        spec_on = self._spec is not None and not self._spec_disabled
        extra = (
            self._pcache.token_paths()
            if spec_on and self._pcache is not None else ()
        )
        tree = self._tree
        if tree:
            from orion_tpu.infer.spec_decode import build_tree
        drafts: dict[int, Any] = {}
        cs = self.constraint_stats
        self._constraint_forced = {}
        for r in cands:
            pos = int(self.seq_lens[r.slot])
            limit = min(
                self.icfg.max_seq_len - 1 - pos,
                r.max_new_tokens - len(r.generated) - 1,
                self.icfg.speculate_tokens,
            )
            c = r.constraint
            if c is None:
                if spec_on and limit > 0:
                    d = (
                        self._spec.propose_tree(
                            r.rid, r.context, limit, extra
                        ) if tree
                        else self._spec.propose(
                            r.rid, r.context, limit, extra
                        )
                    )
                else:
                    d = None if tree else []
                drafts[r.slot] = d
                continue
            if limit <= 0:
                drafts[r.slot] = None if tree else []
                continue
            forced = c.forced_run(limit)
            cs.forced_drafted += len(forced)
            self._constraint_forced[r.slot] = len(forced)
            end = c.walk(forced)
            if tree:
                chains = [forced] if forced else []
                if (
                    end >= 0 and len(forced) < limit
                    and c.mask_choices(end) > 1
                ):
                    # FSM branch point: the grammar itself names the
                    # candidate children — no n-gram statistics needed.
                    branches = c.branch_tokens(
                        self.icfg.spec_tree_width, end
                    )
                    if len(branches) > 1:
                        cs.branch_points += 1
                    bc = []
                    for b in branches:
                        nxt = c.peek(int(b), end)
                        tail = (
                            c.forced_run(limit - len(forced) - 1, nxt)
                            if nxt >= 0 else []
                        )
                        bc.append(forced + [int(b)] + tail)
                    chains = bc or chains
                t = build_tree(chains, limit) if chains else None
                drafts[r.slot] = t if t is not None and len(t) else None
            else:
                d = list(forced)
                if spec_on and end >= 0 and len(d) < limit:
                    cont = self._spec.propose(
                        r.rid, r.context + d, limit - len(d), extra
                    ) or []
                    for tok in cont:
                        nxt = c.peek(int(tok), end)
                        if nxt < 0:
                            break   # keep only the FSM-legal prefix
                        d.append(int(tok))
                        end = nxt
                drafts[r.slot] = d
        return drafts

    def _verify_masks(
        self,
        active: list[Request],
        tokens: np.ndarray,
        lens: np.ndarray,
        parents: Optional[np.ndarray] = None,
    ) -> Optional[np.ndarray]:
        """Per-position legal-token masks [B, W, V] for one verify
        dispatch: column j of a constrained slot carries the FSM mask
        AFTER consuming its (chain-prefix or tree-ancestor) draft path —
        column 0 is the current state (its token, the pending last
        token, already advanced the walk at emission time). Padding
        columns, unconstrained slots, and columns past an FSM-illegal
        draft token (unreachable: the masked parent logits give the
        illegal draft probability 0, so it is always rejected) stay
        all-True. None when no active slot is constrained — the
        ``legal_mask=None`` jit specialization keeps unconstrained
        verify dispatches byte-identical."""
        if not any(r.constraint is not None for r in active):
            return None
        B, W = tokens.shape
        m = np.ones((B, W, self.mcfg.vocab_size), bool)
        masked = 0
        for r in active:
            c = r.constraint
            if c is None:
                continue
            s = r.slot
            states = np.full(W, -1, np.int64)
            states[0] = c.state
            m[s, 0] = c.mask_row()
            for j in range(1, int(lens[s])):
                p = int(parents[s, j]) if parents is not None else j - 1
                ps = int(states[p])
                nxt = c.peek(int(tokens[s, j]), ps) if ps >= 0 else -1
                states[j] = nxt
                if nxt >= 0:
                    m[s, j] = c.mask_row(nxt)
            masked += 1
        self.constraint_stats.masked_steps += 1
        self.constraint_stats.masked_rows += masked
        return m

    def _build_verify_rows(
        self, reqs: list[Request], drafts: dict[int, list[int]]
    ) -> tuple[np.ndarray, np.ndarray]:
        """The [B, speculate_tokens+1] verify-row layout BOTH dispatch
        paths (_verify_all, _mixed_decode) feed the device and
        _accept_and_rollback later walks: column 0 the pending last
        token, columns 1..1+len(d) the drafts, ``lens`` the per-slot real
        width. Rows without a request stay (zeros, len 1) — masked onto
        scratch by the device side."""
        W = self.icfg.speculate_tokens + 1
        tokens = np.zeros((self.max_batch, W), np.int32)
        lens = np.ones(self.max_batch, np.int32)
        for r in reqs:
            d = drafts.get(r.slot, [])
            tokens[r.slot, 0] = self.last_token[r.slot]
            if d:
                tokens[r.slot, 1:1 + len(d)] = d
            lens[r.slot] = 1 + len(d)
        return tokens, lens

    def _build_verify_tree_rows(
        self, reqs: list[Request], drafts: dict[int, Any]
    ) -> tuple[np.ndarray, ...]:
        """Tree-mode verify layout (inference.spec_tree_width > 1): the
        chain row layout plus the flattened DraftTree structure arrays —
        per-column tree depths, parent columns, and packed ancestor mask
        words. Columns without a node (padding, and whole rows without a
        tree) carry CHAIN-shaped defaults (depth j, parent j-1, causal
        prefix words), so a chain-shaped tree feeds the device arrays a
        pure chain would — the degenerate case is bitwise today's
        verify."""
        W = self.icfg.speculate_tokens + 1
        B = self.max_batch
        steps = np.arange(W, dtype=np.int64)
        tokens = np.zeros((B, W), np.int32)
        lens = np.ones(B, np.int32)
        depths = np.tile(steps.astype(np.int32), (B, 1))
        parents = np.tile(
            np.maximum(steps - 1, 0).astype(np.int32), (B, 1)
        )
        words = np.tile(
            ((np.int64(1) << (steps + 1)) - 1).astype(np.int32), (B, 1)
        )
        for r in reqs:
            s = r.slot
            t = drafts.get(s)
            tokens[s, 0] = self.last_token[s]
            if t:
                n = len(t)
                tokens[s, 1:1 + n] = t.tokens
                lens[s] = 1 + n
                depths[s, :1 + n] = t.depths()
                parents[s, 1:1 + n] = t.parents
                words[s, :1 + n] = np.asarray(
                    t.mask_words(), np.int64
                ).astype(np.int32)
        return tokens, lens, depths, parents, words

    def _verify_all(self, drafts: dict[int, list[int]]) -> bool:
        """One verify dispatch for every live decode slot: K drafts + the
        pending last token per slot, scored in a single pass over the
        weights (runner.verify_step); accept the matched prefix + one
        bonus/correction token, then rewind the rejected tail."""
        self._grow_pages(self.icfg.speculate_tokens + 1)
        # Recompute AFTER provisioning: pool pressure may have preempted
        # a drafted slot (its drafts entry simply goes unread).
        active = [r for r in self.slots if r is not None and not r.done]
        if not active:
            self._reap()
            return False
        if not any(drafts.get(r.slot) for r in active) and not any(
            r.constraint is not None for r in active
        ):
            # Every drafted slot was preempted by the provisioning pass:
            # a verify dispatch would be all padding. Run the plain
            # window instead (it re-provisions to the decode window).
            # Constrained slots are exempt: even draftless they must
            # decode through the masked verify program (lens-1 rows).
            return self._decode_window_all()
        if self._tree:
            tokens, lens, depths, parents, words = (
                self._build_verify_tree_rows(active, drafts)
            )
            tree_kw = dict(
                depths=jnp.asarray(depths),
                parents=jnp.asarray(parents),
                tree_mask=jnp.asarray(words),
            )
            vmask = self._verify_masks(active, tokens, lens, parents)
        else:
            tokens, lens = self._build_verify_rows(active, drafts)
            tree_kw = {}
            vmask = self._verify_masks(active, tokens, lens)
        if vmask is not None:
            tree_kw["legal_mask"] = jnp.asarray(vmask)
        mask = np.zeros(self.max_batch, bool)
        for r in active:
            mask[r.slot] = True
        self._key, sub = jax.random.split(self._key)
        common = (
            self.params,
            self.cache,
            jnp.asarray(tokens),
            jnp.asarray(self.seq_lens),
            jnp.asarray(lens),
            jnp.asarray(self.page_table),
            jnp.asarray(mask),
            sub,
        )
        with self._phase("verify/run"):
            if all(
                r.temperature is None and r.top_k is None and r.top_p is None
                for r in active
            ):
                out = self._run_dispatch(
                    "verify", "verify_defaults", *common, **tree_kw
                )
            else:
                out = self._run_dispatch(
                    "verify", "verify", *common,
                    jnp.asarray(self.slot_temp),
                    jnp.asarray(self.slot_top_k),
                    jnp.asarray(self.slot_top_p),
                    **tree_kw,
                )
            if self._guard:
                acc, alt, ok, self.cache = out
                acc, alt, okh = jax.device_get((acc, alt, ok))  # orion: allow[host-sync] the verify step's ONE documented fetch
            else:
                acc, alt, self.cache = out
                acc, alt = jax.device_get((acc, alt))   # orion: allow[host-sync] the verify step's ONE documented fetch
                okh = None
        self.timing["slot_steps"] += len(active)
        self.timing["decode_slot_steps"] += len(active)
        if okh is not None:
            for req in active:
                if not okh[req.slot]:
                    self._quarantine(req, "nan")
            active = [r for r in active if r.slot is not None]
        if self._tree:
            self._accept_and_rollback_tree(active, tokens, lens, drafts,
                                           acc, alt)
        else:
            self._accept_and_rollback(active, tokens, lens, acc, alt)
        self._reap()
        return True

    def _accept_and_rollback(
        self,
        active: list[Request],
        tokens: np.ndarray,
        lens: np.ndarray,
        acc: np.ndarray,
        alt: np.ndarray,
    ) -> None:
        """Walk each slot's verify verdicts: emit the accepted draft
        prefix plus alt at the first rejection (the correction) or at the
        row's end (the bonus), then rewind — cursor stays at the last
        emitted token (it only ever advanced by emissions) and pages
        covering only rejected positions go back to the pool
        (kv_cache.rollback_pages), leaving exactly the page footprint a
        non-speculative window=1 step would have left. Rejected KV beyond
        the cursor is dead by the seq_lens masking invariant, the same
        way decode-window overshoot is."""
        st = self.spec_stats
        st.verify_steps += 1
        st.verify_slot_steps += len(active)
        for r in active:
            s = r.slot
            k = int(lens[s]) - 1
            a = 0
            while a < k and acc[s, a]:
                a += 1
            emit = [int(t) for t in tokens[s, 1:1 + a]] + [int(alt[s, a])]
            n_emit = 0
            for tok in emit:
                if r.done:
                    break
                self.seq_lens[s] += 1
                self.last_token[s] = tok
                r.generated.append(tok)
                n_emit += 1
                self._maybe_finish(r, tok)
            kept = min(n_emit, a)       # draft tokens that reached the stream
            st.drafted += k
            st.accepted += kept
            st.rolled_back += k - kept
            st.emitted += n_emit
            fr = self._constraint_forced.get(s, 0)
            if fr:
                self.constraint_stats.forced_accepted += min(kept, fr)
            if self._spec is not None:
                # Constrained-only engines verify without a proposer —
                # there is no adaptive draft length to steer.
                self._spec.state(r.rid).update(
                    k, kept, self.icfg.speculate_tokens
                )
            if not r.done:
                # Finished slots skip this: _reap releases everything and
                # donates only full pages below the (rewound) cursor.
                self._rollback_slot(r)

    def _rollback_slot(self, req: Request) -> None:
        """Release the pages a verify step provisioned beyond the
        accepted cursor (speculative rollback, kv_cache.rollback_pages)."""
        n_keep = (int(self.seq_lens[req.slot]) - 1) // self.psz + 1
        if len(req.pages) > n_keep:
            rollback_pages(self.alloc, req.pages, n_keep)
            self.page_table[req.slot, n_keep:] = 0

    def _plan_emission(self, req: Request, emit: list[int]) -> int:
        """How many of ``emit``'s tokens this request will actually
        accept — a side-effect-free mirror of the emission loop's
        ``_maybe_finish`` stop conditions, so tree acceptance can size
        the KV compaction BEFORE any engine state mutates (a failed
        compaction dispatch then fails the step with nothing emitted,
        the same containment contract every other dispatch has)."""
        n = 0
        gen = len(req.generated)
        pos = int(self.seq_lens[req.slot])
        for tok in emit:
            n += 1
            gen += 1
            pos += 1
            if (
                (self.eos_id is not None and tok == self.eos_id)
                or pos >= self.icfg.max_seq_len
                or gen >= req.max_new_tokens
            ):
                break
        return n

    def _accept_and_rollback_tree(
        self,
        active: list[Request],
        tokens: np.ndarray,
        lens: np.ndarray,
        drafts: dict[int, Any],
        acc: np.ndarray,
        alt: np.ndarray,
    ) -> None:
        """Tree-mode acceptance: walk each slot's DraftTree root-down,
        descending into the first accepted child in sibling (insertion-
        priority) order — greedy rows can match at most one sibling
        (tokens are distinct), sampled rows' verdicts are the
        sequential multi-branch rejection scheme of
        ``sampling.spec_verify_sample_tree`` — and emit the verified
        path plus the final node's bonus/correction token.

        An accepted path that is not the tree's primary chain lives at
        non-contiguous verify columns; its KV is MOVED into
        cursor-contiguous slots in one batched compaction dispatch
        (kv_cache.compact_draft_kv) before anything else runs — the
        primary-chain case (and all chain-shaped traffic) needs no
        dispatch at all. Then the cursor advances by emissions exactly
        as the chain walk's does, and rollback releases every page
        covering only losing-branch positions, restoring the window=1
        footprint."""
        st = self.spec_stats
        st.verify_steps += 1
        st.verify_slot_steps += len(active)
        W = self.icfg.speculate_tokens + 1
        src = np.tile(np.arange(W, dtype=np.int32), (self.max_batch, 1))
        plans: list[tuple[Request, Any, list[int], list[int]]] = []
        moves = 0
        for r in active:
            s = r.slot
            t = drafts.get(s) or None
            path: list[int] = []
            cur = 0
            if t is not None:
                children = t.children()
                while True:
                    nxt = next(
                        (c for c in children[cur] if acc[s, c]), None
                    )
                    if nxt is None:
                        break
                    path.append(nxt)
                    cur = nxt
            emit = [int(tokens[s, c]) for c in path] + [int(alt[s, cur])]
            plans.append((r, t, path, emit))
            kept = min(self._plan_emission(r, emit), len(path))
            off = [i for i in range(kept) if path[i] != i + 1]
            if off:
                src[s, 1:1 + kept] = path[:kept]
                moves += len(off)
        if moves:
            try:
                with self._phase("compact"):
                    self.cache = self._compact(
                        self.cache,
                        jnp.asarray(self.page_table),
                        jnp.asarray(self.seq_lens),
                        jnp.asarray(src),
                    )
                    # orion: allow[host-sync] compaction must surface device errors BEFORE any token is emitted
                    jax.block_until_ready(self.cache)
            # orion: allow[fault-except] dispatch envelope: ANY compaction failure becomes a failed step, never an emission
            except Exception as e:
                self.robust.dispatch_faults += 1
                self._flight_note(
                    "dispatch_fault", path="compact",
                    error=f"{type(e).__name__}: {e}",
                )
                # A broken compaction program is a speculation-path
                # fault: count it toward the auto-disable ladder so a
                # persistent failure turns speculation off instead of
                # escalating to the max_step_faults re-raise.
                self._note_spec_fault(e)
                raise DispatchFault(
                    "compact", f"{type(e).__name__}: {e}"
                ) from e
            st.compactions += 1
            st.compacted_tokens += moves
        for r, t, path, emit in plans:
            s = r.slot
            n_emit = 0
            for tok in emit:
                if r.done:
                    break
                self.seq_lens[s] += 1
                self.last_token[s] = tok
                r.generated.append(tok)
                n_emit += 1
                self._maybe_finish(r, tok)
            kept = min(n_emit, len(path))
            k = int(lens[s]) - 1
            depth = t.max_depth if t is not None else 0
            st.drafted += k
            st.accepted += kept
            st.rolled_back += k - kept
            st.emitted += n_emit
            st.tree_nodes += k
            st.tree_branch_nodes += max(k - depth, 0)
            fr = self._constraint_forced.get(s, 0)
            if fr:
                self.constraint_stats.forced_accepted += min(kept, fr)
            if self._spec is not None:
                # The adaptive controller steers DEPTH (the chain-
                # equivalent draft length): drafted = the tree's primary
                # depth, accepted = the verified path length. Width fills
                # whatever budget the depth leaves
                # (spec_decode.NgramProposer.propose_tree). Constrained-
                # only engines verify without a proposer.
                self._spec.state(r.rid).update(
                    depth, kept, self.icfg.speculate_tokens
                )
            if not r.done:
                self._rollback_slot(r)

    def _decode_all(self) -> bool:
        if self.mcfg.block_length:
            return self._denoise_all()
        if self._burst is not None and (
            self._long
            or (self._spec is not None and not self._spec_disabled)
            or (self.constrained and any(
                r is not None and r.constraint is not None
                for r in self.slots))
        ):
            # A step that pages in, drafts or verifies reads the first
            # tokens on the host: today's order.
            self._finish_prefill()
        with self._phase("decode/build"):
            self._roll_window()
            live = [r for r in self.slots if r is not None and not r.done]
            if self._long:
                # Host-resident residue on a decode slot (a page-in fault
                # retrying, per the keep-host-refs envelope): restore
                # before any dispatch reads the pages.
                for r in live:
                    if r.host_pages:
                        self._page_in_request(r)
            drafts = None
            if self.constrained and any(
                r.constraint is not None for r in live
            ):
                # Constrained slots decode through the masked verify path
                # unconditionally (the fused window cannot carry FSM
                # masks); forced runs make the step multi-token whenever
                # the grammar allows, and unconstrained co-tenants draft
                # normally.
                drafts = self._propose_constrained_drafts(live)
            elif self._spec is not None and not self._spec_disabled:
                drafts = self._propose_drafts(live)
            window = self._decode_build_window() if drafts is None else None
        if drafts is not None:
            return self._verify_all(drafts)
        return self._decode_run_window(window)

    def _denoise_all(self) -> bool:
        """One block for every live slot of a model that generates by
        diffusion over blocks (``runner.denoise_block``): provision a block
        of pages ahead, launch the block program behind the step's prefill
        where that is in flight (it needs nothing of it but the cache),
        fetch the ``[B, L]`` tokens and emit each slot's: the positions
        after a prompt's tail, up to ``max_new_tokens`` or an EOS. The
        cursor moves by whole blocks."""
        L, S = self.mcfg.block_length, self.icfg.denoising_steps
        with self._phase("decode/build"):
            if (self._burst is not None
                    and self._window_page_need(L) > self.alloc.free_pages):
                self._finish_prefill()   # as _decode_build_window
            self._grow_pages()
            mask = np.array([self._decodes(r) for r in self.slots], bool)
            active = [r for r, m in zip(self.slots, mask) if m]
            if not active:
                if self._burst is not None:
                    self._finish_prefill()
                self._reap()
                return False
            tokens = np.zeros((self.max_batch, L), np.int32)
            tails = np.zeros(self.max_batch, np.int32)
            for req in active:
                # Not yet cached: a prompt's tail, at a first block alone
                # (past it the cursor is the context's length).
                cursor = int(self.seq_lens[req.slot])
                if cursor < len(req.prompt) + len(req.generated):
                    tail = req.context[cursor:]
                    tokens[req.slot, :len(tail)] = tail
                    tails[req.slot] = len(tail)
            args = (
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(tails), jnp.asarray(self.seq_lens.copy()),
                jnp.asarray(self.page_table.copy()), jnp.asarray(mask),
                self._key,
            )
            name = "denoise_defaults"
            if not all(
                r.temperature is None and r.top_k is None and r.top_p is None
                for r in active
            ):
                name, args = "denoise", args + (
                    jnp.asarray(self.slot_temp),
                    jnp.asarray(self.slot_top_k),
                    jnp.asarray(self.slot_top_p),
                )
            self.timing["block_slot_forwards"] += (S + 1) * len(active)
            self.timing["block_kv_positions_read"] += (S + 1) * (
                int(self.seq_lens[mask].sum()) + L * len(active))
            self.timing["block_head_rows"] += len(active) * sum(
                undecided_bounds(L, S))
        with self._phase("decode/run"):
            out = self._executor.run("decode", name, *args)
        again = False
        try:
            if self._burst is not None:
                self.timing["chained_steps"] += 1
                again = self._finish_prefill()
            if not again:
                with self._phase("decode/run"):
                    out = self._executor.wait("decode", name, out, *args)
        except DispatchFault:
            self.cache = out[-1]    # as _decode_run_window
            raise
        if again:
            return self._denoise_all()
        with self._phase("decode/fetch"):
            *out, self._key, self.cache = out
            # orion: allow[host-sync] [B, L] tokens, the forward that decided each (and ok flags): the block program's ONE fetch
            toks, at, *ok = jax.device_get(out)
        with self._phase("decode/emit"):
            # Forward s fed as the mask token what forward s or a later one
            # decided.
            self.timing["block_positions_undecided_fed"] += int(sum(
                (at[mask] >= s).sum() for s in range(S)))
            self.timing["block_head_rows_decided"] += int(
                (at[mask] >= 0).sum())
            for req in active:
                if ok and not ok[0][req.slot]:
                    self._quarantine(req, "nan")
                    continue
                slot, tail = req.slot, int(tails[req.slot])
                for tok in toks[slot, tail:].tolist():
                    if req.done:
                        break   # beyond max_new_tokens or an EOS: discarded
                    self.last_token[slot] = tok
                    req.generated.append(tok)
                    self.timing["tokens_committed"] += 1
                    self._maybe_finish(req, tok)
                # The whole block is cached; no room for another ends it.
                self.seq_lens[slot] += L
                if int(self.seq_lens[slot]) >= self.icfg.max_seq_len:
                    req.done = True
            self._reap()
        return True

    def _decode_window_all(self) -> bool:
        """The plain fused decode window over all live slots, for the
        verify path's fallback when preemption strips every drafted slot
        (_decode_all builds the window inside its own build phase)."""
        with self._phase("decode/build"):
            window = self._decode_build_window()
        return self._decode_run_window(window)

    def _decode_build_window(self):
        """Provision pages and upload the inputs of one fused decode
        window; None when no slot is live. Runs inside ``decode/build``."""
        if self._chunk is not None:
            self._fold_tails()
        W = self.decode_window
        if (
            self._burst is not None
            and self._window_page_need(W) > self.alloc.free_pages
        ):
            # _grow_pages would have to reclaim pages (evict, spill or
            # preempt), perhaps from a request whose first token is not on
            # the host yet: wait for the prefill first, then as ever.
            self._finish_prefill()
        self._grow_pages()
        mask = np.array([self._decodes(r) for r in self.slots], bool)
        active = [r for r, m in zip(self.slots, mask) if m]
        if not active:
            return None
        common = (
            self.params,
            self.cache,
            # Behind a prefill in flight: the array it returned, the picks
            # at their slots, which never leaves the device.
            self._executor.last_token if self._burst is not None
            else jnp.asarray(self.last_token.copy()),
            # Copies: the first tokens are emitted while the window runs.
            jnp.asarray(self.seq_lens.copy()),
            jnp.asarray(self.page_table.copy()),
            jnp.asarray(mask),
            self._key,
        )
        # Token step j of the window reads seq_len + j cached positions
        # per live slot (the sliding window's last at most).
        if self.mcfg.sliding_window is None:
            kv = (W * sum(self.seq_lens[mask].tolist())
                  + len(active) * (W * (W - 1) // 2))
        else:
            kv = int(np.minimum(
                self.seq_lens[mask][:, None] + np.arange(W),
                self.mcfg.sliding_window,
            ).sum())
        if self._chunk is not None:
            # The state and the tail are what this model's kernel reads.
            lens = self.seq_lens[mask].astype(np.int64)
            folded = self.fold_lens[mask]
            L = self.mcfg.n_layers
            self.timing["decode_state_slot_layers"] += L * W * len(active)
            self.timing["decode_state_empty_slot_layers"] += (
                L * W * int((folded == 0).sum()))
            self.timing["decode_tail_token_layers"] += L * int(
                (lens - folded).sum() * W + len(active) * (W * (W + 1) // 2))
            return active, W, common
        if self.mcfg.resumes_prefill:
            lens = self.seq_lens[mask].astype(np.int64)
            steps = lens[:, None] + np.arange(W)    # the new token's position
            per = self.mcfg.n_layers_of("sparse") * self.mcfg.n_kv_heads
            t = self.timing
            t["decode_sparse_visible_keys"] += per * int(
                self._visible_keys(steps).sum())
            t["decode_sparse_context_keys"] += per * int((steps + 1).sum())
            L = self.mcfg.n_layers_of("lightning")
            t["decode_lightning_slot_layers"] += L * W * len(active)
            state = self.cache[LIGHTNING_STATE]
            t["lightning_live_state_bytes"] += len(active) * L * (
                math.prod(state.shape[2:]) * state.dtype.itemsize)
            held = sum(p is not None for r in active for p in r.pages)
            t["sala_live_page_bytes"] += held * sum(
                a.size * a.dtype.itemsize for n, a in self.cache.items()
                if n != LIGHTNING_STATE) // self.icfg.num_pages
            t["sala_live_tokens"] += int(lens.sum())
            return active, W, common
        if self.mcfg.has_kda:
            L = self.mcfg.n_layers_of("kda")
            self.timing["decode_kda_slot_layers"] += L * W * len(active)
            self.timing["kda_live_state_bytes"] += len(active) * sum(
                math.prod(self.cache[name].shape[2:])
                * self.cache[name].dtype.itemsize * L
                for name in ("kda_state", "kda_conv"))
        if self.mcfg.has_latent:
            # The rows the latent decode kernel reads, and what the pool
            # holds for the live slots at this window: whole pages (the
            # ones provisioned for the window ahead among them) of the
            # leaf's own row width, padding included.
            L = self.mcfg.n_layers_of("latent")
            self.timing["decode_latent_token_layers"] += L * kv
            held = sum(p is not None for r in active for p in r.pages)
            self.timing["latent_live_page_bytes"] += (
                held * host_page_bytes(self.cache, L))
            self.timing["latent_live_tokens"] += int(
                self.seq_lens[mask].sum())
            return active, W, common
        self.timing["decode_kv_tokens"] += kv
        self._count_kv_by_layer_kind(self.seq_lens[mask].astype(np.int64), W)
        return active, W, common

    def _visible_keys(self, pos: np.ndarray) -> np.ndarray:
        """Keys a sparse layer's query at position ``pos`` attends in one
        K/V head: every key up to it while its causal blocks are ``topk`` or
        fewer, else ``topk - 1`` whole blocks and its own block up to it."""
        sp = self.mcfg.sparse
        return np.minimum(pos + 1, (sp.topk - 1) * sp.block
                          + pos % sp.block + 1)

    def _count_resumed_prefill(self, start: int, n: int) -> None:
        """The prefill counters of a model of sparse and lightning layers
        for ``n`` real positions from ``start`` on."""
        pos = np.arange(start, start + n, dtype=np.int64)
        sp, t = self.mcfg.sparse, self.timing
        layers = self.mcfg.n_layers_of("sparse")
        t["prefill_sparse_visible_pairs"] += (
            layers * self.mcfg.n_heads * int(self._visible_keys(pos).sum()))
        causal = pos // sp.block + 1
        per = layers * self.mcfg.n_kv_heads
        t["prefill_sparse_selected_pages"] += per * int(
            np.minimum(causal, sp.topk).sum())
        t["prefill_sparse_shared_pages"] += per * int(np.where(
            causal <= sp.topk, causal,
            sp.init_blocks + sp.local_blocks).sum())
        t["prefill_lightning_token_layers"] += (
            self.mcfg.n_layers_of("lightning") * n)

    def _prefill_earlier_chunks(self, req: Request) -> int:
        """A model whose prefill resumes (``ModelConfig.resumes_prefill``):
        launch every chunk of ``inference.prefill_chunk_tokens`` positions
        of ``req``'s prompt but the last, back to back and waited for by
        nobody (each takes the cache the one before hands on; the last
        chunk's wait is theirs). Each reads the history through the slot's
        page-table row and the slot's state row. Returns the positions
        done, a multiple of the chunk: where the last chunk starts."""
        C, n = self.icfg.prefill_chunk_tokens, len(req.context)
        slot, done = req.slot, 0
        if n <= C:
            return 0
        row = jnp.asarray(self.page_table[slot:slot + 1].copy())
        one = lambda value: jnp.asarray(np.full((1,), value, np.int32))
        state_rows, nowhere, length = one(slot + 1), one(self.max_batch), one(C)
        while n - done > C:
            first = done // self.psz
            pages = np.zeros((1, C // self.psz), np.int32)
            pages[0] = [0 if p is None else p
                        for p in req.pages[first:first + C // self.psz]]
            tokens = np.zeros((1, C), np.int32)
            tokens[0] = req.context[done:done + C]
            with self._phase("prefill/run"):
                _, self.cache = self._executor.run(
                    "prefill", "prefill", self.params, self.cache,
                    jnp.asarray(tokens), length, jnp.asarray(pages),
                    one(done), row, state_rows, nowhere,
                    jnp.asarray(self.last_token.copy()), self._key)
            self.timing["prefill_dispatches"] += 1
            self.timing["prefill_tokens"] += C
            self._count_resumed_prefill(done, C)
            done += C
        return done

    def _fold_tails(self) -> None:
        """A power-retention model, at the start of a decode window: every
        live slot whose tail holds a complete chunk has it folded into its
        state row (``runner.fold_step``, one dispatch a slot: one shape),
        and the chunk's pages go back to the pool. The one place besides
        prefill where a state changes."""
        C = self._chunk
        for req in self.slots:
            if req is None or req.done or req.slot is None:
                continue
            slot = req.slot
            while int(self.seq_lens[slot]) - int(self.fold_lens[slot]) >= C:
                with self._phase("fold/run"):
                    # Launched, not waited for: a fold chains on the cache
                    # between the programs before and behind it, and the
                    # window's wait is its wait.
                    self.cache = self._executor.run(
                        "fold", "fold", self.cache,
                        np.int32(slot),     # (jnp.int32 is a program)
                        # a copy: _roll_window writes the row below
                        jnp.asarray(self.page_table[slot].copy()))
                self.fold_lens[slot] += C
                self.timing["folds"] += 1
        self._roll_window()

    def _count_kv_by_layer_kind(self, lens: np.ndarray, W: int) -> None:
        """decode_kv_token_layers, decode_kv_pages_read,
        kv_live_page_layers and kv_dead_window_page_layers of one decode
        window over live slots of lengths ``lens`` (host arithmetic, no
        device value read)."""
        steps = lens[:, None] + np.arange(W)    # the new token's position
        for window, n in self._layers_by_window.items():
            read = steps if window is None else np.minimum(steps, window)
            self.timing["decode_kv_token_layers"] += n * int(read.sum())
            if self.mcfg.has_window_ring:
                self.timing["decode_kv_token_layers_" + (
                    "full" if window is None else "ring")] += n * int(
                        read.sum())
            first = 0 if window is None else np.maximum(steps - window + 1, 0)
            self.timing["decode_kv_pages_read"] += n * int(
                (steps // self.psz - first // self.psz + 1).sum())
        if self.mcfg.has_window_ring:
            self._count_split_cache(lens)
        if self._window_layers:
            # A page is dead for a window layer when the query at position
            # len reads none of it: its last position is under len - window.
            dead = np.maximum(
                lens - self.mcfg.sliding_window + 1, 0) // self.psz
            self.timing["kv_live_page_layers"] += self.mcfg.n_layers * int(
                (-(-lens // self.psz)).sum())
            self.timing["kv_dead_window_page_layers"] += (
                self._window_layers * int(dead.sum()))

    def _count_split_cache(self, lens: np.ndarray) -> None:
        """The kv_full_* / kv_window_* counters of one decode window over
        live slots of lengths ``lens``, from the leaves' own shapes."""
        def page_bytes(k, v, layers):   # one page of K and V in ``layers``
            return layers * sum(
                math.prod(self.cache[n].shape[-3:])
                * self.cache[n].dtype.itemsize for n in (k, v))

        ring = self.cache[RING_K].shape
        full = page_bytes("k", "v", self.mcfg.n_paged_layers)
        pages = sum(p is not None for r in self.slots
                    if self._decodes(r) for p in r.pages)
        held = int(np.minimum(lens, ring[2] * self.psz).sum())
        t = self.timing
        t["kv_full_positions_live"] += int(lens.sum())
        t["kv_full_bytes_live"] += int(lens.sum()) * full // self.psz
        t["kv_full_page_bytes_held"] += pages * full
        t["kv_window_bytes_held"] += held * page_bytes(
            RING_K, RING_V, ring[0]) // self.psz

    def _decode_run_window(self, window) -> bool:
        """Launch a built decode window, behind the step's prefill where
        that is still in flight (its first tokens are then brought to the
        host and emitted while the window runs), wait for it, fetch its
        ``[W, B]`` tokens and emit them (the non-speculative step body)."""
        if window is None:
            if self._burst is not None:
                self._finish_prefill()
            self._reap()
            return False
        active, W, common = window
        name, args = "decode_defaults", common
        if not all(
            r.temperature is None and r.top_k is None and r.top_p is None
            for r in active
        ):
            name, args = "decode", common + (
                jnp.asarray(self.slot_temp),
                jnp.asarray(self.slot_top_k),
                jnp.asarray(self.slot_top_p),
            )
        with self._phase("decode/run"):
            out = self._executor.run("decode", name, *args, window=W)
        again = False
        try:
            if self._burst is not None:
                self.timing["chained_steps"] += 1
                again = self._finish_prefill()
            if not again:
                with self._phase("decode/run"):
                    out = self._executor.wait(
                        "decode", name, out, *args, window=W)
        except DispatchFault:
            # The window's tokens are lost with the step (behind a failed
            # prefill they are void; else the slots decode these positions
            # again); the cache it hands back is the one that is live.
            self.cache = out[-1]
            raise
        if again:
            # The prefill's results are its fallback's: the window above
            # ran on the failed launch's. Build and run it anew.
            with self._phase("decode/build"):
                window = self._decode_build_window()
            return self._decode_run_window(window)
        with self._phase("decode/fetch"):
            # The program's key' sits before its cache: the stream goes on
            # from it once the window is known to have run.
            *out, self._key, self.cache = out
            if self._guard:
                toks, ok = out
                tokens, okh = jax.device_get((toks, ok))   # orion: allow[host-sync] the decode window's ONE documented fetch
                tokens = np.asarray(tokens)
            else:
                toks, = out
                tokens = np.asarray(jax.device_get(toks))  # orion: allow[host-sync] [W, B] — the decode window's ONE documented fetch
                okh = None
        with self._phase("decode/emit"):
            self.timing["slot_steps"] += W * len(active)
            self.timing["decode_slot_steps"] += W * len(active)
            if okh is not None:
                for req in active:
                    if not okh[req.slot]:
                        # Non-finite logits in this slot's window: the
                        # whole window's tokens for it are suspect — drop
                        # them all and quarantine (neighbors' tokens are
                        # unaffected; no slot ever reads another's pages).
                        self._quarantine(req, "nan")
                active = [r for r in active if r.slot is not None]
            for j in range(W):
                for req in active:
                    if req.done:
                        # Finished mid-window: the device still decoded
                        # this slot; the discarded overshoot is the
                        # tunable waste.
                        self.timing["wasted_steps"] += 1
                        continue
                    tok = int(tokens[j, req.slot])
                    self.seq_lens[req.slot] += 1
                    self.last_token[req.slot] = tok
                    req.generated.append(tok)
                    self._maybe_finish(req, tok)
            self._reap()
        return True

    def _mixed_decode(self) -> bool:
        """One UNIFIED mixed prefill+decode step (inference.chunked_prefill,
        runner.mixed_step): a single-token decode for every live slot plus
        up to prefill_chunk_tokens of prompt tail, in ONE dispatch — the
        stall any in-flight decode observes under a prompt burst is
        bounded by the chunk budget, never the whole quadratic prompt.
        Returns True iff any decode slot advanced.

        Speculation composes here (runner.mixed_verify_step): decode-phase
        slots draft and verify up to speculate_tokens per mixed step while
        prompt-phase slots skip drafting — their prompts ARE the chunk
        rows — so a prompt burst and a speculation streak share one
        dispatch."""
        self._roll_window()
        drafts = None
        dec_cands = [
            r for r in self.slots
            if r is not None and not r.done and not r.prefill_pending
        ]
        if self.constrained and any(
            r.constraint is not None for r in dec_cands
        ):
            # Constrained decode-phase slots force the mixed VERIFY
            # program (masked rows; forced runs as free drafts), exactly
            # as _decode_all forces the pure verify path.
            drafts = self._propose_constrained_drafts(dec_cands)
        elif self._spec is not None and not self._spec_disabled:
            drafts = self._propose_drafts(dec_cands)
        if self._long:
            # Decode-phase host residue (a failed page-in retrying):
            # restore AFTER drafting — _propose_drafts held non-resident
            # slots to a 1-token row, so this pass never races a
            # multi-token verify against pages it is still copying.
            for r in dec_cands:
                if r.host_pages:
                    self._page_in_request(r)
        self._grow_pages(
            self.icfg.speculate_tokens + 1 if drafts is not None else None
        )
        psz = self.psz
        S = self.icfg.prefill_chunk_tokens
        # Chunk assembly: pending prompts in admission order (head-of-line
        # fairness matches unchunked admission), each contributing its
        # next page-aligned chunk until the token budget is spent. The
        # final chunk of a prompt may be shorter than a page; mid-prompt
        # chunks end page-aligned so the NEXT chunk resumes page-aligned
        # (the prefix-gather contract of runner.prefill_step).
        pending = sorted(
            (
                r for r in self.slots
                if r is not None and not r.done and r.prefill_pending
            ),
            key=lambda r: r.admit_seq,
        )
        budget = S
        chunks: list[tuple[Request, int]] = []
        for r in pending:
            if budget < 1:
                break
            rem = len(r.context) - r.prefill_done
            k = min(rem, budget)
            if k < rem:
                k = k // psz * psz
                if k == 0:
                    break
            budget -= k
            chunks.append((r, k))
        if self._long:
            # Long-context page passes, restore-then-provision per chunk
            # getter: host-resident pages this chunk's window reads come
            # back in ONE batched h2d (inference.request_resident_pages
            # demoted them after the previous chunk), then the lazy
            # admission path materializes the chunk's own pages (over-pool
            # admission allocated NONE up front). Either raise
            # (DispatchFault / MemoryError) fails the step with both
            # tiers consistent.
            for r, k in chunks:
                if r.host_pages:
                    self._page_in_request(r)
                try:
                    self._provision_chunk_pages(r, k)
                except MemoryError:
                    # Chunk provisioning has no grow-time preemption
                    # valve (_grow_pages only serves decode spans), so
                    # pool exhaustion HERE would fail the step forever.
                    # Park THIS request instead — preempt-to-host past
                    # the break-even, plain preempt below it — and let
                    # co-tenants drain the pressure.
                    self.robust.pool_faults += 1
                    self._preempt(r)
            chunks = [(r, k) for r, k in chunks if r.slot is not None]
        nb = 1 << max(len(chunks) - 1, 0).bit_length()
        n_pages = S // psz
        tokens = np.zeros((nb, S), np.int32)
        lengths = np.ones(nb, np.int32)          # pad rows: length 1
        pages = np.zeros((nb, n_pages), np.int32)  # pad rows: scratch 0
        max_pre = max((r.prefill_done // psz for r, _ in chunks), default=0)
        p_pre = 1 << (max_pre - 1).bit_length() if max_pre > 0 else 0
        pre_lens = np.zeros(nb, np.int32)
        pre_pages = np.zeros((nb, p_pre), np.int32)
        for i, (r, k) in enumerate(chunks):
            start = r.prefill_done
            tokens[i, :k] = r.context[start:start + k]
            lengths[i] = k
            pre_lens[i] = start
            npre = start // psz
            if npre:
                # Rolled-dead (behind-window) pages point at scratch 0 —
                # behind every chunk query's window, never attended.
                pre_pages[i, :npre] = [
                    0 if p is None else p for p in r.pages[:npre]
                ]
            pg = r.pages[npre:npre - (-k // psz)]
            pages[i, :len(pg)] = [0 if p is None else p for p in pg]

        # Decode side: mid-prefill slots mask onto scratch page 0, so the
        # decode sub-body's fused write (which fires for every slot) can
        # never clobber a page their chunks are filling this very step.
        d_pt = self.page_table
        if pending:
            d_pt = self.page_table.copy()
            for r in pending:
                if r.slot is not None:   # provisioning may have preempted
                    d_pt[r.slot] = 0
        dec = [
            r for r in self.slots
            if r is not None and not r.done and not r.prefill_pending
        ]
        if (
            drafts is not None
            and not any(drafts.get(r.slot) for r in dec)
            and not any(r.constraint is not None for r in dec)
        ):
            # The drafted slot(s) were preempted by this step's page
            # provisioning: nothing left to verify — take the plain
            # 1-token mixed step instead of a padding-only verify.
            # Constrained decode slots are exempt: draftless or not,
            # they must ride the masked verify rows.
            drafts = None
        mask = np.array(
            [
                r is not None and not r.done and not r.prefill_pending
                for r in self.slots
            ],
            bool,
        )
        if dec:
            self._key, sub = jax.random.split(self._key)
            # Same key derivation as _decode_all's W-window (split(sub, W),
            # here W=1): at equal engine PRNG state a mixed decode step
            # samples with exactly the key a decode_window=1 step would.
            sub = jax.random.split(sub, 1)[0]
        else:
            # No live decode: do NOT advance the engine PRNG stream —
            # sampled chunked-vs-unchunked equivalence needs one split
            # per SAMPLING event, not per dispatch.
            sub = self._null_key
        chunk_args = (
            jnp.asarray(tokens),
            jnp.asarray(lengths),
            jnp.asarray(pages),
            jnp.asarray(pre_lens),
            jnp.asarray(pre_pages),
        )
        defaults = all(
            r.temperature is None and r.top_k is None and r.top_p is None
            for r in dec
        )
        override_args = (
            jnp.asarray(self.slot_temp),
            jnp.asarray(self.slot_top_k),
            jnp.asarray(self.slot_top_p),
        )
        if drafts is not None:
            # Speculative mixed step: verify rows replace the 1-token
            # decode rows (runner.mixed_verify_step); prompt-phase slots
            # are plain chunk rows, exactly as without speculation.
            if self._tree:
                vtok, vlens, vdepths, vparents, vwords = (
                    self._build_verify_tree_rows(dec, drafts)
                )
                tree_kw = dict(
                    depths=jnp.asarray(vdepths),
                    parents=jnp.asarray(vparents),
                    tree_mask=jnp.asarray(vwords),
                )
                vmask = self._verify_masks(dec, vtok, vlens, vparents)
            else:
                vtok, vlens = self._build_verify_rows(dec, drafts)
                tree_kw = {}
                vmask = self._verify_masks(dec, vtok, vlens)
            if vmask is not None:
                tree_kw["legal_mask"] = jnp.asarray(vmask)
            common = (
                self.params,
                self.cache,
                jnp.asarray(vtok),
                jnp.asarray(self.seq_lens),
                jnp.asarray(vlens),
                jnp.asarray(d_pt),
                jnp.asarray(mask),
                sub,
            ) + chunk_args
            with self._phase("mixed_verify/run"):
                if defaults:
                    out = self._run_dispatch(
                        "mixed_verify", "mixed_verify_defaults", *common,
                        **tree_kw
                    )
                else:
                    out = self._run_dispatch(
                        "mixed_verify", "mixed_verify", *common,
                        *override_args, **tree_kw
                    )
                if self._guard:
                    acc, alt, ok, p_logits, self.cache = out
                    acc, alt, okh = jax.device_get((acc, alt, ok))  # orion: allow[host-sync] the mixed-verify step's ONE documented fetch
                else:
                    acc, alt, p_logits, self.cache = out
                    acc, alt = jax.device_get((acc, alt))   # orion: allow[host-sync] the verify step's ONE documented fetch
                    okh = None
        else:
            common = (
                self.params,
                self.cache,
                jnp.asarray(self.last_token),
                jnp.asarray(self.seq_lens),
                jnp.asarray(d_pt),
                jnp.asarray(mask),
                sub,
            ) + chunk_args
            with self._phase("mixed/run"):
                if defaults:
                    out = self._run_dispatch(
                        "mixed", "mixed_defaults", *common
                    )
                else:
                    out = self._run_dispatch(
                        "mixed", "mixed", *common, *override_args
                    )
                if self._guard:
                    d_toks, ok, p_logits, self.cache = out
                    d_out, okh = jax.device_get((d_toks, ok))   # orion: allow[host-sync] the mixed step's ONE documented fetch
                    d_out = np.asarray(d_out)
                else:
                    d_toks, p_logits, self.cache = out
                    d_out = np.asarray(jax.device_get(d_toks))  # orion: allow[host-sync] [B] — the mixed step's ONE documented fetch
                    okh = None
        real = sum(k for _, k in chunks)
        self.timing["mixed_steps"] += 1
        self.timing["prefill_chunks"] += len(chunks)
        self.timing["chunk_tokens"] += real
        self.timing["chunk_pad_tokens"] += nb * S - real

        # Chunk bookkeeping: advance cursors (seq_lens tracks the cursor,
        # so preemption donates exactly the completed pages and SWA page
        # rolling follows the chunks); prompts that just completed sample
        # their next token off the unified step's logits — fetched only
        # now, so non-finishing steps never pay the [Nc, V] transfer.
        finishing: list[tuple[int, Request]] = []
        for i, (r, k) in enumerate(chunks):
            r.prefill_done += k
            self.seq_lens[r.slot] = r.prefill_done
            if r.prefill_done >= len(r.context):
                finishing.append((i, r))
        if finishing:
            rows = jnp.asarray([i for i, _ in finishing])
            firsts = self._sample(p_logits[rows], [r for _, r in finishing])
            # orion: allow[host-sync] finishing prompts need their sampled first token on the host this step
            for (_, r), first in zip(finishing, np.asarray(firsts)):
                r.prefill_pending = False
                if r.done:
                    continue   # quarantined during mask build
                if r.max_new_tokens <= 0:
                    r.done = True   # prefill-only (scoring) request
                    continue
                tok = int(first)
                self.last_token[r.slot] = tok
                r.generated.append(tok)
                self._maybe_finish(r, tok)
        if self._long and self.icfg.request_resident_pages:
            # Residency demotion between a long request's turns: roll the
            # window first (never demote a page the window already passed
            # — _page_out picks the OLDEST live pages, exactly the
            # about-to-roll ones), then spill still-mid-prefill chunk
            # getters past the cap. Demotion failure degrades to staying
            # resident, so this pass cannot fail the step.
            self._roll_window()
            for r, _k in chunks:
                if r.prefill_pending and not r.done:
                    self._page_out(r)

        # Decode bookkeeping. Speculative: accepted prefix + bonus per
        # slot, then rollback (same walk as the pure verify step).
        # Otherwise W = 1, so no mid-window waste by construction.
        self.timing["slot_steps"] += len(dec)
        if okh is not None:
            # NaN quarantine (decode rows only — the guard rides the
            # decode/verify half of the mixed program; prompt-phase rows
            # are not sampled from this step).
            for r in dec:
                if not okh[r.slot]:
                    self._quarantine(r, "nan")
            dec = [r for r in dec if r.slot is not None]
        if drafts is not None:
            if self._tree:
                self._accept_and_rollback_tree(
                    dec, vtok, vlens, drafts, acc, alt
                )
            else:
                self._accept_and_rollback(dec, vtok, vlens, acc, alt)
        else:
            for r in dec:
                tok = int(d_out[r.slot])
                self.seq_lens[r.slot] += 1
                self.last_token[r.slot] = tok
                r.generated.append(tok)
                self._maybe_finish(r, tok)
        self._reap()
        return bool(dec)

    def _sample_masks(
        self, reqs: list[Request], nb: int
    ) -> Optional[jax.Array]:
        """Host-built legal-token masks for one single-token sampling
        dispatch: row i constrains reqs[i]'s next token to its FSM's
        legal set (all-True for unconstrained slots). Returns None when
        no live request is constrained — the ``legal_mask=None``
        specialization keeps unconstrained dispatches byte-identical to
        a build without this subsystem."""
        if not any(
            r.constraint is not None and not r.done for r in reqs
        ):
            return None
        rows = np.ones((nb, self.mcfg.vocab_size), bool)
        masked = 0
        for i, r in enumerate(reqs):
            if r.constraint is None or r.done or i >= nb:
                continue
            row = r.constraint.mask_row()
            if not row.any():
                # Defense in depth — unreachable through the engine
                # (dead/complete states finish at advance time, dead
                # START states are rejected at submit): an all-masked
                # row would fail the whole dispatch
                # (sampling.check_legal_mask), so contain just this
                # slot and leave its row permissive; neighbors sample
                # exactly what they would have.
                self.constraint_stats.dead_ends += 1
                self._quarantine(r, "constraint_all_masked")
                continue
            rows[i] = row
            masked += 1
        if not masked:
            return None
        self.constraint_stats.masked_steps += 1
        self.constraint_stats.masked_rows += masked
        return jnp.asarray(rows)

    def _sample(
        self, logits: jax.Array, reqs: Optional[list[Request]] = None
    ) -> np.ndarray:
        icfg = self.icfg
        self._key, sub = jax.random.split(self._key)
        legal = (
            self._sample_masks(reqs, logits.shape[0])
            if self.constrained and reqs else None
        )
        if not any(
            r.temperature is not None or r.top_k is not None
            or r.top_p is not None
            for r in (reqs or [])
        ):
            # All-defaults: python scalars keep the greedy short-circuit.
            toks = sample(
                logits, sub, temperature=icfg.temperature,
                top_k=icfg.top_k, top_p=icfg.top_p, legal_mask=legal,
            )
            return np.asarray(jax.device_get(toks))
        # Requests here are admitted (slots assigned), and _admit already
        # resolved the None-means-default rule into the slot arrays — gather
        # from there so the resolution lives in exactly one place.
        nb = logits.shape[0]
        temp = np.full(nb, icfg.temperature, np.float32)
        top_k = np.full(nb, icfg.top_k, np.int32)
        top_p = np.full(nb, icfg.top_p, np.float32)
        for i, req in enumerate(reqs or []):
            temp[i] = self.slot_temp[req.slot]
            top_k[i] = self.slot_top_k[req.slot]
            top_p[i] = self.slot_top_p[req.slot]
        toks = sample(
            logits,
            sub,
            temperature=jnp.asarray(temp),
            top_k=jnp.asarray(top_k),
            top_p=jnp.asarray(top_p),
            legal_mask=legal,
        )
        return np.asarray(jax.device_get(toks))

    def _maybe_finish(self, req: Request, tok: int) -> None:
        # Grammar walk: every emission site funnels through here (the
        # append + _maybe_finish invariant), so this is the single point
        # where a constrained request's FSM consumes the token.
        if req.constraint is not None and not req.done:
            c = req.constraint
            t0 = time.perf_counter()
            # Replay safety: a failover/resubmission may have rebuilt
            # ``generated`` without walking the FSM — re-sync before the
            # incremental advance (no-op when the counts agree; ``tok``
            # is already the last element of ``generated``).
            ok = c.sync(req.generated[:-1]) and c.advance(int(tok))
            self.constraint_stats.advance_s += time.perf_counter() - t0
            if not ok:
                # Only reachable when something upstream bypassed the
                # mask — contain like any poisoned slot; neighbors'
                # outputs stay byte-identical.
                self.constraint_stats.dead_ends += 1
                self._quarantine(req, "constraint_illegal_token")
                return
            if c.is_dead():
                # Non-accepting, no legal continuation: the vocab can't
                # spell the rest of the pattern from here.
                self.constraint_stats.dead_ends += 1
                self._quarantine(req, "constraint_dead_end")
                return
            if c.is_complete():
                # Accepting with no continuation: the only legal move is
                # to stop — finish now instead of burning a step to
                # sample the forced eos.
                self.constraint_stats.completed += 1
                req.done = True
                return
            if self.eos_id is not None and tok == self.eos_id:
                # eos only passes the mask in accepting states: a closed
                # constrained walk is a completion.
                self.constraint_stats.completed += 1
        hit_eos = self.eos_id is not None and tok == self.eos_id
        # seq_lens counts tokens whose KV is cached; the just-sampled token
        # is not yet written, and its write position (== seq_lens) must stay
        # inside the context window.
        ctx_full = int(self.seq_lens[req.slot]) >= self.icfg.max_seq_len
        if hit_eos or ctx_full or len(req.generated) >= req.max_new_tokens:
            req.done = True

    def _reap(self) -> None:
        for i, req in enumerate(self.slots):
            if req is not None and req.done:
                if not req.outcome:
                    req.outcome = "completed"
                # seq_lens counts tokens whose KV is actually in the pool
                # (decode-window overshoot lands beyond it): the full pages
                # below it are what _release_request donates to the cache.
                self._teardown_slot(req, int(self.seq_lens[i]))
                self._just_finished.append(req)
