"""Typed configuration tree for orion-tpu.

The reference stack (``DatCorno/orion``) drives its ``train.py`` from a config /
flag system (SURVEY.md §6 "Config / flag system"); this module is the TPU-native
equivalent: a tree of frozen dataclasses (model / optimizer / train / parallel /
data / checkpoint / inference / runtime), a preset registry covering the five
baseline workloads (BASELINE.json configs 1-5), and dotted ``key=value`` CLI
overrides so every experiment is reproducible from a single command line.
"""

from __future__ import annotations

import dataclasses
import json
import typing
from dataclasses import dataclass, field, fields, replace
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Leaf configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RopeConfig:
    """One rotary table; a layer kind selects it (``ops.rope.rope_table``
    computes the frequencies). The defaults are the plain table."""

    theta: float = 500_000.0
    # Leading share of each head that rotates (partial_rotary_factor); the
    # other dims pass through.
    rotary_fraction: float = 1.0
    # YaRN (rope_type "yarn"): context extension factor, None = off. The
    # frequencies blend 1/f and 1/(factor f) by the linear ramp between the
    # correction dims of beta_fast / beta_slow at yarn_original_max_pos.
    yarn_factor: Optional[float] = None
    yarn_original_max_pos: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    # cos and sin are multiplied by it (YaRN's attention_factor).
    attention_factor: float = 1.0

    def __post_init__(self):
        if not 0.0 < self.rotary_fraction <= 1.0:
            raise ValueError(
                f"rotary_fraction={self.rotary_fraction} must lie in (0, 1]")
        if self.theta <= 1.0 or (
                self.yarn_factor is not None and self.yarn_factor < 1.0):
            raise ValueError(
                f"rope theta={self.theta} must exceed 1 and "
                f"yarn_factor={self.yarn_factor} be None or at least 1")

    @property
    def is_plain(self) -> bool:
        return (self.rotary_fraction == 1.0 and self.yarn_factor is None
                and self.attention_factor == 1.0)


@dataclass(frozen=True)
class SparseConfig:
    """Block selection of a "sparse" layer (InfLLM-v2 as MiniCPM4 publishes
    it; ``ops/sparse.py``): keys are compressed by a mean over ``kernel``
    positions every ``stride``; a query scores the compressed keys wholly in
    its past, the scores of a K/V group's heads are summed and max-pooled
    onto blocks of ``block`` positions (= the page), block 0..
    ``init_blocks`` - 1 and the ``local_blocks`` that end with the query's
    own are forced, and the ``topk`` best blocks (forced ones among them)
    are attended."""

    kernel: int = 32
    stride: int = 16
    block: int = 64
    init_blocks: int = 1
    local_blocks: int = 32
    topk: int = 64

    def __post_init__(self):
        if self.kernel != 2 * self.stride or self.block % self.stride:
            raise ValueError(
                f"model.sparse: kernel={self.kernel} must be 2 x stride="
                f"{self.stride} and stride must divide block={self.block} "
                f"(a page's last kernel reads into the next page alone)")
        if min(self.init_blocks, self.local_blocks) < 1 or (
                self.topk < self.init_blocks + self.local_blocks):
            raise ValueError(
                f"model.sparse: topk={self.topk} must hold the forced blocks "
                f"(init_blocks={self.init_blocks} + local_blocks="
                f"{self.local_blocks}, each at least 1)")


class LayerKind(typing.NamedTuple):
    """What is static about one layer: every kernel and every weight shape
    of the layer follows from it."""

    window: Optional[int]       # sliding window (None = full attention)
    n_heads: int                # query heads
    rope: Optional[RopeConfig]
    moe: bool                   # sparse feed-forward (else dense, d_ff wide)
    # What the layer's attention computes, and with it what it caches:
    # "softmax" (K and V in pages), "power_retention" (a state row beside a
    # paged tail), "latent" (one compressed row a position in pages), "kda"
    # (a state row and a convolution's tail a slot, no page), "lightning" (a
    # state row a slot, no page), "sparse" (K and V in pages beside their
    # compressed keys, of which a query reads the pages it selects). A
    # sparse layer has no rotary embedding: its ``rope`` is None.
    attention: str = "softmax"
    # K/V heads of a softmax layer where the model's kinds differ in them
    # (None = model.n_kv_heads), and whether the layer's softmax carries a
    # learned sink logit a query head (``attn.sink`` [n_heads]).
    n_kv_heads: Optional[int] = None
    sink: bool = False


class LayerPlan(typing.NamedTuple):
    """The layer program of a model whose layers differ in shape: ``lead``
    elements of their own, then ``repeats`` periods of ``period`` elements
    and a tail of the period's first ``tail`` positions. Position j of the
    period has its own stacked leaves, ``counts[j]`` = repeats (+ 1 under
    the tail) deep. An element is ONE layer (``widths`` empty: layer ``lead
    + g * period + j`` is entry g of stack j) or, in a model of several
    attention kinds, a RUN of ``widths[e]`` equal layers one behind the
    other (lead elements first, then the period's positions), which the
    layer program scans: its leaves are one dimension deeper, ``[width,
    ...]`` in the lead and ``[counts[j], width, ...]`` in the period, but
    for a run of one layer, which has no such dimension."""

    lead: int
    period: int
    repeats: int
    tail: int
    widths: Tuple[int, ...] = ()

    @property
    def counts(self) -> Tuple[int, ...]:
        return tuple(self.repeats + (j < self.tail)
                     for j in range(self.period))

    def width(self, e: int) -> int:
        """Layers in element ``e`` (period position j is element lead + j)."""
        return self.widths[e] if self.widths else 1

    def start(self, e: int) -> int:
        """The first layer of element ``e`` (in the first period): a layer
        of the element's kind, and the static index its body is given."""
        return sum(self.width(i) for i in range(e))

    @property
    def period_layers(self) -> int:
        return self.start(self.lead + self.period) - self.start(self.lead)

    def layers(self, e: int):
        """The layers of element ``e`` in the shape of its leaves' leading
        dimensions (nested lists; an int for a lead element of one)."""
        w, first = self.width(e), self.start(e)
        run = lambda at: at if w == 1 else list(range(at, at + w))
        if e < self.lead:
            return run(first)
        return [run(first + g * self.period_layers)
                for g in range(self.counts[e - self.lead])]


@dataclass(frozen=True)
class ModelConfig:
    """Architecture of a decoder-only transformer.

    One parameterization covers the whole model zoo (SURVEY.md §3 "models"):
    GPT-2 (learned positions, LayerNorm, GELU), Llama-3 (RoPE, RMSNorm,
    SwiGLU, GQA) and Mixtral (Llama + top-k MoE).
    """

    name: str = "model"
    vocab_size: int = 50304
    max_seq_len: int = 1024
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    n_kv_heads: int = 12            # < n_heads => grouped-query attention
    d_ff: int = 3072
    head_dim: Optional[int] = None  # default: d_model // n_heads

    # Positional / norm / activation family switches.
    pos_embedding: str = "rope"     # "rope" | "learned"
    rope_theta: float = 500_000.0
    norm: str = "rmsnorm"           # "rmsnorm" | "layernorm"
    norm_eps: float = 1e-5
    activation: str = "swiglu"      # "swiglu" | "geglu" | "gelu"
    tie_embeddings: bool = True
    attn_bias: bool = False
    # Output-projection bias; None follows attn_bias. Qwen2-family models
    # carry q/k/v biases but no o bias (attn_bias=True, attn_out_bias=False).
    attn_out_bias: Optional[bool] = None
    mlp_bias: bool = False
    attn_logit_softcap: Optional[float] = None
    # Sliding-window attention (Mistral-family): attend only to the last N
    # positions. Supported in training (xla + flash kernel, with block
    # skipping) and serving (prefill + both decode paths; the paged kernel
    # skips pages behind the window, making decode O(window)). Composes
    # with sequence parallelism: every SP method threads the window, and
    # the plain ring truncates its scan to O(window) communication.
    sliding_window: Optional[int] = None
    # Interleaved local/global attention (Gemma-family): the window applies
    # only to layers l with l % pattern != pattern-1 (pattern=2 => even
    # layers local, odd global). None => the window applies to every layer.
    # With a pattern, serving keeps FULL-context pages (global layers read
    # the whole history), so only the attention masks are windowed.
    sliding_window_pattern: Optional[int] = None
    # The attention type of every layer as the source publishes it
    # ("full_attention" | "sliding_attention"); the first n_layers entries
    # are read, so a depth cut keeps the published list. Not with
    # sliding_window_pattern, which is another way of writing such a list
    # (``layer_kinds`` is the one list either way).
    layer_types: Optional[Tuple[str, ...]] = None
    # Query heads of every layer, where layers differ (first n_layers
    # entries read); None => n_heads everywhere.
    n_heads_per_layer: Optional[Tuple[int, ...]] = None
    # Rotary tables by attention type; None => the plain table at
    # rope_theta (rope_sliding: None => rope_full's).
    rope_full: Optional[RopeConfig] = None
    rope_sliding: Optional[RopeConfig] = None
    # K/V heads of a WINDOW layer where they differ from a full layer's
    # n_kv_heads (with layer_types). Such a model's cache has two kinds of
    # leaves (infer/kv_cache.ring_leaves): pages for the full layers, which
    # the allocator counts, and a ring of ``ring_pages`` pages a slot for
    # the window layers, which keep only what their window reads.
    n_kv_heads_sliding: Optional[int] = None
    # The values are multiplied by it (attention_value_scale).
    value_scale: float = 1.0
    # "sliding": a window layer's softmax has one more term in its
    # denominator, exp(attn.sink[head]), a learned logit a query head whose
    # column is dropped: a row's weights add up to less than 1.
    attn_sink: Optional[str] = None
    # "per-head": each head's attention output is multiplied by a sigmoid
    # gate computed from the layer's normed input (attn.wg [D, heads]);
    # "elementwise": every number of it by its own (attn.wg [D, heads x
    # head_dim]).
    attn_gate: Optional[str] = None
    # What a layer's attention computes: "softmax", or "power_retention"
    # (ops/retention.py): weights are the SQUARE of the scaled score under
    # a learned decay, log sigmoid(h attn.wr) a K/V head and position from
    # the layer's normed input, normalised by their sum. Such a layer is a
    # recurrence too: serving keeps a fixed-size state row a slot and only
    # the positions since the last complete chunk in pages (the chunk is the
    # program's choice, not the model's: ops/retention.fold_chunk).
    #
    # "kda" (ops/kda.py, Kimi delta attention): a delta rule under a
    # per-channel decay behind a depthwise causal convolution of
    # ``kda_conv_size`` positions, n_heads heads of head_dim keys and
    # values; the log-decay a key channel is kda_lower_bound x sigmoid(
    # exp(A_log) (h wf + dt_bias)). With ``layer_group_size`` G (and the
    # latent sizes below) the layers l with (l + 1) % G == 0 are LATENT
    # layers among the KDA ones: ``LayerKind.attention`` says which a layer
    # is, and a layer's attention is one value a LAYER, not a model.
    #
    # ``mixer_types`` (the source's per-layer list, as published:
    # "minicpm4" | "lightning-attn"; the first n_layers entries are read)
    # makes a layer "sparse" (softmax attention with no rotary embedding
    # over the pages block selection picks, ``sparse``) or "lightning"
    # (ops/lightning.py: linear attention under a fixed decay a head,
    # n_heads K/V heads, rotary q/k, a norm over the concatenated heads).
    attention: str = "softmax"
    mixer_types: Optional[Tuple[str, ...]] = None
    sparse: Optional[SparseConfig] = None
    layer_group_size: Optional[int] = None
    kda_conv_size: int = 4
    kda_lower_bound: float = -5.0
    # RMSNorm over each query and key head before the rotary embedding
    # (attn.q_norm / attn.k_norm [head_dim]; Qwen3-family). On a latent
    # layer: over each head's qk_nope + qk_rope query numbers (q_norm), and
    # over the ONE rotary key all heads share (k_norm [qk_rope_head_dim]),
    # both before the rotation: what a cached row can hold (a norm over a
    # head's expanded key is a number a head and position, which the
    # absorbed form cannot carry).
    qk_norm: bool = False
    # Latent attention (kv_lora_rank set; the five sizes go together): a
    # layer projects its input down to ONE row a position, ``kv_lora_rank``
    # numbers (normed) and a rotary key of ``qk_rope_head_dim`` shared by
    # all heads, and queries through a ``q_lora_rank`` bottleneck (normed)
    # up to n_heads x (qk_nope_head_dim | qk_rope_head_dim). The EXPANDED
    # form rebuilds per-head keys (nope | the shared rotary key) and values
    # (``v_head_dim``) from the row (training, prefill); the ABSORBED form
    # carries the query into the latent space and attends over the rows
    # themselves (decode). Serving pages hold the row and nothing else
    # (infer/kv_cache.latent_leaf). head_dim is qk_nope + qk_rope (where
    # every layer is latent; ``latent_head_dim`` either way), and
    # n_kv_heads = n_heads (the expanded form's). q_lora_rank None: no
    # query bottleneck, one ``wq`` [d_model, n_heads x (nope + rope)].
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # Gemma-family block/embedding details:
    post_norms: bool = False          # extra norms AFTER attention and MLP
    norm_scale_plus_one: bool = False  # rmsnorm multiplies by (1 + w)
    # Embeddings are multiplied by it: True = sqrt(d_model) (Gemma), a
    # number = that number (muP's scale_emb), False = not at all.
    embed_scale: float = False
    # muP's other two scalings, as numbers: each sublayer's output times
    # ``residual_scale`` before it joins the residual stream (scale_depth /
    # sqrt(published depth)), the final norm's output times ``logit_scale``
    # before the head (dim_model_base / hidden_size).
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # Net attention logit scale (default head_dim**-0.5). Gemma-2 uses
    # query_pre_attn_scalar**-0.5, which differs from head_dim for 27B.
    query_scale: Optional[float] = None
    # Final LM-head logit soft-capping (Gemma-2): cap * tanh(logits/cap).
    final_logit_softcap: Optional[float] = None

    # Mixture-of-experts (0 experts => dense MLP).
    n_experts: int = 0
    n_experts_per_token: int = 2
    # Token capacity per expert = capacity_factor * tokens / n_experts.
    capacity_factor: float = 1.25
    router_aux_loss_weight: float = 0.01
    # Dispatch implementation (models/moe.py): "einsum" (one-hot
    # contractions, sharding fully SPMD-automatic), "sorted" (ragged
    # scatter/gather dispatch — no one-hot matmul FLOPs, composes like
    # einsum), "sorted_a2a" (sorted + explicit shard_map all_to_all on ep;
    # per-slice overflow drops; not composable with pp).
    moe_dispatch: str = "sorted"
    # Leading layers whose feed-forward is dense (d_ff wide) before the
    # sparse stack (mlp_only_layers).
    n_dense_layers: int = 0
    # Width of a routed expert (None => d_ff) and of the shared expert
    # added, ungated, beside the routed ones (0 => none).
    moe_d_ff: Optional[int] = None
    shared_expert_d_ff: int = 0
    # The renormalised top-k gates are multiplied by it
    # (moe_routed_scaling_factor).
    router_scale: float = 1.0
    # How the router scores experts: "softmax" over all of them, or
    # "sigmoid" of each logit on its own (float32). With router_bias the
    # top-k is chosen on score + moe.router_bias [E] (a selection bias, a
    # parameter of the layer) while the gates stay the scores WITHOUT it,
    # renormalised over the chosen (+ 1e-20) and scaled.
    router_score: str = "softmax"
    router_bias: bool = False
    # Group-limited selection: the router's experts in ``n_group`` groups of
    # equal size; a group's score is the sum of its two largest (score +
    # bias), the best ``topk_group`` groups are kept and the top-k is taken
    # inside them. 1 / 1: no groups.
    n_group: int = 1
    topk_group: int = 1
    # An expert layer that holds a SHARE of the experts and routes over all
    # of them: router_width is the router's outputs (None => n_experts, the
    # whole layer is here), n_experts stays the number of expert matrices
    # on this device, and they are experts [expert_offset, expert_offset +
    # n_experts). The layer computes its own experts' part of the result
    # (gates renormalised over all chosen experts, held or not) plus the
    # shared expert; nothing stands in for the absent ones.
    router_width: Optional[int] = None
    expert_offset: int = 0

    # Generation by diffusion over blocks (0 => autoregressive, every other
    # model): position i sees position j iff j // block_length <= i //
    # block_length (a block's positions see each other and every earlier
    # block), the logits at a position are over the token AT it, and an
    # undecided position is fed as ``mask_token_id``. The engine generates
    # a block a dispatch (runner.denoise_block; inference.denoising_steps).
    block_length: int = 0
    mask_token_id: int = 0

    # Numerics.
    dtype: str = "bfloat16"         # activation / weight compute dtype
    param_dtype: str = "float32"    # master parameter dtype

    # Kernel selection: "pallas" uses the fused TPU kernels in orion_tpu.ops,
    # "xla" uses the pure-jnp reference path (also the CPU/test path).
    kernels: str = "xla"

    # Weight-only quantization for SERVING ("int8" | None): the inference
    # engine quantizes the given params at init (per-channel scales,
    # models/quantize.py) — decode is HBM-bound, so halving param bytes
    # nearly doubles the decode roofline. Training rejects the flag.
    weight_quant: Optional[str] = None

    # Sequence/context parallelism for attention. When sequence_axis names a
    # mesh axis of size > 1 (the trainer sets this from ParallelConfig.sp),
    # attention runs as ring attention or Ulysses over that axis.
    sequence_axis: Optional[str] = None
    # "ring" | "ring_striped" (load-balanced zigzag-class layout) | "ulysses"
    sequence_method: str = "ring"

    # Pipeline parallelism: when pipeline_axis names a mesh axis of size > 1
    # (the trainer sets this from ParallelConfig.pp), the layer stack runs as
    # a pipeline with this many microbatches. "interleaved" runs the
    # virtual-stage schedule (pp_virtual_stages chunks per device, M <= pp);
    # "1f1b" the hand-written-VJP schedule whose per-stage activation stash
    # is bounded by the stage count — see parallel/pipeline.py.
    pipeline_axis: Optional[str] = None
    pp_microbatches: int = 1
    pp_schedule: str = "gpipe"        # "gpipe" | "interleaved" | "1f1b"
    pp_virtual_stages: int = 1

    # Gradient checkpointing policy for the layer scan:
    #   "none"  - save everything (no recompute; largest memory)
    #   "full"  - save nothing per block (1.33x executed FLOPs; smallest)
    #   "dots"  - checkpoint_dots_with_no_batch_dims (saves every matmul
    #             output, including the [B,S,F] MLP hiddens — OOMs where
    #             "names" fits)
    #   "names" - name-based selective remat: save exactly the activations
    #             annotated with jax.ad_checkpoint.checkpoint_name in the
    #             block body (flash-attention outputs, norm outputs, FFN/
    #             MoE outputs — models/transformer.REMAT_SAVE_NAMES), a few
    #             [B,S,D]-sized tensors per layer. The middle ground
    #             between "full"'s recompute tax and "dots"'s footprint.
    remat: str = "none"
    # With remat="names": park the saved named activations in host RAM
    # (save_and_offload_only_these_names) instead of HBM. Frees the entire
    # named-stash footprint from the device at the cost of PCIe/host
    # transfers overlapping the step. Invalid with any other remat policy.
    remat_offload: bool = False

    # Stream the LM-head projection + cross-entropy over sequence chunks of
    # this size (must divide seq_len) instead of materializing the full
    # [B, S, V] float32 logits. None => dense loss. Cuts the peak activation
    # by ~2x(S/chunk) GiB-scale at large vocab; backward remats per chunk.
    loss_chunk: Optional[int] = None

    # Device-side debug assertions inside manual shard_map regions (the
    # sorted_a2a MoE dispatch and the ring bodies) where runtime.checkify
    # cannot reach: OOB routing/position indices raise host-side instead
    # of surfacing as NaNs or silent drops. Adds a per-assert callback;
    # off in production. (SURVEY.md §6 sanitizers; runtime/asserts.py.)
    debug_asserts: bool = False

    # Layers are evaluated with lax.scan over stacked per-layer params.
    scan_layers: bool = True
    # lax.scan unroll factor for the layer loop (must divide the number of
    # scan units). The v5e profile puts ~19% of device time in the scan's
    # carry/grad dynamic-update-slice fusions; unrolling amortizes the loop
    # bookkeeping at a compile-time cost — but the remat'd body is
    # DUPLICATED per unrolled step (fwd+bwd), which blew past a 12-minute
    # compile budget at unroll=2 on the bench chip (PERF.md). Prefer
    # scan_group. 1 = off.
    scan_unroll: int = 1
    # Grouped layer scan: scan over n_layers/scan_group GROUPS of
    # scan_group statically-unrolled layers, with the remat boundary
    # wrapping the GROUP. Unlike scan_unroll (which duplicates the remat'd
    # body), the group is ONE remat'd body covering G layers, so the scan's
    # stacked-buffer traffic — the fwd carry/named stash writes and the
    # bwd per-layer grad dynamic-update-slices, 18.8% of the bench step
    # (PERF.md) — drops by G× (L/G bigger slices instead of L small ones)
    # while compile time stays bounded (the body grows G×; it is not
    # duplicated into fwd and bwd copies per unrolled step). Must divide
    # n_layers; with sliding_window_pattern the effective group is
    # scan_group * pattern layers (windows stay static per group
    # position). 1 = today's per-layer scan. Exactly grad-preserving.
    scan_group: int = 1

    def __post_init__(self):
        # Domain checks only (each field alone): cross-field constraints
        # (remat_offload needs remat="names", n_layers % scan_group, ...)
        # live in the Trainer / forward pass — dotted CLI overrides apply
        # one field at a time, so a cross-field check here would reject
        # valid override sequences mid-application.
        if self.remat is None:
            # The override parser maps the literal string "none" to Python
            # None for every field; for remat the canonical spelling is
            # the string (presets compare against it) — normalize.
            object.__setattr__(self, "remat", "none")
        if self.remat not in ("none", "full", "dots", "names"):
            raise ValueError(
                f"model.remat={self.remat!r}; pick none|full|dots|names"
            )
        # `is None` first: the override parser maps the literal "none" to
        # None for every field, and None < 1 is a TypeError, not the
        # domain-check message.
        if self.attention not in ("softmax", "power_retention", "kda"):
            raise ValueError(
                f"model.attention={self.attention!r}; "
                f"softmax|power_retention|kda")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"model.router_score={self.router_score!r}; softmax|sigmoid")
        if self.block_length is None or not 0 <= self.block_length <= 16 or (
                self.block_length & (self.block_length - 1)):
            raise ValueError(
                f"model.block_length={self.block_length} must be 0 or a "
                f"power of two up to 16 (a block never straddles a kernel's "
                f"tile, and its positions are one W-query dispatch under "
                f"ancestor words of 31 bits)")
        if self.block_length and not (
                0 <= self.mask_token_id < self.vocab_size):
            raise ValueError(
                f"model.mask_token_id={self.mask_token_id} must lie inside "
                f"the vocabulary of {self.vocab_size}")
        if self.attn_sink not in (None, "sliding"):
            raise ValueError(
                f"model.attn_sink={self.attn_sink!r}; sliding|None")
        if self.scan_group is None or self.scan_group < 1:
            raise ValueError(f"model.scan_group={self.scan_group} must be >= 1")
        if self.scan_unroll is None or self.scan_unroll < 1:
            raise ValueError(
                f"model.scan_unroll={self.scan_unroll} must be >= 1"
            )

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_retention(self) -> bool:
        return self.attention == "power_retention"

    @property
    def is_latent(self) -> bool:
        """EVERY layer is a latent layer."""
        return self.kv_lora_rank is not None and not self.has_kda

    @property
    def has_latent(self) -> bool:
        """Some layer is a latent layer (the cache has the latent leaf)."""
        return self.kv_lora_rank is not None

    @property
    def has_kda(self) -> bool:
        """Some layer is a KDA layer (the cache has its slot leaves)."""
        return self.attention == "kda"

    @property
    def has_sparse(self) -> bool:
        """Some layer selects the pages it reads (the cache has the
        compressed-key leaf)."""
        return self.mixer_types is not None and (
            "minicpm4" in self.mixer_types[:self.n_layers])

    @property
    def resumes_prefill(self) -> bool:
        """A prompt may enter in page-aligned chunks, each resuming from
        what the last left in the cache (pages, compressed keys, state
        rows): the model of sparse and lightning layers."""
        return self.mixer_types is not None

    def layer_attention(self, layer: int) -> str:
        """``LayerKind.attention`` of layer ``layer`` (a Python int)."""
        if self.mixer_types is not None:
            kinds = {"minicpm4": "sparse", "lightning-attn": "lightning"}
            if self.mixer_types[layer] not in kinds:
                raise ValueError(
                    f"model.mixer_types[{layer}]={self.mixer_types[layer]!r};"
                    f" minicpm4|lightning-attn")
            return kinds[self.mixer_types[layer]]
        if self.has_kda:
            G = self.layer_group_size
            return ("latent" if self.has_latent and G and (layer + 1) % G == 0
                    else "kda")
        return "latent" if self.has_latent else self.attention

    @property
    def n_paged_layers(self) -> int:
        """The layers that keep pages: a cache's paged leaves are [these
        layers x pages, ...]. All of them, but for a model with KDA layers,
        whose latent layers alone have any."""
        if self.has_window_ring:
            return sum(self.cache_kind(k) == "softmax"
                       for k in self.layer_kinds)
        if self.mixer_types is not None:
            return self.n_layers_of("sparse")
        return self.n_layers_of("latent") if self.has_kda else self.n_layers

    def n_layers_of(self, attention: str) -> int:
        return sum(k.attention == attention for k in self.layer_kinds)

    def cache_kind(self, kind: LayerKind) -> str:
        """Which leaves of the cache a layer's rows are in: its attention,
        but "ring" for a window layer of a ``has_window_ring`` model."""
        if self.has_window_ring and kind.window is not None:
            return "ring"
        return kind.attention

    def cache_layer(self, l, j: int):
        """Layer ``l``'s index among the layers of ITS attention kind: the
        row of its cache leaves, which are sized over those layers alone.
        ``l`` may be traced under a layer scan; ``j`` is the static index
        of the first layer of its element of the plan, a layer of the same
        kind (``transformer.scan_layer_plan``'s). A model of one kind:
        ``l``."""
        plan = self.layer_plan
        kinds = self.layer_kinds
        if plan is None or len({self.cache_kind(k) for k in kinds}) == 1:
            return l
        att = self.cache_kind(kinds[j])
        same = [self.cache_kind(k) == att for k in kinds]
        first = plan.start(plan.lead)
        if j < first:                   # in a lead run: l - j layers into it
            return sum(same[:j]) + (l - j)
        a_period = sum(same[first:first + plan.period_layers])
        return (sum(same[:j]) + (l - j) // plan.period_layers * a_period
                + (l - j) % plan.period_layers)

    @property
    def has_window_ring(self) -> bool:
        """Window layers keep their K and V in a ring a slot, apart from the
        full layers' pages: the model whose two kinds of layer differ in
        their K/V heads (one pool cannot hold both shapes)."""
        return self.n_kv_heads_sliding is not None

    @property
    def ring_window(self) -> Optional[int]:
        """The positions a window layer of a ``has_window_ring`` model reads
        and keeps (``page_window`` is the POOL's, the full layers')."""
        return self.sliding_window if self.has_window_ring else None

    @property
    def resolved_v_head_dim(self) -> int:
        """A softmax layer's value head width (a latent layer's
        ``v_head_dim`` is read where latent layers are built)."""
        if self.v_head_dim is None or self.has_latent:
            return self.resolved_head_dim
        return self.v_head_dim

    def kv_heads_of(self, kind: Optional[LayerKind]) -> int:
        return (self.n_kv_heads if kind is None or kind.n_kv_heads is None
                else kind.n_kv_heads)

    @property
    def latent_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """Numbers a cached position holds in a latent layer: the
        compressed row and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_shared_experts(self) -> int:
        """The shared expert's width in routed experts' widths (how the
        DeepSeek-family key set states it)."""
        return self.shared_expert_d_ff // self.resolved_moe_d_ff

    @property
    def resolved_router_width(self) -> int:
        return (self.n_experts if self.router_width is None
                else self.router_width)

    @property
    def holds_expert_share(self) -> bool:
        return self.is_moe and self.resolved_router_width != self.n_experts

    @property
    def resolved_moe_d_ff(self) -> int:
        return self.d_ff if self.moe_d_ff is None else self.moe_d_ff

    @property
    def resolved_attn_out_bias(self) -> bool:
        return (
            self.attn_bias
            if self.attn_out_bias is None else self.attn_out_bias
        )

    @property
    def is_gated_mlp(self) -> bool:
        """Gated feed-forwards (a w_gate matrix): SwiGLU and GeGLU."""
        return self.activation in ("swiglu", "geglu")

    @property
    def window_pattern(self) -> Optional[int]:
        """The interleaved local/global layer grouping, iff ACTIVE (a
        sliding window is set and a pattern configured). Single source of
        truth for 'this model scans/pipelines in groups' — transformer
        forward and trainer pp validation both key off it."""
        return (
            self.sliding_window_pattern
            if self.sliding_window is not None else None
        )

    @property
    def scan_unit(self) -> int:
        """Layers per layer-scan iteration (and per remat body): scan_group
        multiples of the window-pattern unit. Windows stay static per
        within-group position because the unit is a multiple of the
        pattern. Must divide n_layers (checked where the scan is built)."""
        return self.scan_group * (self.window_pattern or 1)

    @property
    def layer_kinds(self) -> Tuple[LayerKind, ...]:
        """The kind of every layer: THE per-layer list. ``layer_types``
        writes its attention types out; ``sliding_window_pattern`` is the
        arithmetic form (layers l % pattern != pattern - 1 windowed, the
        Gemma-family interleave); with neither, the window is every
        layer's."""
        L, p = self.n_layers, self.sliding_window_pattern
        if self.layer_types is not None and p is not None:
            raise ValueError(
                "model.layer_types and model.sliding_window_pattern both "
                "write the per-layer list: set one")
        for name in ("layer_types", "n_heads_per_layer", "mixer_types"):
            got = getattr(self, name)
            if got is not None and len(got) < L:
                raise ValueError(
                    f"model.{name} has {len(got)} entries for "
                    f"n_layers={L}")
        full = self.rope_full or RopeConfig(theta=self.rope_theta)
        sliding = self.rope_sliding or full
        kinds = []
        for l in range(L):
            if self.layer_types is not None:
                if self.layer_types[l] not in (
                        "full_attention", "sliding_attention"):
                    raise ValueError(
                        f"model.layer_types[{l}]={self.layer_types[l]!r}; "
                        f"full_attention|sliding_attention")
                windowed = self.layer_types[l] == "sliding_attention"
            else:
                windowed = p is None or l % p != p - 1
            windowed = windowed and self.sliding_window is not None
            att = self.layer_attention(l)
            kinds.append(LayerKind(
                window=self.sliding_window if windowed else None,
                n_heads=(self.n_heads if self.n_heads_per_layer is None
                         else self.n_heads_per_layer[l]),
                rope=(None if att == "sparse"
                      else sliding if windowed else full),
                moe=self.is_moe and l >= self.n_dense_layers,
                attention=att,
                n_kv_heads=(self.n_heads if att == "lightning" else
                            self.n_kv_heads_sliding if windowed else None),
                sink=windowed and self.attn_sink == "sliding",
            ))
        return tuple(kinds)

    def layer_kind(self, layer: int) -> LayerKind:
        """``layer`` must be a PYTHON int (a kind is static in every
        kernel and weight shape); layer scans call their body once per
        static position and pass a layer of that kind."""
        return self.layer_kinds[layer]

    def layer_window(self, layer: int) -> Optional[int]:
        """The sliding window for a given layer index (None = global)."""
        return self.layer_kind(layer).window

    @property
    def page_window(self) -> Optional[int]:
        """The window the page allocator may free behind: the sliding
        window where EVERY layer is windowed, else None (one full layer
        reads the whole history, and pages are shared by all layers). For a
        ``has_window_ring`` model the pages are the full layers' alone, so
        None says what is true of them: every page is read for as long as
        its request lives; what a window layer keeps is ``ring_window``."""
        windows = {k.window for k in self.layer_kinds}
        return self.sliding_window if windows == {self.sliding_window} else None

    @property
    def layer_plan(self) -> Optional[LayerPlan]:
        """None for a model whose layers share one stacked leaf per weight
        (every model of one head count, one rotary table and one
        feed-forward kind: the layer scan and ``window_pattern`` serve it,
        parameter tree and programs as they always were). Else the leading
        dense layers, the smallest period of what follows, and its tail; in
        a model of KDA and latent layers, or of ``mixer_types``, the same
        over RUNS of equal layers (``LayerPlan.widths``)."""
        by_runs = ((self.has_kda and self.has_latent)
                   or self.mixer_types is not None)
        if (self.layer_types is None and self.n_heads_per_layer is None
                and self.n_dense_layers == 0 and not by_runs):
            return None
        kinds = self.layer_kinds

        def periodic(rest):
            p = next((p for p in range(1, len(rest) + 1)
                      if all(rest[i] == rest[i % p]
                             for i in range(len(rest)))), 1)
            return p, len(rest) // p, len(rest) % p

        lead = min(self.n_dense_layers, self.n_layers)
        if not by_runs:
            return LayerPlan(lead, *periodic(kinds[lead:]))
        # Several attention kinds: the elements are RUNS of equal layers
        # (five KDA layers to a latent one: a body a run, not a layer), and
        # the lead goes on over as many runs as leave the fewest bodies
        # (the published model: two dense and three sparse KDA layers, then
        # six periods of a latent layer and five KDA layers, and the last
        # latent layer; its first eight layers: runs of 2, 3, 1 and 2). A
        # published ``mixer_types`` list goes through as it stands: a dense
        # model's runs all count as lead, so an aperiodic list (runs of 1, 8,
        # 1, 6, 2, 4, 1, 6, 3) is nine lead elements and no period.
        runs = []
        for k in kinds:
            if runs and runs[-1][0] == k:
                runs[-1][1] += 1
            else:
                runs.append([k, 1])
        runs = [tuple(r) for r in runs]
        dense = sum(not k.moe for k, _ in runs)
        lead = min(range(dense, len(runs) + 1), key=lambda e: (
            e + sum(periodic(runs[e:])[::2]), e))
        period, repeats, tail = periodic(runs[lead:])
        return LayerPlan(lead, period, repeats, tail,
                         tuple(w for _, w in runs[:lead + period]))

    def num_params(self) -> int:
        """Approximate parameter count (embeddings + blocks + norms)."""
        h, v, L = self.d_model, self.vocab_size, self.n_layers
        hd = self.resolved_head_dim
        q = h * self.n_heads * hd
        kv = 2 * h * self.n_kv_heads * hd
        o = self.n_heads * hd * h
        attn = q + kv + o
        if self.is_gated_mlp:
            mlp = 3 * h * self.d_ff
        else:
            mlp = 2 * h * self.d_ff
        if self.is_moe:
            mlp = mlp * self.n_experts + h * self.n_experts  # experts + router
        norms = 2 * h
        block = attn + mlp + norms
        embed = v * h if self.tie_embeddings else 2 * v * h
        pos = self.max_seq_len * h if self.pos_embedding == "learned" else 0
        return embed + pos + L * block + h

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Training FLOPs per token: 6*N_active plus the attention term.

        Used for the judged MFU metric (BASELINE.json:2); matches the standard
        6*N + 12*L*H*Q*T accounting (PaLM appendix-style).
        """
        s = seq_len if seq_len is not None else self.max_seq_len
        h, L = self.d_model, self.n_layers
        hd = self.resolved_head_dim
        attn = self.n_heads * hd * h + 2 * self.n_kv_heads * hd * h + self.n_heads * hd * h
        if self.is_gated_mlp:
            mlp = 3 * h * self.d_ff
        else:
            mlp = 2 * h * self.d_ff
        if self.is_moe:
            mlp = mlp * self.n_experts_per_token
        dense_flops = 6.0 * L * (attn + mlp) + 6.0 * self.vocab_size * h
        # Attention score/value FLOPs: 12 * L * heads * head_dim * seq.
        attn_flops = 12.0 * L * self.n_heads * hd * s
        return dense_flops + attn_flops


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"                 # "adamw" | "sgd" (momentum in b1)
    learning_rate: float = 3e-4
    min_lr_ratio: float = 0.1
    warmup_steps: int = 100
    decay_steps: Optional[int] = None   # default: train.num_steps
    schedule: str = "cosine"            # "cosine" | "linear" | "constant"
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip_norm: float = 1.0
    # Dtype of Adam moments; bf16 halves optimizer HBM at slight quality cost.
    moment_dtype: str = "float32"

    def __post_init__(self):
        if self.name not in ("adamw", "sgd"):
            raise ValueError(f"optimizer.name={self.name!r}; adamw|sgd")
        if self.schedule not in ("cosine", "linear", "constant"):
            raise ValueError(
                f"optimizer.schedule={self.schedule!r}; "
                f"cosine|linear|constant"
            )
        if self.learning_rate <= 0:
            raise ValueError(
                f"optimizer.learning_rate={self.learning_rate} must be > 0"
            )
        if not 0.0 <= self.min_lr_ratio <= 1.0:
            raise ValueError(
                f"optimizer.min_lr_ratio={self.min_lr_ratio} not in [0, 1]"
            )
        if self.warmup_steps < 0:
            raise ValueError(
                f"optimizer.warmup_steps={self.warmup_steps} must be >= 0"
            )
        if self.decay_steps is not None and self.decay_steps < 1:
            raise ValueError(
                f"optimizer.decay_steps={self.decay_steps} must be >= 1"
            )
        for knob in ("b1", "b2"):
            v = getattr(self, knob)
            if not 0.0 <= v < 1.0:
                raise ValueError(f"optimizer.{knob}={v} not in [0, 1)")
        if self.eps <= 0:
            raise ValueError(f"optimizer.eps={self.eps} must be > 0")
        if self.weight_decay < 0:
            raise ValueError(
                f"optimizer.weight_decay={self.weight_decay} must be >= 0"
            )
        if self.grad_clip_norm < 0:
            raise ValueError(
                f"optimizer.grad_clip_norm={self.grad_clip_norm} "
                f"must be >= 0 (0 disables clipping)"
            )
        import ml_dtypes  # noqa: F401  registers bfloat16 & co with numpy
        import numpy as _np

        try:
            _np.dtype(self.moment_dtype)
        except TypeError as e:
            raise ValueError(
                f"optimizer.moment_dtype={self.moment_dtype!r} is not a "
                f"dtype name"
            ) from e


@dataclass(frozen=True)
class ParallelConfig:
    """Mesh axis sizes. Product must equal the total device count.

    Axis semantics (SURVEY.md §2/§6):
      dp    - pure data parallelism (replicated params, psum grads)
      fsdp  - ZeRO-3 data parallelism (params/grads/opt sharded, gather-on-use)
      tp    - tensor parallelism (heads / mlp hidden sharded)
      pp    - pipeline stages
      sp    - sequence/context parallelism (ring attention / Ulysses)
      ep    - expert parallelism (MoE experts sharded, all_to_all dispatch)
    """

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1
    # Attention algorithm when sp > 1: "ring" | "ring_striped" | "ulysses".
    sequence_method: str = "ring"
    # Pipeline microbatches (pp > 1). Must divide the per-step batch.
    pp_microbatches: int = 1
    # Pipeline schedule: "gpipe" | "interleaved" (virtual stages; bubble
    # amortized by pp_virtual_stages instead of microbatch count) |
    # "1f1b" (hand-written pipeline VJP: per-stage activation stash
    # bounded by the stage count instead of the microbatch count, losses
    # and grads bitwise-equal to gpipe — see parallel/pipeline.py).
    pp_schedule: str = "gpipe"
    pp_virtual_stages: int = 1
    # Mesh axes that live on DCN (multi-slice); all others ride ICI.
    dcn_axes: Tuple[str, ...] = ()

    def __post_init__(self):
        # Domain checks only, matching ModelConfig's rule (cross-field
        # constraints live in the Trainer — dotted CLI overrides apply
        # one field at a time).
        if self.pp_schedule not in ("gpipe", "interleaved", "1f1b"):
            raise ValueError(
                f"parallel.pp_schedule={self.pp_schedule!r}; pick "
                f"gpipe|interleaved|1f1b"
            )
        if self.pp_microbatches is None or self.pp_microbatches < 1:
            raise ValueError(
                f"parallel.pp_microbatches={self.pp_microbatches} must "
                f"be >= 1"
            )
        if self.pp_virtual_stages is None or self.pp_virtual_stages < 1:
            raise ValueError(
                f"parallel.pp_virtual_stages={self.pp_virtual_stages} "
                f"must be >= 1"
            )

    @property
    def axis_sizes(self) -> Mapping[str, int]:
        return {"dp": self.dp, "fsdp": self.fsdp, "tp": self.tp,
                "pp": self.pp, "sp": self.sp, "ep": self.ep}

    @property
    def num_devices(self) -> int:
        n = 1
        for v in self.axis_sizes.values():
            n *= v
        return n


@dataclass(frozen=True)
class DataConfig:
    source: str = "synthetic"       # "synthetic" | "memmap"
    path: Optional[str] = None       # token file (memmap)
    batch_size: int = 8              # global batch, in sequences
    seq_len: int = 1024
    shuffle_seed: int = 0
    # (No num_epochs: loaders are deterministic step-indexed streams —
    # training length is train.num_steps; an "epoch" has no meaning here.)
    # Native (C++) loader for memmap token shards; falls back to numpy.
    use_native_loader: bool = True
    # Sequence packing: batches carry multiple documents per row with
    # segment_ids / per-segment positions / a loss_mask over padding, and
    # attention is masked at document boundaries (the flash kernel's
    # segment path). Synthetic: variable-length documents; memmap: windows
    # split at eos_token_id occurrences. Incompatible with parallel.pp
    # (pipeline microbatching cannot carry per-row segment state).
    packed: bool = False
    eos_token_id: int = 0            # document separator for packed memmap
    # Row-crossing document tails carry into the next row only within
    # fixed groups of this many GLOBAL rows (overhang at a group boundary
    # is dropped, like a final row). A fixed group keeps the packed stream
    # process-count invariant (elastic resume) while letting each host
    # read/pack only group-aligned row ranges instead of the whole global
    # batch.
    pack_carry_group: int = 8
    # Held-out eval stream (train.eval_interval): a separate memmap token
    # file, or — for synthetic/same-file setups — the train source under a
    # different shuffle seed (disjoint windows with high probability).
    eval_path: Optional[str] = None
    eval_seed: int = 1_000_003

    def __post_init__(self):
        if self.source not in ("synthetic", "memmap"):
            raise ValueError(
                f"data.source={self.source!r}; synthetic|memmap"
            )
        if self.source == "memmap" and not self.path:
            raise ValueError("data.source=memmap requires data.path")
        if self.batch_size < 1:
            raise ValueError(
                f"data.batch_size={self.batch_size} must be >= 1"
            )
        if self.seq_len < 1:
            raise ValueError(f"data.seq_len={self.seq_len} must be >= 1")
        if self.eos_token_id < 0:
            raise ValueError(
                f"data.eos_token_id={self.eos_token_id} must be >= 0"
            )
        if self.pack_carry_group < 1:
            raise ValueError(
                f"data.pack_carry_group={self.pack_carry_group} "
                f"must be >= 1"
            )


@dataclass(frozen=True)
class CheckpointConfig:
    directory: Optional[str] = None
    save_interval_steps: int = 1000
    max_to_keep: int = 3
    async_save: bool = True
    restore: bool = True             # restore_or_init on startup
    # Restore-time integrity checking (ckpt/checkpoint.py): every array
    # file's checksum is validated against the manifest before the state is
    # materialized; a corrupt checkpoint is QUARANTINED (moved aside with a
    # typed reason) and restore falls back to the newest intact one. Off
    # skips the checksum pass (manifest/shape checks still run) for very
    # large states where the extra read dominates restore time.
    verify_restore: bool = True

    def __post_init__(self):
        if self.save_interval_steps is None or self.save_interval_steps < 1:
            raise ValueError(
                f"checkpoint.save_interval_steps={self.save_interval_steps} "
                f"must be >= 1"
            )
        if self.max_to_keep is not None and self.max_to_keep < 1:
            raise ValueError(
                f"checkpoint.max_to_keep={self.max_to_keep} must be >= 1 "
                f"(or none to keep all)"
            )


@dataclass(frozen=True)
class TrainConfig:
    num_steps: int = 1000
    log_interval: int = 10
    seed: int = 0
    # Gradient accumulation: data.batch_size is the global batch per optimizer
    # step; grad_accum splits it into that many sequential microbatches (must
    # divide batch_size). Token throughput is unaffected; memory shrinks.
    grad_accum: int = 1
    # Dtype gradients are computed/stacked in (None = param_dtype). With
    # scan_layers, per-layer grads are written into stacked [L, ...]
    # buffers via dynamic-update-slice each bwd step — the "scan stash"
    # share of the profile (PERF.md). "bfloat16" halves those bytes (and
    # the grad-clip/optimizer read traffic); the AdamW update still runs
    # in f32 against the f32 master params, so only the gradient signal
    # itself is rounded (standard mixed-precision practice). Measure per
    # model: the trajectory tracks f32 closely but not bitwise.
    grad_dtype: Optional[str] = None
    # Training-side override of the remat policy ("inherit" = use
    # model.remat as-is). `train.remat=names` is the canonical spelling for
    # selective remat at train time: the Trainer folds it into the model
    # config, so checkpoints/serving configs keep their own model.remat.
    # Values as model.remat: "none" | "full" | "dots" | "names". (The
    # sentinel is "inherit", not None: the CLI override parser maps the
    # literal "none" to None, which must mean remat OFF, not unset.)
    remat: Optional[str] = "inherit"
    # With an effective remat policy of "names": offload the saved named
    # activations to host RAM instead of HBM (model.remat_offload). The
    # middle ground the 16 GB bench chip cannot otherwise express: "full"
    # pays 1.33x executed FLOPs, "dots" OOMs (PERF.md).
    remat_offload: bool = False
    # Profiling window (jax.profiler trace), e.g. (10, 20). None disables.
    profile_steps: Optional[Tuple[int, int]] = None
    profile_dir: str = "/tmp/orion_tpu_profile"
    # Fault injection for recovery tests: raise at this step (SURVEY.md §6).
    inject_fault_at_step: Optional[int] = None
    # Stall watchdog: alarm if no step completes within this many seconds
    # (hung collective / dead peer host). None disables.
    watchdog_timeout_s: Optional[float] = None
    # What the watchdog does on stall: "log" (default) or "abort" (SIGABRT
    # the process so a supervisor restart resumes from the checkpoint — a
    # hung collective is unrecoverable in-process).
    watchdog_action: str = "log"
    metrics_jsonl: Optional[str] = None
    # Held-out evaluation: every eval_interval optimizer steps, average the
    # loss over eval_batches fixed batches from the eval stream (see
    # DataConfig.eval_path/eval_seed). Logged as eval_loss. None disables.
    eval_interval: Optional[int] = None
    eval_batches: int = 8
    # Quantize the data-parallel gradient all-reduce wire traffic to int8
    # with per-block scales (EQuARX-class; comm/quantized.py). Only valid
    # with pure DP (fsdp=tp=pp=sp=ep=1) — the bandwidth win targets the
    # DCN-crossing dp axis of hybrid meshes. None => full-precision psum.
    grad_quant_bits: Optional[int] = None
    # --- ZeRO-1 optimizer-state sharding (PAPERS.md 2004.13336) ----------
    # Shard the weight update and optimizer state 1/dp across the dp axis:
    # gradients reduce-scatter over dp, each replica updates only its own
    # 1/dp shard of the Adam moments (and, when model.param_dtype differs
    # from model.dtype, of a separate f32 master copy carried in the
    # optimizer state), and the updated (cast-down) params all-gather back.
    # Expressed TPU-natively as sharding constraints inside the jit train
    # step (XLA emits the reduce-scatter/all-gather pair); the losses and
    # the post-step full (all-gathered) state are bitwise-equal to the
    # unsharded dp baseline. Needs parallel.dp > 1; composes with
    # grad_accum / scan_group / remat / fsdp / tp, and with parallel.pp
    # (the update dim is picked per leaf AROUND the pp-sharded layer dim,
    # so the reduce-scatter/all-gather run over dp within each stage's
    # param shard — stage-local dp). Only zero1_quantize stays rejected
    # under pp. See PERF.md "ZeRO-1".
    zero1: bool = False
    # Wire precision of the two ZeRO-1 collective legs on the (DCN-riding)
    # dp axis. None = full-precision legs via sharding constraints (the
    # bitwise path). "int8" = both legs blockwise-int8 through the explicit
    # shard_map path (comm.quantized_reduce_scatter / quantized_all_gather,
    # ~4x less DCN traffic than f32, error bounded by one quantization
    # step per leg); "rs_int8" / "ag_int8" quantize only the grad
    # reduce-scatter / param all-gather leg. The int8 path needs a pure-DP
    # mesh (the wire legs run manual over dp) and computes the clip norm
    # from the local shards (allclose, not bitwise, to the baseline).
    zero1_quantize: Optional[str] = None
    # --- Fault tolerance (README "Training robustness") -------------------
    # Gradient anomaly guard: fold a donation-safe all-finite (loss + every
    # grad leaf) and global-norm-spike check into the compiled train step.
    # An anomalous step is SKIPPED — params, moments and the schedule count
    # come out bit-identical to the pre-step state — and counted
    # (metrics.TrainRobustnessStats). Off by default so the compiled step
    # stays bit-for-bit the pre-guard program.
    anomaly_guard: bool = False
    # Spike threshold: a step whose global grad norm exceeds
    # anomaly_spike_factor x the running norm EMA counts as anomalous even
    # when finite (a loss-spike/bad-batch signature). The EMA is
    # host-maintained and persisted in the checkpoint manifest so resume
    # reproduces the same skip decisions bitwise. None = finite-check only.
    anomaly_spike_factor: Optional[float] = None
    # EMA decay for the reference grad norm (only with anomaly_spike_factor).
    anomaly_ema_beta: float = 0.9
    # After this many CONSECUTIVE anomalous (skipped) steps the poison is
    # clearly not transient: auto-rollback restores the newest intact
    # checkpoint and fast-forwards the data cursor past the poisoned batch
    # window (loader.skip_batches) before continuing.
    anomaly_limit: int = 3
    # Emergency checkpoint on preemption (SIGTERM inside the grace window)
    # and on crash/interrupt paths: force-save the newest complete state
    # after awaiting any in-flight async save. Off = rely on periodic saves.
    emergency_ckpt: bool = True
    # Supervisor restarts (train.py --max-restarts overrides): rebuild the
    # trainer and resume from the newest intact checkpoint after a
    # recoverable failure, up to this many times. 0 = crash on first fault.
    max_restarts: int = 0
    # --- Observability (orion_tpu/obs; README "Observability") ----------
    # Per-step phase tracer: spans for data / dispatch / guard / ckpt per
    # train step in a bounded monotonic-clock ring, exportable as Chrome
    # trace-event JSON; the dispatch span rides a
    # jax.profiler.StepTraceAnnotation so host phases line up with the
    # device profile from the train.profile_steps window. Off by default
    # (host path byte-identical to the untraced loop; compiled programs
    # untouched either way).
    trace: bool = False
    trace_ring: int = 16384
    # Chrome-trace export target, written when fit() ends. Setting it
    # implies recording even when `trace` is off. None = record only.
    trace_path: Optional[str] = None
    # Flight recorder: postmortem-dump directory for the training-side
    # trigger (anomaly auto-rollback). Setting it enables event recording
    # even when `trace` is off. None disables.
    flight_dir: Optional[str] = None
    # Prometheus-textfile export of the trainer registry (last step
    # metrics + robustness counters), rewritten every log_interval steps.
    metrics_prom: Optional[str] = None

    def __post_init__(self):
        if self.anomaly_limit is None or self.anomaly_limit < 1:
            raise ValueError(
                f"train.anomaly_limit={self.anomaly_limit} must be >= 1"
            )
        if self.anomaly_spike_factor is not None \
                and self.anomaly_spike_factor <= 1.0:
            raise ValueError(
                f"train.anomaly_spike_factor={self.anomaly_spike_factor} "
                f"must be > 1 (norm ratio vs the running EMA), or none"
            )
        if not 0.0 < self.anomaly_ema_beta < 1.0:
            raise ValueError(
                f"train.anomaly_ema_beta={self.anomaly_ema_beta} must be "
                f"in (0, 1)"
            )
        if self.max_restarts is None or self.max_restarts < 0:
            raise ValueError(
                f"train.max_restarts={self.max_restarts} must be >= 0"
            )
        if self.zero1_quantize not in (None, "int8", "rs_int8", "ag_int8"):
            raise ValueError(
                f"train.zero1_quantize={self.zero1_quantize!r}; pick "
                f"none|int8|rs_int8|ag_int8"
            )
        if self.trace_ring is None or self.trace_ring < 1:
            raise ValueError(
                f"train.trace_ring={self.trace_ring} must be >= 1"
            )


@dataclass(frozen=True)
class InferenceConfig:
    max_seq_len: int = 2048
    page_size: int = 64               # tokens per KV-cache page
    num_pages: int = 512              # global page pool size
    max_batch_size: int = 32          # max concurrent sequences
    prefill_chunk: int = 512          # prefill bucketing
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    max_new_tokens: int = 128
    # Decode steps fused per engine step (one dispatch + ONE host fetch per
    # window). Larger windows amortize host round-trips at the cost of
    # decoding past EOS by up to W-1 tokens.
    decode_window: int = 8
    # KV-cache quantization: None (pool in model dtype) or "int8" (pool in
    # int8 with per-token per-kv-head f32 scales stored alongside;
    # dequantization happens inside the paged kernel / at the xla gather).
    # Decode is HBM-bound on params + KV traffic, so halving KV bytes buys
    # throughput directly at long contexts (see PERF.md serving notes).
    kv_quant: Optional[str] = None
    # Automatic prefix caching (vLLM/SGLang-style): finished/preempted
    # requests donate their full KV pages to a host-side radix tree
    # (infer/prefix_cache.py); new requests map the longest cached prefix
    # at page granularity (refcounted, immutable) and prefill only the
    # uncached tail. Cached pages are reclaimable pool headroom: LRU
    # eviction hands them back to the allocator under pressure, so the
    # admission math is unchanged in the worst case. Off by default; the
    # dominant win is shared-system-prompt traffic (see README "Prefix
    # caching" and tools/prefix_cache_bench.py).
    prefix_cache: bool = False
    # Minimum matched pages worth mapping: shorter matches prefill cold
    # (mapping a 1-page prefix costs table/refcount churn for little gain
    # when page_size is small).
    prefix_cache_min_pages: int = 1
    # --- Tiered prefix cache (README "Tiered prefix cache") -------------
    # Host-RAM second tier behind the radix tree: > 0 sizes a HostPagePool
    # of host_tier_bytes // bytes-per-page slots, and prefix-cache LRU
    # eviction DEMOTES pages (one batched d2h copies their KV bytes —
    # int8 scale pools included — into host buffers; the tree keeps the
    # tokens matchable) instead of discarding. A later match on a
    # host-resident path restores the pages with one batched h2d and
    # resumes tail prefill exactly as a warm HBM hit. 0 (default)
    # disables the tier: the engine is byte-identical to the untiered
    # one. Requires prefix_cache=true (engine-checked — cross-field).
    host_tier_bytes: int = 0
    # Break-even gate: host-resident matches shorter than this many
    # tokens recompute instead of restoring (counted as
    # host_recompute_skips). None (default) derives the threshold from
    # the three measured constants below via the PERF.md "Host-tier
    # break-even" arithmetic; set it explicitly to pin policy.
    host_tier_min_tokens: Optional[int] = None
    # Measured constants feeding the auto threshold (defaults are
    # conservative PCIe-class numbers; tools/prefix_cache_bench.py
    # --capacity-sweep reports real ones for the deployment):
    # sustained h2d bandwidth for the batched restore copy,
    host_tier_h2d_gbps: float = 8.0
    # fixed per-restore overhead (dispatch + sync + allocator work),
    host_tier_restore_overhead_s: float = 0.002
    # and sustained prefill throughput for the recompute alternative.
    host_tier_prefill_tok_s: float = 40000.0
    # Chunked prefill (Sarathi-style stall-free batching): admission no
    # longer prefills whole prompts eagerly — pending prompts split at page
    # granularity into chunks of at most prefill_chunk_tokens, and every
    # engine step with prompt work in flight runs ONE unified mixed
    # dispatch (runner.mixed_step): a single-token decode for every live
    # slot fused with up to the budget of prompt-tail tokens. Bounds the
    # inter-token latency a decode can observe under a long-prompt burst
    # by the chunk budget (max stall ~ chunk_tokens x per-token prefill
    # cost, see PERF.md "Chunked prefill") instead of the whole quadratic
    # prompt, and lets bandwidth-bound decode share the chip with
    # compute-bound prefill. Off by default: pure-throughput batch
    # workloads with no latency SLO prefer whole-prompt prefill.
    chunked_prefill: bool = False
    # Per-step prompt-token budget for chunked prefill. Must be a positive
    # multiple of page_size (chunks split at page granularity so every
    # resumed chunk starts page-aligned, reusing the prefix-cache
    # mid-sequence prefill path unchanged).
    prefill_chunk_tokens: int = 256
    # --- Long context (README "Long context") ---------------------------
    # Blockwise paged-flash prefill (pallas kernel path only): chunk
    # queries attend the paged KV history directly on a (slot, q_block,
    # page) grid with the chunk's pages written in-kernel, instead of the
    # XLA body's dense prefix gather + scatter — per-chunk HBM traffic
    # O(real context) instead of O(padded gather copy), per-dispatch VMEM
    # bounded by the page block. On by default: with kernels="xla" (or
    # paged_prefill=false) the reference body runs unchanged, and the
    # dispatch fallback ladder always retries on that reference body.
    paged_prefill: bool = True
    # Long-context serving (requires chunked_prefill + host_tier_bytes >
    # 0, engine-checked): admits requests whose worst-case page count
    # exceeds the device pool, provided their LIVE footprint fits —
    # sliding-window layers roll pages off as the chunk cursor advances,
    # and a request's cold completed-chunk pages page out to the host
    # tier between its turns (restored ahead of the chunks/decode steps
    # that need them). Preemption of a long request spills its pages to
    # host instead of recomputing from scratch when the spilled span
    # clears the host_tier_min_tokens break-even. Off by default: every
    # admission decision is byte-identical to today's engine.
    long_context: bool = False
    # Device-residency budget per long request, in pages. While a
    # long_context request is mid-prefill with more live pages than this,
    # its coldest completed-chunk pages demote to the host tier after its
    # chunk and restore (one batched h2d) just before its next turn —
    # bounding the device pages a single long context pins between its
    # chunks so co-tenants keep admitting. 0 (default) disables the
    # residency cap: pages move to host only on preemption.
    request_resident_pages: int = 0
    # Speculative decoding (draft-model-free): a host-side prompt-lookup /
    # n-gram proposer (infer/spec_decode.py) drafts up to speculate_tokens
    # continuation tokens per request from the request's OWN prompt+output
    # (and, with prefix_cache, from the radix tree's cached token paths);
    # one verify dispatch (runner.verify_step) scores every live slot's
    # drafts in a single pass over the weights and the engine accepts the
    # matched prefix plus one bonus/correction token. Greedy acceptance is
    # exact argmax match (spec-on output byte-identical to spec-off);
    # sampled acceptance uses rejection sampling, so the output
    # DISTRIBUTION is provably unchanged (the sampled stream itself draws
    # from a different key sequence). The win is self-repetitive text
    # (code, structured output, looping continuations): up to
    # speculate_tokens+1 emitted tokens per weight pass instead of 1. Off
    # by default; see PERF.md "Speculative decoding" and
    # tools/spec_decode_bench.py.
    speculative: bool = False
    # Max draft tokens verified per request per step (the verify dispatch
    # is always speculate_tokens+1 wide — rows with shorter/no drafts pad
    # via per-slot real lengths, so there is ONE jit specialization). The
    # per-request draft length adapts inside [1, speculate_tokens]:
    # halving on low acceptance, doubling back on full acceptance.
    speculate_tokens: int = 4
    # N-gram window for the prompt-lookup proposer: the last n tokens of
    # the context are matched (n from spec_ngram_max down to
    # spec_ngram_min) against earlier context; the continuation of the
    # most recent match is the draft.
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1
    # Token-TREE speculation width (1 = single-path chain drafting, the
    # default and the pre-tree behavior bit-for-bit). With width w > 1
    # the proposer collects up to w DISTINCT n-gram continuations per
    # request (context matches across n values + prefix-cache token
    # paths) and merges them into a token trie of at most
    # speculate_tokens nodes; one verify dispatch scores every branch
    # under a packed ancestor mask (the ragged kernel's intra-slot
    # causal mask generalized), the engine accepts the longest verified
    # root-path, compacts its KV into cursor-contiguous slots and rolls
    # back only the losing branches' pages. Depth stays acceptance-
    # adaptive (SpecState): on traffic where the single path keeps
    # missing, the halved depth frees verify-width for siblings —
    # breadth exactly where chains stall. Greedy output stays
    # byte-identical to spec-off; the chain-degenerate tree is bitwise
    # today's verify. Requires speculate_tokens + 1 <= 31 (int32 mask
    # words) and spec_tree_width <= speculate_tokens.
    spec_tree_width: int = 1
    # Draft-density gate: enter a verify step only when at least this
    # many live decode slots actually drafted (clamped to the live count,
    # so a fully-drafting batch always verifies). A step where ANY slot
    # drafts otherwise runs as a verify step for the WHOLE batch, costing
    # non-drafting co-tenants their multi-step decode window — one
    # repetitive tenant can tax a mostly-non-repetitive batch with one
    # host round-trip per token (the PERF.md scheduling tradeoff). 1 =
    # any draft triggers verification (the prior behavior); gated-off
    # steps are counted as ``spec_gated_steps`` in reset_timing().
    spec_min_draft_slots: int = 1
    # --- Fault tolerance / graceful degradation (README "Robustness") ---
    # Bounded admission queue: when a submit would push the wait queue past
    # this many requests, the lowest-priority (then nearest-deadline, then
    # newest) candidate — possibly the incoming request itself — is SHED
    # with a typed "shed" outcome instead of queueing unboundedly. None =
    # unbounded (the pre-robustness behavior).
    queue_limit: Optional[int] = None
    # Default per-request deadline, in seconds from submit();
    # submit(deadline_s=...) overrides per request. Expired requests are
    # reaped at step boundaries — pages released, full pages donated to the
    # prefix cache — exactly as preemption does. None = no deadline.
    default_deadline_s: Optional[float] = None
    # Degradation ladder rung 1: a failed Pallas dispatch retries on the
    # XLA reference path (same math, partitioner-visible) before the
    # step is declared failed. No-op when kernels="xla" already. Off by
    # default: the envelope catches ANY exception, a Mosaic compile
    # refusal included, so with it on a kernel that never compiled turns
    # into a warning line and a slower, green run. A deployment that
    # prefers degraded service to a failed step opts in; a failed
    # dispatch otherwise fails the step (and, past max_step_faults
    # consecutive steps, the process).
    dispatch_fallback: bool = False
    # How many XLA-fallback retry attempts one dispatch episode gets
    # (ISSUE 12 satellite). 1 = today's single retry; 0 behaves like
    # dispatch_fallback=false for the episode; >1 re-attempts the same
    # fallback program, absorbing multi-shot transients (preempted
    # neighbors, allocator races) that a single retry loses the step to.
    dispatch_retries: int = 1
    # Base for the jittered exponential backoff BETWEEN fallback retry
    # attempts: attempt i sleeps ~ base * 2^i * U[0.5, 1.0) seconds.
    # 0.0 (default) keeps today's immediate retry; set it when the fault
    # source needs wall-clock to clear (device queue drain, neighbor
    # preemption storm) so N replicas don't re-collide in lockstep.
    dispatch_retry_backoff_s: float = 0.0
    # Device-side NaN/Inf logit guard: the decode/verify/mixed programs
    # additionally return a per-slot all-finite flag (riding the existing
    # token fetch — no extra round trip) and the engine QUARANTINES a
    # non-finite slot: that request errors ("error:nan"), its private pages
    # are scrubbed and released WITHOUT prefix-cache donation, and its
    # neighbors' outputs stay byte-identical to a fault-free run. Off by
    # default so the compiled programs stay bit-for-bit the pre-guard ones.
    nan_guard: bool = False
    # Degradation ladder rung 2: after this many verify-path dispatch
    # faults, speculation auto-disables for the rest of the engine's life
    # (SpecDecodeStats.disabled_reason records why); decoding continues on
    # the plain window.
    spec_fault_limit: int = 3
    # A failed step (every dispatch path exhausted) is contained — the
    # engine logs it, counts it (reset_timing "failed_steps") and carries
    # on — until this many CONSECUTIVE steps fail, at which point the
    # fault is clearly not transient and the engine re-raises.
    max_step_faults: int = 4
    # Serving step watchdog: if no engine step completes within this many
    # seconds, flag a stall. Detection-only — the slow step's results are
    # KEPT and the step is counted as "stalled_steps" when it eventually
    # completes (deadline expiry handles the SLO consequences at the next
    # boundary); the process always survives, unlike
    # train.watchdog_action="abort". A dispatch that errors rather than
    # stalls is the failed-step path, not this one. None disables.
    watchdog_timeout_s: Optional[float] = None
    # --- Observability (orion_tpu/obs; README "Observability") ----------
    # Request-lifecycle span tracer: submit/admit/first-token/outcome
    # instants plus a span per device dispatch (prefill/decode/verify/
    # mixed), recorded in a bounded monotonic-clock ring and exportable as
    # Chrome trace-event JSON (Perfetto-loadable); dispatches also carry
    # jax.profiler.TraceAnnotation so host spans align with a device
    # profile captured over the same window. Off by default: the host path
    # is byte-identical to the untraced engine (compiled programs are
    # untouched in both modes).
    trace: bool = False
    # Ring capacity, in events (spans + instants). Bounds tracer memory on
    # long-lived engines; the flight recorder dumps this ring's recent
    # window.
    trace_ring: int = 16384
    # Export target for the Chrome trace (written by engine.close(), or on
    # demand via engine.export_trace(path)). Setting it implies recording
    # even when `trace` is off (a configured export target silently
    # producing nothing would be a foot-gun). None = record only.
    trace_path: Optional[str] = None
    # Flight recorder (orion_tpu/obs/flight.py): directory for postmortem
    # dumps auto-written when a degradation trigger fires — watchdog
    # stall, max_step_faults, NaN quarantine, speculation auto-disable.
    # Setting it also enables event recording (the dump needs a ring to
    # dump) even when `trace` is off. None disables.
    flight_dir: Optional[str] = None
    # Metrics-registry exporters, driven from reset_timing's drain point:
    # every drain appends one JSONL time-series row / rewrites one
    # Prometheus textfile from the drained window + pool/HBM gauges.
    metrics_jsonl: Optional[str] = None
    metrics_prom: Optional[str] = None
    # --- Grammar-constrained decoding (orion_tpu/constrain; ISSUE 16) --
    # Accept per-request regex / JSON-schema constraints: submit(...,
    # constraint=ConstraintSpec(...)) compiles the constraint to a
    # token-level DFA (memoized across requests by constraint hash) and
    # every emitted token is filtered through the request's legal-token
    # mask — composed into sampling.filter_logits, the SAME filtered
    # target greedy, sampled and speculative verification already share.
    # Enabling the flag also builds the verify dispatch programs
    # (constrained slots decode through the verify path: FSM forced runs
    # are free drafts and the per-position masks are host-precomputable
    # there, unlike the fused multi-token decode window whose next mask
    # would depend on a device-side sample). Off by default: an engine
    # without the flag compiles and serves byte-identically to today.
    constrained: bool = False
    # DFA size cap per compiled constraint: subset construction aborts
    # with a typed ConstraintError past this many states (a hostile or
    # pathological pattern fails at submit, not by OOM).
    constraint_max_states: int = 4096
    # Compiled-artifact LRU: how many distinct (pattern, vocab) DFAs the
    # process-wide memo keeps. Repeated schemas across requests hit the
    # cache and pay zero compile.
    constraint_cache: int = 32
    # Generation by diffusion over blocks (a model with model.block_length;
    # read by no other): a block's positions are decided over
    # ``denoising_steps`` forwards, each deciding the positions the sampler
    # is surest of, by ``remasking``: "low_confidence_static" decides an
    # even share of the block a forward (block_length / denoising_steps,
    # the remainder to the first forwards), "low_confidence_dynamic" every
    # position whose drawn token's probability passes
    # ``confidence_threshold``, and the static share where those are fewer.
    # Engine-wide: one schedule for every request. The defaults are the
    # schedule the benchmark's cell runs (PERF.md section 4); the dynamic
    # rule has no chip number behind it until trained weights give a
    # threshold something to pass.
    denoising_steps: int = 2
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9

    def __post_init__(self):
        # Domain checks only (each field alone), matching ModelConfig's
        # rule: dotted CLI overrides apply one field at a time, so
        # cross-field constraints live in the engine.
        if self.queue_limit is not None and self.queue_limit < 1:
            raise ValueError(
                f"inference.queue_limit={self.queue_limit} must be >= 1 "
                f"(or none for unbounded)"
            )
        if self.default_deadline_s is not None and self.default_deadline_s <= 0:
            raise ValueError(
                f"inference.default_deadline_s={self.default_deadline_s} "
                f"must be > 0 (or none)"
            )
        if self.spec_tree_width is None or self.spec_tree_width < 1:
            raise ValueError(
                f"inference.spec_tree_width={self.spec_tree_width} must "
                f"be >= 1 (1 = chain drafting)"
            )
        if self.denoising_steps is None or self.denoising_steps < 1:
            raise ValueError(
                f"inference.denoising_steps={self.denoising_steps} must "
                f"be >= 1")
        if self.remasking not in (
                "low_confidence_static", "low_confidence_dynamic"):
            raise ValueError(
                f"inference.remasking={self.remasking!r}; "
                f"low_confidence_static|low_confidence_dynamic")
        if self.spec_fault_limit is None or self.spec_fault_limit < 1:
            raise ValueError(
                f"inference.spec_fault_limit={self.spec_fault_limit} "
                f"must be >= 1"
            )
        if self.max_step_faults is None or self.max_step_faults < 1:
            raise ValueError(
                f"inference.max_step_faults={self.max_step_faults} "
                f"must be >= 1"
            )
        if self.watchdog_timeout_s is not None and self.watchdog_timeout_s <= 0:
            raise ValueError(
                f"inference.watchdog_timeout_s={self.watchdog_timeout_s} "
                f"must be > 0 (or none)"
            )
        if self.trace_ring is None or self.trace_ring < 1:
            raise ValueError(
                f"inference.trace_ring={self.trace_ring} must be >= 1"
            )
        if self.dispatch_retries is None or self.dispatch_retries < 0:
            raise ValueError(
                f"inference.dispatch_retries={self.dispatch_retries} must "
                f"be >= 0 (0 disables the XLA-fallback retry)"
            )
        if (
            self.dispatch_retry_backoff_s is None
            or self.dispatch_retry_backoff_s < 0
        ):
            raise ValueError(
                f"inference.dispatch_retry_backoff_s="
                f"{self.dispatch_retry_backoff_s} must be >= 0"
            )
        if self.constraint_max_states is None \
                or self.constraint_max_states < 2:
            raise ValueError(
                f"inference.constraint_max_states="
                f"{self.constraint_max_states} must be >= 2 (a DFA needs "
                f"at least a start and an accept state)"
            )
        if self.constraint_cache is None or self.constraint_cache < 1:
            raise ValueError(
                f"inference.constraint_cache={self.constraint_cache} "
                f"must be >= 1"
            )
        if self.host_tier_bytes is None or self.host_tier_bytes < 0:
            raise ValueError(
                f"inference.host_tier_bytes={self.host_tier_bytes} must "
                f"be >= 0 (0 disables the host tier)"
            )
        if self.host_tier_min_tokens is not None \
                and self.host_tier_min_tokens < 0:
            raise ValueError(
                f"inference.host_tier_min_tokens="
                f"{self.host_tier_min_tokens} must be >= 0 (or none for "
                f"the measured break-even)"
            )
        if self.host_tier_h2d_gbps is None or self.host_tier_h2d_gbps <= 0:
            raise ValueError(
                f"inference.host_tier_h2d_gbps={self.host_tier_h2d_gbps} "
                f"must be > 0"
            )
        if (
            self.host_tier_restore_overhead_s is None
            or self.host_tier_restore_overhead_s < 0
        ):
            raise ValueError(
                f"inference.host_tier_restore_overhead_s="
                f"{self.host_tier_restore_overhead_s} must be >= 0"
            )
        if (
            self.host_tier_prefill_tok_s is None
            or self.host_tier_prefill_tok_s <= 0
        ):
            raise ValueError(
                f"inference.host_tier_prefill_tok_s="
                f"{self.host_tier_prefill_tok_s} must be > 0"
            )
        if (
            self.request_resident_pages is None
            or self.request_resident_pages < 0
        ):
            raise ValueError(
                f"inference.request_resident_pages="
                f"{self.request_resident_pages} must be >= 0 (0 disables "
                f"the per-request residency cap)"
            )


@dataclass(frozen=True)
class RouterConfig:
    """Multi-replica serving router (infer/router.py; ISSUE 12).

    N InferenceEngine replicas behind one scheduler face: prefix-affinity
    placement (longest radix match wins, load tiebreak off the replica
    registry gauges), a per-replica health circuit breaker with half-open
    probing, and failover that re-queues a dead replica's in-flight
    requests on survivors under a retry budget — every request still ends
    in exactly one typed outcome. ``replicas=1`` is the plain engine
    behind a pass-through (byte-identical greedy streams).
    """

    replicas: int = 1
    # Prefix-affinity pin threshold: a request whose longest radix match
    # on SOME replica reaches this many tokens is placed there (ties break
    # on load); shorter matches route cold to the least-loaded replica.
    # Matches are page-granular, so sub-page thresholds behave as one page.
    affinity_min_tokens: int = 16
    # Failover retry budget per request: how many times a request may be
    # re-queued onto a survivor after its replica died or circuit-broke
    # before it is SHED with a typed outcome (never a silent drop).
    retry_budget: int = 2
    # Jittered exponential backoff between failover attempts, in ROUTER
    # steps: attempt i waits base * 2^(i-1) + U{0..jitter} steps before
    # re-placement. Step-denominated (not wall clock) so the schedule is
    # deterministic under test and scales with serving cadence.
    retry_backoff_steps: int = 1
    retry_backoff_jitter: int = 1
    # Health circuit breaker: a replica observed unhealthy on this many
    # CONSECUTIVE router steps trips OPEN (stops receiving placements;
    # its in-flight work fails over). "Unhealthy" is any of: consecutive
    # failed engine steps >= break_failed_steps, a watchdog-stalled step
    # since the last sweep, or >= break_quarantined NaN quarantines since
    # the last sweep (a poison storm). A replica whose step() RAISES
    # (DispatchFault/MemoryError escalation) trips immediately.
    break_after: int = 1
    break_failed_steps: int = 2
    break_quarantined: int = 2
    # OPEN -> HALF_OPEN after this many router steps: the next eligible
    # request is routed to the replica as a probe; a completed probe
    # closes the breaker, any new trip re-opens it (and re-arms the
    # timer), so a flapping replica converges to mostly-open.
    probe_after_steps: int = 8
    # Breaker-postmortem routing context (ISSUE 14 satellite): the router
    # keeps a ring of the last N placement decisions (replica,
    # match_tokens, the load gauges read at placement) and attaches it to
    # the flight-recorder note a breaker trip writes — a postmortem shows
    # WHY traffic was where it was when the breaker opened.
    decision_log: int = 16
    # Backoff-jitter PRNG seed (placement itself is deterministic).
    seed: int = 0
    # Disaggregated prefill/decode serving (ISSUE 20): "prefill:K,decode:M"
    # splits the fleet into K prefill replicas (take new submissions, run
    # prompts, then hand the request off) and M decode replicas (accept
    # only migrated-in work, admitted as zero-prefill warm starts off the
    # migrated KV pages). K + M must equal ``replicas``; the replica
    # indices assign in spec order (prefill first). Unset = today's
    # symmetric fleet, byte-identical behavior.
    roles: Optional[str] = None
    # Migrate after EVERY completed prefill chunk instead of once at
    # prompt completion — overlaps migration with the remaining prefill
    # at the cost of one copy envelope per chunk. Requires roles.
    migrate_per_chunk: bool = False

    def __post_init__(self):
        if self.replicas is None or self.replicas < 1:
            raise ValueError(
                f"router.replicas={self.replicas} must be >= 1"
            )
        if self.roles is not None:
            counts = parse_roles(self.roles)
            total = sum(counts.values())
            if total != self.replicas:
                raise ValueError(
                    f"router.roles={self.roles!r} names {total} replicas "
                    f"but router.replicas={self.replicas}"
                )
            if counts.get("prefill", 0) < 1 or counts.get("decode", 0) < 1:
                raise ValueError(
                    f"router.roles={self.roles!r} needs at least one "
                    "prefill and one decode replica"
                )
        if self.migrate_per_chunk and self.roles is None:
            raise ValueError(
                "router.migrate_per_chunk requires router.roles"
            )
        if self.retry_budget is None or self.retry_budget < 0:
            raise ValueError(
                f"router.retry_budget={self.retry_budget} must be >= 0"
            )
        for name in (
            "affinity_min_tokens", "retry_backoff_steps",
            "retry_backoff_jitter",
        ):
            v = getattr(self, name)
            if v is None or v < 0:
                raise ValueError(f"router.{name}={v} must be >= 0")
        for name in (
            "break_after", "break_failed_steps", "break_quarantined",
            "probe_after_steps", "decision_log",
        ):
            v = getattr(self, name)
            if v is None or v < 1:
                raise ValueError(f"router.{name}={v} must be >= 1")


def parse_per_class(spec: str) -> dict[int, dict[str, float]]:
    """Parse the ``slo.per_class`` objective spec: semicolon-separated
    ``<class>:<metric>=<target_ms>[,<metric>=<target_ms>]`` entries, e.g.
    ``"2:ttft=200,itl=40;0:ttft=1000"`` — priority class 2 must see TTFT
    <= 200 ms and ITL <= 40 ms, class 0 TTFT <= 1000 ms. Metrics are
    ``ttft`` | ``itl``; returns ``{cls: {metric: target_ms}}``. Lives in
    config.py (pure string parsing, no deps) so SLOConfig validation and
    obs/slo.py's objective builder share ONE grammar."""
    out: dict[int, dict[str, float]] = {}
    if not spec:
        return out
    for entry in spec.split(";"):
        entry = entry.strip()
        if not entry:
            continue
        if ":" not in entry:
            raise ValueError(
                f"slo.per_class entry {entry!r} needs <class>:<metric>="
                f"<target_ms>[,...]"
            )
        cls_s, targets_s = entry.split(":", 1)
        try:
            cls = int(cls_s.strip())
        except ValueError as e:
            raise ValueError(
                f"slo.per_class class {cls_s!r} is not an int"
            ) from e
        targets: dict[str, float] = {}
        for kv in targets_s.split(","):
            kv = kv.strip()
            if "=" not in kv:
                raise ValueError(
                    f"slo.per_class target {kv!r} needs <metric>="
                    f"<target_ms>"
                )
            metric, ms_s = (s.strip() for s in kv.split("=", 1))
            if metric not in ("ttft", "itl"):
                raise ValueError(
                    f"slo.per_class metric {metric!r} must be ttft|itl"
                )
            try:
                ms = float(ms_s)
            except ValueError as e:
                raise ValueError(
                    f"slo.per_class target {ms_s!r} is not a number"
                ) from e
            if ms <= 0:
                raise ValueError(
                    f"slo.per_class target {metric}={ms} must be > 0 ms"
                )
            targets[metric] = ms
        if cls in out:
            raise ValueError(
                f"slo.per_class repeats class {cls}"
            )
        out[cls] = targets
    return out


def parse_roles(spec: str) -> dict[str, int]:
    """Parse the ``router.roles`` disaggregation spec: comma-separated
    ``<role>:<count>`` entries, e.g. ``"prefill:1,decode:2"``. Roles are
    ``prefill`` | ``decode``; returns ``{role: count}``. Lives in
    config.py (pure string parsing, no deps) so RouterConfig validation
    and infer/router.py's role assignment share ONE grammar."""
    out: dict[str, int] = {}
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        if ":" not in entry:
            raise ValueError(
                f"router.roles entry {entry!r} needs <role>:<count>"
            )
        role, count_s = (s.strip() for s in entry.split(":", 1))
        if role not in ("prefill", "decode"):
            raise ValueError(
                f"router.roles role {role!r} must be prefill|decode"
            )
        try:
            count = int(count_s)
        except ValueError as e:
            raise ValueError(
                f"router.roles count {count_s!r} is not an int"
            ) from e
        if count < 1:
            raise ValueError(
                f"router.roles count {role}:{count} must be >= 1"
            )
        if role in out:
            raise ValueError(f"router.roles repeats role {role}")
        out[role] = count
    if not out:
        raise ValueError(f"router.roles={spec!r} names no roles")
    return out


@dataclass(frozen=True)
class SLOConfig:
    """Serving SLO objectives + burn-rate monitoring (obs/slo.py;
    ISSUE 14). Off by default (no objective configured -> no monitor, no
    per-step cost). The router judges per-priority-class TTFT/ITL
    against these objectives over rolling windows; a window burning the
    error budget faster than ``burn_threshold`` is a typed
    ``slo_breach`` (tracer instant + flight-recorder note/dump +
    registry gauge)."""

    # Fleet-wide latency objectives in ms (every priority class counts
    # toward them). None disables that objective.
    ttft_ms: Optional[float] = None
    itl_ms: Optional[float] = None
    # Fraction of events that must meet the target: 0.99 = 1% error
    # budget. A window's burn rate is (violating fraction) / (1 - goal).
    goal: float = 0.99
    # Rolling judgment window, seconds. Windows open at the first
    # observation and close at the first sweep past window_s.
    window_s: float = 5.0
    # Burn rate above which a window is a breach: 1.0 = budget burning
    # exactly at the allowed rate (the classic page threshold is higher,
    # e.g. 14.4 for a 1h window of a 30d budget — serving steps are
    # seconds, so the default alerts on any over-budget window).
    burn_threshold: float = 1.0
    # Minimum observations before a window is judged for an objective —
    # an empty (or too-thin) class window is no evidence, never a breach.
    min_events: int = 1
    # Per-priority-class overrides: "<cls>:<metric>=<target_ms>,...;..."
    # e.g. "2:ttft=200,itl=40;0:ttft=1000" (see parse_per_class).
    per_class: str = ""

    @property
    def enabled(self) -> bool:
        return (
            self.ttft_ms is not None
            or self.itl_ms is not None
            or bool(self.per_class)
        )

    def __post_init__(self):
        for name in ("ttft_ms", "itl_ms"):
            v = getattr(self, name)
            if v is not None and v <= 0:
                raise ValueError(f"slo.{name}={v} must be > 0 (or none)")
        if self.goal is None or not 0.0 < self.goal < 1.0:
            raise ValueError(
                f"slo.goal={self.goal} must be in (0, 1) — 1.0 leaves "
                f"no error budget to burn"
            )
        if self.window_s is None or self.window_s <= 0:
            raise ValueError(f"slo.window_s={self.window_s} must be > 0")
        if self.burn_threshold is None or self.burn_threshold <= 0:
            raise ValueError(
                f"slo.burn_threshold={self.burn_threshold} must be > 0"
            )
        if self.min_events is None or self.min_events < 1:
            raise ValueError(
                f"slo.min_events={self.min_events} must be >= 1"
            )
        parse_per_class(self.per_class)   # raises on a malformed spec


@dataclass(frozen=True)
class RuntimeConfig:
    # jax.distributed coordination (multi-host). None => single-process.
    coordinator_address: Optional[str] = None
    num_processes: int = 1
    process_id: int = 0
    # Required backend ("cpu" for fake-device testing, "tpu" for a run that
    # must not fall to the CPU): initialize() restricts JAX to it and raises
    # unless it is the default backend. None = whatever JAX picks.
    platform: Optional[str] = None
    deterministic: bool = False       # bitwise-reproducible mode
    debug_nans: bool = False          # TPU-native sanitizer (SURVEY.md §6)
    # checkify validation mode (SURVEY.md §6 "Race detection / sanitizers"):
    # functionalized device-side float (nan/inf) + out-of-bounds-index
    # checks on the train step, raised host-side after each step. Slower
    # (adds a per-step error fetch); see SANITIZERS.md.
    checkify: bool = False

    def __post_init__(self):
        if self.num_processes < 1:
            raise ValueError(
                f"runtime.num_processes={self.num_processes} must be >= 1"
            )
        if not 0 <= self.process_id < self.num_processes:
            raise ValueError(
                f"runtime.process_id={self.process_id} not in "
                f"[0, {self.num_processes})"
            )
        if self.num_processes > 1 and not self.coordinator_address:
            raise ValueError(
                "runtime.num_processes > 1 requires "
                "runtime.coordinator_address"
            )
        if self.platform is not None and self.platform not in (
            "cpu", "tpu", "gpu"
        ):
            raise ValueError(
                f"runtime.platform={self.platform!r}; cpu|tpu|gpu|None"
            )


# Pure composite: every leaf validates itself in its own __post_init__ and
# the cross-SECTION checks need runtime context (mesh shapes, kernel
# availability), so they live in Trainer.__init__ / InferenceEngine.__init__.
@dataclass(frozen=True)
# orion: allow[config-validation] composite node; leaves self-validate, cross-field checks live in the consumers
class Config:
    model: ModelConfig = field(default_factory=ModelConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    inference: InferenceConfig = field(default_factory=InferenceConfig)
    router: RouterConfig = field(default_factory=RouterConfig)
    slo: SLOConfig = field(default_factory=SLOConfig)
    runtime: RuntimeConfig = field(default_factory=RuntimeConfig)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, default=str)


# ---------------------------------------------------------------------------
# Overrides:  dotted key=value strings, e.g.  model.n_layers=4 data.batch_size=2
# ---------------------------------------------------------------------------


def _parse_value(raw: str, target_type: Any) -> Any:
    if raw.lower() in ("none", "null"):
        return None
    origin = typing.get_origin(target_type)
    if origin is typing.Union:  # Optional[X] / Union[X, None] -> X
        non_none = [a for a in typing.get_args(target_type) if a is not type(None)]
        return _parse_value(raw, non_none[0])
    if origin is tuple or target_type is tuple:
        # Accept "(5,7)", "[5,7]", "5,7", and quoted-string forms like
        # '("dp",)'; elements are auto-typed (int/float/str).
        raw = raw.strip()
        if raw.startswith("(") and raw.endswith(")"):
            raw = raw[1:-1]
        if not raw:
            return ()
        if raw.startswith("["):
            return tuple(json.loads(raw))
        return tuple(
            _auto(v.strip().strip("'\""))
            for v in raw.split(",")
            if v.strip()  # tolerate the trailing comma of 1-tuples
        )
    if target_type is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    if target_type is int:
        return int(raw)
    if target_type is float:
        if raw.lower() in ("true", "false"):    # a flag that took a number
            return raw.lower() == "true"
        return float(raw)
    return raw


def _auto(raw: str) -> Any:
    for cast in (int, float):
        try:
            return cast(raw)
        except ValueError:
            pass
    return raw


def apply_overrides(cfg: Config, overrides: Sequence[str]) -> Config:
    """Apply ``section.key=value`` overrides to a Config, returning a new one.

    Same-section overrides are batched into ONE ``replace`` so a leaf
    dataclass's ``__post_init__`` cross-field checks see the whole
    override set at once — ``data.source=memmap data.path=...`` must
    validate identically in either flag order (ISSUE 15: the leaf configs
    now all validate at construction). Duplicate keys keep last-wins."""
    groups: dict[tuple, dict] = {}
    for item in overrides:
        if "=" not in item:
            raise ValueError(f"override must be key=value, got {item!r}")
        key, raw = item.split("=", 1)
        parts = tuple(key.split("."))
        groups.setdefault(parts[:-1], {})[parts[-1]] = raw
    for parent, kv in groups.items():
        cfg = _apply_group(cfg, parent, kv)
    return cfg


def _apply_group(node: Any, parent: Sequence[str], kv: Mapping[str, str]):
    names = {f.name for f in fields(node)}
    if parent:
        name = parent[0]
        if name not in names:
            valid = ", ".join(f.name for f in fields(node))
            raise ValueError(f"unknown config key {name!r}; valid: {valid}")
        return replace(
            node, **{name: _apply_group(getattr(node, name), parent[1:], kv)}
        )
    # `from __future__ import annotations` stringifies f.type; resolve the
    # real type objects so Optional[int] etc. parse correctly.
    hints = typing.get_type_hints(type(node))
    updates = {}
    for name, raw in kv.items():
        if name not in names:
            valid = ", ".join(f.name for f in fields(node))
            raise ValueError(f"unknown config key {name!r}; valid: {valid}")
        try:
            updates[name] = _parse_value(raw, hints[name])
        except ValueError as e:
            raise ValueError(f"bad value for config key {name!r}: {e}") from e
    return replace(node, **updates)


# ---------------------------------------------------------------------------
# Preset registry — the five baseline workloads (BASELINE.json:6-12) plus
# small variants for tests and the single-chip dev box.
# ---------------------------------------------------------------------------

_PRESETS: dict[str, Callable[[], Config]] = {}


def register_preset(name: str):
    def deco(fn: Callable[[], Config]):
        _PRESETS[name] = fn
        return fn
    return deco


def get_config(preset: str, overrides: Sequence[str] = ()) -> Config:
    if preset not in _PRESETS:
        raise ValueError(f"unknown preset {preset!r}; have: {sorted(_PRESETS)}")
    return apply_overrides(_PRESETS[preset](), overrides)


def list_presets() -> Sequence[str]:
    return sorted(_PRESETS)


def _gpt2_model(**kw) -> ModelConfig:
    base = dict(
        name="gpt2-125m", vocab_size=50304, max_seq_len=1024,
        d_model=768, n_layers=12, n_heads=12, n_kv_heads=12, d_ff=3072,
        pos_embedding="learned", norm="layernorm", norm_eps=1e-5,
        activation="gelu", tie_embeddings=True, attn_bias=True, mlp_bias=True,
        dtype="float32", kernels="xla",
    )
    base.update(kw)
    return ModelConfig(**base)


def _llama3_8b_model(**kw) -> ModelConfig:
    base = dict(
        name="llama3-8b", vocab_size=128256, max_seq_len=8192,
        d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
        pos_embedding="rope", rope_theta=500_000.0, norm="rmsnorm",
        norm_eps=1e-5, activation="swiglu", tie_embeddings=False,
        dtype="bfloat16", kernels="xla", remat="full",
    )
    base.update(kw)
    return ModelConfig(**base)


def _llama3_70b_model(**kw) -> ModelConfig:
    base = dict(
        name="llama3-70b", vocab_size=128256, max_seq_len=8192,
        d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8, d_ff=28672,
        pos_embedding="rope", rope_theta=500_000.0, norm="rmsnorm",
        norm_eps=1e-5, activation="swiglu", tie_embeddings=False,
        dtype="bfloat16", kernels="xla", remat="full",
    )
    base.update(kw)
    return ModelConfig(**base)


def _mixtral_model(**kw) -> ModelConfig:
    base = dict(
        name="mixtral-8x7b", vocab_size=32000, max_seq_len=4096,
        d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
        pos_embedding="rope", rope_theta=1_000_000.0, norm="rmsnorm",
        norm_eps=1e-5, activation="swiglu", tie_embeddings=False,
        n_experts=8, n_experts_per_token=2,
        dtype="bfloat16", kernels="xla", remat="full",
    )
    base.update(kw)
    return ModelConfig(**base)


def _laguna_model(**kw) -> ModelConfig:
    """Laguna-S-2.1 (poolside, config.json): 48 layers, the first dense and
    full, then (window, window, window, full) periods; 48 query heads in
    full layers and 72 in window layers over 8 KV heads; YaRN on half of
    each head in full layers, plain theta 1e4 in window layers; a per-head
    sigmoid gate on the attention output; 256 experts 1024 wide, top-10,
    gates renormalised and scaled by 2.5, and a shared expert."""
    types = ("full_attention",) + ("sliding_attention",) * 3
    base = dict(
        name="laguna-s-2.1", vocab_size=100352, max_seq_len=4096,
        d_model=3072, n_layers=48, n_heads=48, n_kv_heads=8, head_dim=128,
        d_ff=12288, pos_embedding="rope", norm="rmsnorm", norm_eps=1e-6,
        activation="swiglu", tie_embeddings=False, sliding_window=512,
        layer_types=types * 12,
        n_heads_per_layer=(48, 72, 72, 72) * 12,
        rope_full=RopeConfig(
            theta=500_000.0, rotary_fraction=0.5, yarn_factor=128.0,
            yarn_original_max_pos=8192, yarn_beta_fast=32.0,
            yarn_beta_slow=1.0, attention_factor=1.4852030263919618),
        rope_sliding=RopeConfig(theta=10_000.0),
        attn_gate="per-head",
        n_experts=256, router_width=256, n_experts_per_token=10,
        n_dense_layers=1, moe_d_ff=1024, shared_expert_d_ff=1024,
        router_scale=2.5,
        # Dropless: an expert's capacity is at least the row length from
        # router_width / top-k = 25.6 up (the published model drops none).
        capacity_factor=26.0,
        dtype="bfloat16", kernels="xla", remat="full",
    )
    base.update(kw)
    return ModelConfig(**base)


@register_preset("gpt2-125m")
def _p_gpt2() -> Config:
    """Baseline config 1: GPT-2 125M single-device CPU-runnable smoke test."""
    return Config(
        model=_gpt2_model(),
        data=DataConfig(batch_size=8, seq_len=1024),
        train=TrainConfig(num_steps=1000),
    )


@register_preset("llama3-8b-dp")
def _p_llama8b_dp() -> Config:
    """Baseline config 2: Llama-3 8B data-parallel (DDP -> XLA all-reduce)."""
    return Config(
        model=_llama3_8b_model(),
        parallel=ParallelConfig(dp=64),
        data=DataConfig(batch_size=64, seq_len=8192),
        optimizer=OptimizerConfig(learning_rate=3e-4),
    )


@register_preset("mistral-7b-fsdp")
def _p_mistral7b() -> Config:
    """Mistral-7B: Llama-family architecture + sliding-window attention
    (model.sliding_window; the flash kernel skips blocks behind the
    window). Weights import via models.convert.from_hf_llama (same state-
    dict schema)."""
    return Config(
        model=ModelConfig(
            name="mistral-7b", vocab_size=32000, max_seq_len=8192,
            d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
            d_ff=14336, pos_embedding="rope", rope_theta=10_000.0,
            norm="rmsnorm", norm_eps=1e-5, activation="swiglu",
            tie_embeddings=False, sliding_window=4096,
            dtype="bfloat16", kernels="pallas", remat="full",
        ),
        parallel=ParallelConfig(fsdp=8),
        data=DataConfig(batch_size=32, seq_len=8192),
        optimizer=OptimizerConfig(learning_rate=3e-4),
    )


@register_preset("llama3-8b-256k-ring")
def _p_llama8b_256k() -> Config:
    """Long-context flagship (SURVEY.md §6 "Long-context"): Llama-3 8B at a
    262,144-token context via striped-ring sequence parallelism on an
    sp-heavy v5p-64 mesh (fsdp=4 x sp=16). The striped (zigzag-class)
    layout needs S % sp^2 == 0: 262144 = 2^18, sp^2 = 256. Every batch row
    is one whole 256k document; activations stay sequence-sharded through
    the whole block stack (norms/MLP are pointwise over sequence), and the
    flash kernel's dynamic block-skip keeps the causal 2x saving inside
    each ring step."""
    return Config(
        model=_llama3_8b_model(max_seq_len=262_144, kernels="pallas"),
        parallel=ParallelConfig(
            fsdp=4, sp=16, sequence_method="ring_striped"
        ),
        data=DataConfig(batch_size=4, seq_len=262_144),
        optimizer=OptimizerConfig(learning_rate=1.5e-4),
    )


@register_preset("gemma2-9b-fsdp")
def _p_gemma2_9b() -> Config:
    """Gemma-2-9B: interleaved local/global attention (window on even
    layers), pre+post norms with (1+w) RMSNorm, GeGLU, sqrt(d) embedding
    scale, dual logit softcaps, tied embeddings. Weights import via
    models.convert.from_hf_gemma2."""
    return Config(
        model=ModelConfig(
            name="gemma2-9b", vocab_size=256_000, max_seq_len=8192,
            d_model=3584, n_layers=42, n_heads=16, n_kv_heads=8,
            head_dim=256, d_ff=14336, pos_embedding="rope",
            rope_theta=10_000.0, norm="rmsnorm", norm_eps=1e-6,
            norm_scale_plus_one=True, post_norms=True, embed_scale=True,
            activation="geglu", tie_embeddings=True,
            sliding_window=4096, sliding_window_pattern=2,
            attn_logit_softcap=50.0, final_logit_softcap=30.0,
            query_scale=256.0 ** -0.5,
            dtype="bfloat16", kernels="pallas", remat="full",
        ),
        parallel=ParallelConfig(fsdp=8),
        data=DataConfig(batch_size=32, seq_len=8192),
        optimizer=OptimizerConfig(learning_rate=3e-4),
    )


@register_preset("qwen2-7b-fsdp")
def _p_qwen2_7b() -> Config:
    """Qwen2/Qwen2.5-7B: Llama-family architecture + q/k/v projection
    biases (no o bias). Weights import via models.convert.from_hf_qwen2."""
    return Config(
        model=ModelConfig(
            name="qwen2-7b", vocab_size=152_064, max_seq_len=8192,
            d_model=3584, n_layers=28, n_heads=28, n_kv_heads=4,
            d_ff=18944, pos_embedding="rope", rope_theta=1_000_000.0,
            norm="rmsnorm", norm_eps=1e-6, activation="swiglu",
            tie_embeddings=False, attn_bias=True, attn_out_bias=False,
            dtype="bfloat16", kernels="pallas", remat="full",
        ),
        parallel=ParallelConfig(fsdp=8),
        data=DataConfig(batch_size=32, seq_len=8192),
        optimizer=OptimizerConfig(learning_rate=3e-4),
    )


@register_preset("llama3-70b-fsdp")
def _p_llama70b_fsdp() -> Config:
    """Baseline config 3: Llama-3 70B FSDP/ZeRO-3 sharded."""
    return Config(
        model=_llama3_70b_model(),
        parallel=ParallelConfig(fsdp=64),
        data=DataConfig(batch_size=64, seq_len=8192),
        optimizer=OptimizerConfig(learning_rate=1.5e-4),
    )


@register_preset("mixtral-8x7b-ep")
def _p_mixtral() -> Config:
    """Baseline config 4: Mixtral 8x7B MoE, expert-parallel all-to-all."""
    return Config(
        model=_mixtral_model(),
        parallel=ParallelConfig(fsdp=8, ep=8),
        data=DataConfig(batch_size=64, seq_len=4096),
    )


@register_preset("llama3-8b-infer")
def _p_llama8b_infer() -> Config:
    """Baseline config 5: Llama-3 8B continuous-batching inference."""
    return Config(
        model=_llama3_8b_model(),
        inference=InferenceConfig(max_seq_len=8192, num_pages=2048),
    )


# -- small variants for tests / the single-chip dev box ---------------------


@register_preset("tiny")
def _p_tiny() -> Config:
    """Tiny GPT-2-family model for CPU tests."""
    return Config(
        model=_gpt2_model(name="tiny", vocab_size=256, max_seq_len=128,
                          d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
                          d_ff=256),
        data=DataConfig(batch_size=4, seq_len=64),
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=5),
        train=TrainConfig(num_steps=20, log_interval=5),
        checkpoint=CheckpointConfig(save_interval_steps=10, max_to_keep=2),
    )


@register_preset("tiny-llama")
def _p_tiny_llama() -> Config:
    """Tiny Llama-family (RoPE/RMSNorm/SwiGLU/GQA) model for CPU tests."""
    return Config(
        model=_llama3_8b_model(name="tiny-llama", vocab_size=256,
                               max_seq_len=128, d_model=64, n_layers=2,
                               n_heads=4, n_kv_heads=2, d_ff=128,
                               dtype="float32", kernels="xla", remat="none"),
        data=DataConfig(batch_size=4, seq_len=64),
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=5),
        train=TrainConfig(num_steps=20, log_interval=5),
    )


@register_preset("tiny-mixtral")
def _p_tiny_mixtral() -> Config:
    """Tiny Mixtral-family (MoE) model for CPU tests."""
    return Config(
        model=_mixtral_model(name="tiny-mixtral", vocab_size=256,
                             max_seq_len=128, d_model=64, n_layers=2,
                             n_heads=4, n_kv_heads=2, d_ff=128, n_experts=4,
                             n_experts_per_token=2, dtype="float32",
                             kernels="xla", remat="none"),
        data=DataConfig(batch_size=4, seq_len=64),
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=5),
        train=TrainConfig(num_steps=20, log_interval=5),
    )


@register_preset("tiny-gemma2")
def _p_tiny_gemma2() -> Config:
    """Tiny Gemma-2-family model (interleaved local/global attention,
    post-norms, GeGLU, dual softcaps) for CPU tests."""
    return Config(
        model=ModelConfig(
            name="tiny-gemma2", vocab_size=256, max_seq_len=128,
            d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, pos_embedding="rope", rope_theta=10_000.0,
            norm="rmsnorm", norm_eps=1e-6, norm_scale_plus_one=True,
            post_norms=True, embed_scale=True, activation="geglu",
            tie_embeddings=True, sliding_window=16,
            sliding_window_pattern=2, attn_logit_softcap=50.0,
            final_logit_softcap=30.0, query_scale=16.0 ** -0.5,
            dtype="float32", kernels="xla", remat="none",
        ),
        data=DataConfig(batch_size=4, seq_len=64),
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=5),
        train=TrainConfig(num_steps=20, log_interval=5),
    )


@register_preset("laguna-s-2.1")
def _p_laguna() -> Config:
    """Laguna-S-2.1 at its published sizes, for serving (a deployment
    holds a share: model.n_experts / model.expert_offset, the vocabulary
    and the depth its chips hold)."""
    return Config(
        model=_laguna_model(),
        inference=InferenceConfig(max_seq_len=4096, page_size=64),
    )


@register_preset("tiny-laguna")
def _p_tiny_laguna() -> Config:
    """Tiny Laguna-family model for CPU tests: a dense lead layer, one
    period of (window x3, full) and one layer more, so the period's end and
    the tail are crossed; 6 and 4 query heads over 2 KV heads (groups 3 and
    2); 16 experts top-4 with a shared one; YaRN on half of each head in
    full layers; a window shorter than the test prompts."""
    types = ("full_attention",) + ("sliding_attention",) * 3
    return Config(
        model=_laguna_model(
            name="tiny-laguna", vocab_size=256, max_seq_len=128, d_model=64,
            n_layers=6, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
            sliding_window=8, layer_types=types * 2,
            n_heads_per_layer=(4, 6, 6, 6) * 2,
            rope_full=RopeConfig(
                theta=10_000.0, rotary_fraction=0.5, yarn_factor=8.0,
                yarn_original_max_pos=16, yarn_beta_fast=4.0,
                yarn_beta_slow=1.0, attention_factor=1.2),
            n_experts=16, router_width=16, n_experts_per_token=4,
            moe_d_ff=32, shared_expert_d_ff=32, capacity_factor=4.0,
            dtype="float32", remat="none"),
        data=DataConfig(batch_size=4, seq_len=64),
        inference=InferenceConfig(max_seq_len=128, page_size=8,
                                  num_pages=128, max_batch_size=4,
                                  prefill_chunk=16),
    )


# MiMo-V2.5's published per-layer lists (config.json): 1 = window layer /
# sparse feed-forward.
MIMO_HYBRID_LAYER_PATTERN = (0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1) * 7 + (0,)
MIMO_MOE_LAYER_FREQ = (0,) + (1,) * 47


def _mimo_model(**kw) -> ModelConfig:
    """MiMo-V2.5's language model (XiaomiMiMo, config.json, model_type
    mimo_v2): 48 layers, the first dense; 9 full layers (4 K/V heads, theta
    1e7) among 39 window layers of 128 positions (8 K/V heads, theta 1e4, a
    learned sink a query head); 64 query heads, keys 192 wide of which the
    first 64 are rotated, values 128 wide and scaled by 0.707; 256 experts
    2048 wide, top-8 on sigmoid scores under a selection bias, gates
    renormalised, no shared expert. The vision and audio towers and the
    multi-token-prediction layers are not part of it."""
    rot = 0.334
    base = dict(
        name="mimo-v2.5", vocab_size=152_576, max_seq_len=18_432,
        d_model=4096, n_layers=48, n_heads=64, n_kv_heads=4, head_dim=192,
        v_head_dim=128, n_kv_heads_sliding=8, value_scale=0.707,
        attn_sink="sliding",
        d_ff=16_384, pos_embedding="rope", rope_theta=10_000_000.0,
        norm="rmsnorm", norm_eps=1e-5,
        activation="swiglu", tie_embeddings=False, sliding_window=128,
        layer_types=tuple(
            "sliding_attention" if w else "full_attention"
            for w in MIMO_HYBRID_LAYER_PATTERN),
        rope_full=RopeConfig(theta=10_000_000.0, rotary_fraction=rot),
        rope_sliding=RopeConfig(theta=10_000.0, rotary_fraction=rot),
        n_experts=256, router_width=256, n_experts_per_token=8,
        n_dense_layers=MIMO_MOE_LAYER_FREQ.index(1), moe_d_ff=2048,
        router_score="sigmoid", router_bias=True,
        # Dropless: an expert's capacity is at least the row length from
        # router_width / top-k = 32 up (the published model drops none).
        capacity_factor=32.0,
        dtype="bfloat16", param_dtype="bfloat16", kernels="pallas",
        remat="full",
    )
    base.update(kw)
    return ModelConfig(**base)


@register_preset("mimo-v2.5")
def _p_mimo() -> Config:
    """MiMo-V2.5's language model at its published sizes, for serving (a
    deployment holds a share: model.n_experts / model.expert_offset, the
    vocabulary and the depth its chips hold)."""
    return Config(
        model=_mimo_model(),
        inference=InferenceConfig(max_seq_len=18_432, page_size=64),
    )


@register_preset("tiny-mimo")
def _p_tiny_mimo() -> Config:
    """Tiny MiMo-family model for CPU tests: a dense full lead layer and two
    periods of (window, window, full); 8 query heads over 2 K/V heads in
    full layers and 4 in window layers, keys 24 wide (8 rotated) and values
    16; a window of 8 positions under pages of 8 (a ring of 2 pages a
    slot), shorter than the test prompts; 16 experts top-4, sigmoid scores
    under a bias, no shared expert."""
    types = ("full_attention",) + (
        "sliding_attention", "sliding_attention", "full_attention") * 2
    return Config(
        model=_mimo_model(
            name="tiny-mimo", vocab_size=256, max_seq_len=128, d_model=64,
            n_layers=7, n_heads=8, n_kv_heads=2, n_kv_heads_sliding=4,
            head_dim=24, v_head_dim=16, d_ff=128, sliding_window=8,
            layer_types=types,
            n_experts=16, router_width=16, n_experts_per_token=4,
            moe_d_ff=32, capacity_factor=4.0,
            dtype="float32", param_dtype="float32", kernels="xla",
            remat="none"),
        data=DataConfig(batch_size=4, seq_len=64),
        inference=InferenceConfig(max_seq_len=128, page_size=8,
                                  num_pages=64, max_batch_size=4,
                                  prefill_chunk=16, decode_window=4),
    )


def _sdar_model(**kw) -> ModelConfig:
    """SDAR-30B-A3B-Chat (JetLM, config.json, model_type sdar_moe): the
    Qwen3-MoE layer (48 alike: 32 query heads over 4 K/V heads of 128 with
    a norm a head, 128 experts 768 wide, top-8 of a softmax renormalised
    over the picks, no shared expert) generated by diffusion over blocks
    of 4 under a block-causal mask; an undecided position is fed as
    <|MASK|> (151669)."""
    base = dict(
        name="sdar-30b-a3b", vocab_size=151_936, max_seq_len=32_768,
        d_model=2048, n_layers=48, n_heads=32, n_kv_heads=4, head_dim=128,
        d_ff=6144, pos_embedding="rope", rope_theta=1_000_000.0,
        norm="rmsnorm", norm_eps=1e-6, activation="swiglu",
        tie_embeddings=False, qk_norm=True,
        n_experts=128, n_experts_per_token=8, moe_d_ff=768,
        # Dropless: an expert's bucket holds a whole row from n_experts /
        # top-k = 16 up (the published model drops none).
        capacity_factor=16.0,
        block_length=4, mask_token_id=151_669,
        dtype="bfloat16", param_dtype="bfloat16", kernels="pallas",
        remat="full",
    )
    base.update(kw)
    return ModelConfig(**base)


@register_preset("sdar-30b-a3b")
def _p_sdar() -> Config:
    """SDAR-30B-A3B-Chat at its published sizes, for serving (a deployment
    holds the depth its chip holds)."""
    return Config(
        model=_sdar_model(),
        inference=InferenceConfig(max_seq_len=5120, page_size=64),
    )


@register_preset("tiny-sdar")
def _p_tiny_sdar() -> Config:
    """Tiny SDAR-family model for CPU tests: 2 layers, 4 query heads over 2
    K/V heads of 16, 8 experts top-2; blocks of 4 under pages of 8, so a
    block starts on and off a page boundary."""
    return Config(
        model=_sdar_model(
            name="tiny-sdar", vocab_size=256, max_seq_len=128, d_model=64,
            n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
            n_experts=8, n_experts_per_token=2, moe_d_ff=32,
            capacity_factor=4.0, mask_token_id=255,
            dtype="float32", param_dtype="float32", kernels="xla",
            remat="none"),
        data=DataConfig(batch_size=4, seq_len=64),
        inference=InferenceConfig(max_seq_len=128, page_size=8,
                                  num_pages=64, max_batch_size=4,
                                  prefill_chunk=16),
    )


def _brumby_model(**kw) -> ModelConfig:
    """Brumby-14B-Base (manifestai/Brumby-14B-Base config.json): the
    Qwen3-14B key set with every attention layer a power-retention layer."""
    base = dict(
        name="brumby-14b", vocab_size=151_936, max_seq_len=32_768,
        d_model=5120, n_layers=40, n_heads=40, n_kv_heads=8, head_dim=128,
        d_ff=17_408, pos_embedding="rope", rope_theta=1_000_000.0,
        norm="rmsnorm", norm_eps=1e-6, activation="swiglu",
        tie_embeddings=False, attention="power_retention", qk_norm=True,
        dtype="bfloat16", param_dtype="bfloat16", kernels="pallas",
        remat="full",
    )
    base.update(kw)
    return ModelConfig(**base)


@register_preset("brumby-14b")
def _p_brumby() -> Config:
    """Brumby-14B-Base at its published sizes, for serving (a deployment
    holds the depth its chip holds)."""
    return Config(
        model=_brumby_model(),
        inference=InferenceConfig(max_seq_len=12_288, page_size=64),
    )


@register_preset("tiny-brumby")
def _p_tiny_brumby() -> Config:
    """Tiny Brumby-family model for CPU tests: head 16 (a state of 9 slabs
    of 16), query groups of 2 over 2 K/V heads; its longest sequence of 128
    makes the fold chunk 16 tokens (ops/retention.fold_chunk) over pages of
    4, so that a short prompt crosses several folds."""
    return Config(
        model=_brumby_model(
            name="tiny-brumby", vocab_size=256, max_seq_len=128, d_model=64,
            n_layers=3, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
            dtype="float32", param_dtype="float32",
            kernels="xla", remat="none"),
        data=DataConfig(batch_size=4, seq_len=64),
        inference=InferenceConfig(max_seq_len=128, page_size=4,
                                  num_pages=160, max_batch_size=4,
                                  prefill_chunk=16, decode_window=4),
    )


def _glm_flash_model(**kw) -> ModelConfig:
    """GLM-4.7-Flash (zai-org, config.json, model_type glm4_moe_lite):
    latent attention (q through 768, one row of 512 + 64 a position, 20
    heads of 192 | 64 and values of 256), one leading dense layer, then 64
    experts 1536 wide, top-4 of sigmoid scores under a selection bias,
    gates renormalised and scaled by 1.8, and a shared expert. The
    multi-token-prediction module is no part of the served logits and is
    left out."""
    base = dict(
        name="glm-4.7-flash", vocab_size=154_880, max_seq_len=202_752,
        d_model=2048, n_layers=47, n_heads=20, n_kv_heads=20, head_dim=256,
        d_ff=10_240, pos_embedding="rope", rope_theta=1_000_000.0,
        norm="rmsnorm", norm_eps=1e-5, activation="swiglu",
        tie_embeddings=False,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256,
        n_experts=64, n_experts_per_token=4, n_dense_layers=1,
        moe_d_ff=1536, shared_expert_d_ff=1536, router_scale=1.8,
        router_score="sigmoid", router_bias=True,
        # Dropless: an expert's bucket holds every row of a decode block
        # from n_experts / top-k = 16 up (the published model drops none).
        capacity_factor=16.0,
        dtype="bfloat16", param_dtype="bfloat16", kernels="pallas",
        remat="full",
    )
    base.update(kw)
    return ModelConfig(**base)


@register_preset("glm-4.7-flash")
def _p_glm_flash() -> Config:
    """GLM-4.7-Flash at its published sizes, for serving (a deployment
    holds the depth its chip holds)."""
    return Config(
        model=_glm_flash_model(),
        inference=InferenceConfig(max_seq_len=21_504, page_size=64),
    )


@register_preset("tiny-glm")
def _p_tiny_glm() -> Config:
    """Tiny GLM-Flash-family model for CPU tests, every ratio that matters
    kept unlike: nope 24 != rope 8, values 16 != the row's 48, queries
    through 40; a leading dense layer, 8 experts top-2 with a shared one,
    sigmoid scores under a bias."""
    return Config(
        model=_glm_flash_model(
            name="tiny-glm", vocab_size=256, max_seq_len=128, d_model=64,
            n_layers=3, n_heads=4, n_kv_heads=4, head_dim=32, d_ff=128,
            q_lora_rank=40, kv_lora_rank=48, qk_nope_head_dim=24,
            qk_rope_head_dim=8, v_head_dim=16,
            n_experts=8, n_experts_per_token=2, moe_d_ff=32,
            shared_expert_d_ff=32, capacity_factor=4.0,
            dtype="float32", param_dtype="float32", kernels="xla",
            remat="none"),
        data=DataConfig(batch_size=4, seq_len=64),
        inference=InferenceConfig(max_seq_len=128, page_size=8,
                                  num_pages=128, max_batch_size=4,
                                  prefill_chunk=16, decode_window=4),
    )


def _ling_flash_model(**kw) -> ModelConfig:
    """Ling-3.0-flash (inclusionAI, config.json, model_type bailing_hybrid):
    Kimi-delta-attention layers (32 heads of 128 keys and values behind a
    convolution of 4 positions, a bounded per-channel decay) with every
    sixth layer a latent layer (one ``wq``, one row of 512 + 64 a position,
    32 heads of 128 | 64 and values of 128, a norm on queries and the rotary
    key, a per-head output gate); two leading dense layers, then 512 experts
    768 wide in 8 groups of which the best 4 are kept, top-8 of sigmoid
    scores under a selection bias, gates renormalised and scaled by 2.5,
    and a shared expert. The multi-token-prediction module is no part of
    the served logits and is left out."""
    base = dict(
        name="ling-3.0-flash", vocab_size=157_184, max_seq_len=262_144,
        d_model=2560, n_layers=42, n_heads=32, n_kv_heads=32, head_dim=128,
        d_ff=6144, pos_embedding="rope", rope_theta=6_000_000.0,
        norm="rmsnorm", norm_eps=1e-6, activation="swiglu",
        tie_embeddings=False,
        attention="kda", layer_group_size=6, kda_conv_size=4,
        kda_lower_bound=-5.0,
        q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, qk_norm=True,
        attn_gate="per-head",
        n_experts=512, router_width=512, n_experts_per_token=8,
        n_dense_layers=2, moe_d_ff=768, shared_expert_d_ff=768,
        router_scale=2.5, router_score="sigmoid", router_bias=True,
        n_group=8, topk_group=4,
        # Dropless: an expert's bucket holds every row of a decode block
        # from router_width / top-k = 64 up (the published model drops none).
        capacity_factor=64.0,
        dtype="bfloat16", param_dtype="bfloat16", kernels="pallas",
        remat="full",
    )
    base.update(kw)
    return ModelConfig(**base)


@register_preset("ling-3.0-flash")
def _p_ling_flash() -> Config:
    """Ling-3.0-flash at its published sizes, for serving (a deployment
    holds a share: model.n_experts / model.expert_offset, the vocabulary
    and the depth its chips hold)."""
    return Config(
        model=_ling_flash_model(),
        inference=InferenceConfig(max_seq_len=12_288, page_size=64),
    )


@register_preset("tiny-ling")
def _p_tiny_ling() -> Config:
    """Tiny Ling-family model for CPU tests: two dense layers and one period
    (KDA x3, latent, KDA x2); 4 heads of 16 (KDA) and of 16 | 8 with values
    of 16 (latent); 16 experts in 4 groups of which 2 are kept, top-4, a
    shared one, sigmoid scores under a bias."""
    return Config(
        model=_ling_flash_model(
            name="tiny-ling", vocab_size=256, max_seq_len=128, d_model=64,
            n_layers=8, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
            kv_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16,
            n_experts=16, router_width=16, n_experts_per_token=4,
            n_group=4, topk_group=2, moe_d_ff=32, shared_expert_d_ff=32,
            capacity_factor=4.0,
            dtype="float32", param_dtype="float32", kernels="xla",
            remat="none"),
        data=DataConfig(batch_size=4, seq_len=64),
        inference=InferenceConfig(max_seq_len=128, page_size=8,
                                  num_pages=64, max_batch_size=4,
                                  prefill_chunk=16, decode_window=4),
    )


# MiniCPM-SALA's published ``mixer_types`` (config.json), as it stands: 8
# sparse-attention layers among 24 lightning layers, in runs of no period.
_SALA_MIXERS = tuple(
    "minicpm4" if l in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning-attn"
    for l in range(32))


def _sala_model(**kw) -> ModelConfig:
    """MiniCPM-SALA (openbmb, config.json, model_type minicpm_sala): a dense
    9B whose layers are ``minicpm4`` (InfLLM-v2 block-sparse softmax
    attention: 32 query heads over 2 K/V heads, a norm on q and k, NO rotary
    embedding, the 64 best blocks of 64 positions a query and K/V head, an
    elementwise output gate) or ``lightning-attn`` (linear attention: 32
    heads of 128 under a fixed decay a head, a norm and rotary q/k, a norm
    over the concatenated heads and an elementwise output gate), under
    muP's scalings (scale_emb 12, scale_depth 1.4 over sqrt(32 layers),
    logits over hidden_size / dim_model_base = 16). The selection's sizes
    are the family's (``SparseConfig``'s defaults)."""
    base = dict(
        name="minicpm-sala", vocab_size=73_448, max_seq_len=524_288,
        d_model=4096, n_layers=32, n_heads=32, n_kv_heads=2, head_dim=128,
        d_ff=16_384, pos_embedding="rope", rope_theta=10_000.0,
        norm="rmsnorm", norm_eps=1e-6, activation="swiglu",
        tie_embeddings=False, qk_norm=True, attn_gate="elementwise",
        mixer_types=_SALA_MIXERS, sparse=SparseConfig(),
        embed_scale=12.0, residual_scale=1.4 / 32 ** 0.5,
        logit_scale=256 / 4096,
        dtype="bfloat16", param_dtype="bfloat16", kernels="pallas",
        remat="full",
    )
    base.update(kw)
    return ModelConfig(**base)


@register_preset("minicpm-sala")
def _p_sala() -> Config:
    """MiniCPM-SALA at its published sizes, for serving (a deployment holds
    a pipeline stage: the depth and the slice of ``mixer_types`` its chip
    holds)."""
    return Config(
        model=_sala_model(),
        inference=InferenceConfig(max_seq_len=71_680, page_size=64,
                                  prefill_chunk=512,
                                  prefill_chunk_tokens=4096),
    )


@register_preset("tiny-sala")
def _p_tiny_sala() -> Config:
    """Tiny MiniCPM-SALA for CPU tests: sparse, lightning x2, sparse; 4
    query heads of 16 over 2 K/V heads; blocks (= pages) of 8 positions,
    compressed keys over 4 positions every 2, block 0 and the last 3 forced
    among the 6 a query attends: from 49 positions on a query chooses."""
    return Config(
        model=_sala_model(
            name="tiny-sala", vocab_size=256, max_seq_len=256, d_model=64,
            n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
            mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                         "minicpm4"),
            sparse=SparseConfig(kernel=4, stride=2, block=8, init_blocks=1,
                                local_blocks=3, topk=6),
            residual_scale=1.4 / 4 ** 0.5, logit_scale=0.25,
            dtype="float32", param_dtype="float32", kernels="xla",
            remat="none"),
        data=DataConfig(batch_size=4, seq_len=64),
        inference=InferenceConfig(max_seq_len=256, page_size=8,
                                  num_pages=160, max_batch_size=4,
                                  prefill_chunk=16, prefill_chunk_tokens=32,
                                  decode_window=4),
    )


@register_preset("llama-1b-bench")
def _p_llama_bench() -> Config:
    """Llama-shaped ~1B model sized for one 16 GB v5e chip (the shape of
    six ``tools/*_bench.py`` instruments).

    pallas kernels, remat=full, batch 8 x seq 2048: the f32 master params
    and grads plus bf16 moments take ~12.5 GB, so larger batches and
    remat=dots/none do not fit. Not measured on current code (PERF.md).
    """
    return Config(
        model=_llama3_8b_model(name="llama-1b", vocab_size=32768,
                               max_seq_len=2048, d_model=2048, n_layers=16,
                               n_heads=16, n_kv_heads=8, d_ff=7168,
                               remat="full", kernels="pallas"),
        data=DataConfig(batch_size=8, seq_len=2048),
        optimizer=OptimizerConfig(moment_dtype="bfloat16", warmup_steps=5),
        train=TrainConfig(num_steps=20, log_interval=5),
    )
