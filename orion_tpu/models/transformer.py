"""The decoder-only transformer: one parameterization for the whole zoo.

Reference model families (SURVEY.md §3 "models"): GPT-2 (learned positions,
LayerNorm, GELU, tied embeddings), Llama-3 (RoPE, RMSNorm, SwiGLU, GQA) and
Mixtral (Llama + top-k MoE) — all expressed by ``ModelConfig`` switches over
this single implementation, the idiomatic TPU shape: pure-pytree params, a
``lax.scan`` over stacked per-layer weights (fast compiles, layer-count
independent HLO), optional ``jax.checkpoint`` rematerialization, and a
logical-axis tree per parameter that ``orion_tpu.parallel.sharding`` maps to
mesh axes (dp/fsdp/tp/sp/ep) — parallelism never appears in model code.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from orion_tpu import ops
from orion_tpu.config import ModelConfig
from orion_tpu.models import moe as moe_lib
from orion_tpu.models.quantize import load_weight as _load_w

Params = dict[str, Any]

# The activations saved under remat="names" (checkpoint_name annotations in
# the block body below + models/moe.py): expensive to recompute relative to
# their [B,S,·]-sized storage. Everything else (QKV projections, the
# [B,S,F] MLP hiddens that make remat="dots" OOM, softmax internals)
# rematerializes in the backward.
REMAT_SAVE_NAMES = (
    "attn_out",        # flash-attention kernel output [B,S,N,H]
    "attn_norm_out",   # pre-attention norm output     [B,S,D]
    "mlp_norm_out",    # pre-FFN norm output           [B,S,D]
    "ffn_out",         # MLP / MoE-combine output      [B,S,D]
    "moe_router_gate",  # renormalized top-k gates     [B,S,k] (models/moe.py)
)


def remat_policy(cfg: ModelConfig):
    """The jax.checkpoint policy for ``cfg.remat`` (None = no remat).

    "names" saves exactly REMAT_SAVE_NAMES; with ``cfg.remat_offload`` the
    saved tensors are parked in host RAM (pinned_host) instead of HBM —
    the save set is identical, only its residence changes, so grads are
    bitwise equal across the three of none/names/names+offload.
    """
    if cfg.remat_offload and cfg.remat != "names":
        raise ValueError(
            f"model.remat_offload requires model.remat='names' "
            f"(got remat={cfg.remat!r}): the offload set IS the named set"
        )
    if cfg.remat == "names":
        if cfg.remat_offload:
            return jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=list(REMAT_SAVE_NAMES),
                offload_src="device",
                offload_dst="pinned_host",
            )
        return jax.checkpoint_policies.save_only_these_names(
            *REMAT_SAVE_NAMES
        )
    if cfg.remat == "dots":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return None

# ---------------------------------------------------------------------------
# Initialization (+ the logical-axis tree used by parallel.sharding)
# ---------------------------------------------------------------------------


def _normal(key, shape, dtype, std: float):
    return std * jax.random.normal(key, shape, dtype)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Initialize the parameter pytree.

    GPT-2-style scheme: N(0, 0.02) everywhere, residual output projections
    scaled by 1/sqrt(2L). Stored in ``cfg.param_dtype`` (fp32 master copy).
    """
    pdt = jnp.dtype(cfg.param_dtype)
    D, V, L = cfg.d_model, cfg.vocab_size, cfg.n_layers
    H = cfg.resolved_head_dim
    N, K, F = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    std = 0.02
    resid_std = std / (2 * L) ** 0.5

    keys = iter(jax.random.split(key, 64))

    def norm_scale():
        # (1 + w) norms (Gemma) initialize w at zero => identity scale.
        if cfg.norm_scale_plus_one:
            return jnp.zeros((D,), pdt)
        return jnp.ones((D,), pdt)

    params: Params = {
        "embed": {"tokens": _normal(next(keys), (V, D), pdt, std)},
        "final_norm": {"scale": norm_scale()},
    }
    if cfg.pos_embedding == "learned":
        params["embed"]["positions"] = _normal(
            next(keys), (cfg.max_seq_len, D), pdt, std
        )
    if not cfg.tie_embeddings:
        params["lm_head"] = _normal(next(keys), (D, V), pdt, std)
    if cfg.norm == "layernorm":
        params["final_norm"]["bias"] = jnp.zeros((D,), pdt)

    def init_block(bkey: jax.Array, kind=None) -> Params:
        """``kind`` (a ``config.LayerKind``) sizes a layer of a model
        whose layers differ; None is the model's one kind."""
        N = cfg.n_heads if kind is None else kind.n_heads
        moe = cfg.is_moe if kind is None else kind.moe
        att = _attention_of(cfg, kind)
        bkeys = iter(jax.random.split(bkey, 16))
        if att == "latent":
            # Latent attention's projections in qkv_proj's place
            # (``latent_proj`` / ``latent_expand``).
            qr, R = cfg.q_lora_rank, cfg.kv_lora_rank
            nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                              cfg.v_head_dim)
            attn = {} if qr is None else {
                "wq_a": _normal(next(bkeys), (D, qr), pdt, std),
                "q_a_norm": jnp.ones((qr,), pdt),
                "wq_b": _normal(next(bkeys), (qr, N * (nope + rope)), pdt,
                                std),
            }
            if qr is None:
                attn["wq"] = _normal(
                    next(bkeys), (D, N * (nope + rope)), pdt, std)
            attn.update({
                "wkv_a": _normal(next(bkeys), (D, R + rope), pdt, std),
                "kv_a_norm": jnp.ones((R,), pdt),
                "wkv_b": _normal(next(bkeys), (R, N * (nope + vd)), pdt, std),
                "wo": _normal(next(bkeys), (N * vd, D), pdt, resid_std),
            })
            if cfg.qk_norm:
                attn["q_norm"] = jnp.ones((nope + rope,), pdt)
                attn["k_norm"] = jnp.ones((rope,), pdt)
        elif att == "kda":
            # Kimi delta attention (``kda_proj``; ops/kda.py): q, k, v
            # behind ONE depthwise convolution leaf (q | k | v columns), the
            # decay's full-rank projection with its A_log / dt_bias, the
            # write strength a head, the output's full-rank gate and the
            # head norm in front of it.
            attn = {
                "wq": _normal(next(bkeys), (D, N * H), pdt, std),
                "wk": _normal(next(bkeys), (D, N * H), pdt, std),
                "wv": _normal(next(bkeys), (D, N * H), pdt, std),
                "conv": jnp.ones((cfg.kda_conv_size, 3 * N * H), pdt)
                / cfg.kda_conv_size,
                "wf": _normal(next(bkeys), (D, N * H), pdt, std),
                "a_log": jnp.zeros((N,), pdt),
                "dt_bias": jnp.zeros((N * H,), pdt),
                "wb": _normal(next(bkeys), (D, N), pdt, std),
                "wg": _normal(next(bkeys), (D, N * H), pdt, std),
                "o_norm": jnp.ones((H,), pdt),
                "wo": _normal(next(bkeys), (N * H, D), pdt, resid_std),
            }
        else:
            # A kind may fix its K/V heads; values may be narrower than keys.
            Kl, Hv = cfg.kv_heads_of(kind), cfg.resolved_v_head_dim
            attn = {
                "wq": _normal(next(bkeys), (D, N * H), pdt, std),
                "wk": _normal(next(bkeys), (D, Kl * H), pdt, std),
                "wv": _normal(next(bkeys), (D, Kl * Hv), pdt, std),
                "wo": _normal(next(bkeys), (N * Hv, D), pdt, resid_std),
            }
            if kind is not None and kind.sink:
                attn["sink"] = jnp.zeros((N,), pdt)
        block: Params = {
            "attn_norm": {"scale": norm_scale()},
            "mlp_norm": {"scale": norm_scale()},
            "attn": attn,
        }
        if cfg.norm == "layernorm":
            block["attn_norm"]["bias"] = jnp.zeros((D,), pdt)
            block["mlp_norm"]["bias"] = jnp.zeros((D,), pdt)
        if cfg.post_norms:
            block["post_attn_norm"] = {"scale": norm_scale()}
            block["post_mlp_norm"] = {"scale": norm_scale()}
        if cfg.attn_bias:
            block["attn"]["bq"] = jnp.zeros((N * H,), pdt)
            block["attn"]["bk"] = jnp.zeros((K * H,), pdt)
            block["attn"]["bv"] = jnp.zeros((K * H,), pdt)
        if cfg.resolved_attn_out_bias:
            block["attn"]["bo"] = jnp.zeros((D,), pdt)
        if moe:
            E, Fe = cfg.n_experts, cfg.resolved_moe_d_ff
            block["moe"] = {
                "router": _normal(
                    next(bkeys), (D, cfg.resolved_router_width), pdt, std),
                "w_in": _normal(next(bkeys), (E, D, Fe), pdt, std),
                "w_out": _normal(next(bkeys), (E, Fe, D), pdt, resid_std),
            }
            if cfg.is_gated_mlp:
                block["moe"]["w_gate"] = _normal(
                    next(bkeys), (E, D, Fe), pdt, std)
            if cfg.router_bias:
                block["moe"]["router_bias"] = jnp.zeros(
                    (cfg.resolved_router_width,), pdt)
            if cfg.shared_expert_d_ff:
                Fs = cfg.shared_expert_d_ff
                block["moe"]["shared"] = {
                    "w_in": _normal(next(bkeys), (D, Fs), pdt, std),
                    "w_gate": _normal(next(bkeys), (D, Fs), pdt, std),
                    "w_out": _normal(next(bkeys), (Fs, D), pdt, resid_std),
                }
        else:
            block["mlp"] = {
                "w_in": _normal(next(bkeys), (D, F), pdt, std),
                "w_out": _normal(next(bkeys), (F, D), pdt, resid_std),
            }
            if cfg.is_gated_mlp:
                block["mlp"]["w_gate"] = _normal(next(bkeys), (D, F), pdt, std)
            if cfg.mlp_bias:
                block["mlp"]["b_in"] = jnp.zeros((F,), pdt)
                block["mlp"]["b_out"] = jnp.zeros((D,), pdt)
        if cfg.attn_gate is not None and att != "kda":
            block["attn"]["wg"] = _normal(
                next(bkeys), (D, _gate_width(cfg, N)), pdt, std)
        if cfg.qk_norm and att not in ("latent", "kda"):
            block["attn"]["q_norm"] = jnp.ones((H,), pdt)
            block["attn"]["k_norm"] = jnp.ones((H,), pdt)
        if att == "lightning":
            # The norm over a position's concatenated heads (out_proj).
            block["attn"]["o_norm"] = jnp.ones((N * H,), pdt)
        if att == "power_retention":
            block["attn"]["wr"] = _normal(next(bkeys), (D, K), pdt, std)

        return block

    layer_keys = jax.random.split(next(keys), L)
    plan = cfg.layer_plan
    if plan is not None:
        # Layers that differ in shape: the leading elements each on their
        # own, and one stack per position of the period (config.LayerPlan);
        # an element's leaves are as deep as its ``layers`` are nested.
        kinds = cfg.layer_kinds

        def element(e):
            at = jnp.asarray(plan.layers(e))
            fn = functools.partial(init_block, kind=kinds[plan.start(e)])
            for _ in range(at.ndim):
                fn = jax.vmap(fn)
            return fn(layer_keys[at])

        # (a plan of lead elements alone has no period to stack)
        period = {str(j): element(plan.lead + j)
                  for j in range(plan.period) if plan.counts[j]}
        params["blocks"] = {"period": period} if period else {}
        if plan.lead:
            params["blocks"]["lead"] = {
                str(i): element(i) for i in range(plan.lead)}
    elif cfg.scan_layers:
        params["blocks"] = jax.vmap(init_block)(layer_keys)
    else:
        params["blocks"] = [init_block(k) for k in layer_keys]
    return params


def param_logical_axes(cfg: ModelConfig) -> Params:
    """Pytree matching init_params' structure; leaves are logical-axis tuples.

    Logical names are mapped to mesh axes by parallel.sharding rules:
    vocab/heads/mlp -> tp, embed -> fsdp, expert -> ep, layers -> unsharded.
    """
    plan = cfg.layer_plan
    if plan is not None:
        kinds = cfg.layer_kinds

        def element(e):
            depth = jnp.asarray(plan.layers(e)).ndim
            return _block_axes(cfg, ("layers",) * depth, kinds[plan.start(e)])

        period = {str(j): element(plan.lead + j)
                  for j in range(plan.period) if plan.counts[j]}
        blocks: Params = {"period": period} if period else {}
        if plan.lead:
            blocks["lead"] = {str(i): element(i) for i in range(plan.lead)}
    else:
        block = _block_axes(
            cfg, ("layers",) if cfg.scan_layers else (), None)
        blocks = block if cfg.scan_layers else [block] * cfg.n_layers
    axes: Params = {
        "embed": {"tokens": ("vocab", "embed")},
        "final_norm": {"scale": ("embed",)},
        "blocks": blocks,
    }
    if cfg.pos_embedding == "learned":
        axes["embed"]["positions"] = ("pos", "embed")
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if cfg.norm == "layernorm":
        axes["final_norm"]["bias"] = ("embed",)
    return axes


def _block_axes(cfg: ModelConfig, lead: tuple, kind) -> Params:
    """One block's logical axes (``kind`` as in ``init_params``)."""
    moe = cfg.is_moe if kind is None else kind.moe
    att = _attention_of(cfg, kind)
    block = {
        "attn_norm": {"scale": lead + ("embed",)},
        "mlp_norm": {"scale": lead + ("embed",)},
        "attn": {
            "wq": lead + ("embed", "heads"),
            "wk": lead + ("embed", "kv_heads"),
            "wv": lead + ("embed", "kv_heads"),
            "wo": lead + ("heads", "embed"),
        },
    }
    if att == "latent":
        block["attn"] = {
            "wq_a": lead + ("embed", None),
            "q_a_norm": lead + (None,),
            "wq_b": lead + (None, "heads"),
            "wkv_a": lead + ("embed", None),
            "kv_a_norm": lead + (None,),
            "wkv_b": lead + (None, "heads"),
            "wo": lead + ("heads", "embed"),
        }
        if cfg.q_lora_rank is None:
            for name in ("wq_a", "q_a_norm", "wq_b"):
                del block["attn"][name]
            block["attn"]["wq"] = lead + ("embed", "heads")
        if cfg.qk_norm:
            block["attn"]["q_norm"] = lead + (None,)
            block["attn"]["k_norm"] = lead + (None,)
    if kind is not None and kind.sink:
        block["attn"]["sink"] = lead + ("heads",)
    if att == "kda":
        block["attn"].update({
            "wk": lead + ("embed", "heads"),
            "wv": lead + ("embed", "heads"),
            "conv": lead + (None, "heads"),
            "wf": lead + ("embed", "heads"),
            "a_log": lead + ("heads",),
            "dt_bias": lead + ("heads",),
            "wb": lead + ("embed", "heads"),
            "wg": lead + ("embed", "heads"),
            "o_norm": lead + (None,),
        })
    if cfg.norm == "layernorm":
        block["attn_norm"]["bias"] = lead + ("embed",)
        block["mlp_norm"]["bias"] = lead + ("embed",)
    if cfg.post_norms:
        block["post_attn_norm"] = {"scale": lead + ("embed",)}
        block["post_mlp_norm"] = {"scale": lead + ("embed",)}
    if cfg.attn_bias:
        block["attn"]["bq"] = lead + ("heads",)
        block["attn"]["bk"] = lead + ("kv_heads",)
        block["attn"]["bv"] = lead + ("kv_heads",)
    if cfg.resolved_attn_out_bias:
        block["attn"]["bo"] = lead + ("embed",)
    if cfg.attn_gate is not None and att != "kda":
        block["attn"]["wg"] = lead + ("embed", "heads")
    if cfg.qk_norm and att not in ("latent", "kda"):
        block["attn"]["q_norm"] = lead + (None,)
        block["attn"]["k_norm"] = lead + (None,)
    if att == "lightning":
        block["attn"]["o_norm"] = lead + ("heads",)
    if att == "power_retention":
        block["attn"]["wr"] = lead + ("embed", "kv_heads")
    if moe:
        block["moe"] = {
            "router": lead + ("embed", "expert"),
            "w_in": lead + ("expert", "embed", "mlp"),
            "w_out": lead + ("expert", "mlp", "embed"),
        }
        if cfg.is_gated_mlp:
            block["moe"]["w_gate"] = lead + ("expert", "embed", "mlp")
        if cfg.router_bias:
            block["moe"]["router_bias"] = lead + ("expert",)
        if cfg.shared_expert_d_ff:
            block["moe"]["shared"] = {
                "w_in": lead + ("embed", "mlp"),
                "w_gate": lead + ("embed", "mlp"),
                "w_out": lead + ("mlp", "embed"),
            }
    else:
        block["mlp"] = {
            "w_in": lead + ("embed", "mlp"),
            "w_out": lead + ("mlp", "embed"),
        }
        if cfg.is_gated_mlp:
            block["mlp"]["w_gate"] = lead + ("embed", "mlp")
        if cfg.mlp_bias:
            block["mlp"]["b_in"] = lead + ("mlp",)
            block["mlp"]["b_out"] = lead + ("embed",)
    return block


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _attention_of(cfg: ModelConfig, kind) -> str:
    """What a layer's attention computes (``config.LayerKind.attention``);
    ``kind`` None is the one kind of a model whose layers share a stack."""
    return cfg.layer_attention(0) if kind is None else kind.attention


def _gate_width(cfg: ModelConfig, n_heads: int) -> int:
    """Columns of ``attn.wg``: a gate a head, or one a number of it."""
    if cfg.attn_gate == "elementwise":
        return n_heads * cfg.resolved_head_dim
    return n_heads


def _gate_act(cfg: ModelConfig):
    """Gating nonlinearity for gated MLPs: SiLU (SwiGLU) or tanh-approx
    GELU (GeGLU, the Gemma-family gate)."""
    if cfg.activation == "swiglu":
        return jax.nn.silu
    return functools.partial(jax.nn.gelu, approximate=True)


def _norm(
    x: jax.Array, p: Params, cfg: ModelConfig, mesh: Optional[Any] = None
) -> jax.Array:
    """``mesh``: the mesh the enclosing jit spans, so the Pallas norm
    kernel runs per shard (ops.rmsnorm); the xla path ignores it."""
    scale = p["scale"]
    if cfg.norm_scale_plus_one:
        # Gemma-family RMSNorm parameterization: x_hat * (1 + w) (weights
        # initialized at zero); same kernels, shifted scale.
        scale = scale + 1.0
    if cfg.norm == "rmsnorm":
        return ops.rmsnorm(
            x, scale, eps=cfg.norm_eps, impl=cfg.kernels, mesh=mesh
        )
    return ops.layernorm(x, scale, p.get("bias"), eps=cfg.norm_eps)


@jax.named_scope("embed")
def embed(
    params: Params, tokens: jax.Array, positions: jax.Array, cfg: ModelConfig
) -> jax.Array:
    """Token (+ learned position) embedding; shared by training forward and
    the inference cache runner."""
    x = params["embed"]["tokens"].astype(jnp.dtype(cfg.dtype))[tokens]
    if cfg.embed_scale:
        # Gemma-family (True): embeddings scaled by sqrt(d_model), rounded
        # in the activation dtype (matches the HF normalizer semantics); a
        # number: by that number (muP's scale_emb).
        scale = (cfg.d_model ** 0.5 if cfg.embed_scale is True
                 else cfg.embed_scale)
        x = x * jnp.asarray(scale, x.dtype)
    if cfg.pos_embedding == "learned":
        x = x + params["embed"]["positions"].astype(x.dtype)[positions]
    return x


@jax.named_scope("unembed")
def unembed(
    params: Params, x: jax.Array, cfg: ModelConfig,
    mesh: Optional[Any] = None, precise: bool = False,
) -> jax.Array:
    """Final norm + LM head -> float32 logits; shared like ``embed``.
    ``precise``: the head's products are accumulated and LEFT in float32
    (else they pass through ``x``'s dtype, or not, as the compiler fuses):
    for a caller that ranks positions by a probability of the logits, which
    two programs must then compute alike."""
    x = _norm(x, params["final_norm"], cfg, mesh)
    if cfg.logit_scale != 1.0:
        x = x * jnp.asarray(cfg.logit_scale, x.dtype)
    kw = {"preferred_element_type": jnp.float32} if precise else {}
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "bsd,vd->bsv", x, params["embed"]["tokens"].astype(x.dtype), **kw
        )
    else:
        logits = jnp.einsum(
            "bsd,dv->bsv", x, _load_w(params["lm_head"], x.dtype), **kw)
    logits = logits.astype(jnp.float32)
    if cfg.final_logit_softcap is not None:
        cap = cfg.final_logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return logits


@jax.named_scope("qkv")
def qkv_proj(
    x: jax.Array, p: Params, cfg: ModelConfig, positions: jax.Array,
    mesh: Optional[Any] = None, kind=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """QKV projection + RoPE. x: [B, S, D] -> q [B,S,N,H], k/v [B,S,K,H].

    Shared between the training forward and the inference cache runner
    (orion_tpu.infer.runner), which attends against different KV sources.
    ``mesh`` as in ``_norm`` (the Pallas RoPE kernel runs per shard).
    ``kind`` (``cfg.layer_kind(l)``, static) gives this layer's query heads
    and rotary table where a model's layers differ; None is the model's one
    kind.
    """
    B, S, _ = x.shape
    N, K, H = cfg.n_heads, cfg.kv_heads_of(kind), cfg.resolved_head_dim
    theta, table = cfg.rope_theta, None
    rotary = cfg.pos_embedding == "rope"
    if kind is not None:
        N = kind.n_heads
        rotary = rotary and kind.rope is not None
        if rotary:
            theta = kind.rope.theta
            table = None if kind.rope.is_plain else kind.rope
    dtype = x.dtype

    q = jnp.einsum("bsd,dh->bsh", x, _load_w(p["wq"], dtype))
    k = jnp.einsum("bsd,dh->bsh", x, _load_w(p["wk"], dtype))
    v = jnp.einsum("bsd,dh->bsh", x, _load_w(p["wv"], dtype))
    if cfg.attn_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    q = q.reshape(B, S, N, H)
    k = k.reshape(B, S, K, H)
    v = v.reshape(B, S, K, cfg.resolved_v_head_dim)
    if cfg.value_scale != 1.0:
        v = v * jnp.asarray(cfg.value_scale, v.dtype)
    if cfg.qk_norm:
        # Per head, over its H numbers, before the rotary embedding.
        q = ops.rmsnorm(q, p["q_norm"], eps=cfg.norm_eps)
        k = ops.rmsnorm(k, p["k_norm"], eps=cfg.norm_eps)

    if rotary:
        rope = functools.partial(
            ops.apply_rope, theta=theta, rope=table, impl=cfg.kernels,
            mesh=mesh,
        )
        q, k = rope(q, positions), rope(k, positions)
    if cfg.query_scale is not None:
        # Net attention scale cfg.query_scale instead of head_dim**-0.5
        # (Gemma-2's query_pre_attn_scalar**-0.5): every attention kernel
        # divides by sqrt(head_dim), so pre-multiply q by the ratio.
        q = q * jnp.asarray(cfg.query_scale * (H ** 0.5), q.dtype)
    return q, k, v


@jax.named_scope("out")
def out_proj(out: jax.Array, p: Params, cfg: ModelConfig,
             h: Optional[jax.Array] = None, att: str = "softmax"
             ) -> jax.Array:
    """Attention output projection. out: [B, S, N, H] -> [B, S, D].

    With ``model.attn_gate`` each head's output is first multiplied by
    ``sigmoid(h wg)``, ``h`` [B, S, D] the layer's normed input (the one
    ``qkv_proj`` read): the head-wise gate of arXiv:2505.06708. A KDA
    layer (``att``) has a gate of its own: every head's output under an
    RMSNorm over its numbers (``o_norm`` [H]) times ``sigmoid(h wg)``, wg
    of full rank [D, N x H]. ``attn_gate`` "elementwise": every number of
    the output by its own gate (wg [D, N x H]); a lightning layer's output
    is first normed over a position's concatenated heads (``o_norm``)."""
    B, S = out.shape[0], out.shape[1]
    dtype = out.dtype
    if att == "lightning":
        out = ops.rmsnorm(out.reshape(B, S, -1), p["o_norm"],
                          eps=cfg.norm_eps).astype(h.dtype).reshape(out.shape)
        dtype = h.dtype
    if att == "kda":
        gate = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", h, _load_w(p["wg"], h.dtype)))
        out = ops.rmsnorm(out, p["o_norm"], eps=cfg.norm_eps).astype(
            h.dtype) * gate.reshape(out.shape)
        dtype = h.dtype
    elif cfg.attn_gate == "elementwise":
        out = out * jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", h, _load_w(p["wg"], h.dtype))).reshape(
                out.shape).astype(dtype)
    elif cfg.attn_gate is not None:
        out = out * _attn_gate(h, p, cfg)[..., None].astype(dtype)
    y = jnp.einsum(
        "bsh,hd->bsd", out.reshape(B, S, -1), _load_w(p["wo"], dtype)
    )
    if cfg.resolved_attn_out_bias:
        y = y + p["bo"].astype(dtype)
    return y


def _attn_gate(h: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """[B, S, N] gate on the attention output: ONE function, so that the
    published modelling code, when at hand, corrects the reading of
    ``gating: per-head`` in one place (the reference has its own)."""
    if cfg.attn_gate != "per-head":
        raise ValueError(f"model.attn_gate={cfg.attn_gate!r}; per-head|None")
    return jax.nn.sigmoid(
        jnp.einsum("bsd,dn->bsn", h, _load_w(p["wg"], h.dtype)))


@jax.named_scope("qkv")
def retention_log_gate(h: jax.Array, p: Params) -> jax.Array:
    """[B, S, K] float32: the log of a power-retention layer's gate, one a
    K/V head and position, from the layer's normed input ``h`` (the one
    ``qkv_proj`` read). ONE function, as ``_attn_gate`` is."""
    return jax.nn.log_sigmoid(jnp.einsum(
        "bsd,dk->bsk", h, _load_w(p["wr"], h.dtype)).astype(jnp.float32))


@jax.named_scope("qkv")
def kda_proj(h: jax.Array, p: Params, cfg: ModelConfig
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """A KDA layer's projections of its normed input ``h`` [B, S, D]: (x
    [B, S, 3 x N x H]: q | k | v BEFORE the convolution, whose last rows a
    cache keeps, so the convolution is the backend's; g [B, S, N, H]
    float32: the log-decay a key channel, ``ops.kda.safe_log_decay``; b
    [B, S, N] float32: the write strength a head)."""
    from orion_tpu.ops.kda import safe_log_decay

    B, S, _ = h.shape
    N, H = cfg.n_heads, cfg.resolved_head_dim
    dtype = h.dtype
    x = jnp.concatenate([
        jnp.einsum("bsd,dh->bsh", h, _load_w(p[w], dtype))
        for w in ("wq", "wk", "wv")], axis=-1)
    with jax.named_scope("kda/gate"):
        z = jnp.einsum("bsd,dh->bsh", h, _load_w(p["wf"], dtype))
        g = safe_log_decay(z.reshape(B, S, N, H), p["a_log"],
                           p["dt_bias"].reshape(N, H), cfg.kda_lower_bound)
        b = jax.nn.sigmoid(jnp.einsum(
            "bsd,dn->bsn", h, _load_w(p["wb"], dtype)).astype(jnp.float32))
    return x, g, b


def kda_activate(y: jax.Array, cfg: ModelConfig
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q, k, v [..., N, H] float32 from the convolution's output ``y`` [...,
    3 x N x H]: SiLU on all three, then q and k l2-normalised a head and q
    scaled by H^-0.5."""
    from orion_tpu.ops.kda import l2norm

    N, H = cfg.n_heads, cfg.resolved_head_dim
    y = jax.nn.silu(y.astype(jnp.float32)).reshape(*y.shape[:-1], 3, N, H)
    q, k, v = (y[..., i, :, :] for i in range(3))
    return l2norm(q) * H ** -0.5, l2norm(k), v


@jax.named_scope("qkv")
def latent_proj(
    x: jax.Array, p: Params, cfg: ModelConfig, positions: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """A latent layer's projections, in ``qkv_proj``'s place: x [B, S, D] ->
    (q [B, S, N, nope + rope], the rotary part rotated; row [B, S, R + rope]:
    the normed compressed row beside the ONE rotated rotary key all heads
    share, which is what a cache holds of the position). The rotation is
    the XLA form at every length (a head of 64 is half a lane tile)."""
    B, S, _ = x.shape
    N, R = cfg.n_heads, cfg.kv_lora_rank
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if ((cfg.is_latent and cfg.resolved_head_dim != nope + rope_d)
            or cfg.n_kv_heads != N):
        raise ValueError(
            f"a latent model's head_dim is qk_nope_head_dim + "
            f"qk_rope_head_dim ({nope} + {rope_d}) and its n_kv_heads its "
            f"n_heads; got head_dim={cfg.resolved_head_dim}, "
            f"n_kv_heads={cfg.n_kv_heads}")
    dtype = x.dtype
    rope = functools.partial(ops.apply_rope, theta=cfg.rope_theta, impl="xla")
    with jax.named_scope("latent/down"):
        if cfg.q_lora_rank is None:
            q = jnp.einsum("bsd,dh->bsh", x, _load_w(p["wq"], dtype))
        else:
            c_q = ops.rmsnorm(
                jnp.einsum("bsd,dr->bsr", x, _load_w(p["wq_a"], dtype)),
                p["q_a_norm"], eps=cfg.norm_eps)
            q = jnp.einsum("bsr,rh->bsh", c_q, _load_w(p["wq_b"], dtype))
        q = q.reshape(B, S, N, nope + rope_d)
        row = jnp.einsum("bsd,dr->bsr", x, _load_w(p["wkv_a"], dtype))
        k_pe = row[..., None, R:]
        if cfg.qk_norm:
            # Before the rotation: each head's query, and the one rotary
            # key all heads share (config.qk_norm).
            q = ops.rmsnorm(q, p["q_norm"], eps=cfg.norm_eps)
            k_pe = ops.rmsnorm(k_pe, p["k_norm"], eps=cfg.norm_eps)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], positions)], axis=-1)
        c_kv = ops.rmsnorm(row[..., :R], p["kv_a_norm"], eps=cfg.norm_eps)
        k_pe = rope(k_pe, positions)[:, :, 0]
        row = jnp.concatenate([c_kv, k_pe], axis=-1)
    return q, row


def _wkv_b_heads(wkv_b: jax.Array, cfg: ModelConfig, dtype
                 ) -> tuple[jax.Array, jax.Array]:
    """``wkv_b`` [R, N x (nope + v)] as (W_uk [R, N, nope], W_uv [R, N, v])."""
    w = _load_w(wkv_b, dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def latent_expand(row: jax.Array, wkv_b: jax.Array, cfg: ModelConfig
                  ) -> tuple[jax.Array, jax.Array]:
    """The EXPANDED form's keys and values from cached rows [B, T, R + rope]:
    k [B, T, N, nope + rope] (every head's own nope part beside the shared
    rotary key) and v [B, T, N, v_head_dim]."""
    R = cfg.kv_lora_rank
    with jax.named_scope("latent/expand"):
        w_uk, w_uv = _wkv_b_heads(wkv_b, cfg, row.dtype)
        k_nope = jnp.einsum("btr,rnh->btnh", row[..., :R], w_uk)
        v = jnp.einsum("btr,rnh->btnh", row[..., :R], w_uv)
        k_pe = jnp.broadcast_to(
            row[..., None, R:], (*k_nope.shape[:3], row.shape[-1] - R))
        return jnp.concatenate([k_nope, k_pe], axis=-1), v


def latent_attention(q: jax.Array, row: jax.Array, wkv_b: jax.Array,
                     cfg: ModelConfig, **kw) -> jax.Array:
    """Causal attention in the EXPANDED form: q [B, S, N, nope + rope] over
    the keys and values of ``latent_expand(row)`` -> [B, S, N, v_head_dim].
    ``kw`` goes to ``ops.attention`` (segment ids, blocks, impl, mesh).
    Values narrower than a key are padded with zeros to the key's size,
    which every attention kernel here takes, and cut again."""
    k, v = latent_expand(row, wkv_b, cfg)
    pad = k.shape[-1] - v.shape[-1]
    if pad < 0:
        raise ValueError(
            f"model.v_head_dim={cfg.v_head_dim} exceeds the key's "
            f"{k.shape[-1]}")
    if pad:
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, pad),))
    return ops.attention(q, k, v, causal=True, **kw)[..., :cfg.v_head_dim]


def latent_absorb(q: jax.Array, wkv_b: jax.Array, cfg: ModelConfig
                  ) -> jax.Array:
    """The ABSORBED form's query: q [B, S, N, nope + rope] carried into the
    rows' space, [B, S, N, R + rope] (``q_nope W_uk^T`` beside ``q_rope``),
    so that its product with a cached row is the expanded form's score."""
    nope = cfg.qk_nope_head_dim
    with jax.named_scope("latent/absorb"):
        w_uk, _ = _wkv_b_heads(wkv_b, cfg, q.dtype)
        q_lat = jnp.einsum("bsnh,rnh->bsnr", q[..., :nope], w_uk)
        return jnp.concatenate([q_lat, q[..., nope:]], axis=-1)


def latent_unabsorb(o_lat: jax.Array, wkv_b: jax.Array, cfg: ModelConfig
                    ) -> jax.Array:
    """The absorbed form's output [B, S, N, R] (the probabilities' mean of
    the compressed rows) through W_uv: [B, S, N, v_head_dim]."""
    with jax.named_scope("latent/absorb"):
        _, w_uv = _wkv_b_heads(wkv_b, cfg, o_lat.dtype)
        return jnp.einsum("bsnr,rnh->bsnh", o_lat, w_uv)


def mlp_or_moe(
    h: jax.Array, bp: Params, cfg: ModelConfig, mesh: Optional[Any] = None,
    valid: Optional[jax.Array] = None,
    layer_stack: Optional[tuple[Params, jax.Array]] = None,
) -> tuple[jax.Array, jax.Array]:
    """The post-attention half of a block: dense MLP or MoE. Returns (y, aux).

    ``valid`` [B, S] marks the real positions of a padded block (prefill):
    the dropless MoE dispatch routes only those. ``layer_stack`` = (the
    layer-stacked ``blocks["moe"]``, this layer's index) lets it read the
    expert matrices in place (moe.moe_mlp_grouped). Which of the two a
    layer is, its parameters say (a model may lead with dense layers)."""
    if "moe" in bp:
        moe_params = {
            k: v if k in ("router", "router_bias") else jax.tree.map(
                lambda a: a.astype(h.dtype), v)
            for k, v in bp["moe"].items()
        }
        return moe_lib.moe_dispatch(
            h, moe_params, cfg, mesh, valid, layer_stack)
    return _mlp_block(h, bp["mlp"], cfg), jnp.zeros((), jnp.float32)


def _train_attend(
    cfg: ModelConfig,
    segment_ids: Optional[jax.Array],
    mesh: Optional[Any] = None,
    window: Optional[int] = None,
    kind=None,
) -> Callable[..., tuple[jax.Array, None]]:
    """The training layer's ``attend`` for ``block``: causal attention of a
    layer's q over its own k/v (flash, or ``sequence_attention`` when the
    sequence axis is live), with no state to hand on. ``window`` is THIS
    layer's sliding window (already resolved through cfg.layer_window for
    interleaved local/global models)."""
    sp_active = (
        cfg.sequence_axis is not None
        and mesh is not None
        and mesh.shape.get(cfg.sequence_axis, 1) > 1
    )

    att = _attention_of(cfg, kind)
    if att == "kda":
        if sp_active or segment_ids is not None:
            raise ValueError(
                "a KDA layer trains whole unpacked sequences on one "
                "sequence shard: no sequence axis, no segment ids")
        from orion_tpu.ops.kda import kda_chunked, short_conv

        def delta(x, g, b, conv):
            with jax.named_scope("qkv"), jax.named_scope("kda/conv"):
                q, k, v = kda_activate(short_conv(x, conv)[0], cfg)
            with jax.named_scope("kernel"):
                # The XLA chunked form, which JAX differentiates.
                return kda_chunked(q, k, v, g, b)[0].astype(x.dtype), None

        return delta

    if att == "latent":
        if sp_active:
            raise ValueError(
                "a latent-attention model trains on one sequence shard: "
                "no sequence axis")

        @jax.named_scope("kernel")
        def expanded(q, row, wkv_b):
            # The expanded form, which JAX differentiates (the absorbed
            # kernel is decode's and has no backward).
            return latent_attention(
                q, row, wkv_b, cfg, q_segment_ids=segment_ids,
                kv_segment_ids=segment_ids, seg_pad_zero=True,
                impl=cfg.kernels, mesh=mesh), None

        return expanded

    if att == "power_retention":
        if sp_active or segment_ids is not None:
            raise ValueError(
                "model.attention=power_retention trains whole unpacked "
                "sequences on one sequence shard: no sequence axis, no "
                "segment ids")
        from orion_tpu.ops.retention import fold_chunk, power_retention

        @jax.named_scope("kernel")
        def retain(q, k, v, log_g):
            # The XLA chunked form, which JAX differentiates (the Pallas
            # kernels have no backward).
            return power_retention(
                q, k, v, log_g,
                chunk=min(fold_chunk(cfg.max_seq_len), q.shape[1]),
                impl="xla")[0], None

        return retain

    if att in ("lightning", "sparse"):
        if sp_active or segment_ids is not None:
            raise ValueError(
                f"a {att} layer trains whole unpacked sequences on one "
                f"sequence shard: no sequence axis, no segment ids")
        from orion_tpu.ops.lightning import lightning_chunked
        from orion_tpu.ops.sparse import whole_sequence

        @jax.named_scope("kernel")
        def whole(q, k, v):
            # The XLA forms from an empty cache, which JAX differentiates.
            if att == "sparse":
                return whole_sequence(q, k, v, cfg.sparse), None
            scale = jnp.asarray(q.shape[-1] ** -0.5, q.dtype)
            return lightning_chunked(q * scale, k, v)[0].astype(q.dtype), None

        return whole

    @jax.named_scope("kernel")
    def attend(q, k, v, sink=None):
        if sp_active:
            if sink is not None:
                raise ValueError(
                    "a layer with an attention sink trains on one sequence "
                    "shard: no sequence axis")
            from orion_tpu.parallel.sequence import sequence_attention

            # sliding_window threads through every SP method; under "ring"
            # it also truncates the ring scan to O(window) comm — the
            # combination SWA exists for (long-context Mistral-family
            # training).
            return sequence_attention(
                q,
                k,
                v,
                mesh,
                method=cfg.sequence_method,
                axis=cfg.sequence_axis,
                causal=True,
                q_segment_ids=segment_ids,
                kv_segment_ids=segment_ids,
                logit_softcap=cfg.attn_logit_softcap,
                window=window,
                impl=cfg.kernels,
                debug_asserts=cfg.debug_asserts,
            ), None
        # Window distance is measured on token INDEX, which equals position
        # distance within a document for contiguous packed rows (positions
        # restart per doc but stay contiguous); cross-document pairs are
        # segment-masked regardless.
        return ops.attention(
            q,
            k,
            v,
            causal=True,
            q_segment_ids=segment_ids,
            kv_segment_ids=segment_ids,
            # Model-level segment_ids follow the pack_rows convention
            # (id 0 = padding; data/loader.py), so all-padding tail
            # blocks may skip their compute in the flash kernel.
            seg_pad_zero=True,
            logit_softcap=cfg.attn_logit_softcap,
            window=window,
            impl=cfg.kernels,
            mesh=mesh,
            sink=sink,
        ), None

    return attend


@jax.named_scope("dense")
def _mlp_block(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    dtype = x.dtype
    h_in = jnp.einsum("bsd,df->bsf", x, _load_w(p["w_in"], dtype))
    if cfg.mlp_bias:
        h_in = h_in + p["b_in"].astype(dtype)
    if cfg.is_gated_mlp:
        h_gate = jnp.einsum("bsd,df->bsf", x, _load_w(p["w_gate"], dtype))
        h = _gate_act(cfg)(h_gate) * h_in
    else:
        h = jax.nn.gelu(h_in)
    y = jnp.einsum("bsf,fd->bsd", h, _load_w(p["w_out"], dtype))
    if cfg.mlp_bias:
        y = y + p["b_out"].astype(dtype)
    return y


def block(
    x: jax.Array,
    bp: Params,
    cfg: ModelConfig,
    positions: jax.Array,
    attend: Callable[..., tuple[jax.Array, Any]],
    *,
    kind=None,
    mesh: Optional[Any] = None,
    ffn_mesh: Optional[Any] = None,
    valid: Optional[jax.Array] = None,
    layer_stack: Optional[tuple[Params, jax.Array]] = None,
    ffn_tap: Optional[Callable[[jax.Array], None]] = None,
) -> tuple[jax.Array, jax.Array, Any]:
    """One transformer layer, the only place one is written: training,
    prefill, the decode window and draft verification all call it. Returns
    ``(x, moe_aux_loss, state)``.

    ``attend(q, k, v) -> (out [B, S, N, H], state)`` (with the log-gates
    [B, S, K] as a fourth argument under model.attention=power_retention;
    ``attend(q, row, wkv_b) -> (out [B, S, N, v_head_dim], state)`` for a
    latent layer; ``attend(x, g, b, conv) -> (out [B, S, N, H], state)``
    for a KDA layer, ``kda_proj``; which of the four a layer is,
    ``kind.attention`` says) is all that differs
    between them: what attention reads and where K/V go (``_train_attend``
    here; the dense and paged backends of ``infer/runner.py``, whose state
    is the KV pool). The body never sees a cache, a page table or a segment
    mask. ``kind`` (``cfg.layer_kind(j)``, static; None is the model's one
    kind) reaches ``qkv_proj``; with cfg.post_norms (Gemma-family) each
    sublayer output is normalized again before the residual add.

    The feed-forward's arguments are each caller's own (``mlp_or_moe``),
    and each caller passes what it passed when it had a body of its own.
    ``ffn_mesh`` is apart from ``mesh`` (norms, rotary kernel) because the
    paged backend (decode, verify) passes none: no serving path ever did
    until PR 26 gave prefill the mesh for the grouped kernel's shard_map,
    and with none ``sorted_a2a`` computes as ``sorted``, which a [B, 1 or
    W, D] block needs in any case (the all-to-all layout splits S over
    ``ep``). It passes no ``layer_stack`` and no ``valid`` because only
    the dropless grouped path reads either, and ``moe.takes_grouped_path``
    keeps a block that short on its buckets (PR 26). Inherited more than
    decided: ROADMAP D13.
    ``ffn_tap`` is handed the feed-forward's normed input (the runner counts
    a prefill's held-expert rows off it).

    The jax.named_scope annotations are the vocabulary of parts
    (``orion_tpu.obs.parts.PARTS``: ``attention/norm``, ``attention/qkv``,
    ``attention/kernel`` ...): the parents here, the children here, in the
    projections, in ``models/moe.py`` and in each backend's ``attend``. They
    reach every compiled instruction's ``op_name``, from which the
    benchmark's ``trace/scopes.py`` splits a program's device time by part
    (and XProf's framework-op view shows an operator the same paths). The
    checkpoint_name marks are what remat="names" saves, identities in every
    other program.
    """
    with jax.named_scope("attention"):
        with jax.named_scope("norm"):
            h = checkpoint_name(
                _norm(x, bp["attn_norm"], cfg, mesh), "attn_norm_out"
            )
        att = _attention_of(cfg, kind)
        if att == "latent":
            # Such a layer hands ``attend`` its queries, the ONE row a
            # position a cache keeps, and the matrix that expands the row
            # (or absorbs the query): which form attends is the backend's.
            q, row = latent_proj(h, bp["attn"], cfg, positions)
            out, state = attend(q, row, bp["attn"]["wkv_b"])
        elif att == "kda":
            # Such a layer hands ``attend`` q | k | v BEFORE the
            # convolution (its tail is the cache's), the log-decays, the
            # write strengths and the convolution's taps.
            out, state = attend(
                *kda_proj(h, bp["attn"], cfg), bp["attn"]["conv"])
        elif att == "power_retention":
            q, k, v = qkv_proj(h, bp["attn"], cfg, positions, mesh, kind)
            # Such a layer's attention also reads the log-gates, handed to
            # ``attend`` the way ``out_proj`` is handed ``h``.
            out, state = attend(q, k, v, retention_log_gate(h, bp["attn"]))
        else:
            q, k, v = qkv_proj(h, bp["attn"], cfg, positions, mesh, kind)
            # A layer with a learned sink hands ``attend`` its logits.
            sink = ({"sink": bp["attn"]["sink"]} if "sink" in bp["attn"]
                    else {})
            out, state = attend(q, k, v, **sink)
        # remat="names" saves the kernel output: the single most expensive
        # per-layer tensor to rebuild (a full flash fwd pass) at [B,S,N,H]
        # storage. (No-op identity under every other policy.)
        out = checkpoint_name(out, "attn_out")
        a = out_proj(out, bp["attn"], cfg, h, att)
        if cfg.post_norms:
            with jax.named_scope("norm"):
                a = _norm(a, bp["post_attn_norm"], cfg, mesh)
        with jax.named_scope("out"):
            x = x + _residual(a, cfg)
    with jax.named_scope("mlp_moe"):
        with jax.named_scope("norm"):
            h2 = checkpoint_name(
                _norm(x, bp["mlp_norm"], cfg, mesh), "mlp_norm_out"
            )
        y, aux = mlp_or_moe(h2, bp, cfg, ffn_mesh, valid, layer_stack)
        if ffn_tap is not None:
            ffn_tap(h2)
        y = checkpoint_name(y, "ffn_out")
        if cfg.post_norms:
            with jax.named_scope("norm"):
                y = _norm(y, bp["post_mlp_norm"], cfg, mesh)
        # The residual add goes where its operand came from: an expert
        # layer's combine, a dense layer's MLP.
        with jax.named_scope("dispatch" if "moe" in bp else "dense"):
            x = x + _residual(y, cfg)
    return x, aux, state


def _residual(y: jax.Array, cfg: ModelConfig) -> jax.Array:
    """A sublayer's output as it joins the residual stream."""
    if cfg.residual_scale == 1.0:
        return y
    return y * jnp.asarray(cfg.residual_scale, y.dtype)


def scan_layer_plan(blocks: Params, plan, body, carry):
    """Run ``body(carry, bp, l, j, stack) -> carry`` over the layers of a
    model whose layers differ in shape (``config.LayerPlan``): the leading
    elements, a ``lax.scan`` over the periods that calls the body once per
    static position, and the tail; an element that is a run of equal layers
    is a ``lax.scan`` over them. ``l`` is the layer's index (traced under a
    scan), ``j`` the STATIC index of a layer of the same kind
    (``cfg.layer_kind(j)``: the element's first), ``stack`` = (the layer-
    stacked MoE weights [layers, ...] the layer's are a row of, or None; that
    row) for the dispatch that reads expert matrices in place. Shared by the
    training forward and the cache runner."""
    def element(carry, leaves, e, g):
        """Element ``e`` in period ``g`` (None: a lead element)."""
        w, j, base = plan.width(e), plan.start(e), plan.start(plan.lead)
        first = j if g is None else base + g * plan.period_layers + (j - base)
        bp = leaves if g is None else jax.tree.map(lambda a: a[g], leaves)
        moe = leaves.get("moe")
        if w == 1:
            return body(carry, bp, first, j,
                        None if g is None or moe is None else (moe, g))
        if moe is not None:     # [.., width, ...] -> one row a layer
            moe = jax.tree.map(
                lambda a: a.reshape(-1, *a.shape[1 + (g is not None):]), moe)

        def one(c, r):
            row = r if g is None else g * w + r
            return body(c, jax.tree.map(lambda a: a[r], bp), first + r, j,
                        None if moe is None else (moe, row)), None

        return jax.lax.scan(one, carry, jnp.arange(w))[0]

    for i in range(plan.lead):
        carry = element(carry, blocks["lead"][str(i)], i, None)

    def one_period(carry, g, positions):
        for j in range(positions):
            carry = element(
                carry, blocks["period"][str(j)], plan.lead + j, g)
        return carry

    if plan.repeats == 1:
        carry = one_period(carry, 0, plan.period)
    elif plan.repeats > 1:
        carry, _ = jax.lax.scan(
            lambda c, g: (one_period(c, g, plan.period), None),
            carry, jnp.arange(plan.repeats))
    if plan.tail:
        carry = one_period(carry, plan.repeats, plan.tail)
    return carry


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    mesh: Optional[Any] = None,
) -> tuple[jax.Array, jax.Array]:
    """tokens: [B, S] int32 -> (logits [B, S, V] float32, moe_aux scalar)."""
    x, moe_aux = _hidden_states(
        params,
        tokens,
        cfg,
        positions=positions,
        segment_ids=segment_ids,
        mesh=mesh,
    )
    return unembed(params, x, cfg, mesh), moe_aux


def _hidden_states(
    params: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    mesh: Optional[Any] = None,
) -> tuple[jax.Array, jax.Array]:
    """The block-stack output [B, S, D] before final norm / LM head.

    Same trace as ``forward`` minus ``unembed``; split out so the chunked
    loss can stream the vocab projection instead of materializing the full
    [B, S, V] float32 logits (the single largest activation at training
    shapes — ~2 GiB at the bench config).
    """
    B, S = tokens.shape
    custom_positions = positions is not None
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    x = embed(params, tokens, positions, cfg)

    def _remat(fn):
        """Wrap a scan/pipeline body in the configured remat policy. The
        boundary is the BODY — for grouped scans that is the whole group,
        so the fwd residual stash and the bwd stacked-grad writes happen
        once per group instead of once per layer (the scan-stash share of
        the profile, PERF.md)."""
        # Built unconditionally: remat_policy owns the offload-requires-
        # names check, which must fire for forward-only callers too (a
        # silently ignored remat_offload would measure the wrong config).
        policy = remat_policy(cfg)
        if cfg.remat == "none":
            return fn
        # policy=None (remat="full") is jax.checkpoint's save-nothing
        # default; the policy dispatch lives in remat_policy.
        return jax.checkpoint(fn, policy=policy)

    def make_block_fn(window: Optional[int], with_rs: bool = False,
                      kind=None):
        """Per-layer body (NOT remat-wrapped: the caller wraps its scan/
        pipeline unit via ``_remat``). ``with_rs`` (the packed-pipeline
        path) takes the per-row state (positions/segment_ids, already
        microbatch-sliced by the pipeline) as a third argument instead of
        closing over the full-batch arrays."""
        if with_rs:
            def block_fn(carry, bp, rs):
                return block(
                    carry, bp, cfg, rs["positions"],
                    _train_attend(cfg, rs.get("segment_ids"), mesh, window,
                                  kind),
                    kind=kind, mesh=mesh, ffn_mesh=mesh,
                )[:2]
        else:
            def block_fn(carry, bp):
                pos = positions
                if pos.shape[0] != carry.shape[0]:
                    pos = jnp.broadcast_to(
                        pos[:1], (carry.shape[0], pos.shape[1])
                    )
                return block(
                    carry, bp, cfg, pos,
                    _train_attend(cfg, segment_ids, mesh, window, kind),
                    kind=kind, mesh=mesh, ffn_mesh=mesh,
                )[:2]

        return block_fn

    def layer_groups(unit: int, with_rs: bool = False):
        """(grouped_blocks, group_fn) for a scan/pipeline over GROUPS of
        ``unit`` statically-unrolled layers. Two callers, one unit rule:

        - window-pattern (Gemma-family) models: the window is static per
          pattern position, so the unit is a multiple of the pattern and
          layer j of a group resolves ``cfg.layer_window(j)`` (correct for
          any group because unit % pattern == 0) — shared with the
          pipeline so the two paths cannot diverge;
        - ``cfg.scan_group``: groups of G homogeneous layers whose single
          remat body cuts the stacked-buffer DUS writes by G.
        """
        L = cfg.n_layers
        if L % unit:
            raise ValueError(
                f"n_layers={L} must be divisible by the layer-scan unit "
                f"{unit} (scan_group={cfg.scan_group}"
                + (f" x sliding_window_pattern={cfg.window_pattern}"
                   if cfg.window_pattern else "")
                + ")"
            )
        fns = [make_block_fn(cfg.layer_window(j), with_rs)
               for j in range(unit)]
        if cfg.scan_group == 1:
            # Default scan_group: the remat boundary stays PER LAYER (the
            # seed's behavior for window-pattern models). A group-wide
            # boundary trades backward recompute working set — up to
            # unit× the interior activations live at once — for the G×
            # stash win; that trade is what scan_group>1 opts into, and
            # must not silently hit memory-edge pattern configs that
            # never set the knob.
            fns = [_remat(f) for f in fns]
        grouped = jax.tree.map(
            lambda a: a.reshape(L // unit, unit, *a.shape[1:]),
            params["blocks"],
        )

        def group_fn(carry, gbp, *rs):
            # *rs absorbs the optional row-state argument, so the same
            # function serves both the 2-arg (scan) and 3-arg (packed
            # pipeline) calling conventions.
            aux_t = jnp.zeros((), jnp.float32)
            for j, f in enumerate(fns):
                carry, aux = f(
                    carry, jax.tree.map(lambda a: a[j], gbp), *rs
                )
                aux_t = aux_t + aux
            return carry, aux_t

        return grouped, (group_fn if cfg.scan_group == 1
                         else _remat(group_fn))

    pp_active = (
        cfg.pipeline_axis is not None
        and mesh is not None
        and mesh.shape.get(cfg.pipeline_axis, 1) > 1
    )
    plan = cfg.layer_plan
    if plan is not None:
        if pp_active or cfg.scan_group > 1 or not cfg.scan_layers:
            raise ValueError(
                f"model {cfg.name!r} has layers of different shapes "
                f"(model.layer_types / n_heads_per_layer / n_dense_layers): "
                f"its layer program runs under scan_layers=true, "
                f"scan_group=1 and no pipeline axis")
        kinds = cfg.layer_kinds
        fns = {j: _remat(make_block_fn(kinds[j].window, kind=kinds[j]))
               for j in map(plan.start, range(plan.lead + plan.period))
               if j < len(kinds)}       # (a plan of lead elements alone)

        def body(carry, bp, l, j, stack):
            x, aux_t = carry
            x, aux = fns[j](x, bp)
            return x, aux_t + aux

        x, moe_aux = scan_layer_plan(
            params["blocks"], plan, body, (x, jnp.zeros((), jnp.float32)))
    elif pp_active:
        if not cfg.scan_layers:
            raise ValueError("pipeline parallelism requires scan_layers=True")
        from orion_tpu.parallel.pipeline import pipeline_forward

        # Packed sequences / custom positions are PER-ROW state: the
        # pipeline slices them per microbatch and each stage looks its
        # active slice up by index (they never ride the ppermute ring),
        # so packing composes with pp (r4 restriction lifted, round 5).
        with_rs = segment_ids is not None or custom_positions
        row_state = None
        if with_rs:
            row_state = {"positions": positions}
            if segment_ids is not None:
                row_state["segment_ids"] = segment_ids

        if cfg.scan_unit == 1:
            pp_blocks = params["blocks"]
            pp_fn = _remat(make_block_fn(cfg.sliding_window, with_rs))
        else:
            # The stage body iterates the SAME unit the layer scan would:
            # scan_group homogeneous layers times the window pattern
            # (Gemma-family local/global groups), via the shared
            # layer_groups — so scan_group composes with pp and grads
            # stay bitwise across scan_group values (the trainer
            # validates the unit count splits over pp*V).
            pp_blocks, pp_fn = layer_groups(cfg.scan_unit, with_rs)

        x, moe_aux = pipeline_forward(
            x,
            pp_blocks,
            pp_fn,
            mesh,
            axis=cfg.pipeline_axis,
            num_microbatches=cfg.pp_microbatches,
            schedule=cfg.pp_schedule,
            virtual_stages=cfg.pp_virtual_stages,
            row_state=row_state,
        )
    elif cfg.scan_layers:
        # The scan unit (= the remat body) is scan_group homogeneous
        # layers, times the window pattern for interleaved local/global
        # (Gemma-family) models. unit == 1 is today's per-layer scan.
        unit = cfg.scan_unit
        if unit == 1:
            x, aux = jax.lax.scan(
                _remat(make_block_fn(cfg.layer_window(0))),
                x, params["blocks"], unroll=cfg.scan_unroll,
            )
        else:
            grouped, group_fn = layer_groups(unit)
            x, aux = jax.lax.scan(
                group_fn, x, grouped, unroll=cfg.scan_unroll
            )
        moe_aux = aux.sum()
    else:
        if cfg.scan_group > 1:
            # Mirror the pp branch: a silently ignored knob would let a
            # probe config measure nothing.
            raise ValueError(
                "model.scan_group > 1 requires model.scan_layers=true "
                "(grouping is a property of the layer scan)"
            )
        moe_aux = jnp.zeros((), jnp.float32)
        for l, bp in enumerate(params["blocks"]):
            x, aux = _remat(make_block_fn(cfg.layer_window(l)))(x, bp)
            moe_aux = moe_aux + aux
    return x, moe_aux


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather_target_impl(V, logits, targets):
    return jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]


def _gather_target_fwd(V, logits, targets):
    return _gather_target_impl(V, logits, targets), (targets,)


def _gather_target_bwd(V, res, g):
    (targets,) = res
    return (g[..., None] * jax.nn.one_hot(targets, V, dtype=g.dtype), None)


_gather_target_impl.defvjp(_gather_target_fwd, _gather_target_bwd)


def _gather_target(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-token target logit [..., S] from logits [..., S, V].

    Forward is the plain gather; the custom VJP replaces gather's scatter-
    add transpose with a one-hot multiply. Two reasons: scatter serializes
    badly on TPU where the select-style one-hot product vectorizes (the CE
    backward materializes a [B, S, V] cotangent either way), and the
    checkify index-check rewrite in this jax version crashes on the
    scatter (trace-time IndexError) — this formulation lets
    runtime.checkify run the FULL check set, including out-of-bounds
    index checks, over the train step (SANITIZERS.md).
    """
    return _gather_target_impl(logits.shape[-1], logits, targets)


def loss_fn(
    params: Params,
    batch: dict[str, jax.Array],
    cfg: ModelConfig,
    mesh: Optional[Any] = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Next-token cross-entropy + weighted MoE aux loss.

    batch: inputs [B,S], targets [B,S], optional loss_mask [B,S] (1 = count),
    optional segment_ids/positions for packed sequences.

    With ``cfg.loss_chunk`` set, the vocab projection + softmax stream over
    sequence chunks under remat, so the full [B, S, V] float32 logits (the
    single largest training activation — ~2 GiB at the bench shapes, x2 for
    log_softmax, live into the backward) are never materialized; peak vocab
    memory drops to [B, chunk, V] per direction. The chunked and dense paths
    are the same math (logsumexp - target logit) and are parity-tested.
    """
    targets = batch["targets"]
    mask = batch.get("loss_mask")
    chunk = cfg.loss_chunk
    S = targets.shape[1]
    if chunk and S % chunk:
        # Refuse rather than silently materialize the dense logits the knob
        # exists to avoid (the config documents the divisibility contract).
        raise ValueError(
            f"model.loss_chunk={chunk} must divide seq_len={S}"
        )
    if not chunk or S == chunk:
        logits, moe_aux = forward(
            params,
            batch["inputs"],
            cfg,
            positions=batch.get("positions"),
            segment_ids=batch.get("segment_ids"),
            mesh=mesh,
        )
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -_gather_target(logp, targets)
        if mask is None:
            mask = jnp.ones_like(nll)
        mask = mask.astype(jnp.float32)
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = (nll * mask).sum() / denom
        loss = ce + cfg.router_aux_loss_weight * moe_aux
        return loss, {"ce_loss": ce, "moe_aux": moe_aux, "tokens": denom}

    x, moe_aux = _hidden_states(
        params,
        batch["inputs"],
        cfg,
        positions=batch.get("positions"),
        segment_ids=batch.get("segment_ids"),
        mesh=mesh,
    )
    B = targets.shape[0]
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    mask = mask.astype(jnp.float32)
    n_chunks = S // chunk

    def to_chunks(a):
        # [B, S, ...] -> [n_chunks, B, chunk, ...] scan-leading layout.
        return a.reshape(B, n_chunks, chunk, *a.shape[2:]).swapaxes(0, 1)

    def ce_chunk(carry, xs):
        xc, tc, mc = xs
        logits = unembed(params, xc, cfg, mesh)  # [B, chunk, V] f32
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = _gather_target(logits, tc)
        nll_sum = ((logz - tgt) * mc).sum()
        return (carry[0] + nll_sum, carry[1] + mc.sum()), None

    # Remat per chunk: the backward recomputes one chunk of logits at a
    # time instead of keeping them all live.
    (nll_total, mask_total), _ = jax.lax.scan(
        jax.checkpoint(ce_chunk),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (to_chunks(x), to_chunks(targets), to_chunks(mask)),
    )
    denom = jnp.maximum(mask_total, 1.0)
    ce = nll_total / denom
    loss = ce + cfg.router_aux_loss_weight * moe_aux
    return loss, {"ce_loss": ce, "moe_aux": moe_aux, "tokens": denom}
