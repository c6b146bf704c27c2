"""Hugging Face checkpoint import (migration path from the reference stack).

The reference is a PyTorch-family framework, so its users' weights live in
HF/torch layouts. These converters map an HF ``state_dict`` (as numpy
arrays; call ``{k: v.detach().cpu().numpy() for k, v in sd.items()}`` on a
torch model) onto this framework's parameter pytree:

  - torch ``nn.Linear`` stores ``[out, in]``; our einsum weights are
    ``[in, out]`` — every projection transposes.
  - HF Llama's rotary embedding is the same rotate-half convention as
    ``ops.rope`` (frequencies over the first half / second half of the
    head dim), so q/k need **no** head-permutation — verified by the
    logits-parity tests against ``transformers`` (tests/test_convert.py).
  - GPT-2's ``Conv1D`` already stores ``[in, out]`` (no transpose), with
    the fused qkv ``c_attn`` split into wq/wk/wv.
  - With ``cfg.scan_layers`` the per-layer trees are stacked into the
    leading ``[L, ...]`` axis the layer scan consumes.

Converted trees restore into any parallelism layout by passing them
through ``parallel.reshard`` / ``train.state_shardings`` or simply handing
them to the trainer/engine, whose jit scatters per the sharding rules.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax.numpy as jnp
import numpy as np

from orion_tpu.config import ModelConfig

Params = dict[str, Any]


def _has_importer(cfg: ModelConfig) -> None:
    """A latent-attention model's projections (``wq_a``, ``q_a_norm``,
    ``wq_b``, ``wkv_a``, ``kv_a_norm``, ``wkv_b``; a router's selection
    bias) have no slot in any schema here: say so, where a converter would
    otherwise fail on the first key it misses (ROADMAP R11). Nor has a
    ``bailing_hybrid`` checkpoint (Ling-3.0-flash: KDA layers' ``conv`` /
    ``wf`` / ``a_log`` / ``dt_bias`` / ``wb`` / ``wg`` / ``o_norm`` among
    latent layers, whose rotary columns it stores interleaved): no importer
    is written for it, as for the other layer-plan models."""
    if cfg.mixer_types is not None:
        raise ValueError(
            f"model {cfg.name!r} has block-sparse and lightning layers "
            f"(model.mixer_types): no converter here reads or writes the "
            f"minicpm_sala key set; it is served from seeded weights only "
            f"(ROADMAP R11)")
    if cfg.has_kda:
        raise ValueError(
            f"model {cfg.name!r} has Kimi-delta-attention layers among "
            f"latent layers (model.attention=kda): no converter here reads "
            f"or writes the bailing_hybrid key set; it is served from "
            f"seeded weights only (ROADMAP R11)")
    if cfg.is_latent:
        raise ValueError(
            f"model {cfg.name!r} has latent-attention projections "
            f"(model.kv_lora_rank): no converter here reads or writes that "
            f"key set (self_attn.q_a_proj / kv_a_proj_with_mqa / kv_b_proj, "
            f"mlp.gate.e_score_correction_bias); it is served from seeded "
            f"weights only (ROADMAP R11)")


def _stack(cfg: ModelConfig, blocks: list[Params]) -> Any:
    if not cfg.scan_layers:
        return blocks
    import jax

    return jax.tree.map(lambda *xs: np.stack(xs), *blocks)


def _cast(cfg: ModelConfig, tree: Params) -> Params:
    import jax

    pdt = jnp.dtype(cfg.param_dtype)
    return jax.tree.map(lambda x: jnp.asarray(x, pdt), tree)


def _maybe_lm_head(
    sd: Mapping[str, np.ndarray],
    cfg: ModelConfig,
    params: Params,
    embed_key: str,
    head_key: str = "lm_head.weight",
) -> None:
    """Validate tie_embeddings against the checkpoint; attach lm_head.

    HF state dicts from a live model include the tied head as a duplicate
    tensor; saved checkpoints usually drop it. So presence alone is not
    trustworthy — when cfg says tied but the dict carries a DIFFERENT head
    than the embedding, the checkpoint is untied and silently reusing the
    embedding would produce garbage logits.
    """
    if cfg.tie_embeddings:
        if head_key in sd and not np.array_equal(
            np.asarray(sd[head_key]), np.asarray(sd[embed_key])
        ):
            raise ValueError(
                f"checkpoint has an untied {head_key} but "
                "cfg.tie_embeddings=True; set tie_embeddings=False"
            )
        return
    if head_key not in sd:
        raise ValueError(
            f"cfg.tie_embeddings=False but the checkpoint has no "
            f"{head_key}; set tie_embeddings=True"
        )
    params["lm_head"] = np.ascontiguousarray(sd[head_key].T)


def _unstack(cfg: ModelConfig, blocks: Any) -> list[Params]:
    """Inverse of _stack: per-layer list of trees from the [L, ...] stack."""
    import jax

    if not cfg.scan_layers:
        return list(blocks)
    return [
        jax.tree.map(lambda x: np.asarray(x[i]), blocks)
        for i in range(cfg.n_layers)
    ]


def to_hf_llama(
    params: Params, cfg: ModelConfig, dtype=None
) -> dict[str, np.ndarray]:
    """Export to the ``LlamaForCausalLM`` state-dict schema (round-trip
    inverse of ``from_hf_llama``; Mistral shares the schema).

    Load into torch with ``model.load_state_dict({k: torch.from_numpy(v)
    for k, v in sd.items()})`` — the path back to the reference's world
    for models trained here.

    Leaves keep their native dtype unless ``dtype`` is given (a bf16
    export arrives as ml_dtypes.bfloat16 numpy arrays; view-cast for
    torch: ``torch.from_numpy(v.view(np.uint16)).view(torch.bfloat16)``).
    """
    _has_importer(cfg)
    unexportable = []
    if cfg.attn_bias or cfg.mlp_bias:
        unexportable.append("attention/mlp biases")
    if cfg.pos_embedding != "rope":
        unexportable.append(f"pos_embedding={cfg.pos_embedding!r}")
    if cfg.norm != "rmsnorm":
        unexportable.append(f"norm={cfg.norm!r}")
    if cfg.activation != "swiglu":
        unexportable.append(f"activation={cfg.activation!r}")
    if cfg.is_moe:
        unexportable.append("MoE experts")
    if cfg.attn_logit_softcap is not None:
        # Part of the attention math, not the weights: the export would
        # load cleanly and silently produce different logits.
        unexportable.append("attn_logit_softcap")
    if cfg.post_norms:
        # Extra weights with no slot: they would silently vanish.
        unexportable.append("post_norms weights")
    for knob in ("final_logit_softcap", "query_scale",
                 "sliding_window_pattern"):
        if getattr(cfg, knob) is not None:
            unexportable.append(knob)
    if cfg.embed_scale or cfg.norm_scale_plus_one:
        # Math the Llama schema does not encode: loads cleanly, computes
        # differently.
        unexportable.append("embed_scale/norm_scale_plus_one semantics")
    if unexportable:
        raise ValueError(
            "model has no slot in the Llama state-dict schema for: "
            + ", ".join(unexportable)
        )

    def a(x):
        return np.asarray(x) if dtype is None else np.asarray(x, dtype)

    def t(x):
        return np.ascontiguousarray(a(x).T)

    sd: dict[str, np.ndarray] = {
        "model.embed_tokens.weight": a(params["embed"]["tokens"]),
        "model.norm.weight": a(params["final_norm"]["scale"]),
    }
    for i, b in enumerate(_unstack(cfg, params["blocks"])):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = a(b["attn_norm"]["scale"])
        sd[p + "post_attention_layernorm.weight"] = a(b["mlp_norm"]["scale"])
        sd[p + "self_attn.q_proj.weight"] = t(b["attn"]["wq"])
        sd[p + "self_attn.k_proj.weight"] = t(b["attn"]["wk"])
        sd[p + "self_attn.v_proj.weight"] = t(b["attn"]["wv"])
        sd[p + "self_attn.o_proj.weight"] = t(b["attn"]["wo"])
        sd[p + "mlp.gate_proj.weight"] = t(b["mlp"]["w_gate"])
        sd[p + "mlp.up_proj.weight"] = t(b["mlp"]["w_in"])
        sd[p + "mlp.down_proj.weight"] = t(b["mlp"]["w_out"])
    if not cfg.tie_embeddings:
        sd["lm_head.weight"] = t(params["lm_head"])
    else:
        sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    return sd


def from_hf_llama(sd: Mapping[str, np.ndarray], cfg: ModelConfig) -> Params:
    """Llama/Llama-2/Llama-3-family ``LlamaForCausalLM`` state dict."""
    _has_importer(cfg)
    L = cfg.n_layers

    def t(name):  # torch Linear [out, in] -> [in, out]
        return np.ascontiguousarray(sd[name].T)

    blocks = []
    for i in range(L):
        p = f"model.layers.{i}."
        blocks.append({
            "attn_norm": {"scale": np.asarray(sd[p + "input_layernorm.weight"])},
            "mlp_norm": {
                "scale": np.asarray(sd[p + "post_attention_layernorm.weight"])
            },
            "attn": {
                "wq": t(p + "self_attn.q_proj.weight"),
                "wk": t(p + "self_attn.k_proj.weight"),
                "wv": t(p + "self_attn.v_proj.weight"),
                "wo": t(p + "self_attn.o_proj.weight"),
            },
            "mlp": {
                "w_gate": t(p + "mlp.gate_proj.weight"),
                "w_in": t(p + "mlp.up_proj.weight"),
                "w_out": t(p + "mlp.down_proj.weight"),
            },
        })
    params: Params = {
        "embed": {"tokens": np.asarray(sd["model.embed_tokens.weight"])},
        "final_norm": {"scale": np.asarray(sd["model.norm.weight"])},
        "blocks": _stack(cfg, blocks),
    }
    _maybe_lm_head(sd, cfg, params, "model.embed_tokens.weight")
    return _cast(cfg, params)


def from_hf_qwen2(sd: Mapping[str, np.ndarray], cfg: ModelConfig) -> Params:
    """Qwen2/Qwen2.5-family ``Qwen2ForCausalLM`` state dict.

    The Llama schema plus q/k/v projection biases (and no o bias) —
    cfg should set ``attn_bias=True, attn_out_bias=False``.
    """
    _has_importer(cfg)
    if not cfg.attn_bias or cfg.resolved_attn_out_bias:
        raise ValueError(
            "Qwen2-family configs need attn_bias=True, attn_out_bias=False "
            f"(got attn_bias={cfg.attn_bias}, "
            f"attn_out_bias={cfg.resolved_attn_out_bias})"
        )
    params = from_hf_llama(sd, cfg)
    blocks = params["blocks"]
    L = cfg.n_layers
    bq, bk, bv = [], [], []
    for i in range(L):
        p = f"model.layers.{i}."
        bq.append(np.asarray(sd[p + "self_attn.q_proj.bias"]))
        bk.append(np.asarray(sd[p + "self_attn.k_proj.bias"]))
        bv.append(np.asarray(sd[p + "self_attn.v_proj.bias"]))
    if cfg.scan_layers:
        blocks["attn"]["bq"] = np.stack(bq)
        blocks["attn"]["bk"] = np.stack(bk)
        blocks["attn"]["bv"] = np.stack(bv)
    else:
        for i, b in enumerate(blocks):
            b["attn"]["bq"], b["attn"]["bk"], b["attn"]["bv"] = (
                bq[i], bk[i], bv[i]
            )
    return _cast(cfg, params)


def from_hf_gemma2(sd: Mapping[str, np.ndarray], cfg: ModelConfig) -> Params:
    """Gemma-2-family ``Gemma2ForCausalLM`` state dict.

    Llama-style projections plus the Gemma block shape: pre AND post norms
    around both sublayers ((1+w) RMSNorm), GeGLU MLP, tied embeddings,
    sqrt(d_model) embedding scale, interleaved local/global attention.
    cfg should set post_norms=True, norm_scale_plus_one=True,
    embed_scale=True, activation='geglu', tie_embeddings=True,
    sliding_window_pattern=2 (+ the softcaps and query_scale).
    """
    _has_importer(cfg)
    need = dict(post_norms=True, norm_scale_plus_one=True,
                embed_scale=True, tie_embeddings=True)
    bad = {k: getattr(cfg, k) for k, v in need.items()
           if getattr(cfg, k) is not v}
    if cfg.activation != "geglu":
        bad["activation"] = cfg.activation
    # Attention-math knobs: without these the import loads cleanly and
    # produces silently wrong logits (the parity test's negative control
    # proves e.g. a uniform-window config diverges from HF).
    for k in ("sliding_window", "sliding_window_pattern", "query_scale",
              "attn_logit_softcap", "final_logit_softcap"):
        if getattr(cfg, k) is None:
            bad[k] = None
    if bad:
        raise ValueError(
            f"Gemma-2-family configs need {need}, activation='geglu', and "
            f"non-None sliding_window(+pattern)/query_scale/softcaps; "
            f"got {bad}"
        )
    L = cfg.n_layers

    def t(name):  # torch Linear [out, in] -> [in, out]
        return np.ascontiguousarray(sd[name].T)

    blocks = []
    for i in range(L):
        p = f"model.layers.{i}."
        blocks.append({
            "attn_norm": {
                "scale": np.asarray(sd[p + "input_layernorm.weight"])
            },
            "post_attn_norm": {
                "scale": np.asarray(
                    sd[p + "post_attention_layernorm.weight"])
            },
            "mlp_norm": {
                "scale": np.asarray(
                    sd[p + "pre_feedforward_layernorm.weight"])
            },
            "post_mlp_norm": {
                "scale": np.asarray(
                    sd[p + "post_feedforward_layernorm.weight"])
            },
            "attn": {
                "wq": t(p + "self_attn.q_proj.weight"),
                "wk": t(p + "self_attn.k_proj.weight"),
                "wv": t(p + "self_attn.v_proj.weight"),
                "wo": t(p + "self_attn.o_proj.weight"),
            },
            "mlp": {
                "w_gate": t(p + "mlp.gate_proj.weight"),
                "w_in": t(p + "mlp.up_proj.weight"),
                "w_out": t(p + "mlp.down_proj.weight"),
            },
        })
    params: Params = {
        "embed": {"tokens": np.asarray(sd["model.embed_tokens.weight"])},
        "final_norm": {"scale": np.asarray(sd["model.norm.weight"])},
        "blocks": _stack(cfg, blocks),
    }
    # Raises if the checkpoint carries an untied lm_head this tied config
    # would silently ignore (same guard as the Llama importer).
    _maybe_lm_head(sd, cfg, params, "model.embed_tokens.weight")
    return _cast(cfg, params)


def from_hf_gpt2(sd: Mapping[str, np.ndarray], cfg: ModelConfig) -> Params:
    """GPT-2 ``GPT2LMHeadModel`` state dict (Conv1D stores [in, out])."""
    _has_importer(cfg)
    D = cfg.d_model
    sd = {k.removeprefix("transformer."): v for k, v in sd.items()}

    blocks = []
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        qkv_w = np.asarray(sd[p + "attn.c_attn.weight"])  # [D, 3D]
        qkv_b = np.asarray(sd[p + "attn.c_attn.bias"])    # [3D]
        blocks.append({
            "attn_norm": {
                "scale": np.asarray(sd[p + "ln_1.weight"]),
                "bias": np.asarray(sd[p + "ln_1.bias"]),
            },
            "mlp_norm": {
                "scale": np.asarray(sd[p + "ln_2.weight"]),
                "bias": np.asarray(sd[p + "ln_2.bias"]),
            },
            "attn": {
                "wq": qkv_w[:, :D],
                "wk": qkv_w[:, D : 2 * D],
                "wv": qkv_w[:, 2 * D :],
                "bq": qkv_b[:D],
                "bk": qkv_b[D : 2 * D],
                "bv": qkv_b[2 * D :],
                "wo": np.asarray(sd[p + "attn.c_proj.weight"]),
                "bo": np.asarray(sd[p + "attn.c_proj.bias"]),
            },
            "mlp": {
                "w_in": np.asarray(sd[p + "mlp.c_fc.weight"]),
                "b_in": np.asarray(sd[p + "mlp.c_fc.bias"]),
                "w_out": np.asarray(sd[p + "mlp.c_proj.weight"]),
                "b_out": np.asarray(sd[p + "mlp.c_proj.bias"]),
            },
        })
    params: Params = {
        "embed": {
            "tokens": np.asarray(sd["wte.weight"]),
            "positions": np.asarray(sd["wpe.weight"]),
        },
        "final_norm": {
            "scale": np.asarray(sd["ln_f.weight"]),
            "bias": np.asarray(sd["ln_f.bias"]),
        },
        "blocks": _stack(cfg, blocks),
    }
    _maybe_lm_head(sd, cfg, params, "wte.weight")
    return _cast(cfg, params)


def from_hf_mixtral(sd: Mapping[str, np.ndarray], cfg: ModelConfig) -> Params:
    """Mixtral ``MixtralForCausalLM`` state dict.

    Weight mapping only — logits parity additionally requires routing
    parity: ours is capacity-based (tokens beyond expert capacity drop),
    HF's is dropless; they agree when ``capacity_factor`` admits every
    routed token (tests pin that regime).
    """
    _has_importer(cfg)
    E = cfg.n_experts

    def t(name):
        return np.ascontiguousarray(sd[name].T)

    blocks = []
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        ep = p + "block_sparse_moe.experts."
        blocks.append({
            "attn_norm": {"scale": np.asarray(sd[p + "input_layernorm.weight"])},
            "mlp_norm": {
                "scale": np.asarray(sd[p + "post_attention_layernorm.weight"])
            },
            "attn": {
                "wq": t(p + "self_attn.q_proj.weight"),
                "wk": t(p + "self_attn.k_proj.weight"),
                "wv": t(p + "self_attn.v_proj.weight"),
                "wo": t(p + "self_attn.o_proj.weight"),
            },
            "moe": {
                "router": t(p + "block_sparse_moe.gate.weight"),
                # HF expert naming: w1 = gate, w2 = down, w3 = up.
                "w_gate": np.stack([t(f"{ep}{e}.w1.weight") for e in range(E)]),
                "w_out": np.stack([t(f"{ep}{e}.w2.weight") for e in range(E)]),
                "w_in": np.stack([t(f"{ep}{e}.w3.weight") for e in range(E)]),
            },
        })
    params: Params = {
        "embed": {"tokens": np.asarray(sd["model.embed_tokens.weight"])},
        "final_norm": {"scale": np.asarray(sd["model.norm.weight"])},
        "blocks": _stack(cfg, blocks),
    }
    _maybe_lm_head(sd, cfg, params, "model.embed_tokens.weight")
    return _cast(cfg, params)
