"""HF-checkpoint import parity (models/convert.py).

The strongest model-family parity evidence we can produce without network
access: build a tiny random Hugging Face model (torch, CPU), convert its
state dict, and require OUR forward to reproduce ITS logits. This pins the
whole architecture — RoPE convention, GQA layout, SwiGLU wiring, norm
placement/eps, tied embeddings, MoE routing — not just shapes.
"""

import numpy as np
import pytest

import jax

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from orion_tpu.config import ModelConfig
from orion_tpu.models import forward
from orion_tpu.models.convert import (
    from_hf_gpt2,
    from_hf_llama,
    from_hf_mixtral,
)

# Too heavy for the tier-1 CPU budget; runs in the full tier (no
# `-m "not slow"`).
pytestmark = pytest.mark.slow

TOKENS = np.array([[5, 3, 9, 250, 17, 42, 7, 1]], np.int32)


def _sd(model):
    return {k: v.detach().cpu().numpy() for k, v in model.state_dict().items()}


def _hf_logits(model, tokens):
    model.eval()
    with torch.no_grad():
        out = model(torch.from_numpy(tokens).long())
    return out.logits.float().numpy()


def test_llama_logits_parity():
    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10_000.0,
        tie_word_embeddings=False, attention_bias=False,
    )
    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="hf-llama-tiny", vocab_size=256, max_seq_len=64, d_model=64,
        n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        rope_theta=10_000.0, norm_eps=1e-5, tie_embeddings=False,
        dtype="float32", param_dtype="float32",
    )
    params = from_hf_llama(_sd(hf), cfg)
    ours, _ = forward(params, TOKENS, cfg)
    np.testing.assert_allclose(
        np.asarray(ours), _hf_logits(hf, TOKENS), atol=2e-4, rtol=1e-3
    )


def test_qwen2_logits_parity():
    """Qwen2-family: the Llama schema plus q/k/v biases and no o bias
    (attn_bias=True, attn_out_bias=False)."""
    from orion_tpu.models.convert import from_hf_qwen2

    hf_cfg = transformers.Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-6,
        rope_theta=10_000.0, tie_word_embeddings=False,
    )
    torch.manual_seed(3)
    hf = transformers.Qwen2ForCausalLM(hf_cfg)
    with torch.no_grad():
        # HF zero-inits the qkv biases; randomize so parity actually
        # exercises the bias path.
        for n, p in hf.named_parameters():
            if n.endswith("proj.bias"):
                torch.nn.init.normal_(p, std=0.1)
    cfg = ModelConfig(
        name="hf-qwen2-tiny", vocab_size=256, max_seq_len=64, d_model=64,
        n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        rope_theta=10_000.0, norm_eps=1e-6, tie_embeddings=False,
        attn_bias=True, attn_out_bias=False,
        dtype="float32", param_dtype="float32",
    )
    params = from_hf_qwen2(_sd(hf), cfg)
    ours, _ = forward(params, TOKENS, cfg)
    np.testing.assert_allclose(
        np.asarray(ours), _hf_logits(hf, TOKENS), atol=2e-4, rtol=1e-3
    )
    # The imported biases are non-trivial (the path is actually exercised).
    assert float(np.abs(np.asarray(params["blocks"]["attn"]["bq"])).max()) > 0


def test_qwen2_rejects_wrong_bias_config():
    from orion_tpu.models.convert import from_hf_qwen2

    cfg = ModelConfig(name="bad", vocab_size=256, d_model=64, n_layers=2,
                      n_heads=4, n_kv_heads=2, d_ff=128)
    with pytest.raises(ValueError, match="attn_bias"):
        from_hf_qwen2({}, cfg)


def test_gemma2_logits_parity():
    """Gemma-2 family: interleaved local/global attention, pre+post (1+w)
    norms, GeGLU, sqrt(d) embedding scale, query_pre_attn_scalar, dual
    softcaps, tied embeddings — the whole block shape pinned against HF."""
    from orion_tpu.models.convert import from_hf_gemma2

    hf_cfg = transformers.Gemma2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, rms_norm_eps=1e-6,
        rope_theta=10_000.0, sliding_window=6,
        query_pre_attn_scalar=32, attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0,
        hidden_activation="gelu_pytorch_tanh",
    )
    torch.manual_seed(5)
    hf = transformers.Gemma2ForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="hf-gemma2-tiny", vocab_size=256, max_seq_len=64, d_model=64,
        n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        rope_theta=10_000.0, norm_eps=1e-6, tie_embeddings=True,
        norm_scale_plus_one=True, post_norms=True, embed_scale=True,
        activation="geglu",
        sliding_window=6, sliding_window_pattern=2,
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        query_scale=32.0 ** -0.5,
        dtype="float32", param_dtype="float32",
    )
    params = from_hf_gemma2(_sd(hf), cfg)
    ours, _ = forward(params, TOKENS, cfg)
    np.testing.assert_allclose(
        np.asarray(ours), _hf_logits(hf, TOKENS), atol=3e-4, rtol=1e-3
    )
    # The interleave matters at this seq len (window 6 < 8 tokens): a
    # uniform-window config must NOT match (guards against silently
    # ignoring the pattern).
    import dataclasses

    uni = dataclasses.replace(cfg, sliding_window_pattern=None)
    ours_uni, _ = forward(params, TOKENS, uni)
    assert not np.allclose(np.asarray(ours_uni), _hf_logits(hf, TOKENS),
                           atol=3e-4)


def test_gemma2_rejects_wrong_block_config():
    from orion_tpu.models.convert import from_hf_gemma2

    cfg = ModelConfig(name="bad", vocab_size=256, d_model=64, n_layers=2,
                      n_heads=4, n_kv_heads=2, d_ff=128)
    with pytest.raises(ValueError, match="Gemma-2"):
        from_hf_gemma2({}, cfg)


def test_gpt2_logits_parity():
    hf_cfg = transformers.GPT2Config(
        vocab_size=256, n_positions=64, n_embd=64, n_layer=2, n_head=4,
        activation_function="gelu_new", resid_pdrop=0.0, embd_pdrop=0.0,
        attn_pdrop=0.0,
    )
    torch.manual_seed(1)
    hf = transformers.GPT2LMHeadModel(hf_cfg)
    cfg = ModelConfig(
        name="hf-gpt2-tiny", vocab_size=256, max_seq_len=64, d_model=64,
        n_layers=2, n_heads=4, n_kv_heads=4, d_ff=256,
        pos_embedding="learned", norm="layernorm", activation="gelu",
        tie_embeddings=True, attn_bias=True, mlp_bias=True,
        dtype="float32", param_dtype="float32",
    )
    params = from_hf_gpt2(_sd(hf), cfg)
    ours, _ = forward(params, TOKENS, cfg)
    np.testing.assert_allclose(
        np.asarray(ours), _hf_logits(hf, TOKENS), atol=2e-4, rtol=1e-3
    )


def test_mixtral_logits_parity():
    hf_cfg = transformers.MixtralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10_000.0,
        tie_word_embeddings=False, router_jitter_noise=0.0,
    )
    torch.manual_seed(2)
    hf = transformers.MixtralForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="hf-mixtral-tiny", vocab_size=256, max_seq_len=64, d_model=64,
        n_layers=2, n_heads=4, n_kv_heads=2, d_ff=96,
        n_experts=4, n_experts_per_token=2,
        # HF routing is dropless; match it by giving every expert capacity
        # for the full sequence (capacity = f*S*k/E >= S needs f >= E/k).
        capacity_factor=2.0,
        rope_theta=10_000.0, norm_eps=1e-5, tie_embeddings=False,
        dtype="float32", param_dtype="float32",
    )
    params = from_hf_mixtral(_sd(hf), cfg)
    ours, _ = forward(params, TOKENS, cfg)
    np.testing.assert_allclose(
        np.asarray(ours), _hf_logits(hf, TOKENS), atol=5e-4, rtol=2e-3
    )


def test_tie_mismatch_raises():
    """An untied checkpoint with cfg.tie_embeddings=True must refuse (the
    silent path would reuse the embedding as the head -> garbage logits)."""
    hf_cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        tie_word_embeddings=False,
    )
    torch.manual_seed(3)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    cfg_tied = ModelConfig(
        name="t", vocab_size=64, d_model=32, n_layers=1, n_heads=2,
        n_kv_heads=2, d_ff=64, tie_embeddings=True,
        dtype="float32", param_dtype="float32",
    )
    with pytest.raises(ValueError, match="untied"):
        from_hf_llama(_sd(hf), cfg_tied)
    # And the reverse: untied cfg, no head in the dict.
    sd = {k: v for k, v in _sd(hf).items() if k != "lm_head.weight"}
    cfg_untied = ModelConfig(
        name="t", vocab_size=64, d_model=32, n_layers=1, n_heads=2,
        n_kv_heads=2, d_ff=64, tie_embeddings=False,
        dtype="float32", param_dtype="float32",
    )
    with pytest.raises(ValueError, match="has no lm_head"):
        from_hf_llama(sd, cfg_untied)


def test_mistral_sliding_window_logits_parity():
    """Mistral-family = Llama schema + sliding window: our windowed
    attention must reproduce transformers' MistralForCausalLM logits with
    a window smaller than the sequence."""
    hf_cfg = transformers.MistralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10_000.0,
        sliding_window=3, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(4)
    hf = transformers.MistralForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="hf-mistral-tiny", vocab_size=256, max_seq_len=64, d_model=64,
        n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        rope_theta=10_000.0, norm_eps=1e-5, tie_embeddings=False,
        sliding_window=3, dtype="float32", param_dtype="float32",
    )
    params = from_hf_llama(_sd(hf), cfg)
    ours, _ = forward(params, TOKENS, cfg)
    np.testing.assert_allclose(
        np.asarray(ours), _hf_logits(hf, TOKENS), atol=2e-4, rtol=1e-3
    )
    # Sanity: the window is actually active (full attention differs).
    import dataclasses as _dc

    full, _ = forward(params, TOKENS, _dc.replace(cfg, sliding_window=None))
    assert not np.allclose(np.asarray(ours), np.asarray(full))


def test_to_hf_llama_round_trip():
    """Export: a model trained here loads into torch LlamaForCausalLM and
    produces OUR logits — the migration path back to the reference world."""
    from orion_tpu.models import init_params
    from orion_tpu.models.convert import to_hf_llama

    cfg = ModelConfig(
        name="export-tiny", vocab_size=256, max_seq_len=64, d_model=64,
        n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        rope_theta=10_000.0, norm_eps=1e-5, tie_embeddings=False,
        dtype="float32", param_dtype="float32",
    )
    params = init_params(cfg, jax.random.key(5))
    ours, _ = forward(params, TOKENS, cfg)

    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10_000.0,
        tie_word_embeddings=False, attention_bias=False,
    )
    hf = transformers.LlamaForCausalLM(hf_cfg)
    sd = {k: torch.from_numpy(v) for k, v in to_hf_llama(params, cfg).items()}
    hf.load_state_dict(sd)
    np.testing.assert_allclose(
        np.asarray(ours), _hf_logits(hf, TOKENS), atol=2e-4, rtol=1e-3
    )


def test_to_hf_llama_rejects_non_llama_configs():
    from orion_tpu.models import init_params
    from orion_tpu.models.convert import to_hf_llama
    from orion_tpu.config import get_config

    cfg = get_config("tiny").model  # GPT-2 family: learned pos, LN, biases
    params = init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="no slot"):
        to_hf_llama(params, cfg)


def test_to_hf_llama_rejects_softcap():
    from orion_tpu.models import init_params
    from orion_tpu.models.convert import to_hf_llama

    cfg = ModelConfig(
        name="t", vocab_size=64, d_model=32, n_layers=1, n_heads=2,
        n_kv_heads=2, d_ff=64, tie_embeddings=False,
        attn_logit_softcap=50.0, dtype="float32", param_dtype="float32",
    )
    with pytest.raises(ValueError, match="softcap"):
        to_hf_llama(init_params(cfg, jax.random.key(0)), cfg)
