"""Weight-only int8 serving quantization (models/quantize.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import get_config
from orion_tpu.infer import InferenceEngine
from orion_tpu.models import forward, init_params
from orion_tpu.models.quantize import (
    load_weight,
    quantize_params,
    quantize_weight,
)

# Too heavy for the tier-1 CPU budget; runs in the full tier (no
# `-m "not slow"`).
pytestmark = pytest.mark.slow


def test_quantize_weight_roundtrip_error_bound():
    w = jax.random.normal(jax.random.key(0), (64, 32)) * jnp.exp(
        jax.random.normal(jax.random.key(1), (1, 32))  # varied channel scales
    )
    deq = load_weight(quantize_weight(w), jnp.float32)
    err = jnp.abs(deq - w)
    bound = jnp.max(jnp.abs(w), axis=0) / 127.0 * 0.5 + 1e-6
    assert (err <= bound[None, :] * 1.001).all()


def test_quantize_weight_stacked_per_layer_scales():
    w = jnp.stack([jnp.ones((8, 4)), 100.0 * jnp.ones((8, 4))])
    qw = quantize_weight(w)
    assert qw["q"].shape == (2, 8, 4) and qw["s"].shape == (2, 4)
    np.testing.assert_allclose(
        np.asarray(load_weight(qw, jnp.float32)), np.asarray(w), rtol=1e-2
    )


def test_quantized_forward_close_to_fp():
    cfg = get_config("tiny-llama").model
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    ref, _ = forward(params, tokens, cfg)
    qparams = quantize_params(params, cfg)
    # The eligible matmul weights actually became int8.
    assert qparams["blocks"]["attn"]["wq"]["q"].dtype == jnp.int8
    out, _ = forward(qparams, tokens, cfg)
    rel = float(
        jnp.linalg.norm(out - ref) / (jnp.linalg.norm(ref) + 1e-9)
    )
    assert rel < 0.05, rel


def test_quantized_engine_matches_quantized_forward():
    """Serving-path exactness: the engine with int8 weights reproduces
    greedy generation from the SAME quantized model's training forward
    (quantization changes the model; serving must not add divergence)."""
    cfg = get_config("tiny-llama", [
        "model.weight_quant=int8",
        "inference.max_seq_len=128", "inference.page_size=16",
        "inference.num_pages=32", "inference.max_batch_size=4",
        "inference.prefill_chunk=16",
    ])
    params = init_params(cfg.model, jax.random.key(0))
    qparams = quantize_params(params, cfg.model)
    prompt = [5, 3, 9, 250, 17]

    toks = list(prompt)
    for _ in range(8):
        logits, _ = forward(qparams, jnp.asarray([toks], jnp.int32), cfg.model)
        toks.append(int(jnp.argmax(logits[0, len(toks) - 1])))
    ref = toks[len(prompt):]

    out = InferenceEngine(cfg, params).generate([prompt], 8)[0]
    assert out == ref


def test_trainer_rejects_weight_quant():
    from orion_tpu.train import Trainer

    cfg = get_config(
        "tiny-llama", ["runtime.platform=cpu", "model.weight_quant=int8"]
    )
    with pytest.raises(ValueError, match="serving-only"):
        Trainer(cfg)
