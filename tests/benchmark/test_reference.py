"""The plain reference against the program at a tiny size in float32, the
control that has to fail, and the layout of the benchmark-made weights."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import model, weights
from tests.benchmark.conftest import CONFIGS, REPO


def _program(cfg_name):
    from benchmarks.harness.cell import program_config

    return program_config(CONFIGS[cfg_name]).model


@pytest.mark.parametrize("cfg_name", ["tiny-serve", "tiny-moe-serve"])
def test_reference_matches_the_program_and_the_control_does_not(cfg_name):
    from orion_tpu.models.transformer import forward

    hf, m = CONFIGS[cfg_name], _program(cfg_name)
    p = weights.make_params(hf, "float32", 2 ** 31 + 5)
    toks = jax.random.randint(jax.random.key(1), (1, 48), 1, hf["vocab_size"])
    got, _ = forward(p, toks, m)
    at = jnp.arange(48)
    want, margin = model.logits_at(p, toks[0], at, hf)
    assert margin.shape == (48,) and bool(jnp.all(margin >= 0))
    assert bool(jnp.all(jnp.isinf(margin))) == ("num_local_experts" not in hf)
    err = float(jnp.linalg.norm(got[0] - want) / jnp.linalg.norm(want))
    assert err < 1e-5
    low = model.logits_at(p, toks[0], at, hf, quant="int8")[0]
    control = float(jnp.linalg.norm(low - want) / jnp.linalg.norm(want))
    limit = hf["correct"]["limits"]["logit_rel_err_worst_probe_median_clear"]
    assert control > 3 * limit > 3 * err


def test_a_sliding_window_is_honoured():
    hf = dict(CONFIGS["tiny-serve"], sliding_window=8)
    p = weights.make_params(hf, "float32", 3)
    toks = jax.random.randint(jax.random.key(2), (40,), 1, 256)
    at = jnp.asarray([39])
    windowed = model.logits_at(p, toks, at, hf)[0]
    full = model.logits_at(p, toks, at, dict(hf, sliding_window=None))[0]
    assert float(jnp.max(jnp.abs(windowed - full))) > 1e-4
    # positions inside the window see the same thing either way
    np.testing.assert_allclose(
        model.logits_at(p, toks, jnp.asarray([5]), hf)[0],
        model.logits_at(p, toks, jnp.asarray([5]),
                        dict(hf, sliding_window=None))[0], rtol=1e-5, atol=1e-6)


def test_training_loss_and_gradients_match_the_program():
    from orion_tpu.models.transformer import loss_fn

    hf, m = CONFIGS["tiny-train"], _program("tiny-train")
    p = weights.make_params(hf, "float32", 11)
    seq = jax.random.randint(jax.random.key(3), (2, 33), 1, 256)
    batch = {"inputs": seq[:, :-1], "targets": seq[:, 1:]}
    l_ref, g_ref = jax.value_and_grad(
        lambda q: model.loss(q, batch["inputs"], batch["targets"], hf))(p)
    l_prog, g_prog = jax.value_and_grad(
        lambda q: loss_fn(q, batch, m)[0])(p)
    assert float(l_prog) == pytest.approx(float(l_ref), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-4
    # the control: int8 operands move the gradients far beyond that
    g_low = jax.grad(lambda q: model.loss(
        q, batch["inputs"], batch["targets"], hf, "int8"))(p)
    worst = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                for a, b in zip(jax.tree.leaves(g_low), jax.tree.leaves(g_ref)))
    assert worst > 3 * hf["correct"]["limits"]["grad_rel_err_max"]


@pytest.mark.parametrize("name", [
    "mistral-7b-train-1chip", "mistral-7b-train-4chip",
    "mixtral-8x7b-serve-1chip"])
def test_real_configurations_resolve_and_the_weights_fit_the_program(name):
    """Shapes only: the tree the benchmark draws is the tree the program's
    own init would make, at the published widths."""
    from benchmarks.harness.cell import program_config
    from orion_tpu.models import init_params

    hf = json.loads((REPO / "benchmarks" / "configs" / f"{name}.json").read_text())
    cfg = program_config(hf)          # checks every published size
    mine = jax.eval_shape(lambda: weights._draw(
        hf, jnp.dtype(cfg.model.param_dtype), jax.random.key(0)))
    theirs = jax.eval_shape(lambda: init_params(cfg.model, jax.random.key(0)))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    if name == "mixtral-8x7b-serve-1chip":
        assert cfg.model.capacity_factor == 4.0      # dropless


def test_the_same_seed_gives_the_same_weights():
    hf = CONFIGS["tiny-serve"]
    a = weights.make_params(hf, "float32", 2 ** 31 + 9)
    b = weights.make_params(hf, "float32", 2 ** 31 + 9)
    c = weights.make_params(hf, "float32", 2 ** 31 + 10)
    assert all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["lm_head"], c["lm_head"]))
