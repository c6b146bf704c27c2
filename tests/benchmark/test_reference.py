"""The plain reference against the program at a tiny size in float32, the
control that has to fail, and the layout of the benchmark-made weights."""

import dataclasses
import hashlib
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import model, weights
from tests.benchmark.conftest import CELLS, CONFIGS, OTHER, REPO

BM = json.loads((REPO / "BENCHMARK.json").read_text())


def _program(cfg_name):
    from benchmarks.harness.cell import program_config

    return program_config(CONFIGS[cfg_name]).model


def _params(hf, dtype, seed):
    """Weights of a tiny Mistral-shaped configuration (``reference/model.py``)."""
    return weights.make_params(model.param_spec(hf), hf["num_hidden_layers"],
                               dtype, seed)


@pytest.mark.parametrize("workload", ["tiny.dense-batch", "tiny.batch",
                                      "tiny.other-batch"])
def test_reference_matches_the_program_and_the_control_does_not(
        tiny_root, workload):
    """Each configuration through the reference ITS file names, found as the
    harness finds it."""
    from benchmarks.harness.cell import Cell
    from orion_tpu.models.transformer import forward

    cell = Cell.find(workload, root=tiny_root)
    hf, ref, cfg = cell.config, cell.reference(), cell.program_config()
    assert (ref.__name__ == "bench_reference_model") == (
        cell.config_name != OTHER)
    p = weights.for_cell(cell, cfg, 2 ** 31 + 5)
    toks = jax.random.randint(jax.random.key(1), (1, 48), 1,
                              cfg.model.vocab_size)
    got, _ = forward(p, toks, cfg.model)
    at = jnp.arange(48)
    want, margin = ref.logits_at(p, toks[0], at, hf)
    assert margin.shape == (48,) and bool(jnp.all(margin >= 0))
    assert bool(jnp.all(jnp.isinf(margin))) == ("num_local_experts" not in hf)
    err = float(jnp.linalg.norm(got[0] - want) / jnp.linalg.norm(want))
    assert err < 1e-5
    low = ref.logits_at(p, toks[0], at, hf, quant="int8")[0]
    control = float(jnp.linalg.norm(low - want) / jnp.linalg.norm(want))
    limit = hf["correct"]["limits"]["logit_rel_err_worst_probe_median_clear"]
    assert control > 3 * limit > 3 * err


def test_a_sliding_window_is_honoured():
    hf = dict(CONFIGS["tiny-serve"], sliding_window=8)
    p = _params(hf, "float32", 3)
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
    p = _params(hf, "float32", 11)
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


def _cell_of(config_name):
    return next(w["name"] for w in BM["workloads"]
                if w["config"] == config_name)


@pytest.mark.parametrize("name", [c["name"] for c in BM["configs"]])
def test_real_configurations_resolve_and_the_weights_fit_the_program(name):
    """Shapes only: the tree the benchmark draws, from the spec of the
    configuration's own reference, is the tree the program's own init would
    make, at the published widths; and the checks of ``program_config`` leave
    the program's Config as the file's preset and overrides give it."""
    from benchmarks.harness.cell import Cell
    from orion_tpu.config import get_config
    from orion_tpu.models import init_params

    cell = Cell.find(_cell_of(name))
    cfg = cell.program_config()       # checks every published size
    o = cell.config["orion"]
    assert dataclasses.asdict(cfg) == dataclasses.asdict(
        get_config(o["preset"], list(o["overrides"])))
    spec = cell.reference().param_spec(cell.config)
    mine = jax.eval_shape(lambda: weights._draw(
        spec, cfg.model.n_layers, jnp.dtype(cfg.model.param_dtype),
        jax.random.key(0)))
    theirs = jax.eval_shape(lambda: init_params(cfg.model, jax.random.key(0)))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    if name == "mixtral-8x7b-serve-1chip":
        assert cfg.model.capacity_factor == 4.0      # dropless


def test_the_tree_of_the_configuration_that_is_not_mistral_fits_the_program(
        tiny_root):
    from benchmarks.harness.cell import Cell
    from orion_tpu.models import init_params

    cell = Cell.find("tiny.other-batch", root=tiny_root)
    cfg = cell.program_config()
    mine = weights.for_cell(cell, cfg, 1)
    theirs = init_params(cfg.model, jax.random.key(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert "bq" in mine["blocks"]["attn"] and "bo" not in mine["blocks"]["attn"]
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


# sha256 over every leaf's path and bytes, as ``weights.make_params`` drew
# them at dc729ef (the parent of the PR that moved ``param_spec`` into the
# reference): the refactor may not move one bit of any cell's weights.
DRAWN_AT_THE_PARENT = {
    ("tiny-serve", "float32", 2 ** 31 + 9):
        "4ec4165377e17e6cd59157abc28e10b32e694e39f3a7dce66d39370164d0f431",
    ("tiny-serve", "bfloat16", 7):
        "1f3724343c3a99b85c0b3400f8bbd8a072b00f3a824579065221c00de9dfee42",
    ("tiny-moe-serve", "float32", 2 ** 31 + 9):
        "7b6111a59afa5f7a4d38343298308d3c1f94828a77cb4dc0759d16749cbcec13",
    ("tiny-moe-serve", "bfloat16", 7):
        "59a5deef5a7d400b784d48eff858a576a03001daaa4d7fd87ee8d103013d0fcd",
}


def _digest(params) -> str:
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(np.asarray(leaf).tobytes())
    return digest.hexdigest()


@pytest.mark.parametrize("cfg_name, dtype, seed", sorted(DRAWN_AT_THE_PARENT))
def test_the_weights_are_bitwise_those_of_the_parent(tiny_root, cfg_name,
                                                     dtype, seed):
    from benchmarks.harness.cell import Cell

    cell = Cell.find(next(c for c, v in CELLS.items() if v[0] == cfg_name),
                     root=tiny_root)
    p = weights.make_params(cell.reference().param_spec(cell.config),
                            cell.config["num_hidden_layers"], dtype, seed)
    assert _digest(p) == DRAWN_AT_THE_PARENT[cfg_name, dtype, seed]


def test_the_same_seed_gives_the_same_weights():
    hf = CONFIGS["tiny-serve"]
    a = _params(hf, "float32", 2 ** 31 + 9)
    b = _params(hf, "float32", 2 ** 31 + 9)
    c = _params(hf, "float32", 2 ** 31 + 10)
    assert all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["lm_head"], c["lm_head"]))


# -- a recurrence's time constants: the kind ("uniform", lo, hi) (PR 44) ---------
# A state-space layer's two such leaves over the ranges its source initialises
# them over (Mamba, arXiv:2312.00752, section 3.6: A = 1..d_state, dt in
# [0.001, 0.1]), beside one leaf of each older kind.
D_STATE = 16
A_LOG = ("uniform", 0.0, math.log(D_STATE))
DT_BIAS = ("uniform", math.log(math.expm1(0.001)), math.log(math.expm1(0.1)))
RECURRENT = {
    ("blocks", "ssm", "A_log"): ((2, 40, D_STATE), A_LOG),
    ("blocks", "ssm", "dt_bias"): ((2, 40), DT_BIAS),
    ("blocks", "ssm", "D"): ((2, 40), A_LOG),
    ("blocks", "ssm", "w_in"): ((2, 24, 80), "normal"),
    ("blocks", "ssm", "w_out"): ((2, 40, 24), "resid"),
    ("blocks", "ssm_norm", "scale"): ((2, 24), "norm"),
}


def test_a_uniform_leaf_lies_in_its_range_and_is_the_seeds_and_the_paths():
    assert DT_BIAS[1:] == pytest.approx((-6.907, -2.252), abs=1e-3)
    a = weights.make_params(RECURRENT, 2, "float32", 2 ** 31 + 9)["blocks"]
    b = weights.make_params(RECURRENT, 2, "float32", 2 ** 31 + 9)["blocks"]
    c = weights.make_params(RECURRENT, 2, "float32", 2 ** 31 + 10)["blocks"]
    for name, (_, lo, hi) in (("A_log", A_LOG), ("dt_bias", DT_BIAS)):
        leaf = np.asarray(a["ssm"][name])
        assert leaf.dtype == np.float32 and leaf.shape == RECURRENT[
            "blocks", "ssm", name][0]
        assert lo <= leaf.min() and leaf.max() < hi
        # spread over the range, not huddled at one end of it
        tenth = 0.1 * (hi - lo)
        assert leaf.min() < lo + tenth and hi - tenth < leaf.max()
        np.testing.assert_array_equal(leaf, np.asarray(b["ssm"][name]))
        assert not np.array_equal(leaf, np.asarray(c["ssm"][name]))
    # two paths of one shape and one range draw from keys of their own
    assert a["ssm"]["D"].shape == a["ssm"]["dt_bias"].shape
    u = lambda name, kind: (np.asarray(a["ssm"][name]) - kind[1]) / (
        kind[2] - kind[1])
    assert np.abs(u("D", A_LOG) - u("dt_bias", DT_BIAS)).max() > 0.5
    # the time constants the ranges are for: 1 / (dt x A) positions
    dt = np.log1p(np.exp(np.asarray(a["ssm"]["dt_bias"])))
    assert 0.001 <= dt.min() and dt.max() <= 0.1
    slowest = 1.0 / (dt[..., None] * np.exp(np.asarray(a["ssm"]["A_log"])))
    assert np.median(slowest) > 10 and slowest.max() > 300
    # in a narrower dtype: the float32 draw, cast
    low = weights.make_params(RECURRENT, 2, "bfloat16", 2 ** 31 + 9)["blocks"]
    assert low["ssm"]["A_log"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(low["ssm"]["A_log"]),
        np.asarray(a["ssm"]["A_log"].astype(jnp.bfloat16)))
    # a leaf of an older kind draws what it drew with no such leaf beside it
    alone = weights.make_params(
        {k: v for k, v in RECURRENT.items() if not isinstance(v[1], tuple)},
        2, "float32", 2 ** 31 + 9)["blocks"]
    for name in ("w_in", "w_out"):
        np.testing.assert_array_equal(np.asarray(alone["ssm"][name]),
                                      np.asarray(a["ssm"][name]))


@pytest.mark.parametrize("kind", [("uniform", 1.0, 1.0), ("uniform", 2.0, 1.0),
                                  ("normal", 0.0, 1.0)])
def test_a_kind_that_is_none_is_refused_by_the_leaf(kind):
    with pytest.raises(ValueError, match="blocks/ssm/A_log"):
        weights.make_params({("blocks", "ssm", "A_log"): ((2, 4), kind)},
                            2, "float32", 0)


# sha256 as above, of the tree ``make_params`` draws for the spec of
# ``data/tiny_other`` (leaves of all three older kinds) at seed 0, computed at
# ea36d75, the parent of the PR that added the fourth kind: the three draw
# what they drew, to the bit.
OTHER_DRAWN_AT_THE_PARENT = {
    "float32":
        "ff849035e2d2dc7098ab8721a342a1b9916f1fc2098ff3547f937441258e5e67",
    "bfloat16":
        "7935b4b7d7516d4c1009b06e9e75e5c29919fd7bbac2296d3daf04df274e4ce7",
}


@pytest.mark.parametrize("dtype", sorted(OTHER_DRAWN_AT_THE_PARENT))
def test_the_older_kinds_draw_what_they_drew_at_the_parent(tiny_root, dtype):
    from benchmarks.harness.cell import Cell

    cell = Cell.find("tiny.other-batch", root=tiny_root)
    spec = cell.reference().param_spec(cell.config)
    assert {kind for _, kind in spec.values()} == {"normal", "resid", "norm"}
    p = weights.make_params(spec, cell.config["num_layers"], dtype, 0)
    assert _digest(p) == OTHER_DRAWN_AT_THE_PARENT[dtype]
