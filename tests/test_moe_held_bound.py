"""The grouped dispatch of a layer that holds a SMALL share of its experts
moves the share's rows and not all k x T (``moe.held_row_bound``,
``bounds_held_rows``, ``_bounded_rows``): against the whole form on the same
inputs at a share of 1/16, tiny widths on the CPU, float32 and bfloat16 — a
random router, a router that sends every assignment to the held experts (four
passes where the bound allows one), padded blocks, the stacked experts read in
place, a layer scan; the rule on the benchmark's own configurations; the
engine's counter; what ``jax.grad`` says."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import get_config
from orion_tpu.models import moe as moe_lib

B, S = 2, 128
SHARE = ["model.n_experts=4", "model.router_width=64",
         "model.capacity_factor=64"]


def _layer(dtype, every_row_held=False, layers=None):
    """(cfg, one layer's parameters or ``layers`` of them stacked, x): 4 of
    64 experts held, top-4; with ``every_row_held`` a bias that makes the
    four held experts every position's choice."""
    cfg = get_config("tiny-mimo", SHARE).model
    E, W, D, F = (cfg.n_experts, cfg.resolved_router_width, cfg.d_model,
                  cfg.resolved_moe_d_ff)
    lead = () if layers is None else (layers,)
    ks = jax.random.split(jax.random.key(0), 6)
    bias = jnp.zeros(lead + (W,)).at[..., :E].add(
        10.0 if every_row_held else 0.0)
    params = {
        "router": 0.3 * jax.random.normal(ks[1], lead + (D, W)),
        "router_bias": bias,
        "w_in": 0.1 * jax.random.normal(ks[2], lead + (E, D, F), dtype),
        "w_gate": 0.1 * jax.random.normal(ks[3], lead + (E, D, F), dtype),
        "w_out": 0.1 * jax.random.normal(ks[4], lead + (E, F, D), dtype),
    }
    return cfg, params, jax.random.normal(ks[0], (B, S, cfg.d_model), dtype)


def _whole(monkeypatch, f, *args):
    """``f(*args)`` with the rule refusing: today's form of the dispatch."""
    with monkeypatch.context() as m:
        m.setattr(moe_lib, "bounds_held_rows", lambda cfg, tokens: False)
        return jax.jit(f)(*args)


def _close(got, want, valid=None):
    """Equal but for the order of a float32 sum over k, behind which a
    bfloat16 result is rounded once: within 1e-5 of the largest entry in
    float32, within one step of the type in bfloat16."""
    assert got.dtype == want.dtype and got.shape == want.shape
    step = 1e-5 if got.dtype == jnp.float32 else float(jnp.finfo(got.dtype).eps)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if valid is not None:
        got, want = (np.where(np.asarray(valid)[..., None], a, 0)
                     for a in (got, want))
    assert np.abs(want).max() > 0.05          # something was computed
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=step * np.abs(want).max())


DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["float32", "bfloat16"])


def test_the_bound_is_twice_the_share_in_whole_row_tiles():
    from orion_tpu.ops.grouped_matmul import ROW_TILE

    cfg = _layer(jnp.float32)[0]
    k, T = cfg.n_experts_per_token, B * S
    assert moe_lib.held_row_bound(cfg, T) == 256 == k * T // 4
    assert moe_lib.held_row_bound(cfg, 8 * T) == k * 8 * T // 8
    assert moe_lib.held_row_bound(cfg, 8 * T + 8) % ROW_TILE == 0
    assert moe_lib.held_row_bound(cfg, 2) == k * 2      # never past k x T
    assert moe_lib.bounds_held_rows(cfg, T)
    assert not moe_lib.bounds_held_rows(cfg, T // 2)    # the tile is a half
    whole = get_config("tiny-mimo").model               # holds every expert
    assert not moe_lib.bounds_held_rows(whole, 64 * T)


@DTYPES
@pytest.mark.parametrize("router", ["random", "every_row_held"])
def test_bounded_rows_compute_what_the_whole_block_does(
        dtype, router, monkeypatch):
    """(a) a random router: one pass; (b) every assignment on a held expert:
    k x T rows against a bound of a quarter of them, so three further passes
    under the loop, and nothing is dropped."""
    cfg, p, x = _layer(dtype, router == "every_row_held")
    f = lambda x, p: moe_lib.moe_dispatch(x, p, cfg)[0]
    assert moe_lib.takes_grouped_path(cfg, B, S)
    held = int(moe_lib.held_rows(x, p["router"], cfg, None, p["router_bias"]))
    R = moe_lib.held_row_bound(cfg, B * S)
    if router == "random":
        assert 0 < held < R
    else:
        assert held == cfg.n_experts_per_token * B * S == 4 * R
    _close(jax.jit(f)(x, p), _whole(monkeypatch, f, x, p))


@DTYPES
@pytest.mark.parametrize("lengths", [[1, 1], [128, 40], [97, 0]],
                         ids=["warm-up", "ragged", "empty-row"])
def test_a_padded_block_routes_its_real_positions_alone(
        dtype, lengths, monkeypatch):
    """(c) ``valid`` with one real position a row (the warm-up's block) and
    with ragged lengths; the padding's rows sort behind the held ones and
    no pass takes them."""
    cfg, p, x = _layer(dtype, every_row_held=True)
    valid = jnp.arange(S)[None, :] < jnp.asarray(lengths)[:, None]
    f = lambda x, p, v: moe_lib.moe_mlp_grouped(x, p, cfg, v)[0]
    got = jax.jit(f)(x, p, valid)
    _close(got, _whole(monkeypatch, f, x, p, valid), valid)
    assert not np.asarray(got, np.float32)[~np.asarray(valid)].any()


@DTYPES
@pytest.mark.parametrize("router", ["random", "every_row_held"])
def test_the_stacked_experts_are_read_in_place_under_a_layer_scan(
        dtype, router, monkeypatch):
    """(d) ``layer_stack`` and (e) ``jax.lax.scan`` over two layers, as
    ``runner._scan_layers`` calls the dispatch: a traced layer index, the
    overflow loop inside the scan's body."""
    cfg, stack, x = _layer(dtype, router == "every_row_held", layers=2)
    valid = jnp.arange(S)[None, :] < jnp.asarray([S, 90])[:, None]

    def scanned(x, stack):
        def body(x, l):
            p = {"router": stack["router"][l],
                 "router_bias": stack["router_bias"][l]}
            y, _ = moe_lib.moe_mlp_grouped(
                x, {**jax.tree.map(lambda a: a[l], stack), **p}, cfg, valid,
                None, (stack, l))
            return (x + y).astype(x.dtype), None
        return jax.lax.scan(body, x, jnp.arange(2))[0]

    def in_line(x, stack):
        for l in range(2):
            y, _ = moe_lib.moe_mlp_grouped(
                x, jax.tree.map(lambda a: a[l], stack), cfg, valid)
            x = (x + y).astype(x.dtype)
        return x

    got = jax.jit(scanned)(x, stack)
    _close(got, _whole(monkeypatch, scanned, x, stack), valid)
    _close(got, jax.jit(in_line)(x, stack), valid)


CELLS = {
    "mimo-v2.5.serve-mixed-16k": True,
    "ling-3.0-flash.serve-reason-128": False,
    "laguna-s-2.1.serve-batch-4k": False,
    "mixtral-8x7b.serve-batch": False,
    "glm-4.7-flash.serve-longctx": False,
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rule_admits_a_sixteenth_and_refuses_a_quarter_and_more(
        cell, monkeypatch):
    """(f) on the benchmark's committed configurations, at every prefill
    shape of the cell: MiMo's 16 of 256 are bounded at an eighth; Ling's 128
    of 512 (a half), Laguna's 128 of 256 (the whole) and the two that hold
    every expert are not, and their dispatch never enters the bounded
    function (traced at the cell's own widths, nothing computed)."""
    from benchmarks.harness.cell import Cell
    from benchmarks.kinds import serve

    found = Cell.find(cell)
    cfg = found.program_config()
    m = dataclasses.replace(cfg.model, kernels="xla")
    shapes = serve.cell_prefill_shapes(found, cfg.inference)
    k = m.n_experts_per_token
    for nb, s in shapes:
        assert moe_lib.takes_grouped_path(m, nb, s)
        assert moe_lib.bounds_held_rows(m, nb * s) is CELLS[cell]
        if CELLS[cell]:
            assert moe_lib.held_row_bound(m, nb * s) == k * nb * s // 8

    def entered(*a, **kw):
        raise AssertionError("the bounded form was traced")

    monkeypatch.setattr(moe_lib, "_bounded_rows", entered)
    E, W, D, F = (m.n_experts, m.resolved_router_width, m.d_model,
                  m.resolved_moe_d_ff)
    bf = jnp.bfloat16
    p = {"router": jax.ShapeDtypeStruct((D, W), jnp.float32),
         "w_in": jax.ShapeDtypeStruct((E, D, F), bf),
         "w_gate": jax.ShapeDtypeStruct((E, D, F), bf),
         "w_out": jax.ShapeDtypeStruct((E, F, D), bf)}
    nb, s = shapes[0]
    trace = lambda: jax.eval_shape(
        lambda x, p, v: moe_lib.moe_dispatch(x, p, m, valid=v)[0],
        jax.ShapeDtypeStruct((nb, s, D), bf), p,
        jax.ShapeDtypeStruct((nb, s), jnp.bool_))
    if CELLS[cell]:
        with pytest.raises(AssertionError, match="bounded form was traced"):
            trace()
    else:
        assert trace().shape == (nb, s, D)


def test_a_gradient_through_a_bounded_layer_is_refused_in_a_sentence(
        monkeypatch):
    """(g) ``lax.while_loop`` has no reverse mode; the error says what the
    layer is and what to differentiate in its place, and the whole form of
    the same layer still differentiates."""
    cfg, p, x = _layer(jnp.float32)
    loss = lambda x, p: moe_lib.moe_mlp_grouped(x, p, cfg)[0].sum()
    with pytest.raises(NotImplementedError,
                       match="while_loop, which has no reverse mode.*"
                             "differentiate a model that holds every"):
        jax.grad(loss, argnums=(0, 1))(x, p)
    assert jnp.isfinite(jax.jit(loss)(x, p))        # forward is unaffected
    gx, gp = _whole(monkeypatch, jax.grad(loss, argnums=(0, 1)), x, p)
    assert float(jnp.abs(gx).max()) > 0 and float(jnp.abs(gp["w_in"]).max()) > 0


@pytest.mark.parametrize("router", ["random", "every_row_held"])
def test_the_engine_counts_the_dispatches_that_passed_the_bound(
        router, monkeypatch):
    """The prefill program carries the count out beside the held rows: four
    prompts of 60 make one block of [4, 64] whose six sparse layers each
    bound 1024 assignments at 256 rows (2 of 32 experts held); a router that
    sends both of a position's first choices there fills 480. The first
    tokens are the whole form's."""
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models.transformer import init_params

    cfg = get_config("tiny-mimo", [
        "model.n_experts=2", "model.router_width=32",
        "model.capacity_factor=32", "inference.prefill_chunk=64"])
    params = init_params(cfg.model, jax.random.key(5))
    if router == "every_row_held":
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a.at[..., :2].add(10.0)
            if getattr(path[-1], "key", None) == "router_bias" else a, params)
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (4, 60), 1, cfg.model.vocab_size)).tolist()

    def run():
        eng = InferenceEngine(cfg, params, seed=0)
        reqs = [eng.submit_request(p, 1) for p in prompts]
        while eng.has_work():
            eng.step()
        t = eng.reset_timing()
        eng.close()
        return [r.generated for r in reqs], t

    firsts, t = run()
    assert t["prefill_dispatches"] == 1 and t["prefill_tokens"] == 240
    if router == "random":
        assert 0 < t["prefill_held_expert_rows"] < 6 * 256
        assert t["prefill_held_bound_overflows"] == 0
    else:
        assert t["prefill_held_expert_rows"] == 6 * 480
        assert t["prefill_held_bound_overflows"] == 6
    with monkeypatch.context() as m:
        m.setattr(moe_lib, "bounds_held_rows", lambda cfg, tokens: False)
        whole, u = run()
    assert firsts == whole
    assert u["prefill_held_expert_rows"] == t["prefill_held_expert_rows"]
    assert u["prefill_held_bound_overflows"] == 0       # nothing is bounded
