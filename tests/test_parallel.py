"""Distributed tier: parallelism layouts over 8 fake CPU devices.

SURVEY.md §5: cross-layout equivalence — the same seed and data must give
allclose losses under DP=8, FSDP=8, TP=2xDP=4, and mixed layouts; MoE under
EP. This is the test that proves parallelism is pure config (sharding rules)
and never changes semantics.
"""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from orion_tpu.config import get_config
from orion_tpu.train import Trainer

# Too heavy for the tier-1 CPU budget; runs in the full tier (no
# `-m "not slow"`).
pytestmark = pytest.mark.slow



def _run(preset: str, steps: int, *parallel: str):
    cfg = get_config(
        preset,
        ["runtime.platform=cpu", f"train.num_steps={steps}",
         "data.batch_size=8", "train.log_interval=1000",
         "optimizer.warmup_steps=2"] + list(parallel),
    )
    return Trainer(cfg).fit()


LAYOUTS = [
    ("dp8", ["parallel.dp=8"]),
    ("fsdp8", ["parallel.fsdp=8"]),
    ("dp4_tp2", ["parallel.dp=4", "parallel.tp=2"]),
    ("dp2_fsdp2_tp2", ["parallel.dp=2", "parallel.fsdp=2", "parallel.tp=2"]),
]


@pytest.fixture(scope="module")
def single_device_baseline():
    return _run("tiny-llama", 4)


@pytest.mark.parametrize("name,overrides", LAYOUTS)
def test_layout_matches_single_device(name, overrides, single_device_baseline):
    layout = _run("tiny-llama", 4, *overrides)
    for b, l in zip(single_device_baseline, layout):
        np.testing.assert_allclose(l.loss, b.loss, rtol=2e-3, atol=2e-3)


def test_moe_ep_matches_single_device():
    base = _run("tiny-mixtral", 4)
    ep = _run("tiny-mixtral", 4, "parallel.ep=4", "parallel.dp=2")
    for b, l in zip(base, ep):
        np.testing.assert_allclose(l.loss, b.loss, rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("dispatch", ["einsum", "sorted", "sorted_a2a"])
def test_moe_dispatch_modes_match_under_ep(dispatch):
    """All three MoE dispatch implementations train to the same losses on an
    ep=4 x dp=2 mesh. Run at generous capacity (no overflow) so
    sorted_a2a's per-slice drop rule coincides with global priority."""
    base = _run("tiny-mixtral", 3, "model.capacity_factor=8.0")
    got = _run(
        "tiny-mixtral", 3, "model.capacity_factor=8.0",
        f"model.moe_dispatch={dispatch}", "parallel.ep=4", "parallel.dp=2",
    )
    for b, l in zip(base, got):
        np.testing.assert_allclose(l.loss, b.loss, rtol=5e-3, atol=5e-3)


def test_moe_sorted_a2a_composes_with_tp():
    """ep x tp: the tp-sharded F contraction must psum before the inverse
    all_to_all (regression: each tp shard used to return a 1/tp partial)."""
    import dataclasses

    import jax.numpy as jnp

    from orion_tpu.models import moe as moe_lib
    from tests.conftest import make_mesh

    cfg = get_config("tiny-mixtral", ["runtime.platform=cpu"]).model
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    mesh = make_mesh(jax.devices("cpu")[:8], dp=2, ep=2, tp=2)
    keys = jax.random.split(jax.random.key(5), 5)
    E, D, F = cfg.n_experts, 16, cfg.d_ff
    x = jax.random.normal(keys[0], (4, 32, D), jnp.float32)
    params = {
        "router": jax.random.normal(keys[1], (D, E)) * 0.3,
        "w_in": jax.random.normal(keys[2], (E, D, F)) * 0.1,
        "w_gate": jax.random.normal(keys[3], (E, D, F)) * 0.1,
        "w_out": jax.random.normal(keys[4], (E, F, D)) * 0.1,
    }
    with jax.default_device(jax.devices("cpu")[0]):
        y_ref, _ = moe_lib.moe_mlp(x, params, cfg)
        y_a2a, _ = jax.jit(
            lambda x, p: moe_lib.moe_mlp_sorted_a2a(x, p, cfg, mesh)
        )(x, params)
    np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_ref),
                               atol=2e-5)


def test_moe_sorted_a2a_uses_explicit_all_to_all():
    """The sorted_a2a path must lower a REAL all_to_all on the ep axis (the
    reference's NCCL-a2a structure), not rely on SPMD-inferred comm."""
    import jax.numpy as jnp

    from orion_tpu.models import moe as moe_lib
    from tests.conftest import make_mesh

    cfg = get_config(
        "tiny-mixtral", ["runtime.platform=cpu", "model.moe_dispatch=sorted_a2a"]
    ).model
    mesh = make_mesh(jax.devices("cpu")[:8], dp=2, ep=4)
    keys = jax.random.split(jax.random.key(0), 5)
    E, D, F = cfg.n_experts, 16, cfg.d_ff
    x = jax.random.normal(keys[0], (4, 32, D), jnp.float32)
    params = {
        "router": jax.random.normal(keys[1], (D, E)) * 0.3,
        "w_in": jax.random.normal(keys[2], (E, D, F)) * 0.1,
        "w_gate": jax.random.normal(keys[3], (E, D, F)) * 0.1,
        "w_out": jax.random.normal(keys[4], (E, F, D)) * 0.1,
    }
    with jax.default_device(jax.devices("cpu")[0]):
        hlo = jax.jit(
            lambda x, p: moe_lib.moe_mlp_sorted_a2a(x, p, cfg, mesh)
        ).lower(x, params).as_text()
        assert "all_to_all" in hlo or "all-to-all" in hlo
        # And it matches the einsum reference (no overflow at these shapes?
        # capacity may drop; compare against sorted on the same slicing
        # instead: run a2a and the plain sorted path on identical inputs at
        # generous capacity).
        import dataclasses

        cfg_big = dataclasses.replace(cfg, capacity_factor=8.0)
        y_ref, aux_ref = moe_lib.moe_mlp(x, params, cfg_big)
        y_a2a, aux_a2a = jax.jit(
            lambda x, p: moe_lib.moe_mlp_sorted_a2a(x, p, cfg_big, mesh)
        )(x, params)
    np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_ref),
                               atol=2e-5)
    np.testing.assert_allclose(float(aux_a2a), float(aux_ref), rtol=1e-5)


def test_quantized_grad_reduce_tracks_exact(single_device_baseline):
    """DP with int8-wire gradient all-reduce (train.grad_quant_bits=8;
    comm/quantized.py) must track the exact-reduction loss trajectory to
    quantization tolerance."""
    quant = _run("tiny-llama", 4, "parallel.dp=8", "train.grad_quant_bits=8")
    for b, l in zip(single_device_baseline, quant):
        np.testing.assert_allclose(l.loss, b.loss, rtol=2e-2, atol=2e-2)


def test_quantized_grad_reduce_rejects_model_sharding():
    from orion_tpu.config import get_config as _gc

    cfg = _gc(
        "tiny-llama",
        ["runtime.platform=cpu", "parallel.dp=4", "parallel.tp=2",
         "data.batch_size=8", "train.grad_quant_bits=8"],
    )
    with pytest.raises(ValueError, match="pure DP"):
        Trainer(cfg)


def test_quantized_grad_reduce_rejects_loss_mask():
    """Masked batches would need token-weighted shard reduction; the
    quantized path must refuse rather than silently bias gradients."""
    import jax.numpy as jnp

    from orion_tpu.config import get_config as _gc

    cfg = _gc(
        "tiny-llama",
        ["runtime.platform=cpu", "parallel.dp=8", "data.batch_size=8",
         "train.grad_quant_bits=8", "train.log_interval=1000"],
    )
    t = Trainer(cfg)
    state = t.init_state()
    batch = dict(t.global_batch(0))
    batch["loss_mask"] = jnp.ones_like(batch["targets"], jnp.float32)
    with pytest.raises(ValueError, match="loss_mask"):
        t.train_step(state, batch)


def test_quantized_grad_reduce_with_grad_accum(single_device_baseline):
    # accum=2 splits the global batch of 8 into [2, 4]; dp=4 divides it.
    quant = _run(
        "tiny-llama", 4, "parallel.dp=4", "train.grad_quant_bits=8",
        "train.grad_accum=2",
    )
    for b, l in zip(single_device_baseline, quant):
        np.testing.assert_allclose(l.loss, b.loss, rtol=2e-2, atol=2e-2)


def test_fsdp_actually_shards_params():
    cfg = get_config(
        "tiny-llama",
        ["runtime.platform=cpu", "parallel.fsdp=8", "data.batch_size=8"],
    )
    t = Trainer(cfg)
    state = t.init_state()
    wq = state["params"]["blocks"]["attn"]["wq"]  # [L, D, N*H]; D on fsdp
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    assert shard_shapes == {(2, 8, 64)}, shard_shapes  # D=64 split 8 ways
    # Optimizer moments shard identically (ZeRO-3).
    mu = state["opt"]["mu"]["blocks"]["attn"]["wq"]
    assert {s.data.shape for s in mu.addressable_shards} == {(2, 8, 64)}


def test_tp_shards_heads():
    cfg = get_config(
        "tiny-llama",
        ["runtime.platform=cpu", "parallel.tp=2", "parallel.dp=4",
         "data.batch_size=8"],
    )
    t = Trainer(cfg)
    state = t.init_state()
    wq = state["params"]["blocks"]["attn"]["wq"]  # [L=2, D=64, N*H=64]
    shapes = {s.data.shape for s in wq.addressable_shards}
    assert shapes == {(2, 64, 32)}, shapes  # head dim split over tp=2


def test_graft_entry_dryrun(cpu_devices):
    """The driver's multichip dry-run must stay green, including odd device
    counts (odd factors must land on dp, never on model-dim axes)."""
    import __graft_entry__ as graft

    graft.dryrun_multichip(8)
    graft.dryrun_multichip(6)
