"""1F1B pipeline schedule (ISSUE 13): the hand-written pipeline VJP in
parallel/pipeline.py, and the composition debt it clears — scan_group x pp
and train.zero1 x pp.

Equivalence ladder: 1f1b forward is tick-for-tick GPipe's (bitwise), the
hand-written backward accumulates in jax.grad's reverse-microbatch order
(grads bitwise vs gpipe for dense / window-pattern / remat=names /
scan_group; the MoE aux cotangent fuses into the same pull with a
different add order — tight allclose there), and at matched dp=1 losses
are bitwise vs the pp=1 layout. The peak-stash pin is the schedule's
reason to exist: XLA's compiled temp bytes for the 1f1b step stay bounded
as M grows and sit well below GPipe's at equal M.

Fast cases ride tier-1; trainer-level knob compositions are slow-marked
per the 870s budget convention.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import get_config
from orion_tpu.models import forward, init_params, loss_fn
from tests.conftest import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(**kw):
    cfg = get_config("tiny-llama").model
    return dataclasses.replace(cfg, n_layers=4, **kw)


def _tokens(key, b=4, s=64, vocab=256):
    return jax.random.randint(key, (b, s), 0, vocab)


def _batch(tokens):
    return {"inputs": tokens, "targets": jnp.roll(tokens, -1, axis=1)}


def _grads(pcfg, mesh, params, batch):
    l, g = jax.jit(
        jax.value_and_grad(lambda p, b: loss_fn(p, b, pcfg, mesh)[0])
    )(params, batch)
    return jax.device_get(l), jax.device_get(g)


def _tree_equal(a, b):
    return all(
        np.array_equal(x, y)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


@pytest.mark.parametrize("pp,M", [(2, 2), (2, 4), (4, 4)])
def test_1f1b_forward_bitwise_vs_scan(cpu_devices, pp, M):
    """The 1f1b forward is the GPipe fill/drain (plus the stash): outputs
    reassemble BITWISE against the plain layer scan."""
    mcfg = _cfg()
    params = init_params(mcfg, jax.random.key(0))
    tokens = _tokens(jax.random.key(1))
    ref, _ = forward(params, tokens, mcfg)

    mesh = make_mesh(cpu_devices, pp=pp, dp=8 // pp)
    pcfg = dataclasses.replace(
        mcfg, pipeline_axis="pp", pp_microbatches=M, pp_schedule="1f1b"
    )
    out, _ = jax.jit(
        lambda p, t: forward(p, t, pcfg, mesh=mesh)
    )(params, tokens)
    assert jnp.array_equal(out, ref), (
        f"maxdiff {float(jnp.abs(out - ref).max())}"
    )


def test_1f1b_losses_grads_bitwise_vs_gpipe(cpu_devices):
    """Loss AND every grad leaf bitwise-equal to the gpipe schedule at the
    identical pp layout (the hand-written VJP accumulates in the same
    reverse-microbatch order as jax.grad's transposed scan); vs the pp=1
    reference the loss is bitwise and grads allclose (the microbatch
    split regroups the matmul batch reductions — true of gpipe since the
    seed)."""
    mcfg = _cfg()
    params = init_params(mcfg, jax.random.key(0))
    batch = _batch(_tokens(jax.random.key(1)))
    l_ref, g_ref = _grads(mcfg, None, params, batch)

    mesh = make_mesh(cpu_devices, pp=2, dp=4)
    gp = dataclasses.replace(mcfg, pipeline_axis="pp", pp_microbatches=2)
    fb = dataclasses.replace(gp, pp_schedule="1f1b")
    l_gp, g_gp = _grads(gp, mesh, params, batch)
    l_fb, g_fb = _grads(fb, mesh, params, batch)

    assert l_fb == l_gp == l_ref
    assert _tree_equal(g_fb, g_gp)
    for a, b in zip(jax.tree.leaves(g_fb), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-5)


def test_1f1b_window_pattern_bitwise_vs_gpipe(cpu_devices):
    """Gemma-2 interleaved local/global models pipeline over pattern
    groups; 1f1b rides the same unified layer_groups stage body, so its
    forward and grads are bitwise the gpipe schedule's."""
    mcfg = dataclasses.replace(get_config("tiny-gemma2").model, n_layers=4)
    params = init_params(mcfg, jax.random.key(0))
    batch = _batch(_tokens(jax.random.key(1)))

    mesh = make_mesh(cpu_devices, pp=2, dp=4)
    gp = dataclasses.replace(mcfg, pipeline_axis="pp", pp_microbatches=2)
    fb = dataclasses.replace(gp, pp_schedule="1f1b")
    l_gp, g_gp = _grads(gp, mesh, params, batch)
    l_fb, g_fb = _grads(fb, mesh, params, batch)
    assert l_fb == l_gp
    assert _tree_equal(g_fb, g_gp)


def test_1f1b_moe_matches_gpipe(cpu_devices):
    """MoE under 1f1b: losses bitwise vs gpipe; grads tight-allclose (the
    router aux cotangent rides the same jax.vjp pull as the activation
    cotangent, whose fused add order differs from the transposed scan's
    by ~1 ulp)."""
    mcfg = get_config("tiny-mixtral").model
    params = init_params(mcfg, jax.random.key(0))
    batch = _batch(_tokens(jax.random.key(2)))

    mesh = make_mesh(cpu_devices, pp=2, dp=2, ep=2)
    gp = dataclasses.replace(mcfg, pipeline_axis="pp", pp_microbatches=2)
    fb = dataclasses.replace(gp, pp_schedule="1f1b")
    l_gp, g_gp = _grads(gp, mesh, params, batch)
    l_fb, g_fb = _grads(fb, mesh, params, batch)
    assert l_fb == l_gp
    for a, b in zip(jax.tree.leaves(g_fb), jax.tree.leaves(g_gp)):
        np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-5)


def test_1f1b_remat_names_bitwise_vs_gpipe(cpu_devices):
    """remat=names wraps the stage body; the 1f1b backward re-linearizes
    the checkpointed body per tick and stays bitwise vs gpipe."""
    mcfg = _cfg(remat="names")
    params = init_params(mcfg, jax.random.key(0))
    batch = _batch(_tokens(jax.random.key(1)))
    mesh = make_mesh(cpu_devices, pp=2, dp=4)
    gp = dataclasses.replace(mcfg, pipeline_axis="pp", pp_microbatches=2)
    fb = dataclasses.replace(gp, pp_schedule="1f1b")
    l_gp, g_gp = _grads(gp, mesh, params, batch)
    l_fb, g_fb = _grads(fb, mesh, params, batch)
    assert l_fb == l_gp
    assert _tree_equal(g_fb, g_gp)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_scan_group_composes_with_pp_grads_bitwise(cpu_devices, schedule):
    """The lifted scan_group x pp rejection: the stage body iterates
    scan_group units through the SAME layer_groups the layer scan uses.
    Under remat=names grads are BITWISE across scan_group values at the
    identical pp layout (the same convention the non-pp scan_group pin
    uses — the named-save cut stabilizes XLA's fusion choices); with
    remat off the grouped body fuses differently by ~1 ulp, so losses
    stay bitwise and grads tight-allclose."""
    mcfg = _cfg(remat="names")
    params = init_params(mcfg, jax.random.key(0))
    batch = _batch(_tokens(jax.random.key(1)))
    mesh = make_mesh(cpu_devices, pp=2, dp=4)
    base = dataclasses.replace(
        mcfg, pipeline_axis="pp", pp_microbatches=2, pp_schedule=schedule
    )
    sg2 = dataclasses.replace(base, scan_group=2)
    l1, g1 = _grads(base, mesh, params, batch)
    l2, g2 = _grads(sg2, mesh, params, batch)
    assert l1 == l2
    assert _tree_equal(g1, g2)

    nr1 = dataclasses.replace(base, remat="none")
    nr2 = dataclasses.replace(sg2, remat="none")
    l1, g1 = _grads(nr1, mesh, params, batch)
    l2, g2 = _grads(nr2, mesh, params, batch)
    assert l1 == l2
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-5)


def _trainer_losses(axes, extra=(), steps=3, ret=False):
    from orion_tpu.train import Trainer

    overrides = [
        "runtime.platform=cpu", "data.batch_size=4", "data.seq_len=64",
        "model.n_layers=4", "train.num_steps=4", "train.log_interval=100",
        "optimizer.warmup_steps=1",
    ] + [f"parallel.{k}={v}" for k, v in axes.items()] + list(extra)
    t = Trainer(get_config("tiny-llama", overrides))
    guard = t.cfg.train.anomaly_guard
    state, _ = t.restore_or_init()
    losses = []
    for step in range(steps):
        batch = t.global_batch(step)
        if guard:
            state, m = t.train_step(state, batch, t._spike_limit())
        else:
            state, m = t.train_step(state, batch)
        losses.append(float(jax.device_get(m["loss"])))
    if ret:
        return losses, jax.device_get(state), t
    return losses


def test_zero1_composes_with_pp_bitwise(cpu_devices):
    """The lifted zero1 x pp rejection (stage-local dp): losses AND the
    full post-step state bitwise vs zero1-off at the identical pp
    layout, with the optimizer moments physically 1/dp per chip
    (memory_report by_category pins the exact shrink)."""
    axes = {"pp": 2, "dp": 4, "pp_microbatches": 2, "pp_schedule": "1f1b"}
    l_off, s_off, t_off = _trainer_losses(axes, ret=True)
    l_on, s_on, t_on = _trainer_losses(axes, ["train.zero1=true"], ret=True)
    assert l_on == l_off
    assert _tree_equal(s_on, s_off)
    rep_on = t_on.memory_report(assert_donation=False)["by_category"]
    rep_off = t_off.memory_report(assert_donation=False)["by_category"]
    assert rep_off["moments"] == 4 * rep_on["moments"]  # exact 1/dp, dp=4
    assert rep_on["params"] == rep_off["params"]


def test_1f1b_peak_stash_bounded_by_pp_not_M(cpu_devices):
    """The 1F1B memory claim, pinned on XLA's compiled memory analysis:
    the step's temp bytes (activations + workspace) do NOT grow when M
    quadruples (stash bounded by the stage count: one boundary row per
    microbatch totals B rows regardless of M, interiors live one tick),
    while GPipe's jax.grad residuals keep every tick's interiors alive —
    multiples above 1f1b at equal M."""
    from orion_tpu.train import Trainer

    def temp_bytes(sched, M):
        overrides = [
            "runtime.platform=cpu", "data.batch_size=8", "data.seq_len=64",
            "model.n_layers=4", "train.num_steps=4",
            "optimizer.warmup_steps=1",
            f"parallel.pp=2", f"parallel.pp_microbatches={M}",
            f"parallel.pp_schedule={sched}",
        ]
        t = Trainer(get_config("tiny-llama", overrides))
        rep = t.memory_report(assert_donation=False)
        if not rep.get("available"):
            pytest.skip("compiled memory analysis unavailable")
        return rep["temp_bytes"]

    fb2, fb8 = temp_bytes("1f1b", 2), temp_bytes("1f1b", 8)
    gp8 = temp_bytes("gpipe", 8)
    assert fb8 <= fb2 * 1.15, (fb2, fb8)
    assert fb8 < gp8, (fb8, gp8)


def test_pp_schedule_and_composition_validation():
    """The ISSUE 13 validation sweep: pp_schedule domain gains '1f1b';
    the lifted combos construct; the genuinely-unsupported ones reject
    with typed errors."""
    from orion_tpu.config import ParallelConfig
    from orion_tpu.train import Trainer

    with pytest.raises(ValueError, match="pp_schedule"):
        ParallelConfig(pp_schedule="bogus")
    common = ["runtime.platform=cpu", "data.batch_size=4",
              "data.seq_len=64", "model.n_layers=4"]
    # 1f1b x virtual stages: rejected (V amortization is interleaved's).
    with pytest.raises(ValueError, match="pp_virtual_stages"):
        Trainer(get_config("tiny-llama", common + [
            "parallel.pp=2", "parallel.pp_schedule=1f1b",
            "parallel.pp_virtual_stages=2",
        ]))
    # zero1_quantize x pp: the int8 wire legs stay rejected under pp.
    with pytest.raises(ValueError, match="zero1_quantize is rejected"):
        Trainer(get_config("tiny-llama", common + [
            "parallel.pp=2", "parallel.dp=2", "train.zero1=true",
            "train.zero1_quantize=int8",
        ]))
    # scan_group x pp divisibility: 4 layers / scan_group 2 = 2 units,
    # which pp=4 cannot stage.
    with pytest.raises(ValueError, match="scan unit"):
        Trainer(get_config("tiny-llama", common + [
            "parallel.pp=4", "model.scan_group=2",
        ]))
    # The lifted combos construct without raising.
    Trainer(get_config("tiny-llama", common + [
        "parallel.pp=2", "parallel.dp=2", "parallel.pp_schedule=1f1b",
        "train.zero1=true", "model.scan_group=2",
        "parallel.pp_microbatches=2",
    ]))


# -- heavier trainer-level compositions (slow tier) -------------------------


@pytest.mark.slow
@pytest.mark.parametrize(
    "extra",
    [
        ["train.remat=names"],
        ["train.grad_accum=2"],
        ["train.anomaly_guard=true"],
        ["model.scan_group=2"],
    ],
    ids=["remat-names", "grad-accum", "anomaly-guard", "scan-group"],
)
def test_trainer_1f1b_knob_compositions_bitwise(cpu_devices, extra):
    """{remat=names, grad_accum, anomaly_guard, scan_group} x 1f1b:
    trainer losses bitwise vs the SAME knobs at pp=1 on a dp=1 layout
    (matched dp keeps the loss reduction grouping identical)."""
    base = _trainer_losses({}, extra)
    fb = _trainer_losses(
        {"pp": 2, "dp": 1, "pp_microbatches": 2, "pp_schedule": "1f1b"},
        extra,
    )
    assert fb == base


@pytest.mark.slow
def test_trainer_1f1b_gemma2_packed(cpu_devices):
    """Window-pattern x packed rows x 1f1b: the full row-state
    composition, trainer-level, bitwise vs gpipe at the same layout."""
    mcfg = get_config("tiny-gemma2").model
    params = init_params(mcfg, jax.random.key(0))
    tokens = _tokens(jax.random.key(1))
    B, S = tokens.shape
    half = S // 2
    seg = jnp.concatenate(
        [jnp.full((B, half), 1, jnp.int32),
         jnp.full((B, S - half), 2, jnp.int32)], axis=1)
    pos = jnp.concatenate(
        [jnp.arange(half, dtype=jnp.int32)[None].repeat(B, 0),
         jnp.arange(S - half, dtype=jnp.int32)[None].repeat(B, 0)], axis=1)
    batch = {"inputs": tokens, "targets": jnp.roll(tokens, -1, 1),
             "segment_ids": seg, "positions": pos}

    mesh = make_mesh(cpu_devices, pp=2, dp=4)
    gp = dataclasses.replace(mcfg, pipeline_axis="pp", pp_microbatches=2)
    fb = dataclasses.replace(gp, pp_schedule="1f1b")
    l_gp, g_gp = _grads(gp, mesh, params, batch)
    l_fb, g_fb = _grads(fb, mesh, params, batch)
    assert l_fb == l_gp
    assert _tree_equal(g_fb, g_gp)


@pytest.mark.slow
def test_zero1_pp_checkpoint_roundtrip(cpu_devices, tmp_path):
    """zero1 x pp checkpoints: the dp-sharded (and pp-sharded) optimizer
    state saves with its layout in the manifest and restores bitwise."""
    from orion_tpu.ckpt import CheckpointManager
    from orion_tpu.config import CheckpointConfig
    from orion_tpu.train import Trainer

    overrides = [
        "runtime.platform=cpu", "data.batch_size=4", "data.seq_len=64",
        "model.n_layers=4", "train.num_steps=4", "optimizer.warmup_steps=1",
        "parallel.pp=2", "parallel.dp=4", "parallel.pp_microbatches=2",
        "parallel.pp_schedule=1f1b", "train.zero1=true",
        f"checkpoint.directory={tmp_path}", "checkpoint.async_save=false",
    ]
    t = Trainer(get_config("tiny-llama", overrides))
    state, _ = t.restore_or_init()
    state, _ = t.train_step(state, t.global_batch(0))
    assert t.ckpt is not None
    t.ckpt.save(1, state, force=True)
    t.ckpt.wait()
    ref = jax.device_get(state)

    t2 = Trainer(get_config("tiny-llama", overrides))
    restored = t2.ckpt.restore_latest(t2.abstract_state())
    assert restored is not None
    got, step = restored
    assert step == 1
    assert _tree_equal(jax.device_get(got), ref)


# -- tools/pp_bubble_bench.py --smoke (tier-1 wiring) -----------------------


def test_pp_bubble_bench_smoke():
    """The bench's tier-1 twin: schedule rows (incl. the typed-error row
    for the known interleaved x dp abort on this runtime), the
    peak-bytes column, the bitwise parity phase, and a verdict with no
    problem of count or structure. The tool's "slower than gpipe /
    interleaved" problems order two CPU wall clocks of two steps each,
    taken beside five other xdist workers: those (and the exit code they
    set) are not judged here, each row's time only held finite and
    positive."""
    import math

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "pp_bubble_bench.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    rows = [json.loads(l) for l in proc.stdout.splitlines()
            if l.strip().startswith("{")]
    verdict = [r for r in rows if r.get("verdict") == "pp_bubble"]
    assert verdict and "problems" in verdict[0], proc.stdout + proc.stderr
    assert proc.returncode == (0 if verdict[0]["ok"] else 1)
    problems = [p for p in verdict[0]["problems"] if "slower than" not in p]
    assert not problems, rows
    timed = [r["ms_per_step"] for r in rows if "ms_per_step" in r]
    assert timed and all(math.isfinite(t) and t > 0 for t in timed), rows
    layouts = {r.get("layout") for r in rows}
    assert "pp2-1f1b-M2" in layouts
    onef = [r for r in rows if r.get("layout") == "pp2-1f1b-M2"][0]
    assert "peak_activation_bytes" in onef
    parity = [r for r in rows if str(r.get("layout", "")).startswith("parity")]
    assert parity and all(r.get("bitwise_vs_pp1") for r in parity)
