"""Grouped layer-scan + name-based selective remat: equivalence + HLO.

The round-9 perf levers (PERF.md "Scan grouping + selective remat") are
exactly grad-preserving and their structural win — G× fewer stacked-buffer
dynamic-update-slice writes in the scanned train step — is assertable from
lowered HLO text on CPU. Tier-1 locks both in without a TPU:

- losses are BITWISE identical across every (scan_group, remat) combo
  (the forward math never changes);
- grads are bitwise identical across scan_group values under remat=none /
  remat=names (the saved names pin the backward's recompute structure) and
  across names vs names+offload (same save set, different residence);
- grads under remat=full are allclose-tight across scan_group: the grouped
  remat body legitimately refuses bitwise (XLA fuses the group's recompute
  with the backward differently), which is the standard remat contract;
- the executed stacked-DUS count (sum over update-slice ops of their
  target buffer's leading dim — the scan trip count) shrinks by exactly G
  under remat=full.

Heavy shapes / end-to-end trainer compositions are `slow` per the tier-1
budget convention (ROADMAP.md).
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import get_config
from orion_tpu.models import init_params, loss_fn


def _grads(preset, overrides, seq=16, batch_extra=None):
    cfg = get_config(preset, overrides).model
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(
        jax.random.key(1), (2, seq), 0, cfg.vocab_size
    )
    batch = {"inputs": tokens, "targets": tokens}
    if batch_extra:
        batch.update(batch_extra)
    (loss, _), grads = jax.jit(
        jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True
        )
    )(params)
    return float(loss), grads


def _assert_tree_bitwise(a, b, msg=""):
    assert jax.tree.structure(a) == jax.tree.structure(b), msg
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb), err_msg=msg
        )


def _assert_tree_close(a, b, atol, msg=""):
    assert jax.tree.structure(a) == jax.tree.structure(b), msg
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), atol=atol, err_msg=msg
        )


BASE = ["model.n_layers=4"]


def test_scan_group_grads_bitwise():
    """scan_group only regroups the scan: under remat=names (the policy
    this knob ships with) losses AND grads are bitwise identical at
    G=1/2/4 — the saved names pin the backward's recompute structure, so
    XLA cannot re-round it. (Under remat=none the degenerate G=n_layers
    case elides the loop entirely and re-fuses; that combination is
    allclose-covered by the slow tier.)"""
    ref_loss, ref_g = _grads("tiny-llama", BASE + ["model.remat=names"])
    for g in (2, 4):
        loss, grads = _grads(
            "tiny-llama",
            BASE + ["model.remat=names", f"model.scan_group={g}"],
        )
        assert loss == ref_loss, g
        _assert_tree_bitwise(ref_g, grads, f"remat=names G={g}")


def test_remat_policies_grad_equivalent():
    """none / full / dots / names / names+offload: bitwise losses, tight-
    allclose grads (remat recompute may re-round), and names==offload
    bitwise (identical save set, only the residence differs)."""
    ref_loss, ref_g = _grads("tiny-llama", BASE)
    variants = {
        "full": ["model.remat=full"],
        "dots": ["model.remat=dots"],
        "names": ["model.remat=names"],
        "names+offload": ["model.remat=names", "model.remat_offload=true"],
    }
    grads_by = {}
    for name, ov in variants.items():
        loss, grads = _grads("tiny-llama", BASE + ov)
        assert loss == ref_loss, name
        _assert_tree_close(ref_g, grads, atol=1e-6, msg=name)
        grads_by[name] = grads
    _assert_tree_bitwise(
        grads_by["names"], grads_by["names+offload"], "offload residence"
    )


@pytest.mark.slow
def test_scan_group_with_full_and_none_remat_close():
    """Grouped remat=full recompute (and the loop-elided remat=none
    G=n_layers case) are allclose-tight across G — bitwise is not promised
    there: XLA fuses the grouped recompute/unlooped body differently."""
    _, f1 = _grads("tiny-llama", BASE + ["model.remat=full"])
    for ov in (["model.remat=full", "model.scan_group=2"],
               ["model.remat=full", "model.scan_group=4"]):
        _, g = _grads("tiny-llama", BASE + ov)
        _assert_tree_close(f1, g, atol=1e-6, msg=str(ov))
    _, n1 = _grads("tiny-llama", BASE)
    _, n4 = _grads("tiny-llama", BASE + ["model.scan_group=4"])
    _assert_tree_close(n1, n4, atol=1e-6)


# -- HLO structure: the stash-write reduction is textually provable -------
# The DUS counter moved to the shared contract engine (ISSUE 15):
# orion_tpu.analysis.contracts.executed_stacked_dus is the single
# definition both this pin and tools/contract_check.py matchers use.
from orion_tpu.analysis.contracts import executed_stacked_dus  # noqa: E402


def _lowered_grad_text(overrides):
    cfg = get_config("tiny-llama", ["model.n_layers=8"] + overrides).model
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                cfg.vocab_size)
    batch = {"inputs": tokens, "targets": tokens}
    f = jax.jit(jax.grad(lambda p: loss_fn(p, batch, cfg)[0]))
    return f.lower(params).as_text()


def test_stacked_dus_writes_shrink_by_group():
    """remat=full: executed stacked-DUS writes drop exactly G× at
    scan_group=G (the 18.8% stash share's byte traffic, PERF.md);
    remat=names drops too (the grad stacking shrinks G×; the named stash
    stays per-layer by design)."""
    full = {
        g: executed_stacked_dus(
            _lowered_grad_text([f"model.remat=full",
                                f"model.scan_group={g}"])
        )
        for g in (1, 2, 4)
    }
    assert full[1] > 0
    assert full[2] * 2 == full[1], full
    assert full[4] * 4 == full[1], full

    names1 = executed_stacked_dus(
        _lowered_grad_text(["model.remat=names"])
    )
    names4 = executed_stacked_dus(
        _lowered_grad_text(["model.remat=names", "model.scan_group=4"])
    )
    assert names4 < names1 * 0.6, (names1, names4)


# -- validation -----------------------------------------------------------


def test_config_validation():
    with pytest.raises(ValueError, match="none|full|dots|names"):
        get_config("tiny-llama", ["model.remat=banana"])
    with pytest.raises(ValueError, match="scan_group"):
        get_config("tiny-llama", ["model.scan_group=0"])


def test_trainer_validation():
    from orion_tpu.train import Trainer

    base = ["runtime.platform=cpu"]
    with pytest.raises(ValueError, match="remat_offload"):
        Trainer(get_config("tiny-llama", base + ["train.remat_offload=true"]))
    with pytest.raises(ValueError, match="divisible by the layer-scan"):
        Trainer(get_config("tiny-llama", base + ["model.scan_group=3"]))
    with pytest.raises(ValueError, match="scan_layers"):
        Trainer(get_config(
            "tiny-llama",
            base + ["model.scan_group=2", "model.scan_layers=false"],
        ))


def test_train_remat_override_folds_into_model():
    """train.remat / train.remat_offload are folded into model.remat by the
    Trainer (the forward's source of truth) without touching the input
    config object."""
    from orion_tpu.train import Trainer

    cfg = get_config("tiny-llama", [
        "runtime.platform=cpu", "train.remat=names",
        "train.remat_offload=true",
    ])
    assert cfg.model.remat == "none"   # untouched until the Trainer folds
    t = Trainer(cfg)
    assert t.cfg.model.remat == "names"
    assert t.cfg.model.remat_offload is True
    # An explicit train.remat=none must DISABLE remat (the override parser
    # spells it None; it is not the "inherit" sentinel).
    t2 = Trainer(get_config("tiny-llama", [
        "runtime.platform=cpu", "model.remat=full", "train.remat=none",
    ]))
    assert t2.cfg.model.remat == "none"
    # Restating the canonical names spelling keeps a configured offload
    # (no silent fall-back of the stash into HBM); overriding to a
    # non-names policy drops it (offload only pairs with names).
    t3 = Trainer(get_config("tiny-llama", [
        "runtime.platform=cpu", "model.remat=names",
        "model.remat_offload=true", "train.remat=names",
    ]))
    assert t3.cfg.model.remat_offload is True
    t4 = Trainer(get_config("tiny-llama", [
        "runtime.platform=cpu", "model.remat=names",
        "model.remat_offload=true", "train.remat=full",
    ]))
    assert t4.cfg.model.remat == "full"
    assert t4.cfg.model.remat_offload is False


@pytest.mark.slow
def test_trainer_donation_no_copies():
    """The donated master-param/optimizer buffers must alias into the step
    outputs — XLA's compiled memory analysis is the ground truth (an
    unaliased buffer silently doubles its footprint)."""
    from orion_tpu.train import Trainer

    cfg = get_config("tiny-llama", [
        "runtime.platform=cpu", "model.n_layers=4", "model.scan_group=2",
        "train.remat=names",
    ])
    report = Trainer(cfg).memory_report(assert_donation=True)
    assert report["available"]
    assert report["donated_state_bytes"] > 0
    assert report["unaliased_donated_bytes"] == 0
    assert report["alias_bytes"] >= report["donated_state_bytes"]


# -- profile-report grouping: stash share stays attributable --------------


def test_profile_report_classifier_and_compare(tmp_path, capsys):
    import gzip
    import json as _json

    from tools import profile_report as pr

    # The grouped scan's rematted/cloned fusion names must collapse onto
    # their base group and classify as scan-stash.
    assert pr.group_name(
        "bitcast_dynamic-update-slice_fusion.12.remat2.clone.1"
    ) == "bitcast_dynamic-update-slice_fusion"
    assert pr.classify("bitcast_dynamic-update-slice_fusion") == "scan-stash"
    assert pr.classify("attention_fwd_kernel") == "attention-kernel"
    assert pr.classify("convolution_f32") == "matmul"
    assert pr.classify("fusion") == "fusion(matmul+elementwise)"

    def write_trace(d, events):
        root = tmp_path / d
        root.mkdir()
        meta = [{"ph": "M", "pid": 1, "name": "process_name",
                 "args": {"name": "/device:TPU:0"}}]
        evts = [{"ph": "X", "pid": 1, "dur": dur, "name": name, "ts": 0}
                for name, dur in events]
        with gzip.open(root / "t.trace.json.gz", "wt") as f:
            _json.dump({"traceEvents": meta + evts}, f)
        return str(root)

    a = write_trace("a", [("fusion.1", 70),
                          ("bitcast_dynamic-update-slice_fusion.3", 20),
                          ("attention_fwd.2", 10)])
    b = write_trace("b", [("fusion.9.remat", 80),
                          ("bitcast_dynamic-update-slice_fusion.7.clone", 10),
                          ("attention_fwd.4", 10)])
    groups, total = pr.leaf_groups(pr.find_trace(a))
    assert total == 100
    assert groups["bitcast_dynamic-update-slice_fusion"] == 20
    shares = pr.bucket_shares(groups)
    assert shares["scan-stash"] == pytest.approx(0.2)

    assert pr.compare(a, b) == 0
    out = capsys.readouterr().out
    assert "scan-stash" in out and "-10.0%" in out


# -- heavy compositions (full tier) ---------------------------------------


@pytest.mark.slow
def test_gemma2_pattern_times_scan_group():
    """Window-pattern (Gemma-family) models group by scan_group x pattern;
    windows stay static per within-group position, so grads match the
    per-pattern-group scan under the same remat policy."""
    _, g1 = _grads("tiny-gemma2", ["model.remat=names"])
    _, g2 = _grads(
        "tiny-gemma2", ["model.remat=names", "model.scan_group=2"]
    )
    _assert_tree_bitwise(g1, g2, "gemma2 scan_group=2")


@pytest.mark.slow
def test_moe_scan_group_and_names():
    """MoE blocks thread the checkpoint names (moe_router_gate) through
    every dispatch mode's shared router; grouping stays grad-preserving."""
    _, g1 = _grads("tiny-mixtral", BASE + ["model.remat=names"])
    _, g2 = _grads(
        "tiny-mixtral",
        BASE + ["model.remat=names", "model.scan_group=2"],
    )
    _assert_tree_bitwise(g1, g2, "mixtral scan_group=2")


@pytest.mark.slow
def test_trainer_grouped_names_matches_baseline_losses():
    """End-to-end: a grouped trainer reproduces the ungrouped run's
    per-step losses bitwise (same data, same updates). Both runs carry
    remat=names — grouping alone is the bitwise contract; the policy
    itself may re-round vs remat=none (only allclose, per
    test_remat_policies_grad_equivalent)."""
    from orion_tpu.train import Trainer

    base_ov = [
        "runtime.platform=cpu", "model.n_layers=4", "train.num_steps=5",
        "train.log_interval=100", "optimizer.warmup_steps=2",
        "train.remat=names",
    ]
    h_ref = Trainer(get_config("tiny-llama", base_ov)).fit()
    h_grp = Trainer(get_config("tiny-llama", base_ov + [
        "model.scan_group=2",
    ])).fit()
    assert [m.loss for m in h_ref] == [m.loss for m in h_grp]


@pytest.mark.slow
def test_trainer_names_offload_trains():
    """remat_offload end to end on the CPU backend (pinned_host residence):
    the loss falls and matches the non-offloaded run bitwise."""
    from orion_tpu.train import Trainer

    base_ov = [
        "runtime.platform=cpu", "model.n_layers=4", "train.num_steps=4",
        "train.log_interval=100", "optimizer.warmup_steps=2",
        "model.scan_group=2", "train.remat=names",
    ]
    h_names = Trainer(get_config("tiny-llama", base_ov)).fit()
    h_off = Trainer(get_config(
        "tiny-llama", base_ov + ["train.remat_offload=true"]
    )).fit()
    assert [m.loss for m in h_names] == [m.loss for m in h_off]
    assert h_off[-1].loss < h_off[0].loss
