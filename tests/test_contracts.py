"""Static-contract engine tests (ISSUE 15).

Two halves: (1) every predicate is proven LIVE by an injected violation —
a deliberate donation leak, a planted host callback, a guard-off program
containing is_finite, a synthetic f64/collective module — a contract that
can only pass vacuously guards nothing; (2) the cpu-viable smoke
contracts hold on the real programs (the full layout grid sweeps via
tools/contract_check.py, whose --smoke twin also runs here)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.analysis import contracts as C


# ---------------------------------------------------------------------------
# Injected violations: every predicate must fire
# ---------------------------------------------------------------------------


def test_donation_leak_fires():
    """A donated buffer whose bytes cannot alias (output smaller than the
    input) must trip donation_complete — the doubled-footprint class."""
    x = np.ones(64, np.float32)
    art = C.artifact_from_fn(
        "leak", lambda v: (v * 2.0)[:8], x, donate_argnums=(0,)
    )
    viols = C.check_artifact(art, (C.donation_complete,), "leak")
    assert viols and "leaked" in viols[0].detail

    # Control: full aliasing passes.
    ok = C.artifact_from_fn(
        "aliased", lambda v: v * 2.0, x, donate_argnums=(0,)
    )
    assert C.check_artifact(ok, (C.donation_complete,), "aliased") == []


def test_planted_host_callback_fires():
    def bad(v):
        jax.debug.callback(lambda a: None, v)
        return v * 2

    art = C.artifact_from_fn("cb", bad, np.ones(4, np.float32))
    viols = C.check_artifact(art, (C.no_host_callbacks,), "cb")
    assert viols and "callback" in viols[0].detail
    # The StableHLO text matcher agrees with the jaxpr walker (the
    # fallback path when no trace is available).
    art_text = C.ProgramArtifact("cb_text", stablehlo_text=art.stablehlo)
    assert C.check_artifact(art_text, (C.no_host_callbacks,), "cb_text")

    ok = C.artifact_from_fn("pure", lambda v: v * 2, np.ones(4, np.float32))
    assert C.check_artifact(ok, (C.no_host_callbacks,), "pure") == []


def test_guard_off_finiteness_fires():
    """A 'guard-off' program that stages is_finite trips purity; the same
    artifact satisfies the guard-ON positive control (finiteness_staged),
    so the two predicates are exact complements on one artifact."""
    art = C.artifact_from_fn(
        "guardy",
        lambda v: jnp.where(jnp.isfinite(v).all(), v, jnp.zeros_like(v)),
        np.ones(4, np.float32),
    )
    viols = C.check_artifact(art, (C.no_finiteness_ops,), "guardy")
    assert viols and "is_finite" in viols[0].detail
    assert C.check_artifact(art, (C.finiteness_staged,), "guardy") == []

    pure = C.artifact_from_fn("pure", lambda v: v + 1, np.ones(4))
    assert C.check_artifact(pure, (C.no_finiteness_ops,), "pure") == []
    assert C.check_artifact(pure, (C.finiteness_staged,), "pure")


def test_f64_fires_on_text_and_jaxpr():
    art = C.ProgramArtifact(
        "f64", stablehlo_text="%0 = stablehlo.add : tensor<4xf64>"
    )
    assert C.check_artifact(art, (C.no_f64,), "f64")
    ok = C.ProgramArtifact(
        "f32", stablehlo_text="%0 = stablehlo.add : tensor<4xf32>"
    )
    assert C.check_artifact(ok, (C.no_f64,), "f32") == []


def test_collective_census_and_inventory():
    txt = "\n".join([
        "  %ag = f32[8,4] all-gather(%p), replica_groups={}",
        "  %ar.1 = f32[8] all-reduce(%a), to_apply=add",
        "  %ars = f32[8] all-reduce-start(%b)",
        "  %ard = f32[8] all-reduce-done(%ars)",   # not a new collective
        "  %cp = f32[8] collective-permute(%c)",
        # Async starts on real TPU backends carry TUPLE result types
        # (spaces inside) — the census must count them too.
        "  %ags = (f32[1,8], f32[8,8]) all-gather-start(%q)",
        "  %agd = f32[8,8] all-gather-done(%ags)",
        "  %cps = (f32[2], f32[2], u32[], u32[]) "
        "collective-permute-start(%r)",
    ])
    census = C.collective_census(txt)
    assert census == {
        "all-reduce": 2, "all-gather": 2, "reduce-scatter": 0,
        "collective-permute": 2, "all-to-all": 0,
    }
    art = C.ProgramArtifact("coll", optimized_text=txt)
    pred = C.collective_inventory(all_gather=0, collective_permute=(0, 2))
    viols = C.check_artifact(art, (pred,), "coll")
    assert len(viols) == 1 and "all-gather count 2" in viols[0].detail
    # Callable bounds resolve against the artifact.
    pred2 = C.collective_inventory(all_reduce=lambda a: (0, 2))
    assert C.check_artifact(art, (pred2,), "coll") == []


def test_bf16_upcast_budget_fires():
    def upcasty(v):
        return (v.astype(jnp.float32) @ v.astype(jnp.float32).T).sum()

    art = C.artifact_from_fn("up", upcasty, np.ones((4, 4), jnp.bfloat16))
    assert C.count_bf16_upcasts(art.jaxpr) >= 2
    assert C.check_artifact(art, (C.bf16_upcast_budget(0),), "up")
    assert C.check_artifact(art, (C.bf16_upcast_budget(8),), "up") == []


def test_output_sharded_over_fires(cpu_devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(cpu_devices[:8]), ("dp",))
    repl = NamedSharding(mesh, P())
    shd = NamedSharding(mesh, P("dp"))
    x = jax.device_put(np.ones((8, 4), np.float32), repl)

    art = C.artifact_from_fn(
        "repl", lambda v: jax.lax.with_sharding_constraint(v, repl), x
    )
    pred = C.output_sharded_over(lambda out: out, "dp", "output")
    assert C.check_artifact(art, (pred,), "repl")   # replicated: fires

    art2 = C.artifact_from_fn(
        "shd", lambda v: jax.lax.with_sharding_constraint(v, shd), x
    )
    assert C.check_artifact(art2, (pred,), "shd") == []


def test_executed_stacked_dus_counter():
    """The migrated test_scan_remat matcher: unit-leading updates into
    stacked buffers count trip_count executed writes each."""
    txt = (
        "stablehlo.dynamic_update_slice %a, %b : "
        "(tensor<8x2x4xf32>, tensor<1x2x4xf32>\n"
        "stablehlo.dynamic_update_slice %c, %d : "
        "(tensor<4x2xf32>, tensor<1x2xf32>\n"
        "stablehlo.dynamic_update_slice %e, %f : "
        "(tensor<8x2xf32>, tensor<8x2xf32>\n"   # not unit-leading: ignored
    )
    assert C.executed_stacked_dus(txt) == 12


# ---------------------------------------------------------------------------
# Registry mechanics
# ---------------------------------------------------------------------------


def test_unknown_contract_and_bad_program():
    with pytest.raises(C.ContractError, match="unknown contract"):
        C.check("nope")
    with pytest.raises(C.ContractError, match="unknown engine program"):
        C.build_engine_program("warp")
    with pytest.raises(C.ContractError, match="speculative"):
        C.build_engine_program("verify")   # needs the speculative knob
    with pytest.raises(C.ContractError, match="chunked_prefill"):
        C.build_engine_program("mixed")


def test_smoke_set_is_cpu_viable():
    assert set(C.smoke_contracts()) <= set(C.CONTRACTS)
    assert len(C.smoke_contracts()) >= 6
    for name in C.smoke_contracts():
        assert C.CONTRACTS[name].devices <= 8


# ---------------------------------------------------------------------------
# Real programs: migrated pins + the smoke sweep
# ---------------------------------------------------------------------------


def test_train_guard_purity_contract():
    """Migrated test_train_fault pin: guard-off train step stages zero
    finiteness ops (and no callbacks, f64, or donation leak); guard-on
    really stages the check."""
    r = C.check("train_hygiene")
    assert r.ok, [str(v) for v in r.violations]
    r_on = C.check("train_guard_staged")
    assert r_on.ok, [str(v) for v in r_on.violations]


def test_decode_guard_purity_contract():
    """The serving twin (PR 6's bit-identical-when-off promise at the
    artifact level): nan_guard-off decode is finiteness-free with the
    cache donation aliased; nan_guard-on stages the per-slot check."""
    r = C.check("decode_hygiene")
    assert r.ok, [str(v) for v in r.violations]
    r_on = C.check("decode_guard_staged")
    assert r_on.ok, [str(v) for v in r_on.violations]


def test_dtype_whitelist_budget_fit():
    """The layout-aware whitelist formula tracks the measured staged
    upcast counts (tight: slack 2), so a single new full-width f32
    activation overruns it."""
    art = C.build_train_step(("model.dtype=bfloat16",))
    n = C.count_bf16_upcasts(art.jaxpr)
    budget = C.dtype_whitelist_budget(art)
    assert 0 < budget - n <= 4, (n, budget)
    art2 = C.build_train_step(
        ("model.dtype=bfloat16", "model.scan_group=2", "train.remat=names")
    )
    n2 = C.count_bf16_upcasts(art2.jaxpr)
    budget2 = C.dtype_whitelist_budget(art2)
    assert n2 > n and 0 < budget2 - n2 <= 4, (n2, budget2)


def test_contract_check_smoke():
    """tools/contract_check.py --smoke: every cpu-fast contract row holds
    on the real programs — typed JSON rows, verdict line, exit 0 (the
    tier-1 CI hook; the full grid is `tools/contract_check.py` with no
    flag)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "contract_check.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    rows = [json.loads(l) for l in proc.stdout.splitlines()
            if l.strip().startswith("{")]
    assert proc.returncode == 0, proc.stdout + proc.stderr
    verdict = rows[-1]
    assert verdict["verdict"] == "contract_check" and verdict["ok"]
    names = {r["contract"] for r in rows if "contract" in r}
    assert names == set(C.smoke_contracts())
    assert all(r["ok"] for r in rows if "contract" in r)
