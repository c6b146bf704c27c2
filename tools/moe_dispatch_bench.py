#!/usr/bin/env python
"""Measure MoE dispatch overhead: einsum vs sorted, Mixtral-scaled, 1 chip.

VERDICT r3 item 3 / weak #2: the einsum dispatch costs ~2*S*(E*C)*D extra
matmul FLOPs per layer plus a materialized [B,S,E,C] float tensor; this
script times one MoE layer (fwd+bwd) under both dispatch modes at a
Mixtral-shaped single-chip slice (D=4096, F=14336, E=8, k=2) and prints the
measured dispatch share. Runs on the real TPU by default:

    python tools/moe_dispatch_bench.py            # on-chip numbers
    python tools/moe_dispatch_bench.py --cpu      # logic check (tiny shape)

Output: one JSON line per mode + a summary line with the dispatch share.

``--decode`` (PR 42) times instead ONE sparse layer's forward at a decode
step's shape, Ling-3.0-flash as its cell holds it ([128, 1, 2560], 128 of
the router's 512 experts, F 768, bf16): the capacity-bucket form (zeros,
scatter-add, experts, gather: ``moe_mlp_sorted`` as it was for every block
before PR 42 and still is for more than one position a row) against the
tree's ``moe_mlp_sorted``, whose bucket at one position a row is the row
itself. The layer's time without a benchmark cell, for whoever takes the
combine side next (ROADMAP S15 (a)); with ``--cpu`` a logic check at
tiny-ling's shapes.

``--prefill [--only mimo,ling,laguna] [--held N]`` (PR 52) times ONE sparse
layer's grouped dispatch and experts (``moe_mlp_grouped``, forward, bf16) at
a cell's prefill block shapes and held share: the whole form (all k x T
sorted rows gathered, selected and un-sorted) against the bounded form
(``moe._bounded_rows``: ``moe.held_row_bound`` rows a pass), the bounded
form FORCED where the rule refuses it, so that the line of
``moe.bounds_held_rows`` (a quarter) can be drawn again when a configuration
with another share arrives: MiMo's 16 of 256 (bound k T / 8), Ling's 128 of
512 (k T / 2), Laguna's 128 of 256 (k T: no bound at all); ``--held N``
holds N experts instead. ms a layer and the rows a pass moves; with
``--cpu`` a logic check at the tiny presets and no time.
"""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import json
import sys
import time

import jax
import jax.numpy as jnp

from orion_tpu.config import get_config
from orion_tpu.models import moe as moe_lib


def bench(fn, args, iters=20, warmup=3):
    out = jax.jit(fn)
    for _ in range(warmup):
        jax.block_until_ready(out(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        r = out(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters


def bucket_form(x, params, cfg):
    """``moe_mlp_sorted`` through the capacity buckets whatever the block's
    shape: what a decode step ran before PR 42."""
    E = cfg.n_experts
    idx, gate, pos, keep, _ = moe_lib.route_indices(
        x, params["router"], cfg, params.get("router_bias"))
    idx, held = moe_lib._held(idx, cfg)
    keep, idx = keep & held, jnp.clip(idx, 0, E - 1)
    xin = moe_lib._scatter_dispatch(
        x, idx, pos, keep, E, moe_lib.moe_capacity(cfg, x.shape[1]))
    out = moe_lib._expert_ffn(xin, params, cfg)
    return moe_lib._gather_combine(out, idx, pos, keep, gate, x.dtype)


def decode_row(cpu: bool) -> int:
    if cpu:
        cfg = get_config("tiny-ling", ["runtime.platform=cpu",
                                       "model.n_experts=4"]).model
        B, dtype = 8, jnp.float32
    else:
        cfg = get_config("ling-3.0-flash", ["model.n_experts=128"]).model
        B, dtype = 128, jnp.bfloat16
    E, W = cfg.n_experts, cfg.resolved_router_width
    D, F = cfg.d_model, cfg.resolved_moe_d_ff
    keys = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(keys[0], (B, 1, D), dtype)
    params = {
        "router": jax.random.normal(keys[1], (D, W), jnp.float32) * 0.3,
        "router_bias": jax.random.normal(keys[5], (W,), jnp.float32) * 0.1,
        "w_in": jax.random.normal(keys[2], (E, D, F), dtype) * 0.02,
        "w_gate": jax.random.normal(keys[3], (E, D, F), dtype) * 0.02,
        "w_out": jax.random.normal(keys[4], (E, F, D), dtype) * 0.02,
    }
    forms = {"buckets": lambda x, p: bucket_form(x, p, cfg),
             "tree": lambda x, p: moe_lib.moe_mlp_sorted(x, p, cfg)[0]}
    ys = {k: jax.jit(f)(x, params) for k, f in forms.items()}
    err = float(jnp.max(jnp.abs(
        ys["tree"].astype(jnp.float32) - ys["buckets"].astype(jnp.float32))))
    # A time is the chip's: the logic check reports none.
    ms = {k: None if cpu else round(1e3 * bench(f, (x, params), iters=50), 4)
          for k, f in forms.items()}
    print(json.dumps({
        "summary": "moe_decode_dispatch", "device": jax.devices()[0].device_kind,
        "shape": {"B": B, "D": D, "F": F, "held": E, "router_width": W,
                  "k": cfg.n_experts_per_token, "dtype": jnp.dtype(dtype).name},
        "buckets_ms_per_layer": ms["buckets"],
        "tree_ms_per_layer": ms["tree"],
        "expert_weight_bytes": 3 * E * D * F * jnp.dtype(dtype).itemsize,
        "max_abs_diff": err,
    }))
    return 0 if err <= (0.0 if cpu else 1e-2) else 1


PREFILL = {   # preset, experts held, (rows, bucket) of the cell's blocks
    "mimo": ("mimo-v2.5", 16, [(1, 2048), (1, 16384), (8, 2048)]),
    "ling": ("ling-3.0-flash", 128, [(1, 1024), (1, 8192), (8, 1024)]),
    "laguna": ("laguna-s-2.1", 128, [(1, 512), (1, 4096), (8, 512)]),
}
PREFILL_TINY = {
    "mimo": ("tiny-mimo", 2, [(2, 128)]),
    "ling": ("tiny-ling", 4, [(2, 256)]),
    "laguna": ("tiny-laguna", 8, [(2, 64)]),
}


def _flag(name: str):
    args = sys.argv[1:]
    return args[args.index(name) + 1] if name in args else None


def prefill_rows(cpu: bool) -> int:
    only, held_n = _flag("--only"), _flag("--held")
    rule = moe_lib.bounds_held_rows
    worst = 0.0
    for name, (preset, held, shapes) in (
            PREFILL_TINY if cpu else PREFILL).items():
        if only and name not in only.split(","):
            continue
        held = int(held_n) if held_n else held
        cfg = get_config(preset, [f"model.n_experts={held}"] + (
            ["runtime.platform=cpu"] if cpu else ["model.kernels=pallas"])
        ).model
        dtype = jnp.float32 if cpu else jnp.bfloat16
        E, W = cfg.n_experts, cfg.resolved_router_width
        D, F, k = cfg.d_model, cfg.resolved_moe_d_ff, cfg.n_experts_per_token
        keys = jax.random.split(jax.random.key(0), 6)
        params = {
            "router": jax.random.normal(keys[1], (D, W), jnp.float32) * 0.3,
            "w_in": jax.random.normal(keys[2], (E, D, F), dtype) * 0.02,
            "w_gate": jax.random.normal(keys[3], (E, D, F), dtype) * 0.02,
            "w_out": jax.random.normal(keys[4], (E, F, D), dtype) * 0.02,
        }
        if cfg.router_bias:
            params["router_bias"] = 0.1 * jax.random.normal(keys[5], (W,))
        for B, S in shapes:
            x = jax.random.normal(keys[0], (B, S, D), dtype)
            ys, ms = {}, {}
            for form, forced in (("whole", lambda c, t: False),
                                 ("bounded", lambda c, t: c.holds_expert_share)):
                moe_lib.bounds_held_rows = forced
                try:
                    f = lambda x, p: moe_lib.moe_mlp_grouped(x, p, cfg)[0]
                    ys[form] = jax.jit(f)(x, params).astype(jnp.float32)
                    # A time is the chip's: the logic check reports none.
                    ms[form] = None if cpu else round(
                        1e3 * bench(f, (x, params), iters=10, warmup=2), 3)
                finally:
                    moe_lib.bounds_held_rows = rule
            err = float(jnp.max(jnp.abs(ys["bounded"] - ys["whole"])))
            worst = max(worst, err / float(jnp.max(jnp.abs(ys["whole"]))))
            print(json.dumps({
                "summary": "moe_prefill_dispatch", "cell": name,
                "device": jax.devices()[0].device_kind,
                "shape": {"B": B, "S": S, "D": D, "F": F, "held": E,
                          "router_width": W, "k": k,
                          "dtype": jnp.dtype(dtype).name},
                "rows_whole": k * B * S,
                "rows_bounded": moe_lib.held_row_bound(cfg, B * S),
                "rows_held": int(moe_lib.held_rows(
                    x, params["router"], cfg, None,
                    params.get("router_bias"))),
                "rule_admits": bool(rule(cfg, B * S)),
                "whole_ms_per_layer": ms["whole"],
                "bounded_ms_per_layer": ms["bounded"],
                "max_abs_diff": err,
            }), flush=True)
    # One step of bfloat16 where the two forms sum over k in another order.
    return 0 if worst <= (1e-5 if cpu else 2 ** -7) else 1


def main() -> int:
    cpu = "--cpu" in sys.argv[1:]
    if cpu:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        print(f"FAIL: no TPU backend (default backend is "
              f"{jax.default_backend()!r}); use --cpu for the logic check")
        return 1
    if "--decode" in sys.argv[1:]:
        return decode_row(cpu)
    if "--prefill" in sys.argv[1:]:
        return prefill_rows(cpu)
    if cpu:
        B, S, D, F = 2, 128, 64, 256
        cfg = get_config("tiny-mixtral", ["runtime.platform=cpu"]).model
        dev = jax.devices("cpu")[0]
    else:
        # Mixtral 8x7B per-layer shape, single-chip slice: B*S sized so the
        # expert weights (bf16) + activations fit a v5e's 16 GB.
        B, S = 1, 2048
        cfg = get_config("mixtral-8x7b-ep").model
        D, F = cfg.d_model, cfg.d_ff
        dev = jax.devices()[0]
    E = cfg.n_experts

    with jax.default_device(dev):
        keys = jax.random.split(jax.random.key(0), 5)
        x = jax.random.normal(keys[0], (B, S, D), jnp.bfloat16)
        params = {
            "router": jax.random.normal(keys[1], (D, E), jnp.float32) * 0.3,
            "w_in": jax.random.normal(keys[2], (E, D, F), jnp.bfloat16) * 0.02,
            "w_gate": jax.random.normal(keys[3], (E, D, F), jnp.bfloat16) * 0.02,
            "w_out": jax.random.normal(keys[4], (E, F, D), jnp.bfloat16) * 0.02,
        }

        results = {}
        for mode, fn in (("einsum", moe_lib.moe_mlp),
                         ("sorted", moe_lib.moe_mlp_sorted)):
            def step(x, p, fn=fn):
                def loss(x, p):
                    y, aux = fn(x, p, cfg)
                    return (y.astype(jnp.float32) ** 2).mean() + 0.01 * aux
                l, g = jax.value_and_grad(loss, argnums=1)(x, p)
                return l, g

            dt = bench(step, (x, params))
            results[mode] = dt
            print(json.dumps({
                "mode": mode, "ms_per_layer_fwdbwd": round(dt * 1e3, 3),
                "shape": {"B": B, "S": S, "D": D, "F": F, "E": E,
                          "C": moe_lib.moe_capacity(cfg, S)},
            }))

    share = 1.0 - results["sorted"] / results["einsum"]
    print(json.dumps({
        "summary": "moe_dispatch_overhead",
        "einsum_ms": round(results["einsum"] * 1e3, 3),
        "sorted_ms": round(results["sorted"] * 1e3, 3),
        "dispatch_share_removed": round(share, 4),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
