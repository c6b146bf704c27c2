#!/usr/bin/env python
"""Sweep the static-contract registry across a layout grid (ISSUE 15).

Every row = one contract (orion_tpu.analysis.contracts.CONTRACTS) at one
layout, evaluated in a SUBPROCESS — a partitioner abort or a trace-time
crash becomes a typed ``error`` row instead of a dead sweep (the
pp_bubble_bench pattern). One JSON line per row; nonzero exit when any
row fails or errors.

    python tools/contract_check.py             # full grid (all contracts
                                               #  x layout variants)
    python tools/contract_check.py --smoke     # tier-1 twin: the cpu-fast
                                               #  smoke contracts, base layouts
    python tools/contract_check.py --contract zero1_collectives
    python tools/contract_check.py --list      # registry with docs

The full grid layers layout variants (grad_accum, scan_group x remat,
kv_quant, sliding windows, guard compositions) on top of each contract's
base overrides; the fake 8-device CPU mesh covers every dp/tp row.
"""
from __future__ import annotations

import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))

import argparse
import json
import os
import re
import subprocess
import sys

_f = os.environ.get("XLA_FLAGS", "")
_m = re.search(r"host_platform_device_count=(\d+)", _f)
if _m is None:
    os.environ["XLA_FLAGS"] = (
        _f + " --xla_force_host_platform_device_count=8"
    ).strip()
# Device budget rows are judged against: a pre-set flag wins (we respect
# it above), otherwise the 8 we just forced.
FAKE_DEVICES = int(_m.group(1)) if _m else 8

# Layout variants layered on top of each contract's base overrides in the
# FULL grid (besides the base row). Keyed by contract name; every variant
# must stay cpu-viable on the fake 8-device mesh.
GRID_VARIANTS: dict = {
    "train_hygiene": [
        ["train.grad_accum=2"],
        ["model.scan_group=2", "train.remat=names"],
        ["model.remat=full"],
    ],
    "train_guard_staged": [
        ["train.grad_accum=2"],
    ],
    "train_dtype_discipline": [
        ["model.scan_group=2", "train.remat=names"],
    ],
    "zero1_collectives": [
        ["train.grad_accum=2", "data.batch_size=16"],
        ["model.dtype=bfloat16"],     # master-split path
    ],
    "pp_ring_hops": [
        ["parallel.pp_schedule=1f1b"],
        ["parallel.pp_microbatches=4"],
    ],
    "decode_hygiene": [
        ["inference.kv_quant=int8"],
        ["model.sliding_window=32"],
    ],
    "decode_guard_staged": [
        ["inference.kv_quant=int8"],
    ],
    "prefill_hygiene": [
        ["inference.kv_quant=int8"],
    ],
    "verify_hygiene": [
        ["inference.kv_quant=int8"],
        ["inference.spec_tree_width=3"],
    ],
    "mixed_hygiene": [
        ["inference.kv_quant=int8"],
    ],
    # The migration envelope across the kv_quant/SWA grid (ISSUE 20):
    # int8 adds the f32 scale pools to the copied tree, a sliding window
    # changes which logical pages exist — neither may change the copy
    # programs' hygiene.
    "migration_hygiene": [
        ["inference.kv_quant=int8"],
        ["model.sliding_window=32"],
        ["inference.kv_quant=int8", "model.sliding_window=32"],
    ],
    "migration_scatter_hygiene": [
        ["inference.kv_quant=int8"],
        ["model.sliding_window=32"],
        ["inference.kv_quant=int8", "model.sliding_window=32"],
    ],
    "long_prefill_hygiene": [
        ["inference.kv_quant=int8"],
        # The paged-flash prefill body, interpret-lowered on CPU: the
        # kernel must not smuggle host callbacks into the mixed program
        # (pallas interpret mode stages pure jax primitives).
        ["model.kernels=pallas_interpret"],
    ],
}


def _rows(smoke: bool, only: str) -> list:
    from orion_tpu.analysis import contracts as C

    names = C.smoke_contracts() if smoke else C.grid_contracts()
    if only:
        if only not in C.CONTRACTS:
            raise SystemExit(
                f"unknown contract {only!r}; have {sorted(C.CONTRACTS)}"
            )
        names = [only]
    rows = []
    for name in names:
        c = C.CONTRACTS[name]
        if max(c.devices, c.tp) > FAKE_DEVICES:
            # The registry's device floor: a host faking fewer devices
            # than the layout needs records a typed skip row instead of
            # a mesh-build abort (Contract.devices contract).
            rows.append({"contract": name, "extra": [], "layout": name,
                         "skip": f"needs {max(c.devices, c.tp)} devices, "
                                 f"host fakes {FAKE_DEVICES}"})
            continue
        rows.append({"contract": name, "extra": [],
                     "layout": name})
        if not smoke:
            for extra in GRID_VARIANTS.get(name, []):
                rows.append({
                    "contract": name, "extra": extra,
                    "layout": name + "+" + ",".join(extra),
                })
    return rows


def run_row(spec: dict) -> dict:
    """Subprocess body: evaluate one contract row, print one JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from orion_tpu.analysis import contracts as C

    res = C.check(spec["contract"], tuple(spec["extra"]))
    row = res.as_row()
    row["layout"] = spec["layout"]
    return row


def _spawn_row(spec: dict, timeout: int) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--row",
           json.dumps(spec)]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
    except subprocess.TimeoutExpired:
        return {"layout": spec["layout"], "contract": spec["contract"],
                "ok": False, "error": f"timeout>{timeout}s"}
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                pass
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    detail = tail[-1][:200] if tail else f"rc={proc.returncode}"
    return {"layout": spec["layout"], "contract": spec["contract"],
            "ok": False, "error": f"subprocess rc={proc.returncode}: "
            f"{detail}"}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="cpu-fast smoke contracts only (tier-1 twin)")
    p.add_argument("--contract", default="",
                   help="run one contract (base layout + its grid rows)")
    p.add_argument("--list", action="store_true",
                   help="list registered contracts and exit")
    p.add_argument("--timeout", type=int, default=0,
                   help="per-row subprocess timeout (s)")
    p.add_argument("--row", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.row:
        print(json.dumps(run_row(json.loads(args.row))), flush=True)
        return 0

    if args.list:
        from orion_tpu.analysis import contracts as C

        for c in C.CONTRACTS.values():
            mark = " [smoke]" if c.smoke else ""
            print(f"{c.name}{mark}: program={c.program} "
                  f"overrides={list(c.overrides)}")
            print(f"    {c.doc}")
        return 0

    timeout = args.timeout or (240 if args.smoke else 600)
    bad = skipped = 0
    for spec in _rows(args.smoke, args.contract):
        if "skip" in spec:
            skipped += 1
            print(json.dumps({**spec, "ok": True, "skipped": True}),
                  flush=True)
            continue
        row = _spawn_row(spec, timeout)
        print(json.dumps(row), flush=True)
        if not row.get("ok"):
            bad += 1
    verdict = {"verdict": "contract_check", "ok": bad == 0,
               "failed_rows": bad, "skipped_rows": skipped}
    print(json.dumps(verdict), flush=True)
    return 0 if bad == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
