"""Tiered prefix/KV cache (ISSUE 18): host-RAM second tier behind the
radix tree.

The load-bearing properties: BYTE IDENTITY (a page that round-trips
device -> host -> device is bitwise identical, scale pools included, and
the tier-off engine is byte-identical to a cache-on engine without the
tier), ACCOUNTING (both pools exactly balanced at every stage, including
after a mid-restore fault — no torn pages, no leaked slots, markers
unpromoted on unwind), and the BREAK-EVEN gate (a host match below
host_tier_min_tokens recomputes instead of restoring). Plus the fleet
half: the router's affinity probe sees host-tier matches, so a host-warm
replica beats a cold one.
"""

import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import get_config
from orion_tpu.infer import InferenceEngine, Router
from orion_tpu.infer.kv_cache import (
    HostPagePool,
    PageAllocator,
    host_tier_break_even_tokens,
)
from orion_tpu.infer.prefix_cache import HostPage, PrefixCache
from orion_tpu.models import init_params
from orion_tpu.runtime.fault import FaultInjector, FaultSpec

slow = pytest.mark.slow

INFER = [
    "inference.max_seq_len=128",
    "inference.page_size=16",
    "inference.num_pages=32",
    "inference.max_batch_size=4",
    "inference.prefill_chunk=16",
    "inference.max_new_tokens=8",
]
# 16 host slots at tiny-llama's measured 8192 B/page; min_tokens=0 so
# every host match restores (the gate itself is tested separately).
TIER = [
    "inference.prefix_cache=true",
    "inference.host_tier_bytes=131072",
    "inference.host_tier_min_tokens=0",
]

SHARED = [(i * 7) % 250 + 1 for i in range(96)]          # 6 full pages


def _setup(overrides=(), tier=True):
    ov = list(INFER) + (list(TIER) if tier else [])
    cfg = get_config("tiny-llama", ov + list(overrides))
    params = init_params(cfg.model, jax.random.key(0))
    return cfg, params


def _snapshot_prefix(eng, tokens, n_pages):
    """Bitwise snapshot of the cached prefix path's KV (+ scale) pages."""
    pages, node = eng._pcache.match(tokens + [999], max_pages=n_pages)
    assert node is not None and len(pages) == n_pages
    assert all(isinstance(p, int) for p in pages)
    blocks = jax.device_get(
        eng._gather_pages(eng.cache, jnp.asarray(pages, dtype=jnp.int32))
    )
    eng._pcache.unlock(node)
    return {k: np.asarray(v) for k, v in blocks.items()}


# -- pure units --------------------------------------------------------------


def test_break_even_math():
    """t* = overhead / (1/prefill_tok_s - bytes_per_token/bw): known
    value, never-wins None, and the one-page floor."""
    # 1 MiB pages of 16 tokens over 8 GB/s vs 40k tok/s prefill: the
    # restore slope is ~8.2us/tok vs 25us/tok recompute -> 2ms overhead
    # amortises at 119 tokens.
    assert host_tier_break_even_tokens(1 << 20, 16, 8.0, 0.002, 40000.0) == 119
    # Restore slope >= recompute slope: the tier never pays.
    assert host_tier_break_even_tokens(1 << 20, 16, 0.01, 0.0, 40000.0) is None
    # Zero overhead still floors at one page (sub-page restores can't exist).
    assert host_tier_break_even_tokens(1024, 16, 8.0, 0.0, 40000.0) == 16


def test_host_pool_unit_mechanics():
    """alloc/retain/release/refcount, exhaustion, LRU eviction order,
    the evict-while-referenced refusal, and a store/load byte round-trip."""
    hp = HostPagePool(4, page_bytes=64)
    a, b, c = hp.alloc(3)
    assert hp.free_slots == 1
    assert [hp.refcount(x) for x in (a, b, c)] == [1, 1, 1]
    hp.retain(a)
    assert hp.refcount(a) == 2
    assert hp.release(a) is False and hp.refcount(a) == 1
    with pytest.raises(MemoryError):
        hp.alloc(2)                          # want 2, have 1
    # LRU order: touch a so b becomes coldest; b then c evict, a is
    # REFUSED while referenced (refcount 2 after re-retain).
    hp.touch(b); hp.touch(c); hp.touch(a)
    hp.retain(a)
    assert hp.evict_lru(3) == [b, c]         # a skipped: still referenced
    assert hp.free_slots == 3
    hp.release(a)
    assert hp.evict_lru(1) == [a]
    assert hp.free_slots == 4

    # store/load round-trip is bitwise, per-array, at the stored rows.
    hids = hp.alloc(2)
    rng = np.random.default_rng(0)
    blocks = {
        "k": rng.standard_normal((2, 3, 8)).astype(np.float32),
        "v": rng.integers(-128, 127, (2, 3, 8)).astype(np.int8),
    }
    hp.store(hids, blocks)
    out = hp.load(hids)
    for name in blocks:
        assert out[name].dtype == blocks[name].dtype
        assert out[name].tobytes() == blocks[name].tobytes()


def test_radix_demote_promote_unit():
    """Tree-level tier mechanics without an engine: demote flips trailing
    device entries to HostPage markers through ONE spill callback,
    promote_path flips them back, _discard and clear release host slots,
    and a locked path never demotes."""
    alloc = PageAllocator(64)
    hp = HostPagePool(8)
    spilled = []

    def spill(pages):
        hids = hp.alloc(len(pages))
        spilled.append(list(pages))
        return hids

    pc = PrefixCache(4, alloc, host_pool=hp, spill=spill)
    toks = list(range(12))                   # 3 pages of 4 tokens
    pages = alloc.alloc(3)
    pc.insert(toks, pages)
    alloc.free(pages)

    # Locked path: evict() finds nothing, demotes nothing.
    got, node = pc.match(toks + [99], max_pages=8)
    assert pc.evict(10) == 0 and not spilled
    pc.unlock(node)

    # Demote 2: ONE spill call carrying both victims (trailing entries
    # first), device refs released, markers in place, counters split.
    assert pc.demote(2) == 2
    assert len(spilled) == 1 and spilled[0] == [pages[2], pages[1]]
    assert (pc.total_pages, pc.host_pages) == (1, 2)
    assert all(alloc.refcount(p) == 0 for p in pages[1:])
    assert alloc.refcount(pages[0]) == 1
    assert hp.free_slots == 8 - 2

    # peek_tiered reports the split; the match surfaces the markers.
    matched, host, first_host = pc.peek_tiered(toks + [99], 8)
    assert (matched, host, first_host) == (3, 2, 1)
    got, node = pc.match(toks + [99], max_pages=8)
    assert got[0] == pages[0]
    assert [isinstance(p, HostPage) for p in got] == [False, True, True]

    # promote_path flips markers to fresh device pages and frees slots.
    fresh = alloc.alloc(2)
    pc.promote_path(node, {1: fresh[0], 2: fresh[1]})
    assert (pc.total_pages, pc.host_pages) == (3, 0)
    assert hp.free_slots == 8
    got2, node2 = pc.match(toks + [99], max_pages=8)
    assert got2 == [pages[0], fresh[0], fresh[1]]
    pc.unlock(node2)
    pc.unlock(node)
    # promote_path TRANSFERRED ownership of the fresh pages to the tree
    # (the engine's allocation ref becomes the tree's retain ref).
    assert all(alloc.refcount(p) == 1 for p in [pages[0]] + fresh)

    # clear() releases host slots too (re-demote first).
    assert pc.demote(3) == 3
    assert (pc.total_pages, pc.host_pages) == (0, 3)
    assert pc.clear() == 0                   # no DEVICE pages left to free
    assert pc.host_pages == 0 and hp.free_slots == 8


# -- engine round trip -------------------------------------------------------


def test_tier_off_by_default():
    """host_tier_bytes defaults to 0 (tier off, no host pool built); the
    tier requires the radix tree; offload without a tier is a no-op 0."""
    cfg, params = _setup(tier=False)
    assert cfg.inference.host_tier_bytes == 0
    eng = InferenceEngine(cfg, params)
    assert eng._host_pool is None
    assert eng.offload_prefix_cache() == 0
    with pytest.raises(ValueError, match="prefix_cache"):
        bad, _ = _setup(overrides=["inference.host_tier_bytes=131072"],
                        tier=False)
        InferenceEngine(bad, params)


def test_offload_restore_round_trip_byte_identical():
    """The tentpole pin: offload demotes the whole idle tree to host
    (counters + occupancy gauges move), a warm re-admission restores it,
    and the restored KV pages are BITWISE identical to the pre-offload
    snapshot — with both pools exactly accounted at every stage."""
    cfg, params = _setup()
    eng = InferenceEngine(cfg, params)
    cold = eng.generate([SHARED], 4)
    before = _snapshot_prefix(eng, SHARED, 6)
    eng.assert_page_accounting()
    eng.reset_timing()

    n = eng.offload_prefix_cache()
    assert n == 6
    assert (eng._pcache.total_pages, eng._pcache.host_pages) == (0, 6)
    t = eng.reset_timing()
    assert t["evicted_to_host"] == 6 and t["spill_s"] > 0
    m = eng._pool_metrics()
    assert m["host_pages"] == 6
    assert m["host_free_slots"] == m["host_capacity"] - 6
    assert 0 < m["host_occupancy"] <= 1
    eng.assert_page_accounting()

    # Warm re-admission: the host hit restores, then serves byte-identically.
    warm = eng.generate([SHARED], 4)
    assert warm == cold
    t = eng.reset_timing()
    assert t["host_hits"] == 1 and t["host_restored_pages"] == 6
    assert t["restore_s"] > 0
    assert t["prefix_hits"] == 1 and t["cached_tokens"] >= 95
    assert (eng._pcache.total_pages, eng._pcache.host_pages) == (6, 0)
    assert eng._host_pool.free_slots == eng._host_pool.capacity
    after = _snapshot_prefix(eng, SHARED, 6)
    assert set(before) == set(after)
    for name in before:
        assert after[name].dtype == before[name].dtype
        assert after[name].tobytes() == before[name].tobytes(), name
    eng.assert_page_accounting()


def test_tier_on_greedy_streams_byte_identical():
    """Tier-on serving (with an offload between rounds) never changes any
    request's tokens vs the tier-off cache-on AND cache-off engines."""
    cfg, params = _setup()
    cfg_pc, _ = _setup(tier=False, overrides=["inference.prefix_cache=true"])
    cfg_off, _ = _setup(tier=False)
    prompts = [SHARED[:48] + [7, 8, 9], SHARED[:48] + [200, 201], [5, 3, 9] * 6]
    ref = InferenceEngine(cfg_off, params).generate(prompts, 6)
    assert InferenceEngine(cfg_pc, params).generate(prompts, 6) == ref
    eng = InferenceEngine(cfg, params)
    assert eng.generate(prompts, 6) == ref           # cold round
    eng.offload_prefix_cache()
    assert eng.generate(prompts, 6) == ref           # host-warm round
    assert eng.reset_timing()["host_hits"] >= 1
    eng.assert_page_accounting()


def test_int8_round_trip_bitwise():
    """kv_quant=int8: the spill/restore copies carry the int8 KV pools AND
    the f32 scale pools; the round trip is bitwise on all of them."""
    cfg, params = _setup(overrides=["inference.kv_quant=int8"])
    eng = InferenceEngine(cfg, params)
    cold = eng.generate([SHARED], 4)
    before = _snapshot_prefix(eng, SHARED, 6)
    assert any(v.dtype == np.int8 for v in before.values())
    assert any("scale" in k for k in before), list(before)
    assert eng.offload_prefix_cache() == 6
    assert eng.generate([SHARED], 4) == cold
    after = _snapshot_prefix(eng, SHARED, 6)
    for name in before:
        assert after[name].dtype == before[name].dtype
        assert after[name].tobytes() == before[name].tobytes(), name
    eng.assert_page_accounting()


def test_restore_into_tight_pool_no_deadlock():
    """Restore when HBM is nearly full: the fresh-page allocation feeds
    through the normal evict-for-headroom path (demoting OTHER cold
    entries if needed) and completes — no deadlock, no accounting drift."""
    cfg, params = _setup(overrides=["inference.num_pages=16"])
    eng = InferenceEngine(cfg, params)
    cold = eng.generate([SHARED], 4)
    assert eng.offload_prefix_cache() == 6
    # Fill the tree with OTHER paths so free HBM pages are scarce when
    # the 6-page restore lands.
    filler = [[(i * 13 + j) % 250 + 1 for i in range(32)] for j in (1, 2)]
    fref = eng.generate(filler, 4)
    assert eng.generate([SHARED], 4) == cold
    t = eng.reset_timing()
    assert t["host_hits"] == 1 and t["host_restored_pages"] == 6
    eng.assert_page_accounting()
    assert eng.generate(filler, 4) == fref       # fillers still serve right
    eng.assert_page_accounting()


def test_break_even_gate_skips_small_match():
    """A host-resident match below host_tier_min_tokens recomputes: the
    skip counter moves, nothing restores, markers stay host-resident,
    and the served tokens are still byte-identical."""
    cfg, params = _setup(overrides=["inference.host_tier_min_tokens=999"])
    eng = InferenceEngine(cfg, params)
    cold = eng.generate([SHARED], 4)
    assert eng.offload_prefix_cache() == 6
    assert eng.generate([SHARED], 4) == cold
    t = eng.reset_timing()
    assert t["host_recompute_skips"] >= 1
    assert t["host_hits"] == 0 and t["host_restored_pages"] == 0
    assert eng._pcache.host_pages == 6           # markers untouched
    # The affinity probe applies the same gate: no phantom warm report.
    assert eng.prefix_match_tokens(SHARED + [1]) == 0
    eng.assert_page_accounting()


def test_mid_restore_fault_unwinds_both_tiers():
    """Chaos pin: an injected fault INSIDE the restore copy envelope
    fails the STEP with a typed outcome — fresh device pages freed, host
    refs dropped, markers unpromoted, both pools balanced — and the
    retry restores for real, byte-identically."""
    cfg, params = _setup()
    inj = FaultInjector()
    eng = InferenceEngine(cfg, params, fault_injector=inj)
    cold = eng.generate([SHARED], 4)
    assert eng.offload_prefix_cache() == 6
    free0 = eng.alloc.free_pages
    inj.specs.append(FaultSpec("restore", step=eng.step_no))
    eng.submit(SHARED, 4)
    eng.step()                                   # faulted admit step
    assert inj.fired == [("restore", eng.step_no - 1, None)]
    t = eng.reset_timing()
    assert t["failed_steps"] == 1 and t["dispatch_faults"] == 1
    # Full unwind: nothing promoted, nothing leaked, no torn pages.
    assert eng._pcache.host_pages == 6
    assert eng._pcache.total_pages == 0
    assert eng.alloc.free_pages == free0
    hp = eng._host_pool
    assert hp.free_slots == hp.capacity - 6
    eng.assert_page_accounting()
    # The retry (same queued request) restores and completes correctly.
    done = {}
    while eng.has_work():
        for r in eng.step():
            done[r.rid] = r
    assert [list(r.generated) for r in done.values()] == cold
    t = eng.reset_timing()
    assert t["host_hits"] == 1 and t["host_restored_pages"] == 6
    eng.assert_page_accounting()


# -- fleet warm-start --------------------------------------------------------


def test_router_prefers_host_warm_replica():
    """Two replicas, DISJOINT trees, replica 0's tree offloaded to host:
    the affinity probe still reports the (above-break-even) host match,
    so the shared-prefix request pins to replica 0 and serves it as a
    real host-tier hit — a host-warm replica beats a cold one."""
    cfg, params = _setup()
    warm_a = SHARED                              # 6 pages on replica 0
    warm_b = [(i * 11) % 250 + 1 for i in range(32)]
    r = Router(get_config("tiny-llama", INFER + TIER + [
        "router.replicas=2",
        "router.affinity_min_tokens=16",
        "router.retry_backoff_jitter=0",
    ]), params)
    pa = r.submit_request(warm_a + [40], 2)
    pb = r.submit_request(warm_b + [41], 2)
    while r.has_work():
        r.step()
    assert (pa.replica, pb.replica) == (0, 1)
    e0 = r.handles[0].engine
    assert e0.offload_prefix_cache() == 6
    assert e0._pcache.host_pages == 6
    # Probe sees the host-resident path; placement pins to replica 0.
    assert e0.prefix_match_tokens(warm_a + [1]) == 96
    r.reset_timing()
    q = r.submit_request(warm_a + [60, 61, 62], 4)
    assert q.replica == 0
    while r.has_work():
        r.step()
    assert r.reset_timing()["affinity_routes"] == 1
    t0 = e0.reset_timing()
    assert t0["host_hits"] == 1 and t0["host_restored_pages"] == 6
    for h in r.handles:
        h.engine.assert_page_accounting()
    r.close()


# -- compositions ------------------------------------------------------------


@slow   # heavy composition: int8 pools x chunked prefill x tier round trip
def test_kv_quant_chunked_long_prompt_composition():
    """kv_quant=int8 + chunked prefill + host tier on a near-capacity
    prompt: offload/restore mid-stream keeps serving correct (greedy
    stream equals the tier-off int8 engine's) and both pools accounted."""
    ov = ["inference.kv_quant=int8", "inference.max_seq_len=256",
          "inference.num_pages=24"]
    cfg, params = _setup(overrides=ov)
    cfg_off, _ = _setup(tier=False, overrides=ov)
    long_p = [(i * 3) % 250 + 1 for i in range(112)]     # 7 pages
    ref = InferenceEngine(cfg_off, params).generate([long_p], 8)
    eng = InferenceEngine(cfg, params)
    assert eng.generate([long_p], 8) == ref
    assert eng.offload_prefix_cache() > 0
    assert eng.generate([long_p], 8) == ref
    assert eng.reset_timing()["host_hits"] >= 1
    eng.assert_page_accounting()


# -- tools/prefix_cache_bench.py --capacity-sweep (tier-1 wiring) ------------


def test_capacity_sweep_bench_smoke():
    """The capacity sweep's three cache states, by what each one prefills
    at every pool size: recompute prefills every token, device-warm none
    of the shared prefix's and restores nothing, host-warm restores real
    pages and prefills fewer tokens than recompute; and the measured
    d2h/h2d bandwidth constants are present for PERF.md. Counts and
    structure only: the tool's ``verdict`` (and so its exit code) orders
    three admit spans of a few milliseconds on CPU clocks beside five
    other xdist workers, which is not a measurement; each span is held
    finite and positive, and the order is the chip's to show."""
    import math

    root = pathlib.Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "prefix_cache_bench.py"),
         "--capacity-sweep", "--smoke"],
        capture_output=True, text=True, timeout=300,
    )
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    verdict = lines[-1]
    assert proc.returncode == (0 if verdict["verdict"] == "ok" else 1), \
        proc.stderr[-2000:]
    rows = {(d["num_pages"], d["phase"]): d for d in lines[:-1]}
    assert len(rows) == 3 * len(verdict["pools"]), lines
    for pool in verdict["pools"]:
        rec, host, warm = (
            rows[pool, ph] for ph in ("recompute", "host", "warm"))
        shared = rec["requests"] * rec["prefix_tokens"]
        assert rec["cached_tokens"] == 0 and rec["host_restored_pages"] == 0
        assert rec["prefill_tokens"] > shared, rec
        assert warm["cached_tokens"] == shared, warm
        assert warm["prefill_tokens"] == rec["prefill_tokens"] - shared
        assert warm["host_restored_pages"] == 0, warm
        assert host["host_hits"] > 0 and host["host_restored_pages"] > 0
        assert host["prefill_tokens"] < rec["prefill_tokens"], host
        assert "d2h_gbps" in host and "h2d_gbps" in host, host
        for d in (rec, host, warm):
            assert math.isfinite(d["ttft_ms"]) and d["ttft_ms"] > 0, d
