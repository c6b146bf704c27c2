"""Disaggregated prefill/decode serving (ISSUE 20): role-split replicas
with live KV-page migration.

The load-bearing properties: BYTE IDENTITY (a request prefilled on
replica A and decoded on replica B emits exactly the greedy stream a
colocated engine emits — f32 and int8 kv_quant, scale pools bitwise,
sliding-window state included), ACCOUNTING (both replicas' page pools
exactly balanced after every handoff, including shared radix-tree prefix
pages and host-tier-resident pages on the source), and CONTAINMENT (a
faulted envelope or a killed prefill replica leaves every request wholly
arrived on the decode side or re-queued with a typed outcome — never
half a context). Plus the config grammar (``parse_roles``) and the
``router_bench --disagg --smoke`` verdict wiring.
"""

import dataclasses
import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import get_config, parse_roles
from orion_tpu.infer import InferenceEngine, Router
from orion_tpu.models import init_params
from orion_tpu.runtime.fault import FaultInjector, FaultSpec

slow = pytest.mark.slow

INFER = [
    "inference.max_seq_len=128",
    "inference.page_size=16",
    "inference.num_pages=32",
    "inference.max_batch_size=4",
    "inference.prefill_chunk=16",
    "inference.max_new_tokens=24",
    # decode_window=2 keeps step boundaries fine-grained, so handoffs
    # land mid-stream instead of a whole request finishing in one step.
    "inference.decode_window=2",
]

PROMPT = [(i * 7) % 250 + 1 for i in range(20)]


def _setup(overrides=()):
    cfg = get_config("tiny-llama", list(INFER) + list(overrides))
    params = init_params(cfg.model, jax.random.key(0))
    return cfg, params


def _split_cfg(cfg, roles, replicas=3, **rkw):
    rcfg = dataclasses.replace(
        cfg.router, replicas=replicas, roles=roles, **rkw
    )
    return dataclasses.replace(cfg, router=rcfg)


def _handoff(src, dst, rid):
    """Full engine-level migration envelope src -> dst (what the router
    drives): export state + pages, import, atomic commit, teardown on
    the source. Returns (dst Request, the gathered blocks)."""
    state = src.export_migration_state(rid)
    live, blocks = src.export_migration_pages(rid)
    host_blocks = jax.device_get(blocks)
    token = dst.import_begin(state)
    dst.import_pages(token, live, host_blocks)
    req = dst.import_commit(token, src.export_migration_state(rid))
    assert req is not None, "commit deferred on an empty destination"
    src.finish_migration(rid)
    return req, host_blocks


def _drain(eng):
    done = {}
    while eng.has_work():
        for er in eng.step():
            done[er.rid] = er
    return done


# -- config grammar ----------------------------------------------------------


def test_parse_roles():
    assert parse_roles("prefill:1,decode:2") == {"prefill": 1, "decode": 2}
    assert parse_roles(" prefill:2 , decode:1 ") == {
        "prefill": 2, "decode": 1,
    }
    for bad in (
        "prefill",                 # no count
        "draft:1,decode:2",        # unknown role
        "prefill:x,decode:2",      # non-int count
        "prefill:0,decode:3",      # count < 1
        "prefill:1,prefill:2",     # repeated role
        "",                        # empty spec
    ):
        with pytest.raises(ValueError):
            parse_roles(bad)


def test_roles_config_validation():
    cfg, _ = _setup()
    # Counts must sum to the fleet size.
    with pytest.raises(ValueError, match="names 2 replicas"):
        _split_cfg(cfg, "prefill:1,decode:1", replicas=3)
    # Both roles must be present.
    with pytest.raises(ValueError, match="at least one"):
        _split_cfg(cfg, "prefill:3", replicas=3)
    # Per-chunk streaming is meaningless on a symmetric fleet.
    with pytest.raises(ValueError, match="requires router.roles"):
        dataclasses.replace(cfg.router, migrate_per_chunk=True)
    # The happy path constructs.
    _split_cfg(cfg, "prefill:1,decode:2", replicas=3)


# -- engine-level handoff ----------------------------------------------------


def test_engine_handoff_byte_identical():
    """Prefill on A, decode on B: the migrated stream is byte-identical
    to a colocated run, the source drains to empty, and both pools stay
    exactly accounted."""
    cfg, params = _setup()
    ref = InferenceEngine(cfg, params).generate([PROMPT], 24)[0]
    src = InferenceEngine(cfg, params)
    dst = InferenceEngine(cfg, params)
    rid = src.submit_request(PROMPT, 24).rid
    steps = 0
    while not src.migration_ready(rid):
        src.step()
        steps += 1
        assert steps < 50
    req, _ = _handoff(src, dst, rid)
    assert not src.has_work()
    src.assert_page_accounting()
    er = _drain(dst)[req.rid]
    assert er.outcome == "completed"
    assert list(er.generated) == ref
    dst.assert_page_accounting()


def test_engine_handoff_int8_scales_bitwise():
    """int8 kv_quant: the f32 k_scale/v_scale pools ride the copy
    envelope and land bitwise identical on the destination, and the
    migrated stream matches the colocated int8 run exactly."""
    cfg, params = _setup(["inference.kv_quant=int8"])
    ref = InferenceEngine(cfg, params).generate([PROMPT], 24)[0]
    src = InferenceEngine(cfg, params)
    dst = InferenceEngine(cfg, params)
    rid = src.submit_request(PROMPT, 24).rid
    while not src.migration_ready(rid):
        src.step()
    req, blocks = _handoff(src, dst, rid)
    assert {"k_scale", "v_scale"} <= set(blocks), sorted(blocks)
    # Re-gather the imported pages on the destination: every pool —
    # quantized KV and f32 scales — must be bitwise what was shipped.
    live = [j for j, p in enumerate(req.pages) if p is not None]
    back = jax.device_get(dst._gather_pages(
        dst.cache, jnp.asarray([req.pages[j] for j in live], jnp.int32)
    ))
    for name, sent in blocks.items():
        got = np.asarray(back[name][:len(live)])
        np.testing.assert_array_equal(got, np.asarray(sent)[:len(live)])
    er = _drain(dst)[req.rid]
    assert er.outcome == "completed"
    assert list(er.generated) == ref
    src.assert_page_accounting()
    dst.assert_page_accounting()


def test_engine_handoff_sliding_window():
    """SWA: a request whose window already rolled pages dead migrates
    with its freed_until watermark — the destination never touches the
    rolled-dead logical pages and the stream stays byte-identical."""
    long_prompt = [(i * 5) % 250 + 1 for i in range(56)]
    cfg, params = _setup(["model.sliding_window=32"])
    ref = InferenceEngine(cfg, params).generate([long_prompt], 24)[0]
    src = InferenceEngine(cfg, params)
    dst = InferenceEngine(cfg, params)
    rid = src.submit_request(long_prompt, 24).rid
    while not src.migration_ready(rid):
        src.step()
    req, _ = _handoff(src, dst, rid)
    assert req.freed_until > 0, "window never rolled — test is vacuous"
    assert all(p is None for p in req.pages[:req.freed_until])
    er = _drain(dst)[req.rid]
    assert er.outcome == "completed"
    assert list(er.generated) == ref
    src.assert_page_accounting()
    dst.assert_page_accounting()


def test_engine_handoff_mismatched_pools():
    """The copy envelope is pool-geometry independent: a destination
    with a DIFFERENT page pool (num_pages) imports the same blocks —
    logical page indices are preserved, physical placement is the
    destination allocator's business."""
    cfg, params = _setup()
    big = dataclasses.replace(
        cfg, inference=dataclasses.replace(cfg.inference, num_pages=64)
    )
    ref = InferenceEngine(cfg, params).generate([PROMPT], 24)[0]
    src = InferenceEngine(cfg, params)
    dst = InferenceEngine(big, params)
    rid = src.submit_request(PROMPT, 24).rid
    while not src.migration_ready(rid):
        src.step()
    req, _ = _handoff(src, dst, rid)
    er = _drain(dst)[req.rid]
    assert er.outcome == "completed"
    assert list(er.generated) == ref
    src.assert_page_accounting()
    dst.assert_page_accounting()


def test_engine_handoff_page_size_mismatch_rejected():
    """Page size is the one geometry the blocks DO bake in: the
    destination must refuse the import up front, before any staging."""
    cfg, params = _setup()
    small = dataclasses.replace(
        cfg, inference=dataclasses.replace(cfg.inference, page_size=8)
    )
    src = InferenceEngine(cfg, params)
    dst = InferenceEngine(small, params)
    rid = src.submit_request(PROMPT, 24).rid
    while not src.migration_ready(rid):
        src.step()
    with pytest.raises(ValueError, match="page_size"):
        dst.import_begin(src.export_migration_state(rid))
    dst.assert_page_accounting()


def test_prefix_shared_page_migration_refcounts():
    """A request whose prompt rides radix-tree shared pages migrates by
    VALUE (the gather copies the shared page's bytes): the source tree's
    refcounts stay intact, the co-tenant still decodes byte-identically,
    and both pools account exactly."""
    warm = [(i * 3) % 250 + 1 for i in range(32)]   # 2 full pages
    p_a = warm + [61, 62, 63]
    p_b = warm + [71, 72, 73]
    cfg, params = _setup(["inference.prefix_cache=true"])
    ref = InferenceEngine(cfg, params).generate([p_a, p_b], 24)
    src = InferenceEngine(cfg, params)
    dst = InferenceEngine(cfg, params)
    rid_a = src.submit_request(p_a, 24).rid
    rid_b = src.submit_request(p_b, 24).rid
    while not (src.migration_ready(rid_a) and src.migration_ready(rid_b)):
        src.step()
    req_a, _ = _handoff(src, dst, rid_a)
    src.assert_page_accounting()     # tree refs: b still holds the warm path
    dst.assert_page_accounting()
    er_a = _drain(dst)[req_a.rid]
    er_b = _drain(src)[rid_b]
    assert list(er_a.generated) == ref[0]
    assert list(er_b.generated) == ref[1]
    src.assert_page_accounting()
    dst.assert_page_accounting()


def test_host_tier_restore_before_migrate():
    """Long-context source whose early pages were demoted to the host
    tier (inference.request_resident_pages): the export envelope pages
    them back in FIRST, so the gathered blocks are complete — and the
    handed-off stream is byte-identical to the colocated long-context
    run."""
    ov = [
        "inference.chunked_prefill=true",
        "inference.prefill_chunk_tokens=16",
        "inference.long_context=true",
        "inference.request_resident_pages=2",
        "inference.host_tier_bytes=262144",
        "inference.host_tier_min_tokens=0",
    ]
    long_prompt = [(i * 11) % 250 + 1 for i in range(80)]
    cfg, params = _setup(ov)
    ref = InferenceEngine(cfg, params).generate([long_prompt], 12)[0]
    src = InferenceEngine(cfg, params)
    dst = InferenceEngine(cfg, params)
    req_src = src.submit_request(long_prompt, 12)
    rid = req_src.rid
    # Step until the residency cap has demoted pages AND there are full
    # pages to stream — the per-chunk export must hit the restore path.
    steps = 0
    while not (
        req_src.host_pages
        and src.migration_in_prefill(rid)
        and src.migration_full_pages(rid) > 0
    ):
        src.step()
        steps += 1
        assert steps < 60, "residency cap never demoted — test is vacuous"
    state = src.export_migration_state(rid)
    token = dst.import_begin(state)
    live, blocks = src.export_migration_pages(
        rid, 0, src.migration_full_pages(rid)
    )
    assert live, "no full pages shipped"
    assert not req_src.host_pages, "export left host-resident pages behind"
    dst.import_pages(token, live, jax.device_get(blocks))
    shipped = max(live) + 1
    # Finish prefill on the source, ship the remainder, commit, tear down
    # — the same sequence the router's per-chunk driver runs.
    while not src.migration_ready(rid):
        src.step()
    live2, blocks2 = src.export_migration_pages(rid, shipped, None)
    if live2:
        dst.import_pages(token, live2, jax.device_get(blocks2))
    req = dst.import_commit(token, src.export_migration_state(rid))
    assert req is not None
    src.finish_migration(rid)
    er = _drain(dst)[req.rid]
    assert er.outcome == "completed"
    assert list(er.generated) == ref
    src.assert_page_accounting()
    dst.assert_page_accounting()


def test_import_abort_frees_staged_pages():
    """A torn stream (source died before commit) unwinds the staging:
    import_abort frees every staged page and the destination pool is
    exactly where it started."""
    cfg, params = _setup()
    src = InferenceEngine(cfg, params)
    dst = InferenceEngine(cfg, params)
    rid = src.submit_request(PROMPT, 24).rid
    while not src.migration_ready(rid):
        src.step()
    free0 = dst.alloc.free_pages
    state = src.export_migration_state(rid)
    live, blocks = src.export_migration_pages(rid)
    token = dst.import_begin(state)
    dst.import_pages(token, live, jax.device_get(blocks))
    assert dst.alloc.free_pages < free0
    dst.import_abort(token)
    assert dst.alloc.free_pages == free0
    dst.assert_page_accounting()
    # Idempotent: a second abort of the same token is a no-op.
    dst.import_abort(token)


# -- router-driven migration -------------------------------------------------


def test_router_split_byte_identical():
    """roles="prefill:1,decode:2": every stream migrates exactly once,
    decode replicas never run prompt prefill, and the fleet output is
    byte-identical to a single-engine run."""
    cfg, params = _setup()
    prompts = [[(i * 7 + j) % 250 + 1 for i in range(20)] for j in range(3)]
    ref = InferenceEngine(cfg, params).generate(prompts, 24)
    r = Router(_split_cfg(cfg, "prefill:1,decode:2"), params)
    out = r.generate(prompts, 24)
    assert out == ref
    assert r.stats.migrations == 3
    assert r.stats.migrations_failed == 0
    for h in r.handles:
        h.engine.assert_page_accounting()
        if h.role == "decode":
            t = h.engine.reset_timing()
            assert t["prefill_s"] == 0.0 and t["prefill_chunks"] == 0
    r.close()


@slow
def test_router_split_int8_byte_identical():
    cfg, params = _setup(["inference.kv_quant=int8"])
    prompts = [[(i * 7 + j) % 250 + 1 for i in range(20)] for j in range(3)]
    ref = InferenceEngine(cfg, params).generate(prompts, 24)
    r = Router(_split_cfg(cfg, "prefill:1,decode:2"), params)
    assert r.generate(prompts, 24) == ref
    assert r.stats.migrations == 3
    for h in r.handles:
        h.engine.assert_page_accounting()
    r.close()


def test_router_per_chunk_streaming():
    """router.migrate_per_chunk with genuinely incremental prefill
    (chunked_prefill + a small per-step token budget): full pages below
    the watermark ship while the prompt is still prefilling, the commit
    still lands atomically, and the output is byte-identical."""
    ov = [
        "inference.chunked_prefill=true",
        "inference.prefill_chunk_tokens=16",
    ]
    cfg, params = _setup(ov)
    prompts = [[(i * 7 + j) % 250 + 1 for i in range(40)] for j in range(3)]
    ref = InferenceEngine(cfg, params).generate(prompts, 24)
    r = Router(
        _split_cfg(cfg, "prefill:1,decode:2", migrate_per_chunk=True),
        params,
    )
    assert r.generate(prompts, 24) == ref
    assert r.stats.migrations == 3
    for h in r.handles:
        h.engine.assert_page_accounting()
    r.close()


def test_migration_fault_containment():
    """Injected scatter faults across the first router steps: each
    failed envelope is counted and unwound (no torn pages anywhere);
    past the retry budget the request simply decodes colocated on its
    prefill replica — byte-identical either way."""
    cfg, params = _setup()
    prompts = [[(i * 7 + j) % 250 + 1 for i in range(40)] for j in range(4)]
    ref = InferenceEngine(cfg, params).generate(prompts, 24)
    inj = FaultInjector(
        [FaultSpec("migration", step=s, path="scatter") for s in range(3)]
    )
    r = Router(_split_cfg(cfg, "prefill:1,decode:2"), params,
               fault_injector=inj)
    assert r.generate(prompts, 24) == ref
    assert r.stats.migrations_failed >= 1
    for h in r.handles:
        h.engine.assert_page_accounting()
    r.close()


def test_kill_prefill_whole_or_requeued():
    """Kill a prefill replica mid-stream (chunked prefill keeps it
    genuinely mid-prompt): every request ends in exactly one typed
    outcome — wholly arrived on the decode side, completed colocated,
    re-queued with the retried tag, or typed error:migration — and
    every completed stream is byte-identical. Never half a context."""
    ov = [
        "inference.chunked_prefill=true",
        "inference.prefill_chunk_tokens=16",
    ]
    cfg, params = _setup(ov)
    prompts = [[(i * 7 + j) % 250 + 1 for i in range(40)] for j in range(4)]
    ref = InferenceEngine(cfg, params).generate(prompts, 24)
    inj = FaultInjector([FaultSpec("replica_kill", step=1, replica=0)])
    r = Router(
        _split_cfg(cfg, "prefill:2,decode:1", migrate_per_chunk=True),
        params, fault_injector=inj,
    )
    reqs = [r.submit_request(p, 24) for p in prompts]
    while r.has_work():
        r.step()
    assert all(rr.outcome for rr in reqs), [rr.outcome for rr in reqs]
    for rr, g in zip(reqs, ref):
        assert rr.outcome in ("completed", "shed", "error:migration")
        if rr.outcome == "completed":
            assert list(rr.generated) == g
    for h in r.handles:
        if not h.dead:
            h.engine.assert_page_accounting()
    r.close()


@slow
def test_router_split_int8_swa_per_chunk_composition():
    """The heavy composition: int8 scale pools + sliding window + per-
    chunk streaming through one handoff pipeline — byte-identical and
    exactly accounted."""
    ov = [
        "inference.kv_quant=int8",
        "model.sliding_window=32",
        "inference.chunked_prefill=true",
        "inference.prefill_chunk_tokens=16",
    ]
    cfg, params = _setup(ov)
    prompts = [[(i * 5 + j) % 250 + 1 for i in range(56)] for j in range(3)]
    ref = InferenceEngine(cfg, params).generate(prompts, 24)
    r = Router(
        _split_cfg(cfg, "prefill:1,decode:2", migrate_per_chunk=True),
        params,
    )
    assert r.generate(prompts, 24) == ref
    assert r.stats.migrations == 3
    for h in r.handles:
        h.engine.assert_page_accounting()
    r.close()


# ---------------------------------------------------------------------------
# tools/router_bench.py --disagg --smoke (the tier-1 acceptance wiring)
# ---------------------------------------------------------------------------


def test_disagg_bench_smoke():
    """tools/router_bench.py --disagg --smoke: colocated vs role-split
    at equal replica count under a prompt burst — every request migrated
    exactly once with measured latency percentiles, decode replicas never
    prefill, and the kill-a-prefill-worker chaos run resolves every request
    whole-or-requeued with zero silent drops. Counts and structure only:
    the tool's ``split_itl_p99_better`` (and so its ``verdict`` and exit
    code) orders two p99s over a few dozen CPU steps beside five other
    xdist workers, which is not a measurement; both are held to finite and
    positive, and the chip cells measure."""
    import math

    root = pathlib.Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "router_bench.py"),
         "--disagg", "--smoke"],
        capture_output=True, text=True, timeout=600,
    )
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    verdict = lines[-1]
    clocks = {"verdict", "split_itl_p99_better"}
    failed = [k for k, v in verdict.items()
              if isinstance(v, bool) and not v and k not in clocks]
    assert not failed, (failed, proc.stderr[-2000:])
    assert proc.returncode == (0 if verdict["verdict"] else 1), proc.stderr[-2000:]
    for name in ("split_all_migrated", "split_decode_replicas_never_prefill",
                 "chaos_no_silent_drops", "chaos_kill_observed",
                 "migration_latency_measured"):
        assert verdict[name] is True, lines
    assert verdict["chaos_migrations_requeued"] >= 0
    for name in ("itl_p99_split_s", "itl_p99_colocated_s"):
        assert math.isfinite(verdict[name]) and verdict[name] > 0, verdict
