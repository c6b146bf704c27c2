"""Speculative decoding: prompt-lookup drafts + batched verification
(ISSUE 3).

The load-bearing property is EQUIVALENCE (mirroring the prefix-cache and
chunked-prefill suites): with inference.speculative on, GREEDY served
tokens must be byte-identical to the non-speculative engine's — the
verify body writes each draft position's KV exactly as a sequential
decode would have and acceptance is exact argmax match — across plain
decode, kv_quant=int8, sliding windows, prefix-cache rows, chunked
prefill (mixed verify steps), tp-sharded pools, and mid-stream preemption
with rollback. Sampled acceptance is rejection sampling: the per-token
OUTPUT DISTRIBUTION is unchanged (pinned statistically at the sampling
unit), while the stream itself draws from a different key sequence.

Rollback is pinned structurally: after every speculative step a live
slot's page footprint equals the non-speculative window=1 engine's
(cursor-covering pages only), and at drain the allocator state matches
exactly (free set + refcounts) — rejected drafts leave no residue.

The workload is deterministic for CI: on the fixed-seed tiny model the
greedy continuation of MIX[1] repeats itself from its 12th token on, which
the n-gram proposer then drafts — the canonical speculative win. The
cyclic prompt's continuation does NOT loop there (so under 12 tokens a
request nothing drafts, and no verify step runs).
"""

import jax
import numpy as np
import pytest

from orion_tpu.config import get_config
from orion_tpu.infer import InferenceEngine
from orion_tpu.infer.spec_decode import SpecState, propose_ngram
from orion_tpu.models import init_params

INFER_OVERRIDES = [
    "inference.max_seq_len=128",
    "inference.page_size=16",
    "inference.num_pages=32",
    "inference.max_batch_size=4",
    "inference.prefill_chunk=16",
    "inference.max_new_tokens=8",
    "inference.decode_window=1",
]
SPEC = [
    "inference.speculative=true",
    "inference.speculate_tokens=4",
]

# MIX[1]'s greedy continuation loops on the seed-0 tiny model (see above).
REP = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]
MIX = [REP, [5, 3, 9, 250, 17], list(range(2, 32))]


def _setup(preset="tiny-llama", overrides=(), spec=True):
    ov = INFER_OVERRIDES + (SPEC if spec else []) + list(overrides)
    cfg = get_config(preset, ov)
    params = init_params(cfg.model, jax.random.key(0))
    return cfg, params


def test_spec_default_off_and_validation():
    cfg, params = _setup(spec=False)
    assert cfg.inference.speculative is False
    eng = InferenceEngine(cfg, params)
    assert eng._spec is None
    bad, _ = _setup(overrides=["inference.speculate_tokens=0"])
    with pytest.raises(ValueError, match="speculate_tokens"):
        InferenceEngine(bad, params)
    bad2, _ = _setup(overrides=["inference.spec_ngram_min=3",
                                "inference.spec_ngram_max=2"])
    with pytest.raises(ValueError, match="spec_ngram"):
        InferenceEngine(bad2, params)


def test_ngram_proposer_unit():
    # Longest n-gram wins: suffix (2, 3) continues with 9 at its earlier
    # occurrence even though suffix (3,) alone would continue with 4.
    ctx = [1, 2, 3, 9, 5, 3, 4, 2, 3]
    assert propose_ngram(ctx, 2, max_n=3, min_n=1) == [9, 5]
    # Most RECENT occurrence preferred at equal n.
    ctx2 = [1, 2, 7, 5, 1, 2, 8, 5, 1, 2]
    assert propose_ngram(ctx2, 1, max_n=2, min_n=1) == [8]
    # Truncated at the source's end; never longer than k.
    assert propose_ngram([4, 6, 4], 5, max_n=1, min_n=1) == [6, 4]
    # No match -> no draft.
    assert propose_ngram([1, 2, 3, 4], 4, max_n=3, min_n=2) == []
    # External sources (prefix-cache paths) draft when the context misses.
    assert propose_ngram(
        [9, 1, 2], 3, max_n=2, min_n=1,
        extra_sources=[(5, 1, 2, 6, 7, 8)],
    ) == [6, 7, 8]
    # Adaptive length: halve on low acceptance, double back on full.
    st = SpecState(draft_len=4)
    st.update(4, 1, cap=4)
    assert st.draft_len == 2
    st.update(2, 2, cap=4)
    assert st.draft_len == 4
    st.update(4, 4, cap=4)
    assert st.draft_len == 4            # capped
    st.update(0, 0, cap=4)
    assert st.draft_len == 4            # no-draft step learns nothing
    # Miss backoff: consecutive no-match scans skip ahead linearly, so a
    # non-repetitive request doesn't pay the O(context) scan every step.
    from orion_tpu.infer.spec_decode import NgramProposer

    pr = NgramProposer(speculate_tokens=4, max_n=3, min_n=1)
    flat = list(range(100, 140))        # no n-gram ever repeats
    scans = [pr.propose(1, flat, 4) for _ in range(12)]
    assert all(d == [] for d in scans)
    s = pr.state(1)
    assert s.miss_streak < 12           # throttle skipped real scans
    assert s.cooldown >= 0
    # A hit resets the streak and drafting resumes immediately.
    pr.state(1).cooldown = 0
    assert pr.propose(1, [7, 8, 9, 7, 8], 2) == [9, 7]
    assert pr.state(1).miss_streak == 0


def test_equivalence_greedy_and_counters():
    """Greedy spec-on byte-identical to spec-off on looping + non-looping
    prompts admitted together, with the acceptance counters surfaced
    through reset_timing and a real amortization on the looping load."""
    cfg_on, params = _setup()
    cfg_off, _ = _setup(spec=False)
    ref = InferenceEngine(cfg_off, params).generate(MIX, 24)
    eng = InferenceEngine(cfg_on, params)
    assert eng.generate(MIX, 24) == ref
    t = eng.reset_timing()
    assert t["verify_steps"] > 0, t
    assert t["spec_drafted"] > 0, t
    assert t["spec_accepted"] > 0, t
    assert t["spec_rolled_back"] == t["spec_drafted"] - t["spec_accepted"]
    assert t["spec_tokens_per_verify"] > 1.3, t


def test_rollback_state_exact():
    """KV/page state after rollback is exactly the non-speculative state:
    mid-run every live slot holds only its cursor-covering pages (the
    window=1 footprint), and at drain the allocator free set and
    refcounts match the spec-off engine's exactly."""
    cfg_on, params = _setup()
    cfg_off, _ = _setup(spec=False)
    prompts = [REP, list(range(2, 32))]

    eng = InferenceEngine(cfg_on, params)
    for p in prompts:
        eng.submit(p, 20)
    while eng.has_work():
        eng.step()
        for r in eng.slots:
            if r is not None and not r.done:
                want = (int(eng.seq_lens[r.slot]) - 1) // eng.psz + 1
                assert len(r.pages) == want, (len(r.pages), want)
    ref = InferenceEngine(cfg_off, params)
    ref.generate(prompts, 20)
    assert sorted(eng.alloc._free) == sorted(ref.alloc._free)
    assert eng.alloc._refs == ref.alloc._refs
    assert all(n == 0 for n in eng.alloc._refs)


def test_spec_verify_sample_rejection_statistics():
    """Rejection sampling preserves the target distribution: over many
    keys, the emitted token (draft if accepted, else the residual sample)
    is distributed as softmax(logits/T) — acceptance frequency matches
    p(draft) and the emission law matches p within Monte-Carlo noise."""
    from orion_tpu.infer.sampling import spec_verify_sample

    V = 8
    logits = jax.random.normal(jax.random.key(2), (1, 1, V)) * 2.0
    temp = 0.7
    p = np.asarray(jax.nn.softmax(np.asarray(logits[0, 0]) / temp))
    draft = int(np.argsort(p)[-2])          # second-likeliest as the draft
    dn = jax.numpy.asarray([[draft]], dtype=jax.numpy.int32)

    run = jax.jit(
        lambda k: spec_verify_sample(logits, dn, k, temperature=temp)
    )
    N = 4000
    keys = jax.random.split(jax.random.key(3), N)
    acc, alt = jax.vmap(run)(keys)
    acc = np.asarray(acc)[:, 0, 0]
    alt = np.asarray(alt)[:, 0, 0]
    emitted = np.where(acc, draft, alt)
    assert abs(acc.mean() - p[draft]) < 0.03, (acc.mean(), p[draft])
    emp = np.bincount(emitted, minlength=V) / N
    tv = 0.5 * np.abs(emp - p).sum()
    assert tv < 0.04, (tv, emp, p)
    # Residual never re-emits the rejected draft.
    assert not np.any(alt[~acc] == draft)
    # Bonus position (no draft): a plain sample from p.
    dn_bonus = jax.numpy.full((1, 1), -1, jax.numpy.int32)
    runb = jax.jit(
        lambda k: spec_verify_sample(logits, dn_bonus, k, temperature=temp)
    )
    accb, altb = jax.vmap(runb)(keys)
    assert not np.asarray(accb).any()       # nothing to accept
    empb = np.bincount(np.asarray(altb)[:, 0, 0], minlength=V) / N
    assert 0.5 * np.abs(empb - p).sum() < 0.04


@pytest.mark.slow
def test_sampled_engine_accept_path():
    """Sampled serving through the rejection-sampling verify path:
    temperature>0 with top_k=1 is argmax-deterministic, so the spec-on
    stream must equal spec-off byte-for-byte while accepts flow through
    the u < p(draft) machinery (p(draft) is 0 or 1 here)."""
    sam = ["inference.temperature=0.9", "inference.top_k=1"]
    cfg_on, params = _setup(overrides=sam)
    cfg_off, _ = _setup(overrides=sam, spec=False)
    a = InferenceEngine(cfg_on, params, seed=5)
    assert a.generate([REP], 20) == (
        InferenceEngine(cfg_off, params, seed=5).generate([REP], 20)
    )
    t = a.reset_timing()
    assert t["spec_drafted"] > 0 and t["spec_accepted"] > 0, t


@pytest.mark.slow
def test_eos_mid_acceptance():
    """EOS surfacing inside an accepted draft run stops the request at
    the EOS token exactly as sequential decoding would."""
    cfg_on, params = _setup()
    cfg_off, _ = _setup(spec=False)
    free = InferenceEngine(cfg_off, params).generate([REP], 20)[0]
    eos = free[6]                # falls inside the looping (drafted) region
    ref = InferenceEngine(cfg_off, params, eos_id=eos).generate([REP], 20)
    eng = InferenceEngine(cfg_on, params, eos_id=eos)
    assert eng.generate([REP], 20) == ref


@pytest.mark.slow
def test_equivalence_kv_quant():
    """int8 KV pool: verify writes quantized draft KV and every query
    attends it dequantized — the sequential decode numerics exactly."""
    q = ["inference.kv_quant=int8"]
    cfg_on, params = _setup(overrides=q)
    cfg_off, _ = _setup(overrides=q, spec=False)
    assert InferenceEngine(cfg_on, params).generate(MIX, 16) == (
        InferenceEngine(cfg_off, params).generate(MIX, 16)
    )


@pytest.mark.slow
def test_equivalence_sliding_window():
    """SWA: verify queries window their own positions per layer, and the
    page roll follows the rewound cursor."""
    swa = ["model.sliding_window=20"]
    cfg_on, params = _setup(overrides=swa)
    cfg_off, _ = _setup(overrides=swa, spec=False)
    assert InferenceEngine(cfg_on, params).generate(MIX, 16) == (
        InferenceEngine(cfg_off, params).generate(MIX, 16)
    )


@pytest.mark.slow
def test_equivalence_prefix_cache():
    """Spec x prefix cache: warm rows speculate over shared pages (the
    rollback never touches them — tail pages are private by construction)
    and the radix tree's cached paths serve as draft sources."""
    pc = ["inference.prefix_cache=true"]
    cfg_on, params = _setup(overrides=pc)
    cfg_off, _ = _setup(overrides=pc, spec=False)
    eng_on = InferenceEngine(cfg_on, params)
    eng_off = InferenceEngine(cfg_off, params)
    assert eng_on.generate(MIX, 16) == eng_off.generate(MIX, 16)
    # Warm round: matched prefixes map in AND speculation still matches.
    assert eng_on.generate(MIX, 16) == eng_off.generate(MIX, 16)
    t = eng_on.reset_timing()
    assert t["prefix_hits"] >= 1, t
    assert t["spec_accepted"] > 0, t
    # The cached paths are exposed to the proposer.
    paths = eng_on._pcache.token_paths()
    assert paths and all(len(p) % eng_on.psz == 0 for p in paths)


@pytest.mark.slow
def test_equivalence_chunked_prefill():
    """Spec x chunked prefill: decode-phase slots speculate through the
    mixed verify step while a long prompt chunks alongside; prompt-phase
    slots never draft; tokens equal the spec-off chunked engine's."""
    ch = ["inference.chunked_prefill=true",
          "inference.prefill_chunk_tokens=16"]
    cfg_on, params = _setup(overrides=ch)
    cfg_off, _ = _setup(overrides=ch, spec=False)

    def run(cfg):
        eng = InferenceEngine(cfg, params)
        out = {}
        eng.submit(REP, 24)
        eng.step()
        eng.step()                      # REP decoding (and speculating)
        eng.submit(list(range(1, 97)), 4)   # 96-token prompt chunks in
        while eng.has_work():
            for r in eng.step():
                out[r.rid] = r.generated
        return out, eng

    got, eng = run(cfg_on)
    ref, _ = run(cfg_off)
    assert got == ref
    t = eng.reset_timing()
    assert t["mixed_steps"] > 0, t
    assert t["spec_accepted"] > 0, t    # speculation ran during the mix


@pytest.mark.slow
def test_equivalence_tp_sharded_pallas(cpu_devices):
    """Spec x tp-sharded KV pool x Pallas serving: drafting/verification
    over the head-sharded pool; tokens equal the unsharded spec-off
    engine's."""
    import dataclasses

    from orion_tpu.config import ParallelConfig
    from orion_tpu.models.transformer import param_logical_axes
    from orion_tpu.parallel.sharding import param_shardings
    from orion_tpu.runtime import build_mesh

    cfg_on, params = _setup()
    cfg_off, _ = _setup(spec=False)
    pcfg_on = dataclasses.replace(
        cfg_on, model=dataclasses.replace(cfg_on.model,
                                          kernels="pallas_interpret")
    )
    pcfg_off = dataclasses.replace(
        cfg_off, model=dataclasses.replace(cfg_off.model,
                                           kernels="pallas_interpret")
    )
    prompts = [REP, [5, 3, 9, 250, 17]]
    ref = InferenceEngine(pcfg_off, params).generate(prompts, 8)

    mesh = build_mesh(ParallelConfig(tp=2), devices=cpu_devices[:2])
    shardings = param_shardings(mesh, param_logical_axes(cfg_on.model))
    sharded = jax.device_put(params, shardings)
    eng = InferenceEngine(pcfg_on, sharded)
    assert eng.mesh is not None
    assert eng.generate(prompts, 8) == ref
    assert eng.reset_timing()["spec_accepted"] > 0


@pytest.mark.slow
def test_preemption_mid_stream_rollback():
    """Pool pressure preempts the youngest request while speculation is
    in flight: the verify step's own page provisioning triggers the
    preemption, the victim donates only cursor-valid pages (never
    rejected-draft garbage), requeues, resumes, and every request still
    produces its solo tokens exactly."""
    ov = ["inference.num_pages=14", "inference.prefix_cache=true"]
    cfg_on, params = _setup(overrides=ov)
    cfg_off, _ = _setup(overrides=["inference.num_pages=14"], spec=False)
    prompts = [[(i * 7) % 250 + 1 for i in range(16)],
               [(i * 11) % 250 + 1 for i in range(16)],
               [7, 8, 9] * 5 + [7]]
    new = [60, 60, 60]
    singles = [
        InferenceEngine(cfg_off, params).generate([p], n)[0]
        for p, n in zip(prompts, new)
    ]
    eng = InferenceEngine(cfg_on, params)
    rids = [eng.submit(p, n) for p, n in zip(prompts, new)]
    out = {}
    while eng.has_work():
        for r in eng.step():
            out[r.rid] = r.generated
    assert [out[rid] for rid in rids] == singles
    assert eng.preemptions >= 1, "scenario failed to exercise preemption"
    t = eng.reset_timing()
    assert t["spec_drafted"] > 0, t


def test_bench_smoke():
    """tools/spec_decode_bench.py --smoke (the tier-1 wiring): greedy
    spec-on/off streams identical on BOTH verify kernel paths AND both
    drafting modes (chain + tree), the self-repetitive workload shows
    > 1.3 decode tokens per verify dispatch with the tree degenerating
    to (not losing to) the chain, and the NON-LOOPING workload shows a
    measured tree-over-chain acceptance uplift — the ISSUE 11 claim as
    a number, not prose."""
    import json
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "spec_decode_bench.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    verdict = lines[-1]
    assert verdict["greedy_identical"] is True, lines
    assert verdict["pallas_greedy_identical"] is True, lines
    assert verdict["tree_greedy_identical"] is True, lines
    assert verdict["tree_pallas_greedy_identical"] is True, lines
    assert verdict["nonloop_tree_greedy_identical"] is True, lines
    assert verdict["spec_tokens_per_verify"] > 1.3, lines
    assert verdict["acceptance_rate"] > 0.5, lines
    # Looping: the tree must not lose to the single path it degenerates
    # to. Non-looping: the tree's branch coverage must buy acceptance.
    assert verdict["tree_tokens_per_verify"] >= (
        verdict["spec_tokens_per_verify"] - 1e-9
    ), verdict
    assert verdict["nonloop_tree_uplift"] > 0, verdict
    assert set(verdict["verify_dev_ms"]) == {"xla", "pallas"}, verdict
    by_mode = {
        (d["workload"], d["mode"]): d for d in lines[:-1]
    }
    for path in ("xla", "pallas"):
        base = by_mode[("looping", f"baseline_{path}")]
        for mode in (f"speculative_{path}", f"tree_{path}"):
            spec = by_mode[("looping", mode)]
            assert spec["verify_path"] == path
            # <=: acceptance gates per-prompt; a prompt that drafts
            # little can pin the step count at the baseline's (observed
            # seed-dependent) — the throughput claim rides
            # tokens-per-verify + the identity checks.
            assert spec["steps"] <= base["steps"]
            assert spec["spec_rolled_back"] == (
                spec["spec_drafted"] - spec["spec_accepted"]
            )
            assert "dev_ms_per_step" in spec and "host_ms_per_step" in spec


# -- pallas verify path (multi-query ragged paged-attention kernel) ---------

PALLAS = ["model.kernels=pallas_interpret"]


def test_equivalence_greedy_pallas_verify():
    """ISSUE 5 acceptance: with kernels=pallas the verify step runs the
    multi-query ragged paged-attention kernel instead of falling back to
    the XLA scatter+gather body — and the greedy spec-on stream stays
    byte-identical to the spec-off pallas engine (whose decode is the W=1
    fused-write kernel), with the rollback footprint unchanged (every
    live slot holds exactly its cursor-covering pages after each step)."""
    cfg_on, params = _setup(overrides=PALLAS)
    cfg_off, _ = _setup(overrides=PALLAS, spec=False)
    ref = InferenceEngine(cfg_off, params).generate(MIX, 20)
    eng = InferenceEngine(cfg_on, params)
    for p in MIX:
        eng.submit(p, 20)
    out = {}
    while eng.has_work():
        for r in eng.step():
            out[r.rid] = r.generated
        for r in eng.slots:
            if r is not None and not r.done:
                want = (int(eng.seq_lens[r.slot]) - 1) // eng.psz + 1
                assert len(r.pages) == want, (len(r.pages), want)
    assert [out[i] for i in sorted(out)] == ref
    t = eng.reset_timing()
    assert t["verify_steps"] > 0 and t["spec_accepted"] > 0, t


def _run_budgets(cfg, params, budgets):
    """MIX with one token budget a request; (streams, engine timing)."""
    eng = InferenceEngine(cfg, params)
    rids = [eng.submit(p, n) for p, n in zip(MIX, budgets)]
    out = {}
    while eng.has_work():
        for r in eng.step():
            out[r.rid] = r.generated
    return [out[i] for i in rids], eng.reset_timing()


def test_draft_density_gating():
    """inference.spec_min_draft_slots: a lone repetitive tenant in a
    mostly-non-repetitive batch no longer drags every co-tenant into
    whole-batch verify steps — under-threshold steps run the plain decode
    window (counted as spec_gated_steps), the threshold clamps to the
    live-slot count (a solo drafting request still verifies), and the
    greedy stream is unchanged either way.

    On the seed-0 tiny model only MIX[1]'s stream repeats itself (from
    its 12th token on, and again past its 16th); the cyclic prompt's does
    not. So the co-tenants get SHORTER budgets: while they live, one slot
    of three drafts and the gate (3) holds every such step back; once
    they are done the batch is the drafting slot alone, the clamp lets it
    through, and it verifies. Equal budgets end all three on one step,
    and no verify ever runs."""
    budgets = [16, 40, 16]
    gate = ["inference.spec_min_draft_slots=3"]
    cfg_gated, params = _setup(overrides=gate)
    cfg_open, _ = _setup()
    cfg_off, _ = _setup(spec=False)
    ref, _ = _run_budgets(cfg_off, params, budgets)
    # The premise, on the engine without the gate: drafts come while the
    # co-tenants are live (verify steps more than one slot wide), else
    # nothing is there to gate.
    got, t_open = _run_budgets(cfg_open, params, budgets)
    assert got == ref
    assert t_open["spec_gated_steps"] == 0, t_open
    assert t_open["verify_slot_steps"] > t_open["verify_steps"], t_open
    got, t = _run_budgets(cfg_gated, params, budgets)
    assert got == ref
    # The gate held back steps that the open engine verified ...
    assert t["spec_gated_steps"] > 0, t
    assert 0 < t["verify_steps"] < t_open["verify_steps"], (t, t_open)
    # ... and let through the shrunk batch's alone: every verify it ran
    # was one slot wide.
    assert t["verify_slot_steps"] == t["verify_steps"], t
    # Solo request: the gate clamps to the live count and verification
    # proceeds (otherwise a 1-slot batch could never speculate).
    solo = InferenceEngine(cfg_gated, params)
    solo.generate([MIX[1]], 40)
    ts = solo.reset_timing()
    assert ts["verify_steps"] > 0 and ts["spec_gated_steps"] == 0, ts
    # Validation: the knob must be >= 1.
    bad, _ = _setup(overrides=["inference.spec_min_draft_slots=0"])
    with pytest.raises(ValueError, match="spec_min_draft_slots"):
        InferenceEngine(bad, params)


def test_spec_pallas_vmem_validation():
    """speculative + pallas kernels + a verify width the ragged kernel
    cannot hold in VMEM is a config error at engine init naming the knob,
    not a Mosaic allocation failure mid-serving."""
    cfg, params = _setup()
    bad, _ = _setup(
        overrides=PALLAS + ["inference.speculate_tokens=100000"])
    with pytest.raises(ValueError, match="speculate_tokens"):
        InferenceEngine(bad, params)
    # The same width is fine on the xla path (no kernel, no VMEM).
    big_xla, _ = _setup(overrides=["inference.speculate_tokens=64"])
    InferenceEngine(big_xla, params)


# slow (tier-1 budget, round 10): heavy pallas-interpret engine pairs.
# Tier-1 keeps the plain pallas verify equivalence + the kernel-level
# ragged/int8/SWA unit tests in tests/test_pallas_ops.py; these pin the
# same compositions end-to-end through the engine.


@pytest.mark.slow
def test_equivalence_pallas_kv_quant():
    """int8 pool on the pallas verify path: the kernel quantizes all W
    drafts in-kernel with the shared common.quantize_kv, so acceptance
    numerics equal the sequential W=1-kernel decode bit-for-bit."""
    q = PALLAS + ["inference.kv_quant=int8"]
    cfg_on, params = _setup(overrides=q)
    cfg_off, _ = _setup(overrides=q, spec=False)
    assert InferenceEngine(cfg_on, params).generate(MIX, 16) == (
        InferenceEngine(cfg_off, params).generate(MIX, 16)
    )


@pytest.mark.slow
def test_equivalence_pallas_sliding_window():
    """SWA on the pallas verify path: per-query windows + the behind-
    window page clamp, against the spec-off W=1 pallas kernel."""
    swa = PALLAS + ["model.sliding_window=20"]
    cfg_on, params = _setup(overrides=swa)
    cfg_off, _ = _setup(overrides=swa, spec=False)
    assert InferenceEngine(cfg_on, params).generate(MIX, 16) == (
        InferenceEngine(cfg_off, params).generate(MIX, 16)
    )


@pytest.mark.slow
def test_equivalence_pallas_gemma2():
    """Gemma-2 family on the pallas verify path: logit softcap +
    interleaved local/global windows (static per scan position) + post
    norms, spec-on == spec-off."""
    cfg_on, params = _setup("tiny-gemma2", overrides=PALLAS)
    cfg_off, _ = _setup("tiny-gemma2", overrides=PALLAS, spec=False)
    assert InferenceEngine(cfg_on, params).generate(MIX, 12) == (
        InferenceEngine(cfg_off, params).generate(MIX, 12)
    )


# -- token-tree speculation (ISSUE 11) --------------------------------------

TREE = SPEC + ["inference.spec_tree_width=3"]


def _ambig_prompt(seed):
    """A prompt with planted AMBIGUOUS n-gram continuations: the same
    (a, b) pair recurs with different continuations, and the random
    filler recurs at n=1 with divergent followers as decode proceeds —
    single-path drafting must bet on the most recent match; tree
    drafting carries the alternatives as branches."""
    import random

    r = random.Random(seed)
    base = [r.randrange(2, 200) for _ in range(6)]
    a, b = r.randrange(2, 200), r.randrange(2, 200)
    out = []
    for _ in range(5):
        out += [a, b, r.randrange(2, 200), r.randrange(2, 200)]
    return base + out + [a, b]


AMBIG = [_ambig_prompt(i) for i in range(2)]


def _tree_for(ref, context, base_len, limit, good_at=2):
    """A deterministic branchy DraftTree whose SECOND branch is the true
    continuation (mocking the proposer): primary = junk chain, sibling
    branch = the next two reference tokens — so acceptance must walk the
    OFF-primary branch and the engine must compact its KV."""
    from orion_tpu.infer.spec_decode import DraftTree

    i = len(context) - base_len
    good = ref[i:i + 2]
    if len(good) < 2 or limit < 4:
        return None
    return DraftTree(tokens=[201, 202, good[0], good[1]],
                     parents=[0, 1, 0, 3])


def test_tree_proposer_and_builder_unit():
    from orion_tpu.infer.spec_decode import (
        DraftTree,
        build_tree,
        propose_ngram_candidates,
    )

    # Two distinct continuations of the suffix (1, 2): most recent first.
    ctx = [1, 2, 3, 9, 1, 2, 4, 8, 1, 2]
    cands = propose_ngram_candidates(ctx, 3, max_n=3, min_n=1,
                                     max_candidates=4)
    assert cands[0] == [4, 8, 1]            # most recent = chain proposal
    assert [3, 9, 1] in cands
    # Prefix-of-existing candidates add nothing.
    assert len(cands) == len({tuple(c) for c in cands})
    t = build_tree(cands, 4)
    assert t.tokens[:3] == [4, 8, 1]        # primary chain contiguous
    assert t.parents[:3] == [0, 1, 2]
    assert 3 in t.tokens and t.parents[t.tokens.index(3)] == 0
    d = t.depths()
    assert d[0] == 0 and d[1:4] == [1, 2, 3]
    # Ancestor words: every column sees root+ancestors+itself, nothing else.
    w = t.mask_words()
    assert w[0] == 1 and w[1] == 0b11 and w[2] == 0b111
    sib = t.tokens.index(3) + 1             # the branch column
    assert w[sib] == (1 << sib) | 1         # root + itself only
    # Budget truncation merges shared prefixes first.
    t2 = build_tree([[5, 6, 7], [5, 9]], 3)
    assert t2.tokens == [5, 6, 7] or len(t2) == 3
    # Chain helper degenerates to sequential parents.
    c = DraftTree.chain([4, 5, 6])
    assert c.parents == [0, 1, 2] and c.max_depth == 3
    # children() preserves sibling insertion (priority) order.
    assert t.children()[0][0] == 1


def test_tree_proposer_reserves_branch_room():
    """With the adaptive depth at the cap, real ambiguity still turns
    into branches: the primary chain's tail is trimmed one node per
    alternative candidate."""
    from orion_tpu.infer.spec_decode import NgramProposer

    pr = NgramProposer(speculate_tokens=4, max_n=3, min_n=1, tree_width=3)
    ctx = [1, 2, 3, 9, 7, 1, 2, 4, 8, 6, 1, 2]
    t = pr.propose_tree(1, ctx, 10)
    assert t is not None and len(t) <= 4
    roots = [i + 1 for i, p in enumerate(t.parents) if p == 0]
    assert len(roots) >= 2                  # both continuations drafted
    # Single-candidate (looping) context: full-depth chain, no trim.
    t2 = pr.propose_tree(2, [7, 8, 9, 7, 8, 9, 7, 8], 10)
    assert t2 is not None and t2.parents == list(range(len(t2)))
    # Width validation.
    with pytest.raises(ValueError, match="tree_width"):
        NgramProposer(speculate_tokens=4, max_n=3, min_n=1, tree_width=0)


def test_tree_config_validation():
    cfg, params = _setup()
    with pytest.raises(ValueError, match="spec_tree_width"):
        _setup(overrides=["inference.spec_tree_width=0"])   # domain check
    wide, _ = _setup(overrides=["inference.spec_tree_width=8"])
    with pytest.raises(ValueError, match="spec_tree_width"):
        InferenceEngine(wide, params)        # width > speculate_tokens
    deep, _ = _setup(overrides=["inference.speculate_tokens=40",
                                "inference.spec_tree_width=2"])
    with pytest.raises(ValueError, match="31"):
        InferenceEngine(deep, params)        # int32 ancestor words
    # Chain width 40 stays legal (no packed words on the chain path).
    chain40, _ = _setup(overrides=["inference.speculate_tokens=40"])
    InferenceEngine(chain40, params)


def test_tree_equivalence_greedy():
    """Greedy tree-spec-on byte-identical to spec-off (xla verify path)
    on looping AND ambiguous prompts, with branch nodes actually drafted
    and the drain-time allocator state equal to the spec-off engine's."""
    cfg_t, params = _setup(overrides=["inference.spec_tree_width=3"])
    cfg_off, _ = _setup(spec=False)
    prompts = MIX + AMBIG
    ref_eng = InferenceEngine(cfg_off, params)
    ref = ref_eng.generate(prompts, 24)
    eng = InferenceEngine(cfg_t, params)
    assert eng.generate(prompts, 24) == ref
    t = eng.reset_timing()
    assert t["verify_steps"] > 0 and t["spec_accepted"] > 0, t
    assert t["spec_tree_nodes"] > 0, t
    # (Branchy-tree acceptance + compaction are pinned deterministically
    # by test_tree_offpath_acceptance_compacts_kv; this workload's
    # branching depends on the model's continuations.)
    assert t["spec_rolled_back"] == t["spec_drafted"] - t["spec_accepted"]
    assert sorted(eng.alloc._free) == sorted(ref_eng.alloc._free)
    assert eng.alloc._refs == ref_eng.alloc._refs


def test_tree_offpath_acceptance_compacts_kv():
    """The tree walk accepting a NON-primary branch: its KV lives at
    off-path verify columns and must be compacted into cursor-contiguous
    slots (kv_cache.compact_draft_kv) before the next step reads it —
    pinned by byte-identity of the CONTINUED stream on both kernel
    paths, with the compaction counters proving the path ran and the
    rollback leaving the window=1 footprint."""
    for kern in ([], PALLAS):
        cfg_off, params = _setup(overrides=kern, spec=False)
        ref = InferenceEngine(cfg_off, params).generate([REP], 16)[0]
        cfg_t, _ = _setup(overrides=kern + ["inference.spec_tree_width=3"])
        eng = InferenceEngine(cfg_t, params)
        eng._spec.propose_tree = (
            lambda rid, context, limit, extra_sources=(), _r=ref:
            _tree_for(_r, context, len(REP), limit)
        )
        got = eng.generate([REP], 16)[0]
        t = eng.reset_timing()
        assert got == ref, kern
        assert t["spec_compactions"] > 0, t
        assert t["spec_compacted_tokens"] > 0, t
        eng.assert_page_accounting()


def test_tree_compaction_fault_contained():
    """A failing compaction dispatch fails the STEP, not the process —
    BEFORE any token was emitted (the plan-then-compact-then-emit
    order), without counting a completed compaction, and feeding the
    speculation auto-disable ladder like every other verify-path
    fault."""
    cfg_off, params = _setup(spec=False)
    ref = InferenceEngine(cfg_off, params).generate([REP], 16)[0]
    cfg_t, _ = _setup(overrides=["inference.spec_tree_width=3",
                                 "inference.spec_fault_limit=2"])
    eng = InferenceEngine(cfg_t, params)
    eng._spec.propose_tree = (
        lambda rid, context, limit, extra_sources=(), _r=ref:
        _tree_for(_r, context, len(REP), limit)
    )

    def boom(*a, **k):
        raise RuntimeError("injected compact fault")

    eng._compact = boom
    out = {}
    eng.submit(REP, 16)
    while eng.has_work():
        for r in eng.step():
            out[r.rid] = r.generated
    t = eng.reset_timing()
    assert t["failed_steps"] >= 1, t
    assert t["spec_compactions"] == 0, t         # nothing counted as done
    assert t["spec_compacted_tokens"] == 0, t
    # Ladder: repeated compact faults auto-disable speculation, and the
    # request still finishes (plain decode) with the spec-off stream.
    assert t["spec_disabled_reason"], t
    assert list(out.values())[0] == ref
    eng.assert_page_accounting()
    """A width>1 engine fed single-candidate (looping) traffic builds
    chain-shaped trees — and must emit byte-identically to the chain
    (width=1) engine, with zero compactions (the primary chain needs no
    KV moves)."""
    cfg_t, params = _setup(overrides=["inference.spec_tree_width=3"])
    cfg_c, _ = _setup()
    a = InferenceEngine(cfg_t, params)
    b = InferenceEngine(cfg_c, params)
    assert a.generate([REP], 24) == b.generate([REP], 24)
    ta, tb = a.reset_timing(), b.reset_timing()
    assert ta["spec_compactions"] == 0, ta
    assert ta["spec_accepted"] == tb["spec_accepted"], (ta, tb)


def test_tree_chain_degenerate_verify_step_bitwise():
    """runner.verify_step fed chain-shaped tree arrays writes BITWISE
    the same KV pools as the plain chain program (XLA body; the pallas
    kernel's twin pin lives in test_pallas_ops), and greedy alt tokens
    match column for column."""
    import numpy as np

    from orion_tpu.infer.kv_cache import init_cache
    from orion_tpu.infer.runner import verify_step

    cfg, params = _setup()
    mcfg, icfg = cfg.model, cfg.inference
    B, W = icfg.max_batch_size, icfg.speculate_tokens + 1
    cache = init_cache(mcfg, icfg)
    tokens = jax.numpy.asarray(
        np.arange(B * W).reshape(B, W) % 200 + 2, jax.numpy.int32)
    seq_lens = jax.numpy.asarray([5, 17, 0, 30], jax.numpy.int32)
    lens = jax.numpy.asarray([W, 2, 1, 3], jax.numpy.int32)
    pt = jax.numpy.asarray(
        np.arange(1, 1 + B * 8).reshape(B, 8), jax.numpy.int32)
    active = jax.numpy.asarray([True, True, False, True])
    key = jax.random.key(0)
    steps = np.arange(W, dtype=np.int64)
    depths = jax.numpy.asarray(np.tile(steps, (B, 1)), jax.numpy.int32)
    parents = jax.numpy.asarray(
        np.tile(np.maximum(steps - 1, 0), (B, 1)), jax.numpy.int32)
    words = jax.numpy.asarray(
        np.tile((np.int64(1) << (steps + 1)) - 1, (B, 1)), jax.numpy.int32)
    a_plain, alt_plain, c_plain = verify_step(
        params, dict(cache), tokens, seq_lens, lens, pt, active, key,
        0.0, 0, 1.0, cfg=mcfg, max_seq_len=icfg.max_seq_len)
    a_tree, alt_tree, c_tree = verify_step(
        params, dict(cache), tokens, seq_lens, lens, pt, active, key,
        0.0, 0, 1.0, cfg=mcfg, max_seq_len=icfg.max_seq_len,
        depths=depths, parents=parents, tree_mask=words)
    for name in c_plain:
        assert (np.asarray(c_plain[name]) == np.asarray(c_tree[name])).all()
    assert (np.asarray(alt_plain) == np.asarray(alt_tree)).all()
    # accept is parent-indexed on the chain program, child-indexed on
    # the tree program: shifted by one column, same verdicts.
    assert (np.asarray(a_plain)[:, :-1] == np.asarray(a_tree)[:, 1:]).all()


@pytest.mark.parametrize("kv_quant", [None, "int8"])
@pytest.mark.parametrize("kernels", ["xla", "pallas_interpret"])
def test_verify_at_one_token_is_the_decode_step(kernels, kv_quant):
    """The decode window's step is the paged backend at W = 1
    (``runner._decode_core``), so ``verify_step`` at W = 1 and
    ``decode_window`` at one step must write the same pool bytes for live
    slots and pick the same greedy token, on both kernel paths and both pool
    formats. The two differ only where nothing reads: a frozen slot past the
    context clamps onto its own last column in the window and goes to the
    scratch page in verify, so every row here is inside the context (one on
    its last position) and the dead row carries the engine's all-zero page
    row."""
    import numpy as np

    from orion_tpu.infer.kv_cache import init_cache
    from orion_tpu.infer.runner import decode_window, verify_step

    cfg, params = _setup(overrides=[
        f"model.kernels={kernels}", "inference.num_pages=40"] + (
            [f"inference.kv_quant={kv_quant}"] if kv_quant else []))
    mcfg, icfg = cfg.model, cfg.inference
    B, P = icfg.max_batch_size, icfg.max_seq_len // icfg.page_size
    jnp = jax.numpy
    cache = {}
    for i, (name, a) in enumerate(sorted(init_cache(mcfg, icfg).items())):
        key = jax.random.key(10 + i)        # a context that is not zeros
        if a.dtype == jnp.int8:
            cache[name] = jax.random.randint(key, a.shape, -127, 128, a.dtype)
        elif name.endswith("_scale"):
            cache[name] = jax.random.uniform(key, a.shape, a.dtype, .01, .1)
        else:
            cache[name] = jax.random.normal(key, a.shape, a.dtype)
    tokens = jnp.asarray([11, 42, 7, 99], jnp.int32)
    seq_lens = jnp.asarray([5, 17, 3, icfg.max_seq_len - 1], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    pt = np.arange(1, 1 + B * P).reshape(B, P)
    pt[2] = 0
    live = np.concatenate([pt[0], pt[1], pt[3]])
    pt = jnp.asarray(pt, jnp.int32)
    toks, c_dec = decode_window(
        params, dict(cache), tokens, seq_lens, pt, active,
        jax.random.split(jax.random.key(0), 1), 0.0, 0, 1.0, mcfg,
        icfg.max_seq_len)
    _, alt, c_ver = verify_step(
        params, dict(cache), tokens[:, None], seq_lens,
        jnp.ones((B,), jnp.int32), pt, active, jax.random.key(0),
        0.0, 0, 1.0, cfg=mcfg, max_seq_len=icfg.max_seq_len)
    assert sorted(c_dec) == sorted(c_ver) == sorted(cache)
    assert ("k_scale" in cache) == (kv_quant == "int8")
    rows = (np.arange(mcfg.n_layers)[:, None] * icfg.num_pages
            + live[None]).ravel()
    for name in cache:
        got, want = np.asarray(c_ver[name])[rows], np.asarray(c_dec[name])[rows]
        assert (got == want).all(), name
        assert (want != np.asarray(cache[name])[rows]).any(), name
    keep = np.asarray(active)
    assert (np.asarray(toks)[0][keep] == np.asarray(alt)[:, 0][keep]).all()


def test_tree_sample_statistics():
    """Multi-branch rejection sampling preserves the target law: with
    two sibling drafts off the root, the emitted token (first accepted
    sibling, else the all-children-excluded residual) is distributed as
    softmax(logits/T), and elder-sibling rejection feeds the younger's
    renormalized acceptance."""
    import numpy as np

    from orion_tpu.infer.sampling import spec_verify_sample_tree

    V = 8
    logits = jax.random.normal(jax.random.key(2), (1, 3, V)) * 2.0
    temp = 0.7
    p = np.asarray(jax.nn.softmax(np.asarray(logits[0, 0]) / temp))
    order = np.argsort(p)
    c1, c2 = int(order[-2]), int(order[-3])
    tokens = jax.numpy.asarray([[0, c1, c2]], jax.numpy.int32)
    parents = jax.numpy.asarray([[0, 0, 0]], jax.numpy.int32)
    lens = jax.numpy.asarray([3], jax.numpy.int32)
    run = jax.jit(lambda k: spec_verify_sample_tree(
        logits, tokens, parents, lens, k, temperature=temp))
    N = 4000
    keys = jax.random.split(jax.random.key(3), N)
    acc, alt = jax.vmap(run)(keys)
    acc, alt = np.asarray(acc)[:, 0], np.asarray(alt)[:, 0]
    emitted = np.where(acc[:, 1], c1, np.where(acc[:, 2], c2, alt[:, 0]))
    assert abs(acc[:, 1].mean() - p[c1]) < 0.03
    emp = np.bincount(emitted, minlength=V) / N
    assert 0.5 * np.abs(emp - p).sum() < 0.04, (emp, p)
    # The residual never re-emits a rejected sibling.
    rej = ~acc[:, 1] & ~acc[:, 2]
    assert not np.any((alt[rej, 0] == c1) | (alt[rej, 0] == c2))
    # Greedy rows: exact argmax match, at most one sibling accepted.
    ga, galt = spec_verify_sample_tree(
        logits, tokens, parents, lens, jax.random.key(0))
    assert not (np.asarray(ga)[0, 1] and np.asarray(ga)[0, 2])


def test_compact_draft_kv_unit():
    """compact_draft_kv moves exactly the requested (slot, column)
    entries — bitwise, across layers and scale pools — and identity
    columns leave the pool untouched."""
    import numpy as np

    from orion_tpu.infer.kv_cache import compact_draft_kv

    L, NP, K, psz, H, B, W = 2, 8, 2, 4, 8, 2, 4
    rng = np.random.default_rng(0)
    cache = {
        "k": jax.numpy.asarray(
            rng.normal(size=(L * NP, K, psz, H)).astype(np.float32)),
        "k_scale": jax.numpy.asarray(
            rng.normal(size=(L * NP, K, 16)).astype(np.float32)),
    }
    pt = jax.numpy.asarray([[1, 2, 3], [4, 5, 6]], jax.numpy.int32)
    seq = jax.numpy.asarray([3, 5], jax.numpy.int32)   # mid-page cursors
    # Slot 0: accepted path at columns [3, 1] -> dst 1, 2; slot 1 identity.
    src = jax.numpy.asarray([[0, 3, 1, 3], [0, 1, 2, 3]], jax.numpy.int32)
    out = compact_draft_kv(cache, pt, seq, src, n_layers=L, num_pages=NP)
    kin, kout = np.asarray(cache["k"]), np.asarray(out["k"])
    sin, sout = np.asarray(cache["k_scale"]), np.asarray(out["k_scale"])
    for layer in range(L):
        for i, s in [(1, 3), (2, 1), (3, 3)]:
            dpos, spos = 3 + i, 3 + s
            dr = layer * NP + int(pt[0, dpos // psz])
            sr = layer * NP + int(pt[0, spos // psz])
            assert (kout[dr, :, dpos % psz] == kin[sr, :, spos % psz]).all()
            assert (sout[dr, :, dpos % psz] == sin[sr, :, spos % psz]).all()
    # Slot 1 (identity src): bitwise untouched everywhere it owns.
    for layer in range(L):
        for pg in (4, 5, 6):
            r = layer * NP + pg
            assert (kout[r] == kin[r]).all()


def test_rollback_multibranch_footprint_with_prefix_cache():
    """Losing-branch rollback under page sharing: a tree-speculating
    engine with the prefix cache on (shared pages below the cursor,
    private draft pages above) must leave free-list + refcounts pinned
    after every step and exactly the non-spec footprint at drain —
    including a warm second round over donated pages."""
    pc = ["inference.prefix_cache=true", "inference.spec_tree_width=3"]
    cfg_t, params = _setup(overrides=pc)
    cfg_off, _ = _setup(overrides=["inference.prefix_cache=true"],
                        spec=False)
    prompts = MIX + AMBIG
    eng = InferenceEngine(cfg_t, params)
    ref_eng = InferenceEngine(cfg_off, params)
    for round_ in range(2):                  # cold + warm (donated pages)
        assert eng.generate(prompts, 16) == ref_eng.generate(prompts, 16)
        eng.assert_page_accounting()
        for r in eng.slots:
            assert r is None                 # drained
    t = eng.reset_timing()
    assert t["spec_accepted"] > 0 and t["prefix_hits"] >= 1, t


@pytest.mark.slow
def test_tree_mid_chunk_preemption_of_speculating_slot():
    """Pool pressure preempting a tree-speculating slot (its verify
    provisioning triggers the eviction) while another slot chunks its
    prompt: the victim donates only cursor-valid pages — never
    rejected-branch garbage — requeues and resumes byte-identically."""
    ov = ["inference.num_pages=14", "inference.prefix_cache=true",
          "inference.chunked_prefill=true",
          "inference.prefill_chunk_tokens=16",
          "inference.spec_tree_width=3"]
    cfg_t, params = _setup(overrides=ov)
    cfg_off, _ = _setup(
        overrides=["inference.num_pages=14",
                   "inference.chunked_prefill=true",
                   "inference.prefill_chunk_tokens=16"], spec=False)
    prompts = [[(i * 7) % 250 + 1 for i in range(16)],
               [(i * 11) % 250 + 1 for i in range(16)],
               [7, 8, 9] * 5 + [7]]
    new = [60, 60, 60]
    singles = [
        InferenceEngine(cfg_off, params).generate([p], n)[0]
        for p, n in zip(prompts, new)
    ]
    eng = InferenceEngine(cfg_t, params)
    rids = [eng.submit(p, n) for p, n in zip(prompts, new)]
    out = {}
    while eng.has_work():
        for r in eng.step():
            out[r.rid] = r.generated
    assert [out[rid] for rid in rids] == singles
    assert eng.preemptions >= 1
    eng.assert_page_accounting()


@pytest.mark.slow
def test_tree_equivalence_pallas_compositions():
    """Tree speculation x {int8 pools, sliding window, chunked prefill}
    on the pallas verify path: greedy byte-identity against spec-off."""
    for extra in (["inference.kv_quant=int8"],
                  ["model.sliding_window=20"],
                  ["inference.chunked_prefill=true",
                   "inference.prefill_chunk_tokens=16"]):
        cfg_t, params = _setup(
            overrides=PALLAS + extra + ["inference.spec_tree_width=3"])
        cfg_off, _ = _setup(overrides=PALLAS + extra, spec=False)
        ref = InferenceEngine(cfg_off, params).generate(MIX + AMBIG, 16)
        assert InferenceEngine(cfg_t, params).generate(
            MIX + AMBIG, 16) == ref, extra


@pytest.mark.slow
def test_tree_sampled_engine_deterministic():
    """Sampled serving (temperature>0, top_k=1 => argmax-deterministic)
    through the tree rejection-sampling walk: byte-equal to spec-off."""
    sam = ["inference.temperature=0.9", "inference.top_k=1",
           "inference.spec_tree_width=3"]
    cfg_t, params = _setup(overrides=sam)
    cfg_off, _ = _setup(
        overrides=["inference.temperature=0.9", "inference.top_k=1"],
        spec=False)
    a = InferenceEngine(cfg_t, params, seed=5)
    assert a.generate([REP] + AMBIG, 20) == (
        InferenceEngine(cfg_off, params, seed=5).generate([REP] + AMBIG, 20)
    )
    assert a.reset_timing()["spec_accepted"] > 0


@pytest.mark.slow
def test_equivalence_pallas_chunked_prefill():
    """Chunked prefill x speculation on the pallas path: the mixed
    verify step runs flash chunk rows and ragged-kernel verify rows over
    the same carried pool in one dispatch."""
    ch = PALLAS + ["inference.chunked_prefill=true",
                   "inference.prefill_chunk_tokens=16"]
    cfg_on, params = _setup(overrides=ch)
    cfg_off, _ = _setup(overrides=ch, spec=False)

    def run(cfg):
        eng = InferenceEngine(cfg, params)
        out = {}
        eng.submit(REP, 24)
        eng.step()
        eng.step()
        eng.submit(list(range(1, 97)), 4)
        while eng.has_work():
            for r in eng.step():
                out[r.rid] = r.generated
        return out, eng

    got, eng = run(cfg_on)
    ref, _ = run(cfg_off)
    assert got == ref
    t = eng.reset_timing()
    assert t["mixed_steps"] > 0 and t["spec_accepted"] > 0, t
