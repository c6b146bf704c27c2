"""Chunked prefill with mixed prefill+decode scheduling (ISSUE 2).

The load-bearing property is EQUIVALENCE (mirroring the prefix-cache
suite): with inference.chunked_prefill on, served tokens must be
byte-identical to the unchunked engine's, across greedy and sampled
decoding, sliding-window models, prefix-cache-hit rows, and preemption
mid-prompt. Plus the acceptance structure: while any decode is live, NO
whole-prompt prefill dispatch is ever issued — prompt tails ride the
unified mixed step at most prefill_chunk_tokens at a time — and the chunk
counters surface the work.

Sampled byte-identity holds per SAMPLING EVENT (one PRNG split per event):
it is exact when finishing rows sample in the same dispatch grouping as
the unchunked engine's admission burst — a single request chunking alone,
or co-admitted prompts whose tails all complete in the same mixed step
(budget covers them). Interleavings that move a sampled event across
steps draw from a different stream; greedy decoding is schedule-invariant
and is what the mixed-interference tests pin.
"""

import jax
import pytest

from orion_tpu.config import get_config
from orion_tpu.infer import InferenceEngine
from orion_tpu.models import init_params

INFER_OVERRIDES = [
    "inference.max_seq_len=128",
    "inference.page_size=16",
    "inference.num_pages=32",
    "inference.max_batch_size=4",
    "inference.prefill_chunk=16",
    "inference.max_new_tokens=8",
]
CHUNKED = [
    "inference.chunked_prefill=true",
    "inference.prefill_chunk_tokens=16",
]


def _setup(preset="tiny-llama", overrides=(), chunked=True):
    ov = INFER_OVERRIDES + (CHUNKED if chunked else []) + list(overrides)
    cfg = get_config(preset, ov)
    params = init_params(cfg.model, jax.random.key(0))
    return cfg, params


def test_chunked_default_off_and_validation():
    cfg, params = _setup(chunked=False)
    assert cfg.inference.chunked_prefill is False
    eng = InferenceEngine(cfg, params)
    assert eng.chunked is False
    # Budget must be a positive multiple of page_size (page-granular
    # chunking keeps every resumed chunk page-aligned).
    bad, _ = _setup(overrides=["inference.prefill_chunk_tokens=24"])
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        InferenceEngine(bad, params)


def test_equivalence_greedy_mixed_lengths():
    """Prompts shorter than, equal to, and spanning multiple chunk budgets,
    admitted together: chunked tokens byte-identical to unchunked, and the
    chunk counters account for every prompt token (cold: no cache)."""
    cfg_on, params = _setup()
    cfg_off, _ = _setup(chunked=False)
    prompts = [[(i * 7) % 250 + 1 for i in range(21)],
               list(range(2, 32)),
               [7] * 18]
    eng = InferenceEngine(cfg_on, params)
    assert eng.generate(prompts, 6) == (
        InferenceEngine(cfg_off, params).generate(prompts, 6)
    )
    t = eng.reset_timing()
    assert t["mixed_steps"] > 0, t
    assert t["chunk_tokens"] == sum(len(p) for p in prompts), t
    assert t["prefill_chunks"] >= 5, t      # 21 and 30 need >= 2 chunks each


def test_no_whole_prompt_dispatch_while_decoding():
    """The acceptance structure: a long prompt admitted mid-decode never
    triggers a whole-prompt prefill dispatch — every step's prompt-side
    work is bounded by the chunk budget — and the decode stream is still
    byte-identical to the unchunked engine's."""
    cfg_on, params = _setup()
    cfg_off, _ = _setup(chunked=False)
    budget = cfg_on.inference.prefill_chunk_tokens

    def run(cfg, instrument):
        eng = InferenceEngine(cfg, params)
        widths = []
        if instrument:
            assert eng.chunked

            def no_prefill(*args):
                raise AssertionError(
                    "whole-prompt prefill dispatched in chunked mode"
                )

            eng._prefill = no_prefill
        out = {}

        def step():
            eng.reset_timing()
            for r in eng.step():
                out[r.rid] = r.generated
            widths.append(eng.reset_timing()["chunk_tokens"])

        eng.submit([5, 3, 9], 16)
        step()
        step()                             # short request is decoding now
        eng.submit(list(range(1, 97)), 4)  # 96-token long prompt, 6 chunks
        while eng.has_work():
            step()
        return out, widths

    got, widths = run(cfg_on, True)
    ref, _ = run(cfg_off, False)
    assert got == ref
    assert any(w > 0 for w in widths), widths    # the prompt did chunk
    assert max(widths) <= budget, widths


def test_equivalence_sampled():
    """Sampled decoding: a single chunking request (one finishing row,
    aligned sampling events) and co-admitted short prompts finishing in
    the SAME mixed step must match the unchunked engine byte-for-byte."""
    sam = ["inference.temperature=0.9", "inference.top_k=40"]
    cfg_on, params = _setup(overrides=sam)
    cfg_off, _ = _setup(overrides=sam, chunked=False)
    single = [[(i * 11) % 250 + 1 for i in range(37)]]
    assert InferenceEngine(cfg_on, params, seed=7).generate(single, 6) == (
        InferenceEngine(cfg_off, params, seed=7).generate(single, 6)
    )
    # Two 16-token prompts with a 32-token budget: both tails complete in
    # one mixed step -> one sample call over rows [0, 1], as unchunked.
    cfg_on32, _ = _setup(
        overrides=sam + ["inference.prefill_chunk_tokens=32"])
    pair = [[(i * 5) % 250 + 1 for i in range(16)],
            [(i * 3) % 250 + 1 for i in range(16)]]
    assert InferenceEngine(cfg_on32, params, seed=3).generate(pair, 6) == (
        InferenceEngine(cfg_off, params, seed=3).generate(pair, 6)
    )


def test_equivalence_sliding_window():
    """SWA: later chunks READ window-distant positions from the pool
    (chunked admission keeps every logical page live and rolls them with
    the chunk cursor) — tokens must equal the unchunked engine's past the
    window."""
    swa = ["model.sliding_window=20"]
    cfg_on, params = _setup(overrides=swa)
    cfg_off, _ = _setup(overrides=swa, chunked=False)
    prompts = [[(i * 13) % 250 + 1 for i in range(21)]]
    assert InferenceEngine(cfg_on, params).generate(prompts, 12) == (
        InferenceEngine(cfg_off, params).generate(prompts, 12)
    )


def test_equivalence_prefix_cache_rows():
    """Chunked x prefix cache: warm rows start their chunk cursor past the
    matched pages (chunk 1 == the warm tail prefill), cold rows chunk from
    zero, and both rounds stay byte-identical to the unchunked cache-on
    engine — with the cached tokens never re-chunked."""
    pc = ["inference.prefix_cache=true"]
    cfg_on, params = _setup(overrides=pc)
    cfg_off, _ = _setup(overrides=pc, chunked=False)
    prompts = [[(i * 7) % 250 + 1 for i in range(21)], list(range(1, 33))]
    eng_on = InferenceEngine(cfg_on, params)
    eng_off = InferenceEngine(cfg_off, params)
    assert eng_on.generate(prompts, 6) == eng_off.generate(prompts, 6)
    eng_on.reset_timing()
    assert eng_on.generate(prompts, 6) == eng_off.generate(prompts, 6)
    t = eng_on.reset_timing()
    assert t["prefix_hits"] >= 1, t
    # Warm round: matched pages are never re-chunked, so the chunked token
    # tally stays below the raw prompt total.
    assert t["chunk_tokens"] < sum(len(p) for p in prompts), t


def test_equivalence_preemption_mid_prompt():
    """Pool pressure preempts the youngest request while its prompt is
    still chunking: it must donate its completed chunks, requeue, resume,
    and still produce single-request tokens exactly.

    The scenario engineers the pressure to land mid-prompt: three older
    decoders whose page-boundary crossings are staggered to fall while
    the 96-token prompt is still consuming its 16-token chunks (the
    admission spare absorbs the first two crossings; the third finds the
    pool empty and evicts the youngest — the chunking request)."""
    ov = ["inference.num_pages=15", "inference.decode_window=1"]
    cfg_on, params = _setup(overrides=ov)
    cfg_off, _ = _setup(overrides=ov, chunked=False)
    shorts = [
        [(i * 7) % 250 + 1 for i in range(13)],
        [(i * 11) % 250 + 1 for i in range(29)],
        [(i * 13) % 250 + 1 for i in range(45)],
    ]
    p_long = [(i * 17) % 250 + 1 for i in range(96)]
    prompts = shorts + [p_long]
    new = [16, 16, 16, 4]
    singles = [
        InferenceEngine(cfg_off, params).generate([p], n)[0]
        for p, n in zip(prompts, new)
    ]
    eng = InferenceEngine(cfg_on, params)
    preempted_mid_prompt = []
    orig = eng._preempt

    def spy(req):
        preempted_mid_prompt.append(req.prefill_pending)
        orig(req)

    eng._preempt = spy
    rids = [eng.submit(p, n) for p, n in zip(prompts, new)]
    out = {}
    while eng.has_work():
        for r in eng.step():
            out[r.rid] = r.generated
    assert [out[rid] for rid in rids] == singles
    assert preempted_mid_prompt, "scenario failed to exercise preemption"
    assert any(preempted_mid_prompt), (
        "no preemption landed mid-prompt (chunk cursor interplay untested)"
    )


def test_scoring_and_zero_token_requests():
    """max_new_tokens=0 scoring rides the chunk path (prefill-only, no
    sampled token, no decode slot) and still completes."""
    cfg_on, params = _setup()
    eng = InferenceEngine(cfg_on, params)
    assert eng.generate([[1, 2, 3], list(range(1, 40))], 0) == [[], []]
    t = eng.reset_timing()
    assert t["chunk_tokens"] == 3 + 39, t
    assert t["slot_steps"] == 0, t          # never decoded


def test_pallas_path_mixed_step():
    """The unified mixed step on the Pallas path (flash chunk rows +
    fused-write ragged paged decode rows in one program, interpret mode)
    must produce the xla chunked engine's tokens."""
    import dataclasses

    cfg, params = _setup()
    pcfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, kernels="pallas_interpret")
    )
    prompts = [[5, 3, 9, 250, 17], list(range(1, 25))]
    ref = InferenceEngine(cfg, params).generate(prompts, 5)
    out = InferenceEngine(pcfg, params).generate(prompts, 5)
    assert out == ref


def test_latency_bench_smoke():
    """tools/serving_latency_bench.py --smoke (the tier-1 wiring): the
    structural stall bound holds — no whole-prompt dispatch while decodes
    are live, per-step chunk tokens within budget — where the unchunked
    engine does dispatch a whole prompt beside live decodes. Counts only:
    the tool's ``chunked_p99_below_unchunked`` orders two p99s of CPU
    clocks taken beside five other xdist workers, which is not a
    measurement; both are held finite and positive."""
    import json
    import math
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "serving_latency_bench.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    verdict = lines[-1]
    assert verdict["stall_bounded"] is True, lines
    assert verdict["unchunked_live_prefill_tokens"] > 0, lines
    by_mode = {d["mode"]: d for d in lines[:-1]}
    assert by_mode["chunked"]["max_live_prefill_dispatch_tokens"] == 0
    for d in by_mode.values():
        assert math.isfinite(d["itl_p99_ms"]) and d["itl_p99_ms"] > 0, d
