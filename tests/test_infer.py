"""Inference-tier tests (SURVEY.md §5): the continuous-batching engine fed
request mixes must produce exactly the tokens of single-request generation,
and the paged KV cache must recycle pages."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import get_config
from orion_tpu.infer import InferenceEngine
from orion_tpu.infer.sampling import sample
from orion_tpu.models import forward, init_params

INFER_OVERRIDES = [
    "inference.max_seq_len=128",
    "inference.page_size=16",
    "inference.num_pages=32",
    "inference.max_batch_size=4",
    "inference.prefill_chunk=16",
    "inference.max_new_tokens=8",
]


def _setup(preset="tiny-llama", overrides=()):
    cfg = get_config(preset, INFER_OVERRIDES + list(overrides))
    params = init_params(cfg.model, jax.random.key(0))
    return cfg, params


def _ref_generate(params, mcfg, prompt, n):
    """Autoregressive greedy generation via the full training forward."""
    toks = list(prompt)
    for _ in range(n):
        logits, _ = forward(params, jnp.asarray([toks], jnp.int32), mcfg)
        toks.append(int(jnp.argmax(logits[0, len(toks) - 1])))
    return toks[len(prompt):]


@pytest.mark.parametrize(
    "preset", ["tiny-llama", "tiny", "tiny-mixtral", "tiny-gemma2"]
)
def test_engine_matches_full_forward(preset):
    """Paged-cache decode must reproduce the no-cache forward exactly
    (greedy), across the model zoo: RoPE/GQA, learned-pos/LayerNorm, MoE,
    and Gemma-2's interleaved local/global windows + post-norms + dual
    softcaps (full-context pages with per-layer masks)."""
    cfg, params = _setup(preset)
    prompt = [5, 3, 9, 250, 17]
    ref = _ref_generate(params, cfg.model, prompt, 8)
    out = InferenceEngine(cfg, params).generate([prompt], 8)[0]
    assert out == ref


@pytest.mark.parametrize(
    "preset", ["tiny-llama", "tiny-mixtral", "tiny-gemma2", "tiny-laguna"]
)
def test_block_body_with_a_plain_attend_is_the_forward(preset):
    """The seam every caller of ``transformer.block`` stands on: the one
    layer body, handed nothing but ``attend(q, k, v) -> (out, state)`` (here
    plain causal attention, no cache), layer by layer through the runner's
    scan, IS the training forward's hidden states — over one kind of layer,
    MoE, Gemma-2's window pattern + post-norms + softcap, and a layer plan
    of unlike head counts, rotary tables and a head gate."""
    from orion_tpu.infer import runner
    from orion_tpu.models import transformer as T
    from orion_tpu.ops.attention import attention_xla

    m = get_config(preset).model
    params = init_params(m, jax.random.key(2))
    toks = jax.random.randint(jax.random.key(3), (2, 24), 1, m.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))
    seen = []

    def body(x, bp, l, j, stack=None):
        def attend(q, k, v):
            return attention_xla(
                q, k, v, causal=True, window=m.layer_window(j),
                logit_softcap=m.attn_logit_softcap), j

        x, aux, state = T.block(
            x, bp, m, positions, attend, kind=runner._kind(m, j))
        seen.append(state)
        assert aux.shape == ()
        return x

    got = runner._scan_layers(
        params, m, body, T.embed(params, toks, positions, m))
    want, _ = T._hidden_states(params, toks, m)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert len(seen) >= 1 and all(isinstance(j, int) for j in seen)


def test_gemma2_engine_pallas_matches_xla_beyond_window():
    """Gemma-2 serving on the Pallas path (flash prefill + ragged paged
    decode with PER-LAYER windows through the grouped layer scan,
    interpret mode) must produce the xla path's tokens — generating PAST
    the sliding window, the hard case for the paged kernel's window/page
    clamp when full-context pages are kept for the global layers."""
    import dataclasses

    cfg, params = _setup("tiny-gemma2")
    prompt = [5, 3, 9, 250, 17]
    n = 24                                  # context 29 >> window 16
    ref = InferenceEngine(cfg, params).generate([prompt], n)[0]
    pcfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, kernels="pallas_interpret")
    )
    out = InferenceEngine(pcfg, params).generate([prompt], n)[0]
    assert out == ref


# slow (tier-1 budget, round 8): softcap serving stays pinned in
# tier-1 by test_gemma2_engine_pallas_matches_xla_beyond_window and
# test_engine_matches_full_forward[tiny-gemma2].
@pytest.mark.slow
def test_gemma2_engine_softcap_regime():
    """Serving must apply the attention logit softcap (regression: prefill
    and the xla decode fallback silently omitted it). Tiny random weights
    never reach the cap, so scale the q/k projections until logits live in
    the tanh-saturating regime — engine tokens must still equal the
    training forward's."""
    import jax.numpy as jnp

    cfg, params = _setup("tiny-gemma2")
    boost = jnp.asarray(6.0, params["blocks"]["attn"]["wq"].dtype)
    params = dict(params)
    params["blocks"] = jax.tree.map(lambda x: x, params["blocks"])
    params["blocks"]["attn"] = dict(params["blocks"]["attn"])
    params["blocks"]["attn"]["wq"] = params["blocks"]["attn"]["wq"] * boost
    params["blocks"]["attn"]["wk"] = params["blocks"]["attn"]["wk"] * boost
    prompt = [5, 3, 9, 250, 17]
    ref = _ref_generate(params, cfg.model, prompt, 8)
    out = InferenceEngine(cfg, params).generate([prompt], 8)[0]
    assert out == ref


@pytest.mark.slow  # 24 full-forward reference decodes, ~40s on the CPU tier
def test_gemma2_engine_beyond_window():
    """Gemma-2 serving past the sliding window: local layers mask to the
    last W positions while global layers read the whole history (pages
    must NOT roll — page_window is None under a pattern); still exactly
    reproduces the full forward."""
    cfg, params = _setup("tiny-gemma2")
    eng = InferenceEngine(cfg, params)
    assert eng.page_window is None          # full-context pages kept
    prompt = [5, 3, 9, 250, 17]
    n = 24                                  # context 29 >> window 16
    ref = _ref_generate(params, cfg.model, prompt, n)
    out = eng.generate([prompt], n)[0]
    assert out == ref


def test_engine_pallas_kernels_match_xla():
    """The full serving path on Pallas kernels (flash prefill + ragged paged
    decode, interpret mode on CPU) must produce the xla path's tokens."""
    cfg, params = _setup()
    import dataclasses

    pcfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, kernels="pallas_interpret")
    )
    prompt = [5, 3, 9, 250, 17]
    ref = InferenceEngine(cfg, params).generate([prompt], 6)[0]
    out = InferenceEngine(pcfg, params).generate([prompt], 6)[0]
    assert out == ref


def test_continuous_batching_preserves_outputs():
    """Batched serving (with queueing beyond max_batch_size) must not change
    any request's tokens."""
    cfg, params = _setup()
    prompts = [
        [5, 3, 9],
        [250, 17, 4, 8, 100, 42],
        [7] * 20,
        [1, 2],
        [99, 98, 97, 96],
        [11, 13, 17, 19, 23],
    ]  # 6 requests > max_batch_size=4 forces admission queueing
    singles = [
        InferenceEngine(cfg, params).generate([p], 6)[0] for p in prompts
    ]
    batched = InferenceEngine(cfg, params).generate(prompts, 6)
    assert batched == singles


def test_mid_flight_admission():
    """A request submitted while another is decoding joins the batch without
    disturbing either result."""
    cfg, params = _setup()
    p1, p2 = [5, 3, 9, 250, 17], [42, 7]
    ref1 = InferenceEngine(cfg, params).generate([p1], 8)[0]
    ref2 = InferenceEngine(cfg, params).generate([p2], 8)[0]

    eng = InferenceEngine(cfg, params)
    eng.submit(p1, 8)
    finished = []
    finished += eng.step()
    finished += eng.step()
    eng.submit(p2, 8)
    while eng.has_work():
        finished += eng.step()
    by_rid = sorted(finished, key=lambda r: r.rid)
    assert [r.generated for r in by_rid] == [ref1, ref2]


def test_sharded_engine_matches_unsharded():
    """The engine is mesh-agnostic (the params' shardings decide): serving
    with tp-sharded params over the fake 8-CPU-device mesh must produce the
    unsharded engine's exact tokens (VERDICT r2: sharded inference was
    untested)."""
    from orion_tpu.config import ParallelConfig
    from orion_tpu.models.transformer import param_logical_axes
    from orion_tpu.parallel.sharding import param_shardings
    from orion_tpu.runtime import build_mesh

    cfg, params = _setup()
    prompt = [5, 3, 9, 250, 17]
    ref = InferenceEngine(cfg, params).generate([prompt], 6)[0]

    mesh = build_mesh(
        ParallelConfig(tp=2, dp=2), devices=jax.devices("cpu")[:4]
    )
    shardings = param_shardings(mesh, param_logical_axes(cfg.model))
    sharded = jax.device_put(params, shardings)
    out = InferenceEngine(cfg, sharded).generate([prompt], 6)[0]
    assert out == ref


@pytest.mark.parametrize("kv_quant", [
    None,
    # slow (tier-1 budget, round 8): tp x pallas stays in tier-1 via
    # the None variant; the int8 cross runs in the slow tier.
    pytest.param("int8", marks=pytest.mark.slow),
])
def test_sharded_engine_pallas_matches_unsharded(kv_quant):
    """Serving on the PALLAS path with tp-sharded params (VERDICT r4
    missing #3): flash prefill and the ragged paged decode kernel run
    under head-sharded shard_maps (a bare pallas_call would gather the
    tp-sharded operands), the KV pool lives sharded over kv heads, and
    the served tokens equal the unsharded engine's exactly — including
    the int8 scale pools riding the same sharding."""
    import dataclasses

    from orion_tpu.config import ParallelConfig
    from orion_tpu.models.transformer import param_logical_axes
    from orion_tpu.parallel.sharding import param_shardings
    from orion_tpu.runtime import build_mesh

    overrides = [] if kv_quant is None else [f"inference.kv_quant={kv_quant}"]
    cfg, params = _setup(overrides=overrides)
    pcfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, kernels="pallas_interpret")
    )
    prompts = [[5, 3, 9, 250, 17], [42, 7]]
    ref = InferenceEngine(pcfg, params).generate(prompts, 6)

    mesh = build_mesh(
        ParallelConfig(tp=2, dp=2), devices=jax.devices("cpu")[:4]
    )
    shardings = param_shardings(mesh, param_logical_axes(cfg.model))
    sharded = jax.device_put(params, shardings)
    eng = InferenceEngine(pcfg, sharded)
    assert eng.mesh is not None              # tp mesh detected from params
    k_shard = eng.cache["k"].sharding
    assert k_shard.spec[1] == "tp"           # pool sharded over kv heads
    out = eng.generate(prompts, 6)
    assert out == ref


# slow (tier-1 budget, round 8): the unsharded gemma2-beyond-window
# and the sharded llama engines keep both halves of this composition
# in tier-1; the full cross stays in the slow tier.
@pytest.mark.slow
def test_sharded_engine_pallas_gemma2_beyond_window():
    """The hardest serving composition: tp-sharded params x Pallas kernels
    x Gemma-2's interleaved per-layer windows, generating PAST the sliding
    window — the paged kernel's window/page clamp and the flash prefill's
    per-layer masks must hold under the head-sharded shard_map exactly as
    unsharded."""
    import dataclasses

    from orion_tpu.config import ParallelConfig
    from orion_tpu.models.transformer import param_logical_axes
    from orion_tpu.parallel.sharding import param_shardings
    from orion_tpu.runtime import build_mesh

    cfg, params = _setup("tiny-gemma2")
    pcfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, kernels="pallas_interpret")
    )
    prompt = [5, 3, 9, 250, 17]
    n = 24                                   # context 29 >> window 16
    ref = InferenceEngine(pcfg, params).generate([prompt], n)[0]

    mesh = build_mesh(ParallelConfig(tp=2), devices=jax.devices("cpu")[:2])
    shardings = param_shardings(mesh, param_logical_axes(cfg.model))
    sharded = jax.device_put(params, shardings)
    out = InferenceEngine(pcfg, sharded).generate([prompt], n)[0]
    assert out == ref


def test_sharded_engine_pallas_rejects_indivisible_heads():
    """tp that does not divide the kv heads must fail loudly at engine
    construction, not silently gather or miscompute."""
    import dataclasses

    from orion_tpu.config import ParallelConfig
    from orion_tpu.models.transformer import param_logical_axes
    from orion_tpu.parallel.sharding import param_shardings
    from orion_tpu.runtime import build_mesh

    cfg, params = _setup()                  # tiny-llama: K=2 kv heads
    pcfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, kernels="pallas_interpret")
    )
    mesh = build_mesh(ParallelConfig(tp=4), devices=jax.devices("cpu")[:4])
    axes = param_logical_axes(cfg.model)
    try:
        shardings = param_shardings(mesh, axes)
        sharded = jax.device_put(params, shardings)
    except Exception:
        pytest.skip("tp=4 param sharding itself rejects this tiny model")
    with pytest.raises(ValueError, match="divisible"):
        InferenceEngine(pcfg, sharded)


def test_burst_admission_prefills_in_one_dispatch():
    """A burst of same-bucket admissions must be served by ONE batched
    prefill dispatch, not one per prompt (VERDICT r2 item 4)."""
    cfg, params = _setup()
    eng = InferenceEngine(cfg, params)
    calls = []
    orig = eng._prefill

    def counting(*args):
        calls.append(args[2].shape)  # tokens [Nb, S_pad]
        return orig(*args)

    eng._prefill = counting
    prompts = [[5, 3, 9], [1, 2], [7, 8, 9, 10], [4]]
    for p in prompts:
        eng.submit(p, 4)
    eng.step()
    assert len(calls) == 1, calls
    assert calls[0][0] == 4, calls  # all four prompts in one batch


# slow (tier-1 budget, round 8): the one-ragged-dispatch admission
# shape is also asserted (xla side) by
# test_mixed_length_burst_xla_keeps_per_bucket_dispatches.
@pytest.mark.slow
def test_mixed_length_burst_prefills_in_one_ragged_dispatch():
    """On the pallas path, prompts spanning DIFFERENT buckets admit in a
    single ragged prefill dispatch (VERDICT r3 item 7): rows pad to the
    burst max, padding blocks skip via segment ids, and outputs equal
    single-request generation. The xla path keeps per-bucket dispatches
    (it has no block skip, so a short row would pay burst-max O(S^2))."""
    cfg, params = _setup(
        overrides=["model.kernels=pallas_interpret"])
    eng = InferenceEngine(cfg, params)
    calls = []
    orig = eng._prefill

    def counting(*args):
        calls.append(args[2].shape)  # tokens [Nb, S_pad]
        return orig(*args)

    eng._prefill = counting
    prompts = [[5, 3, 9], list(range(1, 21)), list(range(7, 47))]
    rids = [eng.submit(p, 4) for p in prompts]
    done = list(eng.step())
    assert len(calls) == 1, calls            # one dispatch, three buckets
    assert calls[0][1] == 48                 # burst max bucket (40 -> 48)
    while eng.has_work():
        done += eng.step()
    out = {r.rid: list(r.generated) for r in done}
    for p, rid in zip(prompts, rids):
        solo = InferenceEngine(cfg, params).generate([p], 4)[0]
        assert out[rid][:4] == solo


def test_mixed_length_burst_xla_keeps_per_bucket_dispatches():
    cfg, params = _setup()          # default kernels: xla
    eng = InferenceEngine(cfg, params)
    calls = []
    orig = eng._prefill

    def counting(*args):
        calls.append(args[2].shape)
        return orig(*args)

    eng._prefill = counting
    for p in [[5, 3, 9], list(range(1, 21)), list(range(7, 47))]:
        eng.submit(p, 2)
    eng.step()
    assert len(calls) == 3, calls   # one dispatch per bucket (16/32/48)
    assert sorted(c[1] for c in calls) == [16, 32, 48]


@pytest.mark.parametrize("window", [1, 2, 16])
def test_greedy_stream_does_not_depend_on_the_decode_window(window):
    """Greedy decode is window-size invariant: a window of one token, one
    that divides the budget and one past it (decoding beyond the end) all
    serve the default window's tokens, for rows that end on different
    steps; the engine keeps the configured window for its life and
    reports it with the timing drain."""
    prompts = [[5, 3, 9, 250, 17], [7, 7, 2], list(range(1, 21))]

    def serve(cfg):
        eng = InferenceEngine(cfg, params)
        rids = [eng.submit(p, n) for p, n in zip(prompts, (8, 3, 6))]
        out = {}
        while eng.has_work():
            for r in eng.step():
                out[r.rid] = r.generated
            assert eng.decode_window == cfg.inference.decode_window
        return [out[i] for i in rids], eng.reset_timing()

    cfg, params = _setup()
    wcfg, _ = _setup(overrides=[f"inference.decode_window={window}"])
    ref, _ = serve(cfg)
    assert [len(g) for g in ref] == [8, 3, 6]
    got, t = serve(wcfg)
    assert got == ref
    assert t["decode_window"] == window
    assert t["prefill_s"] > 0.0     # admission burst has its own bucket


def test_wasted_decode_fraction_pinned_mixed_lengths():
    """The device/host split now carries the decode-waste tally: at a mixed
    max_new_tokens trace with W=8, the slot finishing after 1 decoded token
    burns exactly W-1 garbage steps and the full-length slot burns the
    post-EOS remainder — pinned, so the decode_window tradeoff is
    observable data (VERDICT r4 weak #6)."""
    cfg, params = _setup()       # decode_window=8 via INFER_OVERRIDES? no:
    assert cfg.inference.decode_window == 8
    eng = InferenceEngine(cfg, params)
    eng.submit([5, 3, 9], 2)     # 1 prefill token + 1 decode -> done at j=0
    eng.submit([42, 7], 8)       # 1 prefill + 7 decode -> done at j=6
    while eng.has_work():
        eng.step()
    t = eng.reset_timing()
    assert t["slot_steps"] == 16, t         # one window, two active slots
    assert t["wasted_steps"] == 8, t        # 7 (short slot) + 1 (tail)


def test_eos_stops_generation():
    cfg, params = _setup()
    prompt = [5, 3, 9]
    free_run = InferenceEngine(cfg, params).generate([prompt], 8)[0]
    eos = free_run[2]  # treat the 3rd generated token as EOS
    out = InferenceEngine(cfg, params, eos_id=eos).generate([prompt], 8)[0]
    assert out == free_run[:3]


def test_pages_recycled_and_pool_exhaustion_queues():
    cfg, params = _setup()
    eng = InferenceEngine(cfg, params)
    eng.generate([[7] * 20, [1, 2, 3], [4, 5]], 6)
    assert eng.alloc.free_pages == cfg.inference.num_pages - 1  # page 0 scratch

    # A prompt longer than the context window is rejected at submit.
    with pytest.raises(ValueError):
        eng.submit([1] * 200, 4)


def test_oversized_prompt_rejected_at_submit():
    """A prompt whose pages can never fit the pool raises instead of
    queueing forever."""
    cfg, params = _setup(overrides=["inference.num_pages=4"])
    eng = InferenceEngine(cfg, params)
    with pytest.raises(ValueError, match="pages"):
        eng.submit([1] * 40, 4)


def test_bad_sampling_overrides_rejected_at_submit():
    """Out-of-range per-request sampling params raise at submit() instead of
    silently clamping / degenerating mid-decode."""
    cfg, params = _setup()
    eng = InferenceEngine(cfg, params)
    with pytest.raises(ValueError, match="temperature"):
        eng.submit([1, 2, 3], 2, temperature=-0.5)
    with pytest.raises(ValueError, match="top_k"):
        eng.submit([1, 2, 3], 2, top_k=cfg.model.vocab_size + 1)
    with pytest.raises(ValueError, match="top_k"):
        eng.submit([1, 2, 3], 2, top_k=-1)
    with pytest.raises(ValueError, match="top_p"):
        eng.submit([1, 2, 3], 2, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        eng.submit([1, 2, 3], 2, top_p=1.5)
    # In-range values still queue.
    eng.submit([1, 2, 3], 2, temperature=0.7, top_k=0, top_p=1.0)


def test_default_valued_overrides_stay_on_fast_program():
    """Explicitly passing the engine-default sampling values is normalized to
    'no override': the batch must keep the specialized greedy decode program
    (no sort-based sampling switch)."""
    cfg, params = _setup()
    eng = InferenceEngine(cfg, params)
    icfg = cfg.inference
    rid = eng.submit([1, 2, 3], 2, temperature=icfg.temperature,
                     top_k=icfg.top_k, top_p=icfg.top_p)
    req = eng.waiting[-1]
    assert req.rid == rid
    assert req.temperature is None and req.top_k is None and req.top_p is None


def test_kv_int8_xla_and_pallas_paths_agree():
    """Under inference.kv_quant=int8 the xla gather path and the pallas
    in-kernel path quantize identically (same symmetric per-token-per-head
    rule), so the served tokens must match exactly."""
    cfg, params = _setup(overrides=["inference.kv_quant=int8"])
    import dataclasses

    prompt = [5, 3, 9, 250, 17]
    out_x = InferenceEngine(cfg, params).generate([prompt], 8)[0]
    pcfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, kernels="pallas_interpret")
    )
    out_p = InferenceEngine(pcfg, params).generate([prompt], 8)[0]
    assert out_x == out_p


def test_kv_int8_tracks_unquantized_generation():
    """int8 KV is ~1% per-element error; on a random tiny model the greedy
    argmax stream should track the unquantized engine for at least the
    first tokens (and must run, recycle pages, and stay finite)."""
    cfg, params = _setup()
    qcfg, _ = _setup(overrides=["inference.kv_quant=int8"])
    ref = InferenceEngine(cfg, params).generate([[5, 3, 9, 250, 17]], 6)[0]
    got = InferenceEngine(qcfg, params).generate([[5, 3, 9, 250, 17]], 6)[0]
    assert len(got) == len(ref)
    assert got[0] == ref[0]  # first decode step off the prefill cache


def test_kv_int8_batched_serving_and_page_recycling():
    """Continuous batching + preemption machinery is cache-layout agnostic:
    a full mixed workload serves under kv_quant=int8 and outputs equal
    single-request generation (batching invariance holds quantized)."""
    cfg, params = _setup(overrides=["inference.kv_quant=int8"])
    eng = InferenceEngine(cfg, params)
    prompts = [[5, 3, 9], [250, 17], [1, 2, 3, 4, 5, 6, 7]]
    batched = eng.generate(prompts, 6)
    for p, want in zip(prompts, batched):
        solo = InferenceEngine(cfg, params).generate([p], 6)[0]
        assert solo == want


def test_kv_int8_rejects_large_pages():
    """One lane tile holds one page's scales: page_size > 128 must raise
    clearly at engine construction, not fail inside the kernel."""
    cfg, params = _setup(overrides=["inference.kv_quant=int8",
                                    "inference.max_seq_len=512",
                                    "inference.page_size=256",
                                    "inference.prefill_chunk=256"])
    with pytest.raises(ValueError, match="page_size"):
        InferenceEngine(cfg, params)


def test_step_timing_accounting_sums():
    """The device/host step-time split must account for the measured wall
    time: device_s + host_s == sum of step() durations (to timer noise),
    windows counts only decoding steps, and reset zeroes it."""
    import time as _time

    cfg, params = _setup()
    eng = InferenceEngine(cfg, params)
    eng.submit([5, 3, 9], 6)
    t0 = _time.perf_counter()
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
    wall = _time.perf_counter() - t0
    t = eng.reset_timing()
    assert t["steps"] == steps
    assert 0 < t["windows"] <= steps
    assert t["device_s"] > 0 and t["host_s"] > 0
    assert t["prefill_s"] > 0               # admission burst, own bucket
    total = t["device_s"] + t["host_s"] + t["prefill_s"]
    # The split partitions each step's wall time exactly (the identity is
    # tests/test_phases.py's, on the steps' own spans); the steps lie
    # inside this loop, so their sum cannot exceed its clock.
    assert total <= wall
    # Idle step (no work): counts a step, no window, negligible device.
    eng.step()
    t2 = eng.reset_timing()
    assert t2["steps"] == 1 and t2["windows"] == 0
    assert t2["device_s"] == 0.0


def test_preemption_under_pool_pressure():
    """When concurrent decodes exhaust the page pool, the youngest request
    is preempted, re-prefilled from its context later, and still produces
    exactly the single-request tokens."""
    cfg, params = _setup(overrides=["inference.num_pages=8"])
    # 7 usable pages of 16 tokens; two requests decoding from 15-token
    # prompts out to 15+50=65 tokens each want 5 pages apiece at the end —
    # more than the pool — so at least one preemption must happen.
    prompts = [[5, 3, 9, 250, 17, 8, 100, 42, 77, 31, 2, 6, 90, 55, 21],
               [7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47, 53, 59, 61]]
    singles = [
        InferenceEngine(cfg, params).generate([p], 50)[0] for p in prompts
    ]
    eng = InferenceEngine(cfg, params)
    batched = eng.generate(prompts, 50)
    assert eng.preemptions > 0, "scenario failed to exercise preemption"
    assert batched == singles


def test_max_new_tokens_zero_is_prefill_only():
    cfg, params = _setup()
    assert InferenceEngine(cfg, params).generate([[1, 2, 3]], 0) == [[]]


@pytest.mark.slow
def test_long_generation_allocates_pages_on_demand():
    """Crossing page boundaries mid-decode allocates new pages and keeps
    matching the reference.

    slow (tier-1 budget, round 8): the 20-token reference forward makes
    this the single heaviest infer test (~37s CPU); page-on-demand growth
    stays pinned in tier-1 by the spec-decode rollback suite
    (test_spec_decode.test_rollback_state_exact walks the page footprint
    every step)."""
    cfg, params = _setup()
    prompt = [5, 3, 9, 250, 17, 8, 100, 42, 77, 31, 2, 6, 90, 55, 21]  # 15
    n = 20  # crosses the 16-token page boundary twice
    ref = _ref_generate(params, cfg.model, prompt, n)
    out = InferenceEngine(cfg, params).generate([prompt], n)[0]
    assert out == ref


# -- sampling ---------------------------------------------------------------


def test_sample_greedy_is_argmax():
    logits = jnp.asarray([[0.1, 3.0, -1.0], [2.0, 0.0, 5.0]])
    toks = sample(logits, jax.random.key(0), temperature=0.0)
    assert toks.tolist() == [1, 2]


def test_sample_top_k_restricts_support():
    logits = jnp.asarray([[5.0, 4.0, -10.0, -10.0]])
    for s in range(20):
        t = sample(logits, jax.random.key(s), temperature=1.0, top_k=2)
        assert int(t[0]) in (0, 1)


def test_sample_top_p_restricts_support():
    logits = jnp.asarray([[10.0, 9.0, -10.0, -10.0]])
    for s in range(20):
        t = sample(logits, jax.random.key(s), temperature=1.0, top_p=0.9)
        assert int(t[0]) in (0, 1)


# slow (tier-1 budget, round 8): cumulative admission headroom is
# also exercised in tier-1 by test_chunked_prefill's mid-prompt
# preemption scenario and test_spec_decode's rollback-footprint walk.
@pytest.mark.slow
def test_admission_burst_reserves_decode_headroom():
    """A multi-request admission burst must account for every admitted
    request's first-decode-window headroom cumulatively: over-committing let
    _grow_pages preempt the OLDEST request in the very step it prefilled
    (discarding its work). With the reservation, the second request simply
    waits and nobody is preempted."""
    cfg, params = _setup(overrides=[
        "inference.num_pages=8",        # 7 usable; first_window=5 per req
        "inference.decode_window=64",
        "inference.max_new_tokens=8",
    ])
    eng = InferenceEngine(cfg, params)
    prompts = [[(i * 7 + j) % 250 + 1 for j in range(16)] for i in range(2)]
    refs = [_ref_generate(params, cfg.model, p, 8) for p in prompts]
    outs = eng.generate(prompts, 8)
    assert outs == refs
    assert eng.preemptions == 0, (
        f"admission burst over-committed the pool ({eng.preemptions} "
        "preemptions)"
    )


def test_stream_matches_generate():
    """stream() yields exactly generate()'s tokens, incrementally, in
    window-sized chunks, ending each request exactly once."""
    cfg, params = _setup(overrides=["inference.decode_window=2"])
    prompts = [[5, 3, 9, 250, 17], [7, 7, 2]]
    want = InferenceEngine(cfg, params).generate(prompts, 8)

    eng = InferenceEngine(cfg, params)
    got: dict[int, list[int]] = {}
    chunks = 0
    for rid, toks in eng.stream(prompts, 8):
        assert toks, "empty yield"
        got.setdefault(rid, []).extend(toks)
        chunks += 1
    rids = sorted(got)
    assert [got[r] for r in rids] == want
    assert chunks > len(prompts)  # incremental, not one-shot


def test_stream_zero_token_requests_still_announced():
    """max_new_tokens=0 (scoring) requests yield exactly one empty chunk so
    consumers can realign outputs with prompts."""
    cfg, params = _setup()
    eng = InferenceEngine(cfg, params)
    events = list(eng.stream([[5, 3], [7, 1, 2]], 0))
    assert sorted(r for r, _ in events) == sorted(set(r for r, _ in events))
    assert len(events) == 2
    assert all(toks == [] for _, toks in events)


def test_per_request_sampling_params():
    """Per-request sampling (vLLM-style): a greedy request batched with a
    hot-temperature request still reproduces its single-request greedy
    tokens; the sampled request draws different, valid tokens."""
    cfg, params = _setup()  # config default temperature=0 (greedy)
    p_greedy, p_hot = [5, 3, 9, 250, 17], [7, 11, 2]
    ref = InferenceEngine(cfg, params).generate([p_greedy], 8)[0]

    eng = InferenceEngine(cfg, params)
    eng.submit(p_greedy, 8)
    eng.submit(p_hot, 8, temperature=1.0, top_k=50)
    done = []
    while eng.has_work():
        done += eng.step()
    by_rid = sorted(done, key=lambda r: r.rid)
    assert by_rid[0].generated == ref
    hot = by_rid[1].generated
    assert len(hot) == 8
    assert all(0 <= t < cfg.model.vocab_size for t in hot)


def test_sample_per_row_matches_scalar():
    """The vectorized per-row sampler equals the scalar path row-wise."""
    import jax.numpy as jnp

    key = jax.random.key(0)
    logits = jax.random.normal(jax.random.key(1), (4, 64)) * 3
    for kwargs in [
        dict(temperature=0.0, top_k=0, top_p=1.0),
        dict(temperature=0.7, top_k=5, top_p=1.0),
        dict(temperature=1.3, top_k=0, top_p=0.8),
        dict(temperature=0.9, top_k=7, top_p=0.6),
    ]:
        a = sample(logits, key, **kwargs)
        b = sample(
            logits, key,
            temperature=jnp.full(4, kwargs["temperature"]),
            top_k=jnp.full(4, kwargs["top_k"], jnp.int32),
            top_p=jnp.full(4, kwargs["top_p"]),
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), kwargs


def test_sample_mixed_rows():
    """Greedy rows in a mixed batch are exactly argmax."""
    import jax.numpy as jnp

    logits = jax.random.normal(jax.random.key(2), (3, 32))
    toks = sample(
        logits, jax.random.key(3),
        temperature=jnp.asarray([0.0, 1.0, 0.0]),
        top_k=jnp.asarray([0, 10, 0], jnp.int32),
        top_p=jnp.asarray([1.0, 0.9, 1.0]),
    )
    am = np.argmax(np.asarray(logits), axis=-1)
    assert int(toks[0]) == am[0] and int(toks[2]) == am[2]


@pytest.mark.parametrize("kernels", [
    "xla",
    # slow (tier-1 budget, round 8): the interpret-mode run costs ~25s
    # CPU; the pallas SWA path stays pinned in tier-1 by the sharded
    # gemma2-beyond-window tests.
    pytest.param("pallas_interpret", marks=pytest.mark.slow),
])
def test_sliding_window_engine_matches_forward(kernels):
    """Windowed serving (prefill + paged decode, both kernel paths) must
    reproduce greedy generation from the windowed training forward —
    the training/serving-semantics equivalence SWA makes easy to break."""
    cfg, params = _setup(overrides=[
        "model.sliding_window=6", f"model.kernels={kernels}",
    ])
    prompt = [5, 3, 9, 250, 17, 8, 100, 42, 77]   # context > window
    ref = _ref_generate(params, cfg.model, prompt, 10)
    out = InferenceEngine(cfg, params).generate([prompt], 10)[0]
    assert out == ref


@pytest.mark.slow  # 90-token SWA generation, ~80s on the CPU tier
def test_rolling_window_bounds_page_footprint():
    """SWA serving is O(window) in pages: a pool too small for the full
    context (old behavior: single-request MemoryError) serves a long
    windowed generation correctly because dead pages are never allocated
    at admission and roll back to the pool as the window advances."""
    cfg, params = _setup(overrides=[
        "model.sliding_window=20",
        "inference.num_pages=6",         # 5 usable < 7 full-context pages
        "inference.max_new_tokens=90",
    ])
    prompt = [(i * 13) % 250 + 1 for i in range(10)]
    ref = _ref_generate(params, cfg.model, prompt, 90)

    eng = InferenceEngine(cfg, params)
    out = eng.generate([prompt], 90)[0]
    assert out == ref
    assert eng.preemptions == 0
    # All pages returned after completion.
    assert eng.alloc.free_pages == cfg.inference.num_pages - 1


def test_windowed_submit_accounts_for_bucket_bottom_peak():
    """The singleton-footprint check must use the WORST context (a
    prefill-bucket bottom), not max_context: a request accepted by submit
    but unadmittable would hang generate() forever."""
    cfg, params = _setup(overrides=[
        "model.sliding_window=4096",
        "inference.max_seq_len=8192", "inference.page_size=64",
        "inference.prefill_chunk=512", "inference.num_pages=72",
        "inference.max_batch_size=2",
    ])
    eng = InferenceEngine(cfg, params)
    prompt = [1] * 5633
    # Worst re-prefill (bucket 6144 -> 96 logical pages, only 24 dead)
    # needs ~73 real pages > 71 usable: must reject at submit, not hang.
    with pytest.raises(ValueError, match="pages"):
        eng.submit(prompt, 500)
    # A big enough pool accepts the same request.
    cfg2, _ = _setup(overrides=[
        "model.sliding_window=4096",
        "inference.max_seq_len=8192", "inference.page_size=64",
        "inference.prefill_chunk=512", "inference.num_pages=80",
        "inference.max_batch_size=2",
    ])
    InferenceEngine(cfg2, params).submit(prompt, 500)


# -- the dropless grouped MoE dispatch (models/moe.takes_grouped_path) -------

# The Mixtral serving cell's MoE geometry: E = 8, top-2, capacity_factor
# 4.0 = E / k (dropless), 32 slots, prefill bursts within 4096 padded tokens.
_CELL_PREFILL = [(1, 512), (1, 1024), (1, 1536), (1, 2048), (2, 512),
                 (2, 1024), (2, 1536), (2, 2048), (4, 512), (4, 1024),
                 (8, 512)]
_RULE_CASES = (
    [(f"prefill{b}x{s}", b, s, 4.0, {}, True) for b, s in _CELL_PREFILL]
    + [
        ("decode32x1", 32, 1, 4.0, {}, False),
        ("verify32x5", 32, 5, 4.0, {}, False),
        ("window_step32x1_cf8", 32, 1, 8.0, {}, False),
        ("train_factor_1.25", 8, 512, 1.25, {}, False),   # (a): it drops
        ("live_ep", 8, 512, 4.0, {"dp": 2, "ep": 4}, False),   # (b)
        ("live_tp_only", 8, 512, 4.0, {"dp": 4, "tp": 2}, True),
    ]
)


@pytest.mark.parametrize(
    "B,S,factor,axes,grouped", [c[1:] for c in _RULE_CASES],
    ids=[c[0] for c in _RULE_CASES])
def test_moe_grouped_rule(B, S, factor, axes, grouped):
    """Which blocks leave the capacity buckets: every prefill shape of the
    Mixtral cell, and nothing at the decode / verify shapes, under a
    capacity that drops, or with a live ep axis. ``expert_rows`` (the
    engine's counter) follows the same rule."""
    import dataclasses

    from orion_tpu.models import moe as moe_lib
    from tests.conftest import make_mesh

    cfg = dataclasses.replace(
        get_config("tiny-mixtral").model, n_experts=8,
        n_experts_per_token=2, capacity_factor=factor)
    mesh = make_mesh(jax.devices("cpu")[:8], **axes) if axes else None
    assert moe_lib.takes_grouped_path(cfg, B, S, mesh) is grouped
    buckets = 8 * B * moe_lib.moe_capacity(cfg, S)
    n_valid = B * S // 2 + 1
    assert moe_lib.expert_rows(cfg, B, S, None, mesh) == (
        2 * B * S if grouped else buckets)
    assert moe_lib.expert_rows(cfg, B, S, n_valid, mesh) == (
        2 * n_valid if grouped else buckets)
    einsum = dataclasses.replace(cfg, moe_dispatch="einsum")
    assert moe_lib.expert_rows(einsum, B, S, n_valid, mesh) == buckets


def _moe_cell_decode_jaxpr():
    """The decode-window jaxpr at the Mixtral cell's MoE geometry (tiny
    widths, 32 slots, 8 experts at the dropless factor), with the config
    and the abstract params and cache it was traced on."""
    from functools import partial

    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache, pages_per_seq

    cfg, _ = _setup("tiny-mixtral", [
        "model.n_experts=8", "model.capacity_factor=4.0",
        "inference.max_seq_len=1024", "inference.page_size=64",
        "inference.num_pages=64", "inference.max_batch_size=32",
        "inference.prefill_chunk=512"])
    mcfg, icfg = cfg.model, cfg.inference
    params = jax.eval_shape(lambda: init_params(mcfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: init_cache(mcfg, icfg))
    i32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32)
    B, W = icfg.max_batch_size, 8
    decode = jax.make_jaxpr(partial(
        runner.decode_window, cfg=mcfg, max_seq_len=icfg.max_seq_len,
        temperature=icfg.temperature, top_k=icfg.top_k, top_p=icfg.top_p))(
        params, cache, i32(B), i32(B), i32(B, pages_per_seq(icfg)),
        jax.ShapeDtypeStruct((B,), jnp.bool_),
        jax.eval_shape(lambda: jax.random.split(jax.random.key(0), W)))
    return cfg, params, cache, str(decode)


def test_decode_program_holds_no_grouped_matmul():
    """At the cell's MoE geometry the prefill program multiplies routed rows
    (a ragged dot in its jaxpr) and the decode-window program keeps the
    capacity buckets: the benchmark finds decode's expert fusions by shape,
    ``[E, slots, F]`` and ``[slots, E, 1, D]``, and the output check ties
    the window program to the one-step body. Since PR 42 a bucket at one
    position a row is the row itself, broadcast and not scattered; both
    shapes were kept (``test_decode_program_scatters_no_bucket``)."""
    from functools import partial

    from orion_tpu.infer import runner

    cfg, params, cache, decode = _moe_cell_decode_jaxpr()
    mcfg, icfg = cfg.model, cfg.inference
    i32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32)
    assert "ragged_dot" not in decode
    nb, s_pad = 8, 512
    prefill = jax.make_jaxpr(partial(runner.prefill_step, cfg=mcfg))(
        params, cache, i32(nb, s_pad), i32(nb),
        i32(nb, s_pad // icfg.page_size), i32(nb), i32(nb, 0))
    assert str(prefill).count("ragged_dot") >= 3      # w_in, w_gate, w_out


def test_decode_program_scatters_no_bucket():
    """At one position a row an expert's bucket is the row itself: the
    decode-window program of the cell's MoE geometry holds no scatter-add
    and no zeros of a bucket tensor ``[slots, E, 2, D]`` (capacity 1 and its
    trash row), where a block of two positions a row at the same geometry
    (what a verify block is) still scatters into one. The benchmark still
    finds decode's expert fusions by shape, ``[E, slots, F]`` and
    ``[slots, E, 1, D]``: the experts' operand stays ``[E, slots, 1, D]``
    and the combine still transposes and gathers their output, so both
    shapes are kept (folding the gates into the out matmul would remove
    the second; ROADMAP S15 (a), M6 (2))."""
    from functools import partial

    from orion_tpu.models import moe as moe_lib

    cfg, params, _, decode = _moe_cell_decode_jaxpr()
    mcfg = cfg.model
    B, E, D = cfg.inference.max_batch_size, mcfg.n_experts, mcfg.d_model
    layer = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        params["blocks"]["moe"])

    def block(S):
        assert not moe_lib.takes_grouped_path(mcfg, B, S)
        return str(jax.make_jaxpr(partial(moe_lib.moe_dispatch, cfg=mcfg))(
            jax.ShapeDtypeStruct((B, S, D), jnp.float32), layer))

    def zeros(S):       # of a bucket tensor: the capacity and a trash row
        C = moe_lib.moe_capacity(mcfg, S)
        return f"f32[{B},{E},{C + 1},{D}] = broadcast_in_dim"

    verify = block(2)
    assert "scatter-add" in verify and zeros(2) in verify
    for program in (decode, block(1)):
        assert "scatter-add" not in program and zeros(1) not in program
        # The experts' operand and output: [E, slots, 1, D], as they were.
        assert f"f32[{E},{B},1,{D}]" in program


def _one_position_case(router, B, D=16):
    """A sparse layer at one position a row, [B, 1, D], under one of the
    three routers the serving cells run. Returns (cfg, params) of the layer
    as served and (cfg, params, held) of the SAME function in the einsum
    form over every expert the router chooses among: ``held`` is the slice
    of them the served layer holds, and the others' ``w_out`` is zero there
    (an assignment to an expert held elsewhere adds nothing here)."""
    import dataclasses

    base = get_config("tiny-mixtral").model
    W, E, off, kw = {
        # Mixtral's: softmax, top-2 of 8, every expert held.
        "softmax_top2": (8, 8, 0, dict(n_experts_per_token=2)),
        # Laguna's: the chip holds experts [8, 16) of the router's 32.
        "held_share": (32, 8, 8, dict(n_experts_per_token=2)),
        # Ling's: sigmoid scores under a selection bias, 8 groups of which
        # the best 4 are kept, top-8.
        "grouped_sigmoid": (32, 32, 0, dict(
            n_experts_per_token=8, router_score="sigmoid", router_bias=True,
            n_group=8, topk_group=4, router_scale=2.5)),
    }[router]
    full = dataclasses.replace(
        base, n_experts=W, router_width=W, expert_offset=0,
        capacity_factor=W / kw["n_experts_per_token"],
        moe_dispatch="sorted", **kw)
    cfg = dataclasses.replace(full, n_experts=E, expert_offset=off)
    keys = jax.random.split(jax.random.key(B), 6)
    F = full.d_ff
    x = jax.random.normal(keys[0], (B, 1, D), jnp.float32)
    p_full = {
        "router": jax.random.normal(keys[1], (D, W), jnp.float32) * 0.3,
        "w_in": jax.random.normal(keys[2], (W, D, F), jnp.float32) * 0.1,
        "w_gate": jax.random.normal(keys[3], (W, D, F), jnp.float32) * 0.1,
        "w_out": jax.random.normal(keys[4], (W, F, D), jnp.float32) * 0.1,
    }
    if full.router_bias:
        p_full["router_bias"] = jax.random.normal(keys[5], (W,)) * 0.1
    held = slice(off, off + E)
    params = {k: v[held] if k.startswith("w_") else v
              for k, v in p_full.items()}
    p_full["w_out"] = jnp.zeros_like(p_full["w_out"]).at[held].set(
        p_full["w_out"][held])
    return x, (cfg, params), (full, p_full, held)


@pytest.mark.parametrize("B", [1, 32, 128])
@pytest.mark.parametrize(
    "router", ["softmax_top2", "held_share", "grouped_sigmoid"])
def test_moe_sorted_one_position_is_the_bucket_form(router, B):
    """At one position a row (the decode step) ``moe_mlp_sorted`` builds no
    bucket tensor: an expert's bucket of a batch row held that row or
    nothing, so the experts are handed the block broadcast over them. It
    equals the bucket form (scatter -> experts -> gather, called directly:
    ``tools/moe_dispatch_bench.bucket_form``, what that tool times it
    against) BIT FOR BIT in float32, the einsum form within the tolerance of
    ``tests/test_model.py::test_moe_sorted_matches_einsum`` (its neighbour
    by subject; that file is wholly ``slow``, and these cases are to run in
    tier-1), and its gradients w.r.t. x and the three expert matrices are
    the einsum form's (the broadcast's transpose sums cotangents that are
    zero for the experts a position did not choose). A row whose k choices
    are all held elsewhere comes out zero."""
    from functools import partial

    from orion_tpu.models import moe as moe_lib
    from tools.moe_dispatch_bench import bucket_form

    x, (cfg, params), (full, p_full, held) = _one_position_case(router, B)

    y, aux = jax.jit(partial(moe_lib.moe_mlp_sorted, cfg=cfg))(x, params)
    assert y.shape == x.shape
    np.testing.assert_array_equal(
        np.asarray(y),
        np.asarray(jax.jit(partial(bucket_form, cfg=cfg))(x, params)))
    y_e, aux_e = jax.jit(partial(moe_lib.moe_mlp, cfg=full))(x, p_full)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_e), atol=2e-5)
    np.testing.assert_allclose(float(aux), float(aux_e), rtol=1e-6)

    idx = moe_lib._router_topk(
        x, params["router"], cfg, params.get("router_bias"))[2]
    elsewhere = ~np.asarray(moe_lib._held(idx, cfg)[1]).any(-1)[:, 0]
    if router != "held_share":
        assert not elsewhere.any()
    elif B > 1:
        assert elsewhere.any() and not elsewhere.all()
    assert not np.asarray(y)[elsewhere].any()
    assert np.asarray(y)[~elsewhere].any(-1).all()

    def loss(fn, cfg, x, p):
        y, aux = fn(x, p, cfg)
        return (y ** 2).sum() + aux

    g_s = jax.jit(jax.grad(
        lambda x, p: loss(moe_lib.moe_mlp_sorted, cfg, x, p),
        argnums=(0, 1)))(x, params)
    g_e = jax.jit(jax.grad(
        lambda x, p: loss(moe_lib.moe_mlp, full, x, p),
        argnums=(0, 1)))(x, p_full)
    np.testing.assert_allclose(np.asarray(g_s[0]), np.asarray(g_e[0]),
                               atol=5e-5)
    for name in ("w_in", "w_gate", "w_out"):
        np.testing.assert_allclose(
            np.asarray(g_s[1][name]), np.asarray(g_e[1][name][held]),
            atol=5e-5, err_msg=name)


def _moe_burst(monkeypatch, grouped):
    """A tiny-mixtral engine at the dropless factor, one burst of three rows
    of unlike lengths (padded to 4 x 16), with the rule patched so that
    prefill takes the grouped path (or never does): tiny blocks fail the
    rule's row count, which charges E x 256 rows of tile rounding."""
    from orion_tpu.models import moe as moe_lib

    monkeypatch.setattr(
        moe_lib, "takes_grouped_path",
        lambda cfg, B, S, mesh=None: grouped and S > 1)
    traced, path = [], moe_lib.moe_mlp_grouped
    monkeypatch.setattr(
        moe_lib, "moe_mlp_grouped",
        lambda x, *a: traced.append(x.shape[:2]) or path(x, *a))
    cfg, params = _setup("tiny-mixtral", ["model.capacity_factor=2.0"])
    eng = InferenceEngine(cfg, params)
    prompts = [[5, 3, 9, 250, 17, 8, 1, 2, 3, 4, 5, 6, 7], [9, 8, 7], [42]]
    for p in prompts:
        eng.submit(p, 6)
    done = list(eng.step())
    timing = dict(eng.timing)
    while eng.has_work():
        done += eng.step()
    assert set(traced) == ({(4, 16)} if grouped else set())
    return {r.rid: list(r.generated) for r in done}, timing, cfg.model


def test_moe_grouped_prefill_matches_bucket_prefill(monkeypatch):
    """Greedy tokens of a ragged burst are the same whether prefill
    multiplies only the routed rows of its real positions or every
    expert's capacity bucket over every position, padding included."""
    bucket, _, _ = _moe_burst(monkeypatch, grouped=False)
    grouped, _, _ = _moe_burst(monkeypatch, grouped=True)
    assert len(grouped) == 3 and all(len(g) == 6 for g in grouped.values())
    assert grouped == bucket


@pytest.mark.parametrize("grouped", [False, True])
def test_prefill_expert_rows_exact(monkeypatch, grouped):
    """``prefill_expert_rows`` on a known burst: 13 + 3 + 1 real positions
    in a 4 x 16 block whose pad row has length 1. The grouped path computes
    k rows for each position that routes (the pad row's one included), the
    capacity buckets E x B x C = 4 x 4 x 16."""
    _, t, mcfg = _moe_burst(monkeypatch, grouped)
    assert (t["prefill_dispatches"], t["prefill_tokens"],
            t["prefill_pad_tokens"]) == (1, 17, 47)
    k, E = mcfg.n_experts_per_token, mcfg.n_experts
    assert t["prefill_expert_rows"] == (k * 18 if grouped else E * 4 * 16)
