"""A plain serving step queues its decode window behind its prefill (ISSUE 40):
the first tokens are picked inside the prefill program and stay on the device,
the window's keys are derived inside the decode program, and the host waits
once a program after both are queued.

- the programs: the picks, the scatter and the key of ``prefill_step``; the
  decode window's own key derivation against the host's, at temperature > 0;
- the engine: token-for-token equality of chained runs against the same
  requests with the chain forced off by what the engine reads off a burst (a
  sampled request in it), for a K/V model, a retention model and a model that
  holds a share of its experts; the PRNG stream, key by key, against the
  eager stream it replaces; what a chained step launches, by program name;
- the benchmark's reader of the spans, on the new layout.
"""

import logging
import re

import jax
import jax.numpy as jnp
import pytest

from orion_tpu.config import get_config
from orion_tpu.infer import InferenceEngine, runner
from orion_tpu.infer.kv_cache import init_cache
from orion_tpu.models import init_params

INFER = [
    "inference.max_seq_len=64", "inference.page_size=8",
    "inference.num_pages=48", "inference.max_batch_size=6",
    "inference.prefill_chunk=32", "inference.decode_window=4",
]


@pytest.fixture(scope="module")
def llama():
    cfg = get_config("tiny-llama", INFER)
    return cfg, init_params(cfg.model, jax.random.key(0))


# -- the programs ---------------------------------------------------------------


def _prefilled(cfg, params, B=2, S=16):
    m, icfg = cfg.model, cfg.inference
    prompts = jax.random.randint(jax.random.key(7), (B, S), 1, m.vocab_size)
    lengths = jnp.asarray([13, 9], jnp.int32)
    pt = jnp.arange(1, 1 + B * 8, dtype=jnp.int32).reshape(B, 8)
    return m, icfg, prompts, lengths, pt


def test_prefill_picks_scatters_and_advances_the_key(llama):
    cfg, params = llama
    m, icfg, prompts, lengths, pt = _prefilled(cfg, params)
    pages = pt[:, :16 // icfg.page_size]
    none = jnp.zeros((2,), jnp.int32), jnp.zeros((2, 0), jnp.int32)
    logits, _ = runner.prefill_step(
        params, init_cache(m, icfg), prompts, lengths, pages, *none, cfg=m)
    key = jax.random.key(11)
    last = jnp.asarray([50, 51, 52, 53, 54, 55], jnp.int32)
    # row 0 is slot 4; row 1 is a padding row (out of range: dropped)
    slots = jnp.asarray([4, 6], jnp.int32)
    got = runner.prefill_step(
        params, init_cache(m, icfg), prompts, lengths, pages, *none, None,
        slots, last, key, cfg=m)
    logits2, picks, last2, key2, cache = got
    assert (logits2 == logits).all()
    assert picks.dtype == jnp.int32
    assert (picks == jnp.argmax(logits, -1)).all()
    assert last2.tolist() == [50, 51, 52, 53, int(picks[0]), 55]
    assert (jax.random.key_data(key2)
            == jax.random.key_data(jax.random.split(key)[0])).all()
    assert set(cache) == set(init_cache(m, icfg))


@pytest.mark.parametrize("guard", [False, True])
def test_the_window_derives_the_keys_the_host_derived(llama, guard):
    """Sampled at temperature 0.9: the W keys made inside the program from
    the engine's one key are the host's ``split(split(key)[1], W)``."""
    cfg, params = llama
    m, icfg, prompts, lengths, pt = _prefilled(cfg, params)
    logits, cache = runner.prefill_step(
        params, init_cache(m, icfg), prompts, lengths,
        pt[:, :16 // icfg.page_size], cfg=m)
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    live, W, key = jnp.ones((2,), bool), 4, jax.random.key(3)
    sampling = (jnp.full((2,), 0.9), jnp.zeros((2,), jnp.int32),
                jnp.ones((2,)))
    rest = dict(cfg=m, max_seq_len=icfg.max_seq_len, nan_guard=guard)
    key_next, sub = jax.random.split(key)
    want = runner.decode_window(
        params, dict(cache), first, lengths, pt, live,
        jax.random.split(sub, W), *sampling, **rest)
    got = runner.decode_window(
        params, dict(cache), first, lengths, pt, live, key, *sampling,
        window=W, **rest)
    assert len(got) == len(want) + 1
    assert (got[0] == want[0]).all() and got[0].shape == (W, 2)
    assert len({int(t) for t in got[0].ravel()}) > 2        # sampled
    assert (jax.random.key_data(got[-2])
            == jax.random.key_data(key_next)).all()
    for name in want[-1]:
        assert (got[-1][name] == want[-1][name]).all()


# -- the engine -----------------------------------------------------------------

PROMPTS = [
    [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8], [5, 3, 9, 250, 17], [7, 7, 7],
    list(range(20, 39)), [4, 5, 6, 7, 8, 9, 10, 11], [9, 1], [2, 4, 6, 8, 10],
]


def _waves(eng, waves, sampled: bool):
    """Submit ``waves`` (lists of (prompt, max_new)) at steps 0, 3, 6, ...;
    with ``sampled`` every wave ends in one more request, at temperature
    0.8, which takes the step's burst off the chain. Returns the greedy
    requests in order."""
    reqs, step = [], 0
    while waves or eng.has_work():
        if waves and step % 3 == 0:
            for prompt, max_new in waves.pop(0):
                reqs.append(eng.submit_request(prompt, max_new))
            if sampled:
                eng.submit_request([3, 1, 4, 1, 5], 3, temperature=0.8)
        eng.step()
        step += 1
    return reqs


def _plan():
    """Three waves; PROMPTS[1] ends at its first token by its budget (and
    PROMPTS[4] by the stop token, once the test has chosen one)."""
    return [[(PROMPTS[0], 9), (PROMPTS[1], 1), (PROMPTS[2], 6)],
            [(PROMPTS[3], 12), (PROMPTS[4], 5)],
            [(PROMPTS[5], 8), (PROMPTS[6], 2)]]


def _run(cfg, params, sampled, eos_id=None, waves=None):
    eng = InferenceEngine(cfg, params, seed=0, eos_id=eos_id)
    reqs = _waves(eng, waves or _plan(), sampled)
    t = eng.reset_timing()
    t["preemptions"] = eng.preemptions
    eng.assert_page_accounting()
    eng.close()
    return [list(r.generated) for r in reqs], t


CASES = {
    "llama": ("tiny-llama", INFER),
    # Pages of 4 and a fold chunk of 8 (an eighth of the model's longest
    # sequence): PROMPTS[3] (19 tokens) folds two chunks in its prefill
    # (what the host books at the LAUNCH, before the step's own folds look
    # at the slot) and its slot folds again in the step after the one that
    # admitted it; PROMPTS[0] (11) folds once in each.
    "brumby": ("tiny-brumby", [
        "model.max_seq_len=64", "inference.max_seq_len=64",
        "inference.page_size=4", "inference.num_pages=96",
        "inference.max_batch_size=6", "inference.prefill_chunk=32",
        "inference.decode_window=4"]),
    "laguna-held": ("tiny-laguna", INFER + [
        "model.n_experts=8", "model.expert_offset=8"]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_chained_tokens_are_the_unchained_ones(name):
    preset, overrides = CASES[name]
    cfg = get_config(preset, overrides)
    params = init_params(cfg.model, jax.random.key(5))
    plain, _ = _run(cfg, params, sampled=False)
    # a stop token that ends one request at its FIRST token (PROMPTS[4]'s,
    # unless another request would stop on it earlier than its budget)
    eos = plain[4][0]
    chained, t = _run(cfg, params, sampled=False, eos_id=eos)
    unchained, u = _run(cfg, params, sampled=True, eos_id=eos)
    assert chained == unchained
    assert chained[4] == [eos] and len(chained[1]) == 1
    assert [len(c) for c in chained] <= [len(p) for p in plain]
    # every burst was chained, and none of the other run's
    assert t["chained_steps"] == t["prefill_dispatches"] == 3
    assert t["prefill_picks_in_program"] == 3
    assert u["chained_steps"] == u["prefill_picks_in_program"] == 0
    assert u["prefill_dispatches"] == 3
    # the one that stopped on its first token rode the window it was
    # queued behind (its tokens discarded); the budget's one did not
    assert t["wasted_steps"] >= cfg.inference.decode_window
    if name == "brumby":
        assert t["folds"] == u["folds"] > 0
    if name == "laguna-held":
        # counted by the program, fetched behind the picks
        assert t["prefill_held_expert_rows"] > 0
        assert (t["prefill_held_expert_rows"]
                < u["prefill_held_expert_rows"])     # the sampled rows' too


def test_the_key_stream_is_the_eager_one(llama, monkeypatch):
    """Every sampling event splits the engine's key once, as the eager code
    did: a greedy prefill and a decode window inside their programs, a
    sampled prefill on the host. The keys a sampled burst's sampler and
    every decode window are handed are the parent's, bit for bit."""
    from orion_tpu.infer import engine as engine_mod

    cfg, params = llama
    eng = InferenceEngine(cfg, params, seed=17)
    events = []
    real_sample, real_run = engine_mod.sample, eng._executor.run

    def sample(logits, key, **kw):
        events.append(("sample", jax.random.key_data(key).tolist()))
        return real_sample(logits, key, **kw)

    def run(path, name, *args, **kw):
        if path == "prefill":
            events.append(("prefill", None))
        elif path == "decode":
            events.append(("decode", jax.random.key_data(args[6]).tolist()))
        return real_run(path, name, *args, **kw)

    monkeypatch.setattr(engine_mod, "sample", sample)
    eng._executor.run = run
    waves = [[(PROMPTS[0], 9)], [(PROMPTS[1], 6)]]
    greedy = _waves(eng, [list(w) for w in waves], sampled=False)
    hot = eng.submit_request(PROMPTS[2], 9, temperature=0.9)
    while eng.has_work():
        eng.step()
    assert len(set(hot.generated)) > 2 and all(g.generated for g in greedy)

    k = jax.random.key(17)
    data = lambda key: jax.random.key_data(key).tolist()
    kinds = [e[0] for e in events]
    assert kinds.count("prefill") == 3 and kinds.count("sample") == 1
    for i, (kind, seen) in enumerate(events):
        if kind == "sample":
            continue            # checked at its prefill, one event back
        if kind == "decode":
            assert seen == data(k), i
            k = jax.random.split(k)[0]
            continue
        k, sub = jax.random.split(k)
        if i + 1 < len(events) and events[i + 1][0] == "sample":
            assert events[i + 1][1] == data(sub), i
    assert data(eng._key) == data(k)
    t = eng.reset_timing()
    assert t["chained_steps"] == 2 and t["prefill_picks_in_program"] == 2
    eng.close()


def _compiled(caplog) -> list:
    names = []
    for rec in caplog.records:
        m = re.match(r"Compiling (\S+)", rec.getMessage())
        if m:
            names.append(m.group(1))
    return names


def test_a_chained_step_launches_only_the_engines_own_programs(llama, caplog):
    """With every compiled program forgotten, the step that admits a
    request compiles what it launches: the prefill and the decode window,
    and no sampler, key split or argmax of the host's."""
    cfg, params = llama
    eng = InferenceEngine(cfg, params, seed=0)
    eng.submit_request(PROMPTS[0], 12)
    eng.step()
    eng.step()
    eng.reset_timing()
    jax.clear_caches()
    eng.submit_request(PROMPTS[1], 8)
    with jax.log_compiles(True), caplog.at_level(logging.WARNING):
        eng.step()
    names = _compiled(caplog)
    assert sorted(names) == ["jit(orion_decode_window)", "jit(orion_prefill)"], names
    t = eng.reset_timing()
    assert t["steps"] == t["chained_steps"] == 1
    assert t["prefill_dispatches"] == t["prefill_picks_in_program"] == 1
    # a decode-only step: the window alone, already compiled
    caplog.clear()
    with jax.log_compiles(True), caplog.at_level(logging.WARNING):
        eng.step()
    assert _compiled(caplog) == []
    # a sampled burst is the host's to pick: the sampler's programs are back
    eng.submit_request(PROMPTS[2], 4, temperature=0.7)
    caplog.clear()
    with jax.log_compiles(True), caplog.at_level(logging.WARNING):
        eng.step()
    assert any(not n.startswith("jit(orion_") for n in _compiled(caplog))
    t = eng.reset_timing()
    assert t["chained_steps"] == t["prefill_picks_in_program"] == 0
    while eng.has_work():
        eng.step()
    eng.close()


def test_a_short_pool_waits_for_the_prefill_first(llama):
    """Where the free list cannot cover the window's pages, the step keeps
    the older order (the picks are still the program's) and the tokens are
    those of a roomy pool."""
    _, params = llama
    small = INFER + ["inference.prefill_chunk=8"]
    cfg = get_config("tiny-llama", small)
    # Two requests of 19 tokens are at 31 when the second wave comes: the
    # next window wants a fifth page for each. Of the four pages free the
    # 14 tokens admitted then take two and are promised a third: one page
    # is missing, provisioning has to preempt, and whoever it re-queues
    # has its first token on the host by then.
    waves = lambda: [[(PROMPTS[3], 14), (list(range(60, 79)), 14)],
                     [(list(range(40, 54)), 6)]]
    roomy, t = _run(cfg, params, sampled=False, waves=waves())
    assert t["chained_steps"] == t["prefill_dispatches"] == 2
    assert t["preemptions"] == 0
    tight = get_config("tiny-llama", small + ["inference.num_pages=13"])
    got, u = _run(tight, params, sampled=False, waves=waves())
    assert got == roomy
    assert u["preemptions"] == 1
    assert u["chained_steps"] < u["prefill_dispatches"] == 3
    assert u["prefill_picks_in_program"] == 3


# -- the dispatch seam's own spans and counters (ISSUE 56) ----------------------


def test_the_seam_times_itself(llama):
    """One request on the module's engine shape, the ring on: the admitting
    step is chained (launch, launch, wait, wait), each leaf inside a run span
    of its path and tagged with its program and its launch's number; every
    launch is covered by a wait; the time nothing is queued lies inside the
    run's wall time, part of it inside steps; a 50 ms stall before a
    decode-only step's launch is the longest such interval; and the run
    spans' and the older keys' buckets are the sums of their leaves."""
    import time

    from orion_tpu.runtime.fault import FaultInjector, FaultSpec

    _, params = llama
    cfg = get_config("tiny-llama", INFER + ["inference.trace=true"])
    inj = FaultInjector([FaultSpec("stall", step=1, path="decode",
                                   stall_s=0.05)])
    eng = InferenceEngine(cfg, params, seed=0, fault_injector=inj)
    t_start = time.monotonic()
    (req,) = _waves(eng, [[(PROMPTS[0], 9)]], sampled=False)
    wall = time.monotonic() - t_start
    assert len(req.generated) == 9 and len(inj.fired) == 1
    assert eng._executor.in_flight == 0
    # with the interval still open behind the last wait
    unqueued = eng._executor.unqueued_until(time.monotonic())
    spans = [e for e in eng.tracer.events() if e[0] == "span"]
    t = eng.reset_timing()
    eng.close()

    seam = ("orion/prefill/launch", "orion/decode/launch",
            "orion/prefill/wait", "orion/decode/wait")
    first = sorted((e for e in spans if e[1] in seam and e[4]["step"] == 0),
                   key=lambda e: e[2])
    assert tuple(e[1] for e in first) == seam
    assert [(e[4]["program"], e[4]["seq"]) for e in first] == [
        ("orion_prefill", 1), ("orion_decode_window", 2),
        ("orion_prefill", 1), ("orion_decode_window", 2)]
    for _, name, t0, t1, _ in (e for e in spans if e[1] in seam):
        run = name.rsplit("/", 1)[0] + "/run"
        assert any(r[1] == run and r[2] <= t0 and t1 <= r[3]
                   for r in spans), name
    # a decode-only step: one launch, then its wait
    assert [e[1] for e in sorted(
        (e for e in spans if e[1] in seam and e[4]["step"] == 1),
        key=lambda e: e[2])] == list(seam[1::2])

    assert t["launches"] == t["waits"] == 1 + t["windows"]
    assert 0 < t["unqueued_in_step_s"] <= unqueued <= wall
    assert t["unqueued_s"] <= unqueued
    assert 0.05 <= t["unqueued_max_s"] <= t["unqueued_s"]
    # the same intervals, replayed from the ring's own spans
    launched, idle_since, gaps = 0, None, []
    for _, name, t0, t1, tags in sorted(
            (e for e in spans if e[1] in seam),
            key=lambda e: e[2] if e[1].endswith("launch") else e[3]):
        if name.endswith("/launch"):
            if idle_since is not None:
                gaps.append(t0 - idle_since)
            launched, idle_since = tags["seq"], None
        elif tags["seq"] == launched:
            idle_since = t1
    assert t["unqueued_s"] == pytest.approx(sum(gaps), abs=1e-9)
    assert t["unqueued_max_s"] == pytest.approx(max(gaps), abs=1e-9)

    def total(name):
        return sum(t1 - t0 for _, n, t0, t1, _ in spans if n == name)

    for path in ("prefill", "decode"):
        assert t[path + "_run_s"] == pytest.approx(
            total(f"orion/{path}/run"), abs=1e-9)
        assert t[path + "_launch_s"] == pytest.approx(
            total(f"orion/{path}/launch"), abs=1e-9)
        assert t[path + "_wait_s"] == pytest.approx(
            total(f"orion/{path}/wait"), abs=1e-9)
        assert t[path + "_launch_s"] + t[path + "_wait_s"] <= t[path + "_run_s"]
    assert t["prefill_s"] == pytest.approx(
        t["prefill_run_s"] + t["prefill_sample_s"], abs=1e-12)
    assert t["device_s"] == pytest.approx(
        t["decode_run_s"] + t["decode_fetch_s"], abs=1e-12)
    assert t["host_s"] == pytest.approx(
        t["reap_s"] + t["admit_s"] + t["prefill_build_s"]
        + t["decode_build_s"] + t["emit_s"] + t["step_self_s"], abs=1e-12)
    assert t["host_s"] + t["prefill_s"] + t["device_s"] == pytest.approx(
        total("orion/step"), abs=1e-9)


# -- the benchmark's reader of the spans, on the new layout ---------------------

MS = 1_000_000


def test_programs_keep_their_run_spans_in_the_new_layout():
    """Two chained steps and a decode-only one, on the profiler's clock:
    ``prefill/run`` and ``decode/run`` are entered twice a step (a short
    launch, a long wait), the prefill program runs under ``decode/build``
    and the window's launch, and the window starts the moment the prefill
    ends, inside the prefill's wait. Every prefill program goes to
    ``orion/prefill/run`` and every window to ``orion/decode/run``."""
    from benchmarks.trace import host_spans

    host, ops, modules = [], [], []

    def step(t0, prefill_ms):
        host.append(["orion/step", t0, (prefill_ms + 150) * MS])
        at = t0 + MS
        if prefill_ms:
            host.append(["orion/admit", at, 3 * MS])
            host.append(["orion/prefill/build", at, 1 * MS])
            host.append(["orion/prefill/run", at + 1 * MS, 1 * MS])  # launch
            start = at + 2 * MS             # the program starts as launched
            modules.append(["jit_orion_prefill(3)", start, prefill_ms * MS])
            ops.append(["fusion.1", start, prefill_ms * MS])
            at += 3 * MS
        host.append(["orion/decode/build", at, 2 * MS])
        host.append(["orion/fold/run", at + MS // 2, MS // 4])
        host.append(["orion/decode/run", at + 2 * MS, 1 * MS])      # launch
        at += 3 * MS
        w0 = at
        if prefill_ms:
            w0 = start + prefill_ms * MS    # back to back on the device
            # the wait returns 2 ms after the program's end
            host.append(["orion/prefill/run", at, w0 + 2 * MS - at])
            host.append(["orion/prefill/sample", w0 + 2 * MS, 1 * MS])
            at = w0 + 3 * MS
        modules.append(["jit_orion_decode_window(7)", w0, 136 * MS])
        ops.append(["fusion.2", w0, 136 * MS])
        host.append(["orion/decode/run", at, w0 + 137 * MS - at])   # wait
        host.append(["orion/decode/fetch", w0 + 137 * MS, 1 * MS])

    step(0, 40)
    step(300 * MS, 0)
    # the shortest prefill a served model has (its weights read once):
    # most of it still runs under its own wait, not the window's launch
    step(600 * MS, 8)
    events = {"devices": {"0": {"XLA Ops": ops, "XLA Modules": modules}},
              "host": host}
    got = host_spans.attribute(events)
    assert got["run_module_s"] == {
        "orion/prefill/run": pytest.approx(0.048),
        "orion/decode/run": pytest.approx(3 * 0.136),
    }
    # back to back: the device idles under a launch (the third step's;
    # the first has nothing before it), never between a prefill and its
    # window
    assert got["idle_by_span"]["orion/prefill/run"] == pytest.approx(0.001)
    assert "orion/prefill/sample" not in got["idle_by_span"]


def test_the_trace_tool_reads_what_follows_a_prefill():
    """``tools/step_chain_trace.gaps``: a chained step's window follows its
    prefill at once; a step in the older order shows the host's programs
    in between."""
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "step_chain_trace", pathlib.Path(__file__).resolve().parent.parent
        / "tools" / "step_chain_trace.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    US = 1_000
    modules = [
        ["jit_orion_decode_window(7)", 40_000 * US, 136_000 * US],
        ["jit_orion_prefill(3)", 0, 39_980 * US],           # chained
        ["jit_orion_prefill(3)", 200_000 * US, 30_000 * US],    # older order
        ["jit__threefry_split(9)", 231_900 * US, 1 * US],
        ["jit_argmax(11)", 232_500 * US, 1 * US],
        ["jit_orion_decode_window(7)", 236_000 * US, 136_000 * US],
    ]
    assert tool.gaps(modules) == [
        (20 * US, "jit_orion_decode_window(7)", []),
        (1_900 * US, "jit__threefry_split(9)",
         ["jit__threefry_split(9)", "jit_argmax(11)"]),
    ]
