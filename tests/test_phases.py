"""The serve path's own account of a step (ISSUE 25): one span primitive
(``obs.PhaseClock``) for the engine's ``orion/<phase>`` spans, the
``reset_timing`` leaves that partition a step, the prefill / decode sizing
counters, and the names of the Pallas kernels.

Everything here is a count or an identity between host-clock sums: no
number in this file is a device time.
"""

from __future__ import annotations

import functools

import jax
import pytest

from orion_tpu.config import get_config
from orion_tpu.obs import NULL_TRACER, PhaseClock, Tracer

BASE = [
    "inference.max_seq_len=128",
    "inference.page_size=16",
    "inference.num_pages=64",
    "inference.max_batch_size=4",
    "inference.prefill_chunk=16",
    "inference.decode_window=2",
]
# What reset_timing() returned before the leaves existed: every key keeps
# its name (the benchmark's readers and the router's ITL proxy read them).
OLD_KEYS = {
    "device_s", "host_s", "prefill_s", "decode_device_s", "mixed_device_s",
    "windows", "steps", "slot_steps", "wasted_steps", "decode_slot_steps",
    "mixed_steps", "prefill_chunks", "chunk_tokens", "chunk_pad_tokens",
    "spill_s", "restore_s", "page_in_s", "migrate_out_s", "migrate_in_s",
    "decode_window",
}
MODES = {
    "plain": [],
    "chunked": ["inference.chunked_prefill=true",
                "inference.prefill_chunk_tokens=16"],
    "speculative": ["inference.speculative=true",
                    "inference.speculate_tokens=3"],
}
# The first prompt holds 100 of the 256 token ids in order, so the n-gram
# proposer finds most sampled tokens in it and drafts their successors
# (the speculative mode's verify path), and it spans seven 16-token chunks
# (the chunked mode's paged prefill).
PROMPTS = [list(range(1, 101)), [4, 5, 6, 7], [8, 9]]


@pytest.fixture(scope="module")
def params():
    from orion_tpu.models import init_params

    return init_params(get_config("tiny-llama", BASE).model, jax.random.key(0))


def make_engine(params, extra=()):
    from orion_tpu.infer import InferenceEngine

    return InferenceEngine(
        get_config("tiny-llama", BASE + list(extra)), params, seed=0
    )


# ---------------------------------------------------------------------------
# The primitive
# ---------------------------------------------------------------------------


def test_phase_clock_books_self_time_and_partitions_on_failure():
    buckets = {"outer_s": 0.0, "inner_s": 0.0, "sum_s": 0.0}
    keys = {"outer": ("outer_s", "sum_s"), "inner": ("inner_s", "sum_s"),
            "marker": ()}
    tr = Tracer()
    clock = PhaseClock(tr, keys, lambda: buckets, lambda: {"step": 7})
    with clock("outer") as outer:
        with clock("inner") as inner:
            pass
        with clock("marker"):          # no keys: its time stays with outer
            pass
        with pytest.raises(RuntimeError):
            with clock("inner"):       # raises: books nothing
                raise RuntimeError("x")
    total = outer.t1 - outer.t0
    assert buckets["inner_s"] == pytest.approx(inner.t1 - inner.t0)
    assert buckets["outer_s"] == pytest.approx(total - buckets["inner_s"])
    assert buckets["sum_s"] == pytest.approx(total)
    assert clock.stack == []
    # The ring holds every span, the failed one too, under the ONE name
    # the profile and the docs use, with the owner's tags.
    assert [(e[1], e[4]) for e in tr.events()] == [
        ("orion/inner", {"step": 7}), ("orion/marker", {"step": 7}),
        ("orion/inner", {"step": 7}), ("orion/outer", {"step": 7}),
    ]
    with pytest.raises(KeyError):
        clock("per-token")             # the set of phases is closed


def test_phase_clock_with_the_ring_off_builds_no_tags():
    def tags():
        raise AssertionError("tags built with the tracer off")

    buckets = {"a_s": 0.0}
    clock = PhaseClock(NULL_TRACER, {"a": ("a_s",)}, lambda: buckets, tags)
    with clock("a") as span:
        pass
    assert span.tags is None and buckets["a_s"] > 0.0


def _engine_cls():
    from orion_tpu.infer import InferenceEngine

    return InferenceEngine


@pytest.mark.parametrize("leaf", [
    "prefill/launch", "prefill/wait", "decode/launch", "decode/wait",
    "verify/launch", "verify/wait", "fold/launch",
    "mixed/launch", "mixed/wait", "mixed_verify/launch", "mixed_verify/wait",
])
def test_a_seam_leaf_leaves_its_parents_buckets_what_they_were(leaf):
    """A phase books SELF time, so a child takes its time out of its
    parent's buckets unless it feeds them itself: with the key tuples as
    ``_PHASE_KEYS`` spells them, every key of a ``<path>/run`` phase sums
    to the run span's whole length with the leaf inside it, as it did
    without, and the leaf's own key holds the leaf alone."""
    eng = _engine_cls()
    # a fold is launched and never waited for: eleven leaves, not twelve
    assert sum(p.endswith(("/launch", "/wait")) for p in eng._PHASE_KEYS) == 11
    run = leaf.rsplit("/", 1)[0] + "/run"
    own, *parents = eng._PHASE_KEYS[leaf]
    assert tuple(parents) == eng._PHASE_KEYS[run]
    assert own not in eng._PHASE_KEYS[run] and own in eng._zero_timing()
    buckets = dict.fromkeys(
        set(eng._PHASE_KEYS[leaf]) | set(eng._PHASE_KEYS["step"]), 0.0)
    clock = PhaseClock(NULL_TRACER, eng._PHASE_KEYS, lambda: buckets,
                       lambda: {})
    with clock("step") as step:
        with clock(run) as parent:
            with clock(leaf) as child:
                pass
    whole, inner = parent.t1 - parent.t0, child.t1 - child.t0
    assert buckets[own] == pytest.approx(inner, abs=1e-12)
    for key in eng._PHASE_KEYS[run]:
        assert buckets[key] == pytest.approx(whole, abs=1e-12), key
    assert buckets["host_s"] == pytest.approx(
        step.t1 - step.t0 - whole, abs=1e-12)


def test_every_key_a_phase_feeds_is_in_reset_timing():
    eng = _engine_cls()
    zero = eng._zero_timing()
    fed = {k for keys in eng._PHASE_KEYS.values() for k in keys}
    assert fed <= set(zero)
    assert {"unqueued_s", "unqueued_in_step_s", "unqueued_max_s",
            "launches", "waits"} <= set(zero)
    assert "window_ring_wraps" not in zero


# ---------------------------------------------------------------------------
# (a) the leaves partition their parents; every old key keeps its meaning
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", sorted(MODES))
def test_leaves_partition_the_old_keys(params, mode):
    eng = make_engine(params, MODES[mode] + ["inference.trace=true"])
    eng.generate(PROMPTS, 24)
    steps = [e for e in eng.tracer.events()
             if e[0] == "span" and e[1] == "orion/step"]
    t = eng.reset_timing()
    assert OLD_KEYS <= set(t)
    assert t["prefill_s"] == pytest.approx(
        t["prefill_run_s"] + t["prefill_sample_s"], abs=1e-12)
    assert t["decode_device_s"] == pytest.approx(
        t["decode_run_s"] + t["decode_fetch_s"] + t["verify_run_s"]
        + t["compact_s"], abs=1e-12)
    assert t["device_s"] == pytest.approx(
        t["decode_device_s"] + t["mixed_device_s"], abs=1e-12)
    assert t["host_s"] == pytest.approx(
        t["reap_s"] + t["admit_s"] + t["prefill_build_s"]
        + t["decode_build_s"] + t["emit_s"] + t["step_self_s"], abs=1e-12)
    # The old meaning: host_s is what is left of the steps' wall time
    # after the spans that wait for the device and the tier copies.
    assert len(steps) == t["steps"]
    wall = sum(t1 - t0 for _, _, t0, t1, _ in steps)
    assert t["host_s"] == pytest.approx(
        wall - t["device_s"] - t["prefill_s"] - t["spill_s"]
        - t["restore_s"] - t["page_in_s"], abs=1e-9)
    # The seam's leaves lie inside their run spans, and every launch was
    # waited for; nothing was queued for part of the steps (booked at a
    # step's end, so the last step's tail is in it before the next launch
    # books it to unqueued_s), never longer than the steps took.
    for path in ("prefill", "decode", "verify"):
        assert 0 <= t[path + "_launch_s"] + t[path + "_wait_s"] <= (
            t[path + "_run_s"] + 1e-12)
    assert t["mixed_launch_s"] + t["mixed_wait_s"] <= t["mixed_device_s"]
    assert t["launches"] == t["waits"] > 0
    assert 0 < t["unqueued_max_s"] <= t["unqueued_s"] <= wall + 1e-9
    assert 0 < t["unqueued_in_step_s"] <= wall + 1e-9
    # Each mode took its own path.
    if mode == "plain":
        assert t["decode_run_s"] > 0 and t["prefill_run_s"] > 0
        assert t["device_s"] == pytest.approx(
            t["decode_run_s"] + t["decode_fetch_s"] + t["mixed_device_s"])
    elif mode == "chunked":
        assert t["mixed_device_s"] > 0 and t["prefill_dispatches"] == 0
    else:
        assert t["verify_run_s"] > 0


# ---------------------------------------------------------------------------
# (b) the sizing counters on a burst one can count by hand
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window, kv_tokens", [(None, 122), (8, 90)])
def test_prefill_and_decode_counters_hand_counted(params, window, kv_tokens):
    """Prompts of 5, 9 and 12 tokens share the 16-token bucket: one
    dispatch of 4 rows (3 -> a power of two) x 16 = 64 positions, 26 of
    them real. Five tokens each: the first from the prefill, then two
    windows of W=2. Window 1 reads (5+6) + (9+10) + (12+13) = 55 cached
    positions, window 2 (7+8) + (11+12) + (14+15) = 67. Under a sliding
    window of 8 every term is at most 8: 43 + 47."""
    extra = [] if window is None else [f"model.sliding_window={window}"]
    eng = make_engine(params, extra)
    prompts = [list(range(1, n + 1)) for n in (5, 9, 12)]
    out = eng.generate(prompts, 5)
    assert [len(o) for o in out] == [5, 5, 5]
    t = eng.reset_timing()
    assert (t["prefill_dispatches"], t["prefill_tokens"],
            t["prefill_pad_tokens"]) == (1, 26, 38)
    assert t["windows"] == 2
    assert t["decode_kv_tokens"] == kv_tokens
    # Drained like every other counter.
    t2 = eng.reset_timing()
    assert t2["prefill_tokens"] == t2["decode_kv_tokens"] == 0


def test_prefix_cached_positions_are_not_prefill_tokens(params):
    """A prompt whose first page is served from the prefix cache computes
    only its tail: 16 cached positions are not counted as prefill work."""
    eng = make_engine(params, ["inference.prefix_cache=true"])
    head = list(range(1, 17))
    eng.generate([head + [40, 41, 42]], 2)
    cold = eng.reset_timing()
    assert cold["prefill_tokens"] == 19
    eng.generate([head + [50, 51]], 2)
    warm = eng.reset_timing()
    assert warm["prefix_hits"] == 1
    assert (warm["prefill_tokens"], warm["prefill_pad_tokens"]) == (2, 14)


# ---------------------------------------------------------------------------
# (c) tracing off costs a bounded number of annotations; on, spans nest
# ---------------------------------------------------------------------------


def test_annotations_always_and_bounded_ring_only_when_asked(
        params, monkeypatch):
    opened: list = []

    class Stub:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Stub)

    def per_step(prompts, n_new):
        eng = make_engine(params)
        assert eng.tracer is NULL_TRACER       # inference.trace is off
        for p in prompts:
            eng.submit(p, n_new)
        counts = []
        while eng.has_work():
            del opened[:]
            eng.step()
            assert all(n.startswith("orion/") for n in opened)
            counts.append(len(opened))
        return counts

    small = per_step([[1, 2, 3]], 4)
    large = per_step([[1, 2, 3], [4, 5, 6, 7], [8, 9], [3] * 12], 24)
    # A step with a prefill burst opens the most (one build / run / sample
    # triple per dispatch: one per burst on the Pallas path, one per
    # length bucket of the burst on the XLA path; a launch and a wait leaf
    # inside each of a dispatch's two run spans); a decode-only step the
    # same number whatever the batch and however long the outputs.
    assert max(small) == max(large) == small[0] == large[0] <= 16
    assert set(small[1:]) == set(large[1:]) and len(set(large[1:])) == 1
    assert len(large) > len(small)


def test_ring_spans_carry_their_step_and_nest_inside_it(params):
    eng = make_engine(params, ["inference.trace=true"])
    eng.generate(PROMPTS, 6)
    spans = [e for e in eng.tracer.events() if e[0] == "span"]
    steps = {e[4]["step"]: e for e in spans if e[1] == "orion/step"}
    leaves = [e for e in spans if e[1] != "orion/step"]
    assert {e[1] for e in leaves} == {
        "orion/reap", "orion/admit", "orion/prefill/build",
        "orion/prefill/run", "orion/prefill/sample", "orion/decode/build",
        "orion/decode/run", "orion/decode/fetch", "orion/decode/emit",
        "orion/prefill/launch", "orion/prefill/wait",
        "orion/decode/launch", "orion/decode/wait",
    }
    for _, name, t0, t1, tags in leaves:
        _, _, s0, s1, _ = steps[tags["step"]]
        assert s0 <= t0 <= t1 <= s1, name
    # The dispatch spans name the requests they computed for.
    assert any(e[4]["tids"] for e in leaves if e[1] == "orion/decode/run")
    assert all("decoded" in e[4] for e in steps.values())


# ---------------------------------------------------------------------------
# (d) the kernels carry their names into the programs
# ---------------------------------------------------------------------------


def pallas_names(jaxpr, out=None) -> set:
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.add(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    pallas_names(inner, out)
    return out


KERNELS = ["model.kernels=pallas_interpret"]
# ``rope`` is in a program whose rows are ``ops.rope.KERNEL_MIN_SEQ`` (64)
# tokens or longer (the 100-token prompt's prefill); decode's one token a
# slot, the verify window of 4 and the mixed step's chunk of 16 rotate in
# XLA's own code (ISSUE 34).
PROGRAMS = {
    "plain": (KERNELS, {
        "prefill": {"flash_fwd", "rmsnorm", "rope"},
        "decode": {"paged_decode", "rmsnorm"}}),
    "chunked-paged": (
        KERNELS + MODES["chunked"] + ["inference.paged_prefill=true"], {
            "mixed": {"flash_fwd", "paged_decode", "paged_flash_prefill",
                      "rmsnorm"}}),
    "speculative": (KERNELS + MODES["speculative"], {
        "prefill": {"flash_fwd", "rmsnorm", "rope"},
        "verify": {"ragged_paged", "rmsnorm"}}),
}


@pytest.mark.parametrize("case", sorted(PROGRAMS))
def test_serve_programs_hold_named_kernels(params, case):
    extra, want = PROGRAMS[case]
    eng = make_engine(params, extra)
    seen: dict = {}
    run = eng._executor.run

    def tap(path, name, *args, **kwargs):
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        # keywords are static (the decode window's length): bound, not traced
        program = functools.partial(getattr(eng, "_" + name), **kwargs)
        pallas_names(
            jax.make_jaxpr(program)(*shapes).jaxpr,
            seen.setdefault(path, set()))
        return run(path, name, *args, **kwargs)

    eng._executor.run = tap
    eng.generate(PROMPTS[:1], 24)
    assert {p: seen.get(p) for p in want} == want


def test_train_step_holds_named_kernels():
    from orion_tpu.train import Trainer
    from orion_tpu.train.trainer import make_train_step

    t = Trainer(get_config("tiny-llama", KERNELS + ["train.num_steps=1"]))
    step = make_train_step(t.cfg, t._schedule, t.mesh)
    jaxpr = jax.make_jaxpr(step)(t.init_state(), t.global_batch(0)).jaxpr
    assert pallas_names(jaxpr) == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "rmsnorm", "rope"}
