"""``benchmarks/trace/seam.py`` and the five readers beside it: the device's
idle time split at the dispatch seam with no clock offset in it. On a
hand-made trace whose answers are known under any shift of the device's
clock, and on a small trace recorded on the chip."""

import json

import pytest

from benchmarks.harness.cell import Cell
from benchmarks.trace import host_spans, seam
from tests.benchmark.conftest import REPO

US = 1_000
CELL = "mixtral-8x7b.serve-batch"
WAKE, LATENCY = 600, 300        # us: a window's wake-up, a launch's latency


def reader(name):
    return Cell.find(CELL).reader(name)


def hand_made(shift_us: int = 0, drop=None) -> dict:
    """Three turns of the benchmark's loop on ONE true clock, in us: a chained
    step (prefill 40 ms), a decode-only step behind a fold, a chained step
    (prefill 8 ms); the layout of
    ``test_step_chain.test_programs_keep_their_run_spans_in_the_new_layout``
    with a launch and a wait leaf inside every run span and the benchmark's
    own spans around the steps. The host's events are written on the true
    clock, the device's ``shift_us`` later. Every window's wait returns
    ``WAKE`` after the window's end, a program that is not queued behind
    another starts ``LATENCY`` after its launch began (a fold 250), a chained
    window 5 us after its prefill, and a window holds 10 us in which no
    operation runs. ``drop``: (span name, which of them) to leave out."""
    host, ops, modules = [], [], []

    def span(name, at, dur):
        host.append([name, at * US, dur * US])

    def run(name, at, dur, hole=False):
        at += shift_us
        modules.append([name, at * US, dur * US])
        if hole:
            ops.append(["fusion.2", at * US, 60_000 * US])
            ops.append(["fusion.3", (at + 60_010) * US, (dur - 60_010) * US])
        else:
            ops.append(["fusion.1", at * US, dur * US])
        return at - shift_us + dur

    def turn(t0, prefill_ms=0, fold=False):
        span("bench.generate", t0, 1000)
        at = t0 + 1000
        step0 = at + 10
        span("orion/reap", step0 + 10, 100)
        at = step0 + 190
        p_end = None
        if prefill_ms:
            span("orion/admit", at, 3000)
            span("orion/prefill/build", at + 100, 1000)
            span("orion/prefill/run", at + 1100, 1000)      # uploads, launch
            span("orion/prefill/launch", at + 1600, 400)
            p_end = run("jit_orion_prefill(3)", at + 1600 + LATENCY,
                        prefill_ms * 1000)
            at += 3100
        span("orion/decode/build", at, 2000 if prefill_ms else 1500)
        w0 = 0
        if fold:
            span("orion/fold/run", at + 500, 300)
            span("orion/fold/launch", at + 550, 200)
            w0 = run("jit_orion_fold(5)", at + 550 + 250, 1000) + 5
        at += 2000 if prefill_ms else 1500
        span("orion/decode/run", at, 500)                   # the launch
        span("orion/decode/launch", at + 100, 350)
        w0 = max(w0, at + 100 + LATENCY)
        at += 600
        if prefill_ms:
            w0 = p_end + 5                  # back to back on the device
            span("orion/prefill/run", at, p_end + 500 - at)
            span("orion/prefill/wait", at + 50, p_end + 500 - at - 50)
            span("orion/prefill/sample", p_end + 550, 1000)
            at = p_end + 1600
        w_end = run("jit_orion_decode_window(7)", w0, 136_000, hole=True)
        span("orion/decode/run", at, w_end + WAKE - at)
        span("orion/decode/wait", at + 50, w_end + WAKE - at - 50)
        at = w_end + WAKE
        span("orion/decode/fetch", at + 45, 500)
        span("orion/decode/emit", at + 595, 1000)
        span("orion/step", step0, at + 1695 - step0)
        span("bench.engine_step", step0 - 10, at + 1705 - step0 + 10)
        span("bench.observe", at + 1715, 300)
        return at + 2095

    t = turn(0, prefill_ms=40)
    t = turn(t, fold=True)
    turn(t, prefill_ms=8)
    if drop is not None:
        name, which = drop
        gone = sorted(h for h in host if h[0] == name)[which]
        host.remove(gone)
    return {"devices": {"0": {"XLA Ops": ops, "XLA Modules": modules}},
            "host": host}


# Of the five gaps between the six programs (prefill, window, fold, window,
# prefill, window): two chained prefill -> window gaps of 5 us and the fold
# -> window gap of 100 us, all seam; the window -> fold gap, 4695 us = WAKE
# + 3845 of host + 250, and the window -> prefill gap, 5795 us = WAKE + 4895
# of host + LATENCY. Inside orion/step: 2435 + 3485 us (fetch, emission,
# reap, build, the uploads before a prefill's launch, the step's own time);
# outside it 1410 us twice (observe, generate, the edges).
SEAM_S = (5 + 100 + 5 + WAKE + 250 + WAKE + LATENCY) / 1e6
IN_STEP_S, OUTSIDE_S, INSIDE_S = 5920 / 1e6, 2820 / 1e6, 30 / 1e6


@pytest.mark.parametrize("shift_us", [-800, 0, 800])
def test_the_three_sums_hold_under_any_shift_of_the_device_clock(shift_us):
    got = seam.split(hand_made(shift_us))
    assert got["seam_s"] == pytest.approx(SEAM_S, abs=1e-12)
    assert got["host_in_step_s"] == pytest.approx(IN_STEP_S, abs=1e-12)
    assert got["host_outside_s"] == pytest.approx(OUTSIDE_S, abs=1e-12)
    assert got["inside_s"] == pytest.approx(INSIDE_S, abs=1e-12)
    assert got["gaps"] == {
        "after_wait": [2, pytest.approx((4695 + 5795) / 1e6)],
        "in_chain": [3, pytest.approx(110 / 1e6)]}
    # the four parts are the idle time between the first program's start
    # and the last one's end, exactly
    assert got["span_s"] - got["busy_s"] == pytest.approx(
        SEAM_S + IN_STEP_S + OUTSIDE_S + INSIDE_S, abs=1e-12)
    assert got["host_in_step"]["orion/prefill/run"] == pytest.approx(500e-6)
    assert got["host_in_step"]["orion/decode/emit"] == pytest.approx(2000e-6)
    assert got["host_outside"]["bench.generate"] == pytest.approx(2000e-6)
    assert got["runs"] == 6 and not got["others"]


@pytest.mark.parametrize("shift_us", [-800, 800])
def test_the_bracket_holds_the_shift(shift_us):
    """device - host: no more than the least launch latency (the fold's 250
    us) above the shift, no less than the least wake-up (a prefill's 500 us)
    below it."""
    got = seam.split(hand_made(shift_us))
    assert got["lo_ns"] == (shift_us - 500) * US
    assert got["hi_ns"] == (shift_us + 250) * US
    assert got["lo_ns"] <= shift_us * US <= got["hi_ns"]


def test_host_spans_idle_table_moves_with_the_shift_and_says_by_how_much():
    """The per-instant table books a gap's first ``WAKE`` to the wait span
    only while the two clocks agree. Beside the 30 us inside the windows,
    ``orion/decode/wait`` reads 2 x 0.6 ms on one clock, 2 x 1.4 ms with the
    device's clock 0.8 ms behind and only the fold's 0.1 ms gap with it 0.8
    ms ahead (a window then 'ends' after its wait returned, and the launch
    spans fill instead): 3.11 ms of the 10.63 ms of idle time change rows
    between the two shifts. The seam's sums (the test above) do not move."""
    ahead = host_spans.attribute(hand_made(800))["idle_by_span"]
    behind = host_spans.attribute(hand_made(-800))["idle_by_span"]
    true = host_spans.attribute(hand_made(0))["idle_by_span"]
    assert true["orion/decode/wait"] == pytest.approx((2 * WAKE + 30) / 1e6)
    assert behind["orion/decode/wait"] == pytest.approx(
        (2 * (WAKE + 800) + 30) / 1e6)
    assert ahead["orion/decode/wait"] == pytest.approx((100 + 30) / 1e6)
    assert "orion/prefill/launch" in ahead and \
        "orion/prefill/launch" not in behind
    assert sum(ahead.values()) == pytest.approx(sum(behind.values()))
    assert sum(ahead.values()) == pytest.approx(10_630 / 1e6)
    moved = sum(abs(ahead.get(k, 0.0) - behind.get(k, 0.0))
                for k in set(ahead) | set(behind)) / 2
    assert moved == pytest.approx(3.11e-3)


def test_a_launch_span_dropped_mid_trace_gives_none_with_a_sentence():
    said = []
    events = hand_made(800, drop=("orion/decode/launch", 1))
    assert seam.split(events, say_why=said.append) is None
    assert len(said) == 1 and "5 launch spans against 6 program runs" in said[0]


def test_a_trace_that_opens_mid_dispatch_is_trimmed_to_whole_pairs():
    """The first prefill's launch span fell before the trace began: its run
    is left out, and with it the 5 us gap behind it."""
    said = []
    got = seam.split(hand_made(-800, drop=("orion/prefill/launch", 0)),
                     say_why=said.append)
    assert not said and got["runs"] == 5
    assert got["seam_s"] == pytest.approx(SEAM_S - 5e-6, abs=1e-12)
    assert got["host_in_step_s"] == pytest.approx(IN_STEP_S, abs=1e-12)


def test_a_program_without_the_spans_gives_nothing_and_says_nothing():
    said = []
    events = hand_made()
    events["host"] = [h for h in events["host"]
                      if not h[0].endswith(("/launch", "/wait"))]
    assert seam.split(events, say_why=said.append) is None and not said
    assert seam.for_obs({"trace": None}) is None


def test_a_pairing_that_contradicts_causality_fails_loudly():
    """A launch span that begins 2 ms after its program started on the
    device: no clock offset fits it and the other pairs."""
    events = hand_made(800)
    late = sorted(h for h in events["host"]
                  if h[0] == "orion/prefill/launch")[1]
    late[1] += 2300 * US
    with pytest.raises(RuntimeError, match="pairing error"):
        seam.split(events)


def test_a_program_of_another_name_is_not_paired():
    said = []
    events = hand_made()
    mods = events["devices"]["0"]["XLA Modules"]
    mods[2][0] = "jit_orion_verify(9)"          # where the fold ran
    assert seam.split(events, say_why=said.append) is None
    assert "a program its path launches" in said[0]


def test_the_table_says_the_parts_the_bracket_and_the_edges(capsys):
    got = seam.split(hand_made(800))
    seam.say(got, {"window_s": 0.480, "busy_s": 0.455,
                   "timing": {"steps": 3}})
    out = capsys.readouterr().out
    assert "6 runs of the engine's own programs" in out
    assert "gaps after a wait with nothing queued" in out and "    2 " in out
    assert "orion/prefill/run" in out and "bench.generate" in out
    assert "a step (3 steps): seam 0.620 ms, engine host 1.973, front end " \
        "0.940, inside programs 0.010" in out
    assert "clock bracket (device - host): [300.0, 1050.0] us, width 750.0" \
        in out


def test_recorded_v5e_trace_gives_what_its_run_printed():
    """The whole traced segment of a ``mixtral-8x7b.serve-batch`` run on the
    v5e (the fixture's ``note`` has the run and the table it printed above
    its result line; the operations are merged into their union, which is
    all ``split`` reads of them):
    34 steps, 26 of them chained."""
    events = json.loads(
        (REPO / "tests/benchmark/data/trace_seam_v5e.json").read_text())
    got = seam.split(events)
    assert got["runs"] == 60 and not got["others"]
    assert got["gaps"]["after_wait"] == [33, pytest.approx(0.2020, abs=5e-5)]
    assert got["gaps"]["in_chain"] == [26, pytest.approx(0.0001, abs=5e-5)]
    assert got["seam_s"] == pytest.approx(0.0532, abs=5e-5)
    assert got["host_in_step_s"] == pytest.approx(0.1253, abs=5e-5)
    assert got["host_outside_s"] == pytest.approx(0.0236, abs=5e-5)
    assert 1e3 * got["seam_s"] / 34 == pytest.approx(1.564, abs=5e-4)
    assert 1e3 * got["host_in_step_s"] / 34 == pytest.approx(3.685, abs=5e-4)
    assert 1e3 * got["host_outside_s"] / 34 == pytest.approx(0.694, abs=5e-4)
    assert (got["lo_ns"], got["hi_ns"]) == (-1_931_214, -731_112)
    # the parts are the idle time from the first run's start to the last
    # one's end, which the run's own busy_s / window_s (5.8261 of 6.0365 s)
    # holds with the segment's two edges
    parts = (got["seam_s"] + got["host_in_step_s"] + got["host_outside_s"]
             + got["inside_s"])
    assert got["span_s"] - got["busy_s"] == pytest.approx(parts, abs=1e-9)
    assert 0 < (6.036523303 - 5.826116409) - parts < 0.0085
    # the uploads before a prefill's launch are the largest row, and the
    # same trace under host_spans' per-instant rule reads another table:
    # on this machine the device's clock ran 0.7-1.9 ms BEHIND the host's
    assert max(got["host_in_step"], key=got["host_in_step"].get) == \
        "orion/prefill/run"
    table = host_spans.attribute(events)["idle_by_span"]
    assert table["orion/decode/wait"] > got["seam_s"]
    assert table["orion/prefill/run"] < got["host_in_step"]["orion/prefill/run"]


# -- the readers ----------------------------------------------------------------

TRACED = {"idle_seam_ms_per_step.batch": 1e3 * SEAM_S / 3,
          "idle_engine_host_ms_per_step.batch": 1e3 * IN_STEP_S / 3,
          "idle_frontend_ms_per_step.batch": 1e3 * OUTSIDE_S / 3}


@pytest.mark.parametrize("name", sorted(TRACED))
def test_the_trace_readers_on_the_hand_made_trace(name, monkeypatch):
    monkeypatch.setattr(host_spans, "newest_trace", lambda: "hand-made")
    monkeypatch.setitem(seam._CACHE, "hand-made", seam.split(hand_made(-800)))
    obs = {"trace": {"timing": {"steps": 3}}}
    assert reader(name).read(obs) == pytest.approx(TRACED[name])
    # a parent without the spans, an untraced run, a segment with no step
    monkeypatch.setitem(seam._CACHE, "hand-made", None)
    assert reader(name).read(obs) is None
    assert reader(name).read({"trace": None}) is None
    monkeypatch.setitem(seam._CACHE, "hand-made", seam.split(hand_made()))
    assert reader(name).read({"trace": {"timing": {"steps": 0}}}) is None


@pytest.mark.parametrize("name, want", [
    ("unqueued_ms_per_step.batch", 4.5), ("unqueued_max_ms.batch", 70.0)])
def test_the_program_span_readers_with_and_without_the_keys(name, want):
    timing = {"steps": 200, "unqueued_s": 0.9, "unqueued_in_step_s": 0.6,
              "unqueued_max_s": 0.07}
    assert reader(name).read({"timing": timing, "steps": 200}) == \
        pytest.approx(want)
    old = {"timing": {"steps": 200, "host_s": 0.6}, "steps": 200}
    assert reader(name).read(old) is None       # the parent's timing


def test_the_five_are_entries_of_four_serving_cells_at_least():
    """Four of the seven serving cells. The accepted test files pin the
    other three's lists, and a ``benchmark`` PR may edit those files where
    this one may not: ``test_mimo_cell.py::mimo_root`` holds MiMo's cell to
    28 names by count, ``test_sdar_cell.py`` holds SDAR's cell to its set by
    equality and wants MiMo's cell right behind Ling's in every ``.batch``
    list that names Ling's. The readers read those cells too (``PERF.md`` §5
    has the builder's numbers for all seven)."""
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    serving = next(m["workloads"] for m in bm["end_to_end"]
                   if m["name"] == "serve_tokens_per_s")
    names = set(TRACED) | {"unqueued_ms_per_step.batch",
                           "unqueued_max_ms.batch"}
    # by name and membership, so that a later entry or cell breaks nothing;
    # behind the first 50, where test_contract.py lets an entry name
    # Mixtral's cell
    mine = [m for m in bm["per_layer"] if m["name"] in names]
    assert {m["name"] for m in mine} == names and len(mine) == 5
    assert all(bm["per_layer"].index(m) >= 50 for m in mine)
    for m in mine:
        assert set(serving[:4]) <= set(m["workloads"]) <= set(serving)
        assert m["better"] == "lower"
        assert (m["unit"], m["moves"]) == ("ms", "serve_tokens_per_s")
        assert m["source"] == ("program_span" if m["name"].startswith(
            "unqueued") else "device_trace")
        assert m["layer"] == ("benchmark" if "frontend" in m["name"]
                              else "engine")
