"""``benchmarks/trace/host_spans.py`` and the six readers of the engine's own
spans and counters: on hand-made inputs whose answers are known, and on a
small trace recorded on the chip."""

import json

import pytest

from benchmarks.harness.cell import Cell
from benchmarks.trace import host_spans
from tests.benchmark.conftest import REPO

MS = 1_000_000
CELL = "mixtral-8x7b.serve-batch"


def reader(name):
    return Cell.find(CELL).reader(name)


def hand_made():
    """One turn of the benchmark's loop: bench.generate 0-10, then an engine
    step 10-100 with a prefill (run 20-50) and a decode window (build 60-64,
    run 64-90). The device runs 22-48 (the prefill program), 51-52 (the
    sampler, launched in prefill/sample) and 66-90 (the decode program). It
    is idle 48-51 (2 ms in prefill/run, 1 in prefill/sample), 52-66 (3 ms in
    prefill/sample, 1 in admit's own time, 4 in the part of orion/step that
    no child covers, 4 in decode/build, 2 in decode/run before the program
    starts) and from 90 to the next turn's first operation at 112 (9 ms in
    orion/step, 1 at the edge of bench.engine_step, 10 in bench.generate,
    2 in no span at all)."""
    host = [
        ["bench.generate", 0, 10 * MS],
        ["bench.engine_step", 10 * MS, 90 * MS],
        ["orion/step", 11 * MS, 88 * MS],
        ["orion/admit", 12 * MS, 44 * MS],
        ["orion/prefill/run", 20 * MS, 30 * MS],
        ["orion/prefill/sample", 50 * MS, 5 * MS],
        ["orion/decode/build", 60 * MS, 4 * MS],
        ["orion/decode/run", 64 * MS, 26 * MS],
        ["bench.generate", 100 * MS, 10 * MS],
        ["not/ours", 0, 200 * MS],
    ]
    ops = [["fusion.1", 22 * MS, 26 * MS], ["fusion.2", 51 * MS, 1 * MS],
           ["paged_decode.3_custom-call_bf16_32_64_128_", 66 * MS, 24 * MS],
           ["fusion.4", 112 * MS, 1 * MS]]
    modules = [["jit__unknown(1)", 22 * MS, 26 * MS],
               ["jit_sample(2)", 51 * MS, 1 * MS],
               ["jit__unknown(3)", 66 * MS, 24 * MS],
               ["jit__unknown(1)", 112 * MS, 1 * MS]]
    return {"devices": {"0": {"XLA Ops": ops, "XLA Modules": modules}},
            "host": [h for h in host if h[0].startswith(host_spans.PREFIXES)]}


def test_idle_time_goes_to_the_innermost_span_and_programs_to_their_run_span():
    got = host_spans.attribute(hand_made())
    assert got["idle_by_span"] == {
        "orion/prefill/run": pytest.approx(0.002),
        "orion/prefill/sample": pytest.approx(0.004),
        "orion/admit": pytest.approx(0.001),
        "orion/step": pytest.approx(0.013),
        "orion/decode/build": pytest.approx(0.004),
        "orion/decode/run": pytest.approx(0.002),
        "bench.engine_step": pytest.approx(0.001),
        "bench.generate": pytest.approx(0.010),
        host_spans.OUTSIDE: pytest.approx(0.002),
    }
    assert got["idle_s"] == pytest.approx(0.039)
    # the sampler ran inside prefill/sample and the last program after the
    # step: neither belongs to a run span
    assert got["run_module_s"] == {
        "orion/prefill/run": pytest.approx(0.026),
        "orion/decode/run": pytest.approx(0.024),
    }
    left = host_spans.unattributed(got["idle_by_span"])
    assert set(left) == {"orion/step", "bench.engine_step", "bench.generate",
                         host_spans.OUTSIDE}


def test_a_longer_leaf_takes_what_its_parent_had():
    events = hand_made()
    events["host"] = [h if h[0] != "orion/decode/build"
                      else ["orion/decode/build", 56 * MS, 8 * MS]
                      for h in events["host"]]
    got = host_spans.attribute(events)
    assert got["idle_by_span"]["orion/decode/build"] == pytest.approx(0.008)
    assert got["idle_by_span"]["orion/step"] == pytest.approx(0.009)


def test_a_program_the_device_clock_starts_early_keeps_its_run_span():
    """The v5e profile's device clock ran 0.7-0.9 ms ahead of the host's: a
    decode program 'starts' inside decode/build. It belongs to the run span
    it overlaps most."""
    events = hand_made()
    events["devices"]["0"]["XLA Modules"][2] = [
        "jit__unknown(3)", 63 * MS, 26 * MS]
    got = host_spans.attribute(events)
    assert got["run_module_s"]["orion/decode/run"] == pytest.approx(0.026)


def test_a_program_without_the_spans_gives_nothing():
    events = hand_made()
    events["host"] = [h for h in events["host"] if h[0].startswith("bench.")]
    assert host_spans.attribute(events) is None
    assert host_spans.for_obs({"trace": None}) is None


def test_recorded_v5e_trace_with_the_engines_spans():
    events = json.loads(
        (REPO / "tests/benchmark/data/trace_serve_spans_v5e.json").read_text())
    names = {name for name, _, _ in events["host"]}
    assert {"bench.engine_step", "orion/step", "orion/prefill/run",
            "orion/decode/run", "orion/decode/build"} <= names
    got = host_spans.attribute(events)
    ops = events["devices"]["0"]["XLA Ops"]
    span = (max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)) / 1e9
    assert 0 < got["idle_s"] < span
    assert all(k.startswith(("orion/", "bench.", "host:"))
               for k in got["idle_by_span"])
    # the programs of a prefill and of a decode window are told apart by
    # the span that launched them, and the paged kernel by its name
    assert got["run_module_s"]["orion/prefill/run"] > 0
    assert got["run_module_s"]["orion/decode/run"] > 0
    assert any(name.startswith("paged_decode.") for name, _, _ in ops)
    # the old reduction reads the same dict
    from benchmarks.trace import reduce

    assert reduce.reduce(events, window_s=span)["busy_s"] <= span


# -- the readers, each on a hand-made obs --------------------------------------

TIMING = {
    "steps": 10, "host_s": 0.030, "prefill_s": 0.400, "device_s": 0.600,
    "spill_s": 0.0, "restore_s": 0.0, "page_in_s": 0.0,
    "reap_s": 0.002, "admit_s": 0.006, "prefill_run_s": 0.380,
    "decode_run_s": 0.590, "verify_run_s": 0.0, "mixed_device_s": 0.0,
    "prefill_tokens": 3000, "prefill_pad_tokens": 1000,
    "decode_kv_tokens": 2_000_000,
}
HF = {"num_hidden_layers": 4, "num_key_value_heads": 8,
      "num_attention_heads": 32, "hidden_size": 4096}


def test_span_and_counter_readers():
    obs = {"timing": TIMING, "steps": 10}
    assert reader("admit_ms_per_step.batch").read(obs) == pytest.approx(0.8)
    # 1.030 s of steps less 0.970 s inside run spans, over 10 steps
    assert reader("engine_gap_ms_per_step.batch").read(obs) == pytest.approx(6.0)
    assert reader("prefill_pad_pct.batch").read(obs) == pytest.approx(25.0)


def test_readers_leave_the_metric_out_on_a_program_without_the_keys():
    old = {"timing": {"steps": 10, "host_s": 0.03, "prefill_s": 0.4,
                      "device_s": 0.6}, "steps": 10, "trace": None}
    for name in ("admit_ms_per_step.batch", "engine_gap_ms_per_step.batch",
                 "prefill_pad_pct.batch", "paged_decode_roofline.batch",
                 "prefill_device_ms_per_ktoken.batch",
                 "idle_unattributed_pct.batch"):
        assert reader(name).read(old) is None, name


def test_paged_decode_bytes_and_roofline():
    mod = reader("paged_decode_roofline.batch")
    # one position: K and V x 8 kv heads x 128 x 2 bytes = 4096 B a layer
    assert mod.kv_bytes(HF, 1) == 4 * 4096
    assert mod.kv_bytes(dict(HF, head_dim=64), 10) == 10 * 4 * 2 * 8 * 64 * 2
    obs = {"config": HF, "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"timing": TIMING,
                     "op_s": {"paged_decode.66_custom-call_bf16_32_64_128_": 0.4,
                              "closed_call.9_custom-call_bf16_32_1_4096_": 9.0}}}
    least = 2_000_000 * 4 * 4096 / 819e9
    assert mod.read(obs) == pytest.approx(100 * least / 0.4)
    obs["trace"]["op_s"].pop("paged_decode.66_custom-call_bf16_32_64_128_")
    assert mod.read(obs) is None          # the parent's unnamed kernel


def test_trace_readers_on_the_hand_made_trace(monkeypatch, capsys):
    got = host_spans.attribute(hand_made())
    monkeypatch.setattr(host_spans, "newest_trace", lambda: "hand-made")
    monkeypatch.setitem(host_spans._CACHE, "hand-made", got)
    obs = {"trace": {"timing": dict(TIMING, prefill_tokens=2000)}}
    # 26 ms of prefill programs for 2000 real positions
    assert reader("prefill_device_ms_per_ktoken.batch").read(obs) == \
        pytest.approx(13.0)
    # 13 ms in orion/step, 1 + 10 ms in the benchmark's spans, 2 in none
    assert reader("idle_unattributed_pct.batch").read(obs) == \
        pytest.approx(100 * 26 / 39)
    host_spans.say(got, TIMING)
    out = capsys.readouterr().out.splitlines()
    assert out[1].split()[0] == "orion/step"          # largest first
    assert "inside bench.engine_step: 0.0270 s idle, 0.0040 s of it" in out[-1]
    assert "0.0600 s over 10 steps" in out[-1]
