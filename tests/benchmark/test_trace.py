"""The reduction from a trace to numbers: on a hand-made trace whose answer
is known, and on a small trace recorded on the chip."""

import json

import pytest

from benchmarks.trace import reduce
from tests.benchmark.conftest import REPO

MS = 1_000_000


def test_busy_gaps_and_leaves_on_a_hand_made_trace():
    ops = [
        ["while.1_while_s32_", 0, 50 * MS],          # container of the next two
        ["fusion.1_fusion_bf16_8_32_", 0, 20 * MS],
        ["fusion.2_fusion_bf16_8_32_", 30 * MS, 20 * MS],   # 10 ms gap before
        ["copy.3_copy_f32_4_", 70 * MS, 10 * MS],           # 20 ms gap before
    ]
    host = [["bench.engine_step", 0, 60 * MS], ["bench.observe", 60 * MS, 15 * MS]]
    events = {"devices": {"0": {
        "XLA Ops": ops,
        "XLA Modules": [["jit__unknown(1)", 0, 50 * MS],
                        ["jit__unknown(1)", 70 * MS, 10 * MS],
                        ["jit__unknown(7)", 81 * MS, 30 * MS],
                        ["jit__argmax(2)", 80 * MS, 0]]}},
        "host": host}
    out = reduce.reduce(events, window_s=0.1)
    # the while covers 0-50, so the union is 0-50 and 70-80: 60 ms busy
    assert out["busy_s"] == pytest.approx(0.060)
    assert out["window_s"] == 0.1
    # the container is not counted among the operations
    assert "while.1_while_s32_" not in out["op_s"]
    assert out["op_s"]["fusion.1_fusion_bf16_8_32_"] == pytest.approx(0.020)
    # programs are told apart by their fingerprint
    assert out["module_s"]["jit__unknown(1)"] == pytest.approx(0.060)
    assert out["module_n"]["jit__unknown(1)"] == 2
    # the decode program is the one that ran most often, not the longest
    from benchmarks.metrics.lib import decode_program, decode_step_ms
    obs = {"trace": out, "decode_window": 4}
    assert decode_program(obs) == (pytest.approx(0.060), 2)
    assert decode_step_ms(obs) == pytest.approx(1e3 * 0.060 / (2 * 4))
    # the one gap of the merged intervals (50-70) lies in engine_step at its
    # middle (60 is the boundary: observe starts there)
    assert out["breakdown"]["idle_gaps"] == [["bench.observe", pytest.approx(0.020)]]
    assert out["breakdown"]["device_ops"][0][1] == pytest.approx(0.020)


def test_union_merges_overlaps():
    assert reduce.union([(0, 5), (3, 8), (10, 12), (12, 13)]) == [(0, 8), (10, 13)]
    assert reduce.union([]) == []


def test_short_names_are_stable_across_remat_and_clone_suffixes():
    a = reduce.short_name('%fusion.12.remat2.clone = bf16[8,32]{1,0} fusion(x), kind=kLoop')
    b = reduce.short_name('%fusion.12 = bf16[8,32]{1,0:T(8,128)} fusion(y), kind=kLoop')
    assert a == b == "fusion.12_fusion_bf16_8_32_"
    assert reduce.short_name(
        '%attention.114 = (bf16[1,8,8192,128]{3,2,1,0}, bf16[1,8]{1,0}) '
        'custom-call(bf16[1,32,8192,128]{3,2,1,0} %p), custom_call_target="tpu_custom_call"'
    ) == "attention.114_custom-call_bf16_1_8_8192_128_"
    assert reduce.short_name("while.3") == "while.3"


def test_a_trace_without_device_work_is_refused():
    with pytest.raises(RuntimeError):
        reduce.reduce({"devices": {}, "host": []}, 1.0)
    with pytest.raises(RuntimeError):
        reduce.reduce({"devices": {"0": {"XLA Ops": []}}, "host": []}, 1.0)


def test_recorded_v5e_trace():
    events = json.loads(
        (REPO / "tests/benchmark/data/trace_serve_batch_v5e.json").read_text())
    ops = events["devices"]["0"]["XLA Ops"]
    span = (max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)) / 1e9
    out = reduce.reduce(events, window_s=span)
    assert 0 < out["busy_s"] <= span
    # leaf operations never add up to more than the busy time
    assert sum(out["op_s"].values()) <= out["busy_s"] * 1.0001
    assert not any(k.startswith("while") for k in out["op_s"])
    assert len(out["breakdown"]["device_ops"]) == 10
    # the expert feed-forward of a prefill is found by its shape
    assert any("_14336_" in k for k in out["op_s"])
    # what the host was doing in the gaps is one of the benchmark's spans
    for name, seconds in out["breakdown"]["idle_gaps"]:
        assert seconds > 0
        assert name in reduce.BENCH_SPANS or name.startswith("host:")


@pytest.mark.parametrize("modules, want", [
    # today: the engine jits partials, every program is "unknown"; the decode
    # window is the one that ran most often
    ({"jit__unknown(1)": (0.9, 3), "jit__unknown(2)": (4.0, 20),
      "jit__argmax(3)": (0.1, 40)}, (4.0, 20)),
    # once the program names its jits: the name wins over the count, and
    # two fingerprints of that name are one program
    ({"jit__unknown(1)": (0.9, 30), "jit_decode_window(2)": (4.0, 20),
      "jit_decode_window(7)": (1.0, 5), "jit_prefill_step(3)": (2.0, 9)},
     (5.0, 25)),
    ({"jit__argmax(3)": (0.1, 40)}, None),
])
def test_the_decode_program_is_found_by_its_name_or_by_its_count(modules, want):
    from benchmarks.metrics import lib

    obs = {"decode_window": 8, "trace": {
        "module_s": {k: s for k, (s, _) in modules.items()},
        "module_n": {k: n for k, (_, n) in modules.items()}}}
    assert lib.decode_program(obs) == want
    if want:
        assert lib.decode_step_ms(obs) == pytest.approx(
            1e3 * want[0] / (want[1] * 8))
    assert lib.decode_program({"trace": None}) is None


def test_prefill_expert_roofline_on_the_recorded_gmm_operations():
    """The arithmetic, by hand, on what a v5e recorded: 51,050 routed rows a
    layer x 4 layers x 6 D F over 197 TFLOP/s is 0.3652 s of work at peak;
    the 288 grouped matmuls of the segment took 0.7679 s."""
    from benchmarks.harness.cell import Cell
    from benchmarks.harness.device import PEAKS

    cell = Cell.find("mixtral-8x7b.serve-batch")
    rec = json.loads(
        (REPO / "tests/benchmark/data/trace_prefill_gmm_v5e.json").read_text())
    tr = reduce.reduce(rec, rec["window_s"])
    tr["timing"] = rec["timing"]
    obs = {"trace": tr, "config": cell.config, "peaks": PEAKS["TPU v5 lite"]}
    read = cell.reader("prefill_expert_roofline.batch").read
    got = read(obs)
    seconds = sum(d for _, _, d in rec["devices"]["0"]["XLA Ops"]) / 1e9
    assert len(rec["devices"]["0"]["XLA Ops"]) == 288
    assert seconds == pytest.approx(0.767931238)
    flop = 4 * 51050 * 6 * 4096 * 14336
    assert got == pytest.approx(100 * flop / 197e12 / seconds)
    assert got == pytest.approx(rec["read"]) and 40 < got < 100
    # nothing to read: no trace, no counter (a program before PR 26), no
    # kernel of that name in the segment
    assert read(dict(obs, trace=None)) is None
    assert read(dict(obs, trace=dict(tr, timing={}))) is None
    assert read(dict(obs, trace=dict(tr, op_s={"fusion.1": 1.0}))) is None
