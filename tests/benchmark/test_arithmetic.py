"""Percentiles, inter-token gaps, FLOP and byte counts on hand-computed
shapes, and the front end's burst rule."""

import numpy as np
import pytest

from benchmarks.harness.stats import emission_gaps, percentile
from benchmarks.kinds import shapes
from benchmarks.metrics import flops


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 95) == 95
    assert percentile(xs, 50) == 50
    assert percentile([3.0], 95) == 3.0
    assert percentile([1, 2, 3, 4], 95) == 4
    with pytest.raises(ValueError):
        percentile([], 95)


def test_a_fused_window_gives_one_gap_and_zeros():
    # first token at t=1.0, then windows of 4 tokens at 1.5 and 2.1
    gaps = emission_gaps([(1.0, 1), (1.5, 4), (2.1, 4)])
    assert [g for _, g in gaps] == pytest.approx(
        [0.5, 0, 0, 0, 0.6, 0, 0, 0])
    assert [t for t, _ in gaps] == [1.5] * 4 + [2.1] * 4
    # a first delivery of several tokens: the first is TTFT's
    assert [g for _, g in emission_gaps([(1.0, 3)])] == [0.0, 0.0]
    assert emission_gaps([]) == []


def test_attended_pairs_under_mask_and_window():
    assert flops.attended_pairs(4) == 10                    # 1+2+3+4
    assert flops.attended_pairs(4, window=2) == 1 + 2 + 2 + 2
    assert flops.attended_pairs(4, window=8) == 10
    # 8192 under a 4096 window: the masked count is well under S^2 / 2
    assert flops.attended_pairs(8192, 4096) == 4096 * 4097 // 2 + 4096 * 4096


HF = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
          intermediate_size=16, num_hidden_layers=3, vocab_size=10)


def test_forward_flops_by_hand():
    # per layer: q 2*8*8, k and v 2*8*4 each, o 2*8*8, mlp 3 * 2*8*16
    layer = 128 + 64 + 64 + 128 + 768
    assert flops.forward_matmul_flops_per_token(HF) == 3 * layer + 2 * 8 * 10
    moe = dict(HF, num_local_experts=4, num_experts_per_tok=2)
    layer_moe = 128 + 64 + 64 + 128 + 2 * 768 + 2 * 8 * 4
    assert flops.forward_matmul_flops_per_token(moe) == 3 * layer_moe + 160


def test_attention_and_train_flops_by_hand():
    # S=4, head_dim 4, 2 heads, 3 layers: 10 pairs * (2*4 + 2*4) per head
    assert flops.attention_flops(HF, 4) == 3 * 2 * 10 * 16
    per_token = flops.forward_matmul_flops_per_token(HF) + 960 / 4
    assert flops.train_flops_per_token(HF, 4) == 3 * per_token
    assert flops.flash_fwd_bwd_flops(HF, 4, batch=2) == 3 * 2 * 960


def test_bytes_by_hand():
    moe = dict(HF, num_local_experts=4)
    assert flops.expert_weight_bytes(moe) == 3 * 4 * 3 * 8 * 16 * 2
    # 5 (token, expert) rows in each of 3 layers, three matmuls of 2*8*16
    assert flops.expert_matmul_flops(moe, 5) == 3 * 5 * 3 * 2 * 8 * 16


def test_mistral_masked_count_against_the_unmasked_one():
    hf = dict(hidden_size=4096, num_attention_heads=32, num_key_value_heads=8,
              intermediate_size=14336, num_hidden_layers=3, vocab_size=32000,
              sliding_window=4096)
    masked = flops.attention_flops(hf, 8192) / 8192
    unmasked = 3 * 32 * 128 * 4 * 8192          # the trainer's 12 L H hd S / 3
    assert 2.5 < unmasked / masked < 2.8        # the 2.7x of PERF.md


def test_burst_rule_and_shape_set():
    take = lambda lens, free=32: shapes.take_burst(lens, free, 512, 8192, 2048)
    assert take([100, 100, 100, 100, 100]) == 4         # 4 x 512
    assert take([100, 600, 100]) == 2                   # 2 x 1024; a 3rd pads to 4
    assert take([2000, 100]) == 1
    assert take([100, 2000]) == 1
    assert take([100, 100], free=1) == 1
    assert take([], free=4) == 0
    got = shapes.prefill_shapes([100, 600, 2000], [40], 512, 8192, 2048, 32)
    assert got == [(1, 512), (1, 1024), (1, 2048), (2, 512), (2, 1024), (4, 512)]
    with pytest.raises(ValueError):
        shapes.prefill_shapes([3000], [], 512, 8192, 2048, 32)
    # whatever the front end lets through is in the set
    import random
    rng = random.Random(0)
    allowed = set(shapes.prefill_shapes(range(1, 2049), [], 512, 8192, 2048, 32))
    for _ in range(500):
        lens = [rng.randrange(1, 2049) for _ in range(rng.randrange(1, 12))]
        n = take(lens, free=rng.randrange(1, 33))
        assert n >= 1
        s_pad = max(shapes.bucket_len(x, 512, 8192) for x in lens[:n])
        assert (shapes.pow2_ceil(n), s_pad) in allowed


def test_the_judged_logit_error_is_the_worst_probes_median_clear_of_a_tie():
    from benchmarks.kinds import serve

    name = "logit_rel_err_worst_probe_median_clear"
    numbers = {"probe": [0, 0, 0, 1, 1, 1],
               "err": [0.03, 0.9, 0.05, 0.04, 0.06, 0.8],
               "control_err": [0.2] * 6,
               "margin": [0.5, 0.01, 0.3, float("inf"), 0.2, 0.02],
               "window_kv_rel_err": [0.0, 0.002], "window_token_gap": [0.0, 0.0]}
    got = serve.judged(numbers, margin_min=0.1)
    assert got[name] == pytest.approx(0.05)       # medians 0.04 and 0.05
    assert got["window_kv_rel_err_max"] == 0.002
    assert got["clear_positions_per_probe"] == [2, 2]
    # without the rule the positions at a tie move a probe's median
    assert serve.judged(numbers, 0.0)[name] == pytest.approx(0.06)
    # the rule asks the reference alone: the control is held to it too
    assert serve.judged(numbers, 0.1, errs="control_err")[name] == 0.2
    # a fault confined to ONE probe shows
    one_bad = dict(numbers, err=[0.03, 0.9, 0.05, 0.5, 0.6, 0.8])
    assert serve.judged(one_bad, 0.1)[name] == pytest.approx(0.55)
    limits = {name: 0.06, "window_kv_rel_err_max": 0.01,
              "window_token_gap_max": 0.01}
    assert serve.decide(numbers, {"router_margin_min": 0.1, "limits": limits})[0]
    assert not serve.decide(one_bad, {"router_margin_min": 0.1,
                                      "limits": limits})[0]
    # the two steadier numbers (PR 43): each probe's lower octile, the worst
    # probe's; the median over all probes' clear positions together
    got = serve.judged(numbers, 0.0)
    assert got["logit_rel_err_worst_probe_octile_clear"] == pytest.approx(
        0.04 + 0.25 * 0.02)            # [0.04, 0.06, 0.8] at 1/8; [0.03, ...]
    assert got["logit_rel_err_all_probes_median_clear"] == pytest.approx(0.055)
    nothing_clear = serve.judged(
        dict(numbers, margin=[0.0] * 3 + [1.0] * 3), 0.1)
    assert all(np.isnan(v) for k, v in nothing_clear.items()
               if k.startswith("logit_"))
    # a probe with nothing clear of a tie has no number: not correct
    ok, checks = serve.decide(dict(numbers, margin=[0.0] * 3 + [1.0] * 3),
                              {"router_margin_min": 0.1, "limits": limits})
    assert not ok


def test_spread_is_the_quartile_distance_over_the_median():
    from benchmarks import tools

    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    import statistics
    q = statistics.quantiles(vals, n=4)
    assert tools.spread(vals) == pytest.approx((q[2] - q[0]) / 102.5)
