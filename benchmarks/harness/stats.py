"""Percentile and inter-token-gap arithmetic (the arithmetic of
``orion_tpu.metrics.LatencyStats`` and ``tools/serving_latency_bench.py``;
the benchmark keeps its own so a program change cannot move the yardstick)."""

from __future__ import annotations

import math
from typing import Iterable, Sequence


def percentile(samples: Sequence[float], p: float) -> float:
    """Nearest-rank percentile, ``p`` in [0, 100]. Raises on no samples: a
    tail of nothing is not 0."""
    if not samples:
        raise ValueError("percentile of no samples")
    s = sorted(samples)
    rank = max(math.ceil(p / 100.0 * len(s)) - 1, 0)
    return s[min(rank, len(s) - 1)]


def emission_gaps(
    emissions: Iterable[tuple[float, int]]
) -> list[tuple[float, float]]:
    """Gaps between tokens as a streaming client gets them, as (time of the
    delivery, gap) pairs.

    ``emissions`` are one request's (time, tokens delivered at that time)
    pairs in order; the first pair carries the first token. Every token
    after the first gives one sample: a delivery of n tokens at once is one
    gap since the previous delivery and n - 1 zeros (a fused decode window
    of W hands the client W tokens together)."""
    gaps: list[tuple[float, float]] = []
    prev = None
    for t, n in emissions:
        if n <= 0:
            continue
        if prev is not None:
            gaps.append((t, t - prev))
            n -= 1
        else:
            n -= 1          # the first token is TTFT's, not a gap
        gaps.extend([(t, 0.0)] * n)
        prev = t
    return gaps
