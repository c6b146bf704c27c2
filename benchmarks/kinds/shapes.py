"""The closed set of prefill shapes a serve cell can ask the engine for,
computed from the traffic file and the configuration ALONE.

The engine pads a burst of admissions to (rows to the next power of two) x
(the largest row's bucket of ``inference.prefill_chunk`` tokens); one jit
specialisation per pair. Decode is one shape ([decode_window, max_batch]).
The front end (``take_burst``) never hands the engine a burst whose padded
size exceeds ``prefill_token_budget``, so the set below is every pair that
can occur, and warm-up compiles each of them once."""

from __future__ import annotations

from typing import Iterable, Sequence


def bucket_len(n: int, chunk: int, max_seq_len: int) -> int:
    return min(-(-n // chunk) * chunk, max_seq_len)


def pow2_ceil(n: int) -> int:
    return 1 << (n - 1).bit_length()


def prefill_shapes(prompt_lens: Iterable[int], probe_lens: Iterable[int],
                   chunk: int, max_seq_len: int, budget: int,
                   max_batch: int) -> list[tuple[int, int]]:
    buckets = sorted({bucket_len(n, chunk, max_seq_len) for n in prompt_lens})
    if buckets[-1] > budget:
        raise ValueError(
            f"prefill_token_budget={budget} is under the largest bucket "
            f"{buckets[-1]}: that request could never be admitted"
        )
    shapes = set()
    for s in buckets:
        nb = 1
        while nb <= pow2_ceil(max_batch) and nb * s <= budget:
            shapes.add((nb, s))
            nb *= 2
    for n in probe_lens:      # probes go alone
        shapes.add((1, bucket_len(n, chunk, max_seq_len)))
    return sorted(shapes)


def take_burst(prompt_lens: Sequence[int], free_slots: int, chunk: int,
               max_seq_len: int, budget: int) -> int:
    """How many of the queued requests (in order) go to the engine now: the
    longest prefix that fits the free slots and whose padded prefill stays
    within the budget."""
    n, widest = 0, 0
    for plen in prompt_lens[:free_slots]:
        w = max(widest, bucket_len(plen, chunk, max_seq_len))
        if pow2_ceil(n + 1) * w > budget:
            break
        n, widest = n + 1, w
    return n
