"""The one general traffic generator: reads a mix's parameter file and makes,
from the seed, the requests of a run.

The seed decides the token ids and nothing else. The (prompt, output) lengths
of every block of ``block`` requests are the mix's table, and the ORDER of
block b is drawn from the mix's ``pair_seed`` and b: every seed sends the
same lengths in the same order, so a window holds the same work whatever the
seed (PERF.md, PR 24: with the order drawn from the seed, runs of one seed
agreed to 0.1 % and seeds differed by 2.4 %)."""

from __future__ import annotations

import json
import math
import pathlib
import random
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator

HERE = pathlib.Path(__file__).resolve().parent


def load_mix(name: str, root: pathlib.Path = HERE) -> dict:
    path = root / f"{name}.json"
    with open(path, encoding="utf-8") as f:
        mix = json.load(f)
    if "kind" not in mix:
        raise ValueError(f"{path}: a traffic mix names its 'kind'")
    return mix


def lognormal_quantiles(median: float, sigma: float, lo: int, hi: int,
                        n: int) -> list[int]:
    """The n mid-quantiles of lognormal(median, sigma), clipped to [lo, hi]."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(max(round(median * math.exp(sigma * z)), lo), hi)))
    return out


def length_table(mix: dict) -> list[tuple[int, int]]:
    """The block's (prompt, output) pairs: quantiles of the two lognormals,
    paired by a FIXED shuffle (the file's ``pair_seed``), so the table is a
    property of the mix and not of the run's seed."""
    n = mix["block"]
    p, o = mix["prompt"], mix["output"]
    prompts = lognormal_quantiles(p["median"], p["sigma"], p["min"], p["max"], n)
    outputs = lognormal_quantiles(o["median"], o["sigma"], o["min"], o["max"], n)
    random.Random(mix["pair_seed"]).shuffle(outputs)
    return list(zip(prompts, outputs))


@dataclass
class Planned:
    index: int
    prompt: list[int]
    max_new: int


def request_stream(mix: dict, seed: int, vocab: int) -> Iterator[Planned]:
    """Endless stream of requests. Block b's order comes from
    (``pair_seed``, b), its token ids from (seed, b)."""
    table = length_table(mix)
    index, b = 0, 0
    while True:
        order = list(range(len(table)))
        random.Random(mix["pair_seed"] * 1_000_003 + b).shuffle(order)
        rng = random.Random(seed * 1_000_003 + b)
        for k in order:
            plen, olen = table[k]
            prompt = [rng.randrange(1, vocab) for _ in range(plen)]
            yield Planned(index, prompt, olen)
            index += 1
        b += 1
