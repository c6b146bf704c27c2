"""The stratified generator: the seed decides the token ids, never how much
work a block holds nor in which order it comes."""

import collections
import itertools

import pytest

from benchmarks.traffic import generator

SEEDS = (0, 7, 2 ** 31 + 12345)


def _blocks(mix, seed, n_blocks=3):
    n = mix["block"]
    reqs = list(itertools.islice(
        generator.request_stream(mix, seed, 32000), n_blocks * n))
    return [reqs[b * n:(b + 1) * n] for b in range(n_blocks)]


@pytest.mark.parametrize("seed", SEEDS)
def test_every_block_is_the_same_multiset_for_any_seed(seed):
    mix = generator.load_mix("serve-batch")
    want = collections.Counter(generator.length_table(mix))
    for block in _blocks(mix, seed):
        got = collections.Counter((len(r.prompt), r.max_new) for r in block)
        assert got == want
        assert sum(len(r.prompt) for r in block) == sum(
            p for p, _ in want.elements())
        assert sum(r.max_new for r in block) == sum(
            o for _, o in want.elements())


def test_two_seeds_send_the_same_lengths_in_the_same_order():
    mix = generator.load_mix("serve-batch")
    a, b = _blocks(mix, SEEDS[1]), _blocks(mix, SEEDS[2])
    lengths = lambda blocks: [[(len(r.prompt), r.max_new) for r in blk]
                              for blk in blocks]
    assert lengths(a) == lengths(b)
    # the order is shuffled, block by block, by the mix's pair_seed
    assert lengths(a)[0] != lengths(a)[1]
    assert lengths(a)[0] != generator.length_table(mix)
    other = lengths(_blocks(dict(mix, pair_seed=mix["pair_seed"] + 1), 7))
    assert other[0] != lengths(a)[0]
    # the tokens are the seed's
    assert a[0][0].prompt != b[0][0].prompt


def test_the_same_seed_gives_the_same_requests():
    mix = generator.load_mix("serve-batch")
    a, b = _blocks(mix, SEEDS[2], 1)[0], _blocks(mix, SEEDS[2], 1)[0]
    assert [(r.index, r.prompt, r.max_new) for r in a] == \
        [(r.index, r.prompt, r.max_new) for r in b]


def test_the_table_is_what_the_mix_says():
    batch = generator.length_table(generator.load_mix("serve-batch"))
    assert len(batch) == 32
    assert min(p for p, _ in batch) >= 64
    assert max(p for p, _ in batch) == 2048 and min(o for _, o in batch) >= 32
    assert max(o for _, o in batch) <= 384


def test_quantiles_are_mid_quantiles_clipped():
    q = generator.lognormal_quantiles(100.0, 1.0, 10, 400, 4)
    assert q == sorted(q) and q[0] >= 10 and q[-1] <= 400
    assert q[1] < 100 < q[2]                 # the median sits between them
    assert generator.lognormal_quantiles(100.0, 5.0, 10, 400, 4)[-1] == 400
