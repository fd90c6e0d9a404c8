"""The traffic generator: the seed reorders a fixed set of batches."""
import json

import numpy as np
import pytest

from chip_bench import spec, traffic

SEED = 2**31 + 77


def _tr(name):
    with open(spec.BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("encdec", [False, True])
def test_same_seed_same_batches(encdec):
    a = traffic.build(_tr("flan-mix"), 50304, encdec, SEED, 20, 4)
    b = traffic.build(_tr("flan-mix"), 50304, encdec, SEED, 20, 4)
    for x, y in zip(a, b):
        assert np.array_equal(x.lengths, y.lengths)
        assert all(np.array_equal(s, t) for s, t in zip(x.tokens, y.tokens))


@pytest.mark.parametrize("encdec", [False, True])
def test_flan_mix_token_budget(encdec):
    tr = _tr("flan-mix")
    for gb in traffic.build(tr, 32128, encdec, SEED, 40, 4):
        total = gb.total_tokens
        assert 8192 <= total < 8192 + tr["max_len"]
        assert gb.lengths.sum(axis=1).max() <= tr["max_len"]
        assert all(len(t) == e + d for t, (e, d) in zip(gb.tokens,
                                                         gb.lengths))
        assert bool(gb.has_decoder) == encdec
        if encdec:
            assert gb.lengths[:, 1].min() >= 2 and gb.lengths[:, 1].max() <= 128


def test_uniform_512_all_at_512():
    for gb in traffic.build(_tr("uniform-512"), 50304, False, SEED, 12, 4):
        assert gb.lengths[:, 0].tolist() == [512] * 16
        assert gb.total_tokens == 8192


def test_seeds_share_the_set_of_batches():
    tr = _tr("flan-mix")
    n, warm, block = 4 + 3 * tr["order_block"], 4, tr["order_block"]

    def shapes(seed, lo, hi):
        gbs = traffic.build(tr, 50304, False, seed, n, warm)[lo:hi]
        return sorted(tuple(sorted(g.lengths[:, 0].tolist())) for g in gbs)
    for lo, hi in [(0, warm), (warm, warm + block), (warm + block, n)]:
        assert shapes(1, lo, hi) == shapes(SEED, lo, hi)
    one = traffic.build(tr, 50304, False, 1, n, warm)
    other = traffic.build(tr, 50304, False, SEED, n, warm)
    assert any(not np.array_equal(x.lengths, y.lengths)
               for x, y in zip(one, other))
    assert not np.array_equal(one[0].tokens[0], other[0].tokens[0]) \
        or not np.array_equal(one[0].lengths, other[0].lengths)
