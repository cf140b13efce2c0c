"""The traffic generator: every seed gets the same work."""
import numpy as np
import pytest

from chipbench.traffic import TrafficMix, block_size, exact_counts


def _keys(trees, n=1000):
    return [np.arange(n, dtype=np.int64) * 7 + t for t in range(trees)]


def test_block_holds_exact_shares():
    mix = TrafficMix({"batch": 8, "ops": {"put": 1.0},
                      "trees": [0.4, 0.4] + [0.025] * 8,
                      "keys": {"dist": "zipfian"}}, 10)
    assert mix.block == 40
    subs = mix.submits(np.random.default_rng(5), _keys(10))
    trees = [next(subs).tree for _ in range(80)]
    for block in (trees[:40], trees[40:]):
        assert np.bincount(block, minlength=10).tolist() == \
            [16, 16] + [1] * 8


def test_seeds_differ_in_order_not_in_counts():
    mix = TrafficMix({"batch": 8,
                      "ops": {"get": 0.95, "put": 0.05},
                      "keys": {"dist": "zipfian"}}, 1)
    kinds = []
    for seed in (1, 2**31 + 99):
        subs = mix.submits(np.random.default_rng(seed), _keys(1))
        kinds.append([next(subs).kind for _ in range(100)])
    assert kinds[0] != kinds[1]
    assert kinds[0].count("put") == kinds[1].count("put") == 5


def test_same_seed_same_submits():
    mix = TrafficMix({"batch": 16,
                      "ops": {"get": 0.5, "put": 0.5},
                      "keys": {"dist": "zipfian"}}, 1)
    a = mix.submits(np.random.default_rng(9), _keys(1))
    b = mix.submits(np.random.default_rng(9), _keys(1))
    for _ in range(10):
        x, y = next(a), next(b)
        assert x.kind == y.kind and np.array_equal(x.keys, y.keys)


@pytest.mark.parametrize("shares,block", [
    (([1.0], [1.0]), 1),
    (([0.95, 0.05], [1.0]), 20),
    (([0.5, 0.5], [0.4, 0.4] + [0.025] * 8), 40),
    (([2, 1], [1.0]), 3),
])
def test_block_is_the_least_common_denominator(shares, block):
    assert block_size(*shares) == block
    for s in shares:
        assert exact_counts(s, block).sum() == block


def test_inexact_share_is_refused():
    with pytest.raises(ValueError):
        exact_counts([0.95, 0.05], 10)
    with pytest.raises(ValueError):
        block_size([1, 6], [1, 10], [1, 12])        # 7 * 11 * 13
