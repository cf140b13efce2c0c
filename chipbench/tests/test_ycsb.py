"""The key generator against YCSB's own arithmetic."""
import numpy as np

from chipbench import ycsb


def java_fnvhash64(val: int) -> int:
    """``Utils.fnvhash64`` as YCSB writes it, on Java's signed longs."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) & (2**64 - 1)
    if h >= 2**63:
        h -= 2**64
    return abs(h)


def java_zipfian(u: float, items: int, theta: float, zetan: float) -> int:
    """``ZipfianGenerator.nextLong`` for one uniform draw ``u``."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1 - (2.0 / items) ** (1 - theta)) / (1 - zeta2 / zetan)
    uz = u * zetan
    if uz < 1.0:
        return 0
    if uz < 1.0 + 0.5 ** theta:
        return 1
    return int(items * (eta * u - eta + 1) ** alpha)


def test_fnvhash64_matches_java():
    vals = [0, 1, 2, 255, 256, 12345678901, ycsb.ITEM_COUNT, 2**62 + 7]
    got = ycsb.fnvhash64(np.array(vals, np.int64))
    assert got.tolist() == [java_fnvhash64(v) for v in vals]


def test_zipfian_ranks_match_java():
    rng = np.random.default_rng(7)
    u = np.concatenate([rng.random(2000), [0.0, 0.999999, 0.5]])
    got = ycsb.zipfian_ranks(u)
    items = ycsb.ITEM_COUNT + 1
    want = [java_zipfian(x, items, ycsb.THETA, ycsb.ZETAN) for x in u]
    assert got.tolist() == want


def test_zipfian_head_probabilities():
    # P(rank 0) = 1/zeta_n and P(rank 1) = 0.5**theta / zeta_n exactly.
    rng = np.random.default_rng(11)
    n = 400_000
    ranks = ycsb.zipfian_ranks(rng.random(n))
    for r, p in ((0, 1 / ycsb.ZETAN), (1, 0.5 ** ycsb.THETA / ycsb.ZETAN)):
        sigma = np.sqrt(p * (1 - p) / n)
        assert abs(np.mean(ranks == r) - p) < 5 * sigma


def test_zetan_is_zeta_of_ten_billion_items_head():
    # The constant is zeta(10**10, 0.99); its first million terms carry
    # most of it and the tail integral the rest.
    head = ycsb.zeta(10**6, ycsb.THETA)
    tail = ((10**10) ** 0.01 - (10**6) ** 0.01) / 0.01
    assert abs(head + tail - ycsb.ZETAN) < 1e-3 * ycsb.ZETAN


def test_scrambled_zipfian_stays_in_range_and_is_skewed():
    rng = np.random.default_rng(3)
    idx = ycsb.scrambled_zipfian(rng, 100_000, 419_430)
    assert idx.min() >= 0 and idx.max() < 419_430
    _, counts = np.unique(idx, return_counts=True)
    assert counts.max() > 0.03 * len(idx)       # the rank-0 item, ~3.8%
