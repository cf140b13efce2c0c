"""YCSB's request distributions, vectorized.

A copy of the arithmetic of YCSB's ``ZipfianGenerator``,
``ScrambledZipfianGenerator`` and ``Utils.fnvhash64`` (Cooper et al.,
SoCC 2010; github.com/brianfrankcooper/YCSB, ``core/.../generator``), so
that a cell's key choice is the one YCSB's core workloads make:

* ``zipfian_ranks`` draws item ranks from YCSB's Zipfian over
  ``ITEM_COUNT`` items with the precomputed ``ZETAN`` for theta 0.99;
* ``scrambled_zipfian`` hashes each rank with FNV-1a 64 and folds it onto
  ``[0, n)``, which spreads the popular items over the key space.
"""
from __future__ import annotations

import numpy as np

# ScrambledZipfianGenerator's constants: it draws from a Zipfian over ten
# billion items whose zeta(n, 0.99) is precomputed, whatever n it folds to.
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302
THETA = 0.99

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def zeta(n: int, theta: float) -> float:
    """sum_{i=1..n} 1 / i**theta (YCSB ``ZipfianGenerator.zetastatic``)."""
    i = np.arange(1, n + 1, dtype=np.float64)
    return float(np.sum(1.0 / i ** theta))


def zipfian_ranks(u: np.ndarray, *, items: int = ITEM_COUNT + 1,
                  theta: float = THETA, zetan: float = ZETAN) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextLong`` for uniform draws ``u`` in [0, 1).

    ``items`` is the generator's item count: YCSB builds the scrambled
    generator as ``ZipfianGenerator(0, ITEM_COUNT)``, whose count is
    ``ITEM_COUNT + 1``. Rank 0 is the most popular item."""
    u = np.asarray(u, np.float64)
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    ranks = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    ranks = np.where(uz < 1.0 + 0.5 ** theta, 1, ranks)
    return np.where(uz < 1.0, 0, ranks)


def fnvhash64(vals) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1a over the 8 little-endian octets of
    each value, then ``Math.abs`` of the signed result."""
    v = np.asarray(vals, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    for _ in range(8):
        h = (h ^ (v & np.uint64(0xFF))) * prime
        v = v >> np.uint64(8)
    s = h.view(np.int64)
    # Java's Math.abs leaves Long.MIN_VALUE negative; so does this.
    return np.where(s < 0, -s, s)


def scrambled_zipfian(rng: np.random.Generator, size: int,
                      n: int) -> np.ndarray:
    """``size`` record indices in [0, n) drawn as YCSB's
    ``ScrambledZipfianGenerator(0, n - 1)`` draws them."""
    # numpy's % is never negative, so the Long.MIN_VALUE corner folds
    # onto [0, n) too.
    return fnvhash64(zipfian_ranks(rng.random(size))) % n
