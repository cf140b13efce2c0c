"""The one traffic generator: turns a mix file and a seed into submits.

A mix file (``traffic/<mix>.json``) holds only parameters:

``batch``
    keys per submit;
``ops``
    share of submits of each kind (``get``, ``put``);
``trees``
    share of submits sent to each tree of the configuration, in its
    order, or ``"uniform"``;
``keys``
    the key distribution within a tree: ``{"dist": "zipfian"}``
    (YCSB's scrambled Zipfian, theta 0.99) or ``{"dist": "uniform"}``.

Submits come in blocks: the fewest submits in which every kind and every
tree gets a whole count at its share. Every block holds each in exactly
that count, in an order drawn from the seed, so that every seed gives
the same work.

Keys name records of the configuration (``record index -> key`` through
the loaded key arrays); Put values are fresh positive int32 payloads.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from fractions import Fraction

import numpy as np

from .ycsb import scrambled_zipfian

KINDS = ("get", "put")
VALUE_HI = 2**31 - 1          # payloads in [1, 2**31 - 2]: never a tombstone


@dataclass(frozen=True)
class Submit:
    """One closed-loop submit: a Get or a Put batch against one tree."""

    kind: str
    tree: int
    keys: np.ndarray
    vals: np.ndarray | None


MAX_BLOCK = 1000


def _fractions(shares) -> list[Fraction]:
    w = [Fraction(float(s)).limit_denominator(MAX_BLOCK) for s in shares]
    if any(f < 0 for f in w) or sum(w) <= 0:
        raise ValueError(f"shares must be >= 0 and not all 0: {shares}")
    return [f / sum(w) for f in w]


def block_size(*share_lists) -> int:
    """The fewest submits in which every share of every list is a whole
    count: the least common denominator of the shares. Raises where that
    passes ``MAX_BLOCK``, so that a mix says what it does."""
    block = math.lcm(*(f.denominator for shares in share_lists
                       for f in _fractions(shares)))
    if block > MAX_BLOCK:
        raise ValueError(f"shares {share_lists} need blocks of {block} "
                         f"submits, more than {MAX_BLOCK}")
    return block


def exact_counts(shares, block: int) -> np.ndarray:
    """Per-category counts for one block of ``block`` submits. Raises
    where a share is not a whole count of it."""
    want = [f * block for f in _fractions(shares)]
    if any(c.denominator != 1 for c in want):
        raise ValueError(f"shares {list(shares)} are not whole counts in a "
                         f"block of {block} submits")
    return np.array([int(c) for c in want], np.int64)


class TrafficMix:
    """A parsed mix file bound to one configuration's trees."""

    def __init__(self, mix: dict, n_trees: int):
        self.batch = int(mix["batch"])
        ops = mix["ops"]
        unknown = set(ops) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown op kinds {sorted(unknown)}")
        op_shares = [ops.get(k, 0.0) for k in KINDS]
        trees = mix.get("trees", "uniform")
        if trees == "uniform":
            trees = [1.0] * n_trees
        if len(trees) != n_trees:
            raise ValueError(f"mix gives shares for {len(trees)} trees, the "
                             f"configuration has {n_trees}")
        self.block = block_size(op_shares, trees)
        self.kind_counts = exact_counts(op_shares, self.block)
        self.tree_counts = exact_counts(trees, self.block)
        self.dist = mix["keys"]["dist"]
        if self.dist not in ("zipfian", "uniform"):
            raise ValueError(f"unknown key distribution {self.dist!r}")

    @property
    def kinds(self) -> tuple:
        """The op kinds this mix sends."""
        return tuple(k for k, c in zip(KINDS, self.kind_counts) if c)

    def record_indices(self, rng, n_records: int, size: int) -> np.ndarray:
        if self.dist == "zipfian":
            return scrambled_zipfian(rng, size, n_records)
        return rng.integers(0, n_records, size)

    def submits(self, rng: np.random.Generator, record_keys, *,
                puts_only: bool = False):
        """Endless submits for the trees whose loaded keys are
        ``record_keys`` (one sorted int64 array per tree). ``puts_only``
        keeps the trees and keys of the mix but makes every submit a Put
        (the set-up's warm-up)."""
        counts = self.kind_counts
        if puts_only:
            counts = np.where(np.arange(len(KINDS)) == KINDS.index("put"),
                              self.block, 0)
        kinds = np.repeat(np.arange(len(KINDS)), counts)
        trees = np.repeat(np.arange(len(self.tree_counts)), self.tree_counts)
        while True:
            for k, t in zip(rng.permutation(kinds), rng.permutation(trees)):
                keys = record_keys[t][self.record_indices(
                    rng, len(record_keys[t]), self.batch)]
                vals = None
                if KINDS[k] == "put":
                    vals = rng.integers(1, VALUE_HI, self.batch)
                yield Submit(KINDS[k], int(t), keys, vals)
