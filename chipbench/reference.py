"""The plain reference and the comparison that decides ``correct``.

The reference is a key-value map with the store's stated semantics and
nothing of its machinery: the loaded records as sorted arrays, and a
dict per tree of every acknowledged Put, applied in submission order
(within a batch the last occurrence of a key wins). It imports nothing
of the program.

``replay`` walks the run's log -- the warm-up's Puts, then the window's
submits in order -- and counts the window's Get answers that differ from
the reference's, found flag or value. Its reference then holds the
final state, which answers every key read back after the window.

The control breaks the read guarantee the configuration states (a Get
sees the newest acknowledged Put): it answers as the reference would
with each Put applied one submit late, and loses the last Put batch.
It has to come out as not correct.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class Logged:
    """One submit of the run: a Put (``acked``: it landed) or a Get with
    the program's answers."""

    kind: str
    tree: int
    keys: np.ndarray
    vals: np.ndarray | None = None
    acked: bool = True
    found: np.ndarray | None = None
    got: np.ndarray | None = None


class Reference:
    """Sorted base records plus a dict of newer writes, per tree."""

    def __init__(self, record_keys, record_vals):
        self.base_keys = record_keys
        self.base_vals = record_vals
        self.newer = [dict() for _ in record_keys]

    def put(self, tree: int, keys, vals) -> None:
        self.newer[tree].update(zip(keys.tolist(), vals.tolist()))

    def get(self, tree: int, keys) -> tuple[np.ndarray, np.ndarray]:
        bk, bv = self.base_keys[tree], self.base_vals[tree]
        pos = np.minimum(np.searchsorted(bk, keys), len(bk) - 1)
        found = bk[pos] == keys
        vals = np.where(found, bv[pos], 0)
        newer = self.newer[tree]
        for i, k in enumerate(keys.tolist()):
            v = newer.get(k)
            if v is not None:
                found[i] = True
                vals[i] = v
        return found, vals


def wrong_answers(found, got, want_found, want_vals) -> int:
    """Answers whose found flag differs, or whose value differs where
    found."""
    found = np.asarray(found, bool)
    bad = (found != want_found) | (found & (np.asarray(got) != want_vals))
    return int(np.count_nonzero(bad))


@dataclass
class Replay:
    wrong_gets: int = 0
    gets_compared: int = 0
    reference: Reference | None = field(default=None, repr=False)
    # The control's own map, which answers the read-back in the
    # program's place; None when the program's answers are judged.
    control: Reference | None = field(default=None, repr=False)


def replay(record_keys, record_vals, warm: list, log: list[Logged], *,
           control: bool = False) -> Replay:
    """Run the reference over the warm-up's Puts and the window's log and
    count the window's Get answers that differ from it: the program's
    answers, or with ``control`` the control's (see the module
    docstring; its lateness spans the warm-up too, so it always loses a
    Put)."""
    ref = Reference(record_keys, record_vals)
    lag = Reference(record_keys, record_vals) if control else None
    out = Replay(reference=ref, control=lag)
    late = None                              # the control's held-back Put
    warm_ops = [Logged("put", t, k, v) for t, k, v in warm]
    for op in warm_ops + list(log):
        if op.kind == "put":
            if not op.acked:
                continue
            ref.put(op.tree, op.keys, op.vals)
            if lag is not None:
                if late is not None:
                    lag.put(*late)
                late = (op.tree, op.keys, op.vals)
            continue
        want_found, want_vals = ref.get(op.tree, op.keys)
        found, got = (op.found, op.got) if lag is None \
            else lag.get(op.tree, op.keys)
        out.wrong_gets += wrong_answers(found, got, want_found, want_vals)
        out.gets_compared += len(op.keys)
    # The control never applies its last held-back Put: it is lost.
    return out


def readback_keys(warm: list, log: list[Logged], n_trees: int,
                  rng: np.random.Generator, cap: int) -> list:
    """Per tree, sorted distinct keys of acknowledged Puts to read back:
    every key of the last acknowledged Put (the control loses it), and a
    sample of the others drawn from ``rng``, ``cap`` keys in all at most
    (every key where there are no more)."""
    puts = [(t, k) for t, k, _ in warm]
    puts += [(op.tree, op.keys) for op in log if op.kind == "put"
             and op.acked]
    if not puts:
        return [np.zeros(0, np.int64) for _ in range(n_trees)]
    last_tree, last_keys = puts[-1]
    per_tree = [[] for _ in range(n_trees)]
    for t, k in puts:
        per_tree[t].append(k)
    pool = [np.unique(np.concatenate(k)) if k else np.zeros(0, np.int64)
            for k in per_tree]
    last = np.unique(last_keys)
    pool[last_tree] = np.setdiff1d(pool[last_tree], last)
    sizes = np.array([len(p) for p in pool])
    room = max(0, cap - len(last))
    if sizes.sum() > room:
        # A sample spread over the trees by their share of the keys.
        take = np.floor(sizes / sizes.sum() * room).astype(np.int64)
        pool = [np.sort(rng.choice(p, m, replace=False))
                for p, m in zip(pool, take)]
    pool[last_tree] = np.union1d(pool[last_tree], last)
    return pool
