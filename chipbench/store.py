"""A cell's set-up: records from the seed, installed into the store.

The records go straight into each tree's last level (a copy of
``benchmarks/common.py``'s ``bulk_load``, kept here so that no change to
the program moves the yardstick), and the store's own ``DiskLevels``
then adds its levels. A fixed count of updates through
``StorageService.submit`` forms the memory component, L0 and the upper
levels by the program's own flush and merge policy, so a seed always
gives the same layout.
"""
from __future__ import annotations

import numpy as np

from .traffic import VALUE_HI

KEY_SPACE = 2**31 - 1            # the kernels' int32 key domain


def tree_names(config: dict) -> list[str]:
    return [f"t{i}" for i in range(int(config["trees"]))]


def distinct_keys(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` distinct keys drawn uniformly from ``[0, KEY_SPACE)``, sorted:
    draws with repeats, then drops a random surplus of the distinct ones
    (every ``n``-subset is as likely)."""
    keys = np.zeros(0, np.int64)
    while len(keys) < n:
        more = rng.integers(0, KEY_SPACE, n - len(keys) + n // 50 + 64)
        keys = np.unique(np.concatenate([keys, more]))
    drop = rng.choice(len(keys), len(keys) - n, replace=False)
    return np.delete(keys, drop)


def record_data(config: dict, rng: np.random.Generator):
    """Per tree: ``recordcount`` distinct sorted keys and their values."""
    n = int(config["recordcount"])
    keys, vals = [], []
    for _ in tree_names(config):
        keys.append(distinct_keys(rng, n))
        vals.append(rng.integers(1, VALUE_HI, n))
    return keys, vals


def open_service(config: dict):
    from repro.core import StorageService, StoreConfig
    svc = StorageService.open(StoreConfig(**config["store"]))
    for name in tree_names(config):
        svc.create_tree(name)
    return svc


def install_last_level(store, tree: str, keys, vals) -> None:
    """Install sorted ``keys``/``vals`` as the tree's only disk level, cut
    into SSTables of the configured size; no I/O is accounted."""
    from repro.core.lsm.sstable import partition_run
    t = store.trees[tree]
    t.levels.levels = [partition_run(keys, vals, 0, 0, t.entry_bytes,
                                     store.cfg.page_bytes,
                                     store.cfg.sstable_bytes)]
    t.levels.adjust(store.cfg.active_sstable_bytes)


def disk_tables(store) -> list:
    return [t for tree in store.trees.values()
            for tier in tree.l0.lookup_tiers() + tree.levels.lookup_tiers()
            for t in tier]


def build_blooms(store) -> int:
    """Build every disk table's Bloom filter through the backend (the
    tree memoizes it for the table's life). Returns tables built."""
    n = 0
    for tree in store.trees.values():
        for tier in tree.l0.lookup_tiers() + tree.levels.lookup_tiers():
            for t in tier:
                tree._bloom(t)
                n += 1
    return n


def warm_up(svc, submits, n_updates: int, names: list[str]) -> list:
    """Put ``n_updates`` keys through ``submit`` in the mix's batches, then
    drain. Returns the acknowledged ``(tree, keys, vals)`` in order."""
    from repro.core import Put
    done, acked = 0, []
    while done < n_updates:
        s = next(submits)
        svc.submit_strict([Put(names[s.tree], s.keys, s.vals)])
        acked.append((s.tree, s.keys, s.vals))
        done += len(s.keys)
    svc.drain()
    return acked


def warm_reads(svc, submits, names: list[str], max_batches: int = 8) -> int:
    """Serve Get batches of the cell's shape until every tree's store view
    is resident (a batch went through the one-launch store probe).
    Returns the batches served."""
    from repro.core import Get
    pool = svc.store.device_pool
    pending = set(range(len(names)))
    served = 0
    while pending and served < max_batches * len(names):
        s = next(submits)
        if s.tree not in pending:
            continue
        hits = pool.store_hits
        svc.submit([Get(names[s.tree], s.keys)])
        served += 1
        if pool.store_hits > hits:
            pending.discard(s.tree)
    return served


def _held(sorted_keys: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Which of ``keys`` are in ``sorted_keys``."""
    if not len(sorted_keys):
        return np.zeros(len(keys), bool)
    pos = np.minimum(np.searchsorted(sorted_keys, keys), len(sorted_keys) - 1)
    return sorted_keys[pos] == keys


def memory_keys(tree) -> np.ndarray:
    """Sorted keys the tree's partitioned memory component holds: its
    active table's and every sealed table's."""
    mem = tree.mem
    parts = [np.fromiter(mem.active.keys(), np.int64, len(mem.active))]
    parts += [s.keys for lvl in mem.levels for s in lvl]
    return np.unique(np.concatenate(parts))


def warm_unresolved(svc, submits, names: list[str], record_keys,
                    batch: int, rng: np.random.Generator,
                    samples: int = 512, sds: float = 6.0) -> int:
    """Serve one Get batch for each count of queries the memory component
    can leave unresolved, so that the store probe has met each count
    before the window (its results are sliced to that count on the
    device, one program per count).

    The counts come from ``samples`` of the mix's own Get batches per
    tree, checked against the keys the memory component holds: every
    count within ``sds`` standard deviations of their mean. A batch with
    ``m`` keys the memory component holds and ``batch - m`` it does not
    leaves ``batch - m`` to the probe. Returns the batches served."""
    from repro.core import Get
    hits = {t: [] for t in range(len(names))}
    in_mem = [memory_keys(svc.store.trees[n]) for n in names]
    drawn = 0
    while min(len(h) for h in hits.values()) < samples \
            and drawn < 64 * samples * len(names):
        s = next(submits)
        drawn += 1
        if s.kind == "get" and len(hits[s.tree]) < samples:
            hits[s.tree].append(_held(in_mem[s.tree], s.keys).sum())
    served = 0
    for t, name in enumerate(names):
        if not hits[t]:
            continue
        h = np.asarray(hits[t], np.float64)
        lo = max(0, int(np.floor(h.mean() - sds * h.std())) - 1)
        hi = min(batch, int(np.ceil(h.mean() + sds * h.std())) + 1)
        if not len(in_mem[t]):
            lo = hi = 0
        outside = record_keys[t][~_held(in_mem[t], record_keys[t])]
        for m in range(lo, hi + 1):
            q = np.concatenate([rng.choice(in_mem[t], m) if m else
                                np.zeros(0, np.int64),
                                rng.choice(outside, batch - m)])
            svc.submit([Get(name, rng.permutation(q))])
            served += 1
    return served


def _pow2s(lo: int, hi: int) -> list[int]:
    out, n = [], lo
    while n <= hi:
        out.append(n)
        n *= 2
    return out


def table_entries(config: dict, batch: int) -> int:
    """The most entries one table can hold: a disk or memory table, or an
    active table sealed one batch past its limit."""
    s = config["store"]
    return max(s["sstable_bytes"], s["active_sstable_bytes"]) \
        // s["entry_bytes"] + batch


def warm_merges(backend, config: dict, batch: int) -> int:
    """Run one merge of two sorted runs for every pair of padded sizes a
    flush or merge can fold (the merge program compiles once per pair):
    the accumulated run up to twice the write memory's entries, the next
    run up to one table's. Returns the merges run."""
    s = config["store"]
    acc = _pow2s(16, 2 * s["write_memory_bytes"] // s["entry_bytes"])
    nxt = _pow2s(16, 2 * table_entries(config, batch))
    n = 0
    for a in acc:
        for b in nxt:
            ka = np.arange(a, dtype=np.int64) * 2
            kb = np.arange(b, dtype=np.int64) * 2 + 1
            backend.merge_runs([(ka, ka + 1), (kb, kb + 1)])
            n += 1
    return n


def warm_searches(backend, config: dict, batch: int) -> int:
    """Run one search of a sorted run for every pair of padded sizes the
    memory component's lookups can make: a run up to one table's entries,
    queries up to one batch. Returns the searches run."""
    n = 0
    for r in _pow2s(16, 2 * table_entries(config, batch)):
        run = np.arange(r, dtype=np.int64) * 2
        for q in _pow2s(16, batch):
            backend.lookup_batch(run, run[:q] + 1)
            n += 1
    return n
