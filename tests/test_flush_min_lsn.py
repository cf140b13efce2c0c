"""The log-triggered min-LSN flush keeps every acknowledged update.

A min-LSN flush takes the memory component's oldest table to L0. Any
older version of one of its keys left in a deeper memory level would
answer reads in its place, and would later be flushed into a newer L0
group and win the L0 merge: the acknowledged update would be lost. So
the flush takes every table of every memory level that overlaps its key
range, closing over the range as it widens.
"""
import numpy as np
import pytest

from repro.core.engine import get_backend
from repro.core.lsm.memtable import PartitionedMemComponent
from repro.core.lsm.sstable import reset_sst_ids
from repro.core.lsm.storage import LSMStore, StoreConfig

KB = 1 << 10
HOT = [0.4, 0.4] + [0.025] * 8         # 80% of the writes to 2 of 10 trees


def _store(backend, n_trees):
    """A tiny store whose log (4 KiB) is smaller than its write memory
    (8 KiB), so every flush is log-triggered; ``forced_flush_kind``
    "partial" makes each a min-LSN flush (the adaptive choice flushes in
    full until memory-triggered partial flushes fill its window)."""
    reset_sst_ids()
    cfg = StoreConfig(total_memory_bytes=64 * KB, write_memory_bytes=8 * KB,
                      sim_cache_bytes=4 * KB, page_bytes=512, entry_bytes=8,
                      active_sstable_bytes=128, sstable_bytes=2 * KB,
                      max_log_bytes=4 * KB, forced_flush_kind="partial",
                      backend=backend)
    store = LSMStore(cfg)
    names = [f"t{i}" for i in range(n_trees)]
    for n in names:
        store.create_tree(n)
    return store, names


@pytest.mark.parametrize("n_trees", [1, 10])
@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_log_triggered_flushes_keep_every_update(backend, n_trees):
    store, names = _store(backend, n_trees)
    rng = np.random.default_rng(0)
    share = np.asarray(HOT if n_trees == 10 else [1.0])
    oracle = [dict() for _ in names]
    for _ in range(300):
        t = int(rng.choice(n_trees, p=share))
        keys = rng.integers(0, 3000, 64)
        vals = rng.integers(1, 2**31 - 1, 64)
        store.write_batch(names[t], keys, vals)
        oracle[t].update(zip(keys.tolist(), vals.tolist()))
    assert store.disk.stats.flushes_log > 0
    assert store.disk.stats.flushes_mem == 0
    for t, name in enumerate(names):
        keys = np.array(sorted(oracle[t]), np.int64)
        found, got = store.read_batch(name, keys)
        want = np.array([oracle[t][k] for k in keys.tolist()])
        assert found.all()
        lost = keys[got != want]
        assert not len(lost), f"{name}: {len(lost)} updates lost"


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_min_lsn_flush_leaves_no_version_of_its_keys_in_memory(seed):
    reset_sst_ids()
    mem = PartitionedMemComponent(entry_bytes=8, page_bytes=256,
                                  active_bytes_max=256, size_ratio=3,
                                  backend=get_backend("numpy"))
    rng = np.random.default_rng(seed)
    lsn, flushes = 0, 0
    for i in range(120):
        keys = rng.integers(0, 2000, 32)
        mem.ingest_batch(keys, keys + i, lsn)
        lsn += 32 * 8
        if mem.over_active_limit():
            mem.seal_active()
            mem.maintain()
        if i % 7 == 6 and sum(1 for lvl in mem.levels if lvl) >= 2:
            newest = {}                      # shallowest level wins
            for lvl in reversed(mem.levels):
                for s in lvl:
                    newest.update(zip(s.keys.tolist(), s.vals.tolist()))
            ((fk, fv, _, _),) = mem.flush_min_lsn()
            flushes += 1
            left = [s.keys for lvl in mem.levels for s in lvl]
            if left:
                assert not np.isin(fk, np.concatenate(left)).any()
            assert fv.tolist() == [newest[k] for k in fk.tolist()]
    assert flushes >= 5
