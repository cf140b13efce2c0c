"""Correctness tests for the LSM engine: paper's worked examples for the
grouped L0 (§4.1.2), dynamic levels (§4.1.3), flush policies (§4.2), and
end-to-end store reconciliation against a dict oracle."""
import numpy as np
import pytest

from repro.core.lsm.grouped_l0 import GroupedL0
from repro.core.lsm.levels import DiskLevels
from repro.core.lsm.sstable import merge_runs, sstable_from_run
from repro.core.lsm.storage import SCHEMES, LSMStore, StoreConfig

KB = 1 << 10
MB = 1 << 20


def sst(lo, hi, n=100, lsn=0):
    keys = np.linspace(lo, hi, n).astype(np.int64)
    keys = np.unique(keys)
    return sstable_from_run(keys, keys, lsn, lsn + n, entry_bytes=100,
                            page_bytes=4 * KB)


# --------------------------- merge_runs -----------------------------------
def test_merge_runs_newest_wins():
    newer = (np.array([1, 3, 5], np.int64), np.array([10, 30, 50], np.int64))
    older = (np.array([1, 2, 3], np.int64), np.array([-1, -2, -3], np.int64))
    keys, vals = merge_runs([newer, older])
    assert keys.tolist() == [1, 2, 3, 5]
    assert vals.tolist() == [10, -2, 30, 50]


# --------------------------- grouped L0 (§4.1.2) ---------------------------
def figure3_l0():
    l0 = GroupedL0()
    g0 = [sst(10, 30), sst(32, 55), sst(60, 80)]
    g1 = [sst(0, 23), sst(25, 50)]
    l0.groups = [sorted(g0, key=lambda s: s.min_key),
                 sorted(g1, key=lambda s: s.min_key)]
    return l0


def test_l0_insert_goes_to_oldest_group():
    # Paper: flushing 81-99 inserts into the (older) group 0.
    l0 = figure3_l0()
    l0.insert(sst(81, 99))
    assert l0.num_groups == 2
    assert any(s.min_key == 81 for s in l0.groups[0])


def test_l0_insert_creates_new_group_on_overlap():
    # Paper: flushing 25-53 creates a new group (25-50 overlaps in group 1).
    l0 = figure3_l0()
    l0.insert(sst(25, 53))
    assert l0.num_groups == 3
    assert any(s.min_key == 25 and s.max_key == 53 for s in l0.groups[2])


def test_l0_greedy_merge_selection_matches_paper():
    # Paper: group 1 selected (fewest SSTables); 0-23 chosen (ratio 1 < 4/3),
    # merged together with 10-30 from group 0 and L1 SSTables 0-15, 20-35.
    l0 = figure3_l0()
    l1 = [sst(0, 15), sst(20, 35), sst(37, 48), sst(50, 60)]
    tables, (a, b) = l0.pick_merge(l1, greedy=True)
    ranges = sorted((t.min_key, t.max_key) for t in tables)
    assert ranges == [(0, 23), (10, 30)]
    assert [t.min_key for t in l1[a:b]] == [0, 20]
    # The merge set is ordered newest group first for reconciliation.
    assert tables[0].min_key == 0  # from group 1 (newer)


def test_l0_nongreedy_takes_oldest_leftmost():
    l0 = figure3_l0()
    l1 = [sst(0, 15), sst(20, 35), sst(37, 48), sst(50, 60)]
    tables, _ = l0.pick_merge(l1, greedy=False)
    assert (10, 30) in [(t.min_key, t.max_key) for t in tables]


# --------------------------- dynamic levels (§4.1.3) ------------------------
def test_levels_add_l1_when_memory_shrinks():
    lv = DiskLevels(size_ratio=10)
    lv.levels = [[sst(0, 10_000, n=5000)]]     # one last level, 500KB
    # tiny write memory: |L1|max = 500KB > 10 * write_mem -> insert empty L1s
    lv.adjust(write_mem_bytes=4 * KB)
    assert lv.num_levels >= 2
    assert lv.levels[0] == []


def test_levels_delete_l1_waits_for_factor_f():
    lv = DiskLevels(size_ratio=10, shrink_factor=1.5)
    l2 = [sst(0, 10_000, n=5000)]              # last level: 500KB
    lv.levels = [[sst(0, 5000, n=400)], l2]    # L1: 40KB, |L1|max=50KB
    # write_mem*T slightly above |L2|max (=500KB) but below f*|L2|max
    lv.adjust(write_mem_bytes=51 * KB)
    assert not lv.deleting_l1
    # grows past f*|L2|max -> deletion scheduled
    lv.adjust(write_mem_bytes=80 * KB)
    assert lv.deleting_l1
    assert lv.l0_target_level() == 1           # Figure 4: L0 merges into L2
    # drain L1 and it disappears
    lv.levels[0] = []
    lv.adjust(write_mem_bytes=80 * KB)
    assert lv.num_levels == 1
    assert not lv.deleting_l1


# --------------------------- store end-to-end ------------------------------
def small_config(**kw):
    base = dict(total_memory_bytes=48 * MB, write_memory_bytes=8 * MB,
                sim_cache_bytes=2 * MB, page_bytes=4 * KB, entry_bytes=256,
                active_sstable_bytes=256 * KB, sstable_bytes=512 * KB,
                max_log_bytes=16 * MB, scheme="partitioned",
                flush_policy="opt")
    base.update(kw)
    return StoreConfig(**base)


@pytest.mark.parametrize("scheme", SCHEMES)
def test_store_reconciliation_oracle(scheme):
    rng = np.random.default_rng(42)
    store = LSMStore(small_config(scheme=scheme, write_memory_bytes=2 * MB,
                                  max_log_bytes=8 * MB))
    store.create_tree("t0")
    store.create_tree("t1")
    oracle = {"t0": {}, "t1": {}}
    for step in range(60):
        tree = "t0" if rng.random() < 0.7 else "t1"
        keys = rng.integers(0, 100_000, size=500)
        vals = rng.integers(0, 2**31, size=500)
        store.write(tree, keys, vals)
        for k, v in zip(keys.tolist(), vals.tolist()):
            oracle[tree][k] = v
    # every key readable with its newest value
    for tree, d in oracle.items():
        probe = rng.choice(list(d.keys()), size=300)
        for k in probe.tolist():
            found, val = store.lookup(tree, k)
            assert found, (tree, k)
            assert val == d[k], (tree, k)
    # absent keys stay absent
    for k in rng.integers(200_000, 300_000, size=100).tolist():
        found, _ = store.lookup("t0", k)
        assert not found
    # sane accounting
    st = store.disk.stats
    assert st.pages_flushed > 0
    assert st.entries_written == 60 * 500
    assert store.write_memory_used() <= store.write_memory_bytes * 1.05


def test_store_scan_counts():
    rng = np.random.default_rng(0)
    store = LSMStore(small_config())
    store.create_tree("t")
    keys = rng.permutation(np.arange(0, 50_000, dtype=np.int64))
    for i in range(0, len(keys), 1000):
        store.write("t", keys[i:i + 1000], keys[i:i + 1000])
    n = store.scan("t", 1000, 500)
    assert n >= 500  # all live keys in [1000, 1500) found (dense keyspace)


def test_log_truncation_bounds_log_length():
    store = LSMStore(small_config(max_log_bytes=4 * MB))
    store.create_tree("hot")
    store.create_tree("cold")
    store.write("cold", [1, 2, 3], [1, 2, 3])   # tiny, old LSN
    rng = np.random.default_rng(1)
    for _ in range(80):
        ks = rng.integers(0, 100_000, size=400)
        store.write("hot", ks, ks)
    assert store.log_length <= store.cfg.max_log_bytes
    assert store.disk.stats.flushes_log > 0


def test_flush_policy_selection():
    for policy, expect in [("mem", "big"), ("lsn", "old")]:
        store = LSMStore(small_config(flush_policy=policy,
                                      write_memory_bytes=8 * MB))
        big, old = store.create_tree("big"), store.create_tree("old")
        store.write("old", [0], [0])            # oldest LSN, tiny
        rng = np.random.default_rng(7)
        ks = rng.integers(0, 10**9, size=20_000)
        store.write("big", ks, ks, op=True)     # huge memory user
        t = store._pick_flush_tree()
        assert t.name == expect, policy


def test_scheduler_tick_flush_invariants():
    """After any scheduler tick: store min-LSN is monotone non-decreasing
    (log truncation can only advance), no key is lost across flush + L0
    merge, and write-memory usage respects the configured share."""
    rng = np.random.default_rng(12)
    store = LSMStore(small_config(write_memory_bytes=1 * MB,
                                  max_log_bytes=6 * MB))
    store.create_tree("x")
    store.create_tree("y")
    oracle = {"x": {}, "y": {}}
    INF = 2**62
    last_min_lsn = 0
    budget = store.cfg.mem_flush_threshold * store.write_memory_bytes
    for step in range(50):
        tree = "x" if rng.random() < 0.8 else "y"
        ks = rng.integers(0, 60_000, size=400)
        vs = rng.integers(0, 2**31, size=400)
        store.write_batch(tree, ks, vs, tick=False)
        oracle[tree].update(zip(ks.tolist(), vs.tolist()))
        rep = store.scheduler.tick()
        # min-LSN monotonicity: flushes only drain *old* entries
        m = store.min_lsn()
        assert m >= last_min_lsn, step
        # an empty store reports the INF sentinel; future entries log at
        # >= log_pos, so that's the effective floor
        last_min_lsn = store.log_pos if m >= INF else m
        # memory bound holds after every tick
        assert store.write_memory_used() <= budget * 1.05, step
        # default budget drains all merge debt every tick
        assert rep.carried_debt == 0, step
    assert store.disk.stats.pages_flushed > 0          # flushes happened
    assert store.disk.stats.pages_merge_written > 0    # L0 merges happened
    # no key loss across flush + L0 merge: every write still readable
    for tree, d in oracle.items():
        probe = np.fromiter(d.keys(), np.int64, len(d))
        found, vals = store.read_batch(tree, probe)
        assert found.all(), tree
        np.testing.assert_array_equal(
            vals, np.array([d[int(k)] for k in probe], np.int64))


def test_scheduler_bounded_merge_budget_carries_debt():
    """With a tiny per-tick merge budget, debt carries across ticks but
    mandatory memory/log enforcement still holds the memory bound."""
    rng = np.random.default_rng(3)
    store = LSMStore(small_config(write_memory_bytes=1 * MB,
                                  merge_budget=1))
    store.create_tree("t")
    saw_debt = False
    for _ in range(40):
        ks = rng.integers(0, 60_000, size=400)
        store.write_batch("t", ks, ks)
        saw_debt = saw_debt or store.scheduler.carried_debt > 0
        assert store.write_memory_used() \
            <= store.write_memory_bytes * 1.05
    assert saw_debt
    # engineer leftover debt: a big deferred batch, then one single-unit
    # tick -- flushes run (mandatory) but merge work stays owed
    store.write_batch("t", rng.integers(0, 60_000, size=4000),
                      np.zeros(4000, np.int64), tick=False)
    rep = store.scheduler.tick(merge_budget=1)
    assert rep.merge_steps == 1
    assert store.scheduler.carried_debt > 0
    # an explicit-None tick overrides the bounded default and drains it
    rep = store.scheduler.tick(merge_budget=None)
    assert rep.merge_steps > 0
    assert store.scheduler.carried_debt == 0


def test_no_inline_maintenance_outside_scheduler_tick():
    """With tick=False the write path must do no flush/merge work at all:
    the scheduler is the sole owner of maintenance."""
    store = LSMStore(small_config(write_memory_bytes=1 * MB))
    store.create_tree("t")
    rng = np.random.default_rng(0)
    for _ in range(30):
        ks = rng.integers(0, 60_000, size=400)
        store.write_batch("t", ks, ks, tick=False)
    st = store.disk.stats
    assert st.pages_flushed == 0 and st.pages_merge_written == 0
    assert store.write_memory_used() > store.write_memory_bytes  # over!
    store.scheduler.tick()
    assert store.write_memory_used() <= store.write_memory_bytes * 1.05
    assert store.disk.stats.pages_flushed > 0


def test_opt_policy_allocates_by_write_rate():
    """§4.2: under OPT, hot trees keep write memory share ~ write rate."""
    store = LSMStore(small_config(flush_policy="opt",
                                  write_memory_bytes=8 * MB))
    store.create_tree("hot")
    store.create_tree("cold")
    rng = np.random.default_rng(3)
    for i in range(300):
        tree = "hot" if i % 10 else "cold"      # 90/10 write split
        ks = rng.integers(0, 10**6, size=300)
        store.write(tree, ks, ks)
    hot = store.trees["hot"].mem_bytes
    cold = store.trees["cold"].mem_bytes
    assert hot > 2 * cold
