"""Overlapped maintenance & durability: the prepare/apply determinism
contract, paced flush slices, pacer autotune, and async group commit.

The tentpole claim of ``engine/workers.py``: background workers change
*when wall-clock time is spent*, never what the store contains. Concretely:

  1. ``maintenance_workers=0`` is bit-identical to not having the pool at
     all (no threads, ``take`` computes inline);
  2. any worker count and any worker completion order yields a store equal
     to the inline one in everything except the timing-only IOStats
     (``bg_segments`` / ``bg_overlap_us`` / ``fsync_wait_us``), and its
     WAL replays bit-identically -- the PR-7 interleaving fuzzer's
     invariants with workers on;
  3. paced flush slices (``pacer_flush_threshold``) are a pure function
     of store state + config, so they replay and they are identical with
     workers on or off (``flush_slices`` is deliberately NOT masked);
  4. ``StallGovernor`` converges onto the pacer's knobs without touching
     ``StoreConfig`` (recovery re-paces from configuration);
  5. async group commit preserves ack/sync semantics (``all_durable``,
     barrier ``sync()``) while moving the fsync off the foreground.

CI runs this file on numpy and pallas-interpret via the overlap-parity
job; the SIGKILL side of the contract lives in ``test_crash_kill.py``.
"""
import numpy as np
import pytest

from repro.core.durability import recover
from repro.core.engine.workers import MaintenanceWorkerPool
from repro.core.lsm.storage import StoreConfig
from repro.core.service import Put, ServiceConfig, StorageService
from repro.core.service.governor import MemoryPlan, StallGovernor
from repro.core.shard import ShardedStore
from repro.runtime.latency import LatencyHistogram

from test_differential import KB
from test_recovery import exact_counters, sharded_fingerprint
from test_scheduler_interleave import (TREES, build, gen_schedule,
                                       run_schedule, small_config, state_of)

# IOStats fields that legitimately differ with workers on: they report
# where wall-clock time went, which thread scheduling decides. Everything
# else -- including flush_slices -- must be bit-identical.
TIMING_FIELDS = ("bg_segments", "bg_overlap_us", "fsync_wait_us")


def masked_state(store):
    fp, stats, log_pos, debt = state_of(store)
    return (fp, {k: v for k, v in stats.items()
                 if k not in TIMING_FIELDS}, log_pos, debt)


# ------------------------- worker pool unit behavior ---------------------------
def test_pool_workers_zero_is_inert():
    pool = MaintenanceWorkerPool(0)
    assert not pool.enabled
    assert pool.submit("k", lambda: 1) is False
    assert pool.take("k", lambda: 41 + 1) == 42
    assert pool._threads == [] and pool.submitted == 0
    assert pool.hits == 0 and pool.misses == 0   # inert, not "missing"


def test_pool_rejects_negative_workers():
    with pytest.raises(ValueError, match="workers"):
        MaintenanceWorkerPool(-1)


def test_pool_prepare_hit_and_stats():
    class FakeStats:
        bg_segments = 0
        bg_overlap_us = 0.0
    st = FakeStats()
    pool = MaintenanceWorkerPool(2, stats=st)
    assert pool.submit("a", lambda: np.arange(5) * 2)
    assert not pool.submit("a", lambda: None)    # dedup by key
    pool.drain()
    out = pool.take("a", lambda: pytest.fail("should consume the prepare"))
    np.testing.assert_array_equal(out, np.arange(5) * 2)
    assert pool.hits == 1 and st.bg_segments == 1
    assert st.bg_overlap_us > 0.0
    # consumed: a second take recomputes inline
    assert pool.take("a", lambda: "inline") == "inline"
    assert pool.misses == 1
    pool.close()


def test_pool_cancels_unstarted_and_surfaces_errors_as_misses():
    pool = MaintenanceWorkerPool(1)

    def boom():
        raise RuntimeError("prepare failed")
    pool.submit("bad", boom)
    pool.drain()
    # the worker swallowed the error; take falls back to fn() inline
    assert pool.take("bad", lambda: "fallback") == "fallback"
    assert pool.misses == 1
    pool.close()
    # a closed pool computes inline and refuses submits
    assert not pool.enabled
    assert pool.take("x", lambda: 7) == 7
    assert pool.submit("x", lambda: 8) is False
    pool.close()                                 # idempotent


def test_pool_eviction_counts_wasted():
    pool = MaintenanceWorkerPool(1, max_prepared=2)
    for i in range(4):
        pool.submit(("k", i), lambda i=i: i)
    pool.drain()
    assert pool.prepared == 4
    assert pool.wasted == 2                      # oldest two evicted
    assert pool.take(("k", 3), lambda: None) == 3
    pool.close()
    assert pool.wasted == 3                      # the unconsumed survivor


# --------------------- fuzzer invariants with workers on -----------------------
def worker_config(**kw):
    return small_config(maintenance_workers=2, **kw)


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_interleaved_schedule_with_workers_equals_inline(seed, shards):
    """The PR-7 fuzzer schedules, run with a 2-worker pool: store state,
    log, debt and all non-timing IOStats equal the inline run; replay
    (which never consults the pool with a warm key) is bit-identical."""
    events = gen_schedule(seed)
    inline, oracle = run_schedule(small_config(), events, shards)
    overl, _ = run_schedule(worker_config(), events, shards)
    assert masked_state(overl) == masked_state(inline), \
        f"seed {seed}: workers changed logical state"
    # worker-enabled run is itself deterministic modulo timing fields
    again, _ = run_schedule(worker_config(), events, shards)
    assert masked_state(again) == masked_state(overl)
    # replay determinism with workers on (recovered store worker-enabled)
    rec = recover(worker_config(), overl.wal.clone(), overl.manifest.clone())
    assert sharded_fingerprint(rec) == sharded_fingerprint(overl)
    assert exact_counters(rec) == exact_counters(overl)
    assert rec.log_pos == overl.log_pos
    # both answer the oracle identically
    for t, d in oracle.items():
        ks = np.fromiter(d.keys(), np.int64, len(d))
        if not len(ks):
            continue
        fi, vi = inline.read_batch(t, ks)
        fo, vo = overl.read_batch(t, ks)
        np.testing.assert_array_equal(fi, fo)
        np.testing.assert_array_equal(vi[fi], vo[fo])
    overl.arena.workers.close()
    again.arena.workers.close()


@pytest.mark.parametrize("drain_between", [False, True],
                         ids=["racing", "forced-complete"])
def test_worker_completion_order_is_immaterial(drain_between):
    """Two extreme completion schedules -- prepares racing the apply step
    vs every prepare forced to finish first (``drain`` between events) --
    bracket all interleavings; both must equal the inline store."""
    events = gen_schedule(seed=5)
    inline, _ = run_schedule(small_config(), events, shards=1)
    store = build(worker_config(), 1)
    from test_scheduler_interleave import apply_event
    oracle = {t: {} for t in TREES}
    for ev in events:
        apply_event(store, ev, oracle)
        if drain_between:
            store.arena.workers.drain()
    assert masked_state(store) == masked_state(inline)
    if drain_between:
        # forced-complete maximizes overlap consumption: prepares did land
        assert store.arena.workers.prepared > 0
    store.arena.workers.close()


def test_worker_overlap_actually_consumed():
    """The counters are not decorative: a mixed paced run with workers on
    consumes prepares -- bloom builds submitted at merge write-out are
    taken by the read path (bg_segments > 0, bg_overlap_us > 0)."""
    cfg = worker_config(pacer_interval_bytes=16 * KB,
                        pacer_segment_budget=1)
    svc = StorageService(ShardedStore(cfg, shards=1),
                         config=ServiceConfig(admission=False))
    for t in TREES:
        svc.create_tree(t)
    rng = np.random.default_rng(3)
    for i in range(40):
        ks = rng.integers(0, 2000, 250)
        svc.submit([Put(TREES[0], ks, ks + 1)])
        if i % 4 == 3:
            svc.store.arena.workers.drain()      # let prepares land
            svc.store.read_batch(TREES[0], rng.integers(0, 2000, 100))
    svc.drain()
    st = svc.store.disk.stats
    pool = svc.store.arena.workers
    assert pool.submitted > 0
    assert st.bg_segments == pool.hits
    assert st.bg_segments > 0, "no prepare was ever consumed"
    assert st.bg_overlap_us > 0.0
    pool.close()


# --------------------------- paced flush slices --------------------------------
def fill_to(store, frac, rng):
    """Write until shared write memory exceeds ``frac`` of the budget."""
    guard = 0
    while store.write_memory_used() <= frac * store.write_memory_bytes:
        ks = rng.integers(0, 2000, 60)
        store.write_batch(TREES[0], ks, ks + 1, tick=False)
        guard += 1
        assert guard < 2000


def test_flush_slice_fires_between_thresholds():
    cfg = small_config(pacer_flush_threshold=0.5)
    store = build(cfg, 1)
    rng = np.random.default_rng(9)
    # below the proactive threshold: the mem segment does nothing
    rep = store.scheduler.run_segment("mem")
    assert rep.flushes == 0 and store.disk.stats.flush_slices == 0
    # between proactive (0.5) and hard (0.95): exactly ONE slice
    fill_to(store, 0.55, rng)
    assert store.write_memory_used() \
        <= cfg.mem_flush_threshold * store.write_memory_bytes
    rep = store.scheduler.run_segment("mem")
    assert rep.flushes == 1
    assert store.disk.stats.flush_slices == 1
    # the slice did real work: usage dropped below the proactive line
    # (partitioned flush_partial seals + emits at least one SSTable)
    assert store.write_memory_used() < 0.55 * store.write_memory_bytes


def test_flush_slice_skipped_when_hard_threshold_flushed():
    """A mem segment that already paid a hard-threshold flush never adds
    a proactive slice on top (flush-averse, like the pacer's deferral)."""
    cfg = small_config(pacer_flush_threshold=0.5)
    store = build(cfg, 1)
    fill_to(store, 1.0, np.random.default_rng(9))
    rep = store.scheduler.run_segment("mem")
    assert rep.flushes >= 1
    assert store.disk.stats.flush_slices == 0


def test_flush_slices_replay_and_match_inline_workers():
    """Slices are store-state-pure: the same schedule with the threshold
    on replays bit-identically, and workers do not change slice counts
    (flush_slices is NOT a masked field)."""
    cfg = small_config(pacer_flush_threshold=0.3)
    events = gen_schedule(seed=1)
    store, _ = run_schedule(cfg, events, shards=4)
    assert store.disk.stats.flush_slices > 0, \
        "schedule never exercised a flush slice"
    rec = recover(cfg, store.wal.clone(), store.manifest.clone())
    assert sharded_fingerprint(rec) == sharded_fingerprint(store)
    assert rec.disk.stats.flush_slices == store.disk.stats.flush_slices
    withw, _ = run_schedule(small_config(pacer_flush_threshold=0.3,
                                         maintenance_workers=2),
                            events, shards=4)
    assert masked_state(withw) == masked_state(store)
    withw.arena.workers.close()


def test_flush_slices_defer_like_merge_slices():
    """Through the pacer, a flush slice counts as this pass's flush: the
    merge slice defers (flush-averse), exactly as for hard flushes."""
    cfg = small_config(pacer_flush_threshold=0.5,
                       pacer_interval_bytes=8 * KB, pacer_segment_budget=2)
    svc = StorageService(ShardedStore(cfg, shards=1),
                         config=ServiceConfig(admission=False))
    for t in TREES:
        svc.create_tree(t)
    store = svc.store
    rng = np.random.default_rng(9)
    fill_to(store, 0.55, rng)
    before = svc.pacer.deferrals
    rep = svc.pacer.on_submit(64 * KB)           # slice due, but it flushed
    assert rep.flushes == 1                      # ... via the flush slice
    assert store.disk.stats.flush_slices >= 1
    assert svc.pacer.deferrals == before + 1


# ----------------------------- pacer autotune ----------------------------------
class _StubService:
    """Minimum surface StallGovernor reads: pacer knobs, the stall
    histogram, and the op counter that gates its cycles."""

    class _Disk:
        class _Stats:
            ops = 0
        stats = _Stats()

    class _Store:
        def __init__(self):
            self.disk = _StubService._Disk()

    class _Pacer:
        def __init__(self):
            self.interval_bytes = 64 * KB
            self.segment_budget = 8

    def __init__(self):
        self.pacer = self._Pacer()
        self.stall = LatencyHistogram()
        self.store = self._Store()

    def cycle(self, gov, stall_us, n=4):
        """Advance one governor cycle observing ``n`` stalls of
        ``stall_us`` and actuate like StorageService._apply_plan."""
        for _ in range(n):
            self.stall.record(stall_us)
        self.store.disk.stats.ops += gov.ops_cycle
        plan = gov.observe(self)
        if plan is not None:
            if plan.pacer_interval_bytes is not None:
                self.pacer.interval_bytes = plan.pacer_interval_bytes
            if plan.pacer_segment_budget is not None:
                self.pacer.segment_budget = plan.pacer_segment_budget
        return plan


def test_stall_governor_tightens_to_convergence():
    """Sustained over-target stalls: the budget halves to 1, then the
    interval doubles to its cap -- and a converged governor goes quiet."""
    svc = _StubService()
    gov = StallGovernor(target_stall_us=1000.0, ops_cycle=8,
                        max_interval_bytes=256 * KB)
    assert svc.cycle(gov, 50_000.0) is None      # first cycle = snapshot
    budgets, intervals = [], []
    for _ in range(10):
        svc.cycle(gov, 50_000.0)
        budgets.append(svc.pacer.segment_budget)
        intervals.append(svc.pacer.interval_bytes)
    assert budgets[:3] == [4, 2, 1]              # slices shrink first
    assert svc.pacer.segment_budget == 1
    assert svc.pacer.interval_bytes == 256 * KB  # then slices spread out
    # at both caps there is nothing left to move: no further plans
    assert svc.cycle(gov, 50_000.0) is None
    assert all(r["stall_max_us"] > 1000 for r in gov.records)


def test_stall_governor_deadband_and_dwell():
    """In-band stalls hold the knobs; a direction reversal needs
    ``min_dwell`` consecutive cycles (held reversals are recorded)."""
    svc = _StubService()
    gov = StallGovernor(target_stall_us=1000.0, ops_cycle=8,
                        deadband=0.25, min_dwell=2)
    svc.cycle(gov, 2000.0)                       # snapshot
    svc.cycle(gov, 2000.0)                       # tighten: budget 8 -> 4
    assert svc.pacer.segment_budget == 4
    svc.cycle(gov, 1100.0)                       # in-band: hold
    assert svc.pacer.segment_budget == 4
    assert svc.cycle(gov, 500.0) is None         # reversal #1: held
    assert gov.records[-1]["held"] is True
    assert svc.pacer.segment_budget == 4
    svc.cycle(gov, 500.0)                        # reversal #2: acts
    assert (svc.pacer.interval_bytes, svc.pacer.segment_budget) \
        != (64 * KB, 4)


def test_stall_governor_relaxes_interval_before_budget():
    svc = _StubService()
    gov = StallGovernor(target_stall_us=1000.0, ops_cycle=8,
                        min_interval_bytes=16 * KB, max_segment_budget=32)
    svc.cycle(gov, 100.0)                        # snapshot
    svc.cycle(gov, 100.0)                        # 64K -> 32K
    assert (svc.pacer.interval_bytes, svc.pacer.segment_budget) \
        == (32 * KB, 8)
    svc.cycle(gov, 100.0)                        # floor at 16K
    assert svc.pacer.interval_bytes == 16 * KB
    assert svc.pacer.segment_budget == 8         # budget untouched so far
    svc.cycle(gov, 100.0)                        # then budget grows
    assert svc.pacer.segment_budget == 16


def test_autotune_wires_into_service_and_spares_config():
    """``pacer_autotune=True`` builds the governor; its plans move the
    LIVE pacer only -- StoreConfig keeps the configured knobs, so a
    recovered service re-paces from configuration."""
    cfg = small_config(pacer_interval_bytes=32 * KB,
                       pacer_segment_budget=4, pacer_autotune=True)
    svc = StorageService(ShardedStore(cfg, shards=1),
                         config=ServiceConfig(admission=False))
    assert svc.stall_governor is not None
    off = StorageService(ShardedStore(small_config(
        pacer_interval_bytes=32 * KB), shards=1))
    assert off.stall_governor is None
    svc._apply_plan(MemoryPlan(pacer_interval_bytes=8 * KB,
                               pacer_segment_budget=1, note="test"))
    assert (svc.pacer.interval_bytes, svc.pacer.segment_budget) \
        == (8 * KB, 1)
    assert (cfg.pacer_interval_bytes, cfg.pacer_segment_budget) \
        == (32 * KB, 4)


def test_autotune_converges_on_live_service():
    """End-to-end: a write-heavy paced run with autotune on emits plans
    and every actuated value stays within the governor's bounds."""
    cfg = small_config(pacer_interval_bytes=16 * KB,
                       pacer_segment_budget=8, pacer_autotune=True)
    svc = StorageService(ShardedStore(cfg, shards=1),
                         config=ServiceConfig(admission=False))
    for t in TREES:
        svc.create_tree(t)
    svc.stall_governor.ops_cycle = 256           # act often in a short run
    svc.stall_governor.target_stall_us = 50.0    # unreachably tight:
    rng = np.random.default_rng(13)              # guaranteed tightening
    for _ in range(60):
        ks = rng.integers(0, 2000, 200)
        svc.submit([Put(TREES[0], ks, ks + 3)])
    gov = svc.stall_governor
    assert gov.records, "governor never acted"
    assert any(p.note.startswith("pacer:") for p in svc.plans)
    assert svc.pacer.segment_budget <= 8
    assert gov.min_segment_budget <= svc.pacer.segment_budget
    assert svc.pacer.interval_bytes <= gov.max_interval_bytes


# --------------------------- async group commit --------------------------------
def _files_cfg(tmp_path, name, **kw):
    return small_config(storage_medium="files",
                        storage_dir=str(tmp_path / name),
                        fsync_policy="group", **kw)


def _drive_files(cfg, n=30):
    from repro.core.lsm.sstable import reset_sst_ids
    reset_sst_ids()
    store = ShardedStore(cfg, shards=1)
    for t in TREES:
        store.create_tree(t)
    rng = np.random.default_rng(21)
    for _ in range(n):
        ks = rng.integers(0, 2000, 200)
        store.write_batch(TREES[0], ks, ks + 1, tick=False)
        for name in ("upkeep", "mem", "log", "merge", "wal"):
            store.scheduler.run_segment(name)
    return store


def test_filewal_rejects_async_outside_group_policy(tmp_path):
    from repro.core.storage_io.wal_files import FileWAL
    with pytest.raises(ValueError, match="async_fsync requires"):
        FileWAL.create(str(tmp_path / "w"), fsync_policy="per_batch",
                       async_fsync=True)


def test_async_fsync_state_and_reopen_equal_blocking(tmp_path):
    """Same workload under blocking and async group commit: identical
    store state and identical durable state after the sync barrier."""
    blocking = _drive_files(_files_cfg(tmp_path, "b"))
    asyncw = _drive_files(_files_cfg(tmp_path, "a", wal_async_fsync=True))
    assert sharded_fingerprint(asyncw) == sharded_fingerprint(blocking)
    assert asyncw.log_pos == blocking.log_pos
    for s in (blocking, asyncw):
        s.wal.sync()
        assert s.wal.all_durable
    # commit acks flowed on both paths (exact counts legitimately differ:
    # the async worker's wait timer can make a group durable BEFORE the
    # next commit point asks, which then has no wait to record)
    assert blocking.wal.commit_hist.count > 0
    assert asyncw.wal.commit_hist.count > 0
    snapb = (sharded_fingerprint(blocking), blocking.log_pos)
    blocking.wal.close()
    asyncw.wal.close()
    from repro.core.storage_io import open_plane
    for name, want in (("b", snapb), ("a", snapb)):
        cfg = _files_cfg(tmp_path, name,
                         wal_async_fsync=(name == "a"))
        rec = recover(cfg, *open_plane(cfg))
        assert (sharded_fingerprint(rec), rec.log_pos) == want
        rec.wal.close()


def test_async_fsync_wait_accounting(tmp_path):
    """fsync_wait_us counts foreground time blocked on WAL durability in
    BOTH modes -- every inline fsync when blocking, only the residual
    sync/seal barrier waits when async -- so the two arms' foreground
    durability cost reads off one counter."""
    blocking = _drive_files(_files_cfg(tmp_path, "b"), n=10)
    blocking.wal.sync()
    assert blocking.wal.fsyncs > 0
    assert blocking.disk.stats.fsync_wait_us > 0.0
    blocking.wal.close()
    asyncw = _drive_files(_files_cfg(tmp_path, "a", wal_async_fsync=True),
                          n=10)
    asyncw.wal.sync()
    assert asyncw.wal.all_durable
    assert asyncw.wal.fsyncs > 0
    asyncw.wal.close()


def test_async_all_durable_tracks_inflight(tmp_path):
    """all_durable is False while a handoff is in flight: block the
    durability worker mid-group with a slow pending write, verify the
    flag, then release."""
    from repro.core.storage_io.wal_files import FileWAL
    w = FileWAL.create(str(tmp_path / "w"), fsync_policy="group",
                       group_bytes=1, group_max_wait_s=3600.0,
                       async_fsync=True)
    w.append_set_write_memory(1 << 20)
    with w._dcv:
        pending_before = bool(w._pending)
    assert pending_before or w._unfsynced or w.all_durable is not None
    w.commit(1)                                  # 1-byte threshold: handoff
    w.sync()
    assert w.all_durable
    assert w.fsyncs >= 1
    assert w.commit_hist.count == 1              # the commit was acked once
    w.close()
    # closed WAL: the durability thread is gone
    assert w._dthread is None
