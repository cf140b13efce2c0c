"""Shared benchmark harness for the paper-figure reproductions.

Everything is scaled down from the paper's testbed by ~64x (records,
memory, log) with the paper's *ratios* preserved: 16KB pages -> 4KB, 1KB
records -> 256B, T=10, active SSTable 32MB -> 512KB, bloom 10 bits/key,
clock buffer cache, 95% thresholds. Throughput is the simulated-time proxy
of repro.core.lsm.storage.TimeModel (NVMe bandwidths + CPU constants
calibrated to the paper's relative overheads).
"""
from __future__ import annotations

import numpy as np

from repro.core.engine import get_backend
from repro.core.lsm.sstable import partition_run, reset_sst_ids
from repro.core.lsm.storage import LSMStore, StoreConfig
from repro.core.service import Get, Put, Scan, StorageService
from repro.core.shard import ShardedStore, ShardRouter

KB, MB = 1 << 10, 1 << 20

# Run-level seed offset (``run.py --seed N``): every driver combines it
# with its own fixed per-scenario seed, so seed 0 (the default) keeps
# historical rows reproducible while any other value re-rolls the whole
# suite coherently.
_RUN_SEED = 0


def set_run_seed(n: int) -> None:
    global _RUN_SEED
    _RUN_SEED = int(n)


def run_seed() -> int:
    return _RUN_SEED


BASE = dict(
    total_memory_bytes=64 * MB,
    write_memory_bytes=4 * MB,
    sim_cache_bytes=1 * MB,
    page_bytes=4 * KB,
    entry_bytes=256,
    size_ratio=10,
    active_sstable_bytes=256 * KB,
    sstable_bytes=512 * KB,
    max_log_bytes=16 * MB,
)


def make_store(**kw) -> LSMStore:
    reset_sst_ids()
    cfg = dict(BASE)
    cfg.update(kw)
    return LSMStore(StoreConfig(**cfg))


def make_service(*, governor=None, service_config=None, **kw) -> StorageService:
    """A StorageService front door over a scaled-down store (the way new
    drivers talk to the engine; ``make_store`` remains for internals)."""
    return StorageService(make_store(**kw), governor=governor,
                          config=service_config)


def make_sharded_service(*, shards: int | None = None,
                         router: ShardRouter | None = None, governor=None,
                         service_config=None, **kw) -> StorageService:
    """A StorageService over a ``ShardedStore``: N shards behind one
    shared memory arena (scaled-down config)."""
    reset_sst_ids()
    cfg = dict(BASE)
    cfg.update(kw)
    store = ShardedStore(StoreConfig(**cfg), shards=shards, router=router)
    return StorageService(store, governor=governor, config=service_config)


def _install_last_level(store, tree_name: str, keys) -> None:
    t = store.trees[tree_name]
    ssts = partition_run(keys, keys, 0, 0, t.entry_bytes,
                         store.cfg.page_bytes, store.cfg.sstable_bytes)
    t.levels.levels = [ssts]
    t.levels.adjust(store.cfg.active_sstable_bytes)


def bulk_load(store, tree_name: str, n_records: int,
              key_stride: int = 1) -> None:
    """Install n_records directly into the tree's last level (no I/O).
    Over a ``ShardedStore``, keys are routed and installed per shard."""
    keys = np.arange(0, n_records * key_stride, key_stride, dtype=np.int64)
    if isinstance(store, ShardedStore):
        for si, sel in store.router.split(keys):
            _install_last_level(store.shards[si].store, tree_name, keys[sel])
        return
    _install_last_level(store, tree_name, keys)


class Workload:
    """YCSB-like driver: batched mixed ops against one or more trees.

    Drives everything through the ``StorageService`` front door (typed
    requests + submit): pass either a service or a bare ``LSMStore`` (which
    gets wrapped). Deferred (backpressured) writes are drained and retried
    by ``submit_strict``, so stalls show up in ``IOStats.write_stalls``;
    a request that stays deferred after retries raises rather than being
    silently dropped from the measured op count."""

    def __init__(self, store, trees, key_max, *, zipf_a=0.99,
                 tree_probs=None, seed=0, scan_len=100):
        self.service = (store if isinstance(store, StorageService)
                        else StorageService(store))
        self.store = self.service.store
        self.trees = list(trees)
        self.key_max = key_max
        self.scan_len = scan_len
        self.rng = np.random.default_rng(seed + _RUN_SEED)
        self.tree_probs = tree_probs

    def _keys(self, n):
        # bounded zipf(a~1) over the whole keyspace: rank = N^u, then a
        # multiplicative hash scatters ranks across the key range.
        u = self.rng.random(n)
        rank = np.floor(self.key_max ** u).astype(np.int64)
        return (rank * 2654435761) % self.key_max

    def _tree(self):
        if self.tree_probs is None:
            return self.trees[0]
        return self.trees[self.rng.choice(len(self.trees),
                                          p=self.tree_probs)]

    def run(self, n_ops, *, write_frac=1.0, scan_frac=0.0, batch=256,
            on_batch=None):
        done = 0
        while done < n_ops:
            b = min(batch, n_ops - done)
            tree = self._tree()
            r = self.rng.random()
            if r < write_frac:
                # one typed Put request -> one ingest_run backend call plus
                # one maintenance-scheduler tick per submit
                keys = self._keys(b)
                self.service.submit_strict([Put(tree, keys, keys)])
            elif r < write_frac + scan_frac:
                self.service.submit_strict(
                    [Scan(tree, int(lo), self.scan_len)
                     for lo in self._keys(max(1, b // 16))])
            else:
                # one typed Get request -> one lookup_batch per submit
                # (Bloom probes issued as one backend call per SSTable)
                self.service.submit_strict([Get(tree, self._keys(b))])
            done += b
            if on_batch is not None:
                on_batch(self.store)


def measure(store, fn) -> dict:
    """Run fn() and report deltas: throughput proxy + I/O per op.
    Accepts a bare ``LSMStore`` or a ``StorageService``. ``write_stalls``
    (backpressure deferrals) is surfaced as the ``stalls`` row field.

    Backend compile deltas (JAX compile events inside jitted backend
    calls vs calls that raised none, over the measured window --
    recompile churn from new pow2 buckets, e.g. the fused read path's
    tier stacks) land on the row; when the store runs a device page
    pool, the window's fused-tier hit rate rides along as
    ``device_pool_hit_rate``.

    When measuring a ``StorageService``, the window's request-latency and
    maintenance-stall tails (from the service's streaming histograms)
    land on the row as ``p50_us`` / ``p99_us`` / ``p999_us`` /
    ``max_stall_us`` -- the tail-latency SLO columns."""
    service = store if isinstance(store, StorageService) else None
    store = getattr(store, "store", store)     # unwrap a StorageService
    backend = getattr(store, "backend", None) \
        or get_backend(store.cfg.backend)
    pool = getattr(store, "device_pool", None)
    store.sync_mem_stats()
    before = store.disk.stats.copy()
    js0 = backend.jit_stats()
    ps0 = pool.stats() if pool is not None else None
    lat0 = service.latency.copy() if service is not None else None
    stall0 = service.stall.copy() if service is not None else None
    fn()
    store.sync_mem_stats()
    d = store.disk.stats.delta(before)
    js1 = backend.jit_stats()
    io, cpu = store.cfg.time_model.elapsed(d, scheme=store.cfg.scheme)
    ops = max(d.ops, 1)
    out = {
        "ops": d.ops,
        "throughput": ops / max(io, cpu, 1e-9),
        "io_pages_per_op": (d.pages_written + d.pages_read) / ops,
        "write_pages_per_op": d.pages_written / ops,
        "read_pages_per_op": d.pages_read / ops,
        "write_amp": (d.pages_written * store.cfg.page_bytes
                      / max(d.entries_written * store.cfg.entry_bytes, 1)),
        "stalls": d.write_stalls,
        "flushes_log": d.flushes_log,
        "flushes_mem": d.flushes_mem,
        "jit_compiles": js1["jit_compiles"] - js0["jit_compiles"],
        "jit_cache_hits": js1["jit_cache_hits"] - js0["jit_cache_hits"],
        # One-launch read path: device launches over the window and the
        # average number of lookup tiers each launch covered (per-tier
        # fused -> ~1.0; cross-tier fused -> the whole store per launch).
        "fused_launches": d.fused_launches,
        "fused_tiers_per_launch": d.fused_tiers / max(1, d.fused_launches),
        # Overlapped maintenance & durability: prepares consumed from the
        # worker pool (and the off-thread compute time they covered),
        # foreground time blocked on the async durability worker, and
        # proactive pacer flush slices over the window.
        "bg_segments": d.bg_segments,
        "bg_overlap_us": d.bg_overlap_us,
        "fsync_wait_us": d.fsync_wait_us,
        "flush_slices": d.flush_slices,
    }
    if service is not None:
        dl = service.latency.delta(lat0)
        out["p50_us"] = dl.p50
        out["p99_us"] = dl.p99
        out["p999_us"] = dl.p999
        out["max_stall_us"] = service.stall.delta(stall0).max_value
    if ps0 is not None:
        ps1 = pool.stats()
        dh = (ps1["tier_hits"] - ps0["tier_hits"]
              + ps1.get("store_hits", 0) - ps0.get("store_hits", 0))
        dm = (ps1["tier_misses"] - ps0["tier_misses"]
              + ps1.get("store_misses", 0) - ps0.get("store_misses", 0))
        out["device_pool_hit_rate"] = dh / max(1, dh + dm)
        out["device_pool_resident_pages"] = ps1["resident_pages"]
    return out


def fmt_row(name: str, value: float, derived: str = "") -> str:
    return f"{name},{value:.6g},{derived}"
