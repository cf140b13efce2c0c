"""Unified HBM arbiter: one device-byte budget leased across the
lookup-side ``DevicePagePool`` and the serving-side ``PagedKVPool`` /
prefix cache.

The contract under test: (1) the lease sum equals the configured total
byte-exactly after EVERY shift; (2) a read-heavy -> serving-heavy
workload flip migrates budget between the device pool and the KV pool in
the pressure's direction; (3) the adaptive split's aggregate miss cost is
no worse than the best static split on the same flip; (4) the KV pool's
region actuator grows with fresh page ids and shrinks without ever
invalidating a live page.
"""
from types import SimpleNamespace

import numpy as np

from repro.core.lsm.sstable import reset_sst_ids
from repro.core.lsm.storage import LSMStore, StoreConfig
from repro.core.service import StorageService
from repro.runtime.hbm_arbiter import HBMArbiter, HBMArbiterConfig
from repro.runtime.kvcache import KVPoolConfig, PagedKVPool

KB, MB = 1 << 10, 1 << 20


# --------------------------- unit level (stubbed pressure) -------------------
class _StubPool:
    def __init__(self):
        self.budget_bytes = 4 * MB
        self.st = dict(tier_hits=0, tier_misses=0, store_hits=0,
                       store_misses=0, resident_pages=0,
                       capacity_pages=1024)

    def stats(self):
        return dict(self.st)


def _stub_service(pool):
    disk = SimpleNamespace(stats=SimpleNamespace(ops=0))
    return SimpleNamespace(store=SimpleNamespace(disk=disk,
                                                 device_pool=pool))


def _kv_pool(total=2048):
    return PagedKVPool(KVPoolConfig(page_tokens=16, total_pages=total,
                                    pool_pages=total // 2, sim_pages=64))


def _tick(arb, svc, pool, *, dev_miss=0, kv_off=0, pfx_miss=0):
    """One decision window of synthetic pressure."""
    pool.st["tier_misses"] += dev_miss
    svc.store.disk.stats.ops += arb.cfg.ops_cycle
    if arb.kv_pool is not None:
        arb.kv_pool.stats["offload_pages"] += kv_off
        arb.kv_pool.stats["prefix_misses"] += pfx_miss
    return arb.observe(svc)


def test_leases_conserve_total_byte_exactly():
    kvp = _kv_pool()
    arb = HBMArbiter(kvp, HBMArbiterConfig(total_bytes=48 * MB,
                                           ops_cycle=64))
    pool = _StubPool()
    svc = _stub_service(pool)
    arb.attach(svc.store)
    total = arb.cfg.total_bytes
    assert arb.total_leased() == total
    rng = np.random.default_rng(0)
    for i in range(40):                   # shifting pressure mixes
        _tick(arb, svc, pool,
              dev_miss=int(rng.integers(0, 200)) if i % 13 < 6 else 0,
              kv_off=int(rng.integers(0, 200)) if i % 13 >= 6 else 0,
              pfx_miss=int(rng.integers(0, 50)) if i % 5 == 0 else 0)
        assert arb.total_leased() == total, "lease sum drifted"
        assert all(arb.leases[r] >= arb.cfg.min_lease_bytes
                   for r in arb.REGIONS), "a region starved below floor"
    assert arb.shift_bytes_total > 0, "arbiter never shifted"
    assert sum(1 for r in arb.records if r["shift_bytes"]) > 0


def test_budget_migrates_with_workload_flip():
    """Device-only pressure pulls the device lease up; flipping to
    KV-offload pressure sends bytes back toward the KV pool."""
    kvp = _kv_pool()
    arb = HBMArbiter(kvp, HBMArbiterConfig(total_bytes=48 * MB,
                                           ops_cycle=64))
    pool = _StubPool()
    svc = _stub_service(pool)
    arb.attach(svc.store)
    dev0 = arb.leases["device"]
    for _ in range(6):                    # phase A: read-heavy
        _tick(arb, svc, pool, dev_miss=500)
    dev_read, kv_read = arb.leases["device"], arb.leases["kv"]
    assert dev_read > dev0, "device lease did not grow under read pressure"
    for _ in range(6):                    # phase B: serving-heavy
        _tick(arb, svc, pool, kv_off=500)
    assert arb.leases["kv"] > kv_read, "kv lease did not grow on the flip"
    assert arb.leases["device"] < dev_read, "device lease never donated"
    assert arb.total_leased() == arb.cfg.total_bytes


def test_zero_pressure_holds_all_leases():
    kvp = _kv_pool()
    arb = HBMArbiter(kvp, HBMArbiterConfig(total_bytes=48 * MB,
                                           ops_cycle=64))
    pool = _StubPool()
    svc = _stub_service(pool)
    arb.attach(svc.store)
    before = dict(arb.leases)
    for _ in range(5):
        assert _tick(arb, svc, pool) is None
    assert arb.leases == before


# --------------------------- end to end (real store + kv pool) ---------------
def _small_store_cfg(device_pool_bytes):
    reset_sst_ids()
    return StoreConfig(total_memory_bytes=32 * MB,
                       write_memory_bytes=256 * KB, sim_cache_bytes=1 * MB,
                       page_bytes=4 * KB, entry_bytes=256,
                       active_sstable_bytes=64 * KB, sstable_bytes=128 * KB,
                       max_log_bytes=8 * MB, flush_policy="opt",
                       device_pool_bytes=device_pool_bytes)


def _flip_cost(device_bytes, kv_pages, prefix_pages, governor=None,
               *, n_reads=36, n_serve=3000, key_max=80_000, seed=5):
    """Read-heavy phase then serving-heavy phase; returns aggregate
    miss-cost per op. A device residency miss is a BATCH-level event (the
    whole batch falls back to the staged probe), so its cost is the ops
    it staged; KV offloads and prefix misses are per-op events.
    ``governor=None`` pins a static split; passing the arbiter (whose
    leases must equal the same starting split) makes it adaptive."""
    from repro.core.service import Get, Put

    kvp = PagedKVPool(KVPoolConfig(page_tokens=16,
                                   total_pages=kv_pages + prefix_pages,
                                   pool_pages=kv_pages, sim_pages=64))
    if governor is not None:
        governor.kv_pool = kvp
    svc = StorageService(LSMStore(_small_store_cfg(device_bytes)),
                         governor=governor)
    svc.create_tree("t")
    pool = svc.store.device_pool
    rng = np.random.default_rng(seed)
    for i in range(80):                   # build a multi-tier store whose
        ks = rng.integers(0, key_max, 256)  # resident set needs ~5-6MB
        svc.submit_strict([Put("t", ks, ks * 3)])
    cost = 0

    def fused_get(batch):
        """One Get batch; returns its size if any tier fell back staged."""
        nonlocal cost
        h0 = pool.stats()
        svc.submit_strict([Get("t", rng.integers(0, key_max, batch))])
        h1 = pool.stats()
        missed = (h1["tier_misses"] - h0["tier_misses"]
                  + h1["store_misses"] - h0["store_misses"]) > 0
        served = (h1["tier_hits"] - h0["tier_hits"]
                  + h1["store_hits"] - h0["store_hits"]) > 0
        if missed or not served:
            cost += batch

    k0 = dict(kvp.stats)
    ops0 = svc.store.disk.stats.ops
    for _ in range(n_reads):              # phase A: read-heavy
        fused_get(256)
    streams = {}
    for i in range(n_serve):              # phase B: serving-heavy
        if rng.random() < 0.4:
            kvp.lookup_prefix(int(rng.integers(0, 180)))
        else:
            s = f"s{rng.integers(0, 8)}"
            kvp.append_tokens(s, 16)
            streams[s] = streams.get(s, 0) + 1
            if streams[s] >= 40:          # finite request lifetimes
                kvp.finish_stream(s)
                streams[s] = 0
        if i % 64 == 0:
            fused_get(32)
    k1 = kvp.stats
    ops = (svc.store.disk.stats.ops - ops0
           + k1["ops"] - k0.get("ops", 0))
    cost += (k1["offload_pages"] - k0["offload_pages"]
             + k1["prefix_misses"] - k0["prefix_misses"])
    return cost / max(1, ops)


def test_arbiter_beats_or_matches_best_static_split():
    """The acceptance bar: on a read-heavy -> serving-heavy flip the
    arbiter's aggregate miss cost is no worse than the best STATIC split
    of the same total budget (it spends phase A's idle KV bytes on the
    device pool, then hands them back)."""
    total, pgb = 12 * MB, 16 * KB
    # static A: device-rich (great phase A, starves serving)
    # static B: serving-rich (device pool thrashes in phase A)
    static = {
        "device_rich": _flip_cost(8 * MB, 128, 128),
        "serving_rich": _flip_cost(2 * MB, 320, 320),
    }
    arb = HBMArbiter(None, HBMArbiterConfig(total_bytes=total,
                                            kv_page_bytes=pgb,
                                            ops_cycle=512),
                     leases={"device": 4 * MB, "kv": 4 * MB,
                             "prefix": 4 * MB})
    adaptive = _flip_cost(4 * MB, 256, 256, governor=arb)
    assert arb.total_leased() == total
    assert arb.shift_bytes_total > 0, "arbiter never adapted"
    best = min(static.values())
    assert adaptive <= best * 1.05, \
        f"adaptive {adaptive:.4f} worse than best static {best:.4f} " \
        f"({static})"


# --------------------------- region actuator ---------------------------------
def test_set_regions_grow_mints_fresh_ids():
    kvp = _kv_pool(total=256)
    for s in range(4):
        kvp.append_tokens(f"s{s}", 16 * 20)       # 20 pages per stream
    live = {pid for st in kvp.streams.values() for pid, _ in st.pages}
    old_ids = set(kvp.free) | live
    kvp.set_regions(256, 128)                     # grow 256 -> 384
    assert kvp.total_pages == 384
    minted = set(kvp.free) - old_ids
    assert len(minted) == 128, "grow must mint exactly the delta"
    assert min(minted) >= 256, "grow reused a previously-issued page id"
    assert len(kvp.free) == len(set(kvp.free)), "duplicate free ids"


def test_set_regions_shrink_never_invalidates_live_pages():
    kvp = _kv_pool(total=512)
    for s in range(4):
        kvp.append_tokens(f"s{s}", 16 * 30)
    live_before = {pid for st in kvp.streams.values()
                   for pid, _ in st.pages}
    kvp.set_regions(128, 64)                      # shrink 512 -> 192
    live_after = {pid for st in kvp.streams.values()
                  for pid, _ in st.pages}
    assert kvp.total_pages <= 512
    assert live_after <= live_before, "shrink must only flush, never mint"
    assert live_after.isdisjoint(set(kvp.free)), \
        "a live page id landed on the free list"
    # accounting closes: every retired id is gone from both sets
    assert len(kvp.free) + len(live_after) <= kvp.total_pages \
        + len(kvp.prefix_store)
    # floors hold
    kvp.set_regions(1, 1)
    assert kvp.cfg.pool_pages >= 64
    assert kvp.total_pages - kvp.cfg.pool_pages >= 64
