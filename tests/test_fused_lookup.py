"""Fused device-resident read path vs the staged per-SSTable loop.

The contract under test: with a ``DevicePagePool`` enabled, every lookup
batch must be bit-identical to the staged engine -- results, buffer-cache
page pins, ``IOStats`` -- across schemes, shard counts and backends; the
pool itself only changes *where* the probe computation runs. Plus the
direct backend seam (``prepare_tier`` / ``lookup_fused`` against the
staged primitives), eviction/shrink fallback mid-workload, Bloom
memoization across the manifest edit sites, the jit shape-cache counters,
and the ``MemoryPlan.device_pool_bytes`` actuation path.
"""
import numpy as np
import pytest

from repro.core.engine import NumpyBackend, PallasBackend
from repro.core.engine.backend import assign_bounds
from repro.core.lsm.sstable import partition_run, reset_sst_ids
from repro.core.lsm.storage import LSMStore, StoreConfig
from repro.core.service import (DevicePoolGovernor, MemoryGovernor,
                                MemoryPlan, Get, Put, StorageService)
from repro.core.shard import ShardedStore

KB, MB = 1 << 10, 1 << 20


@pytest.fixture(scope="module")
def backends():
    return NumpyBackend(), PallasBackend(interpret=True)


def small_config(**kw):
    base = dict(total_memory_bytes=32 * MB, write_memory_bytes=256 * KB,
                sim_cache_bytes=1 * MB, page_bytes=4 * KB, entry_bytes=256,
                active_sstable_bytes=64 * KB, sstable_bytes=128 * KB,
                max_log_bytes=8 * MB, scheme="partitioned",
                flush_policy="opt")
    base.update(kw)
    reset_sst_ids()
    return StoreConfig(**base)


def make_tier(rng, n_tables, per_table=700):
    """A disjoint, min_key-sorted lookup tier (what a disk level holds)."""
    keys = np.sort(rng.choice(200_000, size=n_tables * per_table,
                              replace=False)).astype(np.int64)
    vals = rng.integers(1, 2**30, size=len(keys)).astype(np.int64)
    return partition_run(keys, vals, 0, 0, 256, 4 * KB,
                         per_table * 256)


# --------------------------- backend seam -----------------------------------
@pytest.mark.parametrize("n_tables", [1, 3, 7])
def test_lookup_fused_matches_staged_primitives(backends, n_tables):
    """prepare_tier + lookup_fused == per-table bloom_probe + lookup_batch
    on every field, for both backends, including misses and off-tier keys."""
    rng = np.random.default_rng(n_tables)
    reset_sst_ids()
    tier = make_tier(rng, n_tables)
    hits = rng.choice(np.concatenate([t.keys for t in tier]), 300)
    queries = np.concatenate(
        [hits, rng.integers(0, 220_000, 200)]).astype(np.int64)
    starts = np.array([t.min_key for t in tier], np.int64)
    ends = np.array([t.max_key for t in tier], np.int64)
    ti, ok = assign_bounds(starts, ends, queries)
    for b in backends:
        view = b.prepare_tier(tier, lambda s: b.bloom_build(s.keys))
        assert view is not None, b.name
        r = b.lookup_fused(view, queries)
        assert r is not None, b.name
        np.testing.assert_array_equal(r.ti, ti)
        np.testing.assert_array_equal(r.ok, ok)
        for t_i in range(n_tables):
            sel = np.flatnonzero(ok & (ti == t_i))
            sst = tier[t_i]
            pos_ref = b.bloom_probe(b.bloom_build(sst.keys), queries[sel])
            np.testing.assert_array_equal(r.positive[sel], pos_ref,
                                          err_msg=f"{b.name} bloom t={t_i}")
            p, h = b.lookup_batch(sst.keys, queries[sel])
            np.testing.assert_array_equal(r.pos[sel], p)
            np.testing.assert_array_equal(r.hit[sel], h)
            np.testing.assert_array_equal(r.vals[sel][h], sst.vals[p[h]])


@pytest.mark.parametrize("shape", [(3, 2, 5), (1,), (2, 2)])
def test_lookup_store_fused_matches_per_tier(backends, shape):
    """prepare_store + lookup_store_fused == R independent prepare_tier +
    lookup_fused runs, field for field per tier, and the on-device winner
    equals the staged first-resolving-tier scan -- both backends."""
    rng = np.random.default_rng(sum(shape))
    reset_sst_ids()
    tiers = [make_tier(rng, n) for n in shape]
    allk = np.concatenate([t.keys for tier in tiers for t in tier])
    queries = np.concatenate(
        [rng.choice(allk, 300),
         rng.integers(0, 220_000, 200)]).astype(np.int64)
    for b in backends:
        bloom = lambda s: b.bloom_build(s.keys)             # noqa: E731
        sview = b.prepare_store(tiers, bloom)
        assert sview is not None, b.name
        assert sview.num_tiers == len(shape)
        assert sview.num_tables == sum(shape)
        r = b.lookup_store_fused(sview, queries)
        assert r is not None, b.name
        win_ref = np.full(len(queries), -1, np.int64)
        for rr, tier in enumerate(tiers):
            tv = b.prepare_tier(tier, bloom)
            f = b.lookup_fused(tv, queries)
            for fld in ("ti", "ok", "positive", "hit", "pos"):
                np.testing.assert_array_equal(
                    getattr(r, fld)[rr], getattr(f, fld),
                    err_msg=f"{b.name} tier={rr} field={fld}")
            np.testing.assert_array_equal(r.vals[rr][f.hit], f.vals[f.hit])
            first = (win_ref == -1) & f.hit
            win_ref[first] = rr
        np.testing.assert_array_equal(r.win, win_ref, err_msg=b.name)


def test_store_fused_newest_wins_three_tiers(backends):
    """The same key resident in three tiers must resolve from tier 0 (the
    newest): win == 0 and the resolved value is tier 0's, never a deeper
    tier's stale version."""
    keys = np.arange(0, 4000, 4, dtype=np.int64)
    tiers = []
    for r in range(3):
        reset_sst_ids()
        tiers.append(partition_run(keys, keys * 10 + r, 0, 0, 256,
                                   4 * KB, 64 * KB))
    q = keys[::7]
    for b in backends:
        sview = b.prepare_store(tiers, lambda s: b.bloom_build(s.keys))
        r = b.lookup_store_fused(sview, q)
        assert r is not None and (r.win == 0).all(), b.name
        np.testing.assert_array_equal(
            r.vals[0][np.arange(len(q))], q * 10, err_msg=b.name)


def test_store_fused_empty_and_all_miss(backends):
    """Degenerate batches: an empty tier list yields a (0, K) lookup with
    every query unresolved; an all-miss batch resolves nothing."""
    rng = np.random.default_rng(1)
    reset_sst_ids()
    tier = make_tier(rng, 2)                      # keys < 200_000
    q = rng.integers(300_000, 400_000, 128).astype(np.int64)
    for b in backends:
        bloom = lambda s: b.bloom_build(s.keys)             # noqa: E731
        empty = b.prepare_store([], bloom)
        r0 = b.lookup_store_fused(empty, q)
        assert r0 is not None and (r0.win == -1).all(), b.name
        assert r0.ti.shape == (0, len(q))
        r1 = b.lookup_store_fused(b.prepare_store([tier], bloom), q)
        assert (r1.win == -1).all() and not r1.hit.any(), b.name


def _tier_of(rng, sizes, key_lo, key_hi):
    """A lookup tier whose tables hold ``sizes`` entries, keys drawn from
    [key_lo, key_hi): tables of other sizes get Bloom filters of other
    widths."""
    keys = np.sort(rng.choice(np.arange(key_lo, key_hi), sum(sizes),
                              replace=False)).astype(np.int64)
    vals = rng.integers(1, 2**30, len(keys)).astype(np.int64)
    cuts = np.cumsum([0] + list(sizes))
    return [partition_run(keys[a:b], vals[a:b], 0, 0, 256, 4 * KB,
                          (b - a) * 256)[0]
            for a, b in zip(cuts[:-1], cuts[1:])]


def _store_probe_case(case, rng):
    """(tiers, queries) of one case of the store probe's differential."""
    if case == "empty":
        return [], rng.integers(0, 1000, 37).astype(np.int64)
    if case == "pow2":
        # The longest table holds exactly 2**11 entries: queries at both
        # ends of every table take the most halvings the search makes
        # (its second key, the 12th).
        tiers = [_tier_of(rng, [300, 90], 0, 50_000),
                 _tier_of(rng, [2048, 2048, 1000], 0, 200_000)]
        ends = np.concatenate([[t.min_key - 1, t.min_key, t.min_key + 1,
                                t.keys[1], t.keys[-2], t.max_key,
                                t.max_key + 1]
                               for tier in tiers for t in tier])
        return tiers, np.concatenate(
            [ends, rng.integers(0, 200_000, 200)]).astype(np.int64)
    # Uneven tiers of unlike filter widths over unlike key ranges, so a
    # query may have no covering table in a tier, in a batch of 300 that
    # pads to 512.
    tiers = [_tier_of(rng, [120], 40_000, 60_000),
             _tier_of(rng, [700, 260, 700, 1300], 0, 150_000),
             _tier_of(rng, [1500] * 6 + [31], 20_000, 300_000)]
    allk = np.concatenate([t.keys for tier in tiers for t in tier])
    return tiers, np.concatenate(
        [rng.choice(allk, 200), rng.integers(0, 320_000, 100)]
    ).astype(np.int64)


@pytest.mark.parametrize("case", ["uneven", "pow2", "empty"])
def test_store_probe_matches_numpy(backends, case):
    """The Pallas store probe (one Bloom gather and one ranged search of
    each (tier, query)'s covering table) against the numpy backend's
    ``lookup_store_fused``, field for field, and its
    ``read.filter_probes`` count: the (tier, query) pairs with a
    covering table."""
    from repro.runtime import tracing
    nb, pb = backends
    reset_sst_ids()
    tiers, q = _store_probe_case(case, np.random.default_rng(16))
    ref = nb.lookup_store_fused(
        nb.prepare_store(tiers, lambda s: nb.bloom_build(s.keys)), q)
    view = pb.prepare_store(tiers, lambda s: pb.bloom_build(s.keys))
    assert view is not None
    c0 = tracing.counters().get("read.filter_probes", 0)
    with tracing.recording():
        r = pb.lookup_store_fused(view, q)
    for fld in ("ti", "ok", "positive", "pos", "hit", "vals", "win"):
        np.testing.assert_array_equal(getattr(r, fld), getattr(ref, fld),
                                      err_msg=f"{case} field={fld}")
    assert tracing.counters().get("read.filter_probes", 0) - c0 \
        == int(r.ok.sum())
    if case != "empty":
        assert r.hit.any() and (~r.ok).any() and r.ok.any()


def test_fused_refuses_out_of_domain(backends):
    """Out-of-int32 tiers/queries return None (staged fallback), never
    wrong results."""
    nb, pb = backends
    rng = np.random.default_rng(9)
    reset_sst_ids()
    big = np.sort(rng.choice(2**40, 500, replace=False)).astype(np.int64)
    tier = partition_run(big, big, 0, 0, 256, 4 * KB, 128 * KB)
    assert pb.prepare_tier(tier, lambda s: pb.bloom_build(s.keys)) is None
    tier2 = make_tier(rng, 2)
    view = pb.prepare_tier(tier2, lambda s: pb.bloom_build(s.keys))
    assert view is not None
    assert pb.lookup_fused(view, np.array([1, 2**40], np.int64)) is None
    # the numpy reference accepts the full int64 domain
    viewn = nb.prepare_tier(tier, lambda s: nb.bloom_build(s.keys))
    rn = nb.lookup_fused(viewn, big[:64])
    assert rn is not None and rn.hit.all()


# --------------------------- store differential -----------------------------
def drive_store(store, batches=90, read_tail=10, key_max=30_000, seed=0):
    """Mixed churn (flushes + merges retire SSTables under the pool) then a
    read-only tail (tiers stabilize, the pool warms, fused serves)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(batches):
        ks = rng.integers(0, key_max, 256)
        if i % 3 != 2:
            store.write_batch("t", ks, ks * 3)
        f, v = store.read_batch("t", rng.integers(0, key_max, 256))
        out.append((f, v))
    for _ in range(read_tail):
        f, v = store.read_batch("t", rng.integers(0, key_max, 256))
        out.append((f, v))
    return out


def _io_stats(s):
    """IOStats fields that must match bit-for-bit across read paths. The
    ``fused_*`` counters are observability of WHICH path served (launch
    collapse), not I/O accounting, so they are excluded by design."""
    return {k: v for k, v in vars(s.disk.stats).items()
            if not k.startswith("fused_")}


def assert_identical(s0, out0, s1, out1):
    for (f0, v0), (f1, v1) in zip(out0, out1):
        np.testing.assert_array_equal(f0, f1)
        np.testing.assert_array_equal(v0, v1)
    assert _io_stats(s0) == _io_stats(s1)
    assert (s0.disk.cache.hits, s0.disk.cache.misses) \
        == (s1.disk.cache.hits, s1.disk.cache.misses)


@pytest.mark.parametrize("backend,scheme", [
    ("numpy", "partitioned"),
    ("numpy", "accordion-data"),
    ("pallas", "partitioned"),
])
def test_store_fused_vs_staged_bit_identical(backend, scheme):
    batches = 90 if backend == "numpy" else 36
    runs = []
    for pool in (0, 32 * MB):
        s = LSMStore(small_config(backend=backend, scheme=scheme,
                                  device_pool_bytes=pool))
        s.create_tree("t")
        runs.append((s, drive_store(s, batches=batches)))
    (s0, o0), (s1, o1) = runs
    assert_identical(s0, o0, s1, o1)
    st = s1.device_pool.stats()
    assert st["tier_hits"] > 0, "fused path never fired"
    assert st["resident_pages"] <= st["capacity_pages"]


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_fused_scope_tier_vs_store_bit_identical(backend):
    """Three-way differential: staged, per-tier fused, cross-tier fused
    must agree bit-for-bit on results, pins and IOStats; the store scope
    must actually collapse launches (store_hits > 0, fewer launches than
    the per-tier twin for the same workload)."""
    batches = 60 if backend == "numpy" else 24
    runs = []
    for scope, pool in (("store", 0), ("tier", 32 * MB),
                        ("store", 32 * MB)):
        s = LSMStore(small_config(backend=backend, device_pool_bytes=pool,
                                  fused_scope=scope))
        s.create_tree("t")
        runs.append((s, drive_store(s, batches=batches)))
    (s0, o0), (s1, o1), (s2, o2) = runs
    assert_identical(s0, o0, s1, o1)
    assert_identical(s0, o0, s2, o2)
    assert s2.device_pool.stats()["store_hits"] > 0, \
        "one-launch store path never served"
    # per-tier scope covers exactly one tier per launch; store scope must
    # average above it (each store launch covers the whole tier list --
    # cold fallbacks to the per-tier loop dilute but cannot erase it)
    tpl = [s.disk.stats.fused_tiers / max(1, s.disk.stats.fused_launches)
           for s in (s1, s2)]
    assert tpl[0] == 1.0 and tpl[1] > tpl[0]


def test_store_scope_reads_before_any_flush():
    """Empty tier list at the tree level: reads served entirely from the
    mem component (no disk tiers yet) take the store-fused path's empty
    branch without touching the pool."""
    s = LSMStore(small_config(device_pool_bytes=32 * MB))
    s.create_tree("t")
    ks = np.arange(100, dtype=np.int64)
    s.write_batch("t", ks, ks + 1)
    f, v = s.read_batch("t", np.arange(200, dtype=np.int64))
    assert f[:100].all() and not f[100:].any()
    np.testing.assert_array_equal(v[:100], ks + 1)
    st = s.device_pool.stats()
    assert st["store_hits"] == 0 and st["store_misses"] == 0


def test_budget_shrink_races_prepare_store():
    """A budget shrink landing while prepare_store is staging (the
    generation guard): the acquire must return None and cache nothing --
    the next batch re-admits against the new budget instead of serving a
    view sized for the old one."""
    s = LSMStore(small_config(device_pool_bytes=32 * MB))
    s.create_tree("t")
    drive_store(s, batches=30, read_tail=4)
    pool = s.device_pool
    t = s.trees["t"]
    tiers = [ti for ti in t.l0.lookup_tiers() + t.levels.lookup_tiers()
             if ti]
    assert tiers
    pool._views.clear()                   # force a fresh prepare
    calls = {"n": 0}

    def tripwire(sst):
        calls["n"] += 1
        if calls["n"] == 1:               # shrink lands mid-prepare
            pool.set_budget_bytes(16 * MB)
        return t._bloom(sst)

    assert pool.acquire_store(tiers, tripwire) is None
    assert not pool._views, "stale store view cached across a shrink"
    # without the race the same acquire succeeds and caches (one extra
    # round in case the shrink evicted pages -> cold re-admission first)
    view = pool.acquire_store(tiers, t._bloom) \
        or pool.acquire_store(tiers, t._bloom)
    assert view is not None and pool._views


@pytest.mark.parametrize("shards", [1, 4])
def test_sharded_fused_vs_staged_bit_identical(shards):
    runs = []
    for pool in (0, 32 * MB):
        s = ShardedStore(small_config(device_pool_bytes=pool),
                         shards=shards)
        s.create_tree("t")
        runs.append((s, drive_store(s, batches=90)))
    (s0, o0), (s1, o1) = runs
    assert_identical(s0, o0, s1, o1)
    assert s1.device_pool.stats()["tier_hits"] > 0


def test_shrink_mid_workload_falls_back_staged():
    """Shrinking the budget mid-run (evictions drop the prepared views)
    must leave results and accounting identical to a staged-only twin:
    affected tiers re-admit or stay staged, never serve stale views."""
    s0 = LSMStore(small_config(device_pool_bytes=0))
    s0.create_tree("t")
    s1 = LSMStore(small_config(device_pool_bytes=32 * MB))
    s1.create_tree("t")
    rng0, rng1 = (np.random.default_rng(4) for _ in range(2))
    outs = [[], []]
    for i in range(80):
        for s, rng, out in ((s0, rng0, outs[0]), (s1, rng1, outs[1])):
            ks = rng.integers(0, 30_000, 256)
            if i % 3 != 2:
                s.write_batch("t", ks, ks * 3)
            out.append(s.read_batch("t", rng.integers(0, 30_000, 256)))
        if i == 40:
            assert s1.device_pool.stats()["resident_pages"] > 16
            s1.set_device_pool_bytes(16 * 4 * KB)   # violent shrink
            assert s1.device_pool.stats()["resident_pages"] <= 16
        if i == 60:
            s1.set_device_pool_bytes(0)             # disable entirely
            assert not s1.device_pool.enabled
    assert_identical(s0, outs[0], s1, outs[1])


def test_drop_sst_invalidates_pages_and_views():
    s = LSMStore(small_config(device_pool_bytes=32 * MB))
    s.create_tree("t")
    drive_store(s, batches=60, read_tail=8)
    pool = s.device_pool
    assert pool.stats()["tier_hits"] > 0
    # every cached view must be over live SSTables only
    live = {sst.sst_id for t in s.trees.values()
            for tier in t.l0.lookup_tiers() + t.levels.lookup_tiers()
            for sst in tier}
    for key in pool._views:
        assert set(pool._key_ssts(key)) <= live, \
            "view over a retired SSTable survived"
    # dropping a live SSTable kills its residency and every view over it
    tier = next(t for t in s.trees["t"].levels.lookup_tiers() if t)
    sst = tier[0]
    before = pool.stats()["resident_pages"]
    s.disk.drop_sst(sst)
    assert pool.stats()["resident_pages"] < before
    assert all(sst.sst_id not in pool._key_ssts(key)
               for key in pool._views)


# --------------------------- satellites -------------------------------------
def test_bloom_memoized_and_invalidated():
    s = LSMStore(small_config(device_pool_bytes=0))
    s.create_tree("t")
    t = s.trees["t"]
    drive_store(s, batches=40, read_tail=2)
    tier = next(ti for ti in t.levels.lookup_tiers() if ti)
    f1 = t._bloom(tier[0])
    f2 = t._bloom(tier[0])
    assert f1 is f2, "per-SSTable Bloom must be memoized"
    # more churn retires SSTables; the memo must only hold live ids
    drive_store(s, batches=40, read_tail=0, seed=1)
    live = {sst.sst_id for ti in t.l0.lookup_tiers() + t.levels.lookup_tiers()
            for sst in ti}
    assert set(t._bloom_cache) <= live, "stale Bloom memo entries"


def test_jit_shape_cache_counters():
    """``jit_compiles`` counts the JAX compile events raised inside the
    backend's jitted calls; a call that raised none is a cache hit."""
    import jax
    pb = PallasBackend(interpret=True)
    rng = np.random.default_rng(2)
    k = np.sort(rng.choice(10_000, 600, replace=False)).astype(np.int64)
    jax.clear_caches()                   # the first calls compile afresh
    c0, h0 = pb.jit_compiles, pb.jit_cache_hits
    f = pb.bloom_build(k)
    pb.bloom_probe(f, k[:100])
    assert pb.jit_compiles > c0 and pb.jit_cache_hits == h0
    c1, h1 = pb.jit_compiles, pb.jit_cache_hits
    pb.bloom_probe(f, k[100:200])        # same pow2 bucket: cache hit
    assert (pb.jit_compiles, pb.jit_cache_hits) == (c1, h1 + 1)
    pb.bloom_probe(f, k[:550])           # new query bucket: recompile
    assert pb.jit_compiles > c1 and pb.jit_cache_hits == h1 + 1
    st = pb.jit_stats()
    assert st["jit_compiles"] == pb.jit_compiles
    assert st["jit_cache_hits"] == pb.jit_cache_hits


def test_store_probe_compiles_once_per_size_bucket():
    """Store views of other layouts (tables per tier) in the same size
    bucket, and batches of other query counts in the same power of two,
    reuse the compiled store probe: the per-tier rows are operands, the
    search's halvings follow the longest table's power of two, and
    padding and cutting happen on the host."""
    pb = PallasBackend(interpret=True)
    rng = np.random.default_rng(5)
    reset_sst_ids()
    bloom = lambda s: pb.bloom_build(s.keys)                # noqa: E731
    views = [pb.prepare_store([make_tier(rng, a), make_tier(rng, b)], bloom)
             for a, b in ((2, 3), (4, 1), (1, 2))]
    q = rng.integers(0, 200_000, 400).astype(np.int64)
    pb.lookup_store_fused(views[0], q)
    c, h = pb.jit_compiles, pb.jit_cache_hits
    for view in views[1:]:
        for n in (300, 400, 511):
            assert pb.lookup_store_fused(view, q[:n]) is not None
    assert pb.jit_compiles == c and pb.jit_cache_hits == h + 6


def test_memory_plan_actuates_device_pool_budget():
    class PinPool(MemoryGovernor):
        def __init__(self, budget):
            self.budget = budget

        def observe(self, service):
            return MemoryPlan(device_pool_bytes=self.budget,
                              note="test-pin")

    svc = StorageService(LSMStore(small_config(device_pool_bytes=0)),
                         governor=PinPool(8 * MB))
    svc.create_tree("t")
    assert not svc.store.device_pool.enabled
    ks = np.arange(256, dtype=np.int64)
    svc.submit_strict([Put("t", ks, ks)])
    assert svc.store.device_pool.budget_bytes == 8 * MB
    assert svc.store.device_pool.enabled


def test_device_pool_governor_grows_on_misses():
    gov = DevicePoolGovernor(min_bytes=1 * MB, max_bytes=8 * MB,
                             ops_cycle=256)
    svc = StorageService(LSMStore(small_config(device_pool_bytes=1 * MB)),
                         governor=gov)
    svc.create_tree("t")
    rng = np.random.default_rng(0)
    for i in range(60):
        ks = rng.integers(0, 30_000, 256)
        if i % 3 != 2:
            svc.submit_strict([Put("t", ks, ks * 3)])
        svc.submit_strict([Get("t", rng.integers(0, 30_000, 256))])
    # churn keeps tiers cold at 1MB -> misses dominate -> budget doubled
    assert svc.store.device_pool.budget_bytes > 1 * MB
    assert gov.records, "governor never decided"


def test_device_pool_bytes_validation():
    with pytest.raises(ValueError):
        small_config(device_pool_bytes=-1).validate()


def test_fused_scope_validation():
    with pytest.raises(ValueError):
        small_config(fused_scope="bogus").validate()


# --------------------------- governor stability ------------------------------
class _StubPool:
    def __init__(self, budget=8 * MB):
        self.budget_bytes = budget
        self.st = dict(tier_hits=0, tier_misses=0, store_hits=0,
                       store_misses=0, resident_pages=0,
                       capacity_pages=4096)

    def stats(self):
        return dict(self.st)


def _stub_service(pool):
    from types import SimpleNamespace
    disk = SimpleNamespace(stats=SimpleNamespace(ops=0))
    return SimpleNamespace(store=SimpleNamespace(disk=disk,
                                                 device_pool=pool))


def _cycle(gov, svc, pool, d_hit, d_miss, resident=0):
    """Feed one decision window of synthetic hit/miss deltas and apply
    any resulting plan (the StorageService actuation, inlined)."""
    pool.st["tier_hits"] += d_hit
    pool.st["tier_misses"] += d_miss
    pool.st["resident_pages"] = resident
    svc.store.disk.stats.ops += gov.ops_cycle
    plan = gov.observe(svc)
    if plan is not None and plan.device_pool_bytes is not None:
        pool.budget_bytes = plan.device_pool_bytes
    return plan


def test_governor_deadband_holds_steady_workload():
    """The oscillation fix, part 1: a steady ~50/50 hit/miss mix sits
    inside the deadband, so the budget converges (holds) instead of the
    old double/halve flapping on every cycle."""
    pool = _StubPool()
    gov = DevicePoolGovernor(min_bytes=1 * MB, max_bytes=64 * MB,
                             ops_cycle=256, deadband=0.15, min_dwell=2)
    svc = _stub_service(pool)
    gov.attach(svc.store)
    for hits, misses in [(100, 100), (110, 90), (90, 110), (104, 96),
                         (96, 104), (100, 100)]:
        assert _cycle(gov, svc, pool, hits, misses, resident=100) is None
    assert pool.budget_bytes == 8 * MB, "budget moved inside the deadband"
    assert not gov.records


def test_governor_dwell_blocks_single_cycle_reversal():
    """Part 2: one anomalous cycle cannot reverse direction -- the
    reversal is held (recorded with held=True) until the direction has
    dwelt ``min_dwell`` cycles; a sustained reversal then actuates."""
    pool = _StubPool()
    gov = DevicePoolGovernor(min_bytes=1 * MB, max_bytes=64 * MB,
                             ops_cycle=256, deadband=0.15, min_dwell=2)
    svc = _stub_service(pool)
    gov.attach(svc.store)
    p1 = _cycle(gov, svc, pool, 20, 180)            # miss-heavy: grow
    assert p1 is not None and pool.budget_bytes == 16 * MB
    p2 = _cycle(gov, svc, pool, 180, 20, resident=10)   # blip: held
    assert p2 is None and pool.budget_bytes == 16 * MB
    assert gov.records[-1]["held"] is True
    p3 = _cycle(gov, svc, pool, 180, 20, resident=10)   # sustained: shrink
    assert p3 is not None and pool.budget_bytes == 8 * MB
    assert gov.records[-1]["held"] is False


def test_governor_no_oscillation_under_alternation():
    """The pre-fix failure mode: strictly alternating miss-/hit-heavy
    cycles made the budget double and halve forever. With deadband+dwell
    the actuated budget must never immediately retrace the previous step
    (no A -> B -> A bounce between consecutive actuations)."""
    pool = _StubPool()
    gov = DevicePoolGovernor(min_bytes=1 * MB, max_bytes=64 * MB,
                             ops_cycle=256, deadband=0.15, min_dwell=2)
    svc = _stub_service(pool)
    gov.attach(svc.store)
    budgets = [pool.budget_bytes]
    for i in range(12):
        hit, miss = (20, 180) if i % 2 == 0 else (180, 20)
        if _cycle(gov, svc, pool, hit, miss, resident=10) is not None:
            budgets.append(pool.budget_bytes)
    for a, b, c in zip(budgets, budgets[1:], budgets[2:]):
        assert not (a == c and a != b), f"budget bounced {a}->{b}->{c}"
