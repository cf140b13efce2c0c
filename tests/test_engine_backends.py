"""Backend parity: NumpyBackend vs PallasBackend (interpret mode) must
agree on merge reconciliation, Bloom probes (including false positives --
both backends share one hash geometry), and batched lookups; and the
store's batched read path must agree with the scalar lookup loop."""
import numpy as np
import pytest

from repro.core.engine import (NumpyBackend, PallasBackend, bloom_sizing,
                               get_backend)
from repro.core.lsm.cache import ClockCache, Disk
from repro.core.lsm.sstable import reset_sst_ids, sstable_from_run
from repro.core.lsm.storage import SCHEMES, LSMStore, StoreConfig

KB, MB = 1 << 10, 1 << 20


@pytest.fixture(scope="module")
def backends():
    return NumpyBackend(), PallasBackend(interpret=True)


def small_config(**kw):
    base = dict(total_memory_bytes=32 * MB, write_memory_bytes=2 * MB,
                sim_cache_bytes=1 * MB, page_bytes=4 * KB, entry_bytes=256,
                active_sstable_bytes=64 * KB, sstable_bytes=128 * KB,
                max_log_bytes=8 * MB, scheme="partitioned",
                flush_policy="opt")
    base.update(kw)
    reset_sst_ids()
    return StoreConfig(**base)


# --------------------------- primitives -------------------------------------
def test_backend_registry_and_env(monkeypatch):
    monkeypatch.delenv("REPRO_LSM_BACKEND", raising=False)
    assert get_backend("numpy").name == "numpy"
    assert get_backend(None).name == "numpy"
    monkeypatch.setenv("REPRO_LSM_BACKEND", "pallas")
    assert get_backend(None).name == "pallas"      # env fills the default
    assert get_backend("numpy").name == "numpy"    # explicit choice wins
    monkeypatch.delenv("REPRO_LSM_BACKEND")
    with pytest.raises(ValueError):
        get_backend("cuda")


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_merge_parity_newest_wins(backends, seed):
    nb, pb = backends
    rng = np.random.default_rng(seed)
    runs, oracle = [], {}
    for _ in range(rng.integers(2, 6)):
        n = int(rng.integers(1, 1200))
        k = np.sort(rng.choice(50_000, size=n, replace=False)).astype(np.int64)
        v = rng.integers(-2**31 + 1, 2**31, size=n).astype(np.int64)
        runs.append((k, v))
    for k, v in reversed(runs):          # oldest first: newer overwrites
        oracle.update(zip(k.tolist(), v.tolist()))
    k1, v1 = nb.merge_runs(runs)
    k2, v2 = pb.merge_runs(runs)
    np.testing.assert_array_equal(k1, k2)
    np.testing.assert_array_equal(v1, v2)
    assert k1.tolist() == sorted(oracle)
    assert v1.tolist() == [oracle[k] for k in k1.tolist()]


def test_merge_empty_and_single_run(backends):
    nb, pb = backends
    for b in (nb, pb):
        k, v = b.merge_runs([])
        assert len(k) == 0 and len(v) == 0
        k1 = np.array([3, 7, 9], np.int64)
        k, v = b.merge_runs([(k1, k1 * 2)])
        np.testing.assert_array_equal(k, k1)
        np.testing.assert_array_equal(v, k1 * 2)


def test_pallas_interpret_follows_the_device(backends):
    _, pb = backends
    if pb.device.platform == "tpu":
        pytest.skip("this process holds a TPU")
    assert PallasBackend().interpret        # off a TPU: interpret mode
    with pytest.raises(ValueError, match="need a TPU"):
        PallasBackend(interpret=False)


def test_merge_out_of_int32_range_falls_back(backends):
    _, pb = backends
    k1 = np.array([1, 2**40], np.int64)          # beyond int32
    k2 = np.array([2], np.int64)
    before = pb.fallback_calls
    k, v = pb.merge_runs([(k1, k1), (k2, k2)])
    assert pb.fallback_calls == before + 1
    assert k.tolist() == [1, 2, 2**40]


@pytest.mark.parametrize("n", [100, 1500])
def test_bloom_parity_exact(backends, n):
    nb, pb = backends
    rng = np.random.default_rng(n)
    keys = rng.choice(2**30, size=n, replace=False).astype(np.int64)
    f_n = nb.bloom_build(keys)
    f_p = pb.bloom_build(keys)
    probes = np.concatenate([keys, rng.choice(2**30, 4000).astype(np.int64)])
    p_n = nb.bloom_probe(f_n, probes)
    p_p = pb.bloom_probe(f_p, probes)
    np.testing.assert_array_equal(p_n, p_p)      # incl. false positives
    assert p_n[:n].all(), "no false negatives"
    assert p_n[n:].mean() < 0.05


def test_bloom_probe_mixed_domain_no_false_negatives(backends):
    nb, pb = backends
    keys = np.array([5, 10, 20], np.int64)
    probes = np.array([5, 2**40, 20, 7], np.int64)   # mixed int32 domain
    results = []
    for b in (nb, pb):
        got = b.bloom_probe(b.bloom_build(keys), probes)
        assert got[0] and got[2], "present keys must stay positive"
        results.append(got)
    # parity extends to out-of-domain aliasing (both wrap to int32)
    np.testing.assert_array_equal(results[0], results[1])
    wrapped = np.array([2**32 + 5, 2**32 + 10], np.int64)   # alias to 5, 10
    for b in (nb, pb):
        assert b.bloom_probe(b.bloom_build(keys), wrapped).all()


def test_bloom_sizing_bucketed():
    n1, s1 = bloom_sizing(100)
    n2, s2 = bloom_sizing(256)
    assert (n1, s1) == (n2, s2)                  # same bucket
    assert s1 % 128 == 0


@pytest.mark.parametrize("seed", [0, 3])
def test_lookup_batch_parity(backends, seed):
    nb, pb = backends
    rng = np.random.default_rng(seed)
    sk = np.sort(rng.choice(20_000, 800, replace=False)).astype(np.int64)
    q = rng.integers(0, 20_000, 257).astype(np.int64)
    pos_n, f_n = nb.lookup_batch(sk, q)
    pos_p, f_p = pb.lookup_batch(sk, q)
    np.testing.assert_array_equal(pos_n, pos_p)
    np.testing.assert_array_equal(f_n, f_p)
    present = np.isin(q, sk)
    np.testing.assert_array_equal(f_n, present)


# --------------------------- store-level parity ------------------------------
def _drive(store, n_steps=25, batch=300):
    rng = np.random.default_rng(11)
    oracle = {}
    for _ in range(n_steps):
        ks = rng.integers(0, 30_000, size=batch)
        vs = rng.integers(0, 2**31, size=batch)
        store.write("t", ks, vs)
        oracle.update(zip(ks.tolist(), vs.tolist()))
    return oracle


def test_read_batch_matches_scalar_lookup_loop():
    store = LSMStore(small_config())
    store.create_tree("t")
    oracle = _drive(store)
    rng = np.random.default_rng(5)
    probe = np.concatenate([
        rng.choice(np.fromiter(oracle, np.int64), 400),
        rng.integers(40_000, 50_000, size=100)])     # absent keys
    found_b, vals_b = store.read_batch("t", probe)
    for i, k in enumerate(probe.tolist()):
        f, v = store.lookup("t", k)
        assert f == found_b[i], k
        assert v == vals_b[i], k
        assert f == (k in oracle)
        if f:
            assert v == oracle[k]


@pytest.mark.parametrize("scheme", SCHEMES)
def test_store_end_to_end_pallas_backend(scheme):
    """A store configured with backend="pallas" (interpret mode on CPU)
    reconciles exactly like the numpy reference."""
    store_p = LSMStore(small_config(scheme=scheme, backend="pallas"))
    store_p.create_tree("t")
    oracle = _drive(store_p, n_steps=12, batch=200)
    store_n = LSMStore(small_config(scheme=scheme, backend="numpy"))
    store_n.create_tree("t")
    _drive(store_n, n_steps=12, batch=200)
    rng = np.random.default_rng(9)
    probe = np.concatenate([
        rng.choice(np.fromiter(oracle, np.int64), 150),
        rng.integers(40_000, 50_000, size=50)])
    found_p, vals_p = store_p.read_batch("t", probe)
    found_n, vals_n = store_n.read_batch("t", probe)
    np.testing.assert_array_equal(found_p, found_n)
    np.testing.assert_array_equal(vals_p, vals_n)
    for i, k in enumerate(probe.tolist()):
        assert bool(found_p[i]) == (k in oracle)
        if found_p[i]:
            assert int(vals_p[i]) == oracle[k]
    # identical structure -> identical I/O accounting across backends,
    # on the read path AND the write (flush/merge) path
    sp, sn = store_p.disk.stats, store_n.disk.stats
    assert sp.query_pins == sn.query_pins
    assert sp.pages_flushed == sn.pages_flushed
    assert sp.pages_merge_written == sn.pages_merge_written
    assert sp.merge_pins == sn.merge_pins
    assert sp.pages_merge_read == sn.pages_merge_read
    assert (sp.flushes_mem, sp.flushes_log) == (sn.flushes_mem,
                                                sn.flushes_log)


# --------------------------- write-pin accounting ----------------------------
def test_write_sst_accounting_flush_vs_merge():
    """Write-path mirror of the query_pin_many read assertions: flush vs
    merge writes land in the right counters, written pages (data + Bloom)
    install into the buffer cache without a miss, and drop_sst
    invalidates them."""
    cache = ClockCache(1024)
    disk = Disk(page_bytes=4 * KB, cache=cache)
    keys = np.arange(0, 100, dtype=np.int64)
    sst_f = sstable_from_run(keys, keys, 0, 0, 256, 4 * KB)
    sst_m = sstable_from_run(keys, keys, 0, 0, 256, 4 * KB)
    disk.write_sst(sst_f, flush=True)
    assert disk.stats.pages_flushed == sst_f.num_pages + sst_f.bloom_pages()
    assert disk.stats.pages_merge_written == 0
    disk.write_sst(sst_m, flush=False)
    assert disk.stats.pages_merge_written \
        == sst_m.num_pages + sst_m.bloom_pages()
    assert disk.stats.pages_flushed \
        == sst_f.num_pages + sst_f.bloom_pages()   # unchanged
    # freshly written pages are cache-resident: pins hit, no disk read
    misses0 = cache.misses
    for p in range(sst_f.num_pages):
        disk.query_pin(sst_f.sst_id, p)
    disk.query_pin(sst_f.sst_id, -1)               # bloom page unit
    assert cache.misses == misses0
    assert disk.stats.pages_query_read == 0
    # dropping the SSTable invalidates every page (data + bloom)
    disk.drop_sst(sst_f)
    disk.query_pin(sst_f.sst_id, 0)
    assert disk.stats.pages_query_read == 1


def test_write_path_accounting_batched_vs_scalar():
    """Flush/merge write accounting must be identical whether entries
    arrive as one batch or one-at-a-time (with the same tick sequence)."""
    def drive(batched):
        store = LSMStore(small_config(write_memory_bytes=512 * KB))
        store.create_tree("t")
        rng = np.random.default_rng(21)
        for _ in range(20):
            ks = rng.integers(0, 30_000, size=300)
            vs = rng.integers(0, 2**31, size=300)
            if batched:
                store.write_batch("t", ks, vs, tick=False)
            else:
                for k, v in zip(ks.tolist(), vs.tolist()):
                    store.write_batch("t", [k], [v], tick=False)
            store.scheduler.tick()
        return store.disk.stats
    sb, ss = drive(True), drive(False)
    assert sb.pages_flushed == ss.pages_flushed > 0
    assert sb.pages_merge_written == ss.pages_merge_written > 0
    assert sb.merge_pins == ss.merge_pins
    assert sb.pages_merge_read == ss.pages_merge_read
    assert (sb.flushes_mem, sb.flushes_log) == (ss.flushes_mem,
                                                ss.flushes_log)
    assert sb.entries_written == ss.entries_written


def test_ingest_run_parity_and_dedup(backends):
    """ingest_run: numpy and pallas agree bit-for-bit on sorted order,
    surviving values, and source positions (newest occurrence wins)."""
    nb, pb = backends
    rng = np.random.default_rng(4)
    for n, hi in [(1, 10), (257, 40), (1000, 10**6), (640, 25)]:
        keys = rng.integers(0, hi, size=n)
        vals = rng.integers(-2**31 + 1, 2**31, size=n)
        k1, v1, s1 = nb.ingest_run(keys, vals)
        k2, v2, s2 = pb.ingest_run(keys, vals)
        np.testing.assert_array_equal(k1, k2)
        np.testing.assert_array_equal(v1, v2)
        np.testing.assert_array_equal(s1, s2)
        oracle = {}
        for k, v in zip(keys.tolist(), vals.tolist()):
            oracle[k] = v
        assert k1.tolist() == sorted(oracle)
        assert v1.tolist() == [oracle[k] for k in k1.tolist()]
    # out-of-int32-domain keys fall back to the reference
    before = pb.fallback_calls
    k, v, s = pb.ingest_run(np.array([7, 2**40, 7], np.int64),
                            np.array([1, 2, 3], np.int64))
    assert pb.fallback_calls == before + 1
    assert k.tolist() == [7, 2**40] and v.tolist() == [3, 2]


def test_read_batch_counts_ops_like_scalar():
    store = LSMStore(small_config())
    store.create_tree("t")
    store.write("t", [1, 2, 3], [1, 2, 3])
    before = store.disk.stats.ops
    store.read_batch("t", np.arange(64))
    assert store.disk.stats.ops == before + 64
