"""Paged KV pool policies and the HBM tuner's direction."""
import numpy as np

from repro.runtime.hbm_tuner import HBMTuner, HBMTunerConfig
from repro.runtime.kvcache import KVPoolConfig, PagedKVPool


# ----------------------------- KV pool ----------------------------------------
def test_kv_pool_accounting_and_policies():
    pool = PagedKVPool(KVPoolConfig(page_tokens=4, total_pages=64,
                                    pool_pages=32, policy="opt"))
    for i in range(10):
        pool.append_tokens("hot", 16)        # 4 pages per call
        if i % 5 == 0:
            pool.append_tokens("cold", 4)
    assert pool.pool_pages_used <= pool.cfg.pool_pages
    hot, cold = pool.stream("hot"), pool.stream("cold")
    assert hot.allocated > cold.allocated
    # OPT keeps the hot stream's share near its allocation rate
    assert len(hot.pages) >= len(cold.pages)
    # finishing a stream frees its pages
    used = pool.pool_pages_used
    pool.finish_stream("hot")
    assert pool.pool_pages_used < used


def test_kv_pool_min_lsn_policy_evicts_oldest():
    pool = PagedKVPool(KVPoolConfig(page_tokens=1, total_pages=16,
                                    pool_pages=8, policy="lsn"))
    pool.append_tokens("old", 4)
    pool.append_tokens("new", 4)
    pool.append_tokens("new", 4)             # forces flushes
    assert pool.stream("old").offloaded >= 1
    assert pool.stream("new").offloaded == 0


def test_hbm_tuner_moves_toward_prefix_cache_under_reuse():
    """Prefix-heavy workload: ghost hits make the tuner shrink the pool."""
    pool = PagedKVPool(KVPoolConfig(page_tokens=4, total_pages=256,
                                    pool_pages=192, sim_pages=64))
    tuner = HBMTuner(pool, HBMTunerConfig(ops_cycle=64))
    rng = np.random.default_rng(0)
    x0 = pool.cfg.pool_pages
    for step in range(2000):
        # shared prompt chunks cycling through a working set > cache size
        pool.lookup_prefix(int(rng.integers(0, 96)))
        if step % 17 == 0:
            pool.append_tokens("s", 4)
        tuner.maybe_tune()
    assert pool.cfg.pool_pages < x0, \
        (pool.cfg.pool_pages, [r["x_next"] for r in tuner.records])
