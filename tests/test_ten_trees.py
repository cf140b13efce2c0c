"""Ten LSM-trees sharing one write memory under an 80-20 write hotspot,
served through ``StorageService``: every Get answer against a dict per
tree, the numpy and the Pallas backends' stores bit-identical, the flush
counters against what the flushes installed, and the ``opt`` policy's
choice of which tree to flush (§4.2)."""
import numpy as np
import pytest

from repro.core.lsm.sstable import reset_sst_ids
from repro.core.lsm.storage import StoreConfig
from repro.core.service import Get, Put, StorageService, WriteAck
from repro.runtime import tracing
from test_differential import fingerprint

KB, MB = 1 << 10, 1 << 20
TREES = [f"t{i}" for i in range(10)]
HOT = [0.4, 0.4] + [0.025] * 8         # 80% of the submits to 2 of 10 trees
LOGS = {"log_above_write_memory": 256 * MB,   # every flush memory-triggered
        "log_below_write_memory": 4 * KB}     # every flush log-triggered


@pytest.fixture(autouse=True)
def _empty_recorder():
    tracing.clear()
    yield
    tracing.clear()


def _service(backend, log_bytes, trees=TREES, **kw):
    reset_sst_ids()
    cfg = StoreConfig(**{**dict(
        total_memory_bytes=64 * KB, write_memory_bytes=8 * KB,
        sim_cache_bytes=4 * KB, page_bytes=512, entry_bytes=8,
        active_sstable_bytes=1 * KB, sstable_bytes=2 * KB,
        max_log_bytes=log_bytes, scheme="partitioned", flush_policy="opt",
        backend=backend, device_pool_bytes=64 * MB), **kw})
    svc = StorageService.open(cfg)
    for name in trees:
        svc.create_tree(name)
    return svc


def _count_l0_installs(svc) -> list:
    """Wrap every tree's ``l0.insert`` (called only by the flush that
    installs a table in L0) to add up the entries it installs."""
    installed = [0]
    for tree in svc.store.trees.values():
        def insert(sst, _inner=tree.l0.insert):
            installed[0] += sst.num_entries
            return _inner(sst)
        tree.l0.insert = insert
    return installed


def _drive(svc, submits=160, seed=0):
    """Put and Get batches of 64 keys over the ten trees, 80-20 across
    them; returns the Get answers that differ from a dict per tree."""
    rng = np.random.default_rng(seed)
    oracle = [dict() for _ in TREES]
    wrong = 0
    for _ in range(submits):
        t = int(rng.choice(len(TREES), p=HOT))
        keys = rng.integers(0, 1500, 64)
        if rng.random() < 0.6:
            vals = rng.integers(1, 2**31 - 1, 64)
            (ack,) = svc.submit_all([Put(TREES[t], keys, vals)])
            assert isinstance(ack, WriteAck)
            oracle[t].update(zip(keys.tolist(), vals.tolist()))
            continue
        (res,) = svc.submit([Get(TREES[t], keys)])
        want = [oracle[t].get(k) for k in keys.tolist()]
        found = np.array([w is not None for w in want])
        vals = np.array([w or 0 for w in want])
        wrong += int(np.count_nonzero((res.found != found)
                                      | (found & (res.vals != vals))))
    return wrong


@pytest.mark.parametrize("log", sorted(LOGS))
def test_ten_trees_answer_as_the_oracle_on_both_backends(log):
    stores = {}
    for backend in ("numpy", "pallas"):
        svc = _service(backend, LOGS[log])
        installed = _count_l0_installs(svc)
        with tracing.recording():
            assert _drive(svc) == 0
        stats = svc.store.disk.stats
        c = tracing.counters()
        assert stats.flushes_mem + stats.flushes_log > 0
        assert c["flush.entries"] == installed[0] > 0
        assert (c.get("flush.entries_log", 0) == 0) \
            == (stats.flushes_log == 0)
        trees = {r.attrs["tree"] for r in tracing.records()
                 if r.name == "flush.tree"}
        assert {"t0", "t1"} <= trees
        stores[backend] = svc.store
        tracing.clear()
    n, p = stores["numpy"], stores["pallas"]
    assert fingerprint(n) == fingerprint(p)
    assert vars(n.disk.stats) == vars(p.disk.stats)


@pytest.mark.parametrize("policy, victim", [("opt", "cold"), ("mem", "hot"),
                                            ("lsn", "hot")])
def test_opt_flushes_a_cold_tree_holding_more_memory_than_its_rate(
        policy, victim):
    """``hot`` is written first and most: 600 fresh keys, then 40
    rewrites of 64 of them. ``cold`` then writes 400 fresh keys once,
    which fills write memory. ``hot`` holds more memory and the oldest
    entry, so ``mem`` and ``lsn`` flush it; ``cold``'s share of write
    memory (0.4) exceeds its share of the write rate, so ``opt`` flushes
    ``cold``."""
    svc = _service("numpy", 256 * MB, trees=["hot", "cold"],
                   flush_policy=policy)
    rng = np.random.default_rng(1)
    hot, cold = np.split(rng.permutation(1 << 20)[:1000], [600])
    with tracing.recording():
        for ks in np.split(hot, 6):
            svc.submit_all([Put("hot", ks, ks)])
        for _ in range(40):
            svc.submit_all([Put("hot", hot[:64], rng.integers(1, 99, 64))])
        assert not any(r.name == "flush.tree" for r in tracing.records())
        for ks in np.split(cold, 4):
            svc.submit_all([Put("cold", ks, ks)])
    flushed = [r.attrs for r in tracing.records() if r.name == "flush.tree"]
    picks = [r.attrs for r in tracing.records() if r.name == "flush.pick"]
    assert flushed and picks
    assert flushed[0]["tree"] == victim
    assert (flushed[0]["trigger"], flushed[0]["kind"]) == ("mem", "partial")
    assert picks[0] == {"policy": policy, "candidates": 2}
    if policy == "opt":
        store = svc.store
        rate = {n: sum(b for _, b in store._rate_win[n])
                for n in ("hot", "cold")}
        assert rate["cold"] / sum(rate.values()) < 0.4
