"""Differential model-based tests for the batched write path + scheduler.

Random op sequences (write / write_batch / delete / lookup / lookup_batch /
scan / forced flush / scheduler tick / tuner tick) are replayed against a
plain-dict oracle under controlled scheduler ticks. The same sequence is
replayed

  * batched vs scalar (every batch as n batches of one) -- the final store
    state must be *bit-identical* (LSNs are log byte offsets, so a batch
    of n is indistinguishable from n scalar writes), and
  * numpy vs pallas backend -- also bit-identical (merges, ingest dedup
    and Bloom geometry agree exactly across backends),

while every lookup/scan output must be value-identical to the oracle.

Fixed-seed sequences always run; when hypothesis is installed the same
replay machinery is additionally driven property-style.
"""
import numpy as np
import pytest

from repro.core.lsm.sstable import reset_sst_ids
from repro.core.lsm.storage import POLICIES, SCHEMES, LSMStore, StoreConfig
from repro.core.service import (Deferred, Delete, Get, Put, Scan,
                                ServiceConfig, StorageService)
from repro.core.tuner.tuner import AdaptiveMemoryController, TunerConfig

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

KB, MB = 1 << 10, 1 << 20
TREES = ("a", "b")
KEY_SPACE = 2000          # small keyspace: lots of overwrites/tombstones


def small_config(backend="numpy", scheme="partitioned", policy="lsn"):
    # Tiny write memory / active SSTable so short sequences exercise
    # seals, memory merges, flushes and L0/level merges.
    return StoreConfig(
        total_memory_bytes=32 * MB, write_memory_bytes=256 * KB,
        sim_cache_bytes=1 * MB, page_bytes=4 * KB, entry_bytes=256,
        active_sstable_bytes=32 * KB, sstable_bytes=64 * KB,
        max_log_bytes=8 * MB, scheme=scheme, flush_policy=policy,
        backend=backend)


# --------------------------- op generation ----------------------------------
def gen_ops(rng, n_ops=None):
    n = int(n_ops or rng.integers(8, 16))
    ops = []
    for _ in range(n):
        r = rng.random()
        tree = TREES[int(rng.integers(0, len(TREES)))]
        seed = int(rng.integers(0, 2**31))
        if r < 0.40:
            ops.append(("write", tree, seed, int(rng.integers(50, 400))))
        elif r < 0.55:
            ops.append(("delete", tree, seed, int(rng.integers(10, 120))))
        elif r < 0.70:
            ops.append(("lookup", tree, seed, int(rng.integers(20, 200))))
        elif r < 0.82:
            ops.append(("scan", tree, int(rng.integers(0, KEY_SPACE)),
                        int(rng.integers(10, 400))))
        elif r < 0.92:
            ops.append(("flush", tree))
        elif r < 0.96:
            ops.append(("tick",))
        else:
            ops.append(("tune",))
    return ops


# --------------------------- replay ------------------------------------------
def _batch_keys(seed, size, hi=KEY_SPACE):
    rng = np.random.default_rng(seed)
    return rng.integers(0, hi, size=size), rng.integers(0, 2**31, size=size)


def replay(ops, *, backend="numpy", batched=True, scheme="partitioned",
           policy="lsn"):
    """Apply ``ops``; returns (store, outputs, oracle). Asserts every read
    against the oracle as it goes."""
    reset_sst_ids()
    store = LSMStore(small_config(backend, scheme, policy))
    for t in TREES:
        store.create_tree(t)
    ctrl = AdaptiveMemoryController(store, TunerConfig(
        min_step_bytes=64 * KB, min_write_mem=1 * MB, ops_cycle=10**9))
    oracle = {t: {} for t in TREES}
    outputs = []
    for op in ops:
        kind = op[0]
        if kind == "write":
            _, t, seed, size = op
            ks, vs = _batch_keys(seed, size)
            if batched:
                store.write_batch(t, ks, vs, tick=False)
            else:
                for k, v in zip(ks.tolist(), vs.tolist()):
                    store.write_batch(t, [k], [v], tick=False)
            store.scheduler.tick()
            oracle[t].update(zip(ks.tolist(), vs.tolist()))
        elif kind == "delete":
            _, t, seed, size = op
            ks, _ = _batch_keys(seed, size)
            if batched:
                store.delete_batch(t, ks, tick=False)
            else:
                for k in ks.tolist():
                    store.delete_batch(t, [k], tick=False)
            store.scheduler.tick()
            for k in ks.tolist():
                oracle[t][k] = None
        elif kind == "lookup":
            _, t, seed, size = op
            rng = np.random.default_rng(seed)
            ks = rng.integers(0, KEY_SPACE + 500, size=size)  # some absent
            if batched:
                found, vals = store.read_batch(t, ks)
            else:
                found = np.zeros(size, bool)
                vals = np.zeros(size, np.int64)
                for i, k in enumerate(ks.tolist()):
                    f, v = store.lookup(t, k)
                    found[i], vals[i] = f, v
            for i, k in enumerate(ks.tolist()):
                want = oracle[t].get(k)
                assert bool(found[i]) == (want is not None), (t, k)
                if want is not None:
                    assert int(vals[i]) == want, (t, k)
            outputs.append(("lookup", found.tolist(), vals.tolist()))
        elif kind == "scan":
            _, t, lo, width = op
            n = store.scan(t, lo, width)
            want = sum(1 for k, v in oracle[t].items()
                       if lo <= k < lo + width and v is not None)
            assert n == want, (t, lo, width)
            outputs.append(("scan", n))
        elif kind == "flush":
            tree = store.trees[op[1]]
            if not tree.mem.is_empty():
                store.scheduler.flush_tree(tree, trigger="mem")
        elif kind == "tick":
            store.scheduler.tick()
        elif kind == "tune":
            ctrl.tune_now()
    return store, outputs, oracle


# --------------------------- state fingerprint --------------------------------
def _sst_bits(s):
    return (s.keys.tobytes(), s.vals.tobytes(), s.lsn_min, s.lsn_max)


def fingerprint(store):
    """Bit-exact structural state: memory component, L0, disk levels,
    log position, write-memory size (Bloom caches and sst ids excluded)."""
    out = {"log_pos": store.log_pos,
           "write_mem": store.write_memory_bytes}
    for name in sorted(store.trees):
        t = store.trees[name]
        mem, f = t.mem, {}
        if hasattr(mem, "active"):
            f["active"] = sorted(mem.active.items())
        if hasattr(mem, "levels"):
            f["mem_levels"] = [[_sst_bits(s) for s in lvl]
                               for lvl in mem.levels]
        if hasattr(mem, "data"):
            f["data"] = sorted(mem.data.items())
        if hasattr(mem, "segments"):
            f["segments"] = [(s[0].tobytes(), s[1].tobytes(), s[2], s[3],
                              s[4]) for s in mem.segments]
        if hasattr(t.l0, "groups"):
            f["l0"] = [[_sst_bits(s) for s in g] for g in t.l0.groups]
        else:
            f["l0"] = [[_sst_bits(s)] for s in t.l0.runs]
        f["levels"] = [[_sst_bits(s) for s in lvl]
                       for lvl in t.levels.levels]
        out[name] = f
    return out


# --------------------------- fixed-seed suite ---------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("scheme", SCHEMES)
def test_batched_vs_scalar_bit_identical(seed, scheme):
    ops = gen_ops(np.random.default_rng(seed))
    s_b, out_b, _ = replay(ops, batched=True, scheme=scheme)
    s_s, out_s, _ = replay(ops, batched=False, scheme=scheme)
    assert out_b == out_s
    assert fingerprint(s_b) == fingerprint(s_s)
    # identical structure => identical I/O accounting
    assert vars(s_b.disk.stats) == vars(s_s.disk.stats)


# "lsn" is replay's default, which test_batched_vs_scalar_bit_identical runs
@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("policy", [p for p in POLICIES if p != "lsn"])
def test_batched_vs_scalar_across_policies(policy, scheme):
    ops = gen_ops(np.random.default_rng(7), n_ops=12)
    s_b, out_b, _ = replay(ops, batched=True, scheme=scheme, policy=policy)
    s_s, out_s, _ = replay(ops, batched=False, scheme=scheme, policy=policy)
    assert out_b == out_s
    assert fingerprint(s_b) == fingerprint(s_s)


@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("batched", [True, False])
def test_numpy_vs_pallas_bit_identical(batched, policy, scheme):
    ops = gen_ops(np.random.default_rng(5), n_ops=8)
    kw = dict(batched=batched, scheme=scheme, policy=policy)
    s_n, out_n, _ = replay(ops, backend="numpy", **kw)
    s_p, out_p, _ = replay(ops, backend="pallas", **kw)
    assert out_n == out_p
    assert fingerprint(s_n) == fingerprint(s_p)
    assert vars(s_n.disk.stats) == vars(s_p.disk.stats)


def test_delete_shadows_across_flush_and_merge():
    """A tombstone must shadow older versions wherever they sit (memory,
    L0, levels), including after forced flush + merges."""
    reset_sst_ids()
    store = LSMStore(small_config())
    store.create_tree("a")
    ks = np.arange(0, 600, dtype=np.int64)
    store.write_batch("a", ks, ks + 1)
    tree = store.trees["a"]
    store.scheduler.flush_tree(tree, trigger="mem")   # victims to disk
    store.delete_batch("a", ks[::2])                  # delete every other
    store.scheduler.flush_tree(tree, trigger="mem")   # tombstones to disk
    found, vals = store.read_batch("a", ks)
    assert not found[::2].any()
    assert found[1::2].all()
    np.testing.assert_array_equal(vals[1::2], ks[1::2] + 1)
    assert store.scan("a", 0, 600) == 300


def test_tombstones_purged_at_bottom_level():
    """Merges whose output lands in the bottommost level drop tombstones:
    delete-heavy workloads must not accumulate dead entries forever."""
    from repro.core.lsm.sstable import TOMBSTONE
    reset_sst_ids()
    store = LSMStore(small_config())
    store.create_tree("a")
    ks = np.arange(0, 2000, dtype=np.int64)
    store.write_batch("a", ks, ks + 1)
    store.delete_batch("a", ks)
    tree = store.trees["a"]
    for _ in range(200):                       # drain memory to disk
        if tree.mem.is_empty():
            break
        store.scheduler.flush_tree(tree, trigger="mem")
    store.scheduler.tick()
    while tree.merge_l0_once():                # drain L0 into the levels
        pass
    assert tree.mem.is_empty() and tree.l0.num_groups == 0
    for lvl in tree.levels.levels:
        for s in lvl:
            assert not (s.vals == TOMBSTONE).any()
    assert store.scan("a", 0, 2000) == 0
    found, _ = store.read_batch("a", ks[:100])
    assert not found.any()


def test_scan_batch_matches_scalar_scans():
    """A batch of q scans must be bit-identical -- counts, page pins,
    IOStats, cache state -- to q scalar ``scan`` calls (the service's
    grouped-scan step relies on this, including the one-op-per-range
    accounting contract)."""
    def build():
        reset_sst_ids()
        store = LSMStore(small_config())
        store.create_tree("a")
        rng = np.random.default_rng(42)
        for _ in range(6):
            ks = rng.integers(0, KEY_SPACE, 400)
            store.write_batch("a", ks, ks + 1)
        store.delete_batch("a", rng.integers(0, KEY_SPACE, 100))
        return store

    ranges = [(0, 300), (250, 500), (1500, 600), (1999, 50), (700, 1)]
    los = np.array([lo for lo, _ in ranges], np.int64)
    ns = np.array([n for _, n in ranges], np.int64)

    s_scalar = build()
    scalar = [s_scalar.scan("a", lo, n) for lo, n in ranges]
    s_batch = build()
    batched = s_batch.scan_batch("a", los, ns)
    assert batched.tolist() == scalar
    assert vars(s_scalar.disk.stats) == vars(s_batch.disk.stats)
    assert fingerprint(s_scalar) == fingerprint(s_batch)
    # one logical op per range on both paths
    before = s_batch.disk.stats.ops
    s_batch.scan_batch("a", los, ns)
    assert s_batch.disk.stats.ops - before == len(ranges)


def test_write_batch_rejects_reserved_tombstone_payload():
    reset_sst_ids()
    store = LSMStore(small_config())
    store.create_tree("a")
    from repro.core.lsm.sstable import TOMBSTONE
    with pytest.raises(ValueError):
        store.write_batch("a", [1], [TOMBSTONE])


# --------------------------- service front door -------------------------------
def gen_request_batches(rng, n_batches=10):
    """Shuffled mixed-op submit batches (typed requests across both trees)."""
    batches = []
    for _ in range(n_batches):
        reqs = []
        for _ in range(int(rng.integers(2, 7))):
            tree = TREES[int(rng.integers(0, len(TREES)))]
            r = rng.random()
            krng = np.random.default_rng(int(rng.integers(0, 2**31)))
            size = int(rng.integers(10, 200))
            if r < 0.40:
                reqs.append(Put(tree, krng.integers(0, KEY_SPACE, size),
                                krng.integers(0, 2**31, size)))
            elif r < 0.55:
                reqs.append(Delete(tree, krng.integers(0, KEY_SPACE, size)))
            elif r < 0.85:
                reqs.append(Get(tree,
                                krng.integers(0, KEY_SPACE + 500, size)))
            else:
                reqs.append(Scan(tree, int(krng.integers(0, KEY_SPACE)),
                                 int(krng.integers(10, 400))))
        order = rng.permutation(len(reqs))
        batches.append([reqs[i] for i in order])
    return batches


def _kind(req):
    return {Put: "put", Delete: "delete", Get: "get",
            Scan: "scan"}[type(req)]


def direct_apply(store, reqs):
    """The equivalent direct per-tree batched calls: the service's
    documented grouping contract -- (tree, kind) groups in first-appearance
    order, each dispatched as ONE batched store call on the concatenated
    keys, one scheduler tick iff any writes -- hand-rolled against the bare
    ``LSMStore``. Returns per-request read outputs in submission order."""
    groups: dict = {}
    for i, req in enumerate(reqs):
        groups.setdefault((req.tree, _kind(req)), []).append((i, req))
    outputs = {}
    wrote = False
    for (tree, kind), members in groups.items():
        if kind in ("put", "delete"):
            keys = np.concatenate([r.keys for _, r in members])
            if kind == "put":
                vals = np.concatenate(
                    [r.keys if r.vals is None else r.vals
                     for _, r in members])
                store.write_batch(tree, keys, vals, tick=False)
            else:
                store.delete_batch(tree, keys, tick=False)
            wrote = True
        elif kind == "get":
            found, vals = store.read_batch(
                tree, np.concatenate([r.keys for _, r in members]))
            off = 0
            for i, r in members:
                n = len(r.keys)
                outputs[i] = ("get", found[off:off + n].tolist(),
                              vals[off:off + n].tolist())
                off += n
        else:
            for i, r in members:
                outputs[i] = ("scan", store.scan(tree, r.lo, r.n))
    if wrote:
        store.scheduler.tick()
    return [outputs[i] for i in sorted(outputs)]


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("seed", [21, 22])
def test_service_submit_matches_direct_calls(backend, seed):
    """StorageService.submit of shuffled mixed-op batches must leave store
    state AND IOStats bit-identical to the equivalent direct per-tree
    batched calls, with identical per-request read results."""
    batches = gen_request_batches(np.random.default_rng(seed))

    reset_sst_ids()
    svc = StorageService(LSMStore(small_config(backend)),
                         config=ServiceConfig(admission=False))
    for t in TREES:
        svc.create_tree(t)
    out_svc = []
    for reqs in batches:
        for res in svc.submit(reqs):
            assert not isinstance(res, Deferred)
            if hasattr(res, "found"):
                out_svc.append(("get", res.found.tolist(),
                                res.vals.tolist()))
            elif hasattr(res, "count"):
                out_svc.append(("scan", res.count))

    reset_sst_ids()
    store = LSMStore(small_config(backend))
    for t in TREES:
        store.create_tree(t)
    out_direct = []
    for reqs in batches:
        out_direct.extend(direct_apply(store, reqs))

    assert out_svc == out_direct
    assert fingerprint(svc.store) == fingerprint(store)
    assert vars(svc.store.disk.stats) == vars(store.disk.stats)


# "partitioned" is small_config's default, which
# test_service_submit_matches_direct_calls runs
@pytest.mark.parametrize("scheme", [s for s in SCHEMES if s != "partitioned"])
def test_service_submit_matches_direct_calls_schemes(scheme):
    batches = gen_request_batches(np.random.default_rng(23), n_batches=6)
    reset_sst_ids()
    svc = StorageService(LSMStore(small_config(scheme=scheme)),
                         config=ServiceConfig(admission=False))
    for t in TREES:
        svc.create_tree(t)
    for reqs in batches:
        svc.submit(reqs)
    reset_sst_ids()
    store = LSMStore(small_config(scheme=scheme))
    for t in TREES:
        store.create_tree(t)
    for reqs in batches:
        direct_apply(store, reqs)
    assert fingerprint(svc.store) == fingerprint(store)
    assert vars(svc.store.disk.stats) == vars(store.disk.stats)


# --------------------------- hypothesis suite ---------------------------------
if HAVE_HYPOTHESIS:
    @st.composite
    def op_sequences(draw):
        n = draw(st.integers(4, 12))
        ops = []
        for _ in range(n):
            kind = draw(st.sampled_from(
                ["write", "write", "write", "delete", "lookup", "scan",
                 "flush", "tick", "tune"]))
            tree = draw(st.sampled_from(list(TREES)))
            if kind in ("write", "delete", "lookup"):
                ops.append((kind, tree, draw(st.integers(0, 2**31 - 1)),
                            draw(st.integers(10, 300))))
            elif kind == "scan":
                ops.append((kind, tree, draw(st.integers(0, KEY_SPACE)),
                            draw(st.integers(10, 300))))
            elif kind == "flush":
                ops.append((kind, tree))
            else:
                ops.append((kind,))
        return ops

    @settings(max_examples=15, deadline=None)
    @given(op_sequences(),
           st.sampled_from(["partitioned", "btree-dynamic",
                            "accordion-data"]))
    def test_hypothesis_batched_vs_scalar(ops, scheme):
        s_b, out_b, _ = replay(ops, batched=True, scheme=scheme)
        s_s, out_s, _ = replay(ops, batched=False, scheme=scheme)
        assert out_b == out_s
        assert fingerprint(s_b) == fingerprint(s_s)
