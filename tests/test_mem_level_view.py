"""The partitioned memory component's batched reads search each memory
level once, through a view of the whole level. Driven through random
writes, seals, merges, flushes and a checkpoint restore, its answers
must equal a dict of what the component holds (newest level wins) and
the per-table search (``sstable.probe_tier``) it replaced, on both
backends, tombstones and absent keys included."""
import numpy as np
import pytest

from repro.core.durability.checkpoint import _mem_image, _restore_mem
from repro.core.engine import NumpyBackend, PallasBackend
from repro.core.lsm.memtable import PartitionedMemComponent
from repro.core.lsm.sstable import TOMBSTONE, probe_tier, reset_sst_ids

KEY_SPACE = 6_000
ENTRY = 16


@pytest.fixture(scope="module")
def backends():
    return {"numpy": NumpyBackend(), "pallas": PallasBackend(interpret=True)}


def _mem(backend):
    reset_sst_ids()
    return PartitionedMemComponent(entry_bytes=ENTRY, page_bytes=256,
                                   active_bytes_max=64 * ENTRY,
                                   size_ratio=2, backend=backend)


def held(mem) -> dict:
    """What the component holds, newest wins: levels oldest first, then
    the active table."""
    out = {}
    for lvl in reversed(mem.levels):
        for s in lvl:
            out.update(zip(s.keys.tolist(), s.vals.tolist()))
    out.update((k, v) for k, (v, _) in mem.active.items())
    return out


def per_table(mem, keys):
    """The search this replaced: M0's dict, then ``probe_tier`` with the
    backend's ``lookup_batch`` once per table, level by level."""
    keys = np.asarray(keys, np.int64)
    found = np.zeros(len(keys), bool)
    vals = np.zeros(len(keys), np.int64)
    for i, k in enumerate(keys.tolist()):
        hit = mem.active.get(k)
        if hit is not None:
            found[i], vals[i] = True, hit[0]
    unresolved = ~found
    for lvl in mem.levels:
        probe_tier(lvl, keys, found, vals, unresolved,
                   mem.backend.lookup_batch)
    return found, vals


def check(mem, keys):
    found, vals = mem.lookup_batch(keys)
    f0, v0 = per_table(mem, keys)
    np.testing.assert_array_equal(found, f0)
    np.testing.assert_array_equal(vals, v0)
    want = held(mem)
    for k, f, v in zip(keys.tolist(), found.tolist(), vals.tolist()):
        assert f == (k in want), k
        if f:
            assert v == want[k], k
    return found, vals


def queries(mem, rng, n=96):
    """Held keys, keys of the key space (most absent), and keys beyond
    every table."""
    have = np.fromiter(held(mem), np.int64)
    parts = [rng.integers(0, KEY_SPACE, n // 2),
             np.array([KEY_SPACE + 7, 1 << 30], np.int64)]
    if len(have):
        parts.append(rng.choice(have, n // 2))
    return rng.permutation(np.concatenate(parts))


# step -> weight: flushes rare enough that three memory levels build up
STEPS = {"ingest": 0.45, "seal": 0.08, "maintain_step": 0.2, "maintain": 0.1,
         "flush_partial": 0.05, "flush_min_lsn": 0.04, "flush_full": 0.02,
         "restore": 0.06}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_level_views_match_the_per_table_search(backends, backend, seed):
    be = backends[backend]
    mem = _mem(be)
    rng = np.random.default_rng(seed)
    lsn, val = 0, 1
    f0 = be.fallback_calls if backend == "pallas" else 0
    tombstones = 0
    for _ in range(80):
        step = rng.choice(list(STEPS), p=list(STEPS.values()))
        if step == "ingest":
            n = int(rng.integers(1, 160))
            ks = rng.integers(0, KEY_SPACE, n)
            vs = np.arange(val, val + n, dtype=np.int64)
            dead = rng.random(n) < 0.1
            vs[dead] = TOMBSTONE
            tombstones += int(dead.sum())
            val += n
            mem.ingest_batch(ks, vs, lsn)
            lsn += n * ENTRY
            if mem.over_active_limit():
                mem.seal_active()
        elif step == "seal":
            mem.seal_active()
        elif step == "maintain_step":
            mem.maintain_step()
        elif step == "maintain":
            mem.maintain()
        elif step == "flush_partial":
            mem.flush_partial()
        elif step == "flush_min_lsn":
            mem.flush_min_lsn()
        elif step == "flush_full":
            mem.flush_full()
        else:                        # a checkpoint restore replaces levels
            _restore_mem(mem, _mem_image(mem))
        check(mem, queries(mem, rng))
        check(mem, queries(mem, rng))   # a second read reuses the views
    assert tombstones
    if backend == "pallas":
        assert be.fallback_calls == f0   # every key inside int32


def _filled(mem, rng, keys_hi=KEY_SPACE):
    lsn = 0
    for i in range(12):
        ks = rng.integers(0, keys_hi, 64)
        mem.ingest_batch(ks, ks * 3 + i, lsn)
        lsn += 64 * ENTRY
        mem.seal_active()
        mem.maintain()
    assert sum(1 for lvl in mem.levels if lvl) >= 2
    return mem


def test_keys_outside_int32_take_the_fallback_and_count_it(backends):
    pb = backends["pallas"]
    rng = np.random.default_rng(7)
    # a memory level holding keys beyond int32: its view stays on the host
    mem = _filled(_mem(pb), rng)
    big = np.array([2**31 + 5, 2**40], np.int64)
    mem.ingest_batch(big, np.array([11, 12], np.int64), 10**6)
    mem.seal_active()
    q = np.concatenate([queries(mem, rng), big])
    c0 = pb.fallback_calls
    found, vals = check(mem, q)
    assert found[-2:].all() and vals[-2:].tolist() == [11, 12]
    assert pb.fallback_calls > c0
    # int32 levels, queries outside int32: the search falls back too
    mem = _filled(_mem(pb), rng)
    q = np.concatenate([queries(mem, rng), np.array([-3, 2**33])])
    c0 = pb.fallback_calls
    found, _ = mem.lookup_batch(q)
    # the two keys outside int32 stay unresolved: every level searches
    assert pb.fallback_calls - c0 == sum(1 for lvl in mem.levels if lvl)
    assert not found[-2:].any()
    check(mem, q)
