"""The program's recorder (``repro.runtime.tracing``): nothing recorded
when off, parent links, submit numbers, counters and self time while
recording, every span of the store on both backends, the memory
component's level views built once per change, results and IOStats
unchanged by recording, and record times that line up with the
profile's ``repro.*`` events."""
import glob
import os

import numpy as np
import pytest

from repro.core.lsm.sstable import reset_sst_ids
from repro.core.lsm.storage import StoreConfig
from repro.core.service import Get, Put, StorageService
from repro.runtime import tracing

KB, MB = 1 << 10, 1 << 20


@pytest.fixture(autouse=True)
def _empty_recorder():
    tracing.clear()
    yield
    tracing.clear()


def test_nothing_recorded_when_off():
    assert not tracing.active()
    with tracing.span("a", x=1) as rec:
        tracing.count("c", 5)
    assert rec is None
    assert tracing.records() == [] and tracing.counters() == {}


def test_recording_links_parents_submits_and_counts():
    with tracing.recording():
        assert tracing.active()
        with tracing.span("outer", submit=7) as outer:
            tracing.count("c", 2)
            with tracing.span("inner", k=3):
                tracing.count("c")
                tracing.count("d", 4)
        with tracing.span("alone"):
            pass
        tracing.count("c", 10)             # no span open: counter only
    assert not tracing.active()
    recs = tracing.records()
    assert [r.name for r in recs] == ["outer", "inner", "alone"]
    o, i, a = recs
    assert (o.parent, i.parent, a.parent) == (None, 0, None)
    assert (o.submit, i.submit, a.submit) == (7, 7, None)
    assert i.attrs == {"k": 3} and outer is o
    assert o.counts == {"c": 2} and i.counts == {"c": 1, "d": 4}
    assert tracing.counters() == {"c": 13, "d": 4}
    assert all(r.start <= r.end for r in recs)
    assert o.start <= i.start and i.end <= o.end


def test_self_time_is_total_less_children():
    with tracing.recording():
        with tracing.span("p"):
            for _ in range(3):
                with tracing.span("k"):
                    sum(range(2000))
    p, *kids = tracing.records()
    s = tracing.summary()
    assert s["spans"]["k"]["calls"] == 3
    total = p.ns / 1e6
    assert s["spans"]["p"]["total_ms"] == pytest.approx(total)
    assert s["spans"]["p"]["self_ms"] == pytest.approx(
        total - sum(k.ns for k in kids) / 1e6)
    assert s["spans"]["k"]["self_ms"] == s["spans"]["k"]["total_ms"]


def test_clear_forgets_and_open_spans_lose_their_parent():
    with tracing.recording():
        with tracing.span("before"):
            tracing.clear()
            with tracing.span("after"):
                pass
    (r,) = tracing.records()
    assert r.name == "after" and r.parent is None


# ---------------------------------------------------------------- the store
def _service(backend):
    reset_sst_ids()
    cfg = StoreConfig(total_memory_bytes=32 * MB,
                      write_memory_bytes=256 * KB, sim_cache_bytes=1 * MB,
                      page_bytes=4 * KB, entry_bytes=256,
                      active_sstable_bytes=64 * KB, sstable_bytes=128 * KB,
                      max_log_bytes=8 * MB, scheme="partitioned",
                      flush_policy="opt", backend=backend,
                      device_pool_bytes=32 * MB)
    svc = StorageService.open(cfg)
    svc.create_tree("t")
    return svc


def _drive(svc, submits=36, seed=0):
    """Put and Get batches: flushes, disk merges, a warm store view and a
    memory component holding sealed tables."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(submits):
        ks = rng.integers(0, 20_000, 256)
        if i % 3 != 2:
            svc.submit_all([Put("t", ks, ks * 3)])
        (r,) = svc.submit([Get("t", rng.integers(0, 20_000, 256))])
        out.append((r.found, r.vals))
    return out


COMMON = {"service.submit", "service.plan", "service.governor",
          "mem.search", "mem.view_build", "read.pin_replay", "write.ingest",
          "tick.upkeep", "tick.flush", "tick.merge", "tick.wal",
          "flush.pick", "flush.tree"}
DEVICE = {"read.probe_prep", "read.probe_pull", "merge.fold"}


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_a_submit_records_every_span(backend):
    svc = _service(backend)
    with tracing.recording():
        _drive(svc)
    recs = tracing.records()
    names = {r.name for r in recs}
    want = COMMON | (DEVICE if backend == "pallas" else set())
    assert want <= names, want - names
    subs = [r for r in recs if r.name == "service.submit"]
    assert [r.submit for r in subs] == list(range(1, len(subs) + 1))
    assert all(r.parent is None for r in subs)
    assert {r.attrs["gets"] > 0 for r in subs} == {True, False}
    # every other span of a submit belongs to it
    by_index = {r.index: r for r in recs}
    for r in recs:
        if r.parent is not None:
            assert r.submit == by_index[r.parent].submit
    c = tracing.counters()
    assert c["mem.tables_searched"] > 0
    assert c["flush.entries"] > 0 and "flush.entries_log" not in c
    flushes = [r for r in recs if r.name == "flush.tree"]
    assert {by_index[r.parent].name for r in flushes} == {"tick.flush"}
    assert sum(r.counts["flush.entries"] for r in flushes) \
        == c["flush.entries"]
    if backend == "pallas":
        assert c["h2d_bytes"] > 0 and c["d2h_bytes"] > 0
        assert c["merge.steps"] > 0
        folds = [r for r in recs if r.name == "merge.fold"]
        assert sum(r.counts["merge.steps"] for r in folds) \
            == c["merge.steps"]


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_memory_levels_build_their_views_once_per_change(backend):
    from repro.core.engine import get_backend
    from repro.core.lsm.memtable import PartitionedMemComponent
    reset_sst_ids()
    mem = PartitionedMemComponent(entry_bytes=16, page_bytes=256,
                                  active_bytes_max=1 * KB, size_ratio=2,
                                  backend=get_backend(backend))
    rng = np.random.default_rng(5)
    for i in range(16):
        ks = rng.integers(0, 4_000, 64)
        mem.ingest_batch(ks, ks + i, i * KB)
        mem.seal_active()
        mem.maintain()
    levels = sum(1 for lvl in mem.levels if lvl)
    assert levels >= 3
    absent = np.arange(10_000, 10_200)       # every level searches them
    gets = 4
    with tracing.recording():
        for _ in range(gets):
            mem.lookup_batch(absent)
    c = tracing.counters()
    assert c["mem.view_builds"] == levels
    assert c["mem.tables_searched"] == gets * levels <= gets * len(mem.levels)
    builds = [r for r in tracing.records() if r.name == "mem.view_build"]
    assert len(builds) == levels
    assert {tracing.records()[r.parent].name for r in builds} \
        == {"mem.search"}
    # a seal changes M1 alone: only its view is built again
    tracing.clear()
    mem.ingest_batch(np.array([5, 6]), np.array([1, 2]), 99 * KB)
    mem.seal_active()
    assert sum(1 for lvl in mem.levels if lvl) == levels
    with tracing.recording():
        found, _ = mem.lookup_batch(np.concatenate([absent, [5, 6]]))
        mem.lookup_batch(absent)
    assert found[-2:].all()
    assert tracing.counters()["mem.view_builds"] == 1


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_recording_changes_no_result(backend):
    svc0 = _service(backend)
    out0 = _drive(svc0, submits=24, seed=3)
    svc1 = _service(backend)
    with tracing.recording():
        out1 = _drive(svc1, submits=24, seed=3)
    assert tracing.records()
    for (f0, v0), (f1, v1) in zip(out0, out1):
        np.testing.assert_array_equal(f0, f1)
        np.testing.assert_array_equal(v0, v1)
    assert vars(svc0.store.disk.stats) == vars(svc1.store.disk.stats)


def test_records_line_up_with_the_profile(tmp_path):
    import jax
    from jax.profiler import ProfileData
    svc = _service("pallas")
    _drive(svc, submits=6)                 # compiles outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.active()
        _drive(svc, submits=3, seed=1)
    finally:
        jax.profiler.stop_trace()
    assert not tracing.active()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    events = sorted(
        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name)
        for plane in ProfileData.from_file(path).planes
        if not plane.name.startswith("/device:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith(tracing.PREFIX))
    recs = tracing.records()
    assert len(events) == len(recs) > 0
    off = recs[0].start - events[0][0]
    slack = 50_000                                  # ns
    for r, (s, e, name) in zip(recs, events):
        assert name == tracing.PREFIX + r.name
        assert s - slack <= r.start - off <= r.end - off <= e + slack


def test_compile_events_outside_backend_calls():
    """Compiles count per thread once a backend listens; the backend's
    counters move only inside its own calls, and a recording counts
    them on the innermost span."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine.pallas_backend import PallasBackend
    pb = PallasBackend(interpret=True)
    x = jnp.arange(7)
    c0 = tracing.compile_events()
    jax.jit(lambda v: v * 3 + 1)(x)
    assert tracing.compile_events() == c0 + 1
    assert pb.jit_stats() == {"jit_compiles": 0, "jit_cache_hits": 0}
    with tracing.recording():
        with tracing.span("s"):
            jax.jit(lambda v: v - 5)(x)
    assert tracing.counters()["compiles"] == 1
    assert tracing.records()[0].counts == {"compiles": 1}
