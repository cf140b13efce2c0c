"""``filter_probes_per_get`` on windows recorded here: the store probe's
count of (tier, query) pairs with a covering table per Get submit, and
nothing from a program that does not count them."""
import numpy as np
import pytest

from chipbench.metrics import filter_probes_per_get
from chipbench.program import Window
from chipbench.run import Context


def _window(monkeypatch, lookup):
    """The reader's window over what ``lookup`` records inside one Get
    submit of 64 keys."""
    from repro.runtime import tracing
    n0 = len(tracing.records())
    with tracing.recording():
        with tracing.span("service.submit", submit=0, gets=64):
            lookup()
    recs = tracing.records()[n0:]
    w = Window(recs, recs[0].start, recs[0].end + 1)
    monkeypatch.setattr(filter_probes_per_get, "window", lambda ctx: w)
    return Context(trace=None, counts={}, compiles=0, calls={}, peaks={})


def test_reads_the_store_probes_count(monkeypatch):
    from repro.core.engine import PallasBackend
    from repro.core.lsm.sstable import partition_run, reset_sst_ids
    reset_sst_ids()
    keys = np.arange(0, 40_000, 8, dtype=np.int64)
    tiers = [partition_run(keys[i::2], keys[i::2], 0, 0, 256, 4096,
                           256 * 1000) for i in range(2)]
    pb = PallasBackend(interpret=True)
    view = pb.prepare_store(tiers, lambda s: pb.bloom_build(s.keys))
    q = np.arange(0, 64_000, 1000, dtype=np.int64)          # 64 keys
    out = {}
    ctx = _window(monkeypatch, lambda: out.setdefault(
        "r", pb.lookup_store_fused(view, q)))
    ok = int(out["r"].ok.sum())
    assert 0 < ok < 2 * len(q)
    assert filter_probes_per_get.read(ctx) == pytest.approx(ok)


def test_nothing_from_a_program_without_the_counter(monkeypatch):
    """As the parent, whose store probe counts no filter probes."""
    ctx = _window(monkeypatch, lambda: None)
    assert filter_probes_per_get.read(ctx) is None
    monkeypatch.setattr(filter_probes_per_get, "window", lambda ctx: None)
    assert filter_probes_per_get.read(ctx) is None
