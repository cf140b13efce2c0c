"""``merge_groups_per_fold`` on windows recorded here: the merge fold's
count of key-disjoint groups per ``merge.fold`` record, and nothing from
a program that does not count them."""
import numpy as np
import pytest

from chipbench.metrics import merge_groups_per_fold
from chipbench.program import Window
from chipbench.run import Context


def _window(monkeypatch, merges):
    """The reader's window over what ``merges`` records inside one Put
    submit of 64 keys."""
    from repro.runtime import tracing
    n0 = len(tracing.records())
    with tracing.recording():
        with tracing.span("service.submit", submit=0, puts=64):
            merges()
    recs = tracing.records()[n0:]
    w = Window(recs, recs[0].start, recs[0].end + 1)
    monkeypatch.setattr(merge_groups_per_fold, "window", lambda ctx: w)
    return Context(trace=None, counts={}, compiles=0, calls={}, peaks={})


def test_reads_the_folds_groups(monkeypatch):
    from repro.core.engine import PallasBackend
    pb = PallasBackend(interpret=True)
    level = [(np.arange(i, i + 400, 4), np.arange(100)) for i in
             range(0, 2_000, 500)]                 # four disjoint tables
    newer = (np.arange(1, 2_000, 7), np.arange(286))
    ctx = _window(monkeypatch, lambda: (
        pb.merge_runs([newer] + level),            # 2 groups
        pb.merge_runs(level[::-1])))               # 1 group, no device
    assert merge_groups_per_fold.read(ctx) == pytest.approx(1.5)


def test_nothing_from_a_program_without_the_counter(monkeypatch):
    """As the parent, whose fold counts no groups."""
    from repro.runtime import tracing

    def pairwise_fold():
        with tracing.span("merge.fold"):
            tracing.count("merge.steps", 1)
    ctx = _window(monkeypatch, pairwise_fold)
    assert merge_groups_per_fold.read(ctx) is None
    monkeypatch.setattr(merge_groups_per_fold, "window", lambda ctx: None)
    assert merge_groups_per_fold.read(ctx) is None
