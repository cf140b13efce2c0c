"""The readers of the program's own spans and counters, on a tiny traced
window of each cell on the CPU: positive where the trace names a
device, nothing without one."""
import jax
import numpy as np
import pytest

from chipbench import run, store, trace
from chipbench.run import Context
from chipbench.spec import metric_reader
from chipbench.traffic import TrafficMix

from .conftest import CELLS, tiny_cell

SEED = 2**31 + 777
PROGRAM_METRICS = {"mem_search_ms", "mem_tables_per_get", "probe_prep_ms",
                   "pin_replay_ms", "h2d_mb_per_submit", "upkeep_pct",
                   "disk_merge_pct", "merge_steps_per_put"}


@pytest.fixture(scope="module", params=CELLS)
def traced(request):
    """A cell's tiny store after its warm-up, and the trace of a short
    window served through ``run.run_window``, as a traced run takes it."""
    cell = tiny_cell(request.param)
    cfg = cell.config
    names = store.tree_names(cfg)
    rng_data, rng_warm, rng_window = [
        np.random.default_rng(s)
        for s in np.random.SeedSequence(SEED).spawn(3)]
    svc = store.open_service(cfg)
    keys, vals = store.record_data(cfg, rng_data)
    for name, k, v in zip(names, keys, vals):
        store.install_last_level(svc.store, name, k, v)
    mix = TrafficMix(cell.mix, len(names))
    store.warm_up(svc, mix.submits(rng_warm, keys, puts_only=True),
                  int(cfg["warmup_updates"]), names)
    if "get" in mix.kinds:            # a resident store view, as set-up
        store.build_blooms(svc.store)
        store.warm_reads(svc, mix.submits(rng_warm, keys), names)
    captured: dict = {}
    with trace.capture(captured):
        run.run_window(svc, mix.submits(rng_window, keys), names, 1.5,
                       lambda n: jax.profiler.TraceAnnotation(
                           trace.SPAN_PREFIX + n))
    return cell, captured["trace"]


def _program_metrics(cell) -> set:
    return {m["name"] for m in cell.per_layer} & PROGRAM_METRICS


def _ctx(tr):
    return Context(trace=tr, counts={}, compiles=0, calls={}, peaks={})


def test_every_program_metric_is_read_in_some_cell():
    assert set().union(*(_program_metrics(tiny_cell(c)) for c in CELLS)) \
        == PROGRAM_METRICS


def test_positive_with_a_device_trace(traced):
    cell, tr = traced
    assert tr.window is not None
    tr.devices = 1                    # as the chip's trace has it
    try:
        for name in sorted(_program_metrics(cell)):
            v = metric_reader(name)(_ctx(tr))
            assert v is not None and v > 0, (name, v)
    finally:
        tr.devices = 0


def test_nothing_without_a_device_trace(traced):
    cell, tr = traced
    assert tr.devices == 0            # the CPU's profile names no device
    for name in sorted(_program_metrics(cell)):
        assert metric_reader(name)(_ctx(tr)) is None
        assert metric_reader(name)(_ctx(None)) is None
