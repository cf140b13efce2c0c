"""A tiny interpret-mode rehearsal of the ten-tree hotspot cell on the
CPU: its verdict, the control's, and the flush metrics of its traced
run, beside the one-tree update cell they are contrasted with."""
import pytest

from chipbench import calibrate, run, trace

from .conftest import tiny_cell

CELL = "update-hotspot.gpulsm-10tree-2m"
SEED = 2**31 + 4242
FLUSH_METRICS = {"flush_pct", "flush_entries_per_put", "flush_pick_ms"}
# Shares of a peak need the chip's peaks table, which a CPU run has not.
NEEDS_PEAKS = {"merge_roofline"}


def test_program_passes_where_the_control_fails():
    r = calibrate.readings(tiny_cell(CELL), SEED, 1.5, require_tpu=False)
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["program_correct"] is True
    assert r["control_correct"] is False
    assert set(r["metrics"]) == {"ops_per_s", "write_p95_ms", "setup_s"}


@pytest.mark.parametrize("name", [CELL, "update.gpulsm-16m-log256m"])
def test_traced_run_reads_the_flush_metrics(name, monkeypatch):
    read = trace.read_xspace

    def as_on_the_chip(path):          # the chip's profile names a device
        tr = read(path)
        tr.devices = 1
        return tr
    monkeypatch.setattr(trace, "read_xspace", as_on_the_chip)
    cell = tiny_cell(name)
    res = run.run_cell(cell, SEED + 1, 1.5, True, require_tpu=False)
    assert res["correct"] is True
    assert FLUSH_METRICS <= {m["name"] for m in cell.per_layer}
    got = {k: v["value"] for k, v in res["metrics"].items()
           if k in FLUSH_METRICS}
    assert set(got) == FLUSH_METRICS
    assert all(v > 0 for v in got.values()), got
    assert got["flush_pct"] < 100
    # Every other metric listed for the cell reads too: the merge pass,
    # the tick and the transfers of ten trees as of one.
    listed = {m["name"] for m in cell.per_layer}
    assert listed - NEEDS_PEAKS <= set(res["metrics"])
