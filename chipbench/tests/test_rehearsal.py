"""A tiny interpret-mode rehearsal of each cell on the CPU: the whole
run, set-up to verdict, without the look for a chip."""
import json

import pytest

from chipbench import run

from .conftest import CELLS, tiny_cell

SEED = 2**31 + 12345


@pytest.mark.parametrize("name", CELLS)
def test_cell_reaches_its_result(name):
    cell = tiny_cell(name)
    res = run.run_cell(cell, SEED, 1.5, False, require_tpu=False)
    assert list(res)[0] == "correct" and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    json.dumps(res)


# Metrics read from the harness's spans and the program's counters; the
# CPU has no device trace and no peaks, so the device metrics stay out.
HOST_SIDE = {"service_self_ms", "compiles_in_window", "store_view_hit_pct",
             "maint_stall_pct", "write_pages_per_op"}


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_the_host_side_metrics(name):
    cell = tiny_cell(name)
    res = run.run_cell(cell, SEED + 1, 1.5, True, require_tpu=False)
    assert res["correct"] is True
    want = {m["name"] for m in cell.per_layer} & HOST_SIDE
    assert want and set(res["metrics"]) == want


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", "lookup.gpulsm-16m-log256m", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
