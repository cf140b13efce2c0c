"""The control -- the reference with each Put applied one submit late and
the last one lost -- comes out as not correct, where the program's own
answers on the same run come out correct. On the chip the same readings
come from ``calibrate.py`` at each cell's own size."""
import pytest

from chipbench import calibrate

from .conftest import CELLS, tiny_cell


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_where_the_program_passes(name):
    r = calibrate.readings(tiny_cell(name), 2**31 + 21, 1.5,
                           require_tpu=False)
    assert r["program_correct"] is True
    assert r["control_correct"] is False
    assert r["control"]["wrong_readbacks"] > 0
