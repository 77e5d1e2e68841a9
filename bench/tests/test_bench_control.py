"""The control and each planted fault make a run read not correct.

Each test drives the rest of a run below the harness's look for a chip,
at a CPU size, with the timed path replaced underneath (bench/control.py).
The sound run beside them reads correct, so the limits separate them."""
import pytest

from bench import control
from bench.tests import tiny

CELLS = ["sift1m.build", "sift1m.serve", "sift1m.batch"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = tiny.run(tiny.spec(cell))
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    s = tiny.spec(cell)
    with control.control(s.traffic["mode"]):
        line = tiny.run(s)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    s = tiny.spec(cell)
    with control.FAULTS[fault](s.traffic["mode"]):
        line = tiny.run(s)
    assert not line["correct"], line["checks"]
