"""A whole run of each cell, minus the look for a chip, at a test's size:
sound, it is correct; with the timed path broken underneath in any way the
cell can be broken, ``correct`` comes out false.  Limits are the committed
ones in bench/limits/."""

import pytest

import faults

ONE_CHIP = faults.cells(chips=1)
ONE_CHIP_FAULTS = ("stale", "half", "altered")


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_sound_run_is_correct(workload):
    out = faults.run(workload, "none")
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ONE_CHIP_FAULTS)
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_fault_is_caught(workload, fault):
    out = faults.run(workload, fault)
    assert out["correct"] is False and out["failed"] > 0
