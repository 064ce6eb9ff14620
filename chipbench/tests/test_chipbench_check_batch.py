"""The output check fails a broken timed path, and its control.

Each test drives a whole run of a tiny cell on the CPU (the harness's
look for a chip skipped, the ``ref`` backend), with one fault planted in
the program, and sees ``correct`` come out false; the unbroken run comes
out true.  The control (the reference
at 4 bits in the program's place) must fail a compared number too.
"""
import pytest

from chipbench_faults import (altered_consensus, altered_token, half_batch,
                             no_exchange, swapped_lanes)
from chipbench_tiny import TinyCell, run


@pytest.fixture(scope="module")
def flowcell():
    return TinyCell("guppy", "flowcell")


def test_sound_run_is_correct_and_its_control_is_not(flowcell):
    out = run(flowcell, controls=[{"bits": 4}])
    assert out["correct"], out["checks"]
    assert out["_numbers"]["windows_compared"] > 0
    assert out["_numbers"]["reads_voted"] > 0
    assert out["_numbers"]["lanes_tied"] > 0
    ctl = out["_control"][0]
    assert any(ctl[k] > lim["limit"] for k, lim in flowcell.limits.items()
               if k in ctl), ctl


@pytest.mark.parametrize("fault", [altered_token, half_batch, no_exchange,
                                   altered_consensus, swapped_lanes])
def test_fault_in_batch_path_is_caught(flowcell, fault):
    out = run(flowcell, patch=fault)
    assert not out["correct"], out["checks"]
