"""The control that the limits are set against, at a size a test run
holds: the reference computed in bfloat16 (factors, activations and
updates), put in the program's place, must fail one of the cell's
numbers, and so must each planted fault; the program itself passes.

On the chip the same readings, at the cell's own size, are what the
limits in ``limits/`` were set from (``run.py --calibrate``)."""

import json

import pytest

import harness

CELLS = ["heroes-edge-sequential.pythia-410m",
         "heroes-edge-sequential.pythia-1.4b"]


def _calibrate(cell, seeds, capsys):
    cell.driver().calibrate(cell, seeds, controls=len(seeds))
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


@pytest.mark.parametrize("name", CELLS)
def test_control_and_faults_fail_the_limits(name, capsys):
    cell = harness.Cell(name, rehearsal=True)
    for out in _calibrate(cell, [5, 2147483659], capsys):
        def fails(r):
            return any(r[k] > cell.limits[k] for k in r if k in cell.limits)

        assert not fails(out["program"]), out
        assert fails(out["control_bf16"]), out
        assert fails(out["fault_half_batch"]), out
