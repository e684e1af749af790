"""Whole training runs on the CPU with the timed path broken underneath:
``correct`` is true for the sound program and false for the lower-
precision control and for each fault a training cell can have."""

from __future__ import annotations

import pytest
from _faults import config_of, train_fault
from _tiny import run_cell


@pytest.mark.parametrize("fault", [None, "control_int16", "unchanged",
                                   "half_batch"])
@pytest.mark.parametrize("cell", ["train-vgg8b", "train-mlp4"])
def test_training_fault_fails_correct(tiny_root, capsys, cell, fault):
    if fault is None:
        rc, line = run_cell(tiny_root, cell, capsys=capsys)
    else:
        with train_fault(fault, config_of(tiny_root, cell)):
            rc, line = run_cell(tiny_root, cell, capsys=capsys)
    assert rc == 0
    assert line["correct"] is (fault is None)
    assert list(line)[-1] == "checks"
    failed = {k for k, v in line["checks"].items()
              if v["value"] > v["limit"]}
    assert bool(failed) is (fault is not None)
