"""Whole serving runs on the CPU: ``correct`` is true for the sound
program and false for the lower-precision control and for an answer
altered where it is produced."""

from __future__ import annotations

import pytest
from _faults import config_of, serve_fault
from _tiny import run_cell


@pytest.mark.parametrize("fault", [None, "control_int16", "altered"])
def test_serving_fault_fails_correct(tiny_root, capsys, fault):
    cell = "serve-vgg8b"
    if fault is None:
        rc, line = run_cell(tiny_root, cell, capsys=capsys)
    else:
        with serve_fault(fault, config_of(tiny_root, cell)):
            rc, line = run_cell(tiny_root, cell, capsys=capsys)
    assert rc == 0
    assert line["correct"] is (fault is None)
    assert line["attempted"] == 100 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_p95_ms", "serve_images_per_s",
                                    "setup_s"}
