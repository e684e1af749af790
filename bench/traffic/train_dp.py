"""Data-parallel training: the program's DP step on a ``data`` mesh.

The same closed loop, inputs and checks as ``train_closed``; the step is
``dp.make_dp_train_step`` over all the cell's chips with the mix's
``reducer`` (the program's default, ``psum``), the global batch split
over the chips.  The reference runs the whole batch on one chip, so the
comparison also holds the gradient exchange to the single-device result.

Mix parameters: those of ``train_closed``, and ``reducer``.
"""

from __future__ import annotations

from bench.traffic import train_closed
from bench.traffic.train_closed import (  # noqa: F401
    check, expected_extra, reference_steps, release, window)


def build_step(run, cfg):
    from repro.parallel import dp

    from bench import model

    mesh = dp.data_mesh(run.chips)
    return dp.make_dp_train_step(model.nitro_config(cfg), mesh,
                                 dp_reduce=run.mix["reducer"])


def setup(run):
    return train_closed.setup(run, build=build_step)
