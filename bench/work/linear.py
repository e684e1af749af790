"""A linear forward layer: fan_in·F multiply-adds per image, for the
forward product and again for the weight gradient."""

from __future__ import annotations

from bench.work import ACT_BYTES, DELTA_BYTES, GRAD_BYTES, WEIGHT_BYTES


def forward(shp, batch: int) -> tuple[int, int]:
    m, f = shp["fw"]
    macs = batch * m * f
    nbytes = (batch * m * ACT_BYTES + m * f * WEIGHT_BYTES
              + batch * f * ACT_BYTES)
    return macs, nbytes


def grad_w(shp, batch: int) -> tuple[int, int]:
    m, f = shp["fw"]
    macs = batch * m * f
    nbytes = (batch * m * ACT_BYTES + batch * f * DELTA_BYTES
              + m * f * GRAD_BYTES)
    return macs, nbytes
