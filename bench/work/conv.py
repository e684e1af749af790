"""A 'same' K×K conv forward layer: K²·C·F multiply-adds per output pixel,
for the forward product and again for the weight gradient."""

from __future__ import annotations

from bench.work import ACT_BYTES, DELTA_BYTES, GRAD_BYTES, WEIGHT_BYTES


def _dims(shp):
    h, w, c = shp["in"]
    k, _, _, f = shp["fw"]
    return h, w, c, k, f


def forward(shp, batch: int) -> tuple[int, int]:
    h, w, c, k, f = _dims(shp)
    macs = batch * h * w * k * k * c * f
    nbytes = (batch * h * w * c * ACT_BYTES + k * k * c * f * WEIGHT_BYTES
              + batch * h * w * f * ACT_BYTES)
    return macs, nbytes


def grad_w(shp, batch: int) -> tuple[int, int]:
    h, w, c, k, f = _dims(shp)
    macs = batch * h * w * k * k * c * f
    nbytes = (batch * h * w * c * ACT_BYTES + batch * h * w * f * DELTA_BYTES
              + k * k * c * f * GRAD_BYTES)
    return macs, nbytes
