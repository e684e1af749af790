"""Operations and minimum HBM bytes that the NITRO-D algorithm needs.

Counted from the model's logical shapes and dtypes, never from what a
kernel issues: no limb passes, no padding of the 3-channel input to 128
lanes, no conv grad_x (blocks are local, so nothing flows below a
block).  A forward layer does its forward product and its weight
gradient; the learning layers do their forward product, their weight
gradient and the δ they send into the block; the output layer does its
forward product and weight gradient.  One multiply-add is two
operations.

Logical widths of the minimum bytes: activations are int8 (1 byte), the
weights int16 (2 bytes), the gradient δ reaching a forward layer int32
(4 bytes), a weight gradient int32 (4 bytes).  Each operand is read once
and each result written once.

Each block kind has a module here, ``bench/work/<kind>.py``, with
``forward(shp, batch)`` and ``grad_w(shp, batch)``: each returns
``(multiply_adds, bytes)`` for one call on a batch, from the shapes that
``bench.reference.layer_shapes`` gives.
"""

from __future__ import annotations

import importlib

from bench import reference

ACT_BYTES, WEIGHT_BYTES, DELTA_BYTES, GRAD_BYTES = 1, 2, 4, 4


def kind_module(kind: str):
    return importlib.import_module(f"bench.work.{kind}")


def forward_layer_calls(cfg: dict, batch: int, kind: str | None = None,
                        train: bool = True) -> list[tuple[int, int]]:
    """``(multiply_adds, bytes)`` of every forward-layer kernel call of one
    step (of the blocks of ``kind`` only, when given)."""
    calls = []
    for blk, shp in zip(cfg["blocks"], reference.layer_shapes(cfg)):
        if kind is not None and blk["kind"] != kind:
            continue
        mod = kind_module(blk["kind"])
        calls.append(mod.forward(shp, batch))
        if train:
            calls.append(mod.grad_w(shp, batch))
    return calls


def head_macs(cfg: dict, batch: int, train: bool = True) -> int:
    """Learning layers (forward, weight gradient, δ) and output layer
    (forward, weight gradient)."""
    shapes = reference.layer_shapes(cfg)
    lr = sum(s["lr"][0] * s["lr"][1] for s in shapes[:-1])
    out = shapes[-1]["fw"][0] * shapes[-1]["fw"][1]
    if not train:
        return batch * out
    return batch * (3 * lr + 2 * out)


def step_ops(cfg: dict, batch: int, train: bool = True) -> int:
    """Operations of one training step (or inference batch)."""
    macs = sum(m for m, _ in forward_layer_calls(cfg, batch, train=train))
    return 2 * (macs + head_macs(cfg, batch, train))


def forward_macs_per_image(cfg: dict) -> int:
    """Multiply-adds of one image's forward pass through the forward and
    output layers (what the paper's tables call the model's size of work)."""
    fw = sum(m for m, _ in forward_layer_calls(cfg, 1, train=False))
    out = reference.layer_shapes(cfg)[-1]["fw"]
    return fw + out[0] * out[1]


def roofline_seconds(calls, peak_ops: float, peak_bytes: float):
    """Least time of the calls on the chip, each bound by the larger of its
    operations and its bytes; returns (seconds, share bound by compute)."""
    total, compute = 0.0, 0.0
    for macs, nbytes in calls:
        t_ops, t_bytes = 2 * macs / peak_ops, nbytes / peak_bytes
        total += max(t_ops, t_bytes)
        if t_ops >= t_bytes:
            compute += t_ops
    return total, (compute / total if total else 0.0)


def kernel_roofline_share(run, kind: str, pattern: str):
    """A kernel package's share of its roofline in the traced window, in %:
    the least time of the ``kind`` forward layers' calls (per chip: a
    data-parallel chip does its share of the batch) over the summed device
    time of the ops whose HLO name matches ``pattern``; None where the
    trace holds none."""
    from bench import peaks

    t, steps = run.reduced_trace, run.window.get("steps")
    if t is None or not steps:
        return None
    seconds = t.op_seconds(pattern)
    if seconds <= 0:
        return None
    pk = peaks.peaks(run.devices[0].device_kind)
    calls = forward_layer_calls(run.config, run.mix["batch"] // run.chips,
                                kind=kind)
    least, _ = roofline_seconds(calls, pk["int8_ops_per_s"],
                                pk["hbm_bytes_per_s"])
    return 100.0 * least * steps / seconds
