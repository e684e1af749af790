"""The names of the LES training step's layers, as the compiled program
carries them.

Each part of the step opens a ``jax.named_scope`` from this vocabulary.
The scope is metadata only: it changes no value, fusion or layout, but
every HLO instruction the compiler makes from the ops inside it carries
the scope in its ``op_name`` (``jit(step)/block2/backward/...``).  A
profiler trace names device ops by their HLO instruction name
(``fusion.18``), so the compiled step's text maps each traced op to the
layer it belongs to.  ``docs/OBSERVABILITY.md`` ("Layer scopes") says
where each scope is opened and how to read a profile through it.

==========================  ====================================================
scope                       covers
==========================  ====================================================
``block{i}/forward``        block i's forward layers: its kernels and their glue
``block{i}/local_loss``     block i's learning layers, local gradient and loss
``block{i}/backward``       block i's forward-layer gradients (and, under
                            ``fuse_opt``, the update flushed by the grad kernel)
``output``                  the output layers' forward and backward, the metrics
``update``                  IntegerSGD over every parameter group
``dp/reduce_gradients``     the data-parallel gradient exchange
==========================  ====================================================
"""

from __future__ import annotations

import jax

FORWARD = "forward"
LOCAL_LOSS = "local_loss"
BACKWARD = "backward"
OUTPUT = "output"
UPDATE = "update"
REDUCE_GRADIENTS = "dp/reduce_gradients"


def block_scope(index: int, part: str) -> str:
    """``block{index}/{part}``, ``part`` one of the three block parts."""
    if part not in (FORWARD, LOCAL_LOSS, BACKWARD):
        raise ValueError(f"unknown block part {part!r}")
    return f"block{index}/{part}"


def block(index: int, part: str):
    """``jax.named_scope`` of one part of block ``index``."""
    return jax.named_scope(block_scope(index, part))


def output():
    return jax.named_scope(OUTPUT)


def update():
    return jax.named_scope(UPDATE)


def reduce_gradients():
    return jax.named_scope(REDUCE_GRADIENTS)
