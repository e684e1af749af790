"""Per-shape tile autotuning for the fused NITRO kernels.

``tiles``    TileConfig + the candidate search space (VMEM budget, MXU
             alignment) — also the single home of the default tile sizes
             every kernel signature references.
``measure``  the ABBA min-of-N paired timing harness (shared with
             ``benchmarks/``).
``cache``    the on-disk JSON cache: ``(op, shape, dtype, backend,
             prologue, fuse_opt)`` keys + a build fingerprint; atomic
             writes, corruption-tolerant reads.
``state``    process-wide resolution: ``configure`` a cache, dispatchers
             call ``resolve_tiles`` per launch; cache hit/miss counters
             and the int8-path gauge hook a ``MetricRegistry``.
``search``   the measured tuner: candidates → bitwise parity gate → one
             paired-timing session → argmin → cache; plus whole-model
             drivers ``tune_plan`` / ``tune_training``.

Tile choice is *perf-only*: integer accumulation is order-exact, so any
accepted config produces bitwise-identical outputs (parity-gated at tune
time, property-tested in ``tests/test_autotune.py``).
"""

from repro.kernels.autotune.cache import (
    CACHE_FILENAME,
    TileCache,
    build_fingerprint,
    cache_key,
)
from repro.kernels.autotune.measure import time_fn, time_paired
from repro.kernels.autotune.search import (
    ParityError,
    plan_shapes,
    training_shapes,
    tune,
    tune_plan,
    tune_training,
)
from repro.kernels.autotune.state import (
    active_cache,
    configure,
    note_int8_path,
    resolve_tiles,
    set_metrics,
)
from repro.kernels.autotune.tiles import (
    DEFAULT_TILES,
    TileConfig,
    conv_candidates,
    matmul_candidates,
)

__all__ = [
    "CACHE_FILENAME",
    "DEFAULT_TILES",
    "ParityError",
    "TileCache",
    "TileConfig",
    "active_cache",
    "build_fingerprint",
    "cache_key",
    "configure",
    "conv_candidates",
    "matmul_candidates",
    "note_int8_path",
    "plan_shapes",
    "resolve_tiles",
    "set_metrics",
    "time_fn",
    "time_paired",
    "training_shapes",
    "tune",
    "tune_plan",
    "tune_training",
]
