"""Process-wide autotune state: the active cache and the metrics hooks.

The dispatchers (``nitro_matmul/ops.py``, ``nitro_conv/ops.py``) call
:func:`resolve_tiles` on every kernel launch they trace.  Resolution is
deliberately cheap and side-effect-free when tuning is off:

* no cache configured → ``None`` (kernels use their historical
  ``DEFAULT_TILES`` defaults, exactly as before this module existed);
* cache configured, key present → the tuned :class:`TileConfig`
  (+ ``kernel_tile_cache_hits_total``);
* cache configured, key absent → ``None`` fallback
  (+ ``kernel_tile_cache_misses_total``) — resolution never *tunes*;
  measurement happens only through :mod:`repro.kernels.autotune.search`.

Note on jit: dispatchers resolve tiles at **trace** time, so the
counters count trace-time resolutions, and a compiled plan bakes in
whatever the cache held when it was traced — tune before compiling.
"""

from __future__ import annotations

from .cache import TileCache, cache_key
from .tiles import TileConfig

_active_cache: TileCache | None = None
_metrics = None  # (hits_counter, misses_counter, int8_gauge) or None


def configure(cache: "TileCache | str | None") -> TileCache | None:
    """Install (or clear, with ``None``) the process-wide tile cache.

    Accepts a ready ``TileCache`` or a path (directory or file) to open
    one at.  Returns the installed cache for convenience.
    """
    global _active_cache
    if cache is None:
        _active_cache = None
    elif isinstance(cache, TileCache):
        _active_cache = cache
    else:
        _active_cache = TileCache(cache)
    return _active_cache


def active_cache() -> TileCache | None:
    return _active_cache


def set_metrics(registry) -> None:
    """Register the autotune metric families on a ``MetricRegistry``.

    Passing ``None`` detaches metrics (the default state).
    """
    global _metrics
    if registry is None:
        _metrics = None
        return
    _metrics = (
        registry.counter(
            "kernel_tile_cache_hits_total",
            "Tile resolutions served from the autotune cache"),
        registry.counter(
            "kernel_tile_cache_misses_total",
            "Tile resolutions that fell back to DEFAULT_TILES"),
        registry.gauge(
            "kernel_int8_path_active",
            "1 when a plan step issues int8-operand MXU dots, else 0",
            labels=("layer",)),
    )


def note_int8_path(layer: str, active: bool) -> None:
    """Record whether ``layer`` took the int8-operand path (gauge)."""
    if _metrics is not None:
        _metrics[2].labels(layer=str(layer)).set(int(active))


def resolve_tiles(op: str, shape, *, dtype: str, backend: str,
                  prologue: bool = False,
                  fuse_opt: bool = False) -> TileConfig | None:
    """The tuned tiles for one problem, or ``None`` for the defaults."""
    cache = _active_cache
    if cache is None:
        return None
    tiles = cache.get(cache_key(op, shape, dtype, backend,
                                prologue, fuse_opt))
    if _metrics is not None:
        _metrics[0 if tiles is not None else 1].inc()
    return tiles
