"""On-disk JSON cache of tuned tile configurations.

One cache file holds every tuned entry for one build fingerprint::

    {
      "fingerprint": "repro=0.8.0|jax=0.4.xx|backend=cpu",
      "entries": {
        "matmul|256x1152x128|int8,int8|pallas|0|0": {"bm": 64, ...},
        ...
      }
    }

Design points:

* **Keyed** by ``(op, shape, dtype, backend, prologue, fuse_opt)`` —
  every axis that changes which kernel runs or how its grid is laid
  out.  ``prologue`` says a gradient kernel masks δ against a cached
  ``z_star`` (the NITRO-ReLU-bwd/STE prologue); with and without it a
  grad kernel is a different program.  Tile choice never changes
  *results* (integer accumulation is order-exact), only speed, so a
  stale entry is a perf bug at worst —
  but the **fingerprint** still invalidates the whole file when the
  repro version, jax version, or jax backend changes, because a timing
  measured under a different compiler is meaningless.
* **Corruption-safe**: an unreadable / wrong-shape / stale-fingerprint
  file loads as an empty cache (re-tune, don't crash).
* **Concurrent-writer-safe**: writes hold an exclusive ``flock`` on a
  sidecar ``<path>.lock`` (the cache file itself is replaced, so its fd
  cannot carry the lock) while they re-read the file, merge, write a
  temp file in the same directory, and ``os.replace`` it — atomic on
  POSIX.  Readers never observe a torn file; parallel writers — other
  threads *or* other processes — never lose each other's entries.
"""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import threading

try:
    import fcntl
except ImportError:  # non-POSIX: degrade to atomic-replace-only writes
    fcntl = None

from .tiles import TileConfig

CACHE_FILENAME = "tile_cache.json"


def build_fingerprint() -> str:
    """Identity of the code + compiler the cached timings were taken on."""
    import jax

    from repro.obs.metrics import REPRO_VERSION

    return (f"repro={REPRO_VERSION}|jax={jax.__version__}"
            f"|backend={jax.default_backend()}")


def cache_key(op: str, shape, dtype: str, backend: str,
              prologue: bool = False, fuse_opt: bool = False) -> str:
    """The canonical string key for one tuning problem."""
    dims = "x".join(str(int(d)) for d in shape)
    return (f"{op}|{dims}|{dtype}|{backend}"
            f"|{int(prologue)}|{int(fuse_opt)}")


class TileCache:
    """A (path-backed) mapping from cache keys to ``TileConfig``."""

    def __init__(self, path: str, *, fingerprint: str | None = None):
        path = os.fspath(path)
        if os.path.isdir(path) or path.endswith(os.sep):
            path = os.path.join(path, CACHE_FILENAME)
        self.path = path
        self.fingerprint = fingerprint or build_fingerprint()
        self._lock = threading.Lock()
        self._entries: dict[str, TileConfig] = self._load()

    # ---- persistence ------------------------------------------------------

    def _load(self) -> dict[str, TileConfig]:
        """Parse the file; anything unusable degrades to an empty cache."""
        try:
            with open(self.path) as f:
                payload = json.load(f)
            if payload.get("fingerprint") != self.fingerprint:
                return {}  # stale build — timings no longer trustworthy
            entries = payload["entries"]
            return {str(k): TileConfig.from_json(v)
                    for k, v in entries.items()}
        except (OSError, ValueError, KeyError, AttributeError, TypeError):
            return {}

    @contextlib.contextmanager
    def _file_lock(self):
        """Exclusive inter-process lock for read-merge-write cycles."""
        if fcntl is None:
            yield
            return
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        fd = os.open(self.path + ".lock", os.O_CREAT | os.O_RDWR, 0o644)
        try:
            fcntl.flock(fd, fcntl.LOCK_EX)
            yield
        finally:
            os.close(fd)  # closing releases the flock

    def _write(self) -> None:
        """Merge-with-disk then atomic-rename; caller holds ``self._lock``
        and ``_file_lock`` (so the disk state cannot move between the
        re-read and the replace)."""
        on_disk = self._load()
        on_disk.update(self._entries)
        self._entries = on_disk
        payload = {
            "fingerprint": self.fingerprint,
            "entries": {k: v.to_json()
                        for k, v in sorted(self._entries.items())},
        }
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(prefix=".tile_cache.", dir=d)
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
                f.write("\n")
            os.replace(tmp, self.path)  # atomic publish
        except BaseException:
            try:
                os.unlink(tmp)
            finally:
                raise

    # ---- mapping API ------------------------------------------------------

    def get(self, key: str) -> TileConfig | None:
        with self._lock:
            return self._entries.get(key)

    def put(self, key: str, tiles: TileConfig) -> None:
        with self._lock, self._file_lock():
            self._entries[key] = tiles
            self._write()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key: str) -> bool:
        return self.get(key) is not None

    def keys(self) -> list[str]:
        with self._lock:
            return sorted(self._entries)
