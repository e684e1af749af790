"""A profiler trace reduced to what the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``reduce_dir`` reads it with ``jax.profiler.ProfileData`` and keeps:

* the traced window: the host span ``bench.window`` that the harness
  opens around the measured window;
* per device: the operations that ran on it (the device planes' op
  line), clipped to the window, each known by its HLO name
  (``stream_conv_fwd.7``, ``fusion.12``, ``all-reduce.3``);
* the host spans that the benchmark and the program's tracer open
  (names starting with ``bench.`` or ``fleet.``).

From these, ``Reduced`` gives the busy time (the union of a device's op
intervals), the time of ops grouped by name or by a name pattern, the
time of one kind of op during which no other op ran, and the idle gaps
attributed to the host span that covered most of each gap.  Times are
averaged over the devices traced, so they compare with one chip's
window.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
HOST_PREFIXES = ("bench.", "fleet.")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
#: the op line of a device plane, by the names XLA's profiler gives it
OP_LINES = ("XLA Ops",)
NO_SPAN = "no host span"
#: a device op's event is named by its HLO text, ``%name.N = type op(...)``;
#: the op is known by ``name.N``
OP_NAME = re.compile(r"^%?([^\s=]+)")


def op_name(event_name: str) -> str:
    m = OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def _merge(intervals):
    """Sorted, non-overlapping union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a_s, a_e, b_s, b_e) -> float:
    return max(0.0, min(a_e, b_e) - max(a_s, b_s))


def _covered(mine, other) -> float:
    """Length of the intersection of two sorted, merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(mine) and j < len(other):
        total += _overlap(*mine[i], *other[j])
        if mine[i][1] < other[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class Reduced:
    """One traced window; every time is in seconds."""

    window: tuple                      # (start_ns, end_ns)
    ops: dict                          # device id -> [(name, s_ns, e_ns)]
    spans: list = field(default_factory=list)   # [(name, s_ns, e_ns)]

    @property
    def chips(self) -> int:
        return max(len(self.ops), 1)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_intervals(self, dev) -> list:
        return _merge([(s, e) for _, s, e in self.ops[dev]])

    @property
    def busy_s(self) -> float:
        total = sum(e - s for dev in self.ops
                    for s, e in self.busy_intervals(dev))
        return total / 1e9 / self.chips

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern=None) -> float:
        """Summed time of the ops whose name matches ``pattern`` (all ops
        without one), averaged over the devices."""
        rx = re.compile(pattern) if pattern else None
        total = sum(e - s for dev in self.ops for n, s, e in self.ops[dev]
                    if rx is None or rx.search(n))
        return total / 1e9 / self.chips

    def op_count(self, pattern) -> int:
        rx = re.compile(pattern)
        return sum(1 for dev in self.ops for n, _, _ in self.ops[dev]
                   if rx.search(n)) // self.chips

    def exposed_seconds(self, pattern) -> float:
        """Time of the ops matching ``pattern`` during which no other op
        ran on the same device, averaged over the devices."""
        rx = re.compile(pattern)
        total = 0.0
        for dev, ops in self.ops.items():
            mine = _merge([(s, e) for n, s, e in ops if rx.search(n)])
            other = _merge([(s, e) for n, s, e in ops if not rx.search(n)])
            total += sum(e - s for s, e in mine) - _covered(mine, other)
        return total / 1e9 / self.chips

    def top_ops(self, k: int) -> list:
        by = defaultdict(int)
        for dev in self.ops:
            for n, s, e in self.ops[dev]:
                by[n] += e - s
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / 1e9 / self.chips] for n, v in top]

    def gaps(self, dev) -> list:
        w_s, w_e = self.window
        out, t = [], w_s
        for s, e in self.busy_intervals(dev):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if w_e > t:
            out.append((t, w_e))
        return out

    def gap_owners(self, gaps) -> list:
        """For each gap (sorted, disjoint), the host span other than the
        window that covers most of it (``NO_SPAN`` where none does)."""
        starts = [g[0] for g in gaps]
        best = [(0.0, NO_SPAN)] * len(gaps)
        for n, a, b in self.spans:
            if n == WINDOW_SPAN:
                continue
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(gaps) and gaps[i][0] < b:
                ov = _overlap(*gaps[i], a, b)
                if ov > best[i][0]:
                    best[i] = (ov, n)
                i += 1
        return [n for _, n in best]

    def top_gaps(self, k: int) -> list:
        """Idle seconds per host span, largest first."""
        by = defaultdict(int)
        for dev in self.ops:
            gaps = self.gaps(dev)
            for (s, e), owner in zip(gaps, self.gap_owners(gaps)):
                by[owner] += e - s
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / 1e9 / self.chips] for n, v in top]


def reduce_events(ops: dict, spans: list, window=None) -> Reduced:
    """Clip device ops to the window (the ``bench.window`` span unless
    given) and keep the host spans that overlap it."""
    if window is None:
        wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        window = max(wins, key=lambda w: w[1] - w[0])
    w_s, w_e = window
    clipped = {}
    for dev, evs in ops.items():
        clipped[dev] = [(n, max(s, w_s), min(e, w_e)) for n, s, e in evs
                        if e > w_s and s < w_e]
    kept = [(n, s, e) for n, s, e in spans if e > w_s and s < w_e]
    return Reduced(window=(w_s, w_e), ops=clipped, spans=kept)


def read_xplane(path: str):
    """(device ops, host spans) from one ``.xplane.pb`` file (or one
    compressed with gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs.extend((op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
            ops[int(m.group(2))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(HOST_PREFIXES))
    return ops, spans


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str, chips: int | None = None) -> Reduced:
    ops, spans = read_xplane(find_xplane(trace_dir))
    if chips is not None:
        ops = {d: ops[d] for d in sorted(ops)[:chips]}
    return reduce_events(ops, spans)
