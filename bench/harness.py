"""The benchmark's driver: one run of one cell, as ``BENCHMARK.json`` says.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name, so a cell, a mix or a metric is added with new
files and entries alone:

* ``BENCHMARK.json`` names the cell's configuration and traffic mix;
* the configuration is the JSON file its entry names;
* the traffic mix is ``bench/traffic/<traffic>.json``, whose ``kind``
  names the generator ``bench/traffic/<kind>.py``;
* a per-layer metric is read by ``bench/metrics/<metric>.py``.

A generator module provides ``setup(run)``, ``window(run, session,
seconds)``, ``release(run, session)`` and ``check(run, session)``.  A metric
reader provides ``read(run)`` and returns a number or None (nothing to
read: the metric is left out of the line).

Order of a run: find an accelerator with the cell's chips (or exit
non-zero with no result), set the compilation cache, set up and warm up
(``setup_s``), read the device's peak memory so far, measure for
``--seconds`` (under the profiler with ``--trace 1``), read the peak
memory again (``memory_peak_bytes``), free the program's state,
check the outputs against the plain reference, print the result.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

BENCH_DIR = pathlib.Path(__file__).resolve().parent
DEFAULT_ROOT = BENCH_DIR.parent
#: the traced window is at most this long: the profiler's trace grows with
#: every event, and a few seconds of steady state hold hundreds of steps
TRACE_SECONDS = 4.0
ACCELERATORS = ("tpu",)


class NoAccelerator(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


@dataclass
class Check:
    """One number compared with the reference, and its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Run:
    """What one run knows, handed to the generator and the metric readers."""

    root: pathlib.Path
    bench: dict
    workload: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t0: float
    devices: list = field(default_factory=list)
    end_to_end: dict = field(default_factory=dict)
    window: dict = field(default_factory=dict)
    counters: dict = field(default_factory=dict)
    setup_peak_bytes: int = 0
    attempted: int = 0
    failed: int = 0
    reduced_trace: object = None

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


# ---------------------------------------------------------------------------
# Finding things by name
# ---------------------------------------------------------------------------


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    """Import a file of the benchmark by its path."""
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(root: pathlib.Path, name: str):
    """(BENCHMARK.json, workload entry, configuration, traffic mix)."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    workload = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[workload["config"]]["file"])
    mix = load_json(root / "bench" / "traffic" / f"{workload['traffic']}.json")
    return bench, workload, config, mix


def generator(root: pathlib.Path, kind: str):
    return load_module(root / "bench" / "traffic" / f"{kind}.py",
                       f"bench_traffic_{kind}")


def _listed_for(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def end_to_end_names(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if _listed_for(m, cell)]


def per_layer_names(bench: dict, cell: str) -> list[dict]:
    """Per-layer metrics this cell reports: those that list it, and those
    without a list whose end-to-end metric the cell reports."""
    mine = {m["name"] for m in end_to_end_names(bench, cell)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m["moves"] in mine:
            out.append(m)
    return out


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------


def accelerator(chips: int, require: bool = True) -> list:
    """The devices a cell runs on; raises ``NoAccelerator`` off a chip."""
    import jax

    devices = jax.devices()
    if require and devices[0].platform not in ACCELERATORS:
        raise NoAccelerator(
            f"needs a TPU; JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips; "
                            f"JAX found {len(devices)}")
    return devices[:chips]


def set_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    or where ``JAX_COMPILATION_CACHE_DIR`` already puts it."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def _parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _traced_window(run: Run, gen, session) -> None:
    import jax

    from bench import trace_reduce

    seconds = min(run.seconds, TRACE_SECONDS)
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # spans only: no per-call host events
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                gen.window(run, session, seconds)
        finally:
            jax.profiler.stop_trace()
        run.reduced_trace = trace_reduce.reduce_dir(tmp, chips=run.chips)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


class CompileCounter:
    """Counts XLA compilations while active (there should be none inside
    a window: every shape is warmed up in set-up)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _registered: "CompileCounter | None" = None

    def __init__(self):
        self.active, self.count = False, 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._registered is None:
            import jax

            cls._registered = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._registered._on_event)
        return cls._registered

    def _on_event(self, event, duration, **kwargs):
        if self.active and event == self.EVENT:
            self.count += 1


def measure(run: Run, gen) -> list[Check]:
    """Set up, measure, check; fills ``run`` and returns the checks."""
    session = gen.setup(run)
    run.end_to_end["setup_s"] = time.perf_counter() - run.t0
    run.setup_peak_bytes = peak_bytes(run.devices)
    counter = CompileCounter.get()
    counter.active, counter.count = True, 0
    try:
        if run.trace:
            _traced_window(run, gen, session)
        else:
            gen.window(run, session, run.seconds)
    finally:
        counter.active = False
    run.window["compiles"] = counter.count
    run.window["memory_peak_bytes"] = peak_bytes(run.devices)
    gen.release(run, session)
    return gen.check(run, session)


def result_line(run: Run, checks: list[Check]) -> dict:
    """The last line of standard output, as the benchmark's contract has it."""
    cell = run.workload["name"]
    metrics = {}
    if run.trace:
        for m in per_layer_names(run.bench, cell):
            reader = load_module(
                run.root / "bench" / "metrics" / f"{m['name']}.py",
                "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in end_to_end_names(run.bench, cell):
            if m["name"] not in run.end_to_end:
                raise KeyError(f"cell {cell} measured no {m['name']}")
            metrics[m["name"]] = {"value": run.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": run.window["memory_peak_bytes"]}
    line = {"correct": all(c.ok for c in checks) and run.failed == 0,
            "attempted": run.attempted, "failed": run.failed,
            "metrics": metrics, "device": device}
    if run.trace:
        t = run.reduced_trace
        device["busy_s"] = t.busy_s
        device["window_s"] = t.window_s
        line["breakdown"] = {"device_ops": t.top_ops(10),
                             "idle_gaps": t.top_gaps(10)}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    return line


def main(argv=None, *, t0: float | None = None, root=None,
         require_accelerator: bool = True) -> int:
    """Run one cell; returns the process's exit code.

    ``require_accelerator=False`` lets the tests drive a whole run on the
    CPU; the command line never turns it off.
    """
    t0 = time.perf_counter() if t0 is None else t0
    args = _parse(argv)
    root = pathlib.Path(root) if root is not None else DEFAULT_ROOT
    bench, workload, config, mix = find_cell(root, args.workload)
    run = Run(root=root, bench=bench, workload=workload, config=config,
              mix=mix, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), t0=t0)
    try:
        run.devices = accelerator(run.chips, require_accelerator)
    except NoAccelerator as e:
        print(f"bench: {e}; no result", file=sys.stderr, flush=True)
        return 3
    set_compile_cache(root)
    src = root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    gen = generator(root, mix["kind"])
    checks = measure(run, gen)
    line = result_line(run, checks)
    for c in checks:
        verdict = "ok" if c.ok else "FAIL"
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) {verdict}",
              file=sys.stderr)
    print(f"compilations inside the window: {run.window['compiles']}",
          file=sys.stderr)
    print(f"correct: {line['correct']} failed: {run.failed}",
          file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
