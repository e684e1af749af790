"""A tiny copy of the benchmark that runs a whole cell on the CPU in
seconds, for the benchmark's own tests."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
for _p in (REPO / "src", REPO):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def shrink(cfg: dict, scale: float = 1 / 16) -> dict:
    """The configuration at a fraction of its widths (a CPU-sized copy)."""
    cfg = json.loads(json.dumps(cfg))
    for b in cfg["blocks"]:
        b["width"] = max(8, int(round(b["width"] * scale)))
        b["d_lr"] = max(8, int(round(b["d_lr"] * scale)))
    return cfg


#: cells whose generators the tests drive even where ``BENCHMARK.json``
#: does not (yet) hold them: open-loop serving and data-parallel training
GENERATOR_CELLS = [
    {"name": "serve-vgg8b", "config": "vgg8b",
     "traffic": "serve_poisson_vgg8b", "chips": 1, "why": "test"},
    {"name": "train-vgg8b-dp4", "config": "vgg8b",
     "traffic": "train_dp4_b128", "chips": 4, "why": "test"},
]
SERVE_METRICS = [
    {"name": "serve_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
     "source": "host_clock", "workloads": ["serve-vgg8b"]},
    {"name": "serve_images_per_s", "unit": "images/s", "better": "higher",
     "bound": 0.01, "source": "host_clock", "workloads": ["serve-vgg8b"]},
]


def _with_generator_cells(bench: dict) -> dict:
    have = {w["name"] for w in bench["workloads"]}
    for cell in GENERATOR_CELLS:
        if cell["name"] not in have:
            bench["workloads"].append(dict(cell))
    names = {m["name"] for m in bench["end_to_end"]}
    for m in SERVE_METRICS:
        if m["name"] not in names:
            bench["end_to_end"].insert(0, dict(m))
    for m in bench["end_to_end"]:
        if m["name"].startswith("train_") and "workloads" in m:
            if "train-vgg8b-dp4" not in m["workloads"]:
                m["workloads"].append("train-vgg8b-dp4")
    return bench


def make_tiny_root(dest: pathlib.Path) -> pathlib.Path:
    """A copy of ``BENCHMARK.json`` and ``bench/`` with CPU-sized
    configurations and mixes, and every generator's cell; the program is
    the repository's own."""
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    (dest / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (dest / "BENCHMARK.json").write_text(
        json.dumps(_with_generator_cells(bench)))
    for cfg_file in (dest / "bench" / "configs").glob("*.json"):
        cfg = json.loads(cfg_file.read_text())
        cfg_file.write_text(json.dumps(shrink(cfg)))
    for mix_file in (dest / "bench" / "traffic").glob("*.json"):
        mix = json.loads(mix_file.read_text())
        if "batch" in mix:
            mix.update(batch=8, distinct_batches=4)
        else:
            mix.update(rate_per_s=200, batch_size=8, distinct_images=16,
                       warmup_requests=8)
        mix_file.write_text(json.dumps(mix))
    return dest


def run_cell(root, workload: str, *, seed: int = 3_000_000_017,
             seconds: float = 0.5, trace: int = 0, capsys=None):
    """Run one cell on the CPU through the harness; returns (exit code,
    result line or None)."""
    from bench import harness

    # the tests leave JAX's persistent compilation cache as they found it
    keep = harness.set_compile_cache
    harness.set_compile_cache = lambda root: None
    try:
        rc = harness.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          root=root, require_accelerator=False)
    finally:
        harness.set_compile_cache = keep
    line = None
    if capsys is not None:
        out = capsys.readouterr().out.strip().splitlines()
        line = json.loads(out[-1]) if out else None
    return rc, line
