"""A configuration, a traffic mix, a cell and a per-layer metric are added
with new files and ``BENCHMARK.json`` entries alone: no harness code is
edited, and a run of the new cell reports the new metric."""

from __future__ import annotations

import hashlib
import json

from _tiny import run_cell, shrink


def _digest(root):
    code = sorted((root / "bench").rglob("*.py"))
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in code}


def test_new_config_cell_and_metric_from_files_alone(tiny_root, capsys):
    before = _digest(tiny_root)
    bench_dir = tiny_root / "bench"
    cfg = json.loads((bench_dir / "configs" / "mlp4.json").read_text())
    cfg = shrink(dict(cfg, name="mlp2x", blocks=cfg["blocks"][:2]), 1.0)
    (bench_dir / "configs" / "mlp2x.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "train_b4.json").write_text(json.dumps(
        {"kind": "train_closed", "batch": 4, "distinct_batches": 5}))
    (bench_dir / "metrics" / "window_steps.py").write_text(
        "def read(run):\n    return run.window.get('steps')\n")
    bench_file = tiny_root / "BENCHMARK.json"
    bench = json.loads(bench_file.read_text())
    bench["configs"].append({"name": "mlp2x", "source": "test",
                             "file": "bench/configs/mlp2x.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "train-mlp2x", "config": "mlp2x",
                               "traffic": "train_b4", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"].startswith("train_"):
            m["workloads"].append("train-mlp2x")
    bench["per_layer"] = [{"name": "window_steps", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "test", "moves": "train_images_per_s",
                           "workloads": ["train-mlp2x"]}]
    bench_file.write_text(json.dumps(bench))

    rc, line = run_cell(tiny_root, "train-mlp2x", capsys=capsys)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"train_images_per_s",
                                    "train_peak_hbm_mb", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    rc, line = run_cell(tiny_root, "train-mlp2x", trace=1, capsys=capsys)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["window_steps"]["value"] == line["attempted"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert _digest(tiny_root) == {**before, **{
        k: v for k, v in _digest(tiny_root).items() if k not in before}}
    assert all(_digest(tiny_root)[k] == v for k, v in before.items())
