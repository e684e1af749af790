"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only tableX]
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer steps (CI-speed smoke of every table)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from benchmarks import (
        ablations,
        autotune_gain,
        dp_scaling,
        kernel_bench,
        obs_overhead,
        roofline,
        serve_fleet,
        serve_infer,
        table1_mlp,
        table2_cnn,
        table8_lr,
        train_step,
        weight_range,
    )

    q = args.quick
    suites = [
        ("kernel", lambda: kernel_bench.run()),
        ("train", lambda: train_step.run(quick=q)),
        ("autotune", lambda: autotune_gain.run(quick=q)),
        ("infer", lambda: serve_infer.run(quick=q)),
        ("serve", lambda: serve_fleet.run(quick=q)),
        ("obs", lambda: obs_overhead.run(quick=q)),
        ("parallel", lambda: dp_scaling.run(quick=q)),
        ("table1", lambda: table1_mlp.run(steps=150 if q else 600)),
        ("table2", lambda: table2_cnn.run(steps=80 if q else 250)),
        ("table8", lambda: table8_lr.run(steps=60 if q else 150)),
        ("ablations", lambda: ablations.run(steps=50 if q else 120)),
        ("fig3", lambda: weight_range.run(steps=60 if q else 200)),
        ("roofline", lambda: roofline.run()),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        t0 = time.monotonic()
        try:
            fn()
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name}/ERROR,0.0,exception")
        print(f"{name}/elapsed,{(time.monotonic()-t0)*1e6:.0f},wall_time")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
