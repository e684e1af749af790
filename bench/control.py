"""Readings that the limits of ``correct`` are set from, at a cell's size.

    python bench/control.py --workload train-vgg8b --seeds 1,2,3 --seconds 2

For each seed it drives the cell's timed path as a run does (set-up, and
for serving a window of ``--seconds``), then prints one JSON line with
the numbers the run compares, read four ways:

* ``program`` — the program against the plain reference (what a run
  reads: the lower readings);
* ``control`` — the reference computed with every dot product wrapped
  to int16, put in the program's place: the nearest precision below the
  int32 accumulation the configurations state;
* the faults a cell can have, planted in the reference put in the
  program's place: ``unchanged`` (a step that returns its state as it
  was), ``half_batch`` (half of the batch left out of the gradient
  sums), ``no_exchange`` (a data-parallel step that applies one chip's
  gradients alone), ``altered`` (one served logit changed by 1).

The benchmark's own runs never run this; ``PERF.md`` records its
readings beside each limit.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import checks, harness  # noqa: E402


def _values(cs) -> dict:
    return {c.name: c.value for c in cs}


def train_readings(run, gen) -> dict:
    import jax.numpy as jnp

    s = gen.setup(run)
    run.setup_peak_bytes = harness.peak_bytes(run.devices)
    run.window["memory_peak_bytes"] = run.setup_peak_bytes
    gen.release(run, s)
    out = {"program": _values(gen.check(run, s))}
    p0, ref_p, ref_m = gen.reference_steps(run, s)
    want = gen.expected_extra(s.cfg)

    def against(p1, p3, metrics):
        return _values(checks.train_checks(p0, p1, p3, metrics, ref_p[0],
                                           ref_p[-1], ref_m, want, want))

    _, cp, cm = gen.reference_steps(run, s, acc_dtype=jnp.int16)
    out["control"] = against(cp[0], cp[-1], cm)
    out["unchanged"] = against(p0, p0, ref_m)
    batch = run.mix["batch"]
    _, hp, hm = gen.reference_steps(run, s, rows=slice(0, batch // 2))
    out["half_batch"] = against(hp[0], hp[-1], hm)
    if run.chips > 1:
        _, xp, xm = gen.reference_steps(
            run, s, rows=slice(0, batch // run.chips))
        out["no_exchange"] = against(xp[0], xp[-1], xm)
    return out


def serve_readings(run, gen) -> dict:
    import jax.numpy as jnp
    import numpy as np

    s = gen.setup(run)
    gen.window(run, s, run.seconds)
    gen.release(run, s)
    out = {"program": _values(gen.check(run, s)),
           "answered": run.counters["answered"]}
    ref = gen.reference_logits(run, s)
    low = gen.reference_logits(run, s, acc_dtype=jnp.int16)
    every = {i: low[s.image_of[i]] for i in range(len(s.rec.futures))}
    out["control"] = _values(checks.serve_checks(every, ref, s.image_of))
    altered = {i: ref[s.image_of[i]] for i in range(len(s.rec.futures))}
    row = np.array(altered[0])
    row[0] += 1
    altered[0] = row
    out["altered"] = _values(checks.serve_checks(altered, ref, s.image_of))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    bench, workload, config, mix = harness.find_cell(ROOT, args.workload)
    try:
        devices = harness.accelerator(int(workload["chips"]))
    except harness.NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    harness.set_compile_cache(ROOT)
    sys.path.insert(0, str(ROOT / "src"))
    gen = harness.generator(ROOT, mix["kind"])
    readings = serve_readings if mix["kind"] == "serve_open" else train_readings
    for seed in (int(x) for x in args.seeds.split(",")):
        run = harness.Run(root=ROOT, bench=bench, workload=workload,
                          config=config, mix=mix, seed=seed,
                          seconds=args.seconds, trace=False,
                          t0=time.perf_counter(), devices=devices)
        row = {"workload": args.workload, "seed": seed}
        row.update(readings(run, gen))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
