"""Find the highest request rate a serving cell sustains (its knee).

    python bench/sweep_knee.py --workload serve-vgg8b --seed 5 \
        --seconds 6 --rates 1000,2000,4000

Sets the cell up once, then offers each rate in turn for ``--seconds``
through the cell's own open-loop generator, and prints one JSON line per
rate: the latency percentiles (scheduled send to answer), the answered
rate, the batch fill, how late the sender ran, and whether the backlog
grew (the median latency of the window's last fifth over its first
fifth).  A rate holds when its p95 is within the mix's ``p95_limit_ms``
and the backlog does not grow.  The benchmark's cells never search for
a rate: this is run once, by hand, and the rate is written into the mix.
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

from bench import harness  # noqa: E402

#: backlog grows when the last fifth of a window waits this much longer
GROWTH = 2.0


def summarize(rec, seconds: float, limit_ms: float, gen) -> dict:
    import numpy as np

    ok = np.array([f.done() and f.exception() is None for f in rec.futures])
    lat = gen.latencies(rec, ok)
    s = np.sort(lat)
    n = len(lat)
    fifth = max(n // 5, 1)
    first = float(np.median(lat[:fifth])) if n else 0.0
    last = float(np.median(lat[-fifth:])) if n else 0.0
    p95 = gen.percentile(s, 0.95) * 1e3
    grows = last > GROWTH * max(first, 1e-6)
    return {
        "offered_per_s": n / seconds,
        "answered_per_s": int(np.sum(ok & (rec.done <= seconds))) / seconds,
        "p50_ms": gen.percentile(s, 0.50) * 1e3,
        "p95_ms": p95,
        "p99_ms": gen.percentile(s, 0.99) * 1e3,
        "first_fifth_median_ms": first * 1e3,
        "last_fifth_median_ms": last * 1e3,
        "late_p99_ms": gen.percentile(np.sort(rec.late), 0.99) * 1e3,
        "failed": int(n - ok.sum()),
        "holds": bool(p95 <= limit_ms and not grows and ok.all()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)
    bench, workload, config, mix = harness.find_cell(ROOT, args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    run = harness.Run(root=ROOT, bench=bench, workload=workload,
                      config=config, mix=mix, seed=args.seed,
                      seconds=args.seconds, trace=False, t0=T0)
    try:
        run.devices = harness.accelerator(run.chips)
    except harness.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    harness.set_compile_cache(ROOT)
    sys.path.insert(0, str(ROOT / "src"))
    gen = harness.generator(ROOT, mix["kind"])
    s = gen.setup(run)
    print(json.dumps({"setup_s": time.perf_counter() - T0}), flush=True)
    rng = gen._rng(args.seed)
    for rate in rates:
        m = {k: v for k, v in mix.items() if k != "phases"}
        m["rate_per_s"] = rate
        s.times = gen.schedule(m, args.seconds, rng)
        s.image_of = rng.integers(0, len(s.images), len(s.times))
        gen.window(run, s, args.seconds)
        fill = gen.served_fill(s)
        row = summarize(s.rec, args.seconds, mix["p95_limit_ms"], gen)
        row.update(rate_per_s=rate, batch_fill=fill)
        print(json.dumps(row), flush=True)
    gen.release(run, s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
