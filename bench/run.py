"""Run one cell of the chip benchmark.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), then ``checks``: every number compared with
the plain reference beside its limit.  Off a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.  See
``bench/harness.py`` and ``PERF.md``.
"""

import time

T0 = time.perf_counter()  # set-up is timed from here, before JAX loads

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t0=T0))
