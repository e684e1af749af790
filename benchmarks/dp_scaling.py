"""Data-parallel scaling benchmark: per-step time vs device count.

Times one jit-compiled training step at device counts {1, 2, 4} — the
single-device ``les.train_step`` baseline against the sharded
``dp.dp_train_step`` under each reducer (``psum`` / ``ring`` /
``compress``).  Everything runs in one process, which holds every device
it uses: each device count builds its ``data`` mesh over the first n of
``jax.devices()``.  On a CPU host, ``__main__`` forces four host devices
before JAX starts; counts above what the process has are skipped.

Before timing, every variant is **parity-gated**: one step of each
reducer must produce bitwise-identical parameters to the single-device
step on the full batch (the suite's core claim — the benchmark never
times two computations that disagree).  Timing is interleaved min-of-N
with ABBA ordering (``common.time_paired``): co-tenant CPU noise only
inflates samples, so the per-variant minimum bounds the intrinsic cost.

On CPU host devices the "scaling" is honest about being a *semantics*
demo: shards share the same socket, so don't expect linear speedup —
the interesting outputs are the reducer overheads relative to psum and
the parity gate itself.  Real scaling needs real chips; the numbers
here track the *relative* cost of the three exact reduction schedules.

Emits ``name,us_per_call,derived`` CSV rows and ``BENCH_parallel.json``.

    PYTHONPATH=src python -m benchmarks.dp_scaling [--quick] [--smoke]

``--smoke`` (tiny config, devices {1, 2}) is the CI import-and-run gate.
"""

from __future__ import annotations

import argparse
import json
import os

JSON_PATH = "BENCH_parallel.json"

# (config name, batch) — batch must divide by every device count
CONFIGS = [("tiny", 8), ("vgg8b", 16)]
DEVICE_COUNTS = [1, 2, 4]


def _build(config: str, batch: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import tiny_smoke_cfg
    from repro.core import les

    if config == "tiny":
        cfg = tiny_smoke_cfg()
    else:
        from repro.configs import paper
        cfg = paper.get("vgg8b", scale=0.0625, input_shape=(16, 16, 3))
    state = les.create_train_state(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(-127, 128, (batch, *cfg.input_shape)),
                    jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.num_classes, batch), jnp.int32)
    return cfg, state, x, labels


def _measure(config: str, batch: int, devices: int, iters: int) -> dict:
    """Parity-gate then time every variant on a mesh of ``devices``
    devices; returns ``{variant: us_per_step}``."""
    import functools

    import jax
    import numpy as np

    from benchmarks.common import time_paired
    from repro.core import les
    from repro.parallel import dp

    cfg, state, x, labels = _build(config, batch)
    key = jax.random.PRNGKey(2)

    steps = {"single": jax.jit(functools.partial(les.train_step, cfg=cfg))}
    mesh = dp.data_mesh(devices)
    for reducer in dp.REDUCERS:
        steps[reducer] = dp.make_dp_train_step(cfg, mesh, dp_reduce=reducer)

    # parity gate: every reducer's post-step params ≡ the single-device step
    ref = jax.tree_util.tree_leaves(
        steps["single"](state, x=x, labels=labels, key=key)[0].params)
    for reducer in dp.REDUCERS:
        got = jax.tree_util.tree_leaves(
            steps[reducer](state, x=x, labels=labels, key=key)[0].params)
        for a, b in zip(got, ref):
            assert np.asarray(a).dtype == np.asarray(b).dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{reducer} @ {devices}dev")

    return time_paired(steps, state, x=x, labels=labels, key=key,
                       iters=iters)


def run(quick: bool = False, smoke: bool = False) -> None:
    import jax

    from benchmarks.common import emit

    iters = 3 if (quick or smoke) else 10
    configs = [("tiny", 8)] if smoke else CONFIGS
    device_counts = [
        n for n in ([1, 2] if smoke else DEVICE_COUNTS)
        if n <= jax.device_count()
    ]
    results: list[dict] = []
    for config, batch in configs:
        base_us = None
        for devices in device_counts:
            us = _measure(config, batch, devices, iters)
            if base_us is None:
                base_us = us["single"]
            for variant, t in sorted(us.items()):
                emit(f"parallel/{config}/{devices}dev/{variant}", t,
                     f"batch {batch}; {base_us / t:.2f}x vs 1dev single")
            results.append({
                "config": config, "devices": devices, "batch": batch,
                "us_per_step": us,
                "speedup_vs_single_1dev":
                    {m: base_us / t for m, t in us.items()},
                "bit_exact": True,  # parity-gated before timing
            })
    payload = {
        "benchmark": "dp_scaling",
        "device": jax.devices()[0].device_kind,
        "reducers": ["psum", "ring", "compress"],
        "timing": "interleaved min-of-N (ABBA) per device count; "
                  "every reducer parity-gated bitwise against the "
                  "single-device step before timing",
        "note": "CPU host devices share one socket — relative reducer "
                "cost is meaningful, absolute scaling needs real chips",
        "results": results,
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    emit("parallel/json", 0.0, JSON_PATH)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer timing iters")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config, 1-2 devices (CI gate)")
    args = ap.parse_args()
    # Host devices exist only if forced before JAX starts; the flag shapes
    # the CPU platform alone, so an accelerator host keeps its own devices.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={max(DEVICE_COUNTS)}"
    ).strip()
    run(quick=args.quick, smoke=args.smoke)
