"""Training-step benchmark: fused vs unfused step, and the fused optimiser.

Times one jit-compiled ``les.train_step`` (the full fwd+bwd step) in
three variants at a CPU-feasible scale of the paper's VGG8B/VGG11B
configs:

  * ``fused_opt``   — everything fused *including the optimiser*
                      (``fuse_opt=True``): the IntegerSGD update runs as
                      the grad_W kernels' flush epilogue, so grad_W never
                      materialises in HBM — 3 HBM streams per forward-layer
                      weight update (x, δ in; W′ out) instead of the split
                      path's 5+ (grad_W out, then W + grad_W in, W′ out);
  * ``fused``       — fused forward + fused backward: the default path,
                      with the NITRO-ReLU-bwd/STE prologue inside the
                      gradient kernels, optimiser applied from the
                      materialised gradient;
  * ``unfused``     — the unfused matmul → Scaling → ReLU reference
                      composition in the forward (``fused=False``).

Timing is interleaved min-of-N with ABBA ordering (``common.time_paired``)
— this container's CPU swings ~2× with co-tenant load, and the minimum
bounds the intrinsic cost while interference only inflates samples.
Before timing, all variants are checked to produce bit-identical
parameters after one step — the benchmark never compares two computations
that disagree.

Emits the usual ``name,us_per_call,derived`` CSV rows on stdout *and*
machine-readable ``BENCH_train.json`` in the CWD (the artifact README's
training-speed claims reference).

    PYTHONPATH=src python -m benchmarks.train_step [--quick] [--smoke]

``--smoke`` runs a tiny 8×8 config in seconds — the CI gate
(tools/ci_check.sh) uses it to keep the benchmark import-and-run path
exercised on every commit.
"""

from __future__ import annotations

import argparse
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_paired, tiny_smoke_cfg

JSON_PATH = "BENCH_train.json"

# (arch, scale, batch) — paper CNN topologies at CI-feasible width
CONFIGS = [
    ("vgg8b", 0.0625, 16),
    ("vgg11b", 0.0625, 8),
]

# variant → (fused forward, fused optimiser)
VARIANTS = {
    "fused_opt": (True, True),
    "fused": (True, False),
    "unfused": (False, False),
}

#: HBM tensor streams per forward-layer weight update: the fused epilogue
#: reads x/δ and writes W′ (W is read inside the same kernel pass); the
#: split path additionally writes grad_W and re-reads W + grad_W in the
#: standalone update.  Structural counts, not measurements.
HBM_STREAMS = {"fused_opt": 3, "unfused_opt": 5}


def _bench_config(cfg, batch: int, iters: int, results: list) -> None:
    from repro.core import les, model as M

    state = les.create_train_state(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(-127, 128, (batch, *cfg.input_shape)),
                    jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.num_classes, batch), jnp.int32)
    key = jax.random.PRNGKey(2)

    steps = {
        mode: jax.jit(functools.partial(
            les.train_step, cfg=cfg, fused=fwd, fuse_opt=fopt))
        for mode, (fwd, fopt) in VARIANTS.items()
    }

    # parity gate: one step, bit-identical parameters across all variants
    out = {m: fn(state, x=x, labels=labels, key=key) for m, fn in steps.items()}
    ref = jax.tree_util.tree_leaves(out["fused"][0].params)
    for m, (st, _) in out.items():
        for pv, pr in zip(jax.tree_util.tree_leaves(st.params), ref):
            np.testing.assert_array_equal(np.asarray(pv), np.asarray(pr),
                                          err_msg=m)
    del out  # keep the timed heap free of the full parameter trees

    us = time_paired(steps, state, x=x, labels=labels, key=key, iters=iters)
    speedup = us["unfused"] / us["fused"] if us["fused"] else 0.0
    opt_speedup = us["fused"] / us["fused_opt"] if us["fused_opt"] else 0.0
    for m in VARIANTS:
        emit(f"train/{cfg.name}/{m}", us[m],
             f"batch {batch}; {us[m] / batch:.1f} us/sample")
    emit(f"train/{cfg.name}/speedup", 0.0,
         f"{speedup:.2f}x fused/unfused (interleaved min-of-N)")
    emit(f"train/{cfg.name}/opt_speedup", 0.0,
         f"{opt_speedup:.2f}x fused-opt/split-opt path")

    results.append({
        "arch": cfg.name,
        "batch": batch,
        "params": M.count_params(state.params),
        "us_per_step": {m: us[m] for m in us},
        "us_per_sample": {m: us[m] / batch for m in us},
        "speedup_fused_over_unfused": speedup,
        "speedup_fused_opt_over_fused": opt_speedup,
        "hbm_streams_per_weight_update": dict(HBM_STREAMS),
        # timing outcome — shape-checked only (like meets_target), never
        # value-pinned: machine contention can legitimately flip it
        "fused_opt_no_worse_than_unfused": us["fused_opt"] <= us["fused"],
        "bit_exact": True,  # asserted above before timing
    })


def run(quick: bool = False, smoke: bool = False) -> None:
    from repro.configs import paper
    from repro.kernels.nitro_matmul.ops import resolve_backend

    iters = 3 if (quick or smoke) else 10
    results: list[dict] = []
    if smoke:
        _bench_config(tiny_smoke_cfg(), batch=8, iters=iters, results=results)
    else:
        for arch, scale, batch in CONFIGS:
            cfg = paper.get(arch, scale=scale)
            _bench_config(cfg, batch=batch, iters=iters, results=results)
    payload = {
        "benchmark": "train_step",
        "backend": jax.default_backend(),
        "kernel_backend_auto": resolve_backend("auto"),
        "variants": {m: {"fused_fwd": f, "fuse_opt": o}
                     for m, (f, o) in VARIANTS.items()},
        "speedup_estimator": (
            "interleaved min-of-N, ABBA order — co-tenant CPU noise only "
            "inflates samples, so the per-variant minimum bounds the "
            "intrinsic step cost; on CPU all variants resolve to the "
            "reference backend and land near parity, while the structural "
            "win (no HBM round-trip of the post-ReLU-bwd δ) shows on the "
            "TPU kernel path"
        ),
        "results": results,
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    emit("train/json", 0.0, JSON_PATH)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer timing iters")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config only (CI import-and-run gate)")
    args = ap.parse_args()
    run(quick=args.quick, smoke=args.smoke)
