"""Autotuner gain benchmark: tuned-vs-default tiles + int8-vs-int32 MXU path.

Two questions, answered on both paper CNN topologies:

  1. **Tile search** — for every tunable fused problem of the compiled
     inference plan (``kernels.autotune.plan_shapes``), what did the
     tile search win over ``DEFAULT_TILES``?  Default and candidates are
     timed in ONE interleaved (ABBA min-of-N) ``time_paired`` session and
     the winner is the argmin, so ``tuned_us <= default_us`` by
     construction — the recorded gain is the search's own measurement,
     not a re-run that co-tenant noise could flip.  Every candidate is
     parity-gated bitwise against the reference oracle *inside*
     ``tune()`` before it may be timed.

  2. **int8 MXU path** — for every ``operand_dtype='auto'``-eligible plan
     step (int8-narrowed incoming activation × int8 frozen weight), the
     same int8-stored operands are timed through ``operand_dtype='int8'``
     (dots issued on int8 operands, int32 accumulation) against the
     ``'int32'`` escape hatch (operands lifted first).  Outputs are
     asserted bit-identical before timing.  The whole-plan comparison
     (``compile_plan(operand_dtype='auto')`` vs ``'int32'``) rides along.

Also proves the cache contract: after tuning, a second whole-plan
resolution is measurement-free (every key already in the cache) and every
per-problem ``resolve_tiles`` is a counter-verified cache hit.

Emits ``name,us_per_call,derived`` CSV rows on stdout *and*
``BENCH_autotune.json`` in the CWD.

    PYTHONPATH=src python -m benchmarks.autotune_gain [--quick] [--smoke]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_paired, tiny_smoke_cfg

JSON_PATH = "BENCH_autotune.json"

# (arch, scale, batch) — the two paper CNN topologies
CONFIGS = [
    ("vgg8b", 0.5, 8),
    ("vgg11b", 0.5, 4),
]

#: row count for the per-layer int8-vs-int32 linear timings — the plan
#: batch gives single-digit GEMM rows, far below timer resolution, so
#: linear layers are timed at a serving-sized row count (recorded per row)
LINEAR_INT8_ROWS = 1024


def _counter(reg, name: str) -> int:
    fam = reg.json_snapshot()[name]
    return sum(int(s["value"]) for s in fam["samples"])


def _tile_search(plan, batch: int, cache, iters: int, rows: list) -> None:
    from repro.kernels.autotune import plan_shapes, tune

    for p in plan_shapes(plan, batch):
        winner, times = tune(
            p["op"], p["shape"], dtype=p["dtype"], backend=plan.backend,
            prologue=p["prologue"], cache=cache, iters=iters)
        if winner is None:
            continue  # no tile knobs on this (op, backend)
        default_us = next(iter(times.values()))  # default probes first
        tuned_us = times[winner]
        emit(f"autotune/{plan.name}/{p['op']}/{'x'.join(map(str, p['shape']))}",
             tuned_us,
             f"default {default_us:.1f} us, {len(times)} configs, "
             f"{default_us / tuned_us:.2f}x")
        rows.append({
            "op": p["op"],
            "shape": list(p["shape"]),
            "configs_timed": len(times),
            "default_us": default_us,
            "tuned_us": tuned_us,
            "speedup_tuned_over_default": default_us / tuned_us,
            # argmin over a pool that includes the default, one session
            "tuned_no_worse_than_default": tuned_us <= default_us,
            "winner": {k: v for k, v in winner.to_json().items()},
            "bit_exact": True,  # parity-gated inside tune()
        })


def _cache_proof(plan, batch: int, cache, iters: int) -> dict:
    """Second resolution must be measurement-free: all keys hit the cache."""
    from repro.kernels.autotune import (configure, plan_shapes, resolve_tiles,
                                        set_metrics, tune_plan)
    from repro.obs.metrics import MetricRegistry

    retuned = tune_plan(plan, batch, cache=cache, iters=iters)
    reg = MetricRegistry()
    set_metrics(reg)
    configure(cache)
    try:
        for p in plan_shapes(plan, batch):
            resolve_tiles(p["op"], p["shape"], dtype=p["dtype"],
                          backend=plan.backend, prologue=p["prologue"])
        hits = _counter(reg, "kernel_tile_cache_hits_total")
        misses = _counter(reg, "kernel_tile_cache_misses_total")
    finally:
        configure(None)
        set_metrics(None)
    return {
        "entries": len(cache),
        "second_resolution_hits": hits,
        "second_resolution_misses_untunable": misses,
        # tune_plan skips measurement for cached keys; every tunable key
        # was cached by the first pass, so the re-tune returned the same
        # winners without timing a single candidate
        "second_resolution_measurement_free": all(
            k in cache for k in retuned),
    }


def _int8_layers(plan, batch: int, iters: int, rows: list) -> None:
    from repro.kernels.nitro_conv.ops import fused_conv
    from repro.kernels.nitro_matmul.ops import fused_matmul

    rng = np.random.default_rng(2)
    shape = tuple(int(d) for d in plan.input_shape)
    for i, (w, meta) in enumerate(zip(plan.weights, plan.metas)):
        if meta.kind == "conv":
            in_shape = (batch, *shape)
            h, w_sp, _ = shape
            f = int(w.shape[-1])
            shape = (h // 2, w_sp // 2, f) if meta.pool else (h, w_sp, f)
        else:
            feat = 1
            for d in shape:
                feat *= d
            in_shape = (LINEAR_INT8_ROWS, feat)
            shape = (int(w.shape[-1]),)
        if meta.operand_dtype != "int8":
            continue
        x8 = jnp.asarray(rng.integers(-127, 128, in_shape), jnp.int8)
        if meta.kind == "conv":
            run = functools.partial(
                fused_conv, sf=meta.sf, alpha_inv=meta.alpha_inv,
                apply_relu=meta.apply_relu, pool=meta.pool,
                out_dtype=jnp.dtype(meta.out_dtype), backend=plan.backend)
        else:
            run = functools.partial(
                fused_matmul, sf=meta.sf, alpha_inv=meta.alpha_inv,
                apply_relu=meta.apply_relu,
                out_dtype=jnp.dtype(meta.out_dtype), backend=plan.backend)
        fns = {
            od: jax.jit(functools.partial(run, operand_dtype=od))
            for od in ("int8", "int32")
        }
        out8, out32 = fns["int8"](x8, w), fns["int32"](x8, w)
        np.testing.assert_array_equal(np.asarray(out8), np.asarray(out32))
        us = time_paired(fns, x8, w, iters=iters)
        emit(f"autotune/{plan.name}/int8/step{i}-{meta.kind}", us["int8"],
             f"int32 {us['int32']:.1f} us, "
             f"{us['int32'] / us['int8']:.2f}x, alpha_inv={meta.alpha_inv}")
        rows.append({
            "step": i,
            "kind": meta.kind,
            "alpha_inv": meta.alpha_inv,
            "operand_shape": list(in_shape),
            "weight_shape": [int(d) for d in w.shape],
            "int8_us": us["int8"],
            "int32_us": us["int32"],
            "speedup_int8_over_int32": us["int32"] / us["int8"],
            "int8_wins": us["int8"] <= us["int32"],
            "bit_exact": True,  # asserted above before timing
        })


def _bench_config(cfg, batch: int, iters: int, results: list) -> None:
    from repro.core import les, model as M
    from repro.infer.export import freeze
    from repro.infer.plan import compile_plan
    from repro.kernels.autotune import TileCache

    state = les.create_train_state(jax.random.PRNGKey(0), cfg)
    fm = freeze(state, cfg)
    cache = TileCache(os.path.join(tempfile.mkdtemp(prefix="autotune_"),
                                   "tile_cache.json"))

    plans = {
        od: compile_plan(fm, operand_dtype=od) for od in ("auto", "int32")
    }
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(-127, 128, (batch, *cfg.input_shape)),
                    jnp.int32)
    oracle = M.frozen_forward(state.params, cfg, x)
    for plan in plans.values():  # parity gate before any timing
        np.testing.assert_array_equal(np.asarray(plan.logits(x)),
                                      np.asarray(oracle))

    tile_rows: list[dict] = []
    _tile_search(plans["auto"], batch, cache, iters, tile_rows)
    cache_stats = _cache_proof(plans["auto"], batch, cache, iters)

    int8_rows: list[dict] = []
    _int8_layers(plans["auto"], batch, iters, int8_rows)
    plan_us = time_paired({od: p.logits for od, p in plans.items()},
                          x, iters=iters)
    emit(f"autotune/{cfg.name}/plan/int8-auto", plan_us["auto"],
         f"int32 escape hatch {plan_us['int32']:.1f} us, "
         f"{plan_us['int32'] / plan_us['auto']:.2f}x")

    results.append({
        "arch": cfg.name,
        "batch": batch,
        "backend": plans["auto"].backend,
        "tiles": tile_rows,
        "tuned_no_worse_everywhere": all(
            r["tuned_no_worse_than_default"] for r in tile_rows),
        "cache": cache_stats,
        "int8_layers": int8_rows,
        "int8_eligible_steps": sum(
            1 for m in plans["auto"].metas if m.operand_dtype == "int8"),
        "int8_win_layers": sum(1 for r in int8_rows if r["int8_wins"]),
        "plan_us": plan_us,
        "plan_speedup_int8_over_int32": plan_us["int32"] / plan_us["auto"],
        "bit_exact": True,  # every comparison above parity-gated first
    })


def run(quick: bool = False, smoke: bool = False) -> None:
    from repro.configs import paper
    from repro.kernels.nitro_matmul.ops import resolve_backend

    iters = 2 if (quick or smoke) else 5
    results: list[dict] = []
    if smoke:
        _bench_config(tiny_smoke_cfg(), batch=8, iters=iters, results=results)
    else:
        for arch, scale, batch in CONFIGS:
            cfg = paper.get(arch, scale=scale)
            _bench_config(cfg, batch=batch, iters=iters, results=results)
    payload = {
        "benchmark": "autotune_gain",
        "backend": jax.default_backend(),
        "kernel_backend_auto": resolve_backend("auto"),
        "speedup_estimator": (
            "interleaved min-of-N, ABBA order, default + candidates in one "
            "paired session — the tuned result is the argmin of a pool "
            "containing the default, so tuned_us <= default_us structurally; "
            "int8-vs-int32 rows time the SAME int8-stored operands through "
            "both operand paths after asserting bitwise-equal outputs"
        ),
        "results": results,
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    emit("autotune/json", 0.0, JSON_PATH)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer timing iters")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config only (CI import-and-run gate)")
    args = ap.parse_args()
    run(quick=args.quick, smoke=args.smoke)
