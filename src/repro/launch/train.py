"""Training launcher.

Two trainers behind one CLI:

  * ``--arch <paper arch>``  (mlp1..4, vgg8b, vgg11b) — the NITRO-D
    integer-only LES trainer (the paper's algorithm, core library);
    ``--num-devices N`` shards the batch over a data mesh
    (``repro.parallel.dp``) with a bitwise-identical trajectory,
    ``--dp-reduce`` picks the exact all-reduce (psum/ring/compress);
  * ``--arch <lm arch>``     (qwen3-32b, …) — the sharded LM trainer
    (bf16/fp32 AdamW or LES-groups mode), sized by ``--scale`` for
    CPU-budget runs.

Production behaviours wired in: checkpoint/restart (async, manifest),
preemption checkpointing, straggler logging, deterministic data pipeline.
"""

from __future__ import annotations

import argparse
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def train_nitro(arch: str, *, steps: int, batch: int, ckpt_dir: str | None,
                dataset: str, scale: float, seed: int = 0,
                telemetry_every: int = 0, telemetry_out: str | None = None,
                trace_out: str | None = None,
                num_devices: int = 1, dp_reduce: str = "psum",
                fuse_opt: bool = False,
                metrics_port: int | None = None,
                alerts_out: str | None = None,
                autotune: bool = False,
                autotune_cache: str | None = None) -> dict:
    """Integer-only NITRO-D training (paper algorithm).

    ``telemetry_every=N`` runs every N-th step through the
    telemetry-enabled variant of ``les.train_step`` (bitwise-identical
    trajectory — sampling cadence changes cost, never results) and
    appends the per-layer bit-occupancy/saturation records to
    ``telemetry_out`` (default: ``metrics.jsonl`` next to the
    checkpoints).  Each sampled step also feeds the **health monitor**
    (``obs.health.default_rules``): saturation trends, int32 headroom,
    dead-unit growth, optimiser-scalar stall — alerts print inline and
    (with ``alerts_out``) append as JSONL.  ``trace_out`` writes a span
    trace of the run's phases (step / checkpoint / eval) as JSONL.

    ``metrics_port`` (0 = ephemeral) serves the run's metric registry
    over HTTP — ``train_step_seconds`` / ``train_straggler_events_total``
    plus the health gauges and ``repro_build_info`` — at ``/metrics``,
    ``/metrics.json`` and ``/healthz`` (what ``obs_top`` scrapes live).

    ``autotune=True`` searches kernel tile configurations for every fused
    fwd/bwd problem of this (arch, batch) *before* the train step is
    traced — winners persist in ``autotune_cache`` (default:
    ``tile_cache.json`` next to the checkpoints), so a re-run with a warm
    cache resolves them measurement-free.  Tiling is bitwise
    result-invariant; this is purely a throughput knob.

    ``num_devices > 1`` shards the batch over a ``data`` mesh axis via
    ``repro.parallel.dp`` (``dp_reduce`` picks the all-reduce:
    psum / ring / compress) — the trajectory is *bitwise identical* to
    the single-device run, so this is purely a throughput knob.  The
    process must already have that many JAX devices (``main()`` re-execs
    with ``XLA_FLAGS`` to force host devices on CPU).
    """
    from repro.configs import get_paper_config
    from repro.core import les
    from repro.data import synthetic
    from repro.obs import health as H
    from repro.obs.metrics import (MetricRegistry, register_build_info,
                                   start_metrics_server)
    from repro.obs.trace import NULL_TRACER, Tracer
    from repro.train import checkpoint as ckpt
    from repro.train.fault_tolerance import PreemptionGuard, StepTimer, StragglerDetector

    ds = synthetic.make_image_dataset(dataset, n_train=4096, n_test=512, seed=seed)
    cfg = get_paper_config(arch, scale=scale,
                           input_shape=ds.input_shape if arch.startswith("vgg") else None)
    if arch.startswith("mlp"):
        ds = synthetic.flatten_for_mlp(ds)
        d = ds.input_shape[0]
        if cfg.input_shape != (d,):
            from dataclasses import replace
            cfg = replace(cfg, input_shape=(d,))

    state = les.create_train_state(jax.random.PRNGKey(seed), cfg)
    start_step = 0
    checkpointer = ckpt.AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
    if ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
        state, start_step = ckpt.restore(ckpt_dir, state)
        print(f"[restore] resumed from step {start_step}")

    if autotune:
        from repro.kernels import autotune as at
        cache = at.TileCache(
            autotune_cache
            or os.path.join(ckpt_dir or ".", at.CACHE_FILENAME))
        tuned = at.tune_training(cfg, batch, cache=cache)
        at.configure(cache)  # dispatchers resolve tiles when jit traces
        print(f"[autotune] {len(tuned)} problems tuned/cached -> "
              f"{cache.path}")

    if num_devices > 1:
        from repro.parallel import dp
        if batch % num_devices:
            raise SystemExit(
                f"--batch {batch} must divide evenly over "
                f"--num-devices {num_devices}")
        mesh = dp.data_mesh(num_devices)
        print(f"[dp] {num_devices}-device data mesh, reduce={dp_reduce} "
              f"(bitwise ≡ single-device)")
        step_fn = dp.make_dp_train_step(cfg, mesh, dp_reduce=dp_reduce,
                                        fuse_opt=fuse_opt)
    else:
        step_fn = jax.jit(functools.partial(les.train_step, cfg=cfg,
                                            fuse_opt=fuse_opt))
    telem_step_fn = None
    if telemetry_every > 0:
        from repro.obs import telemetry as T
        # a second jit cache entry, not a recompile of the first: the
        # trajectory it returns is bitwise-identical (test-enforced)
        if num_devices > 1:
            # telemetry needs the materialised fw gradients, so these
            # steps keep the split path regardless of --fuse-opt —
            # bitwise-identical trajectory either way (test-enforced)
            telem_step_fn = dp.make_dp_train_step(
                cfg, mesh, dp_reduce=dp_reduce, fuse_opt=fuse_opt,
                telemetry=True)
        else:
            telem_step_fn = jax.jit(
                functools.partial(les.train_step, cfg=cfg, telemetry=True))
        if telemetry_out is None:
            telemetry_out = os.path.join(ckpt_dir or ".", "metrics.jsonl")
        print(f"[telemetry] every {telemetry_every} steps -> {telemetry_out}")
    tracer = Tracer() if trace_out else NULL_TRACER
    guard = PreemptionGuard(install=False)
    straggler = StragglerDetector()
    timer = StepTimer()

    # host-side run metrics + health rules: never touch the jit graph,
    # so the bitwise-identity and float-free guarantees are unaffected
    registry = MetricRegistry()
    register_build_info(registry, backend=jax.default_backend())
    if autotune:
        # count trace-time tile resolutions (hits vs default fallbacks)
        from repro.kernels.autotune import set_metrics
        set_metrics(registry)
    step_seconds = registry.histogram(
        "train_step_seconds", "wall time per training step")
    straggler_events = registry.counter(
        "train_straggler_events_total",
        "steps slower than the straggler EWMA threshold")
    sinks = [H.print_sink]
    if alerts_out:
        sinks.append(H.jsonl_sink(alerts_out))
        print(f"[health] alerts -> {alerts_out}")
    monitor = H.HealthMonitor(registry=registry, sinks=sinks)
    server = None
    if metrics_port is not None:
        server = start_metrics_server(registry, port=metrics_port)
        print(f"[metrics] serving {server.url} (+ /metrics.json /healthz)")

    it = 0
    metrics = None
    while it < steps:
        for x, y in synthetic.batches(ds.x_train, ds.y_train, batch, seed=it):
            if it >= steps or guard.requested:
                break
            sampled = telem_step_fn is not None and it % telemetry_every == 0
            with tracer.span("train.step", step=start_step + it,
                             telemetry=sampled):
                if sampled:
                    state, metrics, telem = telem_step_fn(
                        state, x=jnp.asarray(x), labels=jnp.asarray(y),
                        key=jax.random.PRNGKey(start_step + it),
                    )
                    records = T.to_records(telem, cfg=cfg,
                                           step=start_step + it)
                    T.append_jsonl(telemetry_out, records)
                    monitor.observe_records(records)
                else:
                    state, metrics = step_fn(
                        state, x=jnp.asarray(x), labels=jnp.asarray(y),
                        key=jax.random.PRNGKey(start_step + it),
                    )
            dt = timer.lap()
            step_seconds.observe(dt)
            if straggler.record(dt):
                straggler_events.inc()
                print(f"[straggler] step {it}: {dt:.3f}s vs ewma {straggler.ewma:.3f}s")
            if it % 50 == 0:
                print(f"step {it:5d}  loss={int(metrics.loss)}  "
                      f"scaled={metrics.scaled_loss(batch):.4f}  "
                      f"correct={int(metrics.correct)}/{batch}")
            if checkpointer and it > 0 and it % 200 == 0:
                with tracer.span("train.checkpoint", step=start_step + it):
                    checkpointer.save(start_step + it, state)
            it += 1
        if guard.requested:
            break
    if checkpointer:
        with tracer.span("train.checkpoint", step=start_step + it,
                         final=True):
            checkpointer.save(start_step + it, state)
            checkpointer.wait()

    # test accuracy
    correct = 0
    with tracer.span("train.eval"):
        for i in range(0, len(ds.x_test) - batch + 1, batch):
            correct += int(les.eval_step(
                state, cfg, jnp.asarray(ds.x_test[i:i + batch]),
                jnp.asarray(ds.y_test[i:i + batch])))
    n_eval = (len(ds.x_test) // batch) * batch
    acc = correct / max(n_eval, 1)
    if trace_out:
        n_spans = tracer.export_jsonl(trace_out)
        print(f"[trace] {n_spans} spans -> {trace_out}")
    if monitor.alerts:
        counts = monitor.summary()["by_severity"]
        print(f"[health] {len(monitor.alerts)} alert(s) fired "
              f"({', '.join(f'{k}={v}' for k, v in counts.items() if v)}); "
              f"{len(monitor.active_alerts())} still active")
    if server is not None:
        server.close()
    print(f"[done] test accuracy {acc:.4f} over {n_eval} samples")
    out = {"test_accuracy": acc, "steps": it,
           "straggler_events": straggler.incidents,
           "health": monitor.summary()}
    if metrics is not None:
        out["scaled_loss"] = metrics.scaled_loss(batch)
    return out


def train_lm(arch: str, *, steps: int, batch: int, seq: int, scale: float,
             ckpt_dir: str | None, les_groups: int = 0, seed: int = 0) -> dict:
    """Reduced-scale LM training on CPU (same code path as the dry-run)."""
    from dataclasses import replace

    from repro.configs import get_smoke_config
    from repro.data.loader import ShardedLoader, synthetic_lm_generator
    from repro.launch.mesh import make_test_mesh
    from repro.parallel.sharding import train_rules
    from repro.train import checkpoint as ckpt
    from repro.train import trainer

    cfg = get_smoke_config(arch)
    if les_groups:
        cfg = replace(cfg, les_groups=les_groups, num_layers=max(cfg.num_layers, 4))
    mesh = make_test_mesh(1, 1)
    rules = trainer.resolved_rules(cfg, train_rules(False))

    gen = synthetic_lm_generator(cfg.vocab_size, seq, batch, seed=seed)
    loader = ShardedLoader(gen, global_batch=batch,
                           process_index=0, process_count=1)
    shapes = {"tokens": (batch, seq), "labels": (batch, seq)}
    step_fn = trainer.build_train_step(cfg, mesh, rules, shapes=shapes,
                                       donate=False)
    state = trainer.init_state(jax.random.PRNGKey(seed), cfg)

    start = 0
    if ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
        state, start = ckpt.restore(ckpt_dir, state)
        print(f"[restore] resumed from step {start}")

    losses = []
    for it in range(steps):
        b = next(loader)
        state, metrics = step_fn(state, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(metrics["loss"]))
        if it % 20 == 0:
            print(f"step {it:4d}  loss={losses[-1]:.4f}  "
                  f"gnorm={float(metrics['grad_norm']):.3f}")
    loader.close()
    if ckpt_dir:
        ckpt.save(ckpt_dir, start + steps, state)
    print(f"[done] loss {losses[0]:.4f} → {losses[-1]:.4f}")
    return {"first_loss": losses[0], "last_loss": losses[-1]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--dataset", default="tiles32")
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--les-groups", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry-every", type=int, default=0,
                    help="sample integer-numerics telemetry every N steps "
                         "(0 = off) into --telemetry-out")
    ap.add_argument("--telemetry-out",
                    help="telemetry JSONL path (default: metrics.jsonl "
                         "next to the checkpoints)")
    ap.add_argument("--trace-out",
                    help="write a span trace of the run (JSONL)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics, /metrics.json and /healthz on "
                         "this port (0 = ephemeral; NITRO archs)")
    ap.add_argument("--alerts-out",
                    help="append health alerts as JSONL (they always "
                         "print inline)")
    ap.add_argument("--num-devices", type=int, default=1,
                    help="data-parallel device count (NITRO archs; "
                         "trajectory is bitwise-identical at any value)")
    ap.add_argument("--dp-reduce", default="psum",
                    choices=("psum", "ring", "compress"),
                    help="gradient all-reduce: XLA psum, hand-scheduled "
                         "ring, or int8-limb compressed (all exact)")
    ap.add_argument("--fuse-opt", action="store_true",
                    help="apply the IntegerSGD update in the grad "
                         "kernels' flush (NITRO archs; single-device "
                         "fast path — DP applies the standalone fused "
                         "kernel post-reduce; bitwise-identical)")
    ap.add_argument("--autotune", action="store_true",
                    help="search kernel tile configs for this (arch, "
                         "batch) before compiling (NITRO archs; bitwise "
                         "result-invariant)")
    ap.add_argument("--autotune-cache",
                    help="tile-cache JSON path (default: tile_cache.json "
                         "next to the checkpoints)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.num_devices > 1 and jax.device_count() < args.num_devices:
        if jax.default_backend() != "cpu":
            # An accelerator host has the chips it has; a second process
            # could not reach them anyway (one process per chip).
            raise SystemExit(
                f"--num-devices {args.num_devices}: this "
                f"{jax.default_backend()} host has only "
                f"{jax.device_count()} devices")
        # XLA only honours forced host devices before backend init — too
        # late in this process (device_count() just initialised it), so
        # re-exec ourselves with the flag set (CPU only).
        if os.environ.get("_REPRO_DP_REEXEC"):
            raise SystemExit(
                f"--num-devices {args.num_devices}: still only "
                f"{jax.device_count()} devices after forcing XLA_FLAGS")
        import sys
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.num_devices}"
        ).strip()
        os.environ["_REPRO_DP_REEXEC"] = "1"
        os.execv(sys.executable,
                 [sys.executable, "-m", "repro.launch.train"] + sys.argv[1:])

    from repro.configs import ARCHS, PAPER_ARCHS

    if args.arch in PAPER_ARCHS:
        train_nitro(args.arch, steps=args.steps, batch=args.batch,
                    ckpt_dir=args.ckpt_dir, dataset=args.dataset,
                    scale=args.scale, seed=args.seed,
                    telemetry_every=args.telemetry_every,
                    telemetry_out=args.telemetry_out,
                    trace_out=args.trace_out,
                    num_devices=args.num_devices, dp_reduce=args.dp_reduce,
                    fuse_opt=args.fuse_opt,
                    metrics_port=args.metrics_port,
                    alerts_out=args.alerts_out,
                    autotune=args.autotune,
                    autotune_cache=args.autotune_cache)
    elif args.arch in ARCHS:
        train_lm(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
                 scale=args.scale, ckpt_dir=args.ckpt_dir,
                 les_groups=args.les_groups, seed=args.seed)
    else:
        raise SystemExit(f"unknown arch {args.arch}")


if __name__ == "__main__":
    main()
