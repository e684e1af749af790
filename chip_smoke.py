"""Drive NITRO-D's main path once on a TPU and check it bit for bit.

    python chip_smoke.py              # one chip: train, then serve
    python chip_smoke.py --chips 4    # four chips: data-parallel training only

One chip (the default):

  1. train — VGG8B at its published widths (scale 1, 32×32×3 synthetic
     data, batch 128) for three steps of ``les.train_step`` at its
     defaults (``backend="auto"``, fused forward, fused backward, streaming
     conv), then the same steps with ``backend="reference"``: parameters,
     optimiser state and metrics must be bitwise equal;
  2. serve — ``freeze`` the trained state, ``compile_plan`` it behind a
     ``FleetEngine`` and answer 64 requests; no request may
     fail, and every logits row must equal the reference plan's.

``--chips 4`` runs only ``dp.make_dp_train_step`` for VGG8B on a 4-device
``data`` mesh (global batch 128), once per reducer, each compared bitwise
with the single-device step on the same batch, and prints which devices
hold the batch shards.

Every phase prints its device, compile and step seconds and the device's
``peak_bytes_in_use``.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Off a TPU, outside the repository, on any mismatch or failed request the
script exits non-zero and prints no such line; it never falls back to the
CPU.
"""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
SEED = 0
BATCH = 128
STEPS = 3
SERVE_BATCH = 32
REQUESTS = 64


def _fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    return 1


def _assert_bitwise(got, want, what: str) -> None:
    """Every leaf of two pytrees equal in dtype, shape and bits."""
    import jax
    import numpy as np

    g_leaves, g_tree = jax.tree_util.tree_flatten(got)
    w_leaves, w_tree = jax.tree_util.tree_flatten(want)
    if g_tree != w_tree:
        raise AssertionError(f"{what}: tree structure differs")
    for i, (g, w) in enumerate(zip(g_leaves, w_leaves)):
        g, w = np.asarray(g), np.asarray(w)
        if g.dtype != w.dtype or g.shape != w.shape:
            raise AssertionError(
                f"{what}: leaf {i} is {g.dtype}{g.shape}, "
                f"want {w.dtype}{w.shape}")
        if not np.array_equal(g, w):
            n = int(np.sum(g != w))
            raise AssertionError(f"{what}: leaf {i} differs in {n} elements")


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _batches(steps: int):
    """``steps`` (x, labels) batches of the synthetic 32×32×3 dataset."""
    from repro.data import synthetic

    ds = synthetic.make_image_dataset(
        "tiles32", n_train=BATCH * steps, n_test=REQUESTS, seed=SEED)
    batches = list(synthetic.batches(ds.x_train, ds.y_train, BATCH, seed=SEED))
    return ds, batches[:steps]


def _run_steps(step, state, batches):
    """Compile ``step`` ahead of time, then run it over ``batches``.

    Inputs are placed where the compiled program wants them.  Returns
    ``(state, metrics list, compile_s, step_s list)``; each step is timed
    to ``block_until_ready``.
    """
    import jax

    x0, y0 = batches[0]
    t0 = time.perf_counter()
    compiled = step.lower(
        state, x=x0, labels=y0, key=jax.random.PRNGKey(0)).compile()
    compile_s = time.perf_counter() - t0
    (state_sharding,), kw_shardings = compiled.input_shardings
    metrics, step_s = [], []
    for i, (x, y) in enumerate(batches):
        state = jax.device_put(state, state_sharding)
        kw = jax.device_put(
            {"x": x, "labels": y, "key": jax.random.PRNGKey(i)}, kw_shardings)
        jax.block_until_ready((state, kw))
        t0 = time.perf_counter()
        state, m = compiled(state, **kw)
        jax.block_until_ready((state, m))
        step_s.append(time.perf_counter() - t0)
        metrics.append(m)
    return state, metrics, compile_s, step_s


def train_phase(cfg, batches, device, tag: str):
    """Train on the default path and on the reference backend; both must
    agree bitwise.  Returns the trained state."""
    import jax

    from repro.core import les

    init = les.create_train_state(jax.random.PRNGKey(SEED), cfg)
    results = {}
    for backend in ("auto", "reference"):
        step = jax.jit(functools.partial(les.train_step, cfg=cfg,
                                         backend=backend))
        state, metrics, compile_s, step_s = _run_steps(step, init, batches)
        results[backend] = (state, metrics)
        print(f"[train] device={tag} arch={cfg.name} batch={BATCH} "
              f"backend={backend} compile_s={compile_s:.3f} "
              f"step_s={[round(s, 6) for s in step_s]} "
              f"peak_bytes_in_use={_peak_bytes(device)}", flush=True)
    _assert_bitwise(results["auto"], results["reference"],
                    "train auto vs reference")
    last = results["auto"][1][-1]
    print(f"[train] device={tag} steps={len(batches)} auto == reference "
          f"bitwise (params, optimiser state, metrics); last loss="
          f"{int(last.loss)} correct={int(last.correct)}/{BATCH}", flush=True)
    return results["auto"][0]


def serve_phase(state, cfg, images, device, tag: str) -> None:
    """Serve ``images`` through ``FleetEngine`` on the default backend and
    check every answer against the reference plan."""
    import numpy as np

    from repro.infer import compile_plan, freeze
    from repro.serving.fleet import FleetEngine
    from repro.serving.registry import ModelRegistry

    fm = freeze(state, cfg)
    registry = ModelRegistry(backend="auto")
    entry = registry.register(cfg.name, fm)
    if entry.plan.backend != "pallas":
        raise AssertionError(f"serving plan backend {entry.plan.backend}")
    t0 = time.perf_counter()
    np.asarray(entry.plan.logits(np.zeros((SERVE_BATCH, *cfg.input_shape),
                                          np.int32)))
    compile_s = time.perf_counter() - t0
    want = np.asarray(compile_plan(fm, backend="reference").logits(images))

    t0 = time.perf_counter()
    with FleetEngine(registry, batch_size=SERVE_BATCH) as engine:
        futures = [engine.submit(img, model=cfg.name) for img in images]
        errors = [f.exception() for f in futures]
    serve_s = time.perf_counter() - t0
    failed = [e for e in errors if e is not None]
    if failed:
        raise AssertionError(
            f"{len(failed)}/{len(futures)} requests failed; first: "
            f"{failed[0]!r}")
    got = np.stack([f.result().logits for f in futures])
    _assert_bitwise(got, want, "served logits vs reference plan")
    print(f"[serve] device={tag} requests={len(futures)} failed=0 "
          f"batch_size={SERVE_BATCH} compile_s={compile_s:.3f} "
          f"serve_s={serve_s:.6f} logits == reference plan bitwise "
          f"peak_bytes_in_use={_peak_bytes(device)}", flush=True)


def dp_phase(cfg, batches, devices, tag: str) -> None:
    """Data-parallel VGG8B over four chips, every reducer bitwise equal
    to the single-device step on the same batches."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import les
    from repro.parallel import dp

    mesh = dp.data_mesh(4)
    shard_devices = sorted({
        s.device.id for s in jax.device_put(
            batches[0][0], NamedSharding(mesh, P(dp.DP_AXIS))
        ).addressable_shards
    })
    print(f"[dp] device={tag} mesh={dict(mesh.shape)} batch shards on "
          f"device ids {shard_devices}", flush=True)
    if len(shard_devices) != 4:
        raise AssertionError(f"batch shards on {shard_devices}, want 4")

    init = les.create_train_state(jax.random.PRNGKey(SEED), cfg)
    single = jax.jit(functools.partial(les.train_step, cfg=cfg))
    want, want_m, compile_s, step_s = _run_steps(
        single, jax.device_put(init, devices[0]), batches)
    print(f"[dp] device={tag} single-device compile_s={compile_s:.3f} "
          f"step_s={[round(s, 6) for s in step_s]}", flush=True)
    for reducer in dp.REDUCERS:
        step = dp.make_dp_train_step(cfg, mesh, dp_reduce=reducer)
        got, got_m, compile_s, step_s = _run_steps(step, init, batches)
        _assert_bitwise((got, got_m), (want, want_m),
                        f"dp {reducer} vs single device")
        print(f"[dp] device={tag} reducer={reducer} compile_s="
              f"{compile_s:.3f} step_s={[round(s, 6) for s in step_s]} "
              f"== single device bitwise peak_bytes_in_use="
              f"{[_peak_bytes(d) for d in devices[:4]]}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel phase on 4 chips")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no NITRO-D source tree under {ROOT}")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.configs import paper
    from repro.kernels.nitro_matmul.ops import resolve_backend
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"needs a TPU; JAX found {dev.platform} devices")
    if len(devices) < args.chips:
        return _fail(f"--chips {args.chips} but {len(devices)} devices")
    if resolve_backend("auto") != "pallas":
        return _fail("backend 'auto' does not resolve to the Pallas kernels")
    print(f"[setup] compile cache: {enable_compile_cache()}", flush=True)
    tag = f"{dev.platform}:{dev.device_kind}x{len(devices)}"

    try:
        cfg = paper.get("vgg8b", scale=1.0)
        ds, batches = _batches(STEPS)
        if args.chips == 4:
            dp_phase(cfg, batches, devices, tag)
        else:
            state = train_phase(cfg, batches, dev, tag)
            serve_phase(state, cfg, ds.x_test[:REQUESTS], dev, tag)
    except Exception:  # any failed phase fails the run, never exits 0
        traceback.print_exc()
        return _fail("a phase failed")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
