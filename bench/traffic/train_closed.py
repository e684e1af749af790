"""Closed-loop training: the program's LES step, one batch after another.

Mix parameters (``bench/traffic/<mix>.json``):

* ``batch`` — images per step;
* ``distinct_batches`` — batches made from the seed and cycled through,
  every one of them different (an epoch over a dataset of that many
  batches).

Set-up builds one object, the step as the program compiles it
(``jax.jit(les.train_step)`` at its defaults), with its state made from
the seed, drives it through the first three steps on three different
batches, keeps host copies of what they produced, and hands the same
compiled step and state to the window.  The window keeps ``IN_FLIGHT``
steps queued ahead of the one it waits for, so that the chip stays fed
while the host stands still; when its time is up it sends nothing more,
waits for every step sent, and reads the clock after that wait: images
trained over the wall time of all of it.  ``train_peak_hbm_mb`` is the
device's peak through set-up, where the step runs on the window's own
state and batches one or two at a time: what the job needs, without the
states that the window's queue holds.  After the window the reference
repeats the first three steps from the same weights and batches.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from bench import checks, data, model, reference

CHECK_STEPS = 3
#: steps queued ahead of the one the host waits for: the TPU runtime
#: queues about this many executions and no more, which is 0.18 s of
#: MLP4 steps and 0.86 s of VGG8B steps on a v5e, enough to ride out the
#: host's stalls of a tenth of a second
IN_FLIGHT = 32


@dataclass
class Session:
    cfg: dict
    weight_key: object
    xs: list
    ys: list
    keys: list
    compiled: object = None
    state: object = None
    next_batch: int = 0
    first: dict = field(default_factory=dict)


def build_step(run, cfg):
    """The program's jitted training step for this cell (one chip)."""
    from repro.core import les

    return jax.jit(functools.partial(les.train_step,
                                     cfg=model.nitro_config(cfg)))


def reference_device(run):
    return run.devices[0]


def make_inputs(run):
    cfg, mix = run.config, run.mix
    batch, count = mix["batch"], mix["distinct_batches"]
    key = model.seed_key(run.seed)
    k_data, k_weights, k_drop = jax.random.split(key, 3)
    shape = tuple(cfg.get("image_shape", cfg["input_shape"]))
    x, y = data.images(k_data, n=batch * count, shape=shape,
                       num_classes=cfg["num_classes"])
    x = x.reshape(count, batch, *cfg["input_shape"])
    y = y.reshape(count, batch)
    return (k_weights, list(x), list(y),
            list(jax.random.split(k_drop, count)))


def setup(run, build=build_step) -> Session:
    cfg = run.config
    k_weights, xs, ys, keys = make_inputs(run)
    s = Session(cfg=cfg, weight_key=k_weights, xs=xs, ys=ys, keys=keys)
    state = model.train_state(cfg, model.init_params(cfg, k_weights))
    step = build(run, cfg)
    s.compiled = step.lower(state, x=xs[0], labels=ys[0],
                            key=keys[0]).compile()
    (state_sh,), kw_sh = s.compiled.input_shardings
    s.xs = [jax.device_put(x, kw_sh["x"]) for x in xs]
    s.ys = [jax.device_put(y, kw_sh["labels"]) for y in ys]
    s.keys = [jax.device_put(k, kw_sh["key"]) for k in keys]
    state = jax.device_put(state, state_sh)
    metrics = []
    for i in range(CHECK_STEPS):
        state, m = s.compiled(state, x=s.xs[i], labels=s.ys[i],
                              key=s.keys[i])
        metrics.append(m)
        if i == 0:
            s.first["p1"] = jax.device_get(state.params)
    host = jax.device_get((state, metrics))
    s.first["p3"] = host[0].params
    s.first["extra"] = (host[0].opt_lr, host[0].opt_fw, host[0].step)
    s.first["metrics"] = [dict(m._asdict()) for m in host[1]]
    s.state, s.next_batch = state, CHECK_STEPS
    return s


def window(run, s: Session, seconds: float) -> None:
    batches = len(s.xs)
    i, steps = s.next_batch, 0
    state, compiled = s.state, s.compiled
    span = jax.profiler.TraceAnnotation if run.trace else contextlib.nullcontext
    flight = collections.deque()
    t_start = time.perf_counter()
    deadline = t_start + seconds
    while True:
        if len(flight) == IN_FLIGHT:
            with span("bench.wait"):
                flight.popleft().block_until_ready()
        j = i % batches
        with span("bench.dispatch"):
            state, m = compiled(state, x=s.xs[j], labels=s.ys[j],
                                key=s.keys[j])
        flight.append(m.loss)
        i += 1
        steps += 1
        if time.perf_counter() >= deadline:
            break
    with span("bench.wait"):
        jax.block_until_ready((state, m))
    elapsed = time.perf_counter() - t_start
    s.state, s.next_batch = state, i
    images = steps * run.mix["batch"]
    run.attempted = steps
    run.window.update(seconds=elapsed, steps=steps, images=images)
    run.end_to_end["train_images_per_s"] = images / elapsed


def release(run, s: Session) -> None:
    run.end_to_end["train_peak_hbm_mb"] = run.setup_peak_bytes / 1e6
    s.state = s.compiled = None


def reference_steps(run, s: Session, steps: int = CHECK_STEPS, **kw):
    """The plain reference over the same first batches, from the same
    weights: ([params after each step], [metrics of each step])."""
    dev = reference_device(run)
    cfg = s.cfg
    params = jax.device_put(model.init_params(cfg, s.weight_key), dev)
    step = jax.jit(functools.partial(reference.train_step, cfg, **kw))
    out_p, out_m = [], []
    for i in range(steps):
        x, y, k = (jax.device_put(a, dev)
                   for a in (s.xs[i], s.ys[i], s.keys[i]))
        params, m = step(params, x, y, k)
        out_p.append(jax.device_get(params))
        out_m.append(jax.device_get(m))
    p0 = jax.device_get(model.init_params(cfg, s.weight_key))
    return p0, out_p, out_m


def expected_extra(cfg: dict, steps: int = CHECK_STEPS):
    """Optimiser scalars and step count the configuration implies."""
    g, af = cfg["gamma_inv"], 64 * cfg["num_classes"]
    lr = (np.int32(g), np.int32(cfg["eta_lr"]))
    fw = (np.int32(g * af), np.int32(cfg["eta_fw"]))
    return (lr, fw, np.int32(steps))


def check(run, s: Session):
    p0, ref_p, ref_m = reference_steps(run, s)
    f = s.first
    return checks.train_checks(
        p0, f["p1"], f["p3"], f["metrics"], ref_p[0], ref_p[-1], ref_m,
        f["extra"], expected_extra(s.cfg))
