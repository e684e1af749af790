"""Faults planted under the timed path, for the tests that must see
``correct`` come out false.  Each patches the program where the harness
reaches it, and undoes the patch on exit."""

from __future__ import annotations

import contextlib
import json

import jax.numpy as jnp
import numpy as np


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def train_fault(kind: str, bench_cfg: dict):
    """A replacement for ``les.train_step`` with the named fault."""
    from repro.core import les

    from bench import reference

    real = les.train_step

    def step(state, cfg, x, labels, key, **kw):
        if kind == "unchanged":
            _, m = real(state, cfg, x, labels, key, **kw)
            return state, m
        if kind == "half_batch":
            half = x.shape[0] // 2
            return real(state, cfg, x[:half], labels[:half], key, **kw)
        if kind == "control_int16":
            p, m = reference.train_step(bench_cfg, state.params, x, labels,
                                        key, acc_dtype=jnp.int16)
            return (state._replace(params=p, step=state.step + 1),
                    les.StepMetrics(**m))
        raise ValueError(kind)

    return patched(les, "train_step", step)


class _LowPlan:
    """A served plan computed by the reference with int16 accumulation."""

    def __init__(self, fm, cfg):
        from bench import reference

        self.input_shape = fm.input_shape
        self.backend = "control"
        params = {"blocks": [{"fw": {"w": jnp.asarray(l.w, jnp.int32)}}
                             for l in fm.layers[:-1]],
                  "output": {"w": jnp.asarray(fm.layers[-1].w, jnp.int32)}}
        self._f = lambda x: reference.forward(cfg, params, x,
                                              acc_dtype=jnp.int16)[0]

    def logits(self, x):
        return self._f(jnp.asarray(x))


def serve_fault(kind: str, bench_cfg: dict):
    from repro.serving import fleet, registry

    if kind == "control_int16":
        return patched(registry, "compile_plan",
                       lambda fm, **kw: _LowPlan(fm, bench_cfg))
    if kind == "altered":
        real = fleet.resolve_batch

        def resolve(items, logits, t_done):
            logits = np.array(logits)
            logits[0, 0] += 1
            return real(items, logits, t_done)

        return patched(fleet, "resolve_batch", resolve)
    raise ValueError(kind)


def config_of(root, cell: str) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    w = next(w for w in bench["workloads"] if w["name"] == cell)
    c = next(c for c in bench["configs"] if c["name"] == w["config"])
    return json.loads((root / c["file"]).read_text())
