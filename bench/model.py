"""A configuration file made into the program's model, and its weights.

The file under ``bench/configs/`` is the configuration as it is run.
``nitro_config`` turns it into the program's ``NitroConfig`` (the
program's interface, nothing more); ``init_params`` makes the weights
from the seed on the device in one jitted call, in the program's pytree
layout, so that the program and the plain reference start from the same
integers that neither of them made.

Two weight ranges:

* ``"kaiming"`` — the paper's integer Kaiming initialisation
  (Appendix B.1): U(−b, b) with b = ⌊128·1732 / (⌊√fan_in⌋·1000)⌋.  A
  training job starts from this.
* ``"served"`` — weights in int16, as a trained NITRO-D model keeps them,
  with each layer's range set so that its scaled pre-activations spread
  over the NITRO-ReLU range (standard deviation about 48 for inputs of
  standard deviation 64, then 40).  Kaiming weights would give nearly
  constant activations, which no served model has and which would let a
  lower-precision path go unseen.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import reference

SERVED_Z_STD = 48
INPUT_STD = 64
ACT_STD = 40


def nitro_config(cfg: dict):
    """The program's ``NitroConfig`` for a configuration file."""
    from repro.core.blocks import BlockSpec
    from repro.core.model import NitroConfig

    blocks = tuple(
        BlockSpec(b["kind"], b["width"], pool=b["pool"], dropout=b["dropout"],
                  d_lr=b["d_lr"], alpha_inv=cfg["alpha_inv"],
                  kernel_size=cfg["kernel_size"])
        for b in cfg["blocks"])
    return NitroConfig(
        blocks=blocks, input_shape=tuple(cfg["input_shape"]),
        num_classes=cfg["num_classes"], gamma_inv=cfg["gamma_inv"],
        eta_fw=cfg["eta_fw"], eta_lr=cfg["eta_lr"], name=cfg["name"])


def _fan_in(shape) -> int:
    return math.prod(shape[:-1])


def kaiming_bound(fan_in: int) -> int:
    return max((128 * 1732) // (max(math.isqrt(fan_in), 1) * 1000), 1)


def served_bound(fan_in: int, in_std: int) -> int:
    b = math.sqrt(3) * SERVED_Z_STD * 256 * math.sqrt(fan_in) / in_std
    return min(int(round(b)), 2**15 - 1)


def weight_bounds(cfg: dict, mode: str) -> list[tuple[tuple, int]]:
    """(shape, bound) of every weight, in pytree order: per block fw, lr;
    then the output layer."""
    shapes = reference.layer_shapes(cfg)
    out = []
    for i, shp in enumerate(shapes):
        names = ("fw", "lr") if "lr" in shp else ("fw",)
        for name in names:
            w = shp[name]
            if mode == "kaiming":
                b = kaiming_bound(_fan_in(w))
            elif mode == "served":
                b = served_bound(_fan_in(w), INPUT_STD if i == 0 else ACT_STD)
            else:
                raise ValueError(f"unknown weight mode {mode!r}")
            out.append((w, b))
    return out


@functools.lru_cache(maxsize=None)
def _init_fn(bounds: tuple):
    def init(key):
        keys = jax.random.split(key, len(bounds))
        return [jax.random.randint(k, shape, -b, b + 1, jnp.int32)
                for k, (shape, b) in zip(keys, bounds)]
    return jax.jit(init)


def init_params(cfg: dict, key, mode: str = "kaiming") -> dict:
    """All weights from ``key`` in one jitted call, as int32."""
    bounds = tuple((tuple(s), b) for s, b in weight_bounds(cfg, mode))
    leaves = _init_fn(bounds)(key)
    blocks, i = [], 0
    for _ in cfg["blocks"]:
        blocks.append({"fw": {"w": leaves[i]}, "lr": {"w": leaves[i + 1]}})
        i += 2
    return {"blocks": blocks, "output": {"w": leaves[i]}}


def train_state(cfg: dict, params: dict):
    """The program's ``TrainState`` at step 0 around ``params``."""
    from repro.core import les
    from repro.core.optimizer import IntegerSGDState

    def sgd(gamma, eta):
        return IntegerSGDState(gamma_inv=jnp.asarray(gamma, jnp.int32),
                               eta_inv=jnp.asarray(eta, jnp.int32))

    af = 64 * cfg["num_classes"]
    return les.TrainState(
        params=params,
        opt_lr=sgd(cfg["gamma_inv"], cfg["eta_lr"]),
        opt_fw=sgd(cfg["gamma_inv"] * af, cfg["eta_fw"]),
        step=jnp.zeros((), jnp.int32))


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    if seed < 0:
        raise ValueError(f"--seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
