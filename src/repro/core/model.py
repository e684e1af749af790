"""NITRO-D model container: a stack of integer local-loss blocks + output
layers, described by a static config and a parameter pytree.

The same container expresses every paper architecture (MLP 1–4, VGG8B,
VGG11B) and anything in between; `repro/configs/paper.py` instantiates the
exact Appendix-C tables.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.core import blocks as B
from repro.core.activations import relu_fits_int8
from repro.core.numerics import INT_DTYPE
from repro.obs import layers as scopes


@dataclass(frozen=True)
class NitroConfig:
    """Static NITRO-D architecture + optimiser hyper-parameters."""

    blocks: tuple[B.BlockSpec, ...]
    input_shape: tuple[int, ...]      # per-sample shape, e.g. (32,32,3) / (784,)
    num_classes: int
    gamma_inv: int = 512              # γ_inv (learning layers / output layers)
    eta_fw: int = 0                   # η_inv^fw  (0 = no decay)
    eta_lr: int = 0                   # η_inv^lr
    name: str = "nitro-d"

    @property
    def num_blocks(self) -> int:
        return len(self.blocks)


def init_params(key: jax.Array, cfg: NitroConfig) -> dict:
    """Initialise every block + the output layers (integer Kaiming)."""
    keys = jax.random.split(key, cfg.num_blocks + 1)
    params: dict = {"blocks": [], "output": None}
    shape = cfg.input_shape
    for spec, k in zip(cfg.blocks, keys[:-1]):
        p, shape = B.init_block(k, spec, shape, cfg.num_classes)
        params["blocks"].append(p)
    feat = 1
    for d in shape:
        feat *= d
    params["output"] = B.init_output(keys[-1], feat, cfg.num_classes)
    return params


def forward(
    params: dict,
    cfg: NitroConfig,
    x: jax.Array,
    *,
    train: bool = False,
    key: jax.Array | None = None,
    fused: bool = True,
    backend: str = "auto",
    conv_mode: str = "stream",
    dp_axis: str | None = None,
    dp_shards: int = 1,
) -> tuple[jax.Array, list[jax.Array], list[dict], dict]:
    """Full forward pass.

    Returns (ŷ, block activations a_1..a_L, forward caches, output cache).
    Inference callers only use ŷ; the LES trainer consumes the rest.

    ``fused`` selects the block-layer implementation: the fused kernel
    entry points shared with the inference plan (default), or the unfused
    matmul → scale → relu reference composition.  ``conv_mode`` picks the
    fused conv route: ``'stream'`` (implicit im2col, no HBM patch matrix)
    or ``'materialise'`` (explicit im2col escape hatch).  All combinations
    are bit-exact with each other, test-enforced.  (The backward mirror —
    the ``fuse_bwd`` δ-path knob — lives on ``les.train_step``, which
    threads the same ``backend``/``conv_mode`` into the gradient
    dispatcher ``kernels.grad_ops``.)

    ``dp_axis``/``dp_shards`` describe an enclosing data-parallel
    shard_map context; they only affect IntegerDropout (global-batch
    mask, sliced per shard — see ``layers.dropout_forward``).
    """
    a = jnp.asarray(x, INT_DTYPE)
    acts: list[jax.Array] = []
    caches: list[dict] = []
    if train and key is not None:
        drop_keys = list(jax.random.split(key, cfg.num_blocks))
    else:
        drop_keys = [None] * cfg.num_blocks
    fits_int8 = False  # the network input is not a NITRO-ReLU output
    for i, (spec, p, dk) in enumerate(
        zip(cfg.blocks, params["blocks"], drop_keys)
    ):
        with scopes.block(i, scopes.FORWARD):
            a, cache = B.forward_layers(
                p, spec, a, dropout_key=dk, train=train,
                fused=fused, backend=backend, conv_mode=conv_mode,
                dp_axis=dp_axis, dp_shards=dp_shards, x_fits_int8=fits_int8,
            )
        acts.append(a)
        caches.append(cache)
        # The next block's input is this block's NITRO-ReLU output (max-pool
        # keeps its range); integer dropout rescales it past int8.
        fits_int8 = relu_fits_int8(spec.alpha_inv) and not (
            train and spec.dropout > 0.0
        )
    with scopes.output():
        y_hat, out_cache = B.output_forward(params["output"], a)
    return y_hat, acts, caches, out_cache


def frozen_forward(params: dict, cfg: NitroConfig, x: jax.Array) -> jax.Array:
    """Inference logits on frozen params (train=False, no caches used).

    The single source of truth for the deploy-time forward: ``les.eval_step``,
    ``predict`` and the ``repro.infer`` parity reference all route through it,
    so the fused inference plan has exactly one oracle to match bit-for-bit.
    Deliberately runs the *unfused* reference composition — it must stay an
    independent oracle for the fused kernel paths (train and infer alike).
    """
    y_hat, _, _, _ = forward(params, cfg, x, train=False, fused=False)
    return y_hat


def predict(params: dict, cfg: NitroConfig, x: jax.Array) -> jax.Array:
    """Inference-only path (learning layers unused — paper §E.3)."""
    return jnp.argmax(frozen_forward(params, cfg, x), axis=-1)


def count_params(params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
