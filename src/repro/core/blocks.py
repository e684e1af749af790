"""Integer local-loss blocks — the core NITRO-D architectural unit (§3.2).

Each block owns

  *forward layers*  : IntegerConv2D/IntegerLinear → NITRO Scaling →
                      NITRO-ReLU → [MaxPool2D] → [IntegerDropout]
  *learning layers* : [adaptive int avg-pool to d_lr] → flatten →
                      IntegerLinear(→ G) → NITRO Scaling   (produces ŷ_l)

During the backward pass gradients are *confined to the block*: the local
RSS gradient ∇L_l flows through the learning layers (updating them with
γ_inv^lr) and emerges as δ_l^fw = ∇L_l·W^{il,T} at the block output, then
flows through the forward layers (updating them with γ_inv^fw =
γ_inv^lr·AF).  Nothing crosses block boundaries — this is what bounds
integer bit-growth and makes blocks independently (= in parallel) trainable.

Every NITRO Scaling Layer is paired with its producing linear/conv layer:
the learning-layer and output-layer linears are scaled too (without a
ReLU), which is what keeps ŷ within the one-hot range and makes the
paper's b_∇L = 6 bit-width bound hold.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import activations, layers, numerics, scaling
from repro.core.losses import rss_grad
from repro.core.numerics import INT_DTYPE


def _nitro_ops():
    """Lazy import of the fused-matmul dispatcher.

    ``repro.core.__init__`` imports this module, and the kernel package
    imports ``repro.core`` leaf modules — a module-level import here would
    make ``import repro.kernels.nitro_matmul`` (as the first repro import
    of a process) circular.  Resolving at trace time breaks the cycle; the
    cost is one sys.modules lookup per traced layer.
    """
    from repro.kernels.nitro_matmul import ops

    return ops


def _conv_ops():
    """Lazy import of the conv dispatcher (same cycle-breaking rationale)."""
    from repro.kernels.nitro_conv import ops

    return ops


@dataclass(frozen=True)
class BlockSpec:
    """Static description of one integer local-loss block."""

    kind: str                 # 'conv' | 'linear'
    out_features: int         # conv filters or linear width
    pool: bool = False        # MaxPool2D(2,2) after the activation
    dropout: float = 0.0      # p_c / p_l
    d_lr: int = 4096          # learning-layer input feature budget (conv)
    alpha_inv: int = activations.DEFAULT_ALPHA_INV
    kernel_size: int = 3


# ---------------------------------------------------------------------------
# Parameter initialisation
# ---------------------------------------------------------------------------


def init_block(
    key: jax.Array,
    spec: BlockSpec,
    in_shape: tuple[int, ...],
    num_classes: int,
) -> tuple[dict, tuple[int, ...]]:
    """Init one block's params; returns (params, output shape w/o batch)."""
    k_fw, k_lr = jax.random.split(key)
    if spec.kind == "conv":
        h, w, c = in_shape
        fw = layers.conv_init(k_fw, c, spec.out_features, spec.kernel_size)
        oh, ow = (h // 2, w // 2) if spec.pool else (h, w)
        out_shape = (oh, ow, spec.out_features)
        # learning layers see the adaptive pool of the block output
        dummy = jnp.zeros((1, oh, ow, spec.out_features), INT_DTYPE)
        pooled, _ = layers.avgpool_to(dummy, spec.d_lr)
        lr_in = pooled.shape[1] * pooled.shape[2] * pooled.shape[3]
    elif spec.kind == "linear":
        m = 1
        for d in in_shape:  # linear blocks flatten whatever precedes them
            m *= d
        fw = layers.linear_init(k_fw, m, spec.out_features)
        out_shape = (spec.out_features,)
        lr_in = spec.out_features
    else:
        raise ValueError(f"unknown block kind {spec.kind!r}")
    lr = layers.linear_init(k_lr, lr_in, num_classes)
    return {"fw": fw, "lr": lr}, out_shape


# ---------------------------------------------------------------------------
# Forward layers
# ---------------------------------------------------------------------------


def forward_layers(
    params: dict,
    spec: BlockSpec,
    x: jax.Array,
    *,
    dropout_key: jax.Array | None = None,
    train: bool = True,
    fused: bool = True,
    backend: str = "auto",
    dp_axis: str | None = None,
    dp_shards: int = 1,
    x_fits_int8: bool = False,
) -> tuple[jax.Array, dict]:
    """Run a block's forward layers; cache everything backward needs.

    ``fused=True`` (default) routes the matmul → NITRO Scaling → NITRO-ReLU
    pipeline through the fused kernel entry points the inference plan
    already uses: one VMEM pass emitting both the activation ``a`` and the
    pre-ReLU ``z_star`` the backward needs, instead of three HBM
    round-trips of the int32 pre-activation.  Conv blocks go through the
    ``nitro_conv`` dispatcher, which forms im2col patches implicitly from
    row bands so the ``(N·H·W, K²·C)`` patch matrix never touches HBM.
    ``fused=False`` is the unfused reference composition (explicit im2col
    + ``int_matmul``) — bit-exact with the fused path (the tests enforce
    it), kept as the oracle.

    The cache contract is identical either way (``z_star`` + the layer
    input), so ``forward_layers_backward`` is unchanged.

    ``dp_axis``/``dp_shards`` (a shard_map axis name + its static size)
    make IntegerDropout draw the global-batch mask and slice this
    shard's rows — see ``layers.dropout_forward``; no other layer
    samples, so nothing else needs them.

    ``x_fits_int8`` states that ``x`` is a NITRO-ReLU activation whose
    range ``activations.relu_fits_int8`` bounds (the caller knows this
    statically).  The fused path then narrows it to int8 — same values —
    so the kernels, forward and grad_W alike, take it as one limb
    (``kernels.limbs``) instead of four.
    """
    cache: dict[str, Any] = {}
    if fused and x_fits_int8:
        x = x.astype(jnp.int8)
    if spec.kind == "conv":
        c_in = x.shape[-1]
        sf = scaling.conv_scale_factor(spec.kernel_size, c_in)
        if fused:
            numerics.assert_int(x, "conv input")
            a, cache["z_star"] = _conv_ops().fused_conv_fwd(
                x, params["fw"]["w"], sf=sf, alpha_inv=spec.alpha_inv,
                backend=backend,
            )
            cache["conv"] = layers.ConvCache(x=x)
        else:
            z, cache["conv"] = layers.conv_forward(params["fw"], x)
    else:
        if x.ndim > 2:  # flatten conv activations entering a linear block
            x, _ = layers.flatten_forward(x)
        sf = scaling.linear_scale_factor(x.shape[-1])
        if fused:
            numerics.assert_int(x, "linear input")
            a, cache["z_star"] = _nitro_ops().fused_matmul_fwd(
                x, params["fw"]["w"], sf=sf, alpha_inv=spec.alpha_inv,
                backend=backend,
            )
            cache["linear"] = x
        else:
            z, cache["linear"] = layers.linear_forward(params["fw"], x)
    if not fused:
        z_star = scaling.scale_forward(z, sf)
        cache["z_star"] = z_star
        a = activations.nitro_relu(z_star, spec.alpha_inv)
    if spec.pool:
        a, cache["pool"] = layers.maxpool_forward(a)
    if train and spec.dropout > 0.0:
        a, cache["dropout"] = layers.dropout_forward(
            dropout_key, a, spec.dropout, dp_axis=dp_axis, dp_shards=dp_shards,
        )
    # The block output (what feeds the next block) — a reference, not a
    # copy: ``repro.obs.telemetry`` reads its bit-occupancy when the step
    # runs with telemetry on; jit DCEs it otherwise.
    cache["act"] = a
    return a, cache


def forward_layers_backward(
    params: dict,
    spec: BlockSpec,
    cache: dict,
    delta_fw: jax.Array,
    *,
    backend: str = "auto",
) -> dict:
    """Backward through the forward layers from δ_l^fw; returns weight grads.

    The input-gradient of the first layer is *not* propagated further —
    LES confines gradients to the block.  The dropout/pool backwards stay
    jnp; the NITRO-ReLU derivative + scaling STE that follow them are
    handed to the ``kernels.grad_ops`` dispatcher together with the cached
    ``z_star``: they run as a prologue inside the gradient kernels, so
    the post-ReLU-bwd δ never round-trips through HBM.
    """
    g = delta_fw
    if "dropout" in cache:
        g = layers.dropout_backward(cache["dropout"], g)
    if "pool" in cache:
        g = layers.maxpool_backward(cache["pool"], g)
    if spec.kind == "conv":
        _, grads = layers.conv_backward(
            params["fw"], cache["conv"], g,
            z_star=cache["z_star"], alpha_inv=spec.alpha_inv,
            backend=backend,
        )
    else:
        _, grads = layers.linear_backward(
            params["fw"], cache["linear"], g,
            z_star=cache["z_star"], alpha_inv=spec.alpha_inv,
            backend=backend,
        )
    return grads


def forward_layers_update(
    params: dict,
    spec: BlockSpec,
    cache: dict,
    delta_fw: jax.Array,
    opt_state,
    *,
    backend: str = "auto",
) -> dict:
    """``forward_layers_backward`` + IntegerSGD: returns updated fw params.

    Same jnp dropout/pool backwards, but the weight gradient is consumed
    where it is produced — the ``fuse_opt`` path applies the IntegerSGD
    step in the grad_W kernel's flush (``layers.conv_update`` /
    ``layers.linear_update``), so the full-size grad_W never reaches HBM.
    Bitwise identical to backward-then-``optimizer.apply_tree``.
    """
    g = delta_fw
    if "dropout" in cache:
        g = layers.dropout_backward(cache["dropout"], g)
    if "pool" in cache:
        g = layers.maxpool_backward(cache["pool"], g)
    if spec.kind == "conv":
        _, new_fw = layers.conv_update(
            params["fw"], cache["conv"], g, opt_state,
            z_star=cache["z_star"], alpha_inv=spec.alpha_inv,
            backend=backend,
        )
    else:
        _, new_fw = layers.linear_update(
            params["fw"], cache["linear"], g, opt_state,
            z_star=cache["z_star"], alpha_inv=spec.alpha_inv,
            backend=backend,
        )
    return new_fw


# ---------------------------------------------------------------------------
# Learning layers
# ---------------------------------------------------------------------------


def learning_layers(
    params: dict, spec: BlockSpec, a: jax.Array
) -> tuple[jax.Array, dict]:
    """ŷ_l = scale(pool·flatten(a_l) @ W^il); returns local prediction."""
    cache: dict[str, Any] = {}
    if spec.kind == "conv":
        a, cache["avgpool"] = layers.avgpool_to(a, spec.d_lr)
        a, cache["flat_shape"] = layers.flatten_forward(a)
    z, cache["linear"] = layers.linear_forward(params["lr"], a)
    sf = scaling.linear_scale_factor(a.shape[-1])
    y_hat = scaling.scale_forward(z, sf)
    return y_hat, cache


def learning_layers_backward(
    params: dict, spec: BlockSpec, cache: dict, grad_loss: jax.Array
) -> tuple[jax.Array, dict]:
    """Backward from ∇L_l; returns (δ_l^fw at the block output, lr grads)."""
    g = scaling.scale_backward(grad_loss)  # STE through the output scaling
    g, grads = layers.linear_backward(params["lr"], cache["linear"], g)
    if spec.kind == "conv":
        g = layers.flatten_backward(cache["flat_shape"], g)
        g = layers.avgpool_to_backward(cache["avgpool"], g)
    return g, grads


# ---------------------------------------------------------------------------
# Output layers (final classifier — trained with the global RSS gradient)
# ---------------------------------------------------------------------------


def init_output(key: jax.Array, in_features: int, num_classes: int) -> dict:
    return layers.linear_init(key, in_features, num_classes)


def output_forward(params: dict, a: jax.Array) -> tuple[jax.Array, dict]:
    cache: dict[str, Any] = {}
    if a.ndim > 2:
        a, cache["flat_shape"] = layers.flatten_forward(a)
    z, cache["linear"] = layers.linear_forward(params, a)
    sf = scaling.linear_scale_factor(a.shape[-1])
    return scaling.scale_forward(z, sf), cache


def output_backward(params: dict, cache: dict, grad_loss: jax.Array) -> dict:
    g = scaling.scale_backward(grad_loss)
    _, grads = layers.linear_backward(params, cache["linear"], g)
    return grads


def local_gradient(y_hat: jax.Array, y_onehot: jax.Array) -> jax.Array:
    """∇L_l = ŷ_l − y (RSS)."""
    return rss_grad(y_hat, y_onehot)
