"""Unified backward dispatcher for the integer gradient paths.

Every hand-derived backward in ``repro.core`` funnels through this module,
the backward mirror of the forward dispatchers:

  * ``linear_grads`` — the two gradient matmuls of an IntegerLinear layer;
  * ``conv_grads``   — the two streamed conv gradients;
  * ``linear_weight_update`` / ``conv_weight_update`` — grad_x plus the
    *updated weight*: the IntegerSGD step is applied in the grad_W
    kernels' flush (``fuse_opt``), so grad_W never materialises in HBM
    when its only consumer is the optimiser.

Both take the *raw* block gradient δ (after the jnp dropout/pool
backwards, which stay outside the kernels) plus the cached pre-ReLU
``z_star``, and own the NITRO-ReLU-derivative + scaling-STE step that
precedes the gradient matmuls: it runs as a *prologue inside* the
gradient kernels — each δ tile/band is masked in VMEM just before it
enters the MXU, so the full-size post-ReLU-bwd δ tensor never
round-trips through HBM (on the reference backend the oracle composes
the same ops in jnp).

``z_star=None`` selects the no-activation backward (learning/output
layers: scaling STE only, which is the identity) — plain integer
matmuls on any backend.  ``masked_delta`` followed by that route is the
unfused composition every fused δ path is tested against
(``tests/_gradcheck.py``).

Backend vocabulary is ``nitro_matmul.ops.resolve_backend``'s
(``pallas | interpret | reference | auto``).
"""

from __future__ import annotations

import jax

from repro.core import optimizer as opt
from repro.core.numerics import int_matmul
from repro.kernels.autotune.tiles import TileConfig
from repro.kernels.nitro_conv import ops as conv_ops
from repro.kernels.nitro_matmul import ops as mm_ops


def linear_grads(
    x: jax.Array,
    w: jax.Array,
    delta: jax.Array,
    *,
    z_star: jax.Array | None = None,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> tuple[jax.Array, jax.Array]:
    """IntegerLinear backward: returns ``(grad_x, grad_w)``.

    ``grad_w = xᵀ @ f(δ)`` and ``grad_x = f(δ) @ wᵀ`` where ``f`` is the
    NITRO-ReLU-bwd/STE when ``z_star`` is given (fused into the kernel
    prologues) and the identity otherwise.  ``tiles`` overrides
    the kernel tile sizes (``None`` → per-gradient autotune-cache lookup).
    """
    if z_star is None:
        # No-activation backward: two plain integer matmuls — already a
        # single XLA op each, nothing to fuse.
        return int_matmul(delta, w.T), int_matmul(x.T, delta)
    grad_w = mm_ops.grad_w_matmul(
        x, delta, z_star, alpha_inv=alpha_inv, backend=backend, tiles=tiles
    )
    grad_x = mm_ops.grad_x_matmul(
        delta, z_star, w, alpha_inv=alpha_inv, backend=backend, tiles=tiles
    )
    return grad_x, grad_w


def conv_grads(
    x: jax.Array,
    w: jax.Array,
    delta: jax.Array,
    *,
    z_star: jax.Array | None = None,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> tuple[jax.Array, jax.Array]:
    """IntegerConv2D backward: returns ``(grad_x, grad_w)``.

    Both gradients stream their patches; with ``z_star`` the
    ReLU-bwd/STE prologue masks the δ bands inside the streaming kernels,
    so conv blocks never materialise the post-ReLU-bwd δ in HBM at all.
    """
    grad_w = conv_ops.conv_grad_w(
        x, delta, kernel_size=w.shape[0],
        z_star=z_star, alpha_inv=alpha_inv,
        backend=backend, tiles=tiles,
    )
    grad_x = conv_ops.conv_grad_x(
        delta, w,
        z_star=z_star, alpha_inv=alpha_inv,
        backend=backend, tiles=tiles,
    )
    return grad_x, grad_w


# ---------------------------------------------------------------------------
# Fused weight updates: grad_W + IntegerSGD in one kernel pass (fuse_opt)
# ---------------------------------------------------------------------------


def linear_weight_update(
    x: jax.Array,
    w: jax.Array,
    delta: jax.Array,
    opt_state: opt.IntegerSGDState,
    *,
    z_star: jax.Array | None = None,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> tuple[jax.Array, jax.Array]:
    """IntegerLinear backward + optimiser: returns ``(grad_x, w_new)``.

    The fused path runs ``grad_w_opt_matmul`` — the IntegerSGD update is
    the grad_W kernel's flush epilogue, so grad_W never exists in HBM.
    Without ``z_star`` the materialised gradient is composed with
    ``optimizer.apply_update`` — bitwise identical, because integer
    floor-div over an order-exact int32 accumulation is exact.
    """
    if z_star is None:
        grad_x, grad_w = linear_grads(
            x, w, delta, alpha_inv=alpha_inv, backend=backend, tiles=tiles,
        )
        return grad_x, opt.apply_update(w, grad_w, opt_state)
    w_new = mm_ops.grad_w_opt_matmul(
        x, delta, z_star, w, opt_state.gamma_inv, opt_state.eta_inv,
        alpha_inv=alpha_inv, backend=backend, tiles=tiles,
    )
    grad_x = mm_ops.grad_x_matmul(
        delta, z_star, w, alpha_inv=alpha_inv, backend=backend, tiles=tiles
    )
    return grad_x, w_new


def conv_weight_update(
    x: jax.Array,
    w: jax.Array,
    delta: jax.Array,
    opt_state: opt.IntegerSGDState,
    *,
    z_star: jax.Array | None = None,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> tuple[jax.Array, jax.Array]:
    """IntegerConv2D backward + optimiser: returns ``(grad_x, w_new)``.

    The IntegerSGD step runs in the grad_W kernel's flush
    (``conv_grad_w_opt``).  Without ``z_star`` the gradient is composed
    with ``optimizer.apply_update`` instead.
    """
    if z_star is None:
        grad_x, grad_w = conv_grads(
            x, w, delta, alpha_inv=alpha_inv, backend=backend, tiles=tiles,
        )
        return grad_x, opt.apply_update(w, grad_w, opt_state)
    w_new = conv_ops.conv_grad_w_opt(
        x, delta, w, opt_state.gamma_inv, opt_state.eta_inv,
        kernel_size=w.shape[0], z_star=z_star, alpha_inv=alpha_inv,
        backend=backend, tiles=tiles,
    )
    grad_x = conv_ops.conv_grad_x(
        delta, w, z_star=z_star, alpha_inv=alpha_inv,
        backend=backend, tiles=tiles,
    )
    return grad_x, w_new
