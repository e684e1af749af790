"""Pure-jnp oracle for the fused NITRO matmul kernel.

Composes the three reference ops the kernel fuses — integer matmul, NITRO
Scaling Layer, NITRO-ReLU — exactly as `repro.core` defines them.  The
kernel must match this bit-for-bit on every shape/dtype swept by the tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.activations import nitro_relu, nitro_relu_backward
from repro.core.numerics import int_matmul
from repro.core.scaling import scale_backward, scale_forward


def nitro_matmul_ref(
    x: jax.Array,
    w: jax.Array,
    *,
    sf: int,
    alpha_inv: int = 10,
    apply_relu: bool = True,
    out_dtype=jnp.int32,
    operand_dtype: str = "int32",
) -> jax.Array:
    if operand_dtype == "int8":
        # int8-operand path: skip the int32 lift — ``int_matmul``'s
        # ``preferred_element_type=int32`` accumulates int8 operands into
        # the same int32 values bit-for-bit.
        if not (x.dtype == jnp.int8 and w.dtype == jnp.int8):
            raise ValueError(
                f"operand_dtype='int8' requires int8 operands, got "
                f"{x.dtype}/{w.dtype}"
            )
        z = int_matmul(x, w)
    else:
        z = int_matmul(x.astype(jnp.int32), w.astype(jnp.int32))
    z_star = scale_forward(z, sf)
    if apply_relu:
        z_star = nitro_relu(z_star, alpha_inv)
    return z_star.astype(out_dtype)


def nitro_matmul_fwd_ref(
    x: jax.Array,
    w: jax.Array,
    *,
    sf: int,
    alpha_inv: int = 10,
    out_dtype=jnp.int32,
) -> tuple[jax.Array, jax.Array]:
    """Training-forward oracle: ``(a, z_star)``, matching ``nitro_matmul_fwd``.

    ``z_star`` is always int32 — it is the tensor ``core.blocks`` caches for
    the NITRO-ReLU/STE backward, so its dtype must match ``scale_forward``.
    """
    z = int_matmul(x.astype(jnp.int32), w.astype(jnp.int32))
    z_star = scale_forward(z, sf)
    a = nitro_relu(z_star, alpha_inv)
    return a.astype(out_dtype), z_star


def masked_delta(delta: jax.Array, z_star: jax.Array, alpha_inv: int) -> jax.Array:
    """The backward prologue the grad kernels fuse, composed from the
    reference ops: NITRO-ReLU derivative then the scaling STE (identity).

    The single jnp definition of that composition — the grad oracles
    below, the autotuner's pre-masked operands and the tests' unfused δ
    oracle (``masked_delta`` then ``grad_ops``' ``z_star=None`` route) all
    share it, so the fused/unfused parity oracle cannot drift apart across
    modules.
    """
    return scale_backward(nitro_relu_backward(z_star, delta, alpha_inv))


def nitro_matmul_grad_w_ref(
    x: jax.Array,
    delta: jax.Array,
    z_star: jax.Array,
    *,
    alpha_inv: int = 10,
) -> jax.Array:
    """Weight-gradient oracle: ``xᵀ @ relu_bwd(z*, δ)`` — matches
    ``nitro_matmul_grad_w`` bit-for-bit (int32 accumulation is order-exact)."""
    g = masked_delta(delta.astype(jnp.int32), z_star, alpha_inv)
    return int_matmul(x.astype(jnp.int32).T, g)


def nitro_matmul_grad_x_ref(
    delta: jax.Array,
    z_star: jax.Array,
    w: jax.Array,
    *,
    alpha_inv: int = 10,
) -> jax.Array:
    """Input-gradient oracle: ``relu_bwd(z*, δ) @ wᵀ`` — matches
    ``nitro_matmul_grad_x`` bit-for-bit."""
    g = masked_delta(delta.astype(jnp.int32), z_star, alpha_inv)
    return int_matmul(g, w.astype(jnp.int32).T)
