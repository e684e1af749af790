"""Exact int32 dots from int8 MXU passes (the limb split).

The TPU MXU multiplies int8 operands into an int32 accumulator; it has no
int32×int32 mode.  An int32 operand ``a`` is therefore split into signed
base-256 digits ("limbs")::

    a ≡ Σ_i d_i · 2^(8i)   (mod 2³²),   d_i ∈ [-128, 127],  i = 0..3

and a product of two split operands is recombined from int8 dots::

    a·b ≡ Σ_{i+j<4} (d_i·e_j) << 8(i+j)   (mod 2³²)

Pairs with ``i + j ≥ 4`` carry a factor 2³² and vanish.  Every add and
shift wraps in int32, so the result equals the int32 dot bit for bit,
wrap-around included — the same modular algebra as the limb planes of
``parallel.compress``.

The limb count of an operand is a static fact: an int8 array is one limb
(it *is* its own digit); anything wider takes four.  Callers that can
prove a wider array fits int8 (a NITRO-ReLU activation, by
``core.activations.relu_fits_int8``) narrow it to int8 before the kernel,
so the count is always read off the dtype.

The helpers are plain ``jnp`` and run the same inside a Pallas kernel body
(on VMEM tiles) and in XLA (to pre-split a weight once per call).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Limbs of a full int32 operand.
INT32_LIMBS = 4


def limb_count(dtype) -> int:
    """Static limb count of an operand: 1 for int8, 4 for anything wider."""
    return 1 if jnp.dtype(dtype) == jnp.int8 else INT32_LIMBS


def split_limbs(a: jax.Array, n: int) -> list[jax.Array]:
    """Split ``a`` into ``n`` signed int8 limbs, least significant first.

    ``n == 1`` narrows (an int8 ``a`` passes through); the caller
    guarantees the values fit.  For ``n == 4`` each limb is the
    sign-extended low byte of the running remainder, and the remainder
    drops that digit and shifts right by 8 — every step wraps in int32,
    which is harmless because the limbs are only ever recombined
    mod 2³².
    """
    if n == 1:
        return [a if a.dtype == jnp.int8 else a.astype(jnp.int8)]
    r = a.astype(jnp.int32)
    limbs = []
    for i in range(n):
        d = jax.lax.shift_right_arithmetic(jax.lax.shift_left(r, 24), 24)
        limbs.append(d.astype(jnp.int8))
        if i + 1 < n:
            r = jax.lax.shift_right_arithmetic(r - d, 8)
    return limbs


def stack_limbs(a: jax.Array) -> jax.Array:
    """``a`` as a stacked (L, *a.shape) int8 array, L = ``limb_count``.

    Weights are split once per call in XLA and streamed to the kernel as
    int8 limb planes (the same bytes as the int32 array), so the kernel
    never re-splits a weight tile on every grid step.
    """
    return jnp.stack(split_limbs(a, limb_count(a.dtype)))


def planes(stacked) -> list:
    """The limb list of a ``stack_limbs`` array or of a VMEM ref holding
    a block of one (its leading axis is the limb axis)."""
    return [stacked[i] for i in range(stacked.shape[0])]


def limb_dot(a_limbs, b_limbs, dimension_numbers) -> jax.Array:
    """int32 ``dot_general`` of two limb-split operands, exact mod 2³².

    ``a_limbs``/``b_limbs`` are lists of int8 arrays (from ``split_limbs``
    or the planes of ``stack_limbs``).  Issues one int8×int8→int32 dot per
    limb pair with ``i + j < 4`` — 1 for int8·int8, 4 for int8·int32, 10
    for int32·int32 — and recombines them with wrapping left shifts.
    """
    out = None
    for s in range(INT32_LIMBS):
        level = None
        for i in range(min(s + 1, len(a_limbs))):
            j = s - i
            if j >= len(b_limbs):
                continue
            d = jax.lax.dot_general(
                a_limbs[i], b_limbs[j],
                dimension_numbers=dimension_numbers,
                preferred_element_type=jnp.int32,
            )
            level = d if level is None else level + d
        if level is None:
            continue
        if s:
            level = jax.lax.shift_left(level, jnp.int32(8 * s))
        out = level if out is None else out + level
    return out
