"""Integer-only arithmetic primitives for NITRO-D.

Every operation in this module is closed over the integers: inputs and
outputs are integer JAX arrays and no floating-point intermediate is ever
materialised.  The paper's ``⌊·⌋`` is floor division (rounds toward −∞),
which is exactly ``jnp.floor_divide`` / Python's ``//`` — NOT C truncation.

The carrying dtype is int32 (XLA integer dot requires ≥32-bit accumulation);
logical bit-width invariants (int8 activations, int16 weights) are asserted
by the test-suite, not by the dtype system, mirroring the paper's §4.4
discussion.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

INT_DTYPE = jnp.int32
# Operational range of NITRO-ReLU / int8 activations (paper §3.2).
ACT_MIN = -127
ACT_MAX = 127


def to_int(x) -> jax.Array:
    """Cast to the carrying integer dtype (int32)."""
    return jnp.asarray(x, dtype=INT_DTYPE)


def floor_div(x: jax.Array, d) -> jax.Array:
    """Integer floor division ⌊x/d⌋ — rounds toward −∞ like the paper."""
    return jnp.floor_divide(x, d)


class Reciprocal(NamedTuple):
    """Integer reciprocal of a divisor d ≥ 1, from ``reciprocal``.

    ``⌊u/d⌋ = mulhi32(2u, m) >> sh`` for every 0 ≤ u < 2³¹, with
    ``sh = ⌈log₂ d⌉`` and ``m = ⌊2^(31+sh)/d⌋ + 1`` (both uint32).
    """

    m: jax.Array
    sh: jax.Array


def reciprocal(d) -> Reciprocal:
    """The scalar prologue of ``floor_div_by``: d ≥ 1 (int32, any shape).

    Division by an invariant integer (Granlund & Montgomery, PLDI 1994):
    m·d lies in (2^(31+l), 2^(31+l) + 2^l] for l = ⌈log₂ d⌉, so
    ⌊u·m / 2^(31+l)⌋ = ⌊u/d⌋ for every u < 2³¹.  m ∈ [2³¹+1, 2³²) is
    computed by a restoring division unrolled over its 32 quotient bits
    (bit 31 is always set) — elementwise over ``d``, so a step's divisors
    stacked into one vector take one small op.  The body multiplies 2u,
    not u, so the shift is l, not l − 1, and d = 1 needs no special case.
    """
    d = jnp.asarray(d, INT_DTYPE).astype(jnp.uint32)
    sh = jnp.uint32(32) - jax.lax.clz(d - 1)  # ⌈log₂ d⌉; clz(0) = 32
    r = (jnp.uint32(1) << sh) - d  # remainder after quotient bit 31; < d
    q = jnp.full_like(d, 1 << 31)
    for bit in range(30, -1, -1):
        r = r << 1  # r < d < 2³¹: no overflow
        take = r >= d
        r = jnp.where(take, r - d, r)
        q = q | (take.astype(jnp.uint32) << bit)
    return Reciprocal(m=q + 1, sh=sh)


def _mulhi32(a: jax.Array, m: jax.Array) -> jax.Array:
    """High 32 bits of the 64-bit product of two uint32, from 16-bit halves."""
    a_lo, a_hi = a & 0xFFFF, a >> 16
    m_lo, m_hi = m & 0xFFFF, m >> 16
    lo = a_lo * m_lo
    mid_a = a_hi * m_lo
    mid_m = a_lo * m_hi
    carry = ((lo >> 16) + (mid_a & 0xFFFF) + (mid_m & 0xFFFF)) >> 16
    return a_hi * m_hi + (mid_a >> 16) + (mid_m >> 16) + carry


def floor_div_by(x: jax.Array, r: Reciprocal) -> jax.Array:
    """⌊x/d⌋ for int32 ``x`` and the divisor d that ``r = reciprocal(d)``
    was computed from — bitwise equal to ``floor_div(x, d)`` for every
    int32 x and every d ≥ 1, with no integer divide per element.

    Negative x folds onto u = −x − 1 = ~x ≥ 0, whose quotient unfolds as
    ⌊x/d⌋ = ~⌊u/d⌋; ``x >> 31`` is the all-ones mask of the sign.
    """
    s = x >> 31
    u = jax.lax.bitcast_convert_type(x ^ s, jnp.uint32)
    q = _mulhi32(u << 1, r.m) >> r.sh
    return jax.lax.bitcast_convert_type(q, INT_DTYPE) ^ s


def int_matmul(a: jax.Array, w: jax.Array) -> jax.Array:
    """Integer matrix product with int32 accumulation.

    ``preferred_element_type=int32`` is the XLA contract for int8-style
    accumulate-in-int32 semantics; on TPU this hits the MXU integer mode.
    """
    return jax.lax.dot_general(
        a, w,
        dimension_numbers=(((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=INT_DTYPE,
    )


def clip_act(x: jax.Array) -> jax.Array:
    """Clamp to the NITRO operational range [-127, 127]."""
    return jnp.clip(x, ACT_MIN, ACT_MAX)


def isqrt(n: jax.Array) -> jax.Array:
    """Integer square root ⌊√n⌋ via Newton iteration, pure integer.

    Used by the integer Kaiming initialiser (Appendix B.1).  Converges in
    ≤ 16 iterations for int32 inputs; we run a fixed 20 to stay jit-stable.
    """
    n = to_int(n)

    def body(_, x):
        # Newton step: x <- (x + n // x) // 2, guarded against x == 0.
        x_safe = jnp.maximum(x, 1)
        nxt = floor_div(x_safe + floor_div(n, x_safe), 2)
        return jnp.where(n > 0, jnp.minimum(x, nxt), 0)

    # start from above √n but below the int32-overflow edge: isqrt of any
    # int32 is ≤ 46340, so 46341 is a safe upper seed (x + n//x < 2³¹)
    x0 = jnp.clip(n, 1, 46341)
    out = jax.lax.fori_loop(0, 25, body, x0)
    return jnp.where(n > 0, out, 0)


def bitwidth_bound(x_bits: int, w_bits: int, fan_in: int) -> int:
    """Paper §3.2 upper bound: b_z = x_bits + w_bits - 1 + ceil(log2(fan_in))."""
    return x_bits + w_bits - 1 + max(int(fan_in - 1).bit_length(), 0)


def assert_int(x: jax.Array, name: str = "tensor") -> None:
    if not jnp.issubdtype(x.dtype, jnp.integer):
        raise TypeError(f"{name} must be integer, got {x.dtype}")
