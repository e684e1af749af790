"""Unit + property tests for the integer arithmetic primitives."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import numerics


class TestFloorDiv:
    def test_rounds_toward_neg_infinity(self):
        # The paper's ⌊·⌋ is mathematical floor, not C truncation.
        assert int(numerics.floor_div(jnp.int32(-7), 2)) == -4
        assert int(numerics.floor_div(jnp.int32(7), 2)) == 3
        assert int(numerics.floor_div(jnp.int32(-1), 512)) == -1

    @given(st.integers(-(2**20), 2**20), st.integers(1, 2**16))
    @settings(max_examples=200, deadline=None)
    def test_matches_python_floor(self, x, d):
        assert int(numerics.floor_div(jnp.int32(x), d)) == x // d


I32_MIN, I32_MAX = -(2 ** 31), 2 ** 31 - 1
# γ_inv, η_inv and AF-scaled γ_inv^fw values the recipes use (327680 =
# 512 × AF, 983040 after one plateau), small divisors, powers of two and
# the ends of the divisor domain.
DIVISORS = [1, 2, 3, 7, 512, 3000, 7500, 19000, 25000, 327680, 983040,
            2 ** 30, 2 ** 30 + 1, 2 ** 31 - 1]

_div_by = jax.jit(lambda x, d: numerics.floor_div_by(x, numerics.reciprocal(d)))


def _edge_numerators(d: int) -> np.ndarray:
    """INT32_MIN/MAX, 0, ±1, and every k·d and k·d − 1 for 64 k around
    zero and 64 k at each end of the int32 range."""
    ks = range(-64, 65)
    top, bottom = I32_MAX // d, I32_MIN // d
    ks = [*ks, *range(top - 64, top + 2), *range(bottom - 1, bottom + 65)]
    xs = {I32_MIN, I32_MAX, 0, 1, -1}
    xs.update(x for k in ks for x in (k * d, k * d - 1))
    return np.asarray(sorted(x for x in xs if I32_MIN <= x <= I32_MAX),
                      np.int64)


class TestFloorDivBy:
    """``floor_div_by(x, reciprocal(d))`` ≡ ``jnp.floor_divide(x, d)``,
    bitwise, for every int32 x and every d ≥ 1."""

    @pytest.mark.parametrize("d", DIVISORS)
    def test_exact_at_multiples_and_range_ends(self, d):
        xs = _edge_numerators(d)
        got = np.asarray(_div_by(jnp.asarray(xs, jnp.int32), jnp.int32(d)))
        want = np.asarray(jnp.floor_divide(jnp.asarray(xs, jnp.int32), d))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got, xs // d)  # Python's floor

    @pytest.mark.parametrize("d", DIVISORS)
    def test_reciprocal_is_the_closed_form(self, d):
        """m = ⌊2^(31+l)/d⌋ + 1 and sh = l = ⌈log₂ d⌉, in uint32; the
        prologue computes them elementwise, so a stacked vector of
        divisors gives each its own pair."""
        l = (d - 1).bit_length()
        r = numerics.reciprocal(jnp.asarray([d, 3000], jnp.int32))
        assert r.m.dtype == r.sh.dtype == jnp.uint32
        assert int(r.m[0]) == 2 ** (31 + l) // d + 1
        assert int(r.sh[0]) == l
        assert int(r.m[1]) == 2 ** (31 + 12) // 3000 + 1

    @given(st.integers(I32_MIN, I32_MAX), st.integers(1, I32_MAX))
    @settings(max_examples=300, deadline=None)
    def test_matches_python_floor(self, x, d):
        assert int(_div_by(jnp.int32(x), jnp.int32(d))) == x // d

    def test_traced_divisor_under_jit(self):
        """d traced, as in the train step (γ_inv lives in the optimiser
        state): one trace serves every divisor, and each is exact."""
        traces = []

        @jax.jit
        def div(x, d):
            traces.append(None)
            return numerics.floor_div_by(x, numerics.reciprocal(d))

        rng = np.random.default_rng(0)
        xs = rng.integers(I32_MIN, I32_MAX, 4096, endpoint=True)
        xs = np.concatenate([xs, [I32_MIN, I32_MAX, 0, 1, -1]])
        for d in DIVISORS:
            got = np.asarray(div(jnp.asarray(xs, jnp.int32), jnp.int32(d)))
            np.testing.assert_array_equal(got, xs // d, err_msg=f"d={d}")
        assert len(traces) == 1


class TestIntMatmul:
    @given(
        st.integers(1, 8), st.integers(1, 8), st.integers(1, 8),
        st.integers(0, 2**31 - 1),
    )
    @settings(max_examples=50, deadline=None)
    def test_matches_numpy_int64(self, m, k, n, seed):
        rng = np.random.default_rng(seed)
        a = rng.integers(-127, 128, (m, k)).astype(np.int32)
        w = rng.integers(-127, 128, (k, n)).astype(np.int32)
        got = np.asarray(numerics.int_matmul(jnp.asarray(a), jnp.asarray(w)))
        want = (a.astype(np.int64) @ w.astype(np.int64)).astype(np.int32)
        np.testing.assert_array_equal(got, want)

    def test_accumulates_in_int32(self):
        a = jnp.full((1, 1000), 127, jnp.int32)
        w = jnp.full((1000, 1), 127, jnp.int32)
        out = numerics.int_matmul(a, w)
        assert out.dtype == jnp.int32
        assert int(out[0, 0]) == 127 * 127 * 1000


class TestIsqrt:
    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=300, deadline=None)
    def test_matches_math_isqrt(self, n):
        assert int(numerics.isqrt(jnp.int32(n))) == math.isqrt(n)

    def test_jit_and_vmap(self):
        ns = jnp.arange(0, 100, dtype=jnp.int32)
        got = jax.jit(jax.vmap(numerics.isqrt))(ns)
        want = jnp.asarray([math.isqrt(i) for i in range(100)], jnp.int32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestBitwidthBound:
    def test_paper_example(self):
        # §3.2: b_a = 8, b_W = 8 → b_z = 15 + log2(M)
        assert numerics.bitwidth_bound(8, 8, 1024) == 15 + 10

    def test_assert_int_rejects_float(self):
        with pytest.raises(TypeError):
            numerics.assert_int(jnp.zeros((2,), jnp.float32))
