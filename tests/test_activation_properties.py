"""Property-based tests of the NITRO-ReLU paper identities (§3.2).

Hypothesis-driven: ``segment_means`` / ``mu_int8`` / ``nitro_relu`` /
``nitro_relu_backward`` must satisfy their defining piecewise formulas
across the ``alpha_inv`` range and int8/int32 carrying dtypes, and
``check_alpha_inv`` must enforce its ValueError contract.

Ground truth is pure-Python integer arithmetic (``//`` is the paper's
⌊·⌋), evaluated elementwise — independent of jnp, so these tests anchor
the jnp ops the kernels in turn anchor to.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.activations import (
    DEFAULT_ALPHA_INV,
    mu_int8,
    nitro_relu,
    nitro_relu_backward,
    segment_means,
)
from repro.core.numerics import ACT_MAX, ACT_MIN
from repro.kernels.nitro_matmul.ops import check_alpha_inv

alphas = st.integers(1, 127)
z_values = st.integers(-400, 400)      # straddles both saturation knees
grads = st.integers(-(2 ** 15), 2 ** 15)


def _relu_scalar(z: int, alpha_inv: int) -> int:
    """The §3.2 four-segment definition, in pure Python ints."""
    mu = mu_int8(alpha_inv)
    if z < ACT_MIN:
        return ACT_MIN // alpha_inv - mu
    if z < 0:
        return z // alpha_inv - mu
    if z <= ACT_MAX:
        return z - mu
    return ACT_MAX - mu


def _relu_bwd_scalar(z: int, g: int, alpha_inv: int) -> int:
    """Piecewise derivative: 0 / ⌊g/α_inv⌋ / g / 0."""
    if z < ACT_MIN or z > ACT_MAX:
        return 0
    if z < 0:
        return g // alpha_inv
    return g


class TestSegmentMeans:
    @given(alphas)
    @settings(max_examples=40, deadline=None)
    def test_defining_formulas(self, alpha_inv):
        m0, m1, m2, m3 = segment_means(alpha_inv)
        assert m0 == -127 // alpha_inv
        assert m1 == -127 // (2 * alpha_inv)
        assert (m2, m3) == (63, 127)

    @given(alphas)
    @settings(max_examples=40, deadline=None)
    def test_ordering_and_mu(self, alpha_inv):
        """m0 ≤ m1 < 0 < m2 < m3, and μ is their floored integer mean."""
        m = segment_means(alpha_inv)
        assert m[0] <= m[1] < 0 < m[2] < m[3]
        assert mu_int8(alpha_inv) == sum(m) // 4

    def test_default_alpha(self):
        assert mu_int8() == mu_int8(DEFAULT_ALPHA_INV)


class TestNitroReluForward:
    @given(z_values, alphas)
    @settings(max_examples=60, deadline=None)
    def test_matches_piecewise_definition(self, z, alpha_inv):
        got = nitro_relu(jnp.asarray([z], jnp.int32), alpha_inv)
        assert int(got[0]) == _relu_scalar(z, alpha_inv)

    @given(z_values, z_values, alphas)
    @settings(max_examples=60, deadline=None)
    def test_monotone_nondecreasing(self, z1, z2, alpha_inv):
        lo, hi = min(z1, z2), max(z1, z2)
        out = nitro_relu(jnp.asarray([lo, hi], jnp.int32), alpha_inv)
        assert int(out[0]) <= int(out[1])

    @given(z_values, alphas)
    @settings(max_examples=60, deadline=None)
    def test_saturation_clamps(self, z, alpha_inv):
        """Outside [-127, 127] the output equals the knee's output."""
        knee = min(max(z, ACT_MIN), ACT_MAX)
        out = nitro_relu(jnp.asarray([z, knee], jnp.int32), alpha_inv)
        assert int(out[0]) == int(out[1])

    @given(st.integers(-127, 0), st.integers(-127, 0), alphas)
    @settings(max_examples=60, deadline=None)
    def test_leaky_segment_realises_floor_slope(self, z1, z2, alpha_inv):
        """On the leaky segment the forward difference is exactly the
        difference of the floors — the 1/α_inv slope the backward mirrors."""
        out = nitro_relu(jnp.asarray([z1, z2], jnp.int32), alpha_inv)
        assert int(out[0]) - int(out[1]) == z1 // alpha_inv - z2 // alpha_inv

    @given(st.integers(-127, 400), st.integers(2, 127))
    @settings(max_examples=60, deadline=None)
    def test_output_fits_int8_for_alpha_ge_2(self, z, alpha_inv):
        """The int8-activation claim: for α_inv ≥ 2 every output lies in
        [-127, 127].  (α_inv = 1 is the documented edge: μ = −1 pushes the
        positive saturation to 128.)"""
        out = int(nitro_relu(jnp.asarray([z], jnp.int32), alpha_inv)[0])
        assert -127 <= out <= 127

    @given(st.integers(-127, 127), st.integers(2, 127))
    @settings(max_examples=60, deadline=None)
    def test_int8_dtype_agrees_with_int32(self, z, alpha_inv):
        """Computing in int8 ≡ computing in int32 then narrowing, wherever
        the result fits int8 (which test_output_fits_int8 guarantees)."""
        got8 = nitro_relu(jnp.asarray([z], jnp.int8), alpha_inv)
        got32 = nitro_relu(jnp.asarray([z], jnp.int32), alpha_inv)
        assert got8.dtype == jnp.int8
        assert int(got8[0]) == int(got32[0])


class TestNitroReluBackward:
    @given(z_values, grads, alphas)
    @settings(max_examples=60, deadline=None)
    def test_matches_piecewise_definition(self, z, g, alpha_inv):
        got = nitro_relu_backward(
            jnp.asarray([z], jnp.int32), jnp.asarray([g], jnp.int32), alpha_inv
        )
        assert int(got[0]) == _relu_bwd_scalar(z, g, alpha_inv)

    @given(z_values, alphas)
    @settings(max_examples=40, deadline=None)
    def test_zero_gradient_maps_to_zero(self, z, alpha_inv):
        got = nitro_relu_backward(
            jnp.asarray([z], jnp.int32), jnp.zeros((1,), jnp.int32), alpha_inv
        )
        assert int(got[0]) == 0

    @given(st.integers(1, 2 ** 10), alphas)
    @settings(max_examples=40, deadline=None)
    def test_saturated_segments_block_gradient(self, g, alpha_inv):
        z = jnp.asarray([ACT_MIN - 1, ACT_MAX + 1, -1000, 1000], jnp.int32)
        got = nitro_relu_backward(z, jnp.full((4,), g, jnp.int32), alpha_inv)
        np.testing.assert_array_equal(np.asarray(got), np.zeros(4))

    @given(st.integers(0, 127), grads, alphas)
    @settings(max_examples=40, deadline=None)
    def test_identity_segment_passes_gradient(self, z, g, alpha_inv):
        got = nitro_relu_backward(
            jnp.asarray([z], jnp.int32), jnp.asarray([g], jnp.int32), alpha_inv
        )
        assert int(got[0]) == g

    @given(st.integers(-127, -1), grads, alphas)
    @settings(max_examples=40, deadline=None)
    def test_leaky_segment_floors_like_the_forward(self, z, g, alpha_inv):
        """The backward's ⌊g/α_inv⌋ is the same floor the forward slope
        realises — the chain-rule consistency the fused prologue relies on."""
        got = nitro_relu_backward(
            jnp.asarray([z], jnp.int32), jnp.asarray([g], jnp.int32), alpha_inv
        )
        assert int(got[0]) == g // alpha_inv

    @given(st.integers(-127, 127), st.integers(-127, 127), alphas)
    @settings(max_examples=40, deadline=None)
    def test_int8_dtype_agrees_with_int32(self, z, g, alpha_inv):
        """int8 z*/g inputs ≡ the int32 computation narrowed (the result
        ⌊g/α⌋ or g or 0 always fits int8 when g does)."""
        got8 = nitro_relu_backward(
            jnp.asarray([z], jnp.int8), jnp.asarray([g], jnp.int8), alpha_inv
        )
        got32 = nitro_relu_backward(
            jnp.asarray([z], jnp.int32), jnp.asarray([g], jnp.int32), alpha_inv
        )
        assert got8.dtype == jnp.int8
        assert int(got8[0]) == int(got32[0])


class TestCheckAlphaInv:
    @given(st.integers(-127, 0))
    @settings(max_examples=30, deadline=None)
    def test_nonpositive_raises_with_relu(self, bad):
        with pytest.raises(ValueError, match="alpha_inv"):
            check_alpha_inv(bad, True)

    @given(st.integers(-127, 127))
    @settings(max_examples=30, deadline=None)
    def test_normalised_to_one_without_relu(self, any_value):
        """apply_relu=False: the value is unused and normalised, so frozen
        no-activation layers can carry alpha_inv=0 without recompiles."""
        assert check_alpha_inv(any_value, False) == 1

    @given(alphas)
    @settings(max_examples=30, deadline=None)
    def test_positive_passes_through_as_int(self, alpha_inv):
        out = check_alpha_inv(alpha_inv, True)
        assert out == alpha_inv and isinstance(out, int)

    def test_float_input_rejected_by_contract(self):
        """Activations reject float tensors outright (integer-only)."""
        with pytest.raises(TypeError, match="integer"):
            nitro_relu(jnp.zeros((2,), jnp.float32))
        with pytest.raises(TypeError, match="integer"):
            nitro_relu_backward(
                jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.float32)
            )
