"""Pallas kernel validation: interpret-mode kernel vs pure-jnp oracle,
swept over shapes, dtypes, scale factors and tile sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from _gradcheck import assert_bitwise_equal, kernel_backend  # noqa: F401
from repro.core.scaling import conv_scale_factor, linear_scale_factor
from repro.kernels.autotune.tiles import TileConfig
from repro.kernels.integer_sgd.integer_sgd import integer_sgd_update
from repro.kernels.integer_sgd.ref import integer_sgd_ref
from repro.kernels.nitro_matmul import ops
from repro.kernels.nitro_matmul.nitro_matmul import nitro_matmul
from repro.kernels.nitro_matmul.ref import nitro_matmul_ref


class TestNitroMatmulKernel:
    @pytest.mark.parametrize("m,k,n", [
        (1, 1, 1), (7, 13, 5), (64, 64, 64), (128, 128, 128),
        (130, 200, 90), (256, 384, 128), (33, 257, 65),
    ])
    def test_shape_sweep_matches_ref(self, m, k, n):
        rng = np.random.default_rng(m * 1000 + k * 10 + n)
        x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int32)
        w = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int32)
        sf = linear_scale_factor(k)
        got = nitro_matmul(x, w, sf=sf, interpret=True, bm=32, bn=32, bk=64)
        want = nitro_matmul_ref(x, w, sf=sf)
        assert_bitwise_equal(got, want)

    @pytest.mark.parametrize("in_dtype", [jnp.int8, jnp.int32])
    @pytest.mark.parametrize("out_dtype", [jnp.int8, jnp.int32])
    def test_dtype_sweep(self, in_dtype, out_dtype):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(-127, 128, (48, 96)), in_dtype)
        w = jnp.asarray(rng.integers(-127, 128, (96, 32)), in_dtype)
        sf = linear_scale_factor(96)
        got = nitro_matmul(x, w, sf=sf, out_dtype=out_dtype, interpret=True)
        want = nitro_matmul_ref(x, w, sf=sf, out_dtype=out_dtype)
        assert got.dtype == out_dtype
        assert_bitwise_equal(got, want)

    @pytest.mark.parametrize("apply_relu", [True, False])
    @pytest.mark.parametrize("alpha_inv", [3, 10, 100])
    def test_epilogue_variants(self, apply_relu, alpha_inv):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.integers(-127, 128, (32, 64)), jnp.int32)
        w = jnp.asarray(rng.integers(-127, 128, (64, 32)), jnp.int32)
        sf = linear_scale_factor(64)
        got = nitro_matmul(
            x, w, sf=sf, alpha_inv=alpha_inv, apply_relu=apply_relu, interpret=True
        )
        want = nitro_matmul_ref(x, w, sf=sf, alpha_inv=alpha_inv, apply_relu=apply_relu)
        assert_bitwise_equal(got, want)

    @pytest.mark.parametrize("bm,bn,bk", [(8, 8, 8), (16, 32, 64), (128, 128, 128)])
    def test_tile_size_sweep(self, bm, bn, bk):
        """Result must be invariant to BlockSpec tiling."""
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.integers(-127, 128, (100, 100)), jnp.int32)
        w = jnp.asarray(rng.integers(-127, 128, (100, 100)), jnp.int32)
        sf = linear_scale_factor(100)
        got = nitro_matmul(x, w, sf=sf, bm=bm, bn=bn, bk=bk, interpret=True)
        want = nitro_matmul_ref(x, w, sf=sf)
        assert_bitwise_equal(got, want)

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_property_random_shapes(self, seed):
        rng = np.random.default_rng(seed)
        m, k, n = rng.integers(1, 80, 3)
        x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int32)
        w = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int32)
        sf = linear_scale_factor(int(k))
        got = nitro_matmul(x, w, sf=sf, interpret=True, bm=32, bn=32, bk=32)
        want = nitro_matmul_ref(x, w, sf=sf)
        assert_bitwise_equal(got, want)

    def test_output_range_fits_int8(self):
        """Fused scale+relu output always fits int8 — the contract that lets
        the kernel write int8 activations back to HBM."""
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.integers(-127, 128, (64, 128)), jnp.int32)
        w = jnp.asarray(rng.integers(-127, 128, (128, 64)), jnp.int32)
        out = nitro_matmul(x, w, sf=linear_scale_factor(128), interpret=True)
        assert int(jnp.abs(out).max()) <= 127


class TestNitroOps:
    def test_nitro_linear_matches_layer_pipeline(self):
        """ops.nitro_linear(kernel) ≡ Linear → Scaling → NITRO-ReLU refs."""
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.integers(-127, 128, (4, 10, 48)), jnp.int32)
        w = jnp.asarray(rng.integers(-60, 61, (48, 24)), jnp.int32)
        with pytest.warns(DeprecationWarning, match="use_kernel"):
            got = ops.nitro_linear(x, w, use_kernel=True, interpret=True)
        want = nitro_matmul_ref(
            x.reshape(-1, 48), w, sf=linear_scale_factor(48)
        ).reshape(4, 10, 24)
        assert_bitwise_equal(got, want)

    def test_nitro_conv2d_matches_reference_block(self):
        """Fused conv path ≡ conv_forward → scale → relu from repro.core."""
        from repro.core import activations, layers, scaling

        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.integers(-127, 128, (2, 6, 6, 3)), jnp.int32)
        w = jnp.asarray(rng.integers(-50, 51, (3, 3, 3, 8)), jnp.int32)
        with pytest.warns(DeprecationWarning, match="use_kernel"):
            got = ops.nitro_conv2d(x, w, use_kernel=True, interpret=True)
        z, _ = layers.conv_forward({"w": w}, x)
        want = activations.nitro_relu(
            scaling.scale_forward(z, scaling.conv_scale_factor(3, 3)), 10
        )
        assert_bitwise_equal(got, want)


class TestLegacyBackendKnobs:
    """The deprecated ``use_kernel``/``interpret`` mapping (bugfix
    satellite): explicit use warns, contradictions raise instead of
    silently preferring one knob, and an explicit ``interpret=True`` is
    honoured off-TPU instead of being dropped."""

    def test_legacy_knobs_warn(self):
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.integers(-127, 128, (4, 16)), jnp.int32)
        w = jnp.asarray(rng.integers(-40, 41, (16, 8)), jnp.int32)
        with pytest.warns(DeprecationWarning, match="deprecated"):
            ops.nitro_linear(x, w, use_kernel=False)
        with pytest.warns(DeprecationWarning, match="deprecated"):
            ops._legacy_backend(None, False)

    def test_defaults_do_not_warn(self):
        """The knob-free path must stay silent — only explicit legacy use
        pays the warning."""
        import warnings

        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.integers(-127, 128, (4, 16)), jnp.int32)
        w = jnp.asarray(rng.integers(-40, 41, (16, 8)), jnp.int32)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            ops.nitro_linear(x, w)

    def test_contradictory_knobs_raise(self):
        """use_kernel=False + interpret=True has no meaning: there is no
        kernel to interpret.  Historically the kernel knob silently won."""
        rng = np.random.default_rng(8)
        x = jnp.asarray(rng.integers(-127, 128, (4, 16)), jnp.int32)
        w = jnp.asarray(rng.integers(-40, 41, (16, 8)), jnp.int32)
        with pytest.warns(DeprecationWarning):
            with pytest.raises(ValueError, match="contradictory"):
                ops.nitro_linear(x, w, use_kernel=False, interpret=True)
            with pytest.raises(ValueError, match="contradictory"):
                ops.nitro_conv2d(
                    jnp.zeros((1, 4, 4, 2), jnp.int32),
                    jnp.zeros((3, 3, 2, 2), jnp.int32),
                    use_kernel=False, interpret=True,
                )

    def test_mapping_table(self):
        """The full legacy → backend table, including the fixed row:
        interpret=True with use_kernel unset selects the interpreter
        (previously it resolved to 'reference' off-TPU, silently)."""
        import warnings

        on_tpu = jax.default_backend() == "tpu"
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            assert ops._legacy_backend(True, True) == "interpret"
            assert ops._legacy_backend(True, False) == "pallas"
            assert ops._legacy_backend(False, None) == "reference"
            assert ops._legacy_backend(False, False) == "reference"
            assert ops._legacy_backend(None, True) == "interpret"
            assert ops._legacy_backend(None, None) == (
                "pallas" if on_tpu else "reference"
            )


class TestIntegerSGDKernel:
    @pytest.mark.parametrize("shape", [(1,), (127,), (128,), (1000,), (8, 128), (3, 3, 2, 5)])
    def test_shape_sweep(self, shape):
        rng = np.random.default_rng(sum(shape))
        w = jnp.asarray(rng.integers(-30000, 30000, shape), jnp.int32)
        g = jnp.asarray(rng.integers(-(2**20), 2**20, shape), jnp.int32)
        got = integer_sgd_update(w, g, 512, 3000, interpret=True)
        want = integer_sgd_ref(w, g, 512, 3000)
        assert_bitwise_equal(got, want)

    @pytest.mark.parametrize("gamma,eta", [(1, 0), (512, 0), (512, 3000), (4096, 28000)])
    def test_hyperparameter_sweep(self, gamma, eta):
        rng = np.random.default_rng(gamma + eta)
        w = jnp.asarray(rng.integers(-(2**15), 2**15, (300,)), jnp.int32)
        g = jnp.asarray(rng.integers(-(2**24), 2**24, (300,)), jnp.int32)
        got = integer_sgd_update(w, g, gamma, eta, interpret=True)
        want = integer_sgd_ref(w, g, gamma, eta)
        assert_bitwise_equal(got, want)

    def test_scalars_are_runtime_values(self):
        """One compiled kernel must serve different γ/η (SMEM scalars) —
        the ×3 lr schedule cannot trigger recompilation."""
        w = jnp.zeros((256,), jnp.int32) + 9000
        g = jnp.zeros((256,), jnp.int32) + 51200
        a = integer_sgd_update(w, g, jnp.int32(512), jnp.int32(3000), interpret=True)
        b = integer_sgd_update(w, g, jnp.int32(1536), jnp.int32(3000), interpret=True)
        assert int(a[0]) == 9000 - 100 - 3
        assert int(b[0]) == 9000 - 33 - 3


# ---------------------------------------------------------------------------
# Limb-split dots: int32 products from int8 MXU passes, exact mod 2³²
# ---------------------------------------------------------------------------

INT32_MIN, INT32_MAX = -(2**31), 2**31 - 1

# Fill patterns that stress the limb algebra: the int32 extremes (every
# digit and every carry at its limit), the int8 edges, values whose
# products and sums wrap around int32, and plain random words.
LIMB_PATTERNS = {
    "int32_extremes": [INT32_MIN, INT32_MAX, -1, 0, 1],
    "int8_edges": [-128, 127, -127, 0, -1],
    "wrapping_sums": [2**30, -(2**30), 2**16 + 1, 65535, -65536],
    "random_words": None,
}


def _limb_operand(pattern, shape, dtype, seed):
    rng = np.random.default_rng(seed)
    if dtype == jnp.int8:
        vals = rng.integers(-128, 128, shape)
        vals.flat[0], vals.flat[-1] = -128, 127
    elif LIMB_PATTERNS[pattern] is None:
        vals = rng.integers(INT32_MIN, INT32_MAX + 1, shape, dtype=np.int64)
    else:
        vals = rng.choice(LIMB_PATTERNS[pattern], shape)
    return jnp.asarray(vals, dtype)


def _wrapped_dot(a, b, dims):
    """Ground truth: the int64 product reduced mod 2³² to int32."""
    prod = np.tensordot(np.asarray(a, np.int64), np.asarray(b, np.int64),
                        axes=dims[0])
    return ((prod + 2**31) % 2**32 - 2**31).astype(np.int32)


class TestLimbSplit:
    @pytest.mark.parametrize("pattern", list(LIMB_PATTERNS))
    def test_split_recombines_mod_2_32(self, pattern):
        from repro.kernels.limbs import split_limbs

        a = _limb_operand(pattern, (64,), jnp.int32, seed=3)
        limbs = split_limbs(a, 4)
        assert all(d.dtype == jnp.int8 for d in limbs)
        total = sum(np.asarray(d, np.int64) << (8 * i)
                    for i, d in enumerate(limbs))
        want = np.asarray(a, np.int64) % 2**32
        np.testing.assert_array_equal(total % 2**32, want)

    @pytest.mark.parametrize("pattern", list(LIMB_PATTERNS))
    @pytest.mark.parametrize("a_dtype,b_dtype", [
        (jnp.int8, jnp.int8), (jnp.int8, jnp.int32),
        (jnp.int32, jnp.int8), (jnp.int32, jnp.int32),
    ], ids=["i8xi8", "i8xi32", "i32xi8", "i32xi32"])
    def test_limb_dot_equals_wrapped_int32_dot(self, pattern, a_dtype,
                                               b_dtype):
        from repro.kernels.limbs import limb_count, limb_dot, split_limbs

        a = _limb_operand(pattern, (16, 40), a_dtype, seed=4)
        b = _limb_operand(pattern, (40, 24), b_dtype, seed=5)
        dims = (((1,), (0,)), ((), ()))
        got = limb_dot(split_limbs(a, limb_count(a.dtype)),
                       split_limbs(b, limb_count(b.dtype)), dims)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), _wrapped_dot(a, b, dims))

    @pytest.mark.parametrize("pattern", list(LIMB_PATTERNS))
    def test_grad_kernels_wrap_like_int32(self, kernel_backend, pattern):
        """grad_W and grad_x on full-range int32 δ and W, on every
        backend: equal to the mod-2³² product.  z* = 0 keeps the ReLU
        derivative the identity, so the dot is all that is checked."""
        x = _limb_operand(pattern, (24, 40), jnp.int32, seed=6)
        delta = _limb_operand(pattern, (24, 136), jnp.int32, seed=7)
        w = _limb_operand(pattern, (40, 136), jnp.int32, seed=8)
        z = jnp.zeros_like(delta)
        tiles = TileConfig(bm=16, bn=128, bk=16)
        got_w = ops.grad_w_matmul(x, delta, z, backend=kernel_backend,
                                  tiles=tiles)
        got_x = ops.grad_x_matmul(delta, z, w, backend=kernel_backend,
                                  tiles=tiles)
        np.testing.assert_array_equal(
            np.asarray(got_w), _wrapped_dot(x, delta, (((0,), (0,)), ((), ()))))
        np.testing.assert_array_equal(
            np.asarray(got_x), _wrapped_dot(delta, w, (((1,), (1,)), ((), ()))))

    @pytest.mark.parametrize("x_dtype", [jnp.int8, jnp.int32])
    @pytest.mark.parametrize("pattern", ["int32_extremes", "wrapping_sums"])
    def test_fused_forward_wraps_like_reference(self, x_dtype, pattern):
        """The training forward (one limb for an int8 x, four for int32)
        against the reference backend, with weights at the int32 edges."""
        x = _limb_operand(pattern, (20, 72), x_dtype, seed=9)
        w = _limb_operand(pattern, (72, 40), jnp.int32, seed=10)
        got = ops.fused_matmul_fwd(x, w, sf=linear_scale_factor(72),
                                   backend="interpret")
        want = ops.fused_matmul_fwd(x, w, sf=linear_scale_factor(72),
                                    backend="reference")
        assert_bitwise_equal(got, want)
