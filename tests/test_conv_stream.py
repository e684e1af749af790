"""Streaming implicit-im2col conv: kernel/oracle/dispatcher parity.

The tentpole guarantee: retiring the HBM patch matrix changes *nothing*
numerically.  Every (conv_mode, backend) combination — the Pallas
streaming kernel (interpret mode off-TPU), the pure-jnp row-band oracle,
and the materialised im2col escape hatch — produces bit-identical
activations, caches, gradients, plan logits and post-step parameters,
over both paper CNN configs, K ∈ {3, 5}, odd H/W edges, pooled and
unpooled blocks.  On top of parity, the streaming path is held to its
defining structural property: no (N·H·W, K²·C) patch matrix appears in
the traced program.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _gradcheck import (  # noqa: F401 — kernel_backend is a fixture
    assert_bitwise_equal,
    assert_jaxpr_integer_only,
    collect_aval_shapes,
    kernel_backend,
)
from repro.configs import paper
from repro.core import activations, layers, les, model as M, scaling
from repro.core.blocks import BlockSpec
from repro.core.model import NitroConfig
from repro.core.scaling import conv_scale_factor
from repro.kernels.nitro_conv import (
    conv_grad_w,
    conv_grad_x,
    folds_taps,
    fused_conv,
    fused_conv_fwd,
    resolve_conv_mode,
    stream_conv,
    stream_conv_fwd,
    stream_conv_fwd_ref,
    stream_conv_grad_w,
    stream_conv_grad_w_ref,
    stream_conv_grad_x_ref,
    stream_conv_ref,
)
from repro.kernels.nitro_matmul.ops import check_alpha_inv, fused_matmul


def _rand_case(n, h, w_sp, c, f, k, seed=0, dtype=jnp.int32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 128, (n, h, w_sp, c)), dtype)
    w = jnp.asarray(rng.integers(-40, 41, (k, k, c, f)), dtype)
    return x, w


def _materialised_conv(x, w, *, sf, alpha_inv=10, apply_relu=True,
                       pool=False, out_dtype=jnp.int32):
    """Independent oracle: explicit im2col conv → scale → relu → pool,
    composed from the repro.core reference ops."""
    z, _ = layers.conv_forward({"w": w.astype(jnp.int32)}, x.astype(jnp.int32))
    a = scaling.scale_forward(z, sf)
    if apply_relu:
        a = activations.nitro_relu(a, alpha_inv)
    if pool:
        a = jnp.max(layers.window_view_2x2(a), axis=3)
    return a.astype(out_dtype)


# shape sweep: tile-aligned, odd H/W edges, degenerate smalls, H < band
SHAPES = [
    (2, 8, 8, 3, 8),      # even, multi-band
    (1, 5, 7, 2, 4),      # odd H and W
    (2, 7, 5, 3, 8),      # odd the other way
    (3, 16, 4, 4, 8),     # narrow W
    (1, 1, 1, 1, 1),      # degenerate single pixel (no pool)
    (2, 9, 9, 2, 130),    # F past one filter tile
]


class TestStreamOracle:
    """Pure-jnp row-band oracle vs the materialised reference composition."""

    @pytest.mark.parametrize("k", [3, 5])
    @pytest.mark.parametrize("n,h,w_sp,c,f", SHAPES)
    def test_shape_sweep(self, n, h, w_sp, c, f, k):
        x, w = _rand_case(n, h, w_sp, c, f, k, seed=h * 10 + w_sp)
        sf = conv_scale_factor(k, c)
        got = stream_conv_ref(x, w, sf=sf)
        want = _materialised_conv(x, w, sf=sf)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("k", [3, 5])
    @pytest.mark.parametrize("n,h,w_sp,c,f", [s for s in SHAPES if s[1] > 1])
    def test_pool_epilogue(self, n, h, w_sp, c, f, k):
        """Fused 2×2 pool ≡ separate pool pass, incl. odd-edge cropping."""
        x, w = _rand_case(n, h, w_sp, c, f, k, seed=h + w_sp)
        sf = conv_scale_factor(k, c)
        got = stream_conv_ref(x, w, sf=sf, pool=True)
        want = _materialised_conv(x, w, sf=sf, pool=True)
        assert got.shape == (n, h // 2, w_sp // 2, f)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("bh", [1, 2, 3, 8, 64])
    def test_band_size_invariance(self, bh):
        """The result must not depend on the streaming granularity."""
        x, w = _rand_case(2, 10, 6, 3, 8, 3, seed=bh)
        sf = conv_scale_factor(3, 3)
        got = stream_conv_ref(x, w, sf=sf, pool=True, bh=bh)
        want = _materialised_conv(x, w, sf=sf, pool=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_fwd_two_output_contract(self):
        x, w = _rand_case(2, 9, 7, 3, 8, 3, seed=1)
        sf = conv_scale_factor(3, 3)
        a, z_star = stream_conv_fwd_ref(x, w, sf=sf)
        z, _ = layers.conv_forward({"w": w}, x)
        z_star_want = scaling.scale_forward(z, sf)
        np.testing.assert_array_equal(np.asarray(z_star), np.asarray(z_star_want))
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(activations.nitro_relu(z_star_want, 10))
        )
        assert z_star.dtype == jnp.int32

    @pytest.mark.parametrize("k", [3, 5])
    def test_gradients_match_materialised(self, k):
        x, w = _rand_case(2, 6, 5, 3, 4, k, seed=k)
        rng = np.random.default_rng(7)
        g = jnp.asarray(rng.integers(-63, 64, (2, 6, 5, 4)), jnp.int32)
        gw = stream_conv_grad_w_ref(x, g, kernel_size=k)
        gx = stream_conv_grad_x_ref(g, w)
        gx_want, grads_want = layers.conv_backward(
            {"w": w}, layers.ConvCache(x=x), g, conv_mode="materialise"
        )
        assert_bitwise_equal(gw, grads_want["w"])
        assert_bitwise_equal(gx, gx_want)


class TestStreamKernel:
    """The Pallas kernel (interpret mode) vs the jnp streaming oracle."""

    @pytest.mark.parametrize("k", [3, 5])
    @pytest.mark.parametrize("n,h,w_sp,c,f", SHAPES)
    def test_shape_sweep(self, n, h, w_sp, c, f, k):
        x, w = _rand_case(n, h, w_sp, c, f, k, seed=n + h)
        sf = conv_scale_factor(k, c)
        got = stream_conv(x, w, sf=sf, interpret=True)
        want = stream_conv_ref(x, w, sf=sf)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("pool", [False, True])
    @pytest.mark.parametrize("apply_relu", [False, True])
    def test_epilogue_variants(self, pool, apply_relu):
        x, w = _rand_case(2, 6, 6, 3, 8, 3, seed=3)
        sf = conv_scale_factor(3, 3)
        got = stream_conv(
            x, w, sf=sf, apply_relu=apply_relu, pool=pool, interpret=True
        )
        want = _materialised_conv(x, w, sf=sf, apply_relu=apply_relu, pool=pool)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("bh,bf", [(2, 4), (3, 8), (8, 128)])
    def test_tile_size_sweep(self, bh, bf):
        """Result must be invariant to band height and filter tiling."""
        x, w = _rand_case(2, 7, 6, 3, 12, 3, seed=bh * 10 + bf)
        sf = conv_scale_factor(3, 3)
        got = stream_conv(x, w, sf=sf, bh=bh, bf=bf, interpret=True)
        want = stream_conv_ref(x, w, sf=sf)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_int8_operands(self):
        """The plan feeds int8 activations; row DMA + patches must cope."""
        x, w = _rand_case(2, 8, 8, 4, 8, 3, seed=5, dtype=jnp.int8)
        sf = conv_scale_factor(3, 4)
        got = stream_conv(x, w, sf=sf, pool=True, out_dtype=jnp.int8,
                          interpret=True)
        want = stream_conv_ref(x, w, sf=sf, pool=True, out_dtype=jnp.int8)
        assert got.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_fwd_two_outputs(self):
        x, w = _rand_case(2, 9, 5, 3, 8, 3, seed=6)
        sf = conv_scale_factor(3, 3)
        a_k, z_k = stream_conv_fwd(x, w, sf=sf, interpret=True, bh=4, bf=4)
        a_r, z_r = stream_conv_fwd_ref(x, w, sf=sf)
        np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))
        np.testing.assert_array_equal(np.asarray(z_k), np.asarray(z_r))
        assert z_k.dtype == jnp.int32

    @pytest.mark.parametrize("k,bf", [(3, 128), (5, 2), (3, 4)])
    def test_grad_w_kernel(self, k, bf):
        """VMEM-accumulated grad_w ≡ materialised im2colᵀ @ g."""
        x, w = _rand_case(3, 6, 5, 2, 6, k, seed=k)
        rng = np.random.default_rng(8)
        g = jnp.asarray(rng.integers(-63, 64, (3, 6, 5, 6)), jnp.int32)
        got = stream_conv_grad_w(x, g, kernel_size=k, bf=bf, interpret=True)
        want = stream_conv_grad_w_ref(x, g, kernel_size=k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestDispatcher:
    """conv_mode/backend dispatch + the alpha_inv validation satellite."""

    @pytest.mark.parametrize("pool", [False, True])
    def test_all_routes_agree(self, pool):
        x, w = _rand_case(2, 6, 6, 3, 8, 3, seed=9)
        sf = conv_scale_factor(3, 3)
        outs = {}
        for mode in ("stream", "materialise"):
            for backend in ("reference", "interpret"):
                outs[(mode, backend)] = fused_conv(
                    x, w, sf=sf, pool=pool, backend=backend, conv_mode=mode
                )
        first = next(iter(outs.values()))
        for key, out in outs.items():
            assert_bitwise_equal(out, first, err_msg=str(key))

    def test_fwd_routes_agree(self):
        x, w = _rand_case(2, 7, 7, 3, 8, 3, seed=10)
        sf = conv_scale_factor(3, 3)
        ref = fused_conv_fwd(x, w, sf=sf, backend="reference",
                             conv_mode="stream")
        for mode, backend in [("stream", "interpret"),
                              ("materialise", "reference")]:
            got = fused_conv_fwd(x, w, sf=sf, backend=backend, conv_mode=mode)
            assert_bitwise_equal(got, ref, err_msg=f"{mode}/{backend}")

    def test_grad_routes_agree(self):
        x, w = _rand_case(2, 6, 6, 3, 4, 3, seed=11)
        rng = np.random.default_rng(11)
        g = jnp.asarray(rng.integers(-63, 64, (2, 6, 6, 4)), jnp.int32)
        ref_w = conv_grad_w(x, g, kernel_size=3, backend="reference",
                            conv_mode="materialise")
        ref_x = conv_grad_x(g, w, backend="reference", conv_mode="materialise")
        for mode, backend in [("stream", "reference"), ("stream", "interpret")]:
            assert_bitwise_equal(
                conv_grad_w(x, g, kernel_size=3, backend=backend,
                            conv_mode=mode),
                ref_w, err_msg=f"{mode}/{backend}")
            assert_bitwise_equal(
                conv_grad_x(g, w, backend=backend, conv_mode=mode),
                ref_x, err_msg=f"{mode}/{backend}")

    def test_unknown_conv_mode_raises(self):
        with pytest.raises(ValueError, match="unknown conv_mode"):
            resolve_conv_mode("fuse-everything")
        x, w = _rand_case(1, 4, 4, 2, 2, 3)
        with pytest.raises(ValueError, match="unknown conv_mode"):
            fused_conv(x, w, sf=8, conv_mode="材料")

    def test_even_kernel_rejected_on_stream(self):
        x = jnp.zeros((1, 4, 4, 2), jnp.int32)
        w = jnp.zeros((2, 2, 2, 2), jnp.int32)
        with pytest.raises(ValueError, match="odd kernel"):
            stream_conv_ref(x, w, sf=8)

    def test_alpha_inv_zero_raises(self):
        """Satellite: alpha_inv=0 must raise, not silently become 1."""
        x, w = _rand_case(1, 4, 4, 2, 2, 3)
        with pytest.raises(ValueError, match="alpha_inv"):
            fused_conv(x, w, sf=8, alpha_inv=0)
        x2 = jnp.zeros((4, 8), jnp.int32)
        w2 = jnp.zeros((8, 4), jnp.int32)
        with pytest.raises(ValueError, match="alpha_inv"):
            fused_matmul(x2, w2, sf=8, alpha_inv=0)

    def test_alpha_inv_ignored_without_relu(self):
        """Frozen no-activation layers export alpha_inv=0: still legal (and
        normalised, so it cannot fan out into extra kernel compilations)."""
        assert check_alpha_inv(0, False) == 1
        assert check_alpha_inv(10, True) == 10
        x2 = jnp.asarray(
            np.random.default_rng(0).integers(-127, 128, (4, 8)), jnp.int32
        )
        w2 = jnp.asarray(
            np.random.default_rng(1).integers(-40, 41, (8, 4)), jnp.int32
        )
        out = fused_matmul(x2, w2, sf=8, alpha_inv=0, apply_relu=False,
                           backend="reference")
        assert out.shape == (4, 4)


class TestTrainingParity:
    """forward_layers / train_step across conv modes on the paper configs."""

    @pytest.mark.parametrize("arch", ["vgg8b", "vgg11b"])
    def test_forward_stream_bit_exact_on_paper_cnn(self, arch):
        cfg = paper.get(arch, scale=0.0625)
        state = les.create_train_state(jax.random.PRNGKey(7), cfg)
        rng = np.random.default_rng(11)
        x = jnp.asarray(
            rng.integers(-127, 128, (4, *cfg.input_shape)), jnp.int32
        )
        outs = {
            mode: M.forward(state.params, cfg, x, train=False, fused=True,
                            conv_mode=mode)
            for mode in ("stream", "materialise")
        }
        unfused = M.forward(state.params, cfg, x, train=False, fused=False)
        for mode, (y, acts, caches, _) in outs.items():
            assert_bitwise_equal(y, unfused[0], err_msg=mode)
            for a_m, a_u, c_m, c_u in zip(acts, unfused[1], caches, unfused[2]):
                assert_bitwise_equal(a_m, a_u, err_msg=mode)
                assert_bitwise_equal(c_m["z_star"], c_u["z_star"],
                                     err_msg=mode)

    @pytest.mark.parametrize("kernel_size", [3, 5])
    def test_k5_block_and_odd_input(self, kernel_size):
        """K=5 and odd 9×9 spatial dims through a pooled conv block."""
        cfg = NitroConfig(
            blocks=(BlockSpec("conv", 12, pool=True, d_lr=128,
                              kernel_size=kernel_size),
                    BlockSpec("linear", 32)),
            input_shape=(9, 9, 3), num_classes=10, gamma_inv=512,
        )
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.integers(-127, 128, (3, 9, 9, 3)), jnp.int32)
        y_s, _, c_s, _ = M.forward(state.params, cfg, x, conv_mode="stream")
        y_m, _, c_m, _ = M.forward(state.params, cfg, x,
                                   conv_mode="materialise")
        y_u, _, _, _ = M.forward(state.params, cfg, x, fused=False)
        np.testing.assert_array_equal(np.asarray(y_s), np.asarray(y_m))
        np.testing.assert_array_equal(np.asarray(y_s), np.asarray(y_u))
        np.testing.assert_array_equal(
            np.asarray(c_s[0]["z_star"]), np.asarray(c_m[0]["z_star"])
        )

    def test_train_step_stream_bit_exact(self):
        cfg = paper.get("vgg8b", scale=0.0625)
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(4)
        x = jnp.asarray(
            rng.integers(-127, 128, (8, *cfg.input_shape)), jnp.int32
        )
        y = jnp.asarray(rng.integers(0, cfg.num_classes, 8), jnp.int32)
        key = jax.random.PRNGKey(9)
        stepped = {
            mode: jax.jit(functools.partial(
                les.train_step, cfg=cfg, conv_mode=mode
            ))(st, x=x, labels=y, key=key)
            for mode in ("stream", "materialise")
        }
        assert_bitwise_equal(stepped["stream"][0].params,
                             stepped["materialise"][0].params)
        assert int(stepped["stream"][1].loss) == int(stepped["materialise"][1].loss)

    def test_conv_backward_modes_agree(self):
        x, w = _rand_case(2, 8, 6, 3, 8, 3, seed=12)
        rng = np.random.default_rng(12)
        g = jnp.asarray(rng.integers(-63, 64, (2, 8, 6, 8)), jnp.int32)
        cache = layers.ConvCache(x=x)
        stream = layers.conv_backward({"w": w}, cache, g, conv_mode="stream")
        materialise = layers.conv_backward({"w": w}, cache, g,
                                           conv_mode="materialise")
        assert_bitwise_equal(stream, materialise)


# ---------------------------------------------------------------------------
# Structural property: the streaming path has no HBM patch matrix
# (jaxpr-walking helpers live in the shared harness, tests/_gradcheck.py)
# ---------------------------------------------------------------------------


class TestStructural:
    @staticmethod
    def _patch_shapes(n, h, w_sp, c, k):
        """The forms the materialised patch matrix takes in a traced
        program: the 2-D matmul operand and its pre-reshape 4-D layout."""
        return {(n * h * w_sp, k * k * c), (n, h, w_sp, k * k * c)}

    @pytest.mark.parametrize("backend,c", [
        ("reference", 16), ("interpret", 16),
        ("reference", 8), ("interpret", 8),
    ], ids=["reference", "interpret", "reference-folds", "interpret-folds"])
    def test_no_patch_matrix_in_stream_fwd(self, backend, c):
        """Acceptance criterion: the (N·H·W, K²·C) patch matrix must not
        appear anywhere in the streaming program — including inside the
        Pallas kernel body — while the materialised path (sanity check)
        does produce it.  A shallow input whose taps fold (C = 8: 72
        lanes) may hold only the 4-D (N,H,W,K²·C) patches the folded 1×1
        conv reads: no more HBM than the lane-padded input it replaces."""
        n, h, w_sp, f, k = 4, 16, 16, 8, 3
        x, w = _rand_case(n, h, w_sp, c, f, k, seed=0)
        sf = conv_scale_factor(k, c)
        patch_shapes = self._patch_shapes(n, h, w_sp, c, k)

        def trace(mode):
            jaxpr = jax.make_jaxpr(functools.partial(
                fused_conv, sf=sf, backend=backend, conv_mode=mode
            ))(x, w)
            return collect_aval_shapes(jaxpr.jaxpr)

        found = patch_shapes & trace("stream")
        if folds_taps(k, c):
            assert k * k * c <= 128
            assert found <= {(n, h, w_sp, k * k * c)}, (
                f"folded path materialised {found}"
            )
        else:
            assert not found, (
                "streaming path materialised a full-size patch matrix"
            )
        assert patch_shapes & trace("materialise"), (
            "sanity: materialised path should contain the patch matrix"
        )

    def test_no_patch_matrix_in_stream_plan(self):
        """Same property end-to-end through a compiled multi-layer plan:
        none of the per-layer (N·Hℓ·Wℓ, K²·Cℓ) full patch sizes may appear
        in the streaming program, while the materialised one (sanity)
        contains every one of them."""
        from repro.infer.export import freeze
        from repro.infer.plan import _execute, compile_plan

        cfg = paper.get("vgg8b", scale=0.0625)
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        fm = freeze(state, cfg)
        n = 4
        x = jnp.zeros((n, *cfg.input_shape), jnp.int32)

        # full patch-matrix shapes of every conv layer, tracking geometry
        h, w_sp, c = cfg.input_shape
        patch_shapes = set()
        flat_patches = set()  # the 2-D matmul-operand form only
        for spec in cfg.blocks:
            if spec.kind != "conv":
                break
            patch_shapes |= self._patch_shapes(n, h, w_sp, c, spec.kernel_size)
            flat_patches.add((n * h * w_sp, spec.kernel_size ** 2 * c))
            c = spec.out_features
            if spec.pool:
                h, w_sp = h // 2, w_sp // 2

        for mode, expect_patch in (("stream", False), ("materialise", True)):
            plan = compile_plan(fm, backend="reference", conv_mode=mode)
            jaxpr = jax.make_jaxpr(functools.partial(
                _execute, metas=plan.metas, backend=plan.backend
            ))(plan.weights, x)
            shapes = collect_aval_shapes(jaxpr.jaxpr)
            if expect_patch:
                assert flat_patches <= shapes, "sanity: patches expected"
            else:
                assert not (patch_shapes & shapes), (
                    "streaming plan materialised a full patch matrix"
                )

    @pytest.mark.parametrize("conv_mode,backend", [
        ("stream", "auto"),        # the default train path
        ("stream", "interpret"),   # the actual Pallas kernel bodies, off-TPU
        ("materialise", "auto"),   # explicit-im2col escape hatch
    ])
    def test_train_step_integer_only(self, conv_mode, backend):
        """No float dtype anywhere in the traced step — descending into the
        streaming conv kernel bodies (fwd + grad_w + grad_x)."""
        cfg = NitroConfig(
            blocks=(BlockSpec("conv", 16, pool=True, d_lr=256, dropout=0.1),
                    BlockSpec("linear", 64, dropout=0.1)),
            input_shape=(8, 8, 3), num_classes=10, gamma_inv=512,
            eta_fw=12000, eta_lr=3000,
        )
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(-127, 128, (8, 8, 8, 3)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 10, 8), jnp.int32)
        jaxpr = jax.make_jaxpr(
            functools.partial(les.train_step, cfg=cfg, fused=True,
                              backend=backend, conv_mode=conv_mode)
        )(st, x=x, labels=y, key=jax.random.PRNGKey(1))
        assert_jaxpr_integer_only(jaxpr.jaxpr)


class TestPlanStream:
    @pytest.mark.parametrize("arch", ["vgg8b", "vgg11b"])
    @pytest.mark.parametrize("backend", ["reference", "interpret"])
    def test_plan_parity_on_paper_cnn(self, arch, backend):
        """Streaming plan ≡ materialised plan ≡ frozen_forward oracle."""
        from repro.infer.export import freeze
        from repro.infer.plan import compile_plan

        cfg = paper.get(arch, scale=0.0625)
        state = les.create_train_state(jax.random.PRNGKey(1), cfg)
        fm = freeze(state, cfg)
        rng = np.random.default_rng(3)
        x = jnp.asarray(
            rng.integers(-127, 128, (2, *cfg.input_shape)), jnp.int32
        )
        want = M.frozen_forward(state.params, cfg, x)
        for mode in ("stream", "materialise"):
            plan = compile_plan(fm, backend=backend, conv_mode=mode)
            np.testing.assert_array_equal(
                np.asarray(plan.logits(x)), np.asarray(want),
                err_msg=f"{arch}/{backend}/{mode}",
            )

    def test_step_meta_describes_fusion(self):
        from repro.infer.export import freeze
        from repro.infer.plan import compile_plan

        cfg = paper.get("vgg8b", scale=0.0625)
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        fm = freeze(state, cfg)
        plan = compile_plan(fm, backend="reference", conv_mode="stream")
        for meta, spec in zip(plan.metas, cfg.blocks):
            if spec.kind == "conv":
                assert meta.conv_mode == "stream"
                assert meta.fused_pool == spec.pool
                assert meta.kernel_size == spec.kernel_size
            else:
                assert meta.conv_mode == ""
                assert not meta.fused_pool
        mat = compile_plan(fm, backend="reference", conv_mode="materialise")
        assert all(not m.fused_pool for m in mat.metas)

    def test_summary_counts_patch_traffic(self):
        """Satellite: conv rows must account the im2col patch round-trip
        (~2K²·input bytes) in the materialised estimate and report the
        per-layer streaming delta."""
        from repro.infer.export import freeze
        from repro.infer.plan import compile_plan

        cfg = paper.get("vgg8b", scale=0.0625)
        state = les.create_train_state(jax.random.PRNGKey(0), cfg)
        plan = compile_plan(freeze(state, cfg), backend="reference")
        shape = cfg.input_shape
        in_itemsize = 4
        for row, meta in zip(plan.summary(), plan.metas):
            per_sample = row["hbm_per_sample_bytes"]
            if row["kind"] == "conv":
                h, w_sp, c = shape
                k = meta.kernel_size
                in_bytes = h * w_sp * c * in_itemsize
                # materialised estimate includes patch write + read back
                assert per_sample["materialise"] >= 2 * k * k * in_bytes
                assert per_sample["stream"] < per_sample["materialise"]
                assert row["stream_saving_ratio"] > k  # ≈K², conservatively >K
                f = row["weight_shape"][-1]
                shape = (h // 2, w_sp // 2, f) if meta.pool else (h, w_sp, f)
            else:
                assert row["stream_saving_ratio"] == 1.0
                shape = (row["weight_shape"][-1],)
            in_itemsize = jnp.dtype(meta.out_dtype).itemsize

    def test_window_view_public_name(self):
        """Satellite: the pool window helper is public API now."""
        x = jnp.arange(2 * 5 * 7 * 3, dtype=jnp.int32).reshape(2, 5, 7, 3)
        win = layers.window_view_2x2(x)
        assert win.shape == (2, 2, 3, 4, 3)
        out, _ = layers.maxpool_forward(x)
        np.testing.assert_array_equal(
            np.asarray(jnp.max(win, axis=3)), np.asarray(out)
        )


# ---------------------------------------------------------------------------
# Limb-split conv dots at the int32 edges, odd widths and C = 3
# ---------------------------------------------------------------------------

_I32 = (-(2**31), 2**31 - 1)
_CONV_LIMB_PATTERNS = {
    "int32_extremes": [_I32[0], _I32[1], -1, 0, 1],
    "wrapping_sums": [2**30, -(2**30), 2**16 + 1, -128, 127],
    "random_words": None,
}


def _edge_tensor(pattern, shape, seed):
    rng = np.random.default_rng(seed)
    vals = _CONV_LIMB_PATTERNS[pattern]
    if vals is None:
        return jnp.asarray(
            rng.integers(_I32[0], _I32[1] + 1, shape, dtype=np.int64),
            jnp.int32)
    return jnp.asarray(rng.choice(vals, shape), jnp.int32)


def _wrap32(a):
    return ((a + 2**31) % 2**32 - 2**31).astype(np.int32)


def _np_patches(x, k):
    """(N,H,W,C) → (N,H,W,K²·C) 'same' patches in int64, im2col layout."""
    p = k // 2
    xp = np.pad(np.asarray(x, np.int64), ((0, 0), (p, p), (p, p), (0, 0)))
    n, h, w, _ = x.shape
    return np.concatenate(
        [xp[:, ki:ki + h, kj:kj + w, :] for ki in range(k) for kj in range(k)],
        axis=-1)


# (N, H, W, C, F): W never a multiple of 8, the 3-channel input layer,
# and a filter count past one 128-lane tile.
_LIMB_CONV_SHAPES = [(2, 5, 7, 3, 8), (1, 6, 9, 5, 4), (1, 4, 3, 3, 130)]


class TestLimbConv:
    @pytest.mark.parametrize("pattern", list(_CONV_LIMB_PATTERNS))
    @pytest.mark.parametrize("shape", _LIMB_CONV_SHAPES,
                             ids=lambda s: "x".join(map(str, s)))
    def test_conv_grads_wrap_like_int32(self, kernel_backend, pattern,
                                        shape):
        """Both conv gradients on full-range int32 operands equal the
        mod-2³² int64 ground truth on every backend (z* = 0: the ReLU
        derivative is the identity)."""
        n, h, w_sp, c, f = shape
        k = 3
        x = _edge_tensor(pattern, (n, h, w_sp, c), seed=1)
        delta = _edge_tensor(pattern, (n, h, w_sp, f), seed=2)
        w = _edge_tensor(pattern, (k, k, c, f), seed=3)
        z = jnp.zeros_like(delta)

        got_w = conv_grad_w(x, delta, kernel_size=k, z_star=z,
                            backend=kernel_backend)
        patches = _np_patches(x, k).reshape(-1, k * k * c)
        want_w = patches.T @ np.asarray(delta, np.int64).reshape(-1, f)
        np.testing.assert_array_equal(
            np.asarray(got_w), _wrap32(want_w).reshape(k, k, c, f))

        got_x = conv_grad_x(delta, w, z_star=z, backend=kernel_backend)
        dp = np.pad(np.asarray(delta, np.int64),
                    ((0, 0), (1, 1), (1, 1), (0, 0)))
        want_x = sum(
            dp[:, k - 1 - ki:k - 1 - ki + h, k - 1 - kj:k - 1 - kj + w_sp, :]
            @ np.asarray(w, np.int64)[ki, kj].T
            for ki in range(k) for kj in range(k))
        np.testing.assert_array_equal(np.asarray(got_x), _wrap32(want_x))

    @pytest.mark.parametrize("pattern", ["int32_extremes", "wrapping_sums"])
    @pytest.mark.parametrize("shape", _LIMB_CONV_SHAPES[:2],
                             ids=lambda s: "x".join(map(str, s)))
    def test_fused_forward_wraps_like_reference(self, pattern, shape):
        """The training forward's four-limb input path (int32 x) against
        the reference backend, weights at the int32 edges."""
        n, h, w_sp, c, f = shape
        x = _edge_tensor(pattern, (n, h, w_sp, c), seed=4)
        w = _edge_tensor(pattern, (3, 3, c, f), seed=5)
        sf = conv_scale_factor(3, c)
        got = fused_conv_fwd(x, w, sf=sf, backend="interpret")
        want = fused_conv_fwd(x, w, sf=sf, backend="reference")
        assert_bitwise_equal(got, want)
