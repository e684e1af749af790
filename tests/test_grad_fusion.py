"""Fused NITRO-ReLU-backward/STE gradient path: kernel contract + parity.

The tentpole guarantee: folding the NITRO-ReLU derivative and the scaling
STE into the gradient kernels' δ prologue changes *nothing* numerically —
weight gradients, input gradients and post-step parameters are bit-
identical with the unfused jnp composition (``masked_delta`` on the full
δ, then the ``z_star=None`` route — ``_gradcheck.unfused_delta_path`` at
block and step level), on both paper CNN configs, for every backend
runnable on this host.  On top of parity, the fused backward is held to
its structural property: the full-size post-ReLU-bwd δ tensor never
appears outside a Pallas kernel body in the traced program, and the
whole fused step stays float-free.

All parity assertions go through the shared harness in
``tests/_gradcheck.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _gradcheck import (  # noqa: F401  (fixtures)
    AVAILABLE_BACKENDS,
    assert_bitwise_equal,
    assert_jaxpr_integer_only,
    backend_pair,
    eqn_output_shapes,
    kernel_backend,
    unfused_delta_path,
)
from repro.configs import paper
from repro.core import blocks as B
from repro.core import les, model as M
from repro.core.activations import nitro_relu_backward
from repro.core.blocks import BlockSpec
from repro.core.model import NitroConfig
from repro.core.numerics import int_matmul
from repro.kernels import grad_ops
from repro.kernels.nitro_matmul import (
    grad_w_matmul,
    grad_x_matmul,
    nitro_matmul_grad_w,
    nitro_matmul_grad_w_ref,
    nitro_matmul_grad_x,
    nitro_matmul_grad_x_ref,
)
from repro.kernels.nitro_conv import (
    conv_grad_w,
    conv_grad_x,
    stream_conv_grad_w,
    stream_conv_grad_w_ref,
    stream_conv_grad_x,
    stream_conv_grad_x_ref,
)
from repro.kernels.nitro_matmul.ref import masked_delta


def _linear_case(b, m, n, seed=0):
    """Random (x, delta, z_star, w) for a linear backward; z* spans all
    four NITRO-ReLU segments (±300 straddles the ±127 saturation)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 128, (b, m)), jnp.int32)
    delta = jnp.asarray(rng.integers(-63, 64, (b, n)), jnp.int32)
    z_star = jnp.asarray(rng.integers(-300, 301, (b, n)), jnp.int32)
    w = jnp.asarray(rng.integers(-40, 41, (m, n)), jnp.int32)
    return x, delta, z_star, w


def _conv_case(n, h, w_sp, c, f, k, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 128, (n, h, w_sp, c)), jnp.int32)
    delta = jnp.asarray(rng.integers(-63, 64, (n, h, w_sp, f)), jnp.int32)
    z_star = jnp.asarray(rng.integers(-300, 301, (n, h, w_sp, f)), jnp.int32)
    w = jnp.asarray(rng.integers(-40, 41, (k, k, c, f)), jnp.int32)
    return x, delta, z_star, w


# ---------------------------------------------------------------------------
# Kernel-level: the grad-matmul prologue contract
# ---------------------------------------------------------------------------


class TestGradMatmulKernels:
    @pytest.mark.parametrize("b,m,n", [
        (1, 1, 1), (7, 13, 5), (64, 64, 64), (128, 128, 128),
        (33, 257, 65), (130, 100, 90),
    ])
    def test_shape_sweep_matches_ref(self, b, m, n):
        """Fused grad kernels (interpret) ≡ jnp mask + matmul oracles on
        aligned, ragged and degenerate shapes."""
        x, delta, z_star, w = _linear_case(b, m, n, seed=b + m + n)
        gw = nitro_matmul_grad_w(x, delta, z_star, interpret=True,
                                 bm=32, bn=32, bk=32)
        gx = nitro_matmul_grad_x(delta, z_star, w, interpret=True,
                                 bm=32, bn=32, bk=32)
        assert_bitwise_equal(gw, nitro_matmul_grad_w_ref(x, delta, z_star))
        assert_bitwise_equal(gx, nitro_matmul_grad_x_ref(delta, z_star, w))

    @pytest.mark.parametrize("alpha_inv", [1, 3, 10, 100])
    def test_alpha_sweep(self, alpha_inv):
        x, delta, z_star, w = _linear_case(20, 30, 17, seed=alpha_inv)
        gw = nitro_matmul_grad_w(x, delta, z_star, alpha_inv=alpha_inv,
                                 interpret=True, bm=16, bn=16, bk=16)
        gx = nitro_matmul_grad_x(delta, z_star, w, alpha_inv=alpha_inv,
                                 interpret=True, bm=16, bn=16, bk=16)
        assert_bitwise_equal(
            gw, nitro_matmul_grad_w_ref(x, delta, z_star, alpha_inv=alpha_inv)
        )
        assert_bitwise_equal(
            gx, nitro_matmul_grad_x_ref(delta, z_star, w, alpha_inv=alpha_inv)
        )

    @pytest.mark.parametrize("bm,bn,bk", [(8, 8, 8), (16, 32, 64), (128, 128, 128)])
    def test_tile_size_sweep(self, bm, bn, bk):
        """Result must be invariant to BlockSpec tiling — the masked δ
        padding contract (δ = z* = 0 → 0) holds on every grid."""
        x, delta, z_star, w = _linear_case(100, 100, 100, seed=bm + bn)
        gw = nitro_matmul_grad_w(x, delta, z_star, interpret=True,
                                 bm=bm, bn=bn, bk=bk)
        gx = nitro_matmul_grad_x(delta, z_star, w, interpret=True,
                                 bm=bm, bn=bn, bk=bk)
        assert_bitwise_equal(gw, nitro_matmul_grad_w_ref(x, delta, z_star))
        assert_bitwise_equal(gx, nitro_matmul_grad_x_ref(delta, z_star, w))

    def test_ref_oracle_is_the_unfused_composition(self):
        """The ref oracles ARE relu_bwd → STE → plain matmul, pinned here
        so the kernel tests above transitively anchor to core ops."""
        x, delta, z_star, w = _linear_case(9, 11, 7, seed=3)
        g = nitro_relu_backward(z_star, delta, 10)
        assert_bitwise_equal(
            nitro_matmul_grad_w_ref(x, delta, z_star), int_matmul(x.T, g)
        )
        assert_bitwise_equal(
            nitro_matmul_grad_x_ref(delta, z_star, w), int_matmul(g, w.T)
        )

    def test_dispatcher_backends_agree(self, backend_pair):
        a, b = backend_pair
        x, delta, z_star, w = _linear_case(17, 50, 9, seed=5)
        assert_bitwise_equal(
            grad_w_matmul(x, delta, z_star, backend=a),
            grad_w_matmul(x, delta, z_star, backend=b),
            err_msg=f"grad_w {a} vs {b}",
        )
        assert_bitwise_equal(
            grad_x_matmul(delta, z_star, w, backend=a),
            grad_x_matmul(delta, z_star, w, backend=b),
            err_msg=f"grad_x {a} vs {b}",
        )

    def test_alpha_inv_zero_raises(self):
        x, delta, z_star, w = _linear_case(4, 4, 4)
        with pytest.raises(ValueError, match="alpha_inv"):
            grad_w_matmul(x, delta, z_star, alpha_inv=0)
        with pytest.raises(ValueError, match="alpha_inv"):
            grad_x_matmul(delta, z_star, w, alpha_inv=0)


# ---------------------------------------------------------------------------
# Conv kernels: streamed gradients with the δ-band prologue
# ---------------------------------------------------------------------------


class TestConvGradKernels:
    SHAPES = [
        (2, 8, 8, 3, 8),      # even, multi-band
        (1, 5, 7, 2, 4),      # odd H and W
        (2, 7, 5, 3, 8),      # odd the other way
        (2, 9, 9, 2, 130),    # F past one filter tile
    ]

    @pytest.mark.parametrize("k", [3, 5])
    @pytest.mark.parametrize("n,h,w_sp,c,f", SHAPES)
    def test_grad_w_fused_kernel(self, n, h, w_sp, c, f, k):
        x, delta, z_star, _ = _conv_case(n, h, w_sp, c, f, k, seed=h + f)
        got = stream_conv_grad_w(x, delta, kernel_size=k, z_star=z_star,
                                 interpret=True)
        want = stream_conv_grad_w_ref(x, delta, kernel_size=k, z_star=z_star)
        assert_bitwise_equal(got, want)

    @pytest.mark.parametrize("k", [3, 5])
    @pytest.mark.parametrize("n,h,w_sp,c,f", SHAPES)
    def test_grad_x_fused_kernel(self, n, h, w_sp, c, f, k):
        _, delta, z_star, w = _conv_case(n, h, w_sp, c, f, k, seed=h * 2 + f)
        got = stream_conv_grad_x(delta, z_star, w, interpret=True)
        want = stream_conv_grad_x_ref(delta, w, z_star=z_star)
        assert_bitwise_equal(got, want)

    @pytest.mark.parametrize("bh,bf", [(2, 4), (3, 8), (8, 128)])
    def test_tile_size_sweep(self, bh, bf):
        """Band height / filter tiling must not change the masked result."""
        x, delta, z_star, w = _conv_case(2, 7, 6, 3, 12, 3, seed=bh * 10 + bf)
        gw = stream_conv_grad_w(x, delta, kernel_size=3, z_star=z_star,
                                bh=bh, bf=bf, interpret=True)
        gx = stream_conv_grad_x(delta, z_star, w, bh=bh, bf=bf, interpret=True)
        assert_bitwise_equal(
            gw, stream_conv_grad_w_ref(x, delta, kernel_size=3, z_star=z_star)
        )
        assert_bitwise_equal(
            gx, stream_conv_grad_x_ref(delta, w, z_star=z_star)
        )

    def test_masked_oracles_equal_premasked_unfused(self):
        """The band-masked streaming oracles ≡ jnp pre-mask + the historical
        unfused gradient routes (the defining identity of the fusion)."""
        x, delta, z_star, w = _conv_case(2, 6, 5, 3, 4, 3, seed=9)
        g = nitro_relu_backward(z_star, delta, 10)
        assert_bitwise_equal(
            stream_conv_grad_w_ref(x, delta, kernel_size=3, z_star=z_star),
            stream_conv_grad_w_ref(x, g, kernel_size=3),
        )
        assert_bitwise_equal(
            stream_conv_grad_x_ref(delta, w, z_star=z_star),
            stream_conv_grad_x_ref(g, w),
        )

    def test_dispatcher_modes_and_backends_agree(self, backend_pair):
        """Both δ routes — prologue and pre-masked — agree across backends."""
        a, b = backend_pair
        x, delta, z_star, w = _conv_case(2, 6, 6, 3, 8, 3, seed=11)
        g = masked_delta(delta, z_star, 10)
        for route, d, z in (("prologue", delta, z_star), ("premasked", g, None)):
            assert_bitwise_equal(
                conv_grad_w(x, d, kernel_size=3, z_star=z, backend=a),
                conv_grad_w(x, d, kernel_size=3, z_star=z, backend=b),
                err_msg=f"grad_w {route} {a} vs {b}",
            )
            assert_bitwise_equal(
                conv_grad_x(d, w, z_star=z, backend=a),
                conv_grad_x(d, w, z_star=z, backend=b),
                err_msg=f"grad_x {route} {a} vs {b}",
            )


# ---------------------------------------------------------------------------
# grad_ops dispatcher: fused ≡ unfused on every route
# ---------------------------------------------------------------------------


class TestGradOpsDispatcher:
    def test_linear_fused_vs_unfused(self, kernel_backend):
        """Fused δ prologue ≡ ``masked_delta`` then the z_star=None route."""
        x, delta, z_star, w = _linear_case(12, 40, 24, seed=1)
        fused = grad_ops.linear_grads(
            x, w, delta, z_star=z_star, backend=kernel_backend
        )
        unfused = grad_ops.linear_grads(
            x, w, masked_delta(delta, z_star, 10), backend=kernel_backend
        )
        assert_bitwise_equal(fused, unfused, err_msg=kernel_backend)

    def test_conv_fused_vs_unfused(self, kernel_backend):
        x, delta, z_star, w = _conv_case(2, 8, 6, 3, 8, 3, seed=2)
        fused = grad_ops.conv_grads(
            x, w, delta, z_star=z_star, backend=kernel_backend,
        )
        unfused = grad_ops.conv_grads(
            x, w, masked_delta(delta, z_star, 10), backend=kernel_backend,
        )
        assert_bitwise_equal(fused, unfused, err_msg=kernel_backend)

    def test_no_activation_path_is_plain_matmuls(self):
        """z_star=None (learning/output layers: STE only) must reproduce
        the historical plain integer matmuls exactly."""
        x, delta, _, w = _linear_case(9, 20, 10, seed=4)
        gx, gw = grad_ops.linear_grads(x, w, delta)
        assert_bitwise_equal(gx, int_matmul(delta, w.T))
        assert_bitwise_equal(gw, int_matmul(x.T, delta))


# ---------------------------------------------------------------------------
# Block- and train-step-level parity on the paper configs
# ---------------------------------------------------------------------------


def _block_cases(cfg, batch, seed=7):
    """Forward the paper config once (fused, auto) and yield per-block
    (spec, params, cache, delta) backward inputs with a synthetic δ."""
    state = les.create_train_state(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 1)
    x = jnp.asarray(rng.integers(-127, 128, (batch, *cfg.input_shape)),
                    jnp.int32)
    _, acts, caches, _ = M.forward(state.params, cfg, x, train=False)
    for spec, p, a, cache in zip(cfg.blocks, state.params["blocks"], acts,
                                 caches):
        delta = jnp.asarray(rng.integers(-63, 64, a.shape), jnp.int32)
        yield spec, p, cache, delta


class TestForwardLayersBackwardParity:
    @pytest.mark.parametrize("arch", ["vgg8b", "vgg11b"])
    def test_fused_backward_bit_exact_on_paper_cnn(self, arch, kernel_backend):
        """Acceptance criterion: the fused δ prologue ≡ the unfused δ
        composition through every block of both paper CNNs, on every
        runnable backend."""
        cfg = paper.get(arch, scale=0.0625)
        for i, (spec, p, cache, delta) in enumerate(_block_cases(cfg, 2)):
            fused = B.forward_layers_backward(
                p, spec, cache, delta, backend=kernel_backend
            )
            with unfused_delta_path():
                unfused = B.forward_layers_backward(
                    p, spec, cache, delta, backend=kernel_backend
                )
            assert_bitwise_equal(
                fused, unfused, err_msg=f"{arch} block {i} {kernel_backend}"
            )

    def test_pool_and_dropout_precede_the_fused_prologue(self):
        """Blocks with pool + dropout: the jnp pool/dropout backwards stay
        outside the kernels and compose identically on both δ paths."""
        spec = BlockSpec("conv", 12, pool=True, dropout=0.2, d_lr=128)
        cfg = NitroConfig(blocks=(spec,), input_shape=(8, 8, 3),
                          num_classes=10)
        p = M.init_params(jax.random.PRNGKey(0), cfg)["blocks"][0]
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.integers(-127, 128, (3, 8, 8, 3)), jnp.int32)
        _, cache = B.forward_layers(
            p, spec, x, train=True, dropout_key=jax.random.PRNGKey(5)
        )
        delta = jnp.asarray(rng.integers(-63, 64, (3, 4, 4, 12)), jnp.int32)
        fused = B.forward_layers_backward(p, spec, cache, delta)
        with unfused_delta_path():
            unfused = B.forward_layers_backward(p, spec, cache, delta)
        assert_bitwise_equal(fused, unfused)


class TestTrainStepBackwardParity:
    @staticmethod
    def _step_args(cfg, batch, seed=4):
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.integers(-127, 128, (batch, *cfg.input_shape)),
                        jnp.int32)
        y = jnp.asarray(rng.integers(0, cfg.num_classes, batch), jnp.int32)
        return st, x, y, jax.random.PRNGKey(9)

    @pytest.mark.parametrize("arch,batch", [("vgg8b", 8), ("vgg11b", 4)])
    def test_fused_bwd_step_bit_exact(self, arch, batch):
        cfg = paper.get(arch, scale=0.0625)
        st, x, y, key = self._step_args(cfg, batch)
        stepped = {True: jax.jit(functools.partial(les.train_step, cfg=cfg))(
            st, x=x, labels=y, key=key)}
        with unfused_delta_path():
            stepped[False] = jax.jit(functools.partial(
                les.train_step, cfg=cfg))(st, x=x, labels=y, key=key)
        assert_bitwise_equal(stepped[True][0].params, stepped[False][0].params,
                             err_msg=arch)
        assert int(stepped[True][1].loss) == int(stepped[False][1].loss)
        assert_bitwise_equal(stepped[True][1].local_losses,
                             stepped[False][1].local_losses)

    def test_fused_bwd_step_interpret_backend(self):
        """The actual Pallas grad-kernel bodies, off-TPU, end to end."""
        cfg = paper.get("vgg8b", scale=0.0625)
        st, x, y, key = self._step_args(cfg, 4)
        got = jax.jit(functools.partial(
            les.train_step, cfg=cfg, backend="interpret"
        ))(st, x=x, labels=y, key=key)
        with unfused_delta_path():
            want = jax.jit(functools.partial(
                les.train_step, cfg=cfg, backend="reference"
            ))(st, x=x, labels=y, key=key)
        assert_bitwise_equal(got[0].params, want[0].params)

    def test_multi_step_training_stays_exact(self):
        """Divergence compounds: several fused-bwd steps ≡ unfused-δ steps."""
        cfg = NitroConfig(
            blocks=(BlockSpec("conv", 16, pool=True, d_lr=256),
                    BlockSpec("linear", 64)),
            input_shape=(8, 8, 3), num_classes=10, gamma_inv=512,
            eta_fw=20000, eta_lr=5000,
        )
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(-127, 128, (16, 8, 8, 3)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 10, 16), jnp.int32)
        st_f = st_u = les.create_train_state(jax.random.PRNGKey(0), cfg)
        step_f = jax.jit(functools.partial(les.train_step, cfg=cfg))
        step_u = jax.jit(functools.partial(les.train_step, cfg=cfg))
        for i in range(8):
            k = jax.random.PRNGKey(i)
            st_f, _ = step_f(st_f, x=x, labels=y, key=k)
            with unfused_delta_path():  # traced on the first call
                st_u, _ = step_u(st_u, x=x, labels=y, key=k)
        assert_bitwise_equal(st_f.params, st_u.params)


# ---------------------------------------------------------------------------
# Structural: the fused backward never materialises the post-ReLU-bwd δ
# ---------------------------------------------------------------------------


# The primitives that betray a jnp nitro_relu_backward at full tensor
# size: the two `jnp.where` selects and the floor-division remainder.
_MASK_PRIMS = ("select_n", "rem")


def _structural_cfg():
    """Conv + linear blocks, no dropout, widths chosen so the z* shapes
    collide with nothing else in the program (dropout's fixed-point
    floor-div would otherwise share the linear z* shape)."""
    return NitroConfig(
        blocks=(BlockSpec("conv", 16, pool=True, d_lr=256),
                BlockSpec("linear", 48)),
        input_shape=(8, 8, 3), num_classes=10, gamma_inv=512,
        eta_fw=12000, eta_lr=3000,
    )


def _zstar_shapes(cfg, batch):
    """Full-size z*/post-ReLU-bwd δ shapes of every block."""
    h, w, _ = cfg.input_shape
    conv_spec, linear_spec = cfg.blocks
    return {
        (batch, h, w, conv_spec.out_features),
        (batch, linear_spec.out_features),
    }


class TestBackwardStructure:
    @pytest.mark.parametrize("backend", [
        "auto",        # the default train path
        "interpret",   # the actual grad-kernel bodies, off-TPU
    ])
    def test_fused_bwd_step_is_integer_only(self, backend):
        """Acceptance criterion: the fused-backward train step is float-free
        end-to-end, descending into every Pallas kernel body."""
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
        jaxpr = jax.make_jaxpr(functools.partial(
            les.train_step, cfg=cfg, backend=backend
        ))(st, x=x, labels=y, key=jax.random.PRNGKey(1))
        assert_jaxpr_integer_only(jaxpr.jaxpr)

    def test_no_full_size_post_relu_bwd_delta(self):
        """Acceptance criterion: in the fused step, no ReLU-backward op
        (select/rem) produces a full-size z*-shaped tensor anywhere outside
        a Pallas kernel body — the masked δ exists only as VMEM tiles.  The
        unfused δ composition (sanity) does materialise it."""
        cfg = _structural_cfg()
        batch = 6
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(-127, 128, (batch, 8, 8, 3)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 10, batch), jnp.int32)
        zstar_shapes = _zstar_shapes(cfg, batch)

        def mask_shapes():
            jaxpr = jax.make_jaxpr(functools.partial(
                les.train_step, cfg=cfg, backend="interpret",
            ))(st, x=x, labels=y, key=jax.random.PRNGKey(1))
            return set(
                eqn_output_shapes(jaxpr.jaxpr, _MASK_PRIMS, skip_pallas=True)
            )

        assert not (mask_shapes() & zstar_shapes), (
            "fused backward materialised a full-size post-ReLU-bwd δ"
        )
        with unfused_delta_path():
            unfused = mask_shapes()
        assert unfused & zstar_shapes, (
            "sanity: the unfused δ path should materialise the masked δ"
        )

    def test_forward_fusion_also_holds(self):
        """The same scan proves the *forward* ReLU stays in-kernel too —
        the fused step has no full-size z*-producing select at all."""
        cfg = _structural_cfg()
        batch = 6
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        x = jnp.zeros((batch, 8, 8, 3), jnp.int32)
        y = jnp.zeros((batch,), jnp.int32)
        jaxpr = jax.make_jaxpr(functools.partial(
            les.train_step, cfg=cfg, fused=False, backend="interpret",
        ))(st, x=x, labels=y, key=jax.random.PRNGKey(1))
        # unfused *forward* still materialises z*-shaped selects (sanity
        # that the discriminator sees forward activations as well)
        shapes = set(
            eqn_output_shapes(jaxpr.jaxpr, _MASK_PRIMS, skip_pallas=True)
        )
        assert shapes & _zstar_shapes(cfg, batch)
