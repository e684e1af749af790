"""Fused IntegerSGD epilogue (``fuse_opt``): kernel contract + parity.

The tentpole guarantee of ISSUE 10: applying the IntegerSGD update in the
gradient kernels' *flush* — read the W tile, write W′, never materialise
grad_W in HBM — changes nothing numerically.  Integer floor-division over
an order-exact int32 accumulation is exact, so

    fused-epilogue step  ≡  compute_gradients → apply_gradients

bit for bit, on both paper configs, every runnable backend, over
multi-step trajectories.  On top of parity, the fused
path is held to its structural claims: no full-size grad_W-shaped
floor-division output exists outside a Pallas kernel body, and the whole
fused-opt step stays float-free.

Parity assertions go through ``tests/_gradcheck.py``.
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
)
from repro.configs import paper
from repro.core import blocks as B
from repro.core import les, model as M
from repro.core import optimizer as opt
from repro.core.blocks import BlockSpec
from repro.core.model import NitroConfig
from repro.kernels import grad_ops
from repro.kernels.nitro_conv import conv_grad_w, conv_grad_w_opt
from repro.kernels.nitro_matmul import grad_w_matmul, grad_w_opt_matmul
from repro.kernels.nitro_matmul.ref import masked_delta


def _linear_case(b, m, n, seed=0):
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


OPT = opt.init_state(512, 12000)
OPT_NO_DECAY = opt.init_state(512, 0)


# ---------------------------------------------------------------------------
# Kernel/dispatcher level: the flush epilogue ≡ grad-then-update
# ---------------------------------------------------------------------------


class TestLinearOptEpilogue:
    @pytest.mark.parametrize("state", [OPT, OPT_NO_DECAY],
                             ids=["decay", "no-decay"])
    def test_matches_grad_then_update(self, kernel_backend, state):
        x, delta, z_star, w = _linear_case(12, 40, 24, seed=1)
        got = grad_w_opt_matmul(
            x, delta, z_star, w, state.gamma_inv, state.eta_inv,
            backend=kernel_backend,
        )
        grad_w = grad_w_matmul(x, delta, z_star, backend=kernel_backend)
        assert_bitwise_equal(got, opt.apply_update(w, grad_w, state),
                             err_msg=kernel_backend)

    def test_backend_pair_parity(self, backend_pair):
        # ragged dims on purpose: the epilogue must be exact through the
        # tile padding (padded acc = 0, padded w = 0 → W' = 0, sliced off)
        x, delta, z_star, w = _linear_case(9, 130, 70, seed=2)
        a, b = (
            grad_w_opt_matmul(
                x, delta, z_star, w, OPT.gamma_inv, OPT.eta_inv, backend=bk
            )
            for bk in backend_pair
        )
        assert_bitwise_equal(a, b, err_msg=str(backend_pair))

    def test_dispatcher_escape_hatches(self, kernel_backend):
        """z_star=None routes through the materialised gradient +
        ``opt.apply_update``: fed the pre-masked δ it equals the fused
        epilogue, bitwise."""
        x, delta, z_star, w = _linear_case(8, 32, 16, seed=3)
        want_gx, want_w = grad_ops.linear_weight_update(
            x, w, delta, OPT, z_star=z_star, backend=kernel_backend
        )
        g = masked_delta(delta, z_star, 10)
        for d in (g, delta):
            got_gx, got_w = grad_ops.linear_weight_update(
                x, w, d, OPT, backend=kernel_backend
            )
            if d is g:
                assert_bitwise_equal(got_w, want_w, err_msg="pre-masked")
                assert_bitwise_equal(got_gx, want_gx, err_msg="pre-masked")
            else:
                # no z*: STE-only backward — different math by design;
                # still must equal its own grad-then-update composition
                _, gw = grad_ops.linear_grads(x, w, delta)
                assert_bitwise_equal(got_w, opt.apply_update(w, gw, OPT))


class TestConvOptEpilogue:
    @pytest.mark.parametrize("state", [OPT, OPT_NO_DECAY],
                             ids=["decay", "no-decay"])
    def test_matches_grad_then_update(self, kernel_backend, state):
        x, delta, z_star, w = _conv_case(2, 8, 6, 3, 8, 3, seed=4)
        got = conv_grad_w_opt(
            x, delta, w, state.gamma_inv, state.eta_inv,
            kernel_size=3, z_star=z_star, backend=kernel_backend,
        )
        grad_w = conv_grad_w(
            x, delta, kernel_size=3, z_star=z_star, backend=kernel_backend
        )
        assert_bitwise_equal(got, opt.apply_update(w, grad_w, state),
                             err_msg=kernel_backend)

    def test_backend_pair_parity(self, backend_pair):
        x, delta, z_star, w = _conv_case(2, 9, 7, 3, 5, 3, seed=5)
        a, b = (
            conv_grad_w_opt(
                x, delta, w, OPT.gamma_inv, OPT.eta_inv,
                kernel_size=3, z_star=z_star, backend=bk
            )
            for bk in backend_pair
        )
        assert_bitwise_equal(a, b, err_msg=str(backend_pair))

    def test_weight_update_escape_hatches(self, kernel_backend):
        """``conv_weight_update`` without ``z_star`` takes the
        grad-then-update hatch: fed the pre-masked δ it is bitwise equal
        to the fused epilogue."""
        x, delta, z_star, w = _conv_case(2, 8, 6, 3, 8, 3, seed=7)
        want_gx, want_w = grad_ops.conv_weight_update(
            x, w, delta, OPT, z_star=z_star, backend=kernel_backend
        )
        got_gx, got_w = grad_ops.conv_weight_update(
            x, w, masked_delta(delta, z_star, 10), OPT, backend=kernel_backend
        )
        assert_bitwise_equal(got_w, want_w)
        assert_bitwise_equal(got_gx, want_gx)


# ---------------------------------------------------------------------------
# Train-step level: fuse_opt ≡ the split composition, multi-step
# ---------------------------------------------------------------------------


def _step_args(cfg, batch, seed=4):
    st = les.create_train_state(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 128, (batch, *cfg.input_shape)),
                    jnp.int32)
    y = jnp.asarray(rng.integers(0, cfg.num_classes, batch), jnp.int32)
    return st, x, y


class TestTrainStepFuseOptParity:
    @pytest.mark.parametrize("arch,batch", [("vgg8b", 4), ("vgg11b", 2)])
    def test_multi_step_trajectory_bit_exact(self, arch, batch,
                                             kernel_backend):
        """Acceptance criterion: multi-step fuse_opt trajectory ≡ the
        split composition on both paper configs, every runnable backend.
        Divergence compounds, so trajectory equality is strictly stronger
        than single-step equality."""
        cfg = paper.get(arch, scale=0.0625)
        st_f, x, y = _step_args(cfg, batch)
        st_u = st_f
        step_f = jax.jit(functools.partial(
            les.train_step, cfg=cfg, fuse_opt=True, backend=kernel_backend))
        step_u = jax.jit(functools.partial(
            les.train_step, cfg=cfg, fuse_opt=False, backend=kernel_backend))
        for i in range(3):
            k = jax.random.PRNGKey(i)
            st_f, m_f = step_f(st_f, x=x, labels=y, key=k)
            st_u, m_u = step_u(st_u, x=x, labels=y, key=k)
        assert_bitwise_equal(st_f, st_u,
                             err_msg=f"{arch}/{kernel_backend}")
        assert_bitwise_equal(m_f, m_u)

    def test_unfused_forward_also_exact(self):
        """fuse_opt composes with the unfused forward escape hatch too
        (z* is cached either way)."""
        cfg = paper.get("vgg8b", scale=0.0625)
        st, x, y = _step_args(cfg, 4)
        key = jax.random.PRNGKey(3)
        got = jax.jit(functools.partial(
            les.train_step, cfg=cfg, fused=False, fuse_opt=True))(
            st, x=x, labels=y, key=key)
        want = jax.jit(functools.partial(
            les.train_step, cfg=cfg, fused=False))(st, x=x, labels=y, key=key)
        assert_bitwise_equal(got[0], want[0])

    def test_telemetry_falls_back_to_split_path(self):
        """telemetry needs the materialised fw gradients, so
        ``fuse_opt=True, telemetry=True`` runs the split path — same
        trajectory, same telemetry as the plain telemetry step."""
        cfg = paper.get("vgg8b", scale=0.0625)
        st, x, y = _step_args(cfg, 4)
        key = jax.random.PRNGKey(5)
        st_a, m_a, telem_a = jax.jit(functools.partial(
            les.train_step, cfg=cfg, fuse_opt=True, telemetry=True))(
            st, x=x, labels=y, key=key)
        st_b, m_b, telem_b = jax.jit(functools.partial(
            les.train_step, cfg=cfg, telemetry=True))(
            st, x=x, labels=y, key=key)
        st_c, _ = jax.jit(functools.partial(
            les.train_step, cfg=cfg, fuse_opt=True))(st, x=x, labels=y, key=key)
        assert_bitwise_equal(st_a, st_b)
        assert_bitwise_equal(telem_a, telem_b)
        assert_bitwise_equal(st_a, st_c)  # fused fast path agrees too

    def test_apply_gradients_fused_kernel_path(self, kernel_backend):
        """``apply_gradients(fuse_opt=True)`` — the DP post-reduce apply —
        is bitwise ``apply_gradients`` through the standalone kernel."""
        cfg = paper.get("vgg8b", scale=0.0625)
        st, x, y = _step_args(cfg, 4)
        grads, _, _ = les.compute_gradients(st, cfg, x, y,
                                            jax.random.PRNGKey(2))
        got = les.apply_gradients(st, grads, fuse_opt=True,
                                  backend=kernel_backend)
        want = les.apply_gradients(st, grads)
        assert_bitwise_equal(got, want, err_msg=kernel_backend)


# ---------------------------------------------------------------------------
# Structural: grad_W never materialises, and the step stays float-free
# ---------------------------------------------------------------------------


# floor_divide lowers to div/rem/select_n, and the jnp update's
# reciprocal division (``numerics.floor_div_by``) to mul and shifts beside
# its decay select_n; any IntegerSGD update running *outside* a Pallas
# kernel body betrays itself with one of these at the updated tensor's
# full shape.
_UPDATE_PRIMS = ("div", "rem", "select_n", "mul")


def _structural_cfg():
    """Widths chosen so the fw-weight shapes collide with nothing else:
    the conv fw weight is the only 4-D tensor, and (256, 48) matches no
    lr/output weight (those end in num_classes=10)."""
    return NitroConfig(
        blocks=(BlockSpec("conv", 16, pool=True, d_lr=256),
                BlockSpec("linear", 48)),
        input_shape=(8, 8, 3), num_classes=10, gamma_inv=512,
        eta_fw=12000, eta_lr=3000,
    )


def _fw_weight_shapes(st):
    return {tuple(p["fw"]["w"].shape) for p in st.params["blocks"]}


class TestFuseOptStructure:
    @pytest.mark.parametrize("backend", ["auto", "interpret"])
    def test_fused_opt_step_is_integer_only(self, backend):
        """Acceptance criterion: the fused-epilogue step is float-free
        end-to-end, descending into every Pallas kernel body."""
        cfg = _structural_cfg()
        st, x, y = _step_args(cfg, 6)
        jaxpr = jax.make_jaxpr(functools.partial(
            les.train_step, cfg=cfg, fuse_opt=True, backend=backend
        ))(st, x=x, labels=y, key=jax.random.PRNGKey(1))
        assert_jaxpr_integer_only(jaxpr.jaxpr)

    def test_no_full_size_grad_w_update_outside_kernels(self):
        """Acceptance criterion: in the fused-opt step no floor-division
        output of a forward-layer weight shape exists outside a Pallas
        kernel body — the update happens in the flush, on VMEM tiles.
        (W′ shares grad_W's shape, so scanning for the *division*
        primitives, not raw avals, is what discriminates: the kernel
        output W′ is legitimate; a div/rem/select at that shape is not.)
        The split step (sanity) shows exactly those shapes."""
        cfg = _structural_cfg()
        st, x, y = _step_args(cfg, 6)
        fw_shapes = _fw_weight_shapes(st)

        def update_shapes(fuse_opt):
            jaxpr = jax.make_jaxpr(functools.partial(
                les.train_step, cfg=cfg, fuse_opt=fuse_opt,
                backend="interpret",
            ))(st, x=x, labels=y, key=jax.random.PRNGKey(1))
            return set(eqn_output_shapes(
                jaxpr.jaxpr, _UPDATE_PRIMS, skip_pallas=True))

        assert not (update_shapes(True) & fw_shapes), (
            "fused-opt step ran an IntegerSGD floor-division on a "
            "full-size fw weight outside the kernels"
        )
        assert update_shapes(False) & fw_shapes, (
            "sanity: the split step should update fw weights in jnp"
        )

    def test_lr_and_output_updates_stay_jnp(self):
        """The learning/output layers keep the jnp update on the fused
        path (their backward has no flush): their weight shapes *do*
        appear — proof the scan above is looking at the right thing."""
        cfg = _structural_cfg()
        st, x, y = _step_args(cfg, 6)
        lr_shapes = {tuple(p["lr"]["w"].shape) for p in st.params["blocks"]}
        lr_shapes.add(tuple(st.params["output"]["w"].shape))
        jaxpr = jax.make_jaxpr(functools.partial(
            les.train_step, cfg=cfg, fuse_opt=True, backend="interpret",
        ))(st, x=x, labels=y, key=jax.random.PRNGKey(1))
        shapes = set(eqn_output_shapes(
            jaxpr.jaxpr, _UPDATE_PRIMS, skip_pallas=True))
        assert shapes & lr_shapes
