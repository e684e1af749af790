"""Parity + contract suite for ``repro.kernels.integer_sgd``.

The package predated the shared parity harness and the coverage floor;
this file folds it into both, and pins the two dormant-path behaviours
ISSUE 10 fixed:

  * **kernel ≡ ref ≡ optimizer.apply_update**, bitwise, via the
    ``_gradcheck`` backend fixtures — including ragged-tail shapes that
    exercise the (rows, 128) lane padding and ``η_inv = 0`` (decay off);
  * the ``apply_tree_fused`` dispatcher contract: ``backend=`` vocabulary,
    the contradictory ``use_kernel=False``/``interpret=True`` legacy-knob
    ValueError (previously silently resolved in favour of ``use_kernel``),
    an explicit ``interpret=True`` actually selecting the interpreter, and
    ``numerics.assert_int`` validation on every leaf (previously only the
    jnp path validated);
  * the floor-division decay **asymmetry** (hypothesis property): for
    ``0 ≤ w < η_inv`` decay is 0, but every ``−η_inv ≤ w < 0`` decays by
    −1 — i.e. ``w ← w + 1`` at zero gradient — matching Algorithm 1's
    floor semantics exactly (the docstring used to claim the small-|w|
    decay was zero on both sides).
"""

from __future__ import annotations

import warnings

import jax
from jax.extend import core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from _gradcheck import assert_bitwise_equal, backend_pair, kernel_backend  # noqa: F401
from repro.core import optimizer as opt
from repro.core.numerics import floor_div
from repro.kernels.integer_sgd.integer_sgd import (
    integer_sgd_tile,
    integer_sgd_update,
)
from repro.kernels.integer_sgd.ops import apply_tree_fused
from repro.kernels.integer_sgd.ref import integer_sgd_ref

# Ragged tails on purpose: (7,) under one lane, (129,) one over, (130, 3)
# both rows and lanes ragged, (8, 128) the exact native tile.
SHAPES = [(7,), (3, 5), (129,), (8, 128), (130, 3)]
ETAS = [0, 3000]


def _case(shape, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.integers(-9000, 9000, shape), jnp.int32)
    g = jnp.asarray(rng.integers(-(2 ** 17), 2 ** 17, shape), jnp.int32)
    return w, g


def _tree_apply(w, g, gamma_inv, eta_inv, backend):
    state = opt.init_state(gamma_inv, eta_inv)
    return apply_tree_fused({"w": w}, {"w": g}, state, backend=backend)["w"]


class TestKernelParity:
    @pytest.mark.parametrize("eta_inv", ETAS)
    @pytest.mark.parametrize("shape", SHAPES, ids=str)
    def test_kernel_matches_apply_update(self, shape, eta_inv):
        """The Pallas kernel (interpreted off-TPU) ≡ the jnp Algorithm 1,
        bitwise, across ragged padding shapes and decay on/off."""
        w, g = _case(shape, seed=len(shape))
        state = opt.init_state(512, eta_inv)
        got = integer_sgd_update(
            w, g, state.gamma_inv, state.eta_inv, interpret=True
        )
        assert_bitwise_equal(got, opt.apply_update(w, g, state),
                             err_msg=f"{shape} eta={eta_inv}")

    @pytest.mark.parametrize("eta_inv", ETAS)
    def test_ref_matches_apply_update(self, eta_inv):
        w, g = _case((37, 11), seed=3)
        state = opt.init_state(512, eta_inv)
        assert_bitwise_equal(
            integer_sgd_ref(w, g, state.gamma_inv, state.eta_inv),
            opt.apply_update(w, g, state),
        )

    def test_tile_is_the_shared_epilogue_expression(self):
        """``integer_sgd_tile`` (the grad-kernel flush epilogue body) is
        the same function the standalone kernel and the jnp path compute."""
        w, g = _case((64, 128), seed=5)
        state = opt.init_state(1536, 12000)
        assert_bitwise_equal(
            integer_sgd_tile(w, g, state.gamma_inv, state.eta_inv),
            opt.apply_update(w, g, state),
        )

    @pytest.mark.parametrize("eta_inv", ETAS)
    @pytest.mark.parametrize("shape", SHAPES, ids=str)
    def test_backend_pair_parity(self, backend_pair, shape, eta_inv):
        """Every runnable backend pairing agrees bitwise through the
        ``apply_tree_fused`` dispatcher."""
        w, g = _case(shape, seed=7)
        a = _tree_apply(w, g, 512, eta_inv, backend_pair[0])
        b = _tree_apply(w, g, 512, eta_inv, backend_pair[1])
        assert_bitwise_equal(a, b, err_msg=f"{backend_pair} {shape}")

    def test_tree_structure_preserved(self, kernel_backend):
        state = opt.init_state(512, 3000)
        params = {"a": _case((5,), 1)[0], "b": {"c": _case((4, 6), 2)[0]}}
        grads = {"a": _case((5,), 1)[1], "b": {"c": _case((4, 6), 2)[1]}}
        got = apply_tree_fused(params, grads, state, backend=kernel_backend)
        want = opt.apply_tree(params, grads, state)
        assert_bitwise_equal(got, want)


class TestDispatcherContract:
    def _args(self):
        w, g = _case((6, 9), seed=11)
        return {"w": w}, {"w": g}, opt.init_state(512, 3000)

    def test_contradictory_legacy_knobs_raise(self):
        """use_kernel=False + interpret=True used to silently drop the
        interpreter request; it is now the same ValueError class PR 5
        introduced for ``nitro_matmul.ops._legacy_backend``."""
        p, g, s = self._args()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            with pytest.raises(ValueError, match="contradictory"):
                apply_tree_fused(p, g, s, use_kernel=False, interpret=True)

    def test_backend_and_legacy_knobs_are_exclusive(self):
        p, g, s = self._args()
        with pytest.raises(ValueError, match="not both"):
            apply_tree_fused(p, g, s, backend="reference", use_kernel=True)
        with pytest.raises(ValueError, match="not both"):
            apply_tree_fused(p, g, s, backend="auto", interpret=False)

    def test_unknown_backend_rejected(self):
        p, g, s = self._args()
        with pytest.raises(ValueError, match="backend"):
            apply_tree_fused(p, g, s, backend="cuda")

    def test_legacy_knobs_warn_deprecation(self):
        p, g, s = self._args()
        with pytest.warns(DeprecationWarning, match="use_kernel"):
            apply_tree_fused(p, g, s, use_kernel=False)

    def test_explicit_interpret_selects_the_kernel(self):
        """interpret=True with use_kernel unset must run the Pallas
        interpreter (a ``pallas_call`` in the jaxpr), not fall through to
        the jnp reference because the host has no TPU."""
        p, g, s = self._args()

        def step(pp, gg):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                return apply_tree_fused(pp, gg, s, interpret=True)

        jaxpr = jax.make_jaxpr(step)(p, g)
        prims = {e.primitive.name for e in jaxpr.eqns}

        def all_prims(jx):
            out = set()
            for e in jx.eqns:
                out.add(e.primitive.name)
                for param in e.params.values():
                    items = param if isinstance(param, (tuple, list)) else [param]
                    for it in items:
                        if isinstance(it, jex_core.ClosedJaxpr):
                            out |= all_prims(it.jaxpr)
            return out

        assert "pallas_call" in all_prims(jaxpr.jaxpr), prims
        # and it still agrees with the reference, bitwise
        assert_bitwise_equal(step(p, g), opt.apply_tree(p, g, s))

    def test_float_leaves_rejected_on_every_path(self):
        """The kernel wrapper now validates like ``opt.apply_update``."""
        p, g, s = self._args()
        bad_p = {"w": p["w"].astype(jnp.float32)}
        bad_g = {"w": g["w"].astype(jnp.float32)}
        for backend in ("reference", "interpret"):
            with pytest.raises(TypeError, match="weight"):
                apply_tree_fused(bad_p, g, s, backend=backend)
            with pytest.raises(TypeError, match="gradient"):
                apply_tree_fused(p, bad_g, s, backend=backend)


class TestDecayAsymmetry:
    """Pin the floor-division decay semantics (satellite 2).

    Algorithm 1's decay term is ⌊w/η_inv⌋ with floor (round toward −∞)
    semantics.  The old docstring claimed it "zeroes" for |w| < η_inv;
    in fact that holds only for 0 ≤ w < η_inv — every small *negative*
    weight decays by −1, i.e. gains +1 per zero-gradient step.
    """

    @settings(max_examples=200, deadline=None)
    @given(w=st.integers(-2999, -1), eta_inv=st.integers(1, 3000))
    def test_small_negative_weights_step_toward_zero(self, w, eta_inv):
        if w < -eta_inv:
            w = -(abs(w) % eta_inv) or -1  # keep −η_inv < w < 0
        state = opt.init_state(512, eta_inv)
        new_w = opt.apply_update(
            jnp.asarray([w], jnp.int32), jnp.asarray([0], jnp.int32), state
        )
        assert int(new_w[0]) == w + 1, (w, eta_inv)

    @settings(max_examples=200, deadline=None)
    @given(w=st.integers(0, 2999), eta_inv=st.integers(1, 3000))
    def test_small_positive_weights_are_untouched(self, w, eta_inv):
        w = w % eta_inv  # keep 0 ≤ w < η_inv
        state = opt.init_state(512, eta_inv)
        new_w = opt.apply_update(
            jnp.asarray([w], jnp.int32), jnp.asarray([0], jnp.int32), state
        )
        assert int(new_w[0]) == w, (w, eta_inv)

    @settings(max_examples=200, deadline=None)
    @given(w=st.integers(-(2 ** 20), 2 ** 20), eta_inv=st.integers(1, 30000),
           g=st.integers(-(2 ** 20), 2 ** 20))
    def test_update_matches_pure_python_floor(self, w, eta_inv, g):
        """The whole update against Python's // (true floor division)."""
        gamma_inv = 512
        state = opt.init_state(gamma_inv, eta_inv)
        got = opt.apply_update(
            jnp.asarray([w], jnp.int32), jnp.asarray([g], jnp.int32), state
        )
        want = w - (g // gamma_inv + w // eta_inv)
        assert int(got[0]) == want

    def test_negative_weight_trajectory_reaches_zero_and_stays(self):
        """At zero gradient a small negative weight climbs one unit per
        step until it reaches 0, then never moves again."""
        state = opt.init_state(512, 3000)
        w = jnp.asarray([-4], jnp.int32)
        g = jnp.zeros_like(w)
        seen = []
        for _ in range(7):
            w = opt.apply_update(w, g, state)
            seen.append(int(w[0]))
        assert seen == [-3, -2, -1, 0, 0, 0, 0]

    def test_floor_div_is_floor(self):
        """Anchor: ``numerics.floor_div`` rounds toward −∞, not zero."""
        got = floor_div(jnp.asarray([-1, -2999, 1, 2999], jnp.int32),
                        jnp.asarray(3000, jnp.int32))
        np.testing.assert_array_equal(np.asarray(got), [-1, -1, 0, 0])
