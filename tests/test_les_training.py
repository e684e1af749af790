"""System tests for the NITRO-D learning algorithm (integer-only LES)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _gradcheck import assert_bitwise_equal, assert_jaxpr_integer_only
from repro.core import les, model
from repro.core.blocks import BlockSpec
from repro.core.model import NitroConfig
from repro.data import synthetic


def tiny_cnn_cfg(**kw):
    return NitroConfig(
        blocks=(
            BlockSpec("conv", 16, pool=True, d_lr=256),
            BlockSpec("linear", 64),
        ),
        input_shape=(8, 8, 3),
        num_classes=10,
        gamma_inv=512,
        **kw,
    )


@pytest.fixture(scope="module")
def toy_data():
    rng = np.random.default_rng(0)
    templates = rng.integers(-60, 61, (10, 8, 8, 3))
    y = rng.integers(0, 10, 256).astype(np.int32)
    x = np.clip(templates[y] + rng.integers(-40, 41, (256, 8, 8, 3)), -127, 127)
    return jnp.asarray(x.astype(np.int32)), jnp.asarray(y)


class TestTrainStep:
    @pytest.mark.parametrize("fused,backend", [
        (True, "auto"),        # the default train path (fwd + bwd fused)
        (True, "interpret"),   # the actual Pallas kernel bodies, off-TPU
        (False, "auto"),       # unfused forward reference composition
    ])
    def test_step_is_integer_only(self, toy_data, fused, backend):
        """No float dtype anywhere in the jit-compiled training step —
        fused forward *and* fused backward (including inside the Pallas
        kernel jaxprs), plus the unfused forward oracle."""
        cfg = NitroConfig(
            blocks=(BlockSpec("conv", 16, pool=True, d_lr=256, dropout=0.1),
                    BlockSpec("linear", 64, dropout=0.1)),
            input_shape=(8, 8, 3), num_classes=10, gamma_inv=512,
            eta_fw=12000, eta_lr=3000,
        )
        x, y = toy_data
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        jaxpr = jax.make_jaxpr(
            functools.partial(les.train_step, cfg=cfg, fused=fused,
                              backend=backend)
        )(st, x=x[:8], labels=y[:8], key=jax.random.PRNGKey(1))
        assert_jaxpr_integer_only(jaxpr.jaxpr)

    def test_loss_decreases_on_learnable_task(self, toy_data):
        x, y = toy_data
        cfg = tiny_cnn_cfg(eta_fw=20000, eta_lr=5000)
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        step = jax.jit(functools.partial(les.train_step, cfg=cfg))
        first = None
        for i in range(120):
            st, m = step(st, x=x[:64], labels=y[:64], key=jax.random.PRNGKey(i))
            if first is None:
                first = int(m.local_losses[0])
        # block-0's local loss must fall well below its starting value
        assert int(m.local_losses[0]) < 0.7 * first
        assert int(m.correct) > 6  # above 10% chance on 64 samples

    def test_weights_stay_int16(self, toy_data):
        """Paper §E.3: trained weights fit int16."""
        x, y = toy_data
        cfg = tiny_cnn_cfg(eta_fw=20000, eta_lr=5000)
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        step = jax.jit(functools.partial(les.train_step, cfg=cfg))
        for i in range(60):
            st, _ = step(st, x=x[:64], labels=y[:64], key=jax.random.PRNGKey(i))
        mx = max(int(jnp.abs(p).max()) for p in jax.tree_util.tree_leaves(st.params))
        assert mx < 2**15

    def test_activations_stay_int8(self, toy_data):
        x, y = toy_data
        cfg = tiny_cnn_cfg()
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        _, acts, _, _ = model.forward(st.params, cfg, x[:32], train=False)
        for a in acts:
            assert int(jnp.abs(a).max()) <= 127

    def test_block_gradient_confinement(self, toy_data):
        """LES property: block-0's update is independent of block-1's and
        the output layer's parameters (gradients never cross blocks)."""
        x, y = toy_data
        cfg = tiny_cnn_cfg()
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        step = jax.jit(functools.partial(les.train_step, cfg=cfg))
        st_a, _ = step(st, x=x[:32], labels=y[:32], key=jax.random.PRNGKey(5))

        # perturb downstream params; block-0 update must not change
        mutated = jax.tree_util.tree_map(lambda p: p, st.params)
        mutated["blocks"][1]["fw"]["w"] = mutated["blocks"][1]["fw"]["w"] + 3
        mutated["output"]["w"] = mutated["output"]["w"] - 7
        st_b, _ = step(
            st._replace(params=mutated), x=x[:32], labels=y[:32],
            key=jax.random.PRNGKey(5),
        )
        assert_bitwise_equal(
            st_a.params["blocks"][0], st_b.params["blocks"][0]
        )

    def test_eval_step_counts_correct(self, toy_data):
        x, y = toy_data
        cfg = tiny_cnn_cfg()
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        correct = les.eval_step(st, cfg, x[:50], y[:50])
        assert 0 <= int(correct) <= 50

    def test_lr_plateau_schedule(self):
        cfg = tiny_cnn_cfg()
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        g0 = int(st.opt_lr.gamma_inv)
        st = les.reduce_lr_on_plateau(st, True)
        assert int(st.opt_lr.gamma_inv) == 3 * g0
        assert int(st.opt_fw.gamma_inv) == 3 * g0 * 640  # AF = 2^6·10


class TestMLPPath:
    def test_mlp_trains(self):
        """MLP-1-like architecture (paper Table 4) on flattened data."""
        ds = synthetic.make_image_dataset("digits28", n_train=256, n_test=64)
        ds = synthetic.flatten_for_mlp(ds)
        cfg = NitroConfig(
            blocks=(BlockSpec("linear", 100), BlockSpec("linear", 50)),
            input_shape=ds.input_shape, num_classes=10,
            gamma_inv=512, eta_fw=12000, eta_lr=3000,
        )
        st = les.create_train_state(jax.random.PRNGKey(0), cfg)
        step = jax.jit(functools.partial(les.train_step, cfg=cfg))
        x = jnp.asarray(ds.x_train[:64])
        y = jnp.asarray(ds.y_train[:64])
        first = None
        for i in range(200):
            st, m = step(st, x=x, labels=y, key=jax.random.PRNGKey(i))
            if first is None:
                first = int(m.local_losses[0])
        assert int(m.local_losses[0]) < 0.8 * first  # block-0 is learning
        assert int(m.correct) > 10  # above 10 % chance (6.4 expected)
