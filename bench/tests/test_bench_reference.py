"""The plain reference agrees with the program bit for bit where it must,
and the configuration files are the paper's configurations."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _tiny import REPO, shrink

from bench import data, model, reference


def _cfg(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["vgg8b", "mlp4"])
def test_config_file_is_the_papers(name):
    from repro.configs import paper

    assert model.nitro_config(_cfg(name)) == paper.get(name)


@pytest.mark.parametrize("name", ["vgg8b", "mlp4"])
def test_three_training_steps_bitwise(name):
    from repro.core import les

    cfg = shrink(_cfg(name))
    key = model.seed_key(2**31 + 99)
    params = model.init_params(cfg, key)
    shape = tuple(cfg.get("image_shape", cfg["input_shape"]))
    x, y = data.images(key, n=24, shape=shape, num_classes=10)
    x = x.reshape(3, 8, *cfg["input_shape"])
    y = y.reshape(3, 8)
    keys = jax.random.split(key, 3)
    state = model.train_state(cfg, params)
    step = jax.jit(functools.partial(les.train_step,
                                     cfg=model.nitro_config(cfg)))
    ref = jax.jit(functools.partial(reference.train_step, cfg))
    p = params
    for i in range(3):
        state, m = step(state, x=x[i], labels=y[i], key=keys[i])
        p, rm = ref(p, x[i], y[i], keys[i])
        for a, b in zip(jax.tree_util.tree_leaves(state.params),
                        jax.tree_util.tree_leaves(p)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(m.loss) == int(rm["loss"])
        assert int(m.correct) == int(rm["correct"])
        np.testing.assert_array_equal(np.asarray(m.local_losses),
                                      np.asarray(rm["local_losses"]))
    moved = sum(int(np.sum(np.asarray(a) != np.asarray(b)))
                for a, b in zip(jax.tree_util.tree_leaves(p),
                                jax.tree_util.tree_leaves(params)))
    assert moved > 0


def test_served_forward_bitwise_and_not_trivial():
    from repro.infer import compile_plan, freeze

    cfg = shrink(_cfg("vgg8b"))
    key = model.seed_key(7)
    params = model.init_params(cfg, key, mode="served")
    x, _ = data.images(key, n=16, shape=(32, 32, 3), num_classes=10)
    plan = compile_plan(freeze(params, model.nitro_config(cfg)))
    got = np.asarray(plan.logits(np.asarray(x)))
    want = np.asarray(reference.forward(cfg, params, x)[0])
    np.testing.assert_array_equal(got, want)
    # trained-like weights: logits spread, and int16 accumulation differs
    assert np.std(want) > 5
    low = np.asarray(reference.forward(cfg, params, x,
                                       acc_dtype=jnp.int16)[0])
    assert np.any(low != want)


def test_served_weights_are_int16_and_training_weights_kaiming():
    cfg = _cfg("vgg8b")
    bounds = model.weight_bounds(cfg, "served")
    assert all(0 < b <= 2**15 - 1 for _, b in bounds)
    kb = dict(((tuple(s), b) for s, b in model.weight_bounds(cfg, "kaiming")))
    assert kb[(3, 3, 3, 128)] == (128 * 1732) // (5 * 1000)


def test_images_are_seeded_distinct_and_in_range():
    key = model.seed_key(3_000_000_001)
    x1, y1 = data.images(key, n=64, shape=(32, 32, 3), num_classes=10)
    x2, _ = data.images(key, n=64, shape=(32, 32, 3), num_classes=10)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    flat = np.asarray(x1).reshape(64, -1)
    assert len({r.tobytes() for r in flat}) == 64
    assert flat.min() >= -127 and flat.max() <= 127
    assert 40 < flat.std() < 90
    assert set(np.asarray(y1).tolist()) <= set(range(10))
