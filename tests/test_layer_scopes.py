"""The LES step's layer scopes reach the compiled program, and every
Pallas kernel carries its own name.

``repro.obs.layers`` names the parts of a training step with
``jax.named_scope``; the compiled executable keeps each scope in the
``op_name`` metadata of the instructions made from it, which is what maps
a profile's device ops to layers.  The scopes change no value: the
bitwise tests of the train step (``test_les_training``, ``test_fuse_opt``,
``test_data_parallel``) run the scoped program against its references.
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import les
from repro.core.blocks import BlockSpec
from repro.core.model import NitroConfig
from repro.obs import layers

NM = importlib.import_module("repro.kernels.nitro_matmul.nitro_matmul")
NC = importlib.import_module("repro.kernels.nitro_conv.nitro_conv")
from repro.kernels.integer_sgd.integer_sgd import integer_sgd_update  # noqa: E402

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                   "src")
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')

CONV_CFG = NitroConfig(
    blocks=(BlockSpec("conv", 16, pool=True, d_lr=256, dropout=0.1),
            BlockSpec("conv", 16, d_lr=256),
            BlockSpec("linear", 32)),
    input_shape=(8, 8, 3), num_classes=10, gamma_inv=512)
MLP_CFG = NitroConfig(
    blocks=(BlockSpec("linear", 32, dropout=0.1), BlockSpec("linear", 32)),
    input_shape=(48,), num_classes=10, gamma_inv=512)
CONFIGS = {"conv": CONV_CFG, "mlp": MLP_CFG}


def _inputs(cfg, batch=8):
    state = les.create_train_state(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((batch, *cfg.input_shape), jnp.int32)
    labels = jnp.arange(batch, dtype=jnp.int32) % cfg.num_classes
    return state, x, labels, jax.random.PRNGKey(1)


def _compiled_text(cfg, **kw) -> str:
    state, x, labels, key = _inputs(cfg)
    step = jax.jit(functools.partial(les.train_step, cfg=cfg, **kw))
    return step.lower(state, x=x, labels=labels, key=key).compile().as_text()


def _scopes_in(paths) -> set:
    """Every scope of the vocabulary that some ``op_name`` path holds."""
    out = set()
    for p in paths:
        for m in re.finditer(r"(?:^|/)(block\d+/(?:forward|local_loss|"
                             r"backward)|output|update|dp/reduce_gradients)"
                             r"(?=/|$)", p):
            out.add(m.group(1))
    return out


def _expected(num_blocks: int) -> set:
    return {layers.block_scope(i, part) for i in range(num_blocks)
            for part in (layers.FORWARD, layers.LOCAL_LOSS, layers.BACKWARD)
            } | {layers.OUTPUT, layers.UPDATE}


@pytest.mark.parametrize("fuse_opt", [False, True], ids=["split", "fuse_opt"])
@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_every_layer_scope_reaches_the_compiled_step(arch, fuse_opt):
    cfg = CONFIGS[arch]
    text = _compiled_text(cfg, fuse_opt=fuse_opt)
    assert _scopes_in(OP_NAME.findall(text)) == _expected(cfg.num_blocks)


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_optimizer_ops_sit_under_update(arch):
    """IntegerSGD's ``W - ⌊g/γ⌋ - ⌊W/η⌋``: its subtract and its floor
    divisions' reciprocal multiply are instructions of the ``update``
    scope, and no integer divide or remainder is left there."""
    text = _compiled_text(CONFIGS[arch])
    under = {op: False for op in ("subtract", "multiply", "divide",
                                  "remainder")}
    for line in text.splitlines():
        path = OP_NAME.search(line)
        if path is None or "/update/" not in path.group(1):
            continue
        for op in under:
            if re.search(rf"=\s*\S+\s+{op}\(", line):
                under[op] = True
    assert under == {"subtract": True, "multiply": True, "divide": False,
                     "remainder": False}


def test_block_scope_names():
    assert layers.block_scope(3, layers.BACKWARD) == "block3/backward"
    with pytest.raises(ValueError):
        layers.block_scope(0, "update")


_DP_SCRIPT = """
import json, re, sys
import jax, jax.numpy as jnp
from repro.core import les
from repro.core.blocks import BlockSpec
from repro.core.model import NitroConfig
from repro.parallel import dp
cfg = NitroConfig(
    blocks=(BlockSpec("conv", 16, pool=True, d_lr=256, dropout=0.1),
            BlockSpec("linear", 32)),
    input_shape=(8, 8, 3), num_classes=10, gamma_inv=512)
state = les.create_train_state(jax.random.PRNGKey(0), cfg)
x = jnp.zeros((8, *cfg.input_shape), jnp.int32)
labels = jnp.zeros((8,), jnp.int32)
step = dp.make_dp_train_step(cfg, dp.data_mesh(4), dp_reduce=sys.argv[1])
text = step.lower(state, x, labels, jax.random.PRNGKey(1)).compile().as_text()
paths = re.findall(r'op_name="((?:[^"\\\\]|\\\\.)*)"', text)
print(json.dumps({"devices": jax.device_count(), "paths": paths}))
"""


def test_dp_step_carries_the_scopes_and_the_exchange():
    """The four-device data-parallel step (virtual CPU devices, so in a
    fresh interpreter): every scope of the single-device step, and the
    gradient all-reduce under ``dp/reduce_gradients``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _DP_SCRIPT, "psum"],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    assert _scopes_in(got["paths"]) == _expected(2) | {
        layers.REDUCE_GRADIENTS}
    # the exchange is the psum under the scope (XLA may merge it with the
    # metrics' psum into one all-reduce)
    assert any(p.endswith("/dp/reduce_gradients/psum") for p in got["paths"])


# ---------------------------------------------------------------------------
# Kernel names: every pallas_call names itself, as the trace shows it
# ---------------------------------------------------------------------------

I32 = jnp.int32
S = jax.ShapeDtypeStruct
CONV_X, CONV_W, CONV_G = S((2, 8, 8, 16), I32), S((3, 3, 16, 16), I32), \
    S((2, 8, 8, 16), I32)
# a 3-channel input: its 27 taps fold into one lane tile (``folds_taps``)
IN_X, IN_W = S((2, 8, 8, 3), I32), S((3, 3, 3, 16), I32)
MM_X, MM_W, MM_G = S((8, 128), I32), S((128, 128), I32), S((8, 128), I32)
SCALAR = S((), I32)

KERNELS = [
    ("stream_conv", lambda x, w: NC.stream_conv(x, w, sf=64),
     (CONV_X, CONV_W)),
    ("stream_conv_fwd", lambda x, w: NC.stream_conv_fwd(x, w, sf=64),
     (CONV_X, CONV_W)),
    ("stream_conv_grad_w",
     lambda x, g, z: NC.stream_conv_grad_w(x, g, kernel_size=3, z_star=z),
     (CONV_X, CONV_G, CONV_G)),
    ("stream_conv_grad_w_opt",
     lambda x, g, z, w, gi, ei: NC.stream_conv_grad_w_opt(
         x, g, z, w, gi, ei, kernel_size=3),
     (CONV_X, CONV_G, CONV_G, CONV_W, SCALAR, SCALAR)),
    ("stream_conv_grad_x", lambda g, z, w: NC.stream_conv_grad_x(g, z, w),
     (CONV_G, CONV_G, CONV_W)),
    ("stream_conv_folded", lambda x, w: NC.stream_conv(x, w, sf=64),
     (IN_X, IN_W)),
    ("stream_conv_fwd_folded", lambda x, w: NC.stream_conv_fwd(x, w, sf=64),
     (IN_X, IN_W)),
    ("stream_conv_grad_w_folded",
     lambda x, g, z: NC.stream_conv_grad_w(x, g, kernel_size=3, z_star=z),
     (IN_X, CONV_G, CONV_G)),
    ("stream_conv_grad_w_opt_folded",
     lambda x, g, z, w, gi, ei: NC.stream_conv_grad_w_opt(
         x, g, z, w, gi, ei, kernel_size=3),
     (IN_X, CONV_G, CONV_G, IN_W, SCALAR, SCALAR)),
    ("nitro_matmul", lambda x, w: NM.nitro_matmul(x, w, sf=64), (MM_X, MM_W)),
    ("nitro_matmul_fwd", lambda x, w: NM.nitro_matmul_fwd(x, w, sf=64),
     (MM_X, MM_W)),
    ("nitro_matmul_grad_w",
     lambda x, g, z: NM.nitro_matmul_grad_w(x, g, z), (MM_X, MM_G, MM_G)),
    ("nitro_matmul_grad_w_opt",
     lambda x, g, z, w, gi, ei: NM.nitro_matmul_grad_w_opt(
         x, g, z, w, gi, ei),
     (MM_X, MM_G, MM_G, MM_W, SCALAR, SCALAR)),
    ("nitro_matmul_grad_x", lambda g, z, w: NM.nitro_matmul_grad_x(g, z, w),
     (MM_G, MM_G, MM_W)),
    ("integer_sgd_update",
     lambda w, g, gi, ei: integer_sgd_update(w, g, gi, ei),
     (MM_W, MM_W, SCALAR, SCALAR)),
]


def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_names(sub))
    return out


@pytest.mark.parametrize("name,fn,args", KERNELS,
                         ids=[k[0] for k in KERNELS])
def test_every_pallas_call_carries_its_name(name, fn, args):
    assert _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr) == [name]
