"""Compile every Pallas kernel of the default train and serve path for a
described TPU v5e chip.

Nothing runs here: each test lowers one kernel at a published width of
VGG8B, VGG11B or MLP3 for one chip of a described ``v5e:2x2`` topology and
compiles it with the TPU compiler.  That compiler refuses what the chip
would refuse and the Pallas interpreter accepts: int32 MXU dots, slices
not aligned to the (8, 128) tiling, more VMEM than a kernel may use.  The
interpret-mode tests elsewhere check the results; these check that the
chip can run the same kernels at all.

The topology is described inside a fixture (never at import), so every
test worker collects the same tests and only the worker given this file
loads the TPU compiler.  The persistent compilation cache is off around
these compiles: an entry written for a described chip cannot be read back
without one.
"""

from __future__ import annotations

import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.integer_sgd.integer_sgd import integer_sgd_update

NM = importlib.import_module("repro.kernels.nitro_matmul.nitro_matmul")
NC = importlib.import_module("repro.kernels.nitro_conv.nitro_conv")

BATCH = 128     # the training batch that the chip smoke runs
SF = 4096

# (id, H, W, C, F, pool): every distinct conv layer of VGG8B and VGG11B
# at scale 1 on 32×32×3 inputs (configs/paper.py).
CONV_LAYERS = [
    ("in32x32x3-128", 32, 32, 3, 128, False),
    ("32x32x128-128", 32, 32, 128, 128, False),
    ("32x32x128-256p", 32, 32, 128, 256, True),
    ("16x16x256-256", 16, 16, 256, 256, False),
    ("16x16x256-512p", 16, 16, 256, 512, True),
    ("8x8x512-512", 8, 8, 512, 512, False),
    ("8x8x512-512p", 8, 8, 512, 512, True),
    ("4x4x512-512p", 4, 4, 512, 512, True),
]

# (id, K, N): the linear blocks — VGG8B/VGG11B's 2·2·512 → 1024 and
# MLP3's 784 → 1024 → 1024.
LINEAR_LAYERS = [
    ("vgg-2048-1024", 2048, 1024),
    ("mlp3-784-1024", 784, 1024),
    ("mlp3-1024-1024", 1024, 1024),
]


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the compilation cache off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Lower ``fn`` over ``(shape, dtype)`` args for the chip and compile;
    returns the HLO text, which must hold the Pallas kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _act_dtype(c: int):
    """The conv input as training feeds it: the 3-channel network input is
    int32, every later block input is an int8-narrowed activation."""
    return jnp.int32 if c == 3 else jnp.int8


@pytest.mark.parametrize(
    "layer", CONV_LAYERS, ids=[layer[0] for layer in CONV_LAYERS])
class TestConvKernels:
    def test_serve(self, one_chip, layer):
        _, h, w, c, f, pool = layer
        x_dtype = jnp.int32 if c == 3 else jnp.int8
        _compile(
            lambda x, wt: NC.stream_conv(
                x, wt, sf=SF, pool=pool, out_dtype=jnp.int8,
                operand_dtype="int8" if c != 3 else "int32"),
            one_chip, ((BATCH, h, w, c), x_dtype),
            ((3, 3, c, f), jnp.int8 if c != 3 else jnp.int32),
        )

    def test_fwd(self, one_chip, layer):
        _, h, w, c, f, _ = layer
        _compile(
            lambda x, wt: NC.stream_conv_fwd(x, wt, sf=SF),
            one_chip, ((BATCH, h, w, c), _act_dtype(c)),
            ((3, 3, c, f), jnp.int32),
        )

    def test_grad_w(self, one_chip, layer):
        _, h, w, c, f, _ = layer
        _compile(
            lambda x, g, z: NC.stream_conv_grad_w(
                x, g, kernel_size=3, z_star=z),
            one_chip, ((BATCH, h, w, c), _act_dtype(c)),
            ((BATCH, h, w, f), jnp.int32), ((BATCH, h, w, f), jnp.int32),
        )

    def test_grad_w_opt(self, one_chip, layer):
        _, h, w, c, f, _ = layer
        _compile(
            lambda x, g, z, wt, gi, ei: NC.stream_conv_grad_w_opt(
                x, g, z, wt, gi, ei, kernel_size=3),
            one_chip, ((BATCH, h, w, c), _act_dtype(c)),
            ((BATCH, h, w, f), jnp.int32), ((BATCH, h, w, f), jnp.int32),
            ((3, 3, c, f), jnp.int32), ((), jnp.int32), ((), jnp.int32),
        )

    def test_grad_x(self, one_chip, layer):
        _, h, w, c, f, _ = layer
        _compile(
            lambda g, z, wt: NC.stream_conv_grad_x(g, z, wt),
            one_chip, ((BATCH, h, w, f), jnp.int32),
            ((BATCH, h, w, f), jnp.int32), ((3, 3, c, f), jnp.int32),
        )


@pytest.mark.parametrize(
    "layer", LINEAR_LAYERS, ids=[layer[0] for layer in LINEAR_LAYERS])
class TestMatmulKernels:
    def test_serve(self, one_chip, layer):
        _, k, n = layer
        _compile(
            lambda x, w: NM.nitro_matmul(
                x, w, sf=SF, out_dtype=jnp.int8, operand_dtype="int8"),
            one_chip, ((32, k), jnp.int8), ((k, n), jnp.int8),
        )

    @pytest.mark.parametrize("x_dtype", [jnp.int8, jnp.int32],
                             ids=["x_int8", "x_int32"])
    def test_fwd(self, one_chip, layer, x_dtype):
        _, k, n = layer
        _compile(
            lambda x, w: NM.nitro_matmul_fwd(x, w, sf=SF),
            one_chip, ((BATCH, k), x_dtype), ((k, n), jnp.int32),
        )

    def test_grad_w(self, one_chip, layer):
        _, k, n = layer
        _compile(
            lambda x, d, z: NM.nitro_matmul_grad_w(x, d, z),
            one_chip, ((BATCH, k), jnp.int8), ((BATCH, n), jnp.int32),
            ((BATCH, n), jnp.int32),
        )

    def test_grad_w_opt(self, one_chip, layer):
        _, k, n = layer
        _compile(
            lambda x, d, z, w, gi, ei: NM.nitro_matmul_grad_w_opt(
                x, d, z, w, gi, ei),
            one_chip, ((BATCH, k), jnp.int8), ((BATCH, n), jnp.int32),
            ((BATCH, n), jnp.int32), ((k, n), jnp.int32),
            ((), jnp.int32), ((), jnp.int32),
        )

    def test_grad_x(self, one_chip, layer):
        _, k, n = layer
        _compile(
            lambda d, z, w: NM.nitro_matmul_grad_x(d, z, w),
            one_chip, ((BATCH, n), jnp.int32), ((BATCH, n), jnp.int32),
            ((k, n), jnp.int32),
        )


@pytest.mark.parametrize("shape", [(3, 3, 512, 512), (2048, 1024)],
                         ids=["conv512", "linear2048"])
def test_integer_sgd_update(one_chip, shape):
    _compile(
        lambda w, g, gi, ei: integer_sgd_update(w, g, gi, ei),
        one_chip, (shape, jnp.int32), (shape, jnp.int32),
        ((), jnp.int32), ((), jnp.int32),
    )


def test_no_int32_mxu_dot(one_chip):
    """The widest case, int32·int32 (the masked δ against a weight), is
    split into int8 MXU passes: the kernel body holds no int32 dot."""
    args = [
        jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
        for s in ((BATCH, 1024), (BATCH, 1024), (2048, 1024))
    ]
    jaxpr = jax.make_jaxpr(
        lambda d, z, w: NM.nitro_matmul_grad_x(d, z, w))(*args)
    dots = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general":
                dots.append(tuple(v.aval.dtype for v in eqn.invars))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else [p]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    assert dots, "no dot found in the kernel body"
    assert all(d == (jnp.int8, jnp.int8) for d in dots), dots
    assert len(dots) == 10  # the i + j < 4 limb pairs of two int32 operands
