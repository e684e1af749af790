"""Tap folding: a shallow conv input runs as a 1×1 conv over its patches.

Where ``folds_taps(K, C)`` holds (K > 1, K²·C ≤ 128), the Pallas wrappers
feed the kernels ``im2col(x)`` and the weight ``w.reshape(1, 1, K²·C, F)``
instead of padding each of the K² patch segments to 128 lanes.  Each
wrapper that reads a conv input is held here, through the Pallas
interpreter, bit for bit to the pure-jnp streaming oracle, which never
folds: on both sides of the 128-lane boundary (C = 14 folds at K = 3,
C = 15 does not), for 1- and 3-channel inputs, K = 5, odd H and W, and a
filter count past one lane tile.  Inputs span all of int32, so the
folded patch block takes its four limbs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _gradcheck import assert_bitwise_equal
from repro.configs import paper
from repro.kernels.integer_sgd.ref import integer_sgd_ref
from repro.kernels.nitro_conv import (
    folds_taps,
    stream_conv,
    stream_conv_fwd,
    stream_conv_fwd_ref,
    stream_conv_grad_w,
    stream_conv_grad_w_opt,
    stream_conv_grad_w_ref,
    stream_conv_ref,
)

SF = 4096
GAMMA_INV, ETA_INV = 512, 12000

# (N, H, W, C, K, F): K²·C lanes 9, 27, 75, 126 (fold) and 135 (does not);
# a 1×1 conv folds nothing but takes the same halo-free input path
SHAPES = [
    (2, 5, 7, 1, 3, 130),
    (2, 6, 9, 3, 3, 8),
    (1, 7, 5, 3, 5, 8),
    (1, 5, 6, 14, 3, 8),
    (1, 5, 6, 15, 3, 8),
    (2, 5, 7, 3, 1, 8),
]


def _case(n, h, w_sp, c, k, f, seed=0):
    rng = np.random.default_rng(seed)

    def ints(lo, hi, shape):
        return jnp.asarray(rng.integers(lo, hi, shape, dtype=np.int64),
                           jnp.int32)

    x = ints(-(2**31), 2**31, (n, h, w_sp, c))
    w = ints(-(2**15), 2**15, (k, k, c, f))
    g = ints(-(2**20), 2**20, (n, h, w_sp, f))
    z = ints(-(2**12), 2**12, (n, h, w_sp, f))
    return x, w, g, z


ENTRIES = {
    "stream_conv": lambda x, w, g, z: (
        stream_conv(x, w, sf=SF, interpret=True),
        stream_conv_ref(x, w, sf=SF)),
    "stream_conv_pool": lambda x, w, g, z: (
        stream_conv(x, w, sf=SF, pool=True, interpret=True),
        stream_conv_ref(x, w, sf=SF, pool=True)),
    "stream_conv_fwd": lambda x, w, g, z: (
        stream_conv_fwd(x, w, sf=SF, interpret=True),
        stream_conv_fwd_ref(x, w, sf=SF)),
    "stream_conv_grad_w": lambda x, w, g, z: (
        stream_conv_grad_w(x, g, kernel_size=w.shape[0], interpret=True),
        stream_conv_grad_w_ref(x, g, kernel_size=w.shape[0])),
    "stream_conv_grad_w_z_star": lambda x, w, g, z: (
        stream_conv_grad_w(x, g, kernel_size=w.shape[0], z_star=z,
                           interpret=True),
        stream_conv_grad_w_ref(x, g, kernel_size=w.shape[0], z_star=z)),
    "stream_conv_grad_w_opt": lambda x, w, g, z: (
        stream_conv_grad_w_opt(x, g, z, w, jnp.int32(GAMMA_INV),
                               jnp.int32(ETA_INV), kernel_size=w.shape[0],
                               interpret=True),
        integer_sgd_ref(w, stream_conv_grad_w_ref(
            x, g, kernel_size=w.shape[0], z_star=z),
            jnp.int32(GAMMA_INV), jnp.int32(ETA_INV))),
}


def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_names(sub))
    return out


@pytest.mark.parametrize("entry", list(ENTRIES))
@pytest.mark.parametrize("shape", SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_interpret_matches_reference(entry, shape):
    """The wrapper through the interpreter equals the never-folding oracle
    bit for bit, and its Pallas call is the folded one exactly where
    ``folds_taps`` says."""
    x, w, g, z = _case(*shape)
    got, want = ENTRIES[entry](x, w, g, z)
    assert_bitwise_equal(got, want)
    names = _pallas_names(jax.make_jaxpr(
        lambda *a: ENTRIES[entry](*a)[0])(x, w, g, z).jaxpr)
    n, h, w_sp, c, k, f = shape
    assert len(names) == 1
    assert names[0].endswith("_folded") == folds_taps(k, c), names


@pytest.mark.parametrize("k,c,folds", [
    (3, 1, True), (3, 3, True), (3, 14, True), (3, 15, False),
    (5, 3, True), (5, 5, True), (5, 6, False), (1, 3, False),
    (1, 128, False), (3, 128, False),
])
def test_folds_taps_rule(k, c, folds):
    """K > 1 and K²·C within one 128-lane tile; a 1×1 conv has nothing to
    fold."""
    assert folds_taps(k, c) is folds


@pytest.mark.parametrize("arch", ["vgg8b", "vgg11b"])
def test_folds_taps_engages_on_block0_only(arch):
    """The paper's CNNs fold exactly their 3-channel input conv."""
    cfg = paper.get(arch)
    c, engaged = cfg.input_shape[-1], []
    for spec in cfg.blocks:
        if spec.kind != "conv":
            break
        engaged.append(folds_taps(spec.kernel_size, c))
        c = spec.out_features
    assert engaged == [True] + [False] * (len(engaged) - 1)
    assert len(engaged) > 1
