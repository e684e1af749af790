"""Shared gradient-parity harness for the kernel test-suite.

One place for the assertions and fixtures the parity tests used to
duplicate across ``test_kernels.py``, ``test_fused_training.py`` and
``test_conv_stream.py``:

  * ``assert_bitwise_equal`` — pytree-aware *exact* equality, dtype
    included (every kernel claim in this repo is equality, not tolerance);
  * backend fixtures — ``kernel_backend`` sweeps every backend runnable on
    this host (``pallas`` joins the sweep on TPU), ``backend_pair`` yields
    every unordered backend pairing for A-vs-B parity tests;
  * jaxpr helpers — recursive eqn iteration (optionally skipping Pallas
    kernel bodies), aval-shape collection, the integer-only scan, and the
    primitive/shape query the backward structural tests use;
  * ``unfused_delta_path`` — the oracle the fused δ prologue is held to
    at block and step level: ``masked_delta`` on the full δ, then
    ``grad_ops``' ``z_star=None`` route (plain gradient matmuls/convs).

The oracles that the fused kernels are compared against: the
``backend="reference"`` jnp compositions (whose masked gradients are
pinned to the unfused composition in ``test_grad_fusion.py``), the
``z_star=None`` route after ``masked_delta``, the unfused forward
(``fused=False``: explicit im2col + ``int_matmul``), and
``jax.lax.conv_general_dilated`` on int32 operands
(``test_conv_stream.py``).

Import what you need directly (the file is underscore-prefixed so pytest
does not collect it):

    from _gradcheck import assert_bitwise_equal, backend_pair  # noqa: F401
"""

from __future__ import annotations

import contextlib
import functools
import itertools

import jax
from jax.extend import core as jex_core
import numpy as np
import pytest

from repro.kernels import grad_ops
from repro.kernels.nitro_matmul.ref import masked_delta

# Backends runnable on this host: the Pallas interpreter and the jnp
# oracle run everywhere; the real kernel joins the sweep on TPU.
AVAILABLE_BACKENDS = ("reference", "interpret") + (
    ("pallas",) if jax.default_backend() == "tpu" else ()
)
BACKEND_PAIRS = tuple(itertools.combinations(AVAILABLE_BACKENDS, 2))


@pytest.fixture(params=AVAILABLE_BACKENDS)
def kernel_backend(request):
    """Every backend the dispatcher can run on this host."""
    return request.param


@pytest.fixture(params=BACKEND_PAIRS, ids=lambda p: f"{p[0]}-vs-{p[1]}")
def backend_pair(request):
    """Every unordered pair of runnable backends, for A-vs-B parity."""
    return request.param


def assert_bitwise_equal(got, want, *, err_msg: str = "") -> None:
    """Exact equality for arrays or pytrees of arrays, dtype included.

    The single parity assertion of the suite: values must match
    bit-for-bit AND carry the same dtype (a silently-widened int8 would
    pass a value-only comparison while breaking the HBM-traffic claim).
    """
    got_leaves, got_tree = jax.tree_util.tree_flatten(got)
    want_leaves, want_tree = jax.tree_util.tree_flatten(want)
    assert got_tree == want_tree, (
        f"pytree structure mismatch: {got_tree} vs {want_tree} {err_msg}"
    )
    for i, (g, w) in enumerate(zip(got_leaves, want_leaves)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype, (
            f"dtype mismatch at leaf {i}: {g.dtype} vs {w.dtype} {err_msg}"
        )
        np.testing.assert_array_equal(g, w, err_msg=f"leaf {i} {err_msg}")


# The grad_ops entry points that take (x, w, δ, ...) and a ``z_star``.
_DELTA_ENTRY_POINTS = ("linear_grads", "conv_grads",
                       "linear_weight_update", "conv_weight_update")


@contextlib.contextmanager
def unfused_delta_path():
    """Run every activation backward through the unfused composition.

    Inside the block, each ``grad_ops`` entry point that gets a
    ``z_star`` applies ``masked_delta`` (NITRO-ReLU derivative + STE) to
    the full-size δ first and takes the ``z_star=None`` route: plain
    gradient matmuls/convs, plus ``optimizer.apply_update`` for the
    weight-update pair.  Trace (jit) the block- or step-level call inside
    the ``with``; the layers look the entry points up at trace time.
    """
    originals = {name: getattr(grad_ops, name) for name in _DELTA_ENTRY_POINTS}

    def premasked(fn):
        @functools.wraps(fn)
        def wrapped(x, w, delta, *args, z_star=None, alpha_inv=10, **kw):
            if z_star is not None:
                delta = masked_delta(delta, z_star, alpha_inv)
            return fn(x, w, delta, *args, alpha_inv=alpha_inv, **kw)
        return wrapped

    for name, fn in originals.items():
        setattr(grad_ops, name, premasked(fn))
    try:
        yield
    finally:
        for name, fn in originals.items():
            setattr(grad_ops, name, fn)


# ---------------------------------------------------------------------------
# jaxpr structure helpers
# ---------------------------------------------------------------------------


def iter_eqns(jaxpr, *, skip_pallas: bool = False):
    """Yield every eqn, descending into sub-jaxprs carried in eqn params
    (pjit, cond, scan — and the Pallas kernel body inside ``pallas_call``
    unless ``skip_pallas``, which the structural tests use to reason about
    what exists *outside* VMEM)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if skip_pallas and eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            items = param if isinstance(param, (tuple, list)) else [param]
            for item in items:
                if isinstance(item, jex_core.ClosedJaxpr):
                    yield from iter_eqns(item.jaxpr, skip_pallas=skip_pallas)
                elif isinstance(item, jex_core.Jaxpr):
                    yield from iter_eqns(item, skip_pallas=skip_pallas)


def collect_aval_shapes(jaxpr, shapes=None, *, skip_pallas: bool = False):
    """Every intermediate aval shape in the program (a set of tuples)."""
    if shapes is None:
        shapes = set()
    for eqn in iter_eqns(jaxpr, skip_pallas=skip_pallas):
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                shapes.add(tuple(int(d) for d in aval.shape))
    return shapes


def assert_jaxpr_integer_only(jaxpr) -> None:
    """No float dtype anywhere — descending into Pallas kernel bodies."""
    for eqn in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                assert "float" not in str(aval.dtype), f"float op: {eqn}"


def eqn_output_shapes(jaxpr, prim_names, *, skip_pallas: bool = True):
    """Output shapes of every eqn whose primitive is in ``prim_names``,
    by default looking only *outside* Pallas kernel bodies — i.e. at what
    a program materialises in HBM rather than in VMEM tiles."""
    shapes = []
    for eqn in iter_eqns(jaxpr, skip_pallas=skip_pallas):
        if eqn.primitive.name in prim_names:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    shapes.append(tuple(int(d) for d in aval.shape))
    return shapes
