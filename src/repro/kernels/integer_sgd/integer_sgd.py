"""Fused IntegerSGD update Pallas kernel (paper Algorithm 1).

    W ← W − ( ⌊g/γ_inv⌋ + ⌊W/η_inv⌋ )

A memory-bound elementwise op: the fused kernel reads W and g once and
writes W once (3 HBM streams), where the naive lowering materialises the
two floor-division temporaries (5 streams) — a 1.67× traffic cut on the
optimiser step, which at LES's per-block update frequency is a measurable
slice of the training step's memory term.

γ_inv/η_inv arrive as scalars in SMEM so one compiled kernel serves every
(layer-group, schedule-step) combination — the lr schedule (γ_inv ×3 on
plateau) changes no executable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DEFAULT_BLOCK_ROWS = 8  # (8, 128) native int32 VREG tile


def integer_sgd_tile(w, g, gamma_inv, eta_inv):
    """One IntegerSGD step on in-register values — the shared epilogue body.

    Used both by the standalone kernel below and by the grad-kernel flush
    epilogues (``nitro_matmul._nitro_grad_w_opt_kernel``,
    ``nitro_conv._stream_grad_w_opt_kernel``), so fused ≡ standalone ≡ ref
    is one expression, not three. η_inv == 0 disables decay; floor division
    rounds toward −∞ (see ``core.optimizer.apply_update`` for the
    negative-weight asymmetry this implies).
    """
    delta = jnp.floor_divide(g, gamma_inv)
    decay = jnp.where(
        eta_inv != 0,
        jnp.floor_divide(w, jnp.maximum(eta_inv, 1)),
        jnp.zeros_like(w),
    )
    return w - (delta + decay)


def _integer_sgd_kernel(scalars_ref, w_ref, g_ref, out_ref):
    """scalars = [γ_inv, η_inv]; η_inv == 0 disables decay."""
    out_ref[...] = integer_sgd_tile(
        w_ref[...], g_ref[...], scalars_ref[0], scalars_ref[1]
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def integer_sgd_update(
    w: jax.Array,
    g: jax.Array,
    gamma_inv: jax.Array,
    eta_inv: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    """Apply one IntegerSGD step to a tensor of any shape.

    Flattens to (rows, 128) VPU lanes, pads the ragged tail, runs the fused
    kernel over a 1-D grid, and restores the original shape.
    """
    shape = w.shape
    n = w.size
    rows = -(-n // LANE)  # ceil
    pad = rows * LANE - n
    wf = jnp.pad(w.reshape(-1), (0, pad)).reshape(rows, LANE)
    gf = jnp.pad(g.reshape(-1), (0, pad)).reshape(rows, LANE)

    br = min(block_rows, rows)
    grid_rows = -(-rows // br)
    if grid_rows * br != rows:  # pad rows to a block multiple
        extra = grid_rows * br - rows
        wf = jnp.pad(wf, ((0, extra), (0, 0)))
        gf = jnp.pad(gf, ((0, extra), (0, 0)))

    scalars = jnp.stack(
        [jnp.asarray(gamma_inv, jnp.int32), jnp.asarray(eta_inv, jnp.int32)]
    )
    out = pl.pallas_call(
        _integer_sgd_kernel,
        grid=(grid_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(wf.shape, w.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(scalars, wf, gf)
    return out.reshape(-1)[:n].reshape(shape)
