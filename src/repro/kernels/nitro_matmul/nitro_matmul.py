"""Fused NITRO matmul Pallas TPU kernel.

Computes, in one pass over VMEM tiles::

    z   = x @ w                      (int8 limb dots, int32 accumulate)
    z*  = ⌊z / SF⌋                   (NITRO Scaling Layer)
    out = NITRO-ReLU(z*)             (optional, fused on the VPU)

This is the paper's per-layer hot loop (§3.2).  The reference NITRO-D
library materialises ``z`` (int32) in HBM, reads it back for the scaling
layer, and again for the activation — three HBM round-trips of the widest
tensor in the network.  Fusing them keeps ``z`` in a VMEM scratch
accumulator and writes only the int8 activation back to HBM:

    HBM bytes per layer:  unfused  M·N·(4+4+4+1)   →   fused  M·N·1 (+in/w)

TPU adaptation notes:
  * tiles are 128-aligned for the MXU systolic array, which multiplies
    int8×int8→int32 and has no int32 mode: every dot is ``kernels.limbs``'
    limb split (one MXU pass for int8·int8, four for int8·int32, ten for
    int32·int32), exact mod 2³²;
  * ⌊z/SF⌋ is split as SF = residual·2^shift — the 2^shift part is an
    arithmetic right shift (exact floor semantics for two's-complement),
    the odd residual is one VPU integer divide;
  * grid is (M/bm, N/bn, K/bk) with K innermost ("arbitrary"), the
    canonical Pallas accumulation pattern.

The backward pass gets the same treatment: ``nitro_matmul_grad_w`` /
``nitro_matmul_grad_x`` are true backward kernels whose *prologue* applies
the NITRO-ReLU derivative (+ the scaling STE, which is the identity) to
each incoming δ tile in VMEM before the MXU gradient matmuls — the
post-ReLU-bwd δ tensor, which the unfused composition round-trips through
HBM once per local-loss block, never leaves VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.activations import mu_int8, nitro_relu_backward
from repro.core.scaling import pow2_split
from repro.kernels.autotune.tiles import DEFAULT_TILES
from repro.kernels.integer_sgd.integer_sgd import integer_sgd_tile
from repro.kernels.limbs import (
    INT32_LIMBS,
    limb_count,
    limb_dot,
    planes,
    split_limbs,
    stack_limbs,
)

# MXU-native tile sizes — aliases of the single definition in
# ``kernels.autotune.tiles.DEFAULT_TILES`` (shared with the conv kernel,
# the autotuner, and the docs).
DEFAULT_BM = DEFAULT_TILES.bm
DEFAULT_BN = DEFAULT_TILES.bn
DEFAULT_BK = DEFAULT_TILES.bk


def _scale_tile(z, sf_shift: int, sf_residual: int):
    """NITRO Scaling on a VMEM tile: ⌊z / (residual · 2^shift)⌋.

    Arithmetic right shift implements the power-of-two floor division
    exactly; composing the two floors is exact because both divisors are
    positive (⌊⌊z/a⌋/b⌋ = ⌊z/(ab)⌋).
    """
    if sf_shift:
        z = jax.lax.shift_right_arithmetic(z, sf_shift)
    if sf_residual != 1:
        z = jnp.floor_divide(z, sf_residual)
    return z


def _relu_tile(z, alpha_inv: int, mu: int):
    """NITRO-ReLU on a VMEM tile (VPU select/min/max/floor-div)."""
    neg = jnp.floor_divide(jnp.maximum(z, -127), alpha_inv)
    pos = jnp.minimum(z, 127)
    return jnp.where(z < 0, neg, pos) - mu


def _relu_bwd_tile(g, z, alpha_inv: int):
    """NITRO-ReLU derivative + STE on a VMEM δ tile (the backward prologue).

    Delegates to ``core.activations.nitro_relu_backward`` — pure traceable
    jnp (selects + one floor-div on the VPU), so the kernel prologue can
    never drift from the reference derivative.  The NITRO Scaling Layer's
    straight-through estimator is the identity, so fusing it adds no
    arithmetic — folding this prologue into the gradient matmuls is what
    keeps the post-ReLU-bwd δ tensor out of HBM entirely.
    """
    return nitro_relu_backward(z, g, alpha_inv)


#: ``dot_general`` dimension numbers of the three matmul shapes.
_X_W = (((1,), (0,)), ((), ()))     # x @ w
_XT_G = (((0,), (0,)), ((), ()))    # xᵀ @ g   (grad_W)
_G_WT = (((1,), (1,)), ((), ()))    # g @ wᵀ   (grad_x)


def _own_limbs(x):
    """Split a VMEM tile by its own dtype (int8 → 1 limb, int32 → 4)."""
    return split_limbs(x, limb_count(x.dtype))


def _accumulate_tile(x_ref, w_ref, acc_ref):
    """Zero the VMEM accumulator at k == 0, then MXU-accumulate one
    (bm, bk)·(bk, bn) partial product — int32 accumulation.

    ``w_ref`` holds the weight tile as int8 limb planes (split once in
    XLA by ``stack_limbs``); the x tile is split here by its dtype.  The
    dot is ``limb_dot``'s int8×int8→int32 MXU passes — one when both
    operands are int8 — bit-exact with an int32 dot.
    """

    @pl.when(pl.program_id(2) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += limb_dot(_own_limbs(x_ref[...]), planes(w_ref), _X_W)


def _nitro_matmul_kernel(
    x_ref,
    w_ref,
    out_ref,
    acc_ref,
    *,
    n_k: int,
    sf_shift: int,
    sf_residual: int,
    alpha_inv: int,
    mu: int,
    apply_relu: bool,
    out_dtype,
):
    """One (bm, bn) output tile; accumulates over the K grid dimension."""
    _accumulate_tile(x_ref, w_ref, acc_ref)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        z = _scale_tile(acc_ref[...], sf_shift, sf_residual)
        if apply_relu:
            z = _relu_tile(z, alpha_inv, mu)
        out_ref[...] = z.astype(out_dtype)


def _nitro_matmul_fwd_kernel(
    x_ref,
    w_ref,
    a_ref,
    zstar_ref,
    acc_ref,
    *,
    n_k: int,
    sf_shift: int,
    sf_residual: int,
    alpha_inv: int,
    mu: int,
    out_dtype,
):
    """Training-forward variant: one accumulation pass, two outputs.

    Writes both the post-ReLU activation ``a`` (the block output) and the
    pre-ReLU scaled ``z*`` (the NITRO-ReLU/STE backward's only dependency
    on the forward pass) from the same VMEM accumulator — the unfused
    pipeline writes z (int32), z* (int32) and a (int32) to HBM; this
    writes a + z* and never materialises the raw pre-activation z.
    """
    _accumulate_tile(x_ref, w_ref, acc_ref)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        z_star = _scale_tile(acc_ref[...], sf_shift, sf_residual)
        zstar_ref[...] = z_star
        a_ref[...] = _relu_tile(z_star, alpha_inv, mu).astype(out_dtype)


def _tile_geometry(x: jax.Array, w: jax.Array, bm: int, bn: int, bk: int):
    """Pad operands up to tile multiples (zero padding is exact for integer
    matmul); returns padded operands, clamped block sizes, and the grid."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    pm, pn, pk = (-m) % bm_, (-n) % bn_, (-k) % bk_
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    gm, gn, gk = x.shape[0] // bm_, w.shape[1] // bn_, x.shape[1] // bk_
    return x, w, (bm_, bn_, bk_), (gm, gn, gk)


def _launch(kernel, x, w, tiles, grid, *, out_dtypes, interpret):
    """Shared ``pallas_call`` scaffolding for both kernel variants.

    Everything that must stay in lockstep between the single-output and
    fused-forward kernels lives here — grid, BlockSpecs/index maps, the
    VMEM accumulator scratch, and dimension semantics.  The variants
    differ only in kernel body and the number of (bm, bn) outputs, given
    by ``out_dtypes``.
    """
    bm_, bn_, bk_ = tiles
    gm, gn, gk = grid
    w = stack_limbs(w)
    out_specs = [
        pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)) for _ in out_dtypes
    ]
    out_shape = [
        jax.ShapeDtypeStruct((x.shape[0], w.shape[2]), dt) for dt in out_dtypes
    ]
    single = len(out_dtypes) == 1
    return pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec(
                (w.shape[0], bk_, bn_), lambda i, j, kk: (0, kk, j)
            ),
        ],
        out_specs=out_specs[0] if single else out_specs,
        out_shape=out_shape[0] if single else out_shape,
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w)


@functools.partial(
    jax.jit,
    static_argnames=(
        "sf", "alpha_inv", "apply_relu", "out_dtype",
        "bm", "bn", "bk", "operand_dtype", "interpret",
    ),
)
def nitro_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    sf: int,
    alpha_inv: int = 10,
    apply_relu: bool = True,
    out_dtype=jnp.int32,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    operand_dtype: str = "int32",
    interpret: bool = False,
) -> jax.Array:
    """Fused ``nitro_relu(⌊(x @ w)/sf⌋)`` for 2-D ``x`` (M,K) and ``w`` (K,N).

    Pads every dimension up to its tile multiple (zero padding is exact for
    integer matmul) and slices the result back.

    ``operand_dtype='int8'`` asserts both operands already *are* int8 —
    one ``int8×int8→int32`` MXU pass per tile; narrowing/eligibility
    proofs live in the dispatcher (``ops.fused_matmul``).  ``'int32'``
    splits each operand by its own dtype (``kernels.limbs``).  Bit-exact
    either way.
    """
    if operand_dtype == "int8" and not (
        x.dtype == jnp.int8 and w.dtype == jnp.int8
    ):
        raise ValueError(
            f"operand_dtype='int8' requires int8 operands, got "
            f"{x.dtype}/{w.dtype} (the dispatcher narrows eligible inputs)"
        )
    m, n = x.shape[0], w.shape[1]
    x, w, (bm_, bn_, bk_), (gm, gn, gk) = _tile_geometry(x, w, bm, bn, bk)

    shift, residual = pow2_split(sf)
    kernel = functools.partial(
        _nitro_matmul_kernel,
        n_k=gk,
        sf_shift=shift,
        sf_residual=residual,
        alpha_inv=alpha_inv,
        mu=mu_int8(alpha_inv) if apply_relu else 0,
        apply_relu=apply_relu,
        out_dtype=out_dtype,
    )
    out = _launch(
        kernel, x, w, (bm_, bn_, bk_), (gm, gn, gk),
        out_dtypes=[out_dtype], interpret=interpret,
    )
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=(
        "sf", "alpha_inv", "out_dtype", "bm", "bn", "bk", "interpret",
    ),
)
def nitro_matmul_fwd(
    x: jax.Array,
    w: jax.Array,
    *,
    sf: int,
    alpha_inv: int = 10,
    out_dtype=jnp.int32,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused *training* forward: returns ``(a, z_star)`` in one pass.

    ``a = nitro_relu(⌊(x @ w)/sf⌋)`` is the layer output; ``z_star`` is the
    int32 pre-ReLU scaled tensor the LES backward consumes (NITRO-ReLU
    segment selection + STE through the scaling layer).  Both come out of
    the same VMEM accumulator, so the raw int32 pre-activation ``z`` never
    touches HBM — the bandwidth win of the inference plan, extended to the
    train step.
    """
    m, n = x.shape[0], w.shape[1]
    x, w, (bm_, bn_, bk_), (gm, gn, gk) = _tile_geometry(x, w, bm, bn, bk)

    shift, residual = pow2_split(sf)
    kernel = functools.partial(
        _nitro_matmul_fwd_kernel,
        n_k=gk,
        sf_shift=shift,
        sf_residual=residual,
        alpha_inv=alpha_inv,
        mu=mu_int8(alpha_inv),
        out_dtype=out_dtype,
    )
    a, z_star = _launch(
        kernel, x, w, (bm_, bn_, bk_), (gm, gn, gk),
        out_dtypes=[out_dtype, jnp.int32], interpret=interpret,
    )
    return a[:m, :n], z_star[:m, :n]


# ---------------------------------------------------------------------------
# Backward kernels: gradient matmuls with the NITRO-ReLU-bwd/STE prologue
# ---------------------------------------------------------------------------


def _nitro_grad_w_kernel(x_ref, g_ref, z_ref, out_ref, acc_ref, *, n_k, alpha_inv):
    """One (bm, bn) grad_W tile: acc += x_tileᵀ @ relu_bwd(δ_tile).

    The prologue masks the incoming δ tile against the matching ``z_star``
    tile *in VMEM*, so the full-size post-ReLU-bwd δ never exists — each
    (bk, bn) δ tile is masked just before it enters the MXU.
    """

    @pl.when(pl.program_id(2) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = _relu_bwd_tile(g_ref[...].astype(jnp.int32), z_ref[...], alpha_inv)
    acc_ref[...] += limb_dot(
        _own_limbs(x_ref[...]), split_limbs(g, INT32_LIMBS), _XT_G
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def _nitro_grad_x_kernel(g_ref, z_ref, w_ref, out_ref, acc_ref, *, n_k, alpha_inv):
    """One (bm, bn) grad_x tile: acc += relu_bwd(δ_tile) @ w_tileᵀ.

    ``w`` is indexed in its natural (fan_in, fan_out) layout and transposed
    by the dot_general contraction dims — no wᵀ copy in HBM either.
    """

    @pl.when(pl.program_id(2) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = _relu_bwd_tile(g_ref[...].astype(jnp.int32), z_ref[...], alpha_inv)
    acc_ref[...] += limb_dot(
        split_limbs(g, INT32_LIMBS), planes(w_ref), _G_WT
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("alpha_inv", "bm", "bn", "bk", "interpret"),
)
def nitro_matmul_grad_w(
    x: jax.Array,
    delta: jax.Array,
    z_star: jax.Array,
    *,
    alpha_inv: int = 10,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """Fused weight gradient: ``xᵀ @ nitro_relu_backward(z_star, δ)``.

    x: (B, M) layer input, delta/z_star: (B, N) → (M, N) int32.  The grid
    is (M/bm, N/bn, B/bk) with the batch contraction innermost; the
    ReLU-bwd/STE prologue runs on each (bk, bn) δ tile in VMEM.  Zero
    padding is exact: padded δ and z* are both 0 and the prologue maps
    (δ=0, z*=0) → 0 (identity segment), contributing nothing.
    """
    b, m = x.shape
    b2, n = delta.shape
    assert b == b2, f"batch mismatch {b} vs {b2}"
    assert delta.shape == z_star.shape, "delta/z_star shape mismatch"
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, b)
    pm, pn, pb = (-m) % bm_, (-n) % bn_, (-b) % bk_
    if pb or pm:
        x = jnp.pad(x, ((0, pb), (0, pm)))
    if pb or pn:
        delta = jnp.pad(delta, ((0, pb), (0, pn)))
        z_star = jnp.pad(z_star, ((0, pb), (0, pn)))
    gm, gn, gk = x.shape[1] // bm_, delta.shape[1] // bn_, x.shape[0] // bk_
    kernel = functools.partial(
        _nitro_grad_w_kernel, n_k=gk, alpha_inv=alpha_inv
    )
    out = pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bk_, bm_), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[1], delta.shape[1]), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, delta, z_star)
    return out[:m, :n]


def _nitro_grad_w_opt_kernel(
    scalars_ref, x_ref, g_ref, z_ref, w_ref, out_ref, acc_ref, *, n_k, alpha_inv
):
    """grad_W tile with the IntegerSGD epilogue fused into the flush.

    Accumulation is identical to ``_nitro_grad_w_kernel``; on the last
    k-step the flush reads the matching W tile and writes
    ``W − (⌊acc/γ_inv⌋ + ⌊W/η_inv⌋)`` instead of the raw gradient —
    grad_W never reaches HBM.  γ_inv/η_inv ride in SMEM like the
    standalone ``integer_sgd`` kernel's scalars.
    """

    @pl.when(pl.program_id(2) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = _relu_bwd_tile(g_ref[...].astype(jnp.int32), z_ref[...], alpha_inv)
    acc_ref[...] += limb_dot(
        _own_limbs(x_ref[...]), split_limbs(g, INT32_LIMBS), _XT_G
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        out_ref[...] = integer_sgd_tile(
            w_ref[...], acc_ref[...], scalars_ref[0], scalars_ref[1]
        )


@functools.partial(
    jax.jit,
    static_argnames=("alpha_inv", "bm", "bn", "bk", "interpret"),
)
def nitro_matmul_grad_w_opt(
    x: jax.Array,
    delta: jax.Array,
    z_star: jax.Array,
    w: jax.Array,
    gamma_inv: jax.Array,
    eta_inv: jax.Array,
    *,
    alpha_inv: int = 10,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """Fused weight *update*: one pass computes grad_W in VMEM and applies
    IntegerSGD in the flush, returning W′ directly.

    Same grid/padding as ``nitro_matmul_grad_w``; ``w`` (M, N) shares the
    output tiling.  Padding is exact through the epilogue too: a padded
    position has acc = 0 and w = 0, so W′ = 0 − (⌊0/γ⌋ + decay(0)) = 0,
    and the slice discards it.  3 HBM streams (x, δ/z*, W↔W′) versus 5+
    for the unfused composition (grad_W write + read, W read + write).
    """
    b, m = x.shape
    b2, n = delta.shape
    assert b == b2, f"batch mismatch {b} vs {b2}"
    assert delta.shape == z_star.shape, "delta/z_star shape mismatch"
    assert w.shape == (m, n), f"w shape {w.shape} != ({m}, {n})"
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, b)
    pm, pn, pb = (-m) % bm_, (-n) % bn_, (-b) % bk_
    if pb or pm:
        x = jnp.pad(x, ((0, pb), (0, pm)))
    if pb or pn:
        delta = jnp.pad(delta, ((0, pb), (0, pn)))
        z_star = jnp.pad(z_star, ((0, pb), (0, pn)))
    if pm or pn:
        w = jnp.pad(w, ((0, pm), (0, pn)))
    gm, gn, gk = x.shape[1] // bm_, delta.shape[1] // bn_, x.shape[0] // bk_
    kernel = functools.partial(
        _nitro_grad_w_opt_kernel, n_k=gk, alpha_inv=alpha_inv
    )
    scalars = jnp.stack(
        [jnp.asarray(gamma_inv, jnp.int32), jnp.asarray(eta_inv, jnp.int32)]
    )
    out = pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bk_, bm_), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct(w.shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(scalars, x, delta, z_star, w)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("alpha_inv", "bm", "bn", "bk", "interpret"),
)
def nitro_matmul_grad_x(
    delta: jax.Array,
    z_star: jax.Array,
    w: jax.Array,
    *,
    alpha_inv: int = 10,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """Fused input gradient: ``nitro_relu_backward(z_star, δ) @ wᵀ``.

    delta/z_star: (B, N), w: (M, N) natural layout → (B, M) int32.  Grid is
    (B/bm, M/bn, N/bk) contracting over the fan-out; the prologue masks
    each (bm, bk) δ tile in VMEM.  Padded fan-out columns have δ = z* = 0
    and w = 0, so the extra contraction terms vanish exactly.
    """
    b, n = delta.shape
    m, n2 = w.shape
    assert n == n2, f"fan-out mismatch {n} vs {n2}"
    assert delta.shape == z_star.shape, "delta/z_star shape mismatch"
    bm_, bn_, bk_ = min(bm, b), min(bn, m), min(bk, n)
    pb, pm, pn = (-b) % bm_, (-m) % bn_, (-n) % bk_
    if pb or pn:
        delta = jnp.pad(delta, ((0, pb), (0, pn)))
        z_star = jnp.pad(z_star, ((0, pb), (0, pn)))
    if pm or pn:
        w = jnp.pad(w, ((0, pm), (0, pn)))
    gm, gn, gk = delta.shape[0] // bm_, w.shape[0] // bn_, delta.shape[1] // bk_
    kernel = functools.partial(
        _nitro_grad_x_kernel, n_k=gk, alpha_inv=alpha_inv
    )
    w = stack_limbs(w)
    out = pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec(
                (w.shape[0], bn_, bk_), lambda i, j, kk: (0, j, kk)
            ),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((delta.shape[0], w.shape[1]), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(delta, z_star, w)
    return out[:b, :m]
