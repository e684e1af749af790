"""Streaming implicit-im2col conv Pallas TPU kernel.

Explicit im2col (``layers.conv_im2col_operands`` + an integer matmul, the
unfused oracle's route) pays a hidden ~K²× input-bandwidth tax: the full
``(N·H·W, K²·C)`` patch matrix is written to HBM and read back before the
matmul starts.  This kernel never forms that matrix.  Instead it

  * grids over ``(image, output-row band, filter tile)``;
  * DMAs only the ``bh + K − 1`` input rows the band needs from HBM into a
    VMEM row ring (once per band — the halo rows shared by the K×K window
    travel over HBM a single time, not K² times);
  * builds the band's patch block *in VMEM* from K² overlapping row/column
    slices of the ring — implicit im2col, layout identical to
    ``core.layers.im2col`` so every path shares one flattened weight
    ``w.reshape(K²·C, F)``;
  * runs one MXU matmul per ``(band, filter-tile)`` with int32 accumulation
    and the NITRO scale / NITRO-ReLU epilogue on the VPU;
  * optionally folds a 2×2 max-pool into the epilogue, so pooled layers
    write ``H/2·W/2`` activations instead of ``H·W`` plus a separate jnp
    pool pass.

HBM bytes on the conv input:  explicit      ~(1 + 2·K²)·H·W·C
                              streaming     ~H·W·C   (each band's rows are
                              DMA'd once, at filter-tile 0, and the VMEM
                              ring is reused across the filter grid)

A shallow input whose K²·C taps fit one 128-lane tile (``folds_taps``:
the 3-channel network input, 27 lanes) would pad each of the K² patch
segments to 128 lanes, a K²·128-deep contraction of mostly zeros.  Such a
call folds its taps instead: the wrapper builds the 'same' patches
``im2col(x)`` (N,H,W,K²·C) in HBM and runs the kernels as a 1×1 conv over
them with the weight ``w.reshape(1, 1, K²·C, F)`` — one lane tile of
contraction, the same integer result.  A 1×1 call has no halo, so its
input bands are pipelined blocks, lane-padded in VMEM.  The folded calls
are named ``<kernel>_folded``.

The kernel bodies share the scaffolding:

  ``_stream_conv_kernel``         activation only (+ optional fused pool) —
                                  the inference plan step;
  ``_stream_conv_fwd_kernel``     two outputs ``(a, z_star)`` — the training
                                  forward (z* is the LES backward's cache);
  ``_stream_grad_w_kernel``       Σ patch_bandᵀ·g_band accumulated in a VMEM
                                  scratch — the conv weight gradient;
  ``_stream_grad_w_fused_kernel`` the same with the NITRO-ReLU-bwd/STE
                                  prologue masking each δ band in VMEM;
  ``_stream_grad_x_kernel``       the conv input gradient as a streaming
                                  'full' correlation over *masked* δ rows —
                                  δ and z* rows are DMA'd per band and the
                                  prologue rewrites the δ ring in place.

Geometry (row-band size, H padding) is shared with the pure-jnp oracle via
``ref.conv_geometry`` so the Pallas and reference backends stream the same
bands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.activations import mu_int8
from repro.core.layers import im2col
from repro.core.scaling import pow2_split
from repro.kernels.autotune.tiles import DEFAULT_TILES
from repro.kernels.nitro_conv.ref import DEFAULT_BH, conv_geometry, rot180_swap
from repro.kernels.integer_sgd.integer_sgd import integer_sgd_tile
from repro.kernels.limbs import (
    INT32_LIMBS,
    limb_count,
    limb_dot,
    planes,
    split_limbs,
    stack_limbs,
)
from repro.kernels.nitro_matmul.nitro_matmul import (
    _relu_bwd_tile,
    _relu_tile,
    _scale_tile,
)

#: Filter-tile width (MXU lane dimension) — alias of the single definition
#: in ``kernels.autotune.tiles.DEFAULT_TILES``.
DEFAULT_BF = DEFAULT_TILES.bf

_SUBLANE = 8    # int32 sublane tile: the row ring's width granularity
_LANE = 128     # lane tile: channel and filter granularity
_FLUSH_ROWS = 128  # accumulator rows per step of the update flush (K²C is
                   # a multiple of K²·128)

#: ``dot_general`` dimension numbers: patches @ w, and patchesᵀ @ g.
_P_W = (((1,), (0,)), ((), ()))
_PT_G = (((0,), (0,)), ((), ()))


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def folds_taps(k: int, c: int) -> bool:
    """Whether a K×K conv over C input channels runs folded, as a 1×1 conv
    over its K²·C-channel patches: K > 1 and the taps fit one lane tile."""
    return k > 1 and k * k * c <= _LANE


def _fold(x, w, k: int, name: str):
    """The operands, kernel size and Pallas call name a streaming conv
    call runs with: ``(im2col(x), w as (1,1,K²C,F), 1, name_folded)``
    where ``folds_taps(K, C)``, else the call as given.  ``im2col``'s
    channel order ``(ki·K + kj)·C + c`` is that of ``w.reshape(K²C, F)``,
    so the folded conv is bit-identical.  ``w`` may be None (grad_W)."""
    c = x.shape[-1]
    if not folds_taps(k, c):
        return x, w, k, name
    if w is not None:
        w = w.reshape(1, 1, k * k * c, w.shape[-1])
    return im2col(x, k, k // 2), w, 1, f"{name}_folded"


def _width_geometry(w_sp: int, k: int) -> tuple[int, int]:
    """``(w_out, ring_w)``: the band's output width rounded up to the
    sublane tile, and the row ring's width (halo included), rounded up
    likewise — Mosaic slices and reshapes the ring along that dimension.

    Columns ``[W, w_out)`` see only zero padding and are sliced away.
    """
    w_out = _round_up(w_sp, _SUBLANE)
    return w_out, _round_up(w_out + k - 1, _SUBLANE)


def _pad_rows(x, h, h_pad, p, ring_w, c_pad):
    """Zero-pad an (N,H,W,C) input into the ring layout as int32: the
    halo plus band multiple in H, the halo plus sublane tile in W, the
    channels to the lane tile — exact for integer conv."""
    w_sp, c = x.shape[2], x.shape[3]
    return jnp.pad(
        x.astype(jnp.int32),
        ((0, 0), (p, p + h_pad - h), (p, ring_w - w_sp - p), (0, c_pad - c)),
    )


def _pad_grad(g, h_pad, w_out, f_pad):
    """Zero-pad an (N,H,W,F) gradient band source to the output layout."""
    _, h, w_sp, f = g.shape
    return jnp.pad(
        g.astype(jnp.int32),
        ((0, 0), (0, h_pad - h), (0, w_out - w_sp), (0, f_pad - f)),
    )


def _flat_weight(w, c_pad, f_pad):
    """(K,K,C,F) → (K²·c_pad, f_pad), the patch block's row layout."""
    k, _, c, f = w.shape
    w = jnp.pad(w, ((0, 0), (0, 0), (0, c_pad - c), (0, f_pad - f)))
    return w.reshape(k * k * c_pad, f_pad)


def _load_band(x_hbm, rows_ref, sem, n, band_idx, band_rows: int):
    """DMA one image's input-row band HBM → VMEM row ring."""
    copy = pltpu.make_async_copy(
        x_hbm.at[n, pl.ds(band_idx, band_rows)], rows_ref, sem
    )
    copy.start()
    copy.wait()


def _form_patches(rows_ref, patches_ref, *, k: int, bh: int, w_out: int, c: int):
    """Implicit im2col: K² overlapping slices of the row ring → patch block.

    ``patches[(r·W + w), (ki·K + kj)·C + c] = rows[r + ki, w + kj, c]`` —
    the ``core.layers.im2col`` layout (with C the lane-padded channel
    count), built from VMEM-resident int32 rows.
    """
    for ki in range(k):
        for kj in range(k):
            seg = rows_ref[ki:ki + bh, kj:kj + w_out, :]
            patches_ref[:, (ki * k + kj) * c:(ki * k + kj + 1) * c] = (
                seg.reshape(bh * w_out, c)
            )


def _stage_patches(x_ref, rows, patches, sem, n, band, *, k: int, bh: int,
                   w_out: int, c: int):
    """Fill one band's patch block.  A K×K call DMAs the band's rows, halo
    included, into the VMEM ring and forms the K² slices.  A 1×1 call (a
    folded one) has no halo: its ``x_ref`` is the band's pipelined
    (1, bh, W, C_in) block (``_conv_input``), already the patch block but
    for the zero lanes past C_in."""
    if k == 1:
        c_in = x_ref.shape[-1]
        if c_in < c:
            patches[...] = jnp.zeros_like(patches)
        patches[:, :c_in] = x_ref[0].reshape(bh * w_out, c_in)
        return
    _load_band(x_ref, rows, sem, n, band * bh, bh + k - 1)
    _form_patches(rows, patches, k=k, bh=bh, w_out=w_out, c=c)


def _band_matmul(patches_ref, w_ref, *, bh: int, w_out: int, bf: int,
                 x_limbs: int):
    """One band: (bh·W, K²C) @ (K²C, bf) → int32 (bh, W, bf).

    The patch block is split into ``x_limbs`` int8 limbs (1 when the conv
    input is int8, 4 otherwise) and ``w_ref`` carries the weight tile's
    limb planes, so the dot is ``limb_dot``'s int8 MXU passes — bit-exact
    with an int32 dot.
    """
    z = limb_dot(split_limbs(patches_ref[...], x_limbs), planes(w_ref), _P_W)
    return z.reshape(bh, w_out, bf)


def _maxpool_tile(z, pool_ref, *, bh: int, w_out: int):
    """Fused 2×2 stride-2 max-pool epilogue on a (bh, W, bf) tile.

    The column pairs are read back from a VMEM scratch with stride-2
    loads (the sublane dimension cannot be split in registers); the row
    pairs split the untiled leading dimension.
    """
    w2 = w_out // 2
    pool_ref[...] = z
    a = jnp.maximum(
        pool_ref[:, pl.ds(0, w2, stride=2), :],
        pool_ref[:, pl.ds(1, w2, stride=2), :],
    )
    return a.reshape(bh // 2, 2, w2, -1).max(axis=1)


def _stream_conv_kernel(
    x_hbm, w_ref, out_ref, rows, patches, sem, *pool_scratch,
    k, bh, w_out, c, bf, x_limbs,
    sf_shift, sf_residual, alpha_inv, mu, apply_relu, pool, out_dtype,
):
    """Activation-only streaming conv step (the inference plan's layer)."""
    n, band, f = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(f == 0)  # rows + patches are reused across filter tiles
    def _stage_band():
        _stage_patches(x_hbm, rows, patches, sem, n, band,
                       k=k, bh=bh, w_out=w_out, c=c)

    z = _band_matmul(patches, w_ref, bh=bh, w_out=w_out, bf=bf,
                     x_limbs=x_limbs)
    z = _scale_tile(z, sf_shift, sf_residual)
    if apply_relu:
        z = _relu_tile(z, alpha_inv, mu)
    if pool:
        z = _maxpool_tile(z, pool_scratch[0], bh=bh, w_out=w_out)
    out_ref[0] = z.astype(out_dtype)


def _stream_conv_fwd_kernel(
    x_hbm, w_ref, a_ref, zstar_ref, rows, patches, sem, *,
    k, bh, w_out, c, bf, x_limbs,
    sf_shift, sf_residual, alpha_inv, mu, out_dtype,
):
    """Training-forward variant: ``(a, z_star)`` from one accumulation.

    Mirrors ``nitro_matmul_fwd``: the raw pre-activation ``z`` never leaves
    VMEM; the scaled ``z*`` (int32, the NITRO-ReLU/STE backward cache) and
    the activation are the only HBM writes.
    """
    n, band, f = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(f == 0)
    def _stage_band():
        _stage_patches(x_hbm, rows, patches, sem, n, band,
                       k=k, bh=bh, w_out=w_out, c=c)

    z = _band_matmul(patches, w_ref, bh=bh, w_out=w_out, bf=bf,
                     x_limbs=x_limbs)
    z_star = _scale_tile(z, sf_shift, sf_residual)
    zstar_ref[0] = z_star
    a_ref[0] = _relu_tile(z_star, alpha_inv, mu).astype(out_dtype)


def _grad_w_accumulate(
    x_hbm, g2d, out_ref, rows, patches, acc, sem, *,
    k, bh, w_out, c, x_limbs, n_steps, flush=None,
):
    """Shared grad_w body: acc += patch_bandᵀ @ g2d per (image, band).

    Grid is ``(filter tile, image, band)`` — the filter tile is outermost so
    the (K²C, bf) VMEM accumulator runs over every image/band before its
    single HBM write.  ``g2d`` is the (bh·W, bf) int32 gradient band,
    already in VMEM registers (masked by the caller on the fused path).
    ``flush`` lets the caller transform the finished accumulator before
    the HBM write (the IntegerSGD epilogue): it maps one row block of the
    accumulator to the output rows; ``None`` writes the raw gradient.
    The flush walks ``_FLUSH_ROWS`` rows at a time in a loop, so the
    kernel's code stays small however many rows K²C has.
    """
    n, band = pl.program_id(1), pl.program_id(2)
    step = n * pl.num_programs(2) + band

    @pl.when(step == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    _stage_patches(x_hbm, rows, patches, sem, n, band,
                   k=k, bh=bh, w_out=w_out, c=c)
    acc[...] += limb_dot(
        split_limbs(patches[...], x_limbs),
        split_limbs(g2d, INT32_LIMBS),
        _PT_G,
    )

    @pl.when(step == n_steps - 1)
    def _flush():
        if flush is None:
            out_ref[...] = acc[...]
            return

        def _rows(i, carry):
            rows = pl.ds(pl.multiple_of(i * _FLUSH_ROWS, _FLUSH_ROWS),
                         _FLUSH_ROWS)
            out_ref[rows, :] = flush(rows, acc[rows, :])
            return carry

        jax.lax.fori_loop(0, acc.shape[0] // _FLUSH_ROWS, _rows, 0)


def _stream_grad_w_kernel(
    x_hbm, g_ref, out_ref, rows, patches, acc, sem, *,
    k, bh, w_out, c, bf, x_limbs, n_steps,
):
    """Conv weight gradient, plain δ (the ReLU backward already applied)."""
    g2d = g_ref[0].reshape(bh * w_out, bf)
    _grad_w_accumulate(
        x_hbm, g2d, out_ref, rows, patches, acc, sem,
        k=k, bh=bh, w_out=w_out, c=c, x_limbs=x_limbs, n_steps=n_steps,
    )


def _stream_grad_w_fused_kernel(
    x_hbm, g_ref, z_ref, out_ref, rows, patches, acc, sem, *,
    k, bh, w_out, c, bf, x_limbs, n_steps, alpha_inv,
):
    """Conv weight gradient with the fused NITRO-ReLU-bwd/STE prologue.

    The δ band is masked against the matching ``z_star`` band in VMEM just
    before the MXU contraction — the post-ReLU-bwd δ never exists outside
    this (bh·W, bf) register tile.
    """
    g2d = _relu_bwd_tile(
        g_ref[0].reshape(bh * w_out, bf),
        z_ref[0].reshape(bh * w_out, bf),
        alpha_inv,
    )
    _grad_w_accumulate(
        x_hbm, g2d, out_ref, rows, patches, acc, sem,
        k=k, bh=bh, w_out=w_out, c=c, x_limbs=x_limbs, n_steps=n_steps,
    )


def _stream_grad_w_opt_kernel(
    scalars_ref, x_hbm, g_ref, z_ref, w_ref, out_ref, rows, patches, acc,
    sem, *, k, bh, w_out, c, bf, x_limbs, n_steps, alpha_inv,
):
    """Conv weight *update*: fused prologue + IntegerSGD flush epilogue.

    Accumulation matches ``_stream_grad_w_fused_kernel`` exactly; the last
    (image, band) step reads the flattened (K²C, bf) W tile and writes
    ``W − (⌊acc/γ_inv⌋ + ⌊W/η_inv⌋)`` — grad_W never reaches HBM.
    γ_inv/η_inv arrive in SMEM.
    """
    g2d = _relu_bwd_tile(
        g_ref[0].reshape(bh * w_out, bf),
        z_ref[0].reshape(bh * w_out, bf),
        alpha_inv,
    )
    _grad_w_accumulate(
        x_hbm, g2d, out_ref, rows, patches, acc, sem,
        k=k, bh=bh, w_out=w_out, c=c, x_limbs=x_limbs, n_steps=n_steps,
        flush=lambda rows, a: integer_sgd_tile(
            w_ref[rows, :], a, scalars_ref[0], scalars_ref[1]
        ),
    )


def _stream_grad_x_kernel(
    g_hbm, z_hbm, w_ref, out_ref, rows, zrows, patches, sem, zsem, *,
    k, bh, w_out, c, bf, alpha_inv,
):
    """Conv input gradient: streaming 'full' correlation over masked δ.

    Both the δ rows and the matching ``z_star`` rows are DMA'd into VMEM
    rings at filter-tile 0; the ReLU-bwd prologue rewrites the δ ring in
    place (the zero halo is preserved — relu_bwd(0, 0) = 0), patches are
    formed from the *masked* rows, and the rot180-swapped weight closes
    the correlation.  No scale/ReLU epilogue: sf = 1 for gradients.
    """
    n, band, f = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(f == 0)  # masked rows + patches are reused across filter tiles
    def _stage_band():
        _load_band(g_hbm, rows, sem, n, band * bh, bh + k - 1)
        _load_band(z_hbm, zrows, zsem, n, band * bh, bh + k - 1)
        rows[...] = _relu_bwd_tile(rows[...], zrows[...], alpha_inv)
        _form_patches(rows, patches, k=k, bh=bh, w_out=w_out, c=c)

    out_ref[0] = _band_matmul(patches, w_ref, bh=bh, w_out=w_out, bf=bf,
                              x_limbs=INT32_LIMBS)


def _conv_layout(x_shape, k: int, bh: int, *, pool: bool):
    """Every static size of one streaming conv call.

    Returns ``(bh, h_pad, p, w_out, ring_w, c_pad)``: the band geometry
    shared with the jnp oracle (``ref.conv_geometry``), the sublane-padded
    widths (``_width_geometry``) and the lane-padded input channels.
    """
    _, h, w_sp, c = x_shape
    bh_, h_pad, p = conv_geometry(h, k, bh, pool=pool)
    w_out, ring_w = _width_geometry(w_sp, k)
    return bh_, h_pad, p, w_out, ring_w, _round_up(c, _LANE)


def _filter_tiling(f: int, bf: int) -> tuple[int, int]:
    """``(bf, f_pad)``: a lane-aligned filter tile and the padded count."""
    bf_ = _round_up(min(bf, f), _LANE)
    return bf_, _round_up(f, bf_)


def _conv_scratches(k, bh, w_out, ring_w, c_pad):
    """The kernel's VMEM working set: int32 row ring, int32 patch block,
    DMA semaphore."""
    return [
        pltpu.VMEM((bh + k - 1, ring_w, c_pad), jnp.int32),
        pltpu.VMEM((bh * w_out, k * k * c_pad), jnp.int32),
        pltpu.SemaphoreType.DMA,
    ]


def _conv_input(x, k: int, layout, index_map):
    """The conv input operand and its spec.  A K×K call's input stays in
    HBM in the ring layout, channels lane-padded, for the kernel's halo
    DMA.  A 1×1 call (a folded one) has no halo: Pallas fetches each
    band's (1, bh, W, C) block ahead of the step that reads it, at the
    input's own channel width, which ``_stage_patches`` lane-pads in VMEM,
    so no lane-padded copy of the input is written to HBM."""
    bh, h_pad, p, w_out, ring_w, c_pad = layout
    if k > 1:
        return (_pad_rows(x, x.shape[1], h_pad, p, ring_w, c_pad),
                pl.BlockSpec(memory_space=pl.ANY))
    c = x.shape[-1]
    return (_pad_rows(x, x.shape[1], h_pad, p, ring_w, c),
            pl.BlockSpec((1, bh, w_out, c), index_map))


def _weight_spec(w_planes, bf, index_map):
    """BlockSpec of one (L, K²C, bf) filter tile of the weight's limb planes."""
    return pl.BlockSpec((w_planes.shape[0], w_planes.shape[1], bf), index_map)


#: Scoped VMEM limit of the conv kernels.  The grad kernels at 512
#: channels hold a (K²C, bf) accumulator, the double-buffered weight/output
#: tiles and one (K²C, bf) partial product per limb pair — past Mosaic's
#: 16 MiB default on v5e, well inside its 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

_ARBITRARY3 = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT_BYTES,
)


@functools.partial(
    jax.jit,
    static_argnames=(
        "sf", "alpha_inv", "apply_relu", "pool", "out_dtype",
        "bh", "bf", "operand_dtype", "interpret",
    ),
)
def stream_conv(
    x: jax.Array,
    w: jax.Array,
    *,
    sf: int,
    alpha_inv: int = 10,
    apply_relu: bool = True,
    pool: bool = False,
    out_dtype=jnp.int32,
    bh: int = DEFAULT_BH,
    bf: int = DEFAULT_BF,
    operand_dtype: str = "int32",
    interpret: bool = False,
) -> jax.Array:
    """Streaming fused 'same' conv: ``relu(⌊conv(x, w)/sf⌋)`` (+2×2 pool).

    x: (N,H,W,C) int, w: (K,K,C,F) int, K odd → (N,H,W,F) activations, or
    (N,H//2,W//2,F) with ``pool=True``.  Bit-exact with explicit im2col +
    an integer matmul (+ separate pool) on every shape.

    ``operand_dtype='int8'`` asserts both operands already *are* int8
    (the dispatcher proves eligibility and narrows): one int8 MXU pass per
    band.  Otherwise each operand is limb-split by its own dtype.  The row
    ring is int32 either way; the limb count is fixed here, statically.
    """
    if operand_dtype == "int8" and not (
        x.dtype == jnp.int8 and w.dtype == jnp.int8
    ):
        raise ValueError(
            f"operand_dtype='int8' requires int8 operands, got "
            f"{x.dtype}/{w.dtype} (the dispatcher narrows eligible inputs)"
        )
    n, h, w_sp, _ = x.shape
    f = w.shape[-1]
    if pool and (h < 2 or w_sp < 2):
        raise ValueError(f"2x2 pool epilogue needs H,W >= 2, got {h}x{w_sp}")
    x, w, k, name = _fold(x, w, w.shape[0], "stream_conv")
    layout = _conv_layout(x.shape, k, bh, pool=pool)
    bh_, h_pad, _, w_out, ring_w, c_pad = layout
    bf_, f_pad = _filter_tiling(f, bf)
    xp, x_spec = _conv_input(x, k, layout, lambda ni, bi, fi: (ni, bi, 0, 0))
    w_planes = stack_limbs(_flat_weight(w, c_pad, f_pad))

    shift, residual = pow2_split(sf)
    kernel = functools.partial(
        _stream_conv_kernel,
        k=k, bh=bh_, w_out=w_out, c=c_pad, bf=bf_,
        x_limbs=limb_count(x.dtype),
        sf_shift=shift, sf_residual=residual, alpha_inv=alpha_inv,
        mu=mu_int8(alpha_inv) if apply_relu else 0,
        apply_relu=apply_relu, pool=pool, out_dtype=out_dtype,
    )
    oh, ow = (bh_ // 2, w_out // 2) if pool else (bh_, w_out)
    scratches = _conv_scratches(k, bh_, w_out, ring_w, c_pad)
    if pool:
        scratches.append(pltpu.VMEM((bh_, w_out, bf_), jnp.int32))
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=(n, h_pad // bh_, f_pad // bf_),
        in_specs=[
            x_spec,
            _weight_spec(w_planes, bf_, lambda ni, bi, fi: (0, 0, fi)),
        ],
        out_specs=pl.BlockSpec(
            (1, oh, ow, bf_), lambda ni, bi, fi: (ni, bi, 0, fi)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n, (h_pad // bh_) * oh, ow, f_pad), out_dtype
        ),
        scratch_shapes=scratches,
        compiler_params=_ARBITRARY3,
        interpret=interpret,
    )(xp, w_planes)
    if pool:
        return out[:, : h // 2, : w_sp // 2, :f]
    return out[:, :h, :w_sp, :f]


@functools.partial(
    jax.jit,
    static_argnames=("sf", "alpha_inv", "out_dtype", "bh", "bf", "interpret"),
)
def stream_conv_fwd(
    x: jax.Array,
    w: jax.Array,
    *,
    sf: int,
    alpha_inv: int = 10,
    out_dtype=jnp.int32,
    bh: int = DEFAULT_BH,
    bf: int = DEFAULT_BF,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Streaming *training* forward: ``(a, z_star)`` in one pass.

    The conv analogue of ``nitro_matmul_fwd`` — same two-output contract,
    minus the HBM patch matrix on the input side.  An int8 ``x`` (a
    NITRO-ReLU activation narrowed by the caller) is one limb.
    """
    n, h, w_sp, _ = x.shape
    f = w.shape[-1]
    x, w, k, name = _fold(x, w, w.shape[0], "stream_conv_fwd")
    layout = _conv_layout(x.shape, k, bh, pool=False)
    bh_, h_pad, _, w_out, ring_w, c_pad = layout
    bf_, f_pad = _filter_tiling(f, bf)
    xp, x_spec = _conv_input(x, k, layout, lambda ni, bi, fi: (ni, bi, 0, 0))
    w_planes = stack_limbs(_flat_weight(w, c_pad, f_pad))

    shift, residual = pow2_split(sf)
    kernel = functools.partial(
        _stream_conv_fwd_kernel,
        k=k, bh=bh_, w_out=w_out, c=c_pad, bf=bf_,
        x_limbs=limb_count(x.dtype),
        sf_shift=shift, sf_residual=residual, alpha_inv=alpha_inv,
        mu=mu_int8(alpha_inv), out_dtype=out_dtype,
    )
    out_spec = pl.BlockSpec(
        (1, bh_, w_out, bf_), lambda ni, bi, fi: (ni, bi, 0, fi)
    )
    a, z_star = pl.pallas_call(
        kernel,
        name=name,
        grid=(n, h_pad // bh_, f_pad // bf_),
        in_specs=[
            x_spec,
            _weight_spec(w_planes, bf_, lambda ni, bi, fi: (0, 0, fi)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, h_pad, w_out, f_pad), out_dtype),
            jax.ShapeDtypeStruct((n, h_pad, w_out, f_pad), jnp.int32),
        ],
        scratch_shapes=_conv_scratches(k, bh_, w_out, ring_w, c_pad),
        compiler_params=_ARBITRARY3,
        interpret=interpret,
    )(xp, w_planes)
    return a[:, :h, :w_sp, :f], z_star[:, :h, :w_sp, :f]


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "alpha_inv", "bh", "bf", "interpret"),
)
def stream_conv_grad_w(
    x: jax.Array,
    grad_out: jax.Array,
    *,
    kernel_size: int,
    z_star: jax.Array | None = None,
    alpha_inv: int = 10,
    bh: int = DEFAULT_BH,
    bf: int = DEFAULT_BF,
    interpret: bool = False,
) -> jax.Array:
    """Streaming conv weight gradient: (N,H,W,C) × (N,H,W,F) → (K,K,C,F).

    Patch bands are formed in VMEM exactly as in the forward kernel and
    contracted against the matching gradient rows; the (K²C, bf) partial
    sums live in a VMEM accumulator until the last band.  int32 adds are
    order-exact, so the result matches ``im2colᵀ @ g`` bit-for-bit.

    With ``z_star`` (same shape as ``grad_out``) the NITRO-ReLU-bwd/STE
    prologue masks each δ band in VMEM before the contraction — the fused
    backward path; without it the δ is consumed as-is (the caller already
    applied the activation backward).  Padded δ and z* are 0, and the
    prologue maps (0, 0) → 0, so padding contributes nothing.
    """
    n, _, _, c = x.shape
    f = grad_out.shape[-1]
    x, _, k, name = _fold(x, None, kernel_size, "stream_conv_grad_w")
    layout = _conv_layout(x.shape, k, bh, pool=False)
    bh_, h_pad, _, w_out, ring_w, c_pad = layout
    bf_, f_pad = _filter_tiling(f, bf)
    xp, x_spec = _conv_input(x, k, layout, lambda fi, ni, bi: (ni, bi, 0, 0))

    n_bands = h_pad // bh_
    g_spec = pl.BlockSpec(
        (1, bh_, w_out, bf_), lambda fi, ni, bi: (ni, bi, 0, fi)
    )
    operands = [xp, _pad_grad(grad_out, h_pad, w_out, f_pad)]
    in_specs = [x_spec, g_spec]
    geom = dict(k=k, bh=bh_, w_out=w_out, c=c_pad, bf=bf_,
                x_limbs=limb_count(x.dtype), n_steps=n * n_bands)
    if z_star is None:
        kernel = functools.partial(_stream_grad_w_kernel, **geom)
    else:
        kernel = functools.partial(
            _stream_grad_w_fused_kernel, alpha_inv=alpha_inv, **geom
        )
        operands.append(_pad_grad(z_star, h_pad, w_out, f_pad))
        in_specs.append(g_spec)
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=(f_pad // bf_, n, n_bands),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((k * k * c_pad, bf_), lambda fi, ni, bi: (0, fi)),
        out_shape=jax.ShapeDtypeStruct((k * k * c_pad, f_pad), jnp.int32),
        scratch_shapes=_conv_scratches(k, bh_, w_out, ring_w, c_pad)[:2] + [
            pltpu.VMEM((k * k * c_pad, bf_), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=_ARBITRARY3,
        interpret=interpret,
    )(*operands)
    # a folded call's K²·C taps are the channels of its one 1×1 tap
    return out.reshape(k, k, c_pad, f_pad)[:, :, :x.shape[-1], :f].reshape(
        kernel_size, kernel_size, c, f)


@functools.partial(
    jax.jit,
    static_argnames=("kernel_size", "alpha_inv", "bh", "bf", "interpret"),
)
def stream_conv_grad_w_opt(
    x: jax.Array,
    grad_out: jax.Array,
    z_star: jax.Array,
    w: jax.Array,
    gamma_inv: jax.Array,
    eta_inv: jax.Array,
    *,
    kernel_size: int,
    alpha_inv: int = 10,
    bh: int = DEFAULT_BH,
    bf: int = DEFAULT_BF,
    interpret: bool = False,
) -> jax.Array:
    """Streaming conv weight *update*: grad_W stays in VMEM, IntegerSGD is
    applied in the flush, and the kernel returns W′ (K,K,C,F) directly.

    Same band geometry, padding, and accumulation order as the fused
    ``stream_conv_grad_w`` — bitwise-identical grad_W by construction —
    then the flush applies ``W − (⌊acc/γ_inv⌋ + ⌊W/η_inv⌋)`` per filter
    tile.  ``w`` rides in VMEM flattened to the (K²C, bf) output layout.
    Padded channels/filters have acc = 0 and w = 0 → W′ = 0, sliced away.
    """
    n, _, _, c = x.shape
    k = kernel_size
    f = grad_out.shape[-1]
    assert w.shape == (k, k, c, f), f"w shape {w.shape} != {(k, k, c, f)}"
    x, w, k, name = _fold(x, w, k, "stream_conv_grad_w_opt")
    layout = _conv_layout(x.shape, k, bh, pool=False)
    bh_, h_pad, _, w_out, ring_w, c_pad = layout
    bf_, f_pad = _filter_tiling(f, bf)
    xp, x_spec = _conv_input(x, k, layout, lambda fi, ni, bi: (ni, bi, 0, 0))

    n_bands = h_pad // bh_
    g_spec = pl.BlockSpec(
        (1, bh_, w_out, bf_), lambda fi, ni, bi: (ni, bi, 0, fi)
    )
    w_spec = pl.BlockSpec((k * k * c_pad, bf_), lambda fi, ni, bi: (0, fi))
    kernel = functools.partial(
        _stream_grad_w_opt_kernel,
        k=k, bh=bh_, w_out=w_out, c=c_pad, bf=bf_,
        x_limbs=limb_count(x.dtype), n_steps=n * n_bands,
        alpha_inv=alpha_inv,
    )
    scalars = jnp.stack(
        [jnp.asarray(gamma_inv, jnp.int32), jnp.asarray(eta_inv, jnp.int32)]
    )
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=(f_pad // bf_, n, n_bands),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            x_spec,
            g_spec,
            g_spec,
            w_spec,
        ],
        out_specs=w_spec,
        out_shape=jax.ShapeDtypeStruct((k * k * c_pad, f_pad), jnp.int32),
        scratch_shapes=_conv_scratches(k, bh_, w_out, ring_w, c_pad)[:2] + [
            pltpu.VMEM((k * k * c_pad, bf_), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=_ARBITRARY3,
        interpret=interpret,
    )(
        scalars,
        xp,
        _pad_grad(grad_out, h_pad, w_out, f_pad),
        _pad_grad(z_star, h_pad, w_out, f_pad),
        _flat_weight(w, c_pad, f_pad),
    )
    # a folded call's K²·C taps are the channels of its one 1×1 tap
    return out.reshape(k, k, c_pad, f_pad)[:, :, :x.shape[-1], :f].reshape(
        kernel_size, kernel_size, c, f)


@functools.partial(
    jax.jit,
    static_argnames=("alpha_inv", "bh", "bf", "interpret"),
)
def stream_conv_grad_x(
    delta: jax.Array,
    z_star: jax.Array,
    w: jax.Array,
    *,
    alpha_inv: int = 10,
    bh: int = DEFAULT_BH,
    bf: int = DEFAULT_BF,
    interpret: bool = False,
) -> jax.Array:
    """Streaming conv input gradient with the fused ReLU-bwd prologue.

    (N,H,W,F) δ × (N,H,W,F) z* × (K,K,C,F) weight → (N,H,W,C) int32: the
    'full' correlation of ``relu_bwd(z*, δ)`` with the rot180-swapped
    kernel, streamed exactly like the forward conv — δ *and* z* rows are
    DMA'd per band, masked in the VMEM ring, and the patch block is built
    from the masked rows.  The post-ReLU-bwd δ tensor never exists in HBM.
    The masked δ is a full int32 operand: four limbs.

    (The unfused input gradient stays ``stream_conv(δ_masked, rot180_swap(w),
    sf=1, apply_relu=False)`` — this kernel is that conv plus the prologue.)
    """
    n, h, w_sp, f = delta.shape
    k, c = w.shape[0], w.shape[2]
    assert delta.shape == z_star.shape, "delta/z_star shape mismatch"
    bh_, h_pad, p, w_out, ring_w, f_in = _conv_layout(
        delta.shape, k, bh, pool=False
    )
    bc, c_pad = _filter_tiling(c, bf)
    w_planes = stack_limbs(_flat_weight(rot180_swap(w), f_in, c_pad))
    kernel = functools.partial(
        _stream_grad_x_kernel,
        k=k, bh=bh_, w_out=w_out, c=f_in, bf=bc, alpha_inv=alpha_inv,
    )
    ring = pltpu.VMEM((bh_ + k - 1, ring_w, f_in), jnp.int32)
    out = pl.pallas_call(
        kernel,
        name="stream_conv_grad_x",
        grid=(n, h_pad // bh_, c_pad // bc),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # δ rows, DMA'd in-kernel
            pl.BlockSpec(memory_space=pl.ANY),  # z* rows, ditto
            _weight_spec(w_planes, bc, lambda ni, bi, fi: (0, 0, fi)),
        ],
        out_specs=pl.BlockSpec(
            (1, bh_, w_out, bc), lambda ni, bi, fi: (ni, bi, 0, fi)
        ),
        out_shape=jax.ShapeDtypeStruct((n, h_pad, w_out, c_pad), jnp.int32),
        scratch_shapes=[
            ring,                                       # masked δ row ring
            ring,                                       # z* row ring
            pltpu.VMEM((bh_ * w_out, k * k * f_in), jnp.int32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=_ARBITRARY3,
        interpret=interpret,
    )(
        _pad_rows(delta, h, h_pad, p, ring_w, f_in),
        _pad_rows(z_star, h, h_pad, p, ring_w, f_in),
        w_planes,
    )
    return out[:, :h, :w_sp, :c]
