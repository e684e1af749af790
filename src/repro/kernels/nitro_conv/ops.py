"""Public wrappers + backend dispatch for the streaming conv kernel.

This module is the **single conv entry point** every consumer shares:

  * training forward — ``core.blocks.forward_layers`` calls
    ``fused_conv_fwd`` (activation *and* the cached pre-ReLU ``z_star``);
  * training backward — ``kernels.grad_ops`` (behind
    ``core.layers.conv_backward``) calls ``conv_grad_w`` / ``conv_grad_x``,
    passing the cached ``z_star`` so the NITRO-ReLU-bwd/STE prologue runs
    inside the gradient kernels;
  * inference — ``infer.plan`` calls ``fused_conv`` (activation only,
    optionally int8-narrowed, optionally with the fused 2×2 pool).

Every entry point runs one data path, implicit im2col: row bands are
staged through VMEM (Pallas) or band-local patch blocks (jnp), and the
``(N·H·W, K²·C)`` patch matrix never exists.  One static knob picks who
runs it:

``backend`` (same vocabulary as ``nitro_matmul.ops``)
  * ``'pallas'``     — the real TPU kernel;
  * ``'interpret'``  — the same kernel through the Pallas interpreter;
  * ``'reference'``  — the pure-jnp streaming oracle from ``ref.py``;
  * ``'auto'``       — pallas on TPU, reference elsewhere.

Every backend is bit-identical — integer arithmetic makes the
tiling/accumulation order irrelevant.  The tests hold them to the
explicit-im2col composition (``core.layers.conv_forward``) and to
``jax.lax.conv_general_dilated`` on int32 operands.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.autotune import state as autotune
from repro.kernels.autotune.tiles import TileConfig
from repro.kernels.nitro_conv import ref as conv_ref
from repro.kernels.nitro_conv.nitro_conv import (
    stream_conv,
    stream_conv_fwd,
    stream_conv_grad_w,
    stream_conv_grad_w_opt,
    stream_conv_grad_x,
)
from repro.kernels.nitro_matmul.ops import (
    _guard_int8,
    check_alpha_inv,
    resolve_backend,
    resolve_operand_dtype,
)


def _stream_tile_kw(tiles: TileConfig | None) -> dict:
    """bh/bf kwargs for the streaming kernels (defaults when untuned)."""
    return {} if tiles is None else dict(bh=tiles.bh, bf=tiles.bf)


# ---------------------------------------------------------------------------
# Forward entry points
# ---------------------------------------------------------------------------


def fused_conv(
    x: jax.Array,
    w: jax.Array,
    *,
    sf: int,
    alpha_inv: int = 10,
    apply_relu: bool = True,
    pool: bool = False,
    out_dtype=jnp.int32,
    backend: str = "auto",
    tiles: TileConfig | None = None,
    operand_dtype: str = "auto",
) -> jax.Array:
    """One fused conv+scale(+relu)(+2×2 pool) — the inference plan step.

    (N,H,W,C) int × (K,K,C,F) int → (N,H,W,F), or (N,H//2,W//2,F) when
    ``pool=True``; the pool runs in the kernel epilogue.

    ``tiles``/``operand_dtype`` mirror ``fused_matmul``'s knobs: both are
    perf-only and bitwise result-invariant.  ``tiles=None`` consults the
    autotune cache under the conv's own key.
    """
    alpha_inv = check_alpha_inv(alpha_inv, apply_relu)
    backend = resolve_backend(backend)
    od = resolve_operand_dtype(operand_dtype, x, w)
    if od == "int8":
        x = _guard_int8(x, "x")
        w = _guard_int8(w, "w")
    if tiles is None:
        tiles = autotune.resolve_tiles(
            "conv", (x.shape[0], x.shape[1], x.shape[2], x.shape[3],
                     w.shape[0], w.shape[-1]),
            dtype=f"{x.dtype},{w.dtype}", backend=backend,
        )
    if backend == "reference":
        return conv_ref.stream_conv_ref(
            x, w, sf=sf, alpha_inv=alpha_inv, apply_relu=apply_relu,
            pool=pool, out_dtype=out_dtype,
            bh=None if tiles is None else tiles.bh, operand_dtype=od,
        )
    return stream_conv(
        x, w, sf=sf, alpha_inv=alpha_inv, apply_relu=apply_relu, pool=pool,
        out_dtype=out_dtype, interpret=(backend == "interpret"),
        operand_dtype=od, **_stream_tile_kw(tiles),
    )


def fused_conv_fwd(
    x: jax.Array,
    w: jax.Array,
    *,
    sf: int,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused conv *training* forward: ``(a, z_star)``, both (N,H,W,F).

    ``a`` keeps int32 (matching the unfused reference pipeline's dtype);
    ``z_star`` is the int32 pre-ReLU tensor ``forward_layers_backward``
    consumes for the NITRO-ReLU/STE backward.
    """
    alpha_inv = check_alpha_inv(alpha_inv, True)
    backend = resolve_backend(backend)
    if tiles is None:
        tiles = autotune.resolve_tiles(
            "conv_fwd", (x.shape[0], x.shape[1], x.shape[2], x.shape[3],
                         w.shape[0], w.shape[-1]),
            dtype=f"{x.dtype},{w.dtype}", backend=backend,
        )
    if backend == "reference":
        return conv_ref.stream_conv_fwd_ref(
            x, w, sf=sf, alpha_inv=alpha_inv,
            bh=None if tiles is None else tiles.bh,
        )
    return stream_conv_fwd(
        x, w, sf=sf, alpha_inv=alpha_inv,
        interpret=(backend == "interpret"), **_stream_tile_kw(tiles),
    )


# ---------------------------------------------------------------------------
# Backward entry points (integer conv gradients)
# ---------------------------------------------------------------------------


def conv_grad_w(
    x: jax.Array,
    grad_out: jax.Array,
    *,
    kernel_size: int,
    z_star: jax.Array | None = None,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> jax.Array:
    """Conv weight gradient: correlate input patches with ``grad_out``.

    (N,H,W,C) × (N,H,W,F) → (K,K,C,F) int32.  Patch bands are formed on
    the fly (VMEM accumulator in the kernel, band loop in the jnp oracle).

    ``z_star`` (same shape as ``grad_out``) enables the fused backward:
    the NITRO-ReLU-derivative/STE prologue is applied to the δ bands in
    VMEM.  Without it the caller has already applied the activation
    backward.
    """
    backend = resolve_backend(backend)
    if z_star is not None:
        alpha_inv = check_alpha_inv(alpha_inv, True)
    if tiles is None:
        tiles = autotune.resolve_tiles(
            "conv_grad_w",
            (x.shape[0], x.shape[1], x.shape[2], x.shape[3],
             kernel_size, grad_out.shape[-1]),
            dtype=f"{x.dtype},{grad_out.dtype}", backend=backend,
            prologue=z_star is not None,
        )
    if backend == "reference":
        return conv_ref.stream_conv_grad_w_ref(
            x, grad_out, kernel_size=kernel_size,
            z_star=z_star, alpha_inv=alpha_inv,
            bh=None if tiles is None else tiles.bh,
        )
    return stream_conv_grad_w(
        x, grad_out, kernel_size=kernel_size,
        z_star=z_star, alpha_inv=alpha_inv,
        interpret=(backend == "interpret"), **_stream_tile_kw(tiles),
    )


def conv_grad_w_opt(
    x: jax.Array,
    grad_out: jax.Array,
    w: jax.Array,
    gamma_inv: jax.Array,
    eta_inv: jax.Array,
    *,
    kernel_size: int,
    z_star: jax.Array,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> jax.Array:
    """Conv weight *update*: ``conv_grad_w`` with IntegerSGD applied in the
    streaming kernel's flush — returns W′ (K,K,C,F), grad_W never in HBM.

    ``z_star`` is required; a caller without it has pre-masked δ and no
    prologue, and composes ``conv_grad_w`` with ``optimizer.apply_update``
    instead (``grad_ops.conv_weight_update``).
    """
    backend = resolve_backend(backend)
    alpha_inv = check_alpha_inv(alpha_inv, True)
    if tiles is None:
        tiles = autotune.resolve_tiles(
            "conv_grad_w",
            (x.shape[0], x.shape[1], x.shape[2], x.shape[3],
             kernel_size, grad_out.shape[-1]),
            dtype=f"{x.dtype},{grad_out.dtype}", backend=backend,
            prologue=True, fuse_opt=True,
        )
    if backend == "reference":
        from repro.kernels.integer_sgd.ref import integer_sgd_ref

        grad_w = conv_ref.stream_conv_grad_w_ref(
            x, grad_out, kernel_size=kernel_size,
            z_star=z_star, alpha_inv=alpha_inv,
            bh=None if tiles is None else tiles.bh,
        )
        return integer_sgd_ref(w, grad_w, gamma_inv, eta_inv)
    return stream_conv_grad_w_opt(
        x, grad_out, z_star, w, gamma_inv, eta_inv,
        kernel_size=kernel_size, alpha_inv=alpha_inv,
        interpret=(backend == "interpret"), **_stream_tile_kw(tiles),
    )


def conv_grad_x(
    grad_out: jax.Array,
    w: jax.Array,
    *,
    z_star: jax.Array | None = None,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> jax.Array:
    """Conv input gradient: 'full' correlation of ``grad_out`` with the
    rotated kernel — one more conv, streamed the same way (unit scale, no
    activation).  (N,H,W,F) × (K,K,C,F) → (N,H,W,C) int32.

    With ``z_star`` the streaming kernel/oracle masks each δ band by the
    NITRO-ReLU derivative *before* patch formation (the fused backward).
    """
    backend = resolve_backend(backend)
    if z_star is not None:
        alpha_inv = check_alpha_inv(alpha_inv, True)
    if tiles is None:
        tiles = autotune.resolve_tiles(
            "conv_grad_x",
            (grad_out.shape[0], grad_out.shape[1], grad_out.shape[2],
             grad_out.shape[3], w.shape[0], w.shape[2]),
            dtype=f"{grad_out.dtype},{w.dtype}", backend=backend,
            prologue=z_star is not None,
        )
    if backend == "reference":
        return conv_ref.stream_conv_grad_x_ref(
            grad_out, w, z_star=z_star, alpha_inv=alpha_inv,
            bh=None if tiles is None else tiles.bh,
        )
    if z_star is not None:
        return stream_conv_grad_x(
            grad_out, z_star, w, alpha_inv=alpha_inv,
            interpret=(backend == "interpret"), **_stream_tile_kw(tiles),
        )
    return stream_conv(
        grad_out, conv_ref.rot180_swap(w), sf=1, apply_relu=False, pool=False,
        interpret=(backend == "interpret"), **_stream_tile_kw(tiles),
    )
