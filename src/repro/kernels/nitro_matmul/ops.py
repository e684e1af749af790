"""Public wrappers + backend dispatch for the fused NITRO matmul kernel.

This module is the **single entry point** every matmul path shares:

  * training forward — ``core.blocks.forward_layers`` calls
    ``fused_matmul_fwd`` (returns the activation *and* the cached pre-ReLU
    ``z_star``);
  * training backward — ``kernels.grad_ops`` calls ``grad_w_matmul`` /
    ``grad_x_matmul`` (gradient matmuls whose VMEM prologue applies the
    NITRO-ReLU derivative + scaling STE to the δ tiles);
  * inference — ``infer.plan`` calls ``fused_matmul`` (activation only,
    optionally narrowed to int8 between layers).

Backend selection is centralised here (``resolve_backend``):

  * ``'pallas'``     — the real TPU kernel;
  * ``'interpret'``  — the same kernel through the Pallas interpreter
                       (bit-exact off-TPU; what the parity tests use);
  * ``'reference'``  — the pure-jnp oracle from ``ref.py`` (fast on CPU);
  * ``'auto'``       — pallas on TPU, reference elsewhere.

``nitro_linear`` / ``nitro_conv2d`` remain as drop-in fused replacements
for the reference layer pipeline (IntegerLinear/IntegerConv2D → NITRO
Scaling → NITRO-ReLU) with the legacy ``use_kernel``/``interpret`` knobs.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core.layers import conv_im2col_operands
from repro.core.scaling import conv_scale_factor, linear_scale_factor
from repro.kernels.autotune import state as autotune
from repro.kernels.autotune.tiles import TileConfig
from repro.kernels.nitro_matmul.nitro_matmul import (
    nitro_matmul,
    nitro_matmul_fwd,
    nitro_matmul_grad_w,
    nitro_matmul_grad_w_opt,
    nitro_matmul_grad_x,
)
from repro.kernels.nitro_matmul.ref import (
    nitro_matmul_fwd_ref,
    nitro_matmul_grad_w_ref,
    nitro_matmul_grad_x_ref,
    nitro_matmul_ref,
)

BACKENDS = ("auto", "pallas", "interpret", "reference")

#: Operand-dtype policy for the MXU dots (inference entry points):
#:   * ``'auto'``  — int8 operands stay int8 (one MXU pass) whenever
#:                   *both* operands already are; anything else is
#:                   limb-split by its dtype.  Never changes results.
#:   * ``'int8'``  — force the int8 path: int8 operands pass through;
#:                   concrete non-int8 operands are guarded (telemetry
#:                   ``bit_width`` ≤ 7) and narrowed; traced non-int8
#:                   operands raise.
#:   * ``'int32'`` — split each operand by its own dtype
#:                   (``kernels.limbs``: int8 one limb, wider four).
OPERAND_DTYPES = ("auto", "int8", "int32")


def _guard_int8(arr: jax.Array, name: str) -> jax.Array:
    """Runtime guard for the forced-int8 path: prove fit, then narrow.

    int8 arrays pass through.  A *concrete* wider array is checked with
    the telemetry ``bit_width`` reduction (≤ 7 bits ⇒ values in
    [-127, 127] ⇒ exact int8) and narrowed; a traced wider array cannot
    be value-checked, so it raises — use ``operand_dtype='auto'`` (which
    keys off dtypes alone) under jit, or narrow before tracing.
    """
    if arr.dtype == jnp.int8:
        return arr
    if isinstance(arr, jax.core.Tracer):
        raise ValueError(
            f"operand_dtype='int8': operand {name!r} is a traced "
            f"{arr.dtype} array — int8 fit cannot be proven under jit; "
            f"pass int8 operands or use operand_dtype='auto'"
        )
    from repro.obs.telemetry import bit_width

    bits = int(bit_width(arr).max())
    if bits > 7:
        raise ValueError(
            f"operand_dtype='int8': operand {name!r} needs {bits} bits "
            f"(> 7) — values do not fit int8; use the int32 escape hatch"
        )
    return arr.astype(jnp.int8)


def resolve_operand_dtype(
    operand_dtype: str, x: jax.Array, w: jax.Array
) -> str:
    """Resolve the ``'auto'`` policy to a concrete ``'int8'``/``'int32'``."""
    if operand_dtype not in OPERAND_DTYPES:
        raise ValueError(
            f"unknown operand_dtype {operand_dtype!r}; one of {OPERAND_DTYPES}"
        )
    if operand_dtype == "auto":
        return (
            "int8"
            if x.dtype == jnp.int8 and w.dtype == jnp.int8
            else "int32"
        )
    return operand_dtype


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str) -> str:
    """Validate + resolve ``'auto'`` to a concrete backend for this host."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    if backend == "auto":
        return "pallas" if _on_tpu() else "reference"
    return backend


def check_alpha_inv(alpha_inv: int, apply_relu: bool) -> int:
    """Validate the NITRO-ReLU leak divisor ``α_inv = ⌊1/α⌋``.

    ``alpha_inv`` divides the negative segment, so 0 would floor-divide by
    zero inside the kernel — historically it was silently coerced to 1
    (``alpha_inv or 1``); now it raises.  When ``apply_relu=False`` the
    value is unused and normalised to 1, so frozen no-activation layers
    (exported with ``alpha_inv=0``) neither fail nor fan out into
    spurious kernel recompilations.
    """
    if not apply_relu:
        return 1
    if int(alpha_inv) < 1:
        raise ValueError(
            f"alpha_inv must be a positive integer when apply_relu=True, "
            f"got {alpha_inv!r}"
        )
    return int(alpha_inv)


def fused_matmul(
    x2: jax.Array,
    w2: jax.Array,
    *,
    sf: int,
    alpha_inv: int = 10,
    apply_relu: bool = True,
    out_dtype=jnp.int32,
    backend: str = "auto",
    tiles: TileConfig | None = None,
    operand_dtype: str = "auto",
) -> jax.Array:
    """One fused matmul+scale(+relu) on 2-D operands — the inference step.

    ``tiles`` overrides the kernel tile sizes; ``None`` consults the
    process-wide autotune cache (``kernels.autotune``) and falls back to
    the defaults on a miss.  ``operand_dtype`` selects the MXU operand
    path (see ``OPERAND_DTYPES``) — both knobs are perf-only and bitwise
    result-invariant.
    """
    backend = resolve_backend(backend)
    alpha_inv = check_alpha_inv(alpha_inv, apply_relu)
    od = resolve_operand_dtype(operand_dtype, x2, w2)
    if od == "int8":
        x2 = _guard_int8(x2, "x")
        w2 = _guard_int8(w2, "w")
    if tiles is None:
        tiles = autotune.resolve_tiles(
            "matmul", (x2.shape[0], x2.shape[1], w2.shape[1]),
            dtype=f"{x2.dtype},{w2.dtype}", backend=backend,
        )
    if backend == "reference":
        return nitro_matmul_ref(
            x2, w2, sf=sf, alpha_inv=alpha_inv, apply_relu=apply_relu,
            out_dtype=out_dtype, operand_dtype=od,
        )
    tile_kw = {} if tiles is None else dict(
        bm=tiles.bm, bn=tiles.bn, bk=tiles.bk
    )
    return nitro_matmul(
        x2, w2, sf=sf, alpha_inv=alpha_inv, apply_relu=apply_relu,
        out_dtype=out_dtype, operand_dtype=od,
        interpret=(backend == "interpret"), **tile_kw,
    )


def fused_matmul_fwd(
    x2: jax.Array,
    w2: jax.Array,
    *,
    sf: int,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused training forward on 2-D operands: ``(a, z_star)``, both int32.

    ``a`` keeps int32 (not the inference plan's int8 narrowing) so the
    fused train step is bit- *and dtype*-identical to the unfused
    reference pipeline; ``z_star`` is what ``forward_layers_backward``
    consumes for the NITRO-ReLU/STE backward.  (Training entry points
    take ``tiles`` but not ``operand_dtype`` — train operands are int32
    by the dtype-identical contract.)
    """
    backend = resolve_backend(backend)
    alpha_inv = check_alpha_inv(alpha_inv, True)
    if tiles is None:
        tiles = autotune.resolve_tiles(
            "matmul_fwd", (x2.shape[0], x2.shape[1], w2.shape[1]),
            dtype=f"{x2.dtype},{w2.dtype}", backend=backend,
        )
    if backend == "reference":
        return nitro_matmul_fwd_ref(x2, w2, sf=sf, alpha_inv=alpha_inv)
    tile_kw = {} if tiles is None else dict(
        bm=tiles.bm, bn=tiles.bn, bk=tiles.bk
    )
    return nitro_matmul_fwd(
        x2, w2, sf=sf, alpha_inv=alpha_inv,
        interpret=(backend == "interpret"), **tile_kw,
    )


def grad_w_matmul(
    x2: jax.Array,
    delta2: jax.Array,
    z_star2: jax.Array,
    *,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> jax.Array:
    """Fused backward weight matmul on 2-D operands.

    ``x2ᵀ @ relu_bwd(z*, δ)`` with the NITRO-ReLU-derivative/STE prologue
    applied to the δ tiles in VMEM (pallas/interpret) or composed from the
    reference ops (reference) — bit-identical either way.
    """
    backend = resolve_backend(backend)
    alpha_inv = check_alpha_inv(alpha_inv, True)
    if tiles is None:
        tiles = autotune.resolve_tiles(
            "matmul_grad_w", (x2.shape[0], x2.shape[1], delta2.shape[1]),
            dtype=f"{x2.dtype},{delta2.dtype}", backend=backend,
            prologue=True,
        )
    if backend == "reference":
        return nitro_matmul_grad_w_ref(x2, delta2, z_star2, alpha_inv=alpha_inv)
    tile_kw = {} if tiles is None else dict(
        bm=tiles.bm, bn=tiles.bn, bk=tiles.bk
    )
    return nitro_matmul_grad_w(
        x2, delta2, z_star2, alpha_inv=alpha_inv,
        interpret=(backend == "interpret"), **tile_kw,
    )


def grad_w_opt_matmul(
    x2: jax.Array,
    delta2: jax.Array,
    z_star2: jax.Array,
    w2: jax.Array,
    gamma_inv: jax.Array,
    eta_inv: jax.Array,
    *,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> jax.Array:
    """Fused backward weight *update* on 2-D operands — returns W′.

    pallas/interpret run ``nitro_matmul_grad_w_opt`` (IntegerSGD applied in
    the grad kernel's flush, grad_W never in HBM); reference composes the
    same two oracles the unfused path uses — bit-identical either way
    because integer floor-div over an order-exact int32 accumulation is
    exact.
    """
    backend = resolve_backend(backend)
    alpha_inv = check_alpha_inv(alpha_inv, True)
    if tiles is None:
        tiles = autotune.resolve_tiles(
            "matmul_grad_w", (x2.shape[0], x2.shape[1], delta2.shape[1]),
            dtype=f"{x2.dtype},{delta2.dtype}", backend=backend,
            prologue=True, fuse_opt=True,
        )
    if backend == "reference":
        from repro.kernels.integer_sgd.ref import integer_sgd_ref

        grad_w = nitro_matmul_grad_w_ref(
            x2, delta2, z_star2, alpha_inv=alpha_inv
        )
        return integer_sgd_ref(w2, grad_w, gamma_inv, eta_inv)
    tile_kw = {} if tiles is None else dict(
        bm=tiles.bm, bn=tiles.bn, bk=tiles.bk
    )
    return nitro_matmul_grad_w_opt(
        x2, delta2, z_star2, w2, gamma_inv, eta_inv, alpha_inv=alpha_inv,
        interpret=(backend == "interpret"), **tile_kw,
    )


def grad_x_matmul(
    delta2: jax.Array,
    z_star2: jax.Array,
    w2: jax.Array,
    *,
    alpha_inv: int = 10,
    backend: str = "auto",
    tiles: TileConfig | None = None,
) -> jax.Array:
    """Fused backward input matmul on 2-D operands.

    ``relu_bwd(z*, δ) @ w2ᵀ`` — the transpose happens via the kernel's
    contraction dims, and the prologue masks δ in VMEM exactly as
    ``grad_w_matmul`` does.
    """
    backend = resolve_backend(backend)
    alpha_inv = check_alpha_inv(alpha_inv, True)
    if tiles is None:
        tiles = autotune.resolve_tiles(
            "matmul_grad_x", (delta2.shape[0], delta2.shape[1], w2.shape[0]),
            dtype=f"{delta2.dtype},{w2.dtype}", backend=backend,
            prologue=True,
        )
    if backend == "reference":
        return nitro_matmul_grad_x_ref(delta2, z_star2, w2, alpha_inv=alpha_inv)
    tile_kw = {} if tiles is None else dict(
        bm=tiles.bm, bn=tiles.bn, bk=tiles.bk
    )
    return nitro_matmul_grad_x(
        delta2, z_star2, w2, alpha_inv=alpha_inv,
        interpret=(backend == "interpret"), **tile_kw,
    )


def _legacy_backend(use_kernel: bool | None, interpret: bool | None) -> str:
    """Map the historical ``use_kernel``/``interpret`` knobs to a backend.

    Both knobs are deprecated in favour of ``backend=``; passing either
    explicitly warns.  ``use_kernel=False`` with ``interpret=True`` is
    contradictory (no kernel to interpret) and raises instead of the
    historical behaviour of silently preferring ``use_kernel`` — and an
    explicit ``interpret=True`` with ``use_kernel`` unset now selects the
    interpreter instead of being silently dropped off-TPU.
    """
    if use_kernel is not None or interpret is not None:
        warnings.warn(
            "use_kernel/interpret are deprecated; use backend="
            "'pallas'|'interpret'|'reference'|'auto' instead",
            DeprecationWarning,
            stacklevel=3,
        )
    if use_kernel is False and interpret:
        raise ValueError(
            "contradictory legacy knobs: use_kernel=False disables the "
            "kernel but interpret=True requests the Pallas interpreter; "
            "pass backend='reference' or backend='interpret' instead"
        )
    if use_kernel is None:
        use_kernel = _on_tpu() or bool(interpret)
    if not use_kernel:
        return "reference"
    if interpret is None:
        interpret = not _on_tpu()
    return "interpret" if interpret else "pallas"


def nitro_linear(
    x: jax.Array,
    w: jax.Array,
    *,
    alpha_inv: int = 10,
    apply_relu: bool = True,
    out_dtype=jnp.int32,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused integer linear layer: nitro_relu(⌊(x@w)/(2⁸·M)⌋).

    Accepts any leading batch dims on ``x``; contracts the last one.
    ``use_kernel=None`` auto-selects: Pallas on TPU, oracle on CPU (the
    tests exercise the kernel explicitly with ``interpret=True``).
    """
    m = x.shape[-1]
    lead = x.shape[:-1]
    out = fused_matmul(
        x.reshape(-1, m), w, sf=linear_scale_factor(m), alpha_inv=alpha_inv,
        apply_relu=apply_relu, out_dtype=out_dtype,
        backend=_legacy_backend(use_kernel, interpret),
    )
    return out.reshape(*lead, w.shape[-1])


def nitro_conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    alpha_inv: int = 10,
    apply_relu: bool = True,
    out_dtype=jnp.int32,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused integer conv layer via im2col + the same fused matmul.

    x: (N,H,W,C) int, w: (K,K,C,F) int → (N,H,W,F) activations.
    im2col is pad+static-slices (layout work the TPU does in the XLA
    prologue); all FLOPs go through the fused MXU kernel.
    """
    k = w.shape[0]
    c_in = x.shape[-1]
    n, h, ww, _ = x.shape
    patches, w_flat = conv_im2col_operands(w, x)
    out = fused_matmul(
        patches, w_flat, sf=conv_scale_factor(k, c_in), alpha_inv=alpha_inv,
        apply_relu=apply_relu, out_dtype=out_dtype,
        backend=_legacy_backend(use_kernel, interpret),
    )
    return out.reshape(n, h, ww, w.shape[-1])
