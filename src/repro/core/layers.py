"""Integer layers with hand-derived integer backward passes.

``jax.grad`` cannot differentiate integer computations, and NITRO-D's
learning rule is defined directly on integers — so every layer here exposes
an explicit ``forward`` (returning a cache) and ``backward`` (consuming it),
all closed over ℤ.  Layout is NHWC / (batch, features), weights are
(fan_in, fan_out) for linear and (K, K, C_in, C_out) for conv — the
TPU-native layouts.

``conv_forward`` realises Conv2D as explicit im2col + integer matmul: patch
extraction followed by an int32 ``dot_general``.  It is the unfused
oracle; the fused path streams the same patches through VMEM in the
``nitro_conv`` kernels, and ``conv_backward`` / ``conv_update`` route both
conv gradients there (see kernels/nitro_conv).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import numerics
from repro.core.init import integer_kaiming_uniform
from repro.core.numerics import floor_div, int_matmul, to_int

# ---------------------------------------------------------------------------
# Integer Linear
# ---------------------------------------------------------------------------


def linear_init(key: jax.Array, fan_in: int, fan_out: int) -> dict:
    """IntegerLinear params — no bias (Appendix B.1)."""
    return {"w": integer_kaiming_uniform(key, (fan_in, fan_out), fan_in)}


def linear_forward(params: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """z = x @ W with int32 accumulation. Cache = input activations."""
    numerics.assert_int(x, "linear input")
    return int_matmul(x, params["w"]), x


def linear_backward(
    params: dict,
    cache: jax.Array,
    grad_out: jax.Array,
    *,
    z_star: jax.Array | None = None,
    alpha_inv: int = 10,
    backend: str = "auto",
) -> tuple[jax.Array, dict]:
    """grad_x = g @ Wᵀ, grad_W = xᵀ @ g — both integer matmuls.

    Routed through the shared ``kernels.grad_ops`` dispatcher.  With
    ``z_star`` (the cached pre-ReLU tensor) the NITRO-ReLU-bwd/STE step
    runs as a prologue *inside* the gradient kernels.  Learning/output
    layers pass no ``z_star``: their scaling STE backward is the identity.
    """
    from repro.kernels import grad_ops  # lazy: cycle-free (see blocks.py)

    grad_x, grad_w = grad_ops.linear_grads(
        cache, params["w"], grad_out,
        z_star=z_star, alpha_inv=alpha_inv, backend=backend,
    )
    return grad_x, {"w": grad_w}


def linear_update(
    params: dict,
    cache: jax.Array,
    grad_out: jax.Array,
    opt_state,
    *,
    z_star: jax.Array | None = None,
    alpha_inv: int = 10,
    backend: str = "auto",
) -> tuple[jax.Array, dict]:
    """``linear_backward`` + IntegerSGD in one pass: (grad_x, {'w': W′}).

    The weight update runs as the grad_W kernel's flush epilogue
    (``grad_ops.linear_weight_update``), so grad_W never reaches HBM —
    bitwise identical to ``linear_backward`` → ``optimizer.apply_update``.
    """
    from repro.kernels import grad_ops  # lazy: cycle-free (see blocks.py)

    grad_x, w_new = grad_ops.linear_weight_update(
        cache, params["w"], grad_out, opt_state,
        z_star=z_star, alpha_inv=alpha_inv, backend=backend,
    )
    return grad_x, {"w": w_new}


# ---------------------------------------------------------------------------
# Integer Conv2D (K×K, stride 1, 'same' padding) via im2col + matmul
# ---------------------------------------------------------------------------


def conv_init(key: jax.Array, in_channels: int, out_channels: int, kernel_size: int = 3) -> dict:
    fan_in = kernel_size * kernel_size * in_channels
    shape = (kernel_size, kernel_size, in_channels, out_channels)
    return {"w": integer_kaiming_uniform(key, shape, fan_in)}


def im2col(x: jax.Array, kernel_size: int, padding: int) -> jax.Array:
    """Extract K×K patches: (N,H,W,C) → (N,H,W,K·K·C), integer-safe.

    Built from pad + static slices (no gather, no float conv), so it lowers
    to cheap reshapes on any backend.
    """
    n, h, w, c = x.shape
    k = kernel_size
    xp = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    rows = []
    for i in range(k):
        for j in range(k):
            rows.append(jax.lax.dynamic_slice(xp, (0, i, j, 0), (n, h, w, c)))
    # (N,H,W,K*K,C) → (N,H,W,K*K*C); K*K ordering matches weight reshape.
    patches = jnp.stack(rows, axis=3)
    return patches.reshape(n, h, w, k * k * c)


def conv_im2col_operands(
    w: jax.Array, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Lower a 'same' conv to 2-D matmul operands.

    (N,H,W,C) input + (K,K,C,F) weight → (N·H·W, K²C) patches and (K²C, F)
    flattened weight.  Shared by the reference ``conv_forward``, the fused
    training forward in ``core.blocks``, and the inference plan — one
    definition of the patch/weight layout keeps all three bit-identical.
    """
    k = w.shape[0]
    n, h, ww, c = x.shape
    patches = im2col(x, k, k // 2).reshape(n * h * ww, k * k * c)
    return patches, w.reshape(-1, w.shape[-1])


class ConvCache(NamedTuple):
    x: jax.Array  # input activations (N,H,W,C)


def conv_forward(params: dict, x: jax.Array) -> tuple[jax.Array, ConvCache]:
    """z[n,h,w,f] = Σ_{i,j,c} x[n,h+i-p,w+j-p,c] · W[i,j,c,f] (int32)."""
    numerics.assert_int(x, "conv input")
    n, h, ww, _ = x.shape
    patches, w_flat = conv_im2col_operands(params["w"], x)
    z = int_matmul(patches, w_flat).reshape(n, h, ww, w_flat.shape[-1])
    return z, ConvCache(x=x)


def conv_backward(
    params: dict,
    cache: ConvCache,
    grad_out: jax.Array,
    *,
    z_star: jax.Array | None = None,
    alpha_inv: int = 10,
    backend: str = "auto",
) -> tuple[jax.Array, dict]:
    """Integer conv backward, routed through ``kernels.grad_ops``.

    grad_W : correlation of input patches with grad_out (im2colᵀ · g).
    grad_x : 'full' correlation of grad_out with the spatially-flipped,
             channel-transposed kernel — one more conv on the MXU path.

    Both gradients stream their patches from row bands — the
    ``(N·H·W, K²·C)`` patch matrix is never materialised.  With ``z_star``
    the NITRO-ReLU-bwd/STE step is fused into the kernels' δ prologue.
    Integer accumulation is order-exact, so every backend agrees
    bit-for-bit.
    """
    from repro.kernels import grad_ops  # lazy: cycle-free

    grad_x, grad_w = grad_ops.conv_grads(
        cache.x, params["w"], grad_out,
        z_star=z_star, alpha_inv=alpha_inv, backend=backend,
    )
    return grad_x, {"w": grad_w}


def conv_update(
    params: dict,
    cache: ConvCache,
    grad_out: jax.Array,
    opt_state,
    *,
    z_star: jax.Array | None = None,
    alpha_inv: int = 10,
    backend: str = "auto",
) -> tuple[jax.Array, dict]:
    """``conv_backward`` + IntegerSGD in one pass: (grad_x, {'w': W′}).

    The update runs in the streaming grad_W kernel's flush
    (``grad_ops.conv_weight_update``).  Bitwise identical to
    ``conv_backward`` → ``optimizer.apply_update`` on every backend.
    """
    from repro.kernels import grad_ops  # lazy: cycle-free

    grad_x, w_new = grad_ops.conv_weight_update(
        cache.x, params["w"], grad_out, opt_state,
        z_star=z_star, alpha_inv=alpha_inv, backend=backend,
    )
    return grad_x, {"w": w_new}


# ---------------------------------------------------------------------------
# MaxPool2D (2×2, stride 2) — integer max with argmax routing on backward
# ---------------------------------------------------------------------------


class PoolCache(NamedTuple):
    onehot: jax.Array  # (N,h,w,4,C) one-hot of the argmax inside each window
    in_shape: tuple[int, int, int, int]


def window_view_2x2(x: jax.Array) -> jax.Array:
    """(N,H,W,C) → (N,H//2,W//2,4,C), cropping odd trailing rows/cols
    (floor pooling, matching framework semantics for odd sizes).

    The shared definition of 2×2/stride-2 window extraction: ``maxpool``
    here, the inference plan's cacheless pool, and the streaming-conv
    oracle's pool epilogue all reduce over axis 3 of this view, so pooling
    semantics (including odd-edge cropping) are defined exactly once.
    """
    n, h, w, c = x.shape
    h2, w2 = h // 2, w // 2
    x = x[:, : h2 * 2, : w2 * 2, :]
    x = x.reshape(n, h2, 2, w2, 2, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h2, w2, 4, c)


def maxpool_forward(x: jax.Array) -> tuple[jax.Array, PoolCache]:
    numerics.assert_int(x, "maxpool input")
    win = window_view_2x2(x)
    idx = jnp.argmax(win, axis=3)
    onehot = (idx[:, :, :, None, :] == jnp.arange(4)[None, None, None, :, None])
    out = jnp.max(win, axis=3)
    return out, PoolCache(onehot=onehot.astype(numerics.INT_DTYPE), in_shape=x.shape)


def maxpool_backward(cache: PoolCache, grad_out: jax.Array) -> jax.Array:
    """Route gradient to the (first) max position of each 2×2 window."""
    n, h, w, c = cache.in_shape
    h2, w2 = h // 2, w // 2
    g = grad_out[:, :, :, None, :] * cache.onehot  # (N,h2,w2,4,C)
    g = g.reshape(n, h2, w2, 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
    g = g.reshape(n, h2 * 2, w2 * 2, c)
    if (h2 * 2, w2 * 2) != (h, w):  # repad cropped odd edges with zeros
        g = jnp.pad(g, ((0, 0), (0, h - h2 * 2), (0, w - w2 * 2), (0, 0)))
    return g


# ---------------------------------------------------------------------------
# Adaptive integer average pooling (learning-layer dimensionality reduction)
# ---------------------------------------------------------------------------


class AvgPoolCache(NamedTuple):
    in_shape: tuple[int, int, int, int]
    window: int
    target: int


def avgpool_to(x: jax.Array, target: int) -> tuple[jax.Array, AvgPoolCache]:
    """Integer adaptive average pool (N,H,W,C) → (N,s,s,C).

    ``s`` is the largest grid with s²·C ≤ d_lr (the learning layers' input
    budget).  Mean is Σ // count; backward is STE replication (no division) —
    the NITRO Amplification Factor analysis accounts only for the learning
    layers' matmul, so pooling must not re-scale the backward signal.
    """
    n, h, w, c = x.shape
    s = max(math.isqrt(max(target // c, 1)), 1)
    s = min(s, h, w)
    window = h // s
    xs = x[:, : s * window, : s * window, :]
    xs = xs.reshape(n, s, window, s, window, c)
    # int32 is safe: window sums are ≤ 127·window² « 2³¹ for any real config.
    total = jnp.sum(xs, axis=(2, 4), dtype=numerics.INT_DTYPE)
    out = floor_div(total, window * window)
    return out, AvgPoolCache(in_shape=x.shape, window=window, target=s)


def avgpool_to_backward(cache: AvgPoolCache, grad_out: jax.Array) -> jax.Array:
    """STE unpool: replicate each pooled grad across its window, zero-pad."""
    n, h, w, c = cache.in_shape
    s, window = cache.target, cache.window
    g = jnp.broadcast_to(
        grad_out[:, :, None, :, None, :], (n, s, window, s, window, c)
    ).reshape(n, s * window, s * window, c)
    pad_h, pad_w = h - s * window, w - s * window
    if pad_h or pad_w:
        g = jnp.pad(g, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
    return g


# ---------------------------------------------------------------------------
# Integer inverted dropout
# ---------------------------------------------------------------------------

_DROPOUT_FP_BITS = 8  # fixed-point denominator 2^8 for the 1/(1-p) rescale


class DropoutCache(NamedTuple):
    mask: jax.Array
    q: int


def dropout_forward(
    key: jax.Array, x: jax.Array, rate: float,
    *, dp_axis: str | None = None, dp_shards: int = 1,
) -> tuple[jax.Array, DropoutCache]:
    """Integer inverted dropout.

    The float 1/(1−p) rescale becomes a fixed-point multiply-then-shift:
    q = round(256/(1−p)); out = (x·mask·q) >> 8.  Expectation is preserved to
    <0.4 % while staying in ℤ.  rate == 0 is the identity.

    Dropout is the one sampled operation in the training step, so it is
    also the one place batch sharding could break bitwise determinism:
    ``jax.random.bits(key, (B/n, ...))`` on a shard is *not* a row-slice
    of ``bits(key, (B, ...))`` on the full batch.  Under data parallelism
    (``dp_axis`` names the active shard_map axis, ``dp_shards`` its
    static size) every shard therefore draws the **global-batch** mask
    from the shared key and slices its own row block by
    ``lax.axis_index`` — identical masks to the single-device run at any
    device count, test-enforced.  The redundant per-shard mask generation
    is threefry on uint32, a negligible slice of step cost.
    """
    if rate <= 0.0:
        return x, DropoutCache(mask=jnp.ones((), numerics.INT_DTYPE), q=1 << _DROPOUT_FP_BITS)
    keep = 1.0 - rate
    q = int(round((1 << _DROPOUT_FP_BITS) / keep))
    # Integer Bernoulli: uniform uint32 bits < ⌊keep·2³²⌋ — keeps the whole
    # training step free of float ops (the jaxpr is asserted float-free).
    threshold = jnp.uint32(min(int(keep * (1 << 32)), (1 << 32) - 1))
    if dp_axis is not None and dp_shards > 1:
        local_b = x.shape[0]
        bits = jax.random.bits(
            key, (local_b * dp_shards, *x.shape[1:]), jnp.uint32
        )
        start = jax.lax.axis_index(dp_axis) * local_b
        bits = jax.lax.dynamic_slice_in_dim(bits, start, local_b, axis=0)
    else:
        bits = jax.random.bits(key, x.shape, jnp.uint32)
    mask = (bits < threshold).astype(numerics.INT_DTYPE)
    out = floor_div(x * mask * q, 1 << _DROPOUT_FP_BITS)
    return out, DropoutCache(mask=mask, q=q)


def dropout_backward(cache: DropoutCache, grad_out: jax.Array) -> jax.Array:
    return floor_div(grad_out * cache.mask * cache.q, 1 << _DROPOUT_FP_BITS)


# ---------------------------------------------------------------------------
# Flatten
# ---------------------------------------------------------------------------


def flatten_forward(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    return x.reshape(x.shape[0], -1), x.shape


def flatten_backward(in_shape: tuple[int, ...], grad_out: jax.Array) -> jax.Array:
    return grad_out.reshape(in_shape)
