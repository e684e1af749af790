"""Plain NITRO-D, written from the paper and independent of the program.

The integer local-loss training step and the served forward pass of
NITRO-D (arXiv 2407.11698, §3 and Appendices B–D) in ``jax.numpy``, with
no kernels, no fusion and no batching tricks.  It imports nothing of the
program under test: the benchmark compares what the program's timed path
produced against this, bit for bit, since integer arithmetic leaves no
rounding to excuse a difference.

Conventions (those of the paper, and so of any faithful implementation):

* ``⌊·⌋`` is floor division; every product and sum is int32 and wraps
  modulo 2³², so the order of accumulation cannot change a result;
* conv layers are K×K, stride 1, zero 'same' padding, NHWC, weights
  (K, K, C, F); linear weights are (fan_in, fan_out); no biases;
* NITRO Scaling divides by SF = 2⁸·fan_in, NITRO-ReLU is the four-segment
  integer leaky ReLU shifted by μ_int8, the one-hot target is 32;
* the block's learning layers see the block's output after pool and
  dropout, through an adaptive average pool to at most ``d_lr`` features
  (conv blocks only); their backward replicates (no division);
* IntegerSGD: W ← W − (⌊g/γ_inv⌋ + ⌊W/η_inv⌋), with η_inv = 0 meaning no
  decay, and γ_inv of forward layers multiplied by AF = 2⁶·G;
* IntegerDropout keeps a unit where 32 random bits fall under
  ⌊(1−p)·2³²⌋ and rescales by q = round(2⁸/(1−p)) then ⌊·/2⁸⌋; the bits
  of block l come from ``jax.random.split(key, L)[l]`` over the whole
  batch.

``acc_dtype`` narrows every dot product's result to a smaller integer
type (wrapping), the lower-precision control that the comparison must
catch; the benchmark's own runs never set it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ONE_HOT = 32
ACT_LO, ACT_HI = -127, 127
DROPOUT_BITS = 8


# ---------------------------------------------------------------------------
# Architecture from a configuration file
# ---------------------------------------------------------------------------


def layer_shapes(cfg: dict) -> list[dict]:
    """Per block: input shape, forward weight shape, pooled learning-layer
    grid and learning weight shape; the output layer's shape last."""
    shape = tuple(cfg["input_shape"])
    g = cfg["num_classes"]
    k = cfg["kernel_size"]
    out = []
    for blk in cfg["blocks"]:
        width = blk["width"]
        if blk["kind"] == "conv":
            h, w, c = shape
            fw = (k, k, c, width)
            oh, ow = (h // 2, w // 2) if blk["pool"] else (h, w)
            s = min(max(math.isqrt(max(blk["d_lr"] // width, 1)), 1), oh, ow)
            lr = (s * s * width, g)
            nxt = (oh, ow, width)
        else:
            fan_in = math.prod(shape)
            fw = (fan_in, width)
            s = 0
            lr = (width, g)
            nxt = (width,)
        out.append({"in": shape, "fw": fw, "lr": lr, "grid": s, "out": nxt})
        shape = nxt
    out.append({"in": shape, "fw": (math.prod(shape), g)})
    return out


def mu_int8(alpha_inv: int) -> int:
    means = (-127 // alpha_inv, -127 // (2 * alpha_inv), 63, 127)
    return sum(means) // 4


# ---------------------------------------------------------------------------
# Integer pieces
# ---------------------------------------------------------------------------


def _dot(a, b, contract, acc_dtype):
    z = jax.lax.dot_general(a, b, (contract, ((), ())),
                            preferred_element_type=jnp.int32)
    if acc_dtype is not None:
        z = z.astype(acc_dtype).astype(jnp.int32)
    return z


def matmul(a, w, acc_dtype=None):
    return _dot(a, w, ((a.ndim - 1,), (0,)), acc_dtype)


def _shifted(x, k):
    """The K² zero-padded shifts of an NHWC tensor, in (i, j) order."""
    n, h, w, _ = x.shape
    p = k // 2
    xp = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
    return [xp[:, i:i + h, j:j + w, :] for i in range(k) for j in range(k)]


def conv(x, w, acc_dtype=None):
    """'same' K×K convolution as a sum of K² shifted channel matmuls."""
    k = w.shape[0]
    shifts = _shifted(x, k)
    z = 0
    for t, xs in enumerate(shifts):
        z = z + _dot(xs, w[t // k, t % k], ((3,), (0,)), None)
    if acc_dtype is not None:
        z = z.astype(acc_dtype).astype(jnp.int32)
    return z


def conv_grad_w(x, g, k, acc_dtype=None):
    """∂/∂W of 'same' conv: for each tap, Σ over N,H,W of x-shift ⊗ g."""
    taps = [_dot(xs, g, ((0, 1, 2), (0, 1, 2)), acc_dtype)
            for xs in _shifted(x, k)]
    c, f = taps[0].shape
    return jnp.stack(taps).reshape(k, k, c, f)


def relu(z, alpha_inv):
    neg = jnp.maximum(z, ACT_LO) // alpha_inv
    return jnp.where(z < 0, neg, jnp.minimum(z, ACT_HI)) - mu_int8(alpha_inv)


def relu_backward(z, g, alpha_inv):
    out = jnp.where(z < 0, g // alpha_inv, g)
    return jnp.where((z < ACT_LO) | (z > ACT_HI), 0, out)


def _pool_windows(x):
    """2×2 stride-2 windows in (row, col) order: (0,0) (0,1) (1,0) (1,1)."""
    h2, w2 = x.shape[1] // 2, x.shape[2] // 2
    x = x[:, :2 * h2, :2 * w2]
    return jnp.stack([x[:, 0::2, 0::2], x[:, 0::2, 1::2],
                      x[:, 1::2, 0::2], x[:, 1::2, 1::2]])


def maxpool(x):
    win = _pool_windows(x)
    return win.max(axis=0), jnp.argmax(win, axis=0)


def maxpool_backward(first, g, in_shape):
    n, h, w, c = in_shape
    h2, w2 = h // 2, w // 2
    out = jnp.zeros((n, 2 * h2, 2 * w2, c), g.dtype)
    for t in range(4):
        out = out.at[:, t // 2::2, t % 2::2].set(jnp.where(first == t, g, 0))
    return jnp.pad(out, ((0, 0), (0, h - 2 * h2), (0, w - 2 * w2), (0, 0)))


def avgpool(x, s):
    n, h, _, c = x.shape
    win = h // s
    xs = x[:, :s * win, :s * win].reshape(n, s, win, s, win, c)
    return xs.sum(axis=(2, 4), dtype=jnp.int32) // (win * win)


def avgpool_backward(g, s, in_shape):
    n, h, w, c = in_shape
    win = h // s
    g = jnp.repeat(jnp.repeat(g, win, axis=1), win, axis=2)
    return jnp.pad(g, ((0, 0), (0, h - s * win), (0, w - s * win), (0, 0)))


def dropout_mask(key, shape, rate):
    keep = 1.0 - rate
    threshold = jnp.uint32(min(int(keep * 2**32), 2**32 - 1))
    q = int(round(2**DROPOUT_BITS / keep))
    bits = jax.random.bits(key, shape, jnp.uint32)
    return (bits < threshold).astype(jnp.int32), q


def rescale(x, mask, q):
    return (x * mask * q) // 2**DROPOUT_BITS


# ---------------------------------------------------------------------------
# Forward, gradients, update
# ---------------------------------------------------------------------------


def _block_forward(cfg, blk, p, a, key, train, acc_dtype):
    alpha = cfg["alpha_inv"]
    cache = {"x": a}
    if blk["kind"] == "conv":
        fan = cfg["kernel_size"] ** 2 * a.shape[-1]
        z = conv(a, p["fw"]["w"], acc_dtype)
    else:
        a = a.reshape(a.shape[0], -1)
        cache["x"] = a
        fan = a.shape[-1]
        z = matmul(a, p["fw"]["w"], acc_dtype)
    zs = z // (256 * fan)
    cache["z"] = zs
    a = relu(zs, alpha)
    if blk["pool"] and blk["kind"] == "conv":
        cache["pool_in"] = a.shape
        a, cache["first"] = maxpool(a)
    if train and blk["dropout"] > 0.0:
        cache["mask"], cache["q"] = dropout_mask(key, a.shape, blk["dropout"])
        a = rescale(a, cache["mask"], cache["q"])
    return a, cache


def forward(cfg, params, x, *, train=False, key=None, acc_dtype=None):
    """Logits, plus each block's output and cache when training."""
    a = x.astype(jnp.int32)
    nblk = len(cfg["blocks"])
    keys = jax.random.split(key, nblk) if train else [None] * nblk
    acts, caches = [], []
    for blk, p, k in zip(cfg["blocks"], params["blocks"], keys):
        a, cache = _block_forward(cfg, blk, p, a, k, train, acc_dtype)
        acts.append(a)
        caches.append(cache)
    flat = a.reshape(a.shape[0], -1)
    logits = matmul(flat, params["output"]["w"], acc_dtype) // (
        256 * flat.shape[-1])
    return logits, flat, acts, caches


def _rss(y_hat, y):
    d = y_hat - y
    return jnp.sum((d * d) // 2)


def gradients(cfg, params, x, labels, key, *, acc_dtype=None, rows=None):
    """Integer batch-sum gradients and the step's metrics.

    ``rows`` (a slice) keeps only those rows of the batch in the gradient
    sums while the dropout bits are still drawn for the whole batch: the
    shard one device of a data-parallel step would see on its own.
    """
    shapes = layer_shapes(cfg)
    g_cls = cfg["num_classes"]
    y = (labels[:, None] == jnp.arange(g_cls)).astype(jnp.int32) * ONE_HOT
    logits, flat, acts, caches = forward(cfg, params, x, train=True,
                                         key=key, acc_dtype=acc_dtype)
    keep = slice(None) if rows is None else rows

    def mm_t(a, g):  # aᵀ @ g over the kept rows
        return _dot(a[keep], g[keep], ((0,), (0,)), acc_dtype)

    g_o = logits - y
    grads = {"blocks": [], "output": {"w": mm_t(flat, g_o)}}
    local = []
    for blk, p, a, cache, shp in zip(cfg["blocks"], params["blocks"], acts,
                                     caches, shapes):
        if blk["kind"] == "conv":
            s = shp["grid"]
            pooled = avgpool(a, s)
            lr_in = pooled.reshape(a.shape[0], -1)
        else:
            lr_in = a
        y_l = matmul(lr_in, p["lr"]["w"], acc_dtype) // (256 * lr_in.shape[-1])
        g_l = y_l - y
        local.append(_rss(y_l, y))
        g_lr = mm_t(lr_in, g_l)
        d = _dot(g_l, p["lr"]["w"], ((1,), (1,)), acc_dtype)
        if blk["kind"] == "conv":
            d = avgpool_backward(d.reshape(pooled.shape), s, a.shape)
        if "mask" in cache:
            d = rescale(d, cache["mask"], cache["q"])
        if "first" in cache:
            d = maxpool_backward(cache["first"], d, cache["pool_in"])
        d = relu_backward(cache["z"], d, cfg["alpha_inv"])
        if blk["kind"] == "conv":
            xk, dk = cache["x"][keep], d[keep]
            g_fw = conv_grad_w(xk, dk, cfg["kernel_size"], acc_dtype)
        else:
            g_fw = mm_t(cache["x"], d)
        grads["blocks"].append({"fw": {"w": g_fw}, "lr": {"w": g_lr}})
    metrics = {
        "loss": _rss(logits, y),
        "correct": jnp.sum(jnp.argmax(logits, axis=-1) == labels),
        "local_losses": jnp.stack(local),
    }
    return grads, metrics


def _sgd(w, g, gamma_inv, eta_inv):
    decay = w // eta_inv if eta_inv else 0
    return w - (g // gamma_inv + decay)


def apply(cfg, params, grads):
    gamma = cfg["gamma_inv"]
    gamma_fw = gamma * 64 * cfg["num_classes"]
    blocks = [
        {"fw": {"w": _sgd(p["fw"]["w"], g["fw"]["w"], gamma_fw, cfg["eta_fw"])},
         "lr": {"w": _sgd(p["lr"]["w"], g["lr"]["w"], gamma, cfg["eta_lr"])}}
        for p, g in zip(params["blocks"], grads["blocks"])
    ]
    out = {"w": _sgd(params["output"]["w"], grads["output"]["w"], gamma,
                     cfg["eta_lr"])}
    return {"blocks": blocks, "output": out}


def train_step(cfg, params, x, labels, key, *, acc_dtype=None, rows=None):
    """One NITRO-D step: (new params, metrics)."""
    grads, metrics = gradients(cfg, params, x, labels, key,
                               acc_dtype=acc_dtype, rows=rows)
    return apply(cfg, params, grads), metrics
