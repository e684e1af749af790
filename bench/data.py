"""Synthetic labelled images, made on the device from a key.

A stand-in for CIFAR-10 after the paper's integer pre-processing
(Appendix B.2: mean 0, standard deviation about 64, clipped to the int8
range): each class is a smooth sinusoid pattern per channel, each image
its class pattern shifted by up to 3 pixels plus uniform noise.  Every
image differs from every other (the noise alone sees to that), and the
same key gives the same images.  All of it is one jitted call, so no
image crosses the host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NOISE = 45
MAX_SHIFT = 3


@functools.partial(jax.jit, static_argnames=("n", "shape", "num_classes"))
def images(key, *, n: int, shape: tuple, num_classes: int):
    """``n`` images of ``shape`` (H, W, C) as int32 in [−127, 127], with
    int32 labels in [0, num_classes)."""
    h, w, c = shape
    k_t, k_y, k_s, k_n = jax.random.split(key, 4)
    freq = jax.random.uniform(k_t, (num_classes, c, 4), minval=0.5,
                              maxval=3.0)
    yy = jnp.arange(h, dtype=jnp.float32)[:, None, None]
    xx = jnp.arange(w, dtype=jnp.float32)[None, :, None]
    amp = 50.0 + 7.0 * (jnp.arange(num_classes) % 5)

    def template(f, a):
        fx, fy = f[:, 0], f[:, 1]
        px, py = f[:, 2] * 2.1, f[:, 3] * 2.1
        t = (jnp.sin(2 * jnp.pi * fx * xx / w + px)
             * jnp.cos(2 * jnp.pi * fy * yy / h + py))
        return jnp.round(a * t)

    templates = jax.vmap(template)(freq, amp)            # (G, H, W, C)
    labels = jax.random.randint(k_y, (n,), 0, num_classes, jnp.int32)
    shifts = jax.random.randint(k_s, (n, 2), -MAX_SHIFT, MAX_SHIFT + 1)

    def place(y, s):
        return jnp.roll(templates[y], (s[0], s[1]), axis=(0, 1))

    x = jax.vmap(place)(labels, shifts)
    x = x + jax.random.randint(k_n, x.shape, -NOISE, NOISE + 1)
    # the paper's pre-processing lands σ at about 64: the templates' σ is
    # about 30 and the noise's 26, so scale by 1.6 before clipping
    x = jnp.clip(jnp.round(x * 1.6), -127, 127).astype(jnp.int32)
    return x, labels
