"""Hand-scheduled collectives for compute/communication overlap.

XLA schedules its own all-reduces, but a *chunked ring* built from
``ppermute`` exposes the schedule to the compiler as N independent steps,
letting gradient synchronisation of layer *l* overlap the backward compute
of layer *l−1* (the classic Horovod-style overlap, expressed in
shard_map).  For NITRO-D the payloads are **int32 gradients**: integer
addition is associative, so the ring produces the *bitwise-identical*
result to ``psum`` at any device count — the data-parallel suite
(``tests/test_data_parallel.py``) enforces ring ≡ psum ≡ single-device as
an equality, not a tolerance.  Algorithms:

  * ``ring_all_reduce``      — reduce-scatter ring + all-gather ring,
    2·(N−1)/N · bytes on the wire per chip (bandwidth-optimal).
  * ``ring_reduce_scatter``  — first half only; rank *r* ends holding
    reduced chunk *r*, which composes with FSDP-style sharded optimisers
    (each chip updates its own shard) and with the by-rank
    ``ring_all_gather``.

Both operate on one tensor *inside* an active shard_map over ``axis_name``
(``jax.vmap(..., axis_name=...)`` also works and is how the unit tests
exercise N > 1 semantics without devices).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def axis_size(axis_name: str) -> int:
    """The named-axis extent as a *static* int — the ring schedules below
    and ``pipeline.py`` need it for trip counts and permutations."""
    return jax.lax.axis_size(axis_name)


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Reduce-scatter via an (N−1)-step ppermute ring.

    x: identical-shape local tensor on every rank, first dim divisible by N.
    Returns this rank's reduced chunk (shape x.shape with dim0 / N): rank
    *r* holds chunk *r* — Σ over ranks of everyone's r-th chunk.

    Schedule: at step *i* rank *r* forwards slot ``r−1−i`` (which has
    accumulated ``i+1`` contributions) one hop down the ring and adds the
    incoming piece into slot ``r−2−i``; after N−1 steps slot *r* is the
    last one written and carries all N contributions.  (A schedule that
    ends with slot *r+1* complete — the other textbook variant — would
    break the by-rank reassembly in ``ring_all_reduce``.)
    """
    n = axis_size(axis_name)
    if n == 1:
        return x
    if x.shape[0] % n:
        raise ValueError(
            f"ring_reduce_scatter: leading dim {x.shape[0]} not divisible "
            f"by ring size {n}; pad first (ring_all_reduce does)"
        )
    idx = jax.lax.axis_index(axis_name)
    chunks = jnp.stack(jnp.split(x, n, axis=0))      # (N, chunk, ...)

    # unrolled loop: each step is an independent HLO op → overlappable
    acc = chunks
    for i in range(n - 1):
        send_slot = (idx - 1 - i) % n
        piece = jnp.take(acc, send_slot, axis=0, mode="wrap")
        piece = jax.lax.ppermute(piece, axis_name, _ring_perm(n))
        recv_slot = (idx - 2 - i) % n
        acc = acc.at[recv_slot].add(piece)
    return jnp.take(acc, idx, axis=0, mode="wrap")


def ring_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    """All-gather via an (N−1)-step ppermute ring; concatenates on dim0
    in rank order (rank r's tensor occupies rows [r·len, (r+1)·len))."""
    n = axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    out = jnp.zeros((n, *x.shape), x.dtype)
    out = out.at[idx].set(x)
    piece = x
    for i in range(n - 1):
        piece = jax.lax.ppermute(piece, axis_name, _ring_perm(n))
        src = (idx - i - 1) % n
        out = out.at[src].set(piece)
    return out.reshape(n * x.shape[0], *x.shape[1:])


def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Bandwidth-optimal ring all-reduce (reduce-scatter + all-gather).

    Pads dim0 up to a multiple of N (zero rows — additively inert), so any
    tensor shape reduces; bitwise ≡ ``psum`` for integer dtypes.
    """
    n = axis_size(axis_name)
    if n == 1:
        return x
    pad = (-x.shape[0]) % n
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x
    reduced = ring_reduce_scatter(xp, axis_name)
    full = ring_all_gather(reduced, axis_name)
    return full[: x.shape[0]] if pad else full
