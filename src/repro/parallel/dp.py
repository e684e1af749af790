"""Data-parallel NITRO-D training, bitwise-identical at any device count.

NITRO-D's integer arithmetic buys a property float data parallelism can
never have: **the sharded step is an equality, not an approximation**.
Every gradient ``les.compute_gradients`` produces is a *batch sum* of
per-sample int32 contributions (RSS loss and both backward paths are
linear in the batch dimension), and int32 addition is associative and
commutative — so splitting the batch over a ``data`` mesh axis, reducing
per-shard gradients with *any* exact integer all-reduce, and applying
IntegerSGD once reproduces the single-device ``les.train_step`` bit for
bit, at any device count and any reduction order.
``tests/test_data_parallel.py`` enforces this as ``assert_bitwise_equal``
over multi-step ``TrainState`` trajectories across real host-device
counts {1, 2, 4} × every reducer below.

Three interchangeable reducers (``dp_reduce=``):

  * ``"psum"``     — XLA's all-reduce (default; ``compress.exact_integer_psum``)
  * ``"ring"``     — the hand-scheduled chunked ``collectives.ring_all_reduce``
                     (exposes per-chunk steps for comms/compute overlap)
  * ``"compress"`` — ``compress.nitro_compressed_psum``: the same exact sum
                     carried as int8 limb planes on the wire

All three are bitwise-equivalent — that is the point.  The only sampled
operation in the step, IntegerDropout, draws the *global-batch* mask from
the replicated key and slices this shard's rows
(``dp_axis``/``dp_shards`` threading in ``core.layers.dropout_forward``),
so masks match the single-device run exactly.

The batch specs come from ``sharding.train_rules()`` (logical ``"batch"``
axis → ``data`` mesh axis); the step itself is a ``shard_map`` whose
interior stays integer-only — ``assert_jaxpr_integer_only`` descends into
the shard_map sub-jaxpr.

CPU-only sessions simulate devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set **before the
first JAX import** (``launch/train.py --num-devices`` re-execs itself to
guarantee this; the tests use subprocess workers).  See
``docs/PARALLEL.md``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import les
from repro.core import model as M
from repro.obs import layers as scopes
from repro.parallel import collectives, compress, sharding

DP_AXIS = "data"

#: Valid ``dp_reduce=`` values, in (default-first) order.
REDUCERS = ("psum", "ring", "compress")


def data_mesh(num_devices: int | None = None) -> Mesh:
    """A 1-D ``("data",)`` mesh over ``num_devices`` (default: all).

    Raises with the ``XLA_FLAGS`` recipe when the session has fewer
    devices than asked — the flag only works before JAX initialises, so
    this cannot be fixed from here.
    """
    avail = jax.device_count()
    n = avail if num_devices is None else num_devices
    if n > avail:
        raise ValueError(
            f"data_mesh: asked for {n} devices but this process has {avail}. "
            f"Set XLA_FLAGS=--xla_force_host_platform_device_count={n} in the "
            f"environment *before the first jax import* (launch/train.py "
            f"--num-devices does this via re-exec)."
        )
    from repro.launch.mesh import make_mesh

    return make_mesh((n,), (DP_AXIS,))


def reduce_gradients(grads, axis_name: str, method: str = "psum"):
    """All-reduce an integer gradient pytree over ``axis_name``.

    Every method computes the exact int32 sum over shards — they differ
    only in schedule/wire format, never in the result (test-enforced
    bitwise).  Must be called inside a shard_map (or vmap) binding
    ``axis_name``.
    """
    if method == "psum":
        return compress.exact_integer_psum(grads, axis_name)
    if method == "ring":
        return jax.tree_util.tree_map(
            lambda g: collectives.ring_all_reduce(g, axis_name), grads
        )
    if method == "compress":
        return compress.nitro_compressed_psum(grads, axis_name)
    raise ValueError(
        f"unknown dp_reduce method {method!r}; expected one of {REDUCERS}"
    )


def _reduce_tensor_telemetry(tt, axis_name: str):
    """Shard-local TensorTelemetry → global: counts sum, envelope maxes."""
    return type(tt)(
        bit_hist=jax.lax.psum(tt.bit_hist, axis_name),
        sat_int8=jax.lax.psum(tt.sat_int8, axis_name),
        sat_int32=jax.lax.psum(tt.sat_int32, axis_name),
        max_abs=jax.lax.pmax(tt.max_abs, axis_name),
    )


def _grads_fit_int16(grads, axis_name: str) -> jax.Array:
    """1 iff every shard-local gradient element fits 2 int8 limbs (int16).

    The exactness precondition of running ``dp_reduce="compress"`` at
    ``num_limbs=2`` — evaluated on the *pre-reduce* shard-local
    gradients (the values that would go on the wire) and pmin-ed so
    every shard reports the global verdict.  Integer-only throughout
    (comparisons → int32), so the float-free jaxpr guarantee holds.
    """
    local = jnp.min(jnp.stack([
        compress.fits_limbs(g, 2).astype(jnp.int32)
        for g in jax.tree_util.tree_leaves(grads)
    ]))
    return jax.lax.pmin(local, axis_name)


def _dp_telemetry(cfg, new_state, aux, grads, state, axis_name: str):
    """Telemetry under sharding, bitwise ≡ the single-device readout.

    Weights, reduced gradients and optimiser scalars are replicated —
    their summaries are already global.  ``z_star``/``act`` live in the
    shard-local caches (local batch rows only), so their histograms,
    saturation and dead-unit *counts* psum across shards and ``max_abs``
    pmaxes — exactly the reductions the single-device pass performs over
    the whole batch, reassociated (integer ops: associativity is exact).
    """
    from repro.obs import telemetry as T

    telem = T.collect_train_telemetry(
        cfg, new_state.params, aux.fw_caches,
        [g["fw"] for g in grads.blocks], grads.output,
        state.opt_lr, state.opt_fw,
    )
    for bt in telem["blocks"]:
        bt["z_star"] = _reduce_tensor_telemetry(bt["z_star"], axis_name)
        bt["act"] = _reduce_tensor_telemetry(bt["act"], axis_name)
        bt["dead"] = jax.lax.psum(bt["dead"], axis_name)
    return telem


def dp_train_step(
    state: les.TrainState,
    cfg: M.NitroConfig,
    x: jax.Array,
    labels: jax.Array,
    key: jax.Array,
    *,
    mesh: Mesh,
    dp_reduce: str = "psum",
    fused: bool = True,
    fuse_opt: bool = False,
    backend: str = "auto",
    telemetry: bool = False,
):
    """One data-parallel NITRO-D step — ``les.train_step`` over a mesh.

    Same signature/returns as ``les.train_step`` plus ``mesh`` (a 1-D
    ``data`` mesh from ``data_mesh``) and ``dp_reduce`` (see ``REDUCERS``).
    State and key are replicated; ``x``/``labels`` shard on the batch dim
    per ``sharding.train_rules()``.  Inside the shard_map each shard runs
    ``compute_gradients`` on its batch slice, the integer gradients and
    metrics all-reduce exactly, and every shard applies the identical
    IntegerSGD update — so all outputs are replicated and bitwise equal
    to the single-device step on the full batch.

    ``fuse_opt=True`` applies the post-reduce update with the standalone
    fused IntegerSGD kernel (``les.apply_gradients(fuse_opt=True)``) —
    DP cannot use the grad-kernel flush epilogue because the all-reduce
    *needs* the materialised gradient, but the update itself still fuses.
    Bitwise identical, so cross-device-count trajectory identity holds
    with it on or off (test-enforced).

    ``check_vma=False``: the ring reducer is built from ``ppermute``,
    whose per-device results shard_map cannot prove replicated (they are
    — by the all-gather's construction; the tests prove it bitwise).
    """
    if dp_reduce not in REDUCERS:
        raise ValueError(
            f"unknown dp_reduce method {dp_reduce!r}; expected one of {REDUCERS}"
        )
    n = mesh.shape[DP_AXIS]
    if x.shape[0] % n:
        raise ValueError(
            f"dp_train_step: batch {x.shape[0]} not divisible by the "
            f"{DP_AXIS} mesh axis ({n} shards)"
        )
    with sharding.use_rules(mesh, sharding.train_rules()):
        batch_spec = sharding.resolve(("batch",))

    def _body(state, x, labels, key):
        grads, metrics, aux = les.compute_gradients(
            state, cfg, x, labels, key,
            fused=fused, backend=backend, dp_axis=DP_AXIS, dp_shards=n,
        )
        if telemetry:
            # pre-reduce: the shard-local widths are what hit the wire
            fits16 = _grads_fit_int16(grads, DP_AXIS)
        with scopes.reduce_gradients():
            grads = reduce_gradients(grads, DP_AXIS, dp_reduce)
        with scopes.output():
            metrics = les.StepMetrics(
                *(jax.lax.psum(m, DP_AXIS) for m in metrics)
            )
        new_state = les.apply_gradients(
            state, grads, fuse_opt=fuse_opt, backend=backend
        )
        if telemetry:
            telem = _dp_telemetry(
                cfg, new_state, aux, grads, state, DP_AXIS
            )
            # topology-scoped extras: excluded from the cross-topology
            # bitwise-identity comparisons (shard count is not a property
            # of the *training trajectory*), surfaced as the `_dp` row
            telem["dp"] = {
                "grad_fits_int16": fits16,
                "shards": jnp.asarray(n, jnp.int32),
            }
            return new_state, metrics, telem
        return new_state, metrics

    sharded = jax.shard_map(
        _body,
        mesh=mesh,
        in_specs=(P(), batch_spec, batch_spec, P()),
        out_specs=P(),
        check_vma=False,
    )
    return sharded(state, x, labels, key)


def make_dp_train_step(
    cfg: M.NitroConfig,
    mesh: Mesh,
    *,
    dp_reduce: str = "psum",
    fused: bool = True,
    fuse_opt: bool = False,
    backend: str = "auto",
    telemetry: bool = False,
):
    """jit-compiled ``dp_train_step`` closure over (cfg, mesh, knobs) —
    the DP analogue of ``jax.jit(partial(les.train_step, cfg=cfg))``."""

    def step(state, x, labels, key):
        return dp_train_step(
            state, cfg, x, labels, key,
            mesh=mesh, dp_reduce=dp_reduce, fused=fused, fuse_opt=fuse_opt,
            backend=backend, telemetry=telemetry,
        )

    return jax.jit(step)
