"""The NITRO-D learning algorithm (paper §3.3) — integer-only LES training.

One training step:

  1. forward through every block's *forward layers* and the *output layers*;
  2. output layers: ∇L_o = ŷ − y → IntegerSGD update (γ_inv^lr, η_inv^lr);
  3. per block (independently — XLA schedules these concurrently, the LES
     block-parallelism the paper highlights):
       a. learning layers on a_l → ŷ_l;
       b. ∇L_l = ŷ_l − y → learning-layer update (γ_inv^lr, η_inv^lr);
       c. δ_l^fw from the learning-layer backward → forward-layer update
          (γ_inv^fw = γ_inv^lr·AF — NITRO Amplification Factor, η_inv^fw).

No gradient crosses a block boundary.  Everything below is integer: the
whole step jit-compiles to an integer-only XLA program (verifiable — the
test-suite asserts no float dtype appears in the jaxpr).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import blocks as B
from repro.core import model as M
from repro.core import optimizer as opt
from repro.core.losses import ONE_HOT_VALUE, one_hot_int, rss_grad, rss_loss
from repro.core.numerics import INT_DTYPE
from repro.obs import layers as scopes


class TrainState(NamedTuple):
    params: dict
    opt_lr: opt.IntegerSGDState   # learning + output layers
    opt_fw: opt.IntegerSGDState   # forward layers (γ amplified by AF)
    step: jax.Array


def create_train_state(key: jax.Array, cfg: M.NitroConfig) -> TrainState:
    params = M.init_params(key, cfg)
    af = opt.amplification_factor(cfg.num_classes)
    return TrainState(
        params=params,
        opt_lr=opt.init_state(cfg.gamma_inv, cfg.eta_lr),
        opt_fw=opt.init_state(cfg.gamma_inv * af, cfg.eta_fw),
        step=jnp.zeros((), INT_DTYPE),
    )


class StepGrads(NamedTuple):
    """Raw integer gradients of one step, pre-optimiser.

    Same structure as ``TrainState.params``: ``blocks`` is a tuple of
    ``{"fw": ..., "lr": ...}`` gradient dicts, ``output`` the output-layer
    gradient dict.  This is the pytree a data-parallel step all-reduces
    between ``compute_gradients`` and ``apply_gradients`` — int32
    summation is exact and order-invariant, so the reduction point is
    also the bitwise-determinism point (see ``repro.parallel.dp``).
    """

    blocks: tuple
    output: dict


class StepAux(NamedTuple):
    """Non-gradient byproducts of ``compute_gradients`` that the
    telemetry readout consumes (jit DCEs them otherwise)."""

    fw_caches: tuple


class StepMetrics(NamedTuple):
    loss: jax.Array          # integer RSS of the output layers
    correct: jax.Array       # # correct top-1 predictions in the batch
    local_losses: jax.Array  # per-block integer RSS (L,)

    def scaled_loss(self, batch_size: int) -> float:
        """Display-only per-sample loss in one-hot units: loss / (B·32²).

        The raw integer RSS grows with the batch size and the squared
        one-hot magnitude (Appendix B.2's 32), which makes progress
        lines hard to eyeball across configs.  This divides both out —
        a *host-side float convenience only*: it must be called on a
        concrete (already-computed) metric outside the jitted step, so
        the training jaxpr stays float-free (calling it on a tracer
        raises, by design).
        """
        return float(self.loss) / (float(batch_size) * ONE_HOT_VALUE ** 2)


def compute_gradients(
    state: TrainState,
    cfg: M.NitroConfig,
    x: jax.Array,
    labels: jax.Array,
    key: jax.Array,
    *,
    fused: bool = True,
    backend: str = "auto",
    dp_axis: str | None = None,
    dp_shards: int = 1,
) -> tuple[StepGrads, StepMetrics, StepAux]:
    """Forward + backward over a batch — raw gradients, no parameter update.

    This is the first half of ``train_step``, split out so a data-parallel
    step (``repro.parallel.dp``) can all-reduce the integer gradients
    between gradient computation and the IntegerSGD update.  The returned
    ``StepGrads``/``StepMetrics`` are *sums over the batch this call saw*:
    summing them across batch shards (exact int32 addition) reproduces
    the full-batch values bit-for-bit, which is what makes integer data
    parallelism bitwise-deterministic at any device count.

    ``dp_axis``/``dp_shards`` describe the data-parallel context this
    call runs in (a ``shard_map`` axis name and its size).  They exist
    solely so IntegerDropout draws the *global-batch* mask and slices
    this shard's rows — the one sampled operation whose per-shard
    evaluation would otherwise diverge from the single-device run.
    Outside shard_map leave them at their defaults.
    """
    params = state.params
    y = one_hot_int(labels, cfg.num_classes)

    # ---- forward ----------------------------------------------------------
    y_hat, acts, fw_caches, out_cache = M.forward(
        params, cfg, x, train=True, key=key, fused=fused, backend=backend,
        dp_axis=dp_axis, dp_shards=dp_shards,
    )

    # ---- output layers ----------------------------------------------------
    with scopes.output():
        grad_o = rss_grad(y_hat, y)
        out_grads = B.output_backward(params["output"], out_cache, grad_o)

    # ---- per-block local gradients (independent → parallel) ---------------
    block_grads = []
    local_losses = []
    for i, (spec, p, a_l, fw_cache) in enumerate(
        zip(cfg.blocks, params["blocks"], acts, fw_caches)
    ):
        with scopes.block(i, scopes.LOCAL_LOSS):
            y_hat_l, lr_cache = B.learning_layers(p, spec, a_l)
            grad_l = B.local_gradient(y_hat_l, y)
            local_losses.append(rss_loss(y_hat_l, y))
            delta_fw, lr_grads = B.learning_layers_backward(
                p, spec, lr_cache, grad_l)
        with scopes.block(i, scopes.BACKWARD):
            fw_grads = B.forward_layers_backward(
                p, spec, fw_cache, delta_fw, backend=backend,
            )
        block_grads.append({"fw": fw_grads, "lr": lr_grads})

    grads = StepGrads(blocks=tuple(block_grads), output=out_grads)
    with scopes.output():
        metrics = _step_metrics(y_hat, y, labels, local_losses)
    return grads, metrics, StepAux(fw_caches=tuple(fw_caches))


def _step_metrics(y_hat, y, labels, local_losses) -> StepMetrics:
    return StepMetrics(
        loss=rss_loss(y_hat, y),
        correct=jnp.sum(jnp.argmax(y_hat, axis=-1) == labels),
        local_losses=jnp.stack(local_losses),
    )


def apply_gradients(
    state: TrainState,
    grads: StepGrads,
    *,
    fuse_opt: bool = False,
    backend: str = "auto",
) -> TrainState:
    """IntegerSGD update of every parameter group from raw gradients.

    The second half of ``train_step``: deterministic given (state, grads),
    so two replicas holding identical state and identical (all-reduced)
    gradients step to bitwise-identical new states.

    ``fuse_opt=True`` routes the update through the standalone fused
    IntegerSGD kernel (``kernels.integer_sgd.apply_tree_fused`` — W and g
    read once, W′ written once) instead of the jnp ``opt.apply_tree`` —
    bitwise identical.  This is the data-parallel step's fused path: DP
    must materialise the gradient for the all-reduce, so it cannot use
    the grad-kernel flush epilogue, but the post-reduce update still
    avoids the floor-division temporaries' HBM round-trips.  ``backend``
    is only consulted when ``fuse_opt`` is set.
    """
    if fuse_opt:
        # lazy import: core must not import kernels at module scope
        from repro.kernels.integer_sgd.ops import apply_tree_fused

        def _apply(p, g, s):
            return apply_tree_fused(p, g, s, backend=backend)
    else:
        def _apply(p, g, s):
            return opt.apply_tree(p, g, s)

    blocks = state.params["blocks"]
    with scopes.update():
        # one call per optimiser group, so each group's reciprocals are
        # computed once a step (``opt.apply_tree``)
        new_fw = _apply([p["fw"] for p in blocks],
                        [g["fw"] for g in grads.blocks], state.opt_fw)
        new_lr, new_output = _apply(
            ([p["lr"] for p in blocks], state.params["output"]),
            ([g["lr"] for g in grads.blocks], grads.output),
            state.opt_lr,
        )
        new_blocks = [{"fw": fw, "lr": lr} for fw, lr in zip(new_fw, new_lr)]
        new_params = {"blocks": new_blocks, "output": new_output}
        return state._replace(params=new_params, step=state.step + 1)


def _fused_opt_step(
    state: TrainState,
    cfg: M.NitroConfig,
    x: jax.Array,
    labels: jax.Array,
    key: jax.Array,
    *,
    fused: bool,
    backend: str,
) -> tuple[TrainState, StepMetrics]:
    """The monolithic fast path behind ``train_step(fuse_opt=True)``.

    Bypasses the ``compute_gradients``/``apply_gradients`` split: each
    block's forward-layer weight gradient is consumed *inside* the grad_W
    kernel whose flush applies the IntegerSGD update
    (``blocks.forward_layers_update``), so the full-size grad_W never
    materialises in HBM.  The learning/output layers keep the jnp update —
    their gradients are small (d_lr × classes) and their backward has no
    Pallas flush to fuse into.  Bitwise identical to the split
    composition: integer floor-div over an order-exact int32 accumulation
    is exact, so fused ≡ unfused is provable (and test-enforced).
    """
    params = state.params
    y = one_hot_int(labels, cfg.num_classes)

    y_hat, acts, fw_caches, out_cache = M.forward(
        params, cfg, x, train=True, key=key, fused=fused, backend=backend,
    )

    with scopes.output():
        grad_o = rss_grad(y_hat, y)
        out_grads = B.output_backward(params["output"], out_cache, grad_o)

    new_fws = []
    all_lr_grads = []
    local_losses = []
    for i, (spec, p, a_l, fw_cache) in enumerate(
        zip(cfg.blocks, params["blocks"], acts, fw_caches)
    ):
        with scopes.block(i, scopes.LOCAL_LOSS):
            y_hat_l, lr_cache = B.learning_layers(p, spec, a_l)
            grad_l = B.local_gradient(y_hat_l, y)
            local_losses.append(rss_loss(y_hat_l, y))
            delta_fw, lr_grads = B.learning_layers_backward(
                p, spec, lr_cache, grad_l)
        with scopes.block(i, scopes.BACKWARD):
            new_fw = B.forward_layers_update(
                p, spec, fw_cache, delta_fw, state.opt_fw, backend=backend,
            )
        new_fws.append(new_fw)
        all_lr_grads.append(lr_grads)

    with scopes.update():
        # the learning and output layers as one group (see apply_gradients)
        new_lr, new_output = opt.apply_tree(
            ([p["lr"] for p in params["blocks"]], params["output"]),
            (all_lr_grads, out_grads),
            state.opt_lr,
        )
    new_blocks = [{"fw": fw, "lr": lr} for fw, lr in zip(new_fws, new_lr)]

    with scopes.output():
        metrics = _step_metrics(y_hat, y, labels, local_losses)
    new_params = {"blocks": new_blocks, "output": new_output}
    return state._replace(params=new_params, step=state.step + 1), metrics


def train_step(
    state: TrainState,
    cfg: M.NitroConfig,
    x: jax.Array,
    labels: jax.Array,
    key: jax.Array,
    *,
    fused: bool = True,
    fuse_opt: bool = False,
    backend: str = "auto",
    telemetry: bool = False,
):
    """One integer-only NITRO-D step over a batch. jit-able (cfg static).

    Composes ``compute_gradients`` (forward + backward → raw integer
    gradients) with ``apply_gradients`` (IntegerSGD update) — the split
    exists so the data-parallel step in ``repro.parallel.dp`` can
    all-reduce the gradients in between; this single-device composition
    is bitwise identical to the pre-split monolithic step.

    The forward pass runs on the fused kernels by default (the same entry
    points the inference plan compiles to); ``fused=False`` is the unfused
    reference escape hatch, bit-exact with the fused step.  The backward
    is fused too: the NITRO-ReLU derivative + scaling STE run as the
    gradient kernels' δ prologue (``kernels.grad_ops``), and every conv
    streams its patches (implicit im2col).  ``backend="reference"`` runs
    the same step on the pure-jnp oracles, whose δ path is the unfused
    composition — the bitwise bar for the kernels.

    ``fuse_opt=True`` takes the monolithic fast path (``_fused_opt_step``):
    the IntegerSGD update of each forward-layer weight runs as the grad_W
    kernel's *flush epilogue*, so grad_W never materialises in HBM —
    3 HBM streams per weight update instead of 5+.  Bitwise identical to
    the split composition (test-enforced).  The split survives where the
    materialised gradient has another consumer: data parallelism (the
    all-reduce — ``parallel.dp`` applies the standalone fused kernel
    post-reduce instead) and ``telemetry=True`` (the readout inspects the
    fw gradients), which therefore falls back to the split path here.

    ``telemetry=True`` returns ``(state, metrics, telem)`` where
    ``telem`` is the integer-only numerics-telemetry pytree of
    ``repro.obs.telemetry`` (per-layer bit-occupancy/saturation, dead
    units, optimiser scalars).  Telemetry is a pure readout added as an
    extra jit output: the returned ``TrainState`` trajectory is bitwise
    identical with it on or off, and the whole jaxpr stays float-free —
    both test-enforced.
    """
    if fuse_opt and not telemetry:
        return _fused_opt_step(
            state, cfg, x, labels, key,
            fused=fused, backend=backend,
        )
    grads, metrics, aux = compute_gradients(
        state, cfg, x, labels, key,
        fused=fused, backend=backend,
    )
    new_state = apply_gradients(state, grads)
    if telemetry:
        # lazy import: obs is an optional read-only layer over the core
        from repro.obs import telemetry as T

        telem = T.collect_train_telemetry(
            cfg, new_state.params, aux.fw_caches,
            [g["fw"] for g in grads.blocks], grads.output,
            state.opt_lr, state.opt_fw,
        )
        return new_state, metrics, telem
    return new_state, metrics


def eval_step(
    state: TrainState, cfg: M.NitroConfig, x: jax.Array, labels: jax.Array
) -> jax.Array:
    """# correct predictions (integer) over a batch."""
    y_hat = M.frozen_forward(state.params, cfg, x)
    return jnp.sum(jnp.argmax(y_hat, axis=-1) == labels)


def reduce_lr_on_plateau(state: TrainState, plateau) -> TrainState:
    """Apply the ÷3 schedule to both optimiser groups (γ_inv ×3)."""
    plateau = jnp.asarray(plateau)
    return state._replace(
        opt_lr=opt.step_lr_schedule(state.opt_lr, plateau),
        opt_fw=opt.step_lr_schedule(state.opt_fw, plateau),
    )
