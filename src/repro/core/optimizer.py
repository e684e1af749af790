"""IntegerSGD optimizer with integer weight decay (paper §3.3, Algorithm 1).

Update rule, entirely in ℤ::

    δ_t ← ⌊ ∇f_t(W_{t-1}) / γ_inv ⌋
    if η_inv ≠ 0:  δ_t ← δ_t + ⌊ W_{t-1} / η_inv ⌋
    W_t ← W_{t-1} − δ_t

``γ_inv = ⌊1/γ⌋`` and ``η_inv = γ_inv · λ_inv`` are the inverse learning /
composite decay rates.  The decay term is a *floor* division (rounds
toward −∞, matching ``jnp.floor_divide``), which makes its small-weight
behaviour asymmetric:

  * ``0 ≤ w < η_inv``      → ``⌊w/η_inv⌋ = 0``  — small positive weights
    are untouched;
  * ``−η_inv ≤ w < 0``     → ``⌊w/η_inv⌋ = −1`` — every small *negative*
    weight gets a constant +1 nudge per step (``w ← w + 1`` at zero
    gradient), an asymmetric pull toward zero that positive weights of
    the same magnitude don't get: at zero gradient a small negative
    weight climbs one unit per step until it reaches 0 and stays there,
    while a small positive weight never moves at all.

Algorithm 1 specifies exactly this floor arithmetic — the asymmetry is
the faithful integer semantics, not a bug — but it means decay is *not*
"zeroed for |w| < η_inv": that holds for the positive half only.  Pinned
by a hypothesis property test over negative weights
(``tests/test_integer_sgd.py``).

Both floor divisions are by a divisor that is the same for every element
of every tensor of a group within a step, so they run as a multiply by a
precomputed integer reciprocal (division by an invariant integer,
Granlund & Montgomery, PLDI 1994: ``numerics.reciprocal`` /
``numerics.floor_div_by``), never as a per-element integer divide — the
v5e vector unit has none, and XLA's expansion of ``jnp.floor_divide``'s
div + rem is what set the update's cost.  The reciprocals are computed
once per ``apply_tree`` call (one parameter group, one step).  Exact for
every divisor d ≥ 1 and every int32 numerator: bitwise equal to
``jnp.floor_divide``, pinned by ``tests/test_numerics.py``
(``TestFloorDivBy``) and, for the whole update after lr-schedule steps,
by ``tests/test_integer_sgd.py`` (``TestReciprocalUpdate``).

NITRO Amplification Factor: a block's *forward layers* receive the local
gradient amplified by the learning layers' matmul (bit-width
O(13 + log₂ G)).  AF = 2⁶·G normalises that amplification, so the effective
divisor for forward-layer updates is ``γ_inv^fw = γ_inv^lr · AF``.

    NOTE (paper deviation, recorded): the paper's text writes
    ``γ_inv^fw = γ_inv^lr / AF``, which for its own hyper-parameters
    (γ_inv = 512, G = 10 ⇒ AF = 640) floor-divides to zero and would make
    Algorithm 1 divide by zero.  The motivation (§3.3: the forward layers
    otherwise get "disproportionately large weight updates") and the AF
    bit-width derivation both require the forward-layer *effective learning
    rate* to shrink by AF, i.e. the inverse rate to grow:
    γ_inv^fw = γ_inv^lr × AF.  We implement that reading.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import numerics
from repro.core.numerics import floor_div_by


def amplification_factor(num_classes: int) -> int:
    """AF = 2⁶ × G (paper §3.3)."""
    return (2 ** 6) * int(num_classes)


class IntegerSGDState(NamedTuple):
    """Mutable optimizer scalars, kept as int32 arrays so the lr schedule
    (÷3 on plateau, Appendix D) is a pure-integer in-graph update."""

    gamma_inv: jax.Array  # inverse learning rate (int32 scalar)
    eta_inv: jax.Array    # inverse composite decay rate (int32 scalar, 0 = off)


def init_state(gamma_inv: int, eta_inv: int = 0) -> IntegerSGDState:
    return IntegerSGDState(
        gamma_inv=jnp.asarray(gamma_inv, numerics.INT_DTYPE),
        eta_inv=jnp.asarray(eta_inv, numerics.INT_DTYPE),
    )


def _reciprocals(state: IntegerSGDState) -> tuple[numerics.Reciprocal, ...]:
    """The integer reciprocals of γ_inv and max(η_inv, 1): one prologue,
    vectorised over both divisors, for every leaf of a group to share."""
    r = numerics.reciprocal(
        jnp.stack([state.gamma_inv, jnp.maximum(state.eta_inv, 1)])
    )
    return tuple(numerics.Reciprocal(r.m[i], r.sh[i]) for i in range(2))


def _update(w, grad, recips, decay_on) -> jax.Array:
    numerics.assert_int(w, "weights")
    numerics.assert_int(grad, "gradient")
    gamma_r, eta_r = recips
    delta = floor_div_by(grad, gamma_r)
    decay = jnp.where(decay_on, floor_div_by(w, eta_r), jnp.zeros_like(w))
    return w - (delta + decay)


def apply_update(
    w: jax.Array, grad: jax.Array, state: IntegerSGDState
) -> jax.Array:
    """One Algorithm-1 step for a single weight tensor.

    Floor-division decay: zero for ``0 ≤ w < η_inv`` but −1 for
    ``−η_inv ≤ w < 0`` (the asymmetry documented in the module
    docstring); ``η_inv == 0`` disables decay entirely.
    """
    return _update(w, grad, _reciprocals(state), state.eta_inv != 0)


def apply_tree(params, grads, state: IntegerSGDState):
    """Apply IntegerSGD across a whole parameter pytree: the reciprocals
    are computed once for the group, not once per leaf."""
    recips = _reciprocals(state)
    decay_on = state.eta_inv != 0
    return jax.tree_util.tree_map(
        lambda w, g: _update(w, g, recips, decay_on), params, grads
    )


def step_lr_schedule(state: IntegerSGDState, plateau: jax.Array) -> IntegerSGDState:
    """γ_inv ← γ_inv · 3 when the accuracy plateaus (integer analogue of the
    paper's 'reduce lr by 3× on plateau')."""
    new_gamma = jnp.where(plateau, state.gamma_inv * 3, state.gamma_inv)
    return state._replace(gamma_inv=new_gamma)
