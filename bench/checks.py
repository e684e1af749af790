"""The comparisons that decide ``correct``, on host copies of the results.

NITRO-D is integer-only, so the program and the plain reference agree
bit for bit or not at all: every limit is 0 (``PERF.md`` gives the
readings each was set from).  Training compares what the guide to the
benchmark asks for, each step's loss, the first update's norm and the
three steps' change, and beside them every element of the state and
every metric.  Serving compares every answered request's logits.
"""

from __future__ import annotations

import numpy as np

from bench.harness import Check

#: leaves that the reference moves by less than this share of the median
#: leaf's movement are left out of the norm comparisons: a leaf at
#: nought cannot be compared by a relative gap
QUIET_LEAF = 1e-3


def _leaves(tree) -> list[np.ndarray]:
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _norms(after, before) -> np.ndarray:
    return np.array([np.sqrt(np.sum((a.astype(np.float64)
                                     - b.astype(np.float64)) ** 2))
                     for a, b in zip(_leaves(after), _leaves(before))])


def norm_gap(prog_after, ref_after, before) -> float:
    """Worst leaf: |‖Δprog‖ − ‖Δref‖| over the larger of the reference
    leaf's ‖Δ‖ and the median leaf's."""
    prog = _norms(prog_after, before)
    ref = _norms(ref_after, before)
    med = float(np.median(ref))
    keep = ref >= QUIET_LEAF * med
    if not keep.any():
        return float("inf")
    scale = np.maximum(ref, med)[keep]
    return float(np.max(np.abs(prog[keep] - ref[keep]) / scale))


def elements_differ(a, b) -> int:
    la, lb = _leaves(a), _leaves(b)
    if len(la) != len(lb):  # different structure: nothing matches
        return max(sum(x.size for x in la + lb), 1)
    n = 0
    for x, y in zip(la, lb):
        if x.shape != y.shape:
            n += max(x.size, y.size)
        else:
            n += int(np.sum(x != y))
    return n


def train_checks(p0, prog_p1, prog_p3, prog_metrics, ref_p1, ref_p3,
                 ref_metrics, prog_extra, want_extra) -> list[Check]:
    """Three steps of the program against three of the reference.

    ``*_metrics`` are three dicts with ``loss``, ``correct`` and
    ``local_losses``; ``prog_extra`` / ``want_extra`` hold the rest of the
    program's state after step 3 (optimiser scalars, step count) and what
    the configuration says it must be.
    """
    loss_gap = max(abs(int(p["loss"]) - int(r["loss"])) / max(abs(int(r["loss"])), 1)
                   for p, r in zip(prog_metrics, ref_metrics))
    metric_diff = sum(elements_differ(p, r)
                      for p, r in zip(prog_metrics, ref_metrics))
    return [
        Check("loss_gap", float(loss_gap), 0.0),
        Check("first_update_norm_gap", norm_gap(prog_p1, ref_p1, p0), 0.0),
        Check("three_step_change_norm_gap", norm_gap(prog_p3, ref_p3, p0),
              0.0),
        Check("params_differ", float(elements_differ(prog_p3, ref_p3)), 0.0),
        Check("metrics_differ", float(metric_diff), 0.0),
        Check("optimizer_state_differ",
              float(elements_differ(prog_extra, want_extra)), 0.0),
    ]


def serve_checks(answers: dict, ref_logits: np.ndarray,
                 image_of: np.ndarray) -> list[Check]:
    """``answers`` maps request index → served logits row (None when the
    request never got an answer); ``image_of`` the request → image."""
    missing = sum(1 for v in answers.values() if v is None)
    wrong, gap = 0, 0
    for i, row in answers.items():
        if row is None:
            continue
        want = ref_logits[image_of[i]]
        row = np.asarray(row)
        if row.shape != want.shape:
            wrong += 1
            gap = max(gap, 2**31)
            continue
        d = np.abs(row.astype(np.int64) - want.astype(np.int64))
        if d.any():
            wrong += 1
            gap = max(gap, int(d.max()))
    return [
        Check("unanswered", float(missing), 0.0),
        Check("rows_differ", float(wrong), 0.0),
        Check("max_logit_gap", float(gap), 0.0),
    ]
