"""The numbers that decide ``correct``: the program's first three rounds
against the plain reference's, on the same weights, batches and noise.

    loss_gap    largest relative gap of a round's mean loss
    gnorm_gap   largest relative gap of a round's mean pre-clip gradient
                norm (the norm the DP kernel computes)
    update_gap  worst leaf: gap between the norms of the parameters'
                change after round 1 (lr times the averaged noised
                gradient), against the reference's norm of that leaf or
                of the median leaf, whichever is larger
    change_gap  the same after round 3
    drift_gap   worst leaf, after round 1 or 3: the program's parameters
                less the reference's, read along the direction of that
                leaf's drift (the reference's noise-free move: lr times the
                clipped gradients), against the largest leaf's drift norm
    eps_gap     relative gap of the ledger's epsilon after round 3

The noise is the same draw on both sides and some hundred times the
clipped gradient, so the two norm numbers cannot see a gradient dropped,
reversed or scaled under it; ``drift_gap`` reads about 1 for every
gradient dropped and 2 for the largest one reversed, whatever the noise.
A single bfloat16 step of a small leaf against its small drift would read
as large as a lost gradient there, hence the largest leaf's scale.

Leaves whose clipped gradient at the first step is under a thousandth of
the median leaf's in the reference move by noise and round-off alone and
are left out of the leaf numbers.
"""
from __future__ import annotations

import numpy as np

NAMES = ("loss_gap", "gnorm_gap", "update_gap", "change_gap", "drift_gap",
         "eps_gap")


def rel_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


def leaf_gap(got, want, keep) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = max(float(np.median(want)), 1e-30)
    gaps = np.abs(got - want) / np.maximum(want, floor)
    return float(np.max(gaps[keep]))


def _f32(tree):
    """The tree's leaves as flat float32 arrays, one at a time."""
    import jax
    return (np.asarray(x, np.float32).ravel() for x in jax.tree.leaves(tree))


def _dot(a: np.ndarray, b: np.ndarray, chunk: int = 1 << 22) -> float:
    """float32 products summed in float64 chunk by chunk."""
    return sum(float(np.dot(a[i:i + chunk], b[i:i + chunk]))
               for i in range(0, a.size, chunk))


def drift_gap(got_params, want_params, drift, keep) -> float:
    """Worst kept leaf of |<got - want, d / |d|>| / max |d| over the rounds
    given (lists of parameter trees and drift trees)."""
    worst = 0.0
    for got, want, d in zip(got_params, want_params, drift):
        along, size = [], []
        for g, w, x in zip(_f32(got), _f32(want), _f32(d)):
            along.append(abs(_dot(g - w, x)))
            size.append(np.sqrt(_dot(x, x)))
        along, size = np.array(along)[keep], np.array(size)[keep]
        gaps = along / np.maximum(size * np.max(size), 1e-60)
        worst = max(worst, float(np.max(gaps)))
    return worst


def kept_leaves(first_clipped) -> np.ndarray:
    g = np.asarray(first_clipped, np.float64)
    return g >= 1e-3 * np.median(g)


def numbers(program: dict, reference: dict) -> dict[str, float]:
    keep = kept_leaves(reference["first_clipped"])
    out = {
        "loss_gap": rel_gap(program["loss"], reference["loss"]),
        "gnorm_gap": rel_gap(program["gnorm"], reference["gnorm"]),
        "update_gap": leaf_gap(program["params_norms"][0],
                               reference["params_norms"][0], keep),
        "change_gap": leaf_gap(program["params_norms"][-1],
                               reference["params_norms"][-1], keep),
        "drift_gap": drift_gap(program["params"], reference["params"],
                               reference["drift"], keep),
        "eps_gap": rel_gap(program["epsilon"], reference["epsilon"]),
    }
    # a NaN anywhere must read as a failure, not slip past a comparison
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def against(numbers: dict[str, float], limits: dict[str, float]) -> dict:
    """Each number beside its limit, in a fixed order."""
    return {k: {"value": numbers[k], "limit": float(limits[k])}
            for k in NAMES}
