"""The numbers a training cell compares with the plain reference.

From the first ``k`` steps of a run, taken from the same weights W0:

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_gap``: the first gradient as the optimizer got it, worked out
  from the state after one step, (W0 - W1) / lr, against the reference's
  gradient;
- ``update_gap``: the change of the parameters over the k steps,
  Wk - W0, against the reference's.

The last two are taken by the worst leaf: the gap between the leaf's
norm in the run and in the reference (not the norm of their
difference), relative to the larger of that leaf's reference norm and
the median leaf's. A leaf whose reference gradient is under a thousandth
of the median leaf's moves by round-off alone and is left out.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def _norms(tree) -> List[float]:
    import jax

    return [float(np.linalg.norm(np.asarray(a, np.float64))) for a in jax.tree.leaves(tree)]


def _diff(a, b, scale: float = 1.0):
    import jax

    return jax.tree.map(
        lambda u, v: (np.asarray(u, np.float64) - np.asarray(v, np.float64)) * scale, a, b)


def _worst_leaf(run: List[float], ref: List[float], keep: List[bool]) -> float:
    med = float(np.median(ref))
    return max(abs(a - b) / max(b, med) for a, b, k in zip(run, ref, keep) if k)


def readings(losses: Sequence[float], w0, w1, wk, lr: float,
             ref_losses: Sequence[float], ref_grads, ref_wk) -> Dict[str, float]:
    """loss_gap, grad_gap and update_gap of a run (losses of its first k
    steps, its weights before, after one and after k steps) against the
    reference (its losses, first gradients and weights after k steps)."""
    g_ref = _norms(ref_grads)
    med = float(np.median(g_ref))
    keep = [g >= 1e-3 * med for g in g_ref]
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)),
        "grad_gap": _worst_leaf(_norms(_diff(w0, w1, 1.0 / lr)), g_ref, keep),
        "update_gap": _worst_leaf(_norms(_diff(wk, w0)), _norms(_diff(ref_wk, w0)), keep),
    }
