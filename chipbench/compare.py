"""The numbers that decide ``correct``, computed alike for the program,
the control and the planted faults.

Training compares, by the worst weight slice (each layer of a stacked
weight is its own slice):

- ``loss_gap`` — the largest ``|loss - reference loss|`` (nats) over the
  checked steps;
- ``grad_norm_gap`` — the first gradient as the optimizer got it (its
  first moment after one step over ``1 - beta1``): the largest gap
  between the two norms of a slice, over the larger of the reference's
  norm of that slice and the median slice's;
- ``update_norm_gap`` — the same for the change of the weights over the
  checked steps, leaving out slices whose reference gradient is under a
  thousandth of the median slice's (they move by round-off alone).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# leaves with a leading layer axis are split into one slice per layer
STACKED = "layers"
SKIP_FRACTION = 1e-3


@jax.jit
def _slice_norms(tree):
    def norms(path, x):
        x = x.astype(jnp.float32)
        if STACKED in jax.tree_util.keystr(path):
            return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        return jnp.sqrt(jnp.sum(x * x))[None]
    return jax.tree_util.tree_map_with_path(norms, tree)


@jax.jit
def _diff_norms(a, b):
    return _slice_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def slice_norms(tree) -> np.ndarray:
    """Norms of every slice, in a fixed order, on the host."""
    return np.concatenate([np.asarray(x) for x in
                           jax.tree.leaves(_slice_norms(tree))])


def diff_norms(a, b) -> np.ndarray:
    return np.concatenate([np.asarray(x) for x in
                           jax.tree.leaves(_diff_norms(a, b))])


def worst_slice_gap(got: np.ndarray, want: np.ndarray,
                    keep: np.ndarray = None) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if keep is not None:
        got, want = got[keep], want[keep]
    base = np.maximum(want, np.median(want))
    return float(np.max(np.abs(got - want) / base))


def moving_slices(ref_grad_norms: np.ndarray) -> np.ndarray:
    """Slices the reference's gradient moves: those at or above a
    thousandth of the median slice's gradient norm."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= SKIP_FRACTION * np.median(g)


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog`` and ``ref``: ``{"losses": [...], "grad_norms": slice
    norms of the first gradient, "update_norms": slice norms of the
    weights' change}`` over the same checked steps."""
    n = min(len(prog["losses"]), len(ref["losses"]))
    loss_gap = max(abs(float(a) - float(b)) for a, b in
                   zip(prog["losses"][:n], ref["losses"][:n]))
    out = {"loss_gap": loss_gap,
           "grad_norm_gap": worst_slice_gap(prog["grad_norms"],
                                            ref["grad_norms"])}
    if "update_norms" in prog and "update_norms" in ref:
        out["update_norm_gap"] = worst_slice_gap(
            prog["update_norms"], ref["update_norms"],
            moving_slices(ref["grad_norms"]))
    return out

