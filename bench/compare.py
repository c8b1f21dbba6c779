"""The numbers that decide ``correct``: gaps between the program and the reference.

Training (first steps, by the worst leaf; each layer of a stacked segment,
``seg<k>/...``, is a leaf):

* ``loss_gap``: largest relative gap of a step's loss;
* ``grad_gap``: gap between the norms of the first clipped gradient, program
  against reference, over the larger of the reference leaf's norm and the
  median leaf's;
* ``change_gap``: the same for the parameters' change after the steps.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's are left out: they move by round-off alone.

Serving: ``logit_gap``, the widest gap by which a served token's reference
logit lies below the reference's best at that position.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: a leaf whose reference gradient norm is under this share of the median
#: leaf's is left out of ``change_gap``
NOUGHT = 1e-3


def _flat_norms(res: Dict[str, np.ndarray]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, vals in res.items():
        vals = np.asarray(vals, np.float64)
        if _stacked(name.split("/", 1)[0]):
            out.update({f"{name}[{i}]": float(x) for i, x in enumerate(vals)})
        else:
            out[name] = float(vals[0])
    return out


def _stacked(top: str) -> bool:
    """Whether a top-level key holds layers stacked along a leading axis."""
    return top.startswith("seg")


@jax.jit
def _norms(tree):
    out = {}
    for path, a in jax.tree_util.tree_leaves_with_path(tree):
        names = [k.key for k in path]
        x = a.astype(jnp.float32)
        x = x.reshape(x.shape[0], -1) if _stacked(names[0]) else x.reshape(1, -1)
        out["/".join(names)] = jnp.sqrt(jnp.sum(x * x, axis=1))
    return out


@jax.jit
def _diff_norms(a, b):
    return _norms(jax.tree.map(lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def device_slice_norms(tree) -> Dict[str, float]:
    """Norm of every leaf of a device tree, one per layer for stacked leaves."""
    return _flat_norms(jax.device_get(_norms(tree)))


def device_diff_norms(a, b) -> Dict[str, float]:
    """Per-leaf norms of ``a - b``, computed on the device."""
    return _flat_norms(jax.device_get(_diff_norms(a, b)))


def norm_gap(prog: Dict[str, float], ref: Dict[str, float], keep=None) -> Tuple[float, str]:
    """Worst leaf's |prog - ref| over max(ref leaf, median ref leaf)."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    worst, where = 0.0, ""
    for n in names:
        g = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if not np.isfinite(prog[n]):
            g = float("inf")
        if g > worst or not where:
            worst, where = g, n
    return worst, where


def counted_leaves(ref_grad: Dict[str, float]) -> List[str]:
    med = float(np.median(list(ref_grad.values())))
    return [n for n, g in ref_grad.items() if g >= NOUGHT * med]


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog, ref, strict=True)]
    return max(gaps) if all(np.isfinite(prog)) else float("inf")


def train_gaps(prog: Dict[str, object], ref: Dict[str, object]) -> Dict[str, float]:
    """Both sides: ``losses``, ``first_grad_norms`` and ``change_norms``."""
    ref_grad = ref["first_grad_norms"]
    grad_gap, grad_leaf = norm_gap(prog["first_grad_norms"], ref_grad)
    change_gap, change_leaf = norm_gap(prog["change_norms"], ref["change_norms"],
                                       keep=set(counted_leaves(ref_grad)))
    return {
        "loss_gap": loss_gap(prog["losses"], ref["losses"]),
        "grad_gap": grad_gap,
        "change_gap": change_gap,
        "_grad_leaf": grad_leaf,
        "_change_leaf": change_leaf,
        "_left_out": sorted(set(ref_grad) - set(counted_leaves(ref_grad))),
    }


def logit_gap(logits: np.ndarray, tokens: Sequence[int]) -> float:
    """Widest gap of ``tokens`` below the row-wise best of ``logits``."""
    lg = np.asarray(logits, np.float64)
    idx = np.asarray(tokens)
    return float(np.max(lg.max(axis=-1) - lg[np.arange(len(idx)), idx]))
