"""Plain reference of the first training steps: float32 loss, grads, AdamW.

Gradients come from ``bench.reference.decoder``; AdamW is the published
algorithm with decoupled weight decay, global-norm clipping and linear
warm-up into a cosine schedule, with the hyper-parameters of the traffic
file. Everything stays on the device in float32 and the function returns
norms only, one per leaf (a stacked layer is a leaf), so that the host
holds no copy of the state.

``param_dtype="bfloat16"`` stores the parameters in bfloat16 after every
update (no float32 master copy): the control. ``rows`` keeps only the first
rows of each batch: a planted fault.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare
from bench import model as bm
from bench.reference import decoder


def lr_at(step: int, opt: Dict[str, Any]) -> float:
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    if opt.get("schedule", "cosine") == "constant":
        return float(opt["lr"])
    total = opt["total_steps"]
    progress = (step - opt["warmup_steps"]) / max(total - opt["warmup_steps"], 1)
    progress = min(max(progress, 0.0), 1.0)
    return opt["lr"] * warm * 0.5 * (1.0 + math.cos(math.pi * progress))


def _round(tree, param_dtype: Optional[str]):
    if param_dtype is None:
        return tree
    return jax.tree.map(lambda a: a.astype(param_dtype).astype(jnp.float32), tree)


def _adamw(opt: Dict[str, Any], param_dtype: Optional[str]):
    b1, b2, eps, wd, clip = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"], \
        opt["clip_norm"]

    def update(p, m, v, g, t, lr):
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

        def leaf(p, m, v, g):
            g = g * scale
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * g * g
            return p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p), m, v

        out = jax.tree.map(leaf, p, m, v, g)
        pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return _round(pick(0), param_dtype), pick(1), pick(2), scale

    return jax.jit(update, donate_argnums=(0, 1, 2))


def run(cfg: Dict[str, Any], weight_seed: int, batches: List[np.ndarray], opt: Dict[str, Any],
        *, param_dtype: Optional[str] = None, rows: Optional[int] = None,
        other_params: Any = None) -> Dict[str, Any]:
    """Losses of ``len(batches)`` steps, per-leaf norms of the first clipped
    gradient and of the parameters' change after the last update.

    ``other_params`` (a host tree in the same layout) gets its change from
    the same starting weights measured too, as ``other_change_norms``.
    """
    f32cfg = dict(cfg, param_dtype="float32")
    p = _round(bm.make_weights(f32cfg, weight_seed), param_dtype)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    grad_fn = decoder.loss_and_grad(f32cfg)
    update = _adamw(opt, param_dtype)
    losses: List[float] = []
    first: Dict[str, float] = {}
    for step, tokens in enumerate(batches):
        loss, g = grad_fn(p, jnp.asarray(tokens[:rows] if rows else tokens))
        losses.append(float(loss))
        norms = compare.device_slice_norms(g) if step == 0 else None
        p, m, v, scale = update(p, m, v, g, float(step + 1), lr_at(step, opt))
        del g
        if norms is not None:
            first = {k: x * float(scale) for k, x in norms.items()}
    del m, v
    p0 = bm.make_weights(f32cfg, weight_seed)
    out = {"losses": losses, "first_grad_norms": first,
           "change_norms": compare.device_diff_norms(p, p0)}
    del p
    if other_params is not None:
        out["other_change_norms"] = compare.device_diff_norms(jax.device_put(other_params), p0)
    return out
