"""Plain reference of the first training steps: float32 loss, grads, AdamW.

Weights and gradients come from the configuration's family
(``bench/families/``); AdamW is the published algorithm with decoupled
weight decay, global-norm clipping and linear warm-up into a cosine
schedule, with the hyper-parameters of the traffic file. Everything is
computed on the device in float32 and the function returns norms only, one
per leaf (a stacked layer is a leaf).

A step's gradient is taken over chunks of at most ``CHUNK_ROWS`` rows and
averaged, so that a batch of any size fits on one chip, with the optimizer's
state parked on the host meanwhile. Rows have equal length and every term
of the loss is a mean over tokens, so that average is the batch's mean; a
batch of one chunk is taken whole, by the same program as before chunking.

``param_dtype="bfloat16"`` stores the parameters in bfloat16 after every
update (no float32 master copy): the control. ``rows`` keeps only the first
rows of each batch: a planted fault.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare

#: rows of one gradient evaluation: the float32 gradient over two rows of
#: 2,048 tokens fits on a v5e chip beside the AdamW state of the training cut
CHUNK_ROWS = 2


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


@functools.lru_cache(maxsize=8)
def _accumulate(grad_fn: Callable) -> Callable:
    """Jitted ``(acc, params, tokens, w) -> acc + w * (loss, grads)``, ``acc`` donated."""

    def add(acc, p, tokens, w):
        loss, g = grad_fn(p, tokens)
        return acc[0] + w * loss, jax.tree.map(lambda a, b: a + w * b, acc[1], g)

    return jax.jit(add, donate_argnums=(0,))


def batch_loss_and_grad(grad_fn: Callable, p: Any, tokens: np.ndarray) -> tuple:
    """Mean loss and gradient of ``tokens``' rows, over chunks of at most ``CHUNK_ROWS``."""
    n = len(tokens)
    if n <= CHUNK_ROWS:
        return grad_fn(p, jnp.asarray(tokens))
    add = _accumulate(grad_fn)
    acc = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
    for i in range(0, n, CHUNK_ROWS):
        part = tokens[i:i + CHUNK_ROWS]
        acc = add(acc, p, jnp.asarray(part), len(part) / n)
    return acc


def run(family, cfg: Dict[str, Any], weight_seed: int, batches: List[np.ndarray],
        opt: Dict[str, Any], *, param_dtype: Optional[str] = None, rows: Optional[int] = None,
        other_params: Any = None) -> Dict[str, Any]:
    """Losses of ``len(batches)`` steps, per-leaf norms of the first clipped
    gradient and of the parameters' change after the last update.

    ``family`` is the configuration's module under ``bench/families/``.
    ``other_params`` (a host tree in the same layout) gets its change from
    the same starting weights measured too, as ``other_change_norms``.
    """
    f32cfg = dict(cfg, param_dtype="float32")
    p = _round(family.make_weights(f32cfg, weight_seed), param_dtype)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    grad_fn = family.loss_and_grad(f32cfg)
    update = _adamw(opt, param_dtype)
    losses: List[float] = []
    first: Dict[str, float] = {}
    for step, tokens in enumerate(batches):
        tokens = tokens[:rows] if rows else tokens
        # beside the chunks' accumulator the optimizer's state does not fit
        # on one chip: it waits on the host while the gradient is taken
        park = len(tokens) > CHUNK_ROWS
        if park:
            m, v = jax.device_get((m, v))
        loss, g = batch_loss_and_grad(grad_fn, p, tokens)
        if park:
            m, v = jax.device_put((m, v))
        losses.append(float(loss))
        norms = compare.device_slice_norms(g) if step == 0 else None
        p, m, v, scale = update(p, m, v, g, float(step + 1), lr_at(step, opt))
        del g
        if norms is not None:
            first = {k: x * float(scale) for k, x in norms.items()}
    del m, v
    p0 = family.make_weights(f32cfg, weight_seed)
    out = {"losses": losses, "first_grad_norms": first,
           "change_norms": compare.device_diff_norms(p, p0)}
    del p
    if other_params is not None:
        out["other_change_norms"] = compare.device_diff_norms(jax.device_put(other_params), p0)
    return out
