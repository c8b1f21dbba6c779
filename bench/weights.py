"""Random weights laid out as the program's parameter tree, from the seed.

A family describes its tree as nested dicts of shapes (its ``layout``);
this module fills such a tree on the device in one jitted call, each leaf by
its role, and checks that the program's own tree has the same shapes.
Neither the program nor the reference takes weights the other made.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

#: the program's names for the dtypes a configuration can state
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _leaves(tree: Dict[str, Any], path: Tuple[str, ...] = ()):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _leaves(v, path + (k,))
        else:
            yield path + (k,), v


def _set(tree: Dict[str, Any], path: Tuple[str, ...], value: Any) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _leaf_value(key: jax.Array, name: str, shape: Tuple[int, ...]) -> jax.Array:
    """Random f32 values for one leaf, by its role."""
    z = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    if name == "scale" or name.endswith("_norm"):
        return 1.0 + 0.1 * z
    if name == "table" or name == "unembed":
        return 0.02 * z
    if name in ("bias", "bq", "bk", "bv"):
        return 0.02 * z
    fan_in = shape[-2]
    return z / math.sqrt(fan_in)


def random_tree(layout: Dict[str, Any], dtype: str, seed: int) -> Dict[str, Any]:
    """Every leaf of ``layout`` (nested dicts of shapes) from ``seed``, in
    ``dtype``, on the device, in one jitted call. Leaf ``i`` in sorted path
    order draws from ``fold_in(key(seed), i)``."""
    leaves = list(_leaves(layout))

    @jax.jit
    def build(key):
        out: Dict[str, Any] = {}
        for i, (path, shape) in enumerate(leaves):
            value = _leaf_value(jax.random.fold_in(key, i), path[-1], shape)
            _set(out, path, value.astype(DTYPES[dtype]))
        return out

    return build(jax.random.key(seed))


def check_tree(want: Dict[str, Any], program_model) -> None:
    """Raise unless the program's own tree has the shapes of ``want``."""
    got = jax.eval_shape(lambda r: program_model.init(r)[0], jax.random.key(0))
    got_shapes = jax.tree.map(lambda s: tuple(s.shape), got)
    if got_shapes != want:
        raise ValueError(f"program parameter tree differs from the benchmark's layout:\n"
                         f"program {got_shapes}\nbench {want}")
