"""A configuration file as the program runs it, and its weights from the seed.

The configuration files use the published ``config.json`` key names. This
module maps them onto the program's ``ModelConfig`` and makes the weights:
random from the seed, on the device in one jitted call, in the parameter
dtype, laid out as the program's tree. The plain reference reads the same
tree (``bench.reference.decoder``); neither side takes weights the other
made.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

#: the program's names for the dtypes a configuration can state
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def program_config(cfg: Dict[str, Any], *, attn_impl: str = "auto"):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    heads = cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"],
        family="dense",
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"] // heads),
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        qkv_bias=bool(cfg.get("use_qkv_bias", cfg.get("attention_bias", False))),
        qk_norm=bool(cfg.get("qk_layernorm", False) or cfg.get("qk_norm", False)),
        rope_theta=float(cfg["rope_theta"]),
        rope_fraction=float(cfg.get("partial_rotary_factor", 1.0)),
        norm=cfg["norm"],
        norm_eps=float(cfg.get("layer_norm_eps", cfg.get("rms_norm_eps", 1e-6))),
        act=cfg["hidden_act"],
        glu=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"],
        remat=cfg.get("remat", "none"),
        z_loss_coef=float(cfg.get("z_loss_coef", 0.0)),
        attn_impl=attn_impl,
    )


def padded_vocab(cfg: Dict[str, Any]) -> int:
    """Rows of the program's embedding table: the vocabulary padded to 512."""
    return ((cfg["vocab_size"] + 511) // 512) * 512


def layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree for a dense decoder, as shapes.

    Layers are stacked along a leading axis in one segment (``seg0/u0``),
    as the program stacks a uniform layer pattern.
    """
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // H)
    ff, V = cfg["intermediate_size"], padded_vocab(cfg)
    layernorm = cfg["norm"] == "layernorm"

    def norm(dim: int) -> Dict[str, Tuple[int, ...]]:
        return {"scale": (dim,), "bias": (dim,)} if layernorm else {"scale": (dim,)}

    attn: Dict[str, Tuple[int, ...]] = {
        "wq": (d, H * hd), "wk": (d, KV * hd), "wv": (d, KV * hd), "wo": (H * hd, d)}
    if cfg.get("use_qkv_bias", cfg.get("attention_bias", False)):
        attn.update(bq=(H * hd,), bk=(KV * hd,), bv=(KV * hd,))
    if cfg.get("qk_layernorm", False) or cfg.get("qk_norm", False):
        attn.update(q_norm=(hd,), k_norm=(hd,))
    layer = {
        "ln1": norm(d),
        "attn": attn,
        "ln2": norm(d),
        "mlp": {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)},
    }
    stacked = jax.tree.map(lambda s: (L,) + s, layer, is_leaf=lambda x: isinstance(x, tuple))
    tree: Dict[str, Any] = {"embed": {"table": (V, d)}, "seg0": {"u0": stacked},
                            "final_norm": norm(d)}
    if not cfg["tie_word_embeddings"]:
        tree["unembed"] = (d, V)
    return tree


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


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights from ``seed``, on the device, in one jitted call."""
    dtype = DTYPES[cfg["param_dtype"]]
    leaves = list(_leaves(layout(cfg)))

    @jax.jit
    def build(key):
        out: Dict[str, Any] = {}
        for i, (path, shape) in enumerate(leaves):
            _set(out, path, _leaf_value(jax.random.fold_in(key, i), path[-1], shape).astype(dtype))
        return out

    return build(jax.random.key(seed))


def check_layout(cfg: Dict[str, Any], program_model) -> None:
    """Raise unless the program's own tree has this layout's shapes."""
    want = layout(cfg)
    got = jax.eval_shape(lambda r: program_model.init(r)[0], jax.random.key(0))
    got_shapes = jax.tree.map(lambda s: tuple(s.shape), got)
    if got_shapes != want:
        raise ValueError(f"program parameter tree differs from the benchmark's layout:\n"
                         f"program {got_shapes}\nbench {want}")


def leaf_paths(tree: Dict[str, Any]):
    """``[(path, leaf)]`` in sorted order, the order both sides compare in."""
    return list(_leaves(tree))
