"""The dense decoder family: StableLM 2 and Qwen3, as their model cards define them.

A family is what the benchmark knows of one architecture; the harness finds
it as ``families/<bench_family>.py`` by the configuration file's
``bench_family`` key (``dense`` when the key is absent). It gives

* the program mapping: ``program_config`` (the program's ``ModelConfig``
  for a configuration file), ``make_weights`` (random weights from the
  seed, on the device, in the parameter dtype, laid out as the program's
  tree) and ``check_layout``;
* the plain reference: ``loss_and_grad`` and ``position_logits``
  (``bench.reference.decoder``, which reads the tree ``layout`` describes);
* the counters: ``flops_per_token`` and ``attention_calls``.

The configuration files use the published ``config.json`` key names.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax

from bench import weights
from bench.reference.decoder import loss_and_grad, position_logits  # noqa: F401


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


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights from ``seed``, on the device, in one jitted call."""
    return weights.random_tree(layout(cfg), cfg["param_dtype"], seed)


def check_layout(cfg: Dict[str, Any], program_model) -> None:
    """Raise unless the program's own tree has this layout's shapes."""
    weights.check_tree(layout(cfg), program_model)


def flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Model FLOPs per trained token: 6 x matmul weights + causal attention.

    Recomputation (remat) is not counted. The embedding lookup is not a
    matmul; the output head is.
    """
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // H)
    ff, V = cfg["intermediate_size"], cfg["vocab_size"]
    per_layer = d * (H * hd) * 2 + d * (KV * hd) * 2 + 3 * d * ff
    matmul_params = L * per_layer + d * V
    # causal attention: QK^T and PV, 2 FLOPs per MAC, half the positions
    attn = L * 2 * 2 * H * hd * seq / 2
    return 6.0 * matmul_params + 3.0 * attn


def attention_calls(cfg: Dict[str, Any], rows: int, seq: int) -> Dict[str, Any]:
    """Shapes of one flash-attention call of the step, for the kernel counter."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"batch": rows, "heads": H, "kv_heads": cfg["num_key_value_heads"],
            "q_len": seq, "kv_len": seq, "head_dim": cfg.get("head_dim", d // H),
            "causal": True, "dtype_bytes": 2}
