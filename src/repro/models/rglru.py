"""Griffin / RecurrentGemma recurrent block: conv1d + RG-LRU with gating.

Recurrent block (temporal mixing):
    x → [W_in gate-branch → GeLU] ⊙ [W_in rec-branch → conv1d(w=4) → RG-LRU]
      → W_out
RG-LRU:
    r_t = σ(W_a ξ + b_a);  i_t = σ(W_x ξ + b_x)
    a_t = exp(c · softplus(Λ) · (−r_t))          (a = σ(Λ)^{c·r} in the paper;
                                                  identical parameterization)
    h_t = a_t ⊙ h_{t-1} + sqrt(1−a_t²) ⊙ (i_t ⊙ ξ_t)

Per-layer decode state: (h (B, lru_width) f32, conv tail (B, w−1, lru_width)).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .layers import ParamStore, causal_conv1d, dense, shard_activation

__all__ = ["init_recurrent_block", "recurrent_block", "init_rglru_state"]

_C = 8.0  # Griffin's fixed temperature


def init_recurrent_block(store: ParamStore, name: str, cfg) -> None:
    sub = store.sub(name)
    d, w = cfg.d_model, cfg.lru_width
    sub.param("w_in_rec", (d, w), ("embed", "lru"))
    sub.param("w_in_gate", (d, w), ("embed", "lru"))
    sub.param("conv_w", (cfg.conv1d_width, w), (None, "lru"), scale=0.3)
    sub.param("conv_b", (w,), ("lru",), init="zeros")
    sub.param("lambda_", (w,), ("lru",), init="normal", scale=1.0)
    sub.param("w_a", (w, w), ("lru", "lru"))
    sub.param("b_a", (w,), ("lru",), init="zeros")
    sub.param("w_x", (w, w), ("lru", "lru"))
    sub.param("b_x", (w,), ("lru",), init="zeros")
    sub.param("w_out", (w, d), ("lru", "embed"))


def init_rglru_state(cfg, batch: int, dtype) -> Dict[str, Any]:
    w = cfg.lru_width
    return {"h": jnp.zeros((batch, w), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv1d_width - 1, w), dtype)}


def recurrent_block(x: jax.Array, p: Dict[str, Any], cfg, *,
                    state: Optional[Dict[str, Any]] = None
                    ) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    B, T, d = x.shape
    gate = jax.nn.gelu(dense(x, p["w_in_gate"]))
    xi = dense(x, p["w_in_rec"])
    xi = shard_activation(xi, "lru_bsw")
    tail = state["conv"] if state is not None else None
    xi, new_tail = causal_conv1d(xi, p["conv_w"], p["conv_b"], tail)

    r = jax.nn.sigmoid(dense(xi, p["w_a"], p["b_a"]).astype(jnp.float32))
    i = jax.nn.sigmoid(dense(xi, p["w_x"], p["b_x"]).astype(jnp.float32))
    log_a_base = -_C * jax.nn.softplus(p["lambda_"].astype(jnp.float32))  # (W,)
    a = jnp.exp(log_a_base[None, None, :] * r)        # (B,T,W) in (0,1)
    gated_in = (i * xi.astype(jnp.float32)).astype(x.dtype)

    h0 = state["h"] if state is not None else None
    h, h_last = ops.rglru(gated_in, a.astype(jnp.float32), initial_state=h0,
                          impl=cfg.attn_impl)
    out = dense(h.astype(x.dtype) * gate, p["w_out"])
    new_state = None
    if state is not None:
        new_state = {"h": h_last, "conv": new_tail}
    return out, new_state
