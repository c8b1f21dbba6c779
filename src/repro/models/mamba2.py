"""Mamba-2 mixer (Dao & Gu, arXiv:2405.21060; ``Mamba2Mixer`` in HF transformers).

    in_proj: x → [z (d_inner), xBC (d_inner + 2N), dt (H)]
    xBC → causal depthwise conv1d (width conv1d_width, bias) → SiLU
        → x (H heads × P), B (N), C (N)       (one group shared by all heads)
    dt = softplus(dt + dt_bias);  A = −exp(A_log)            (per head)
    h_t = exp(dt_t A) h_{t−1} + dt_t x_t ⊗ B_t;  y_t = h_t C_t + D x_t
    out = out_proj(rmsnorm(y ⊙ silu(z)))   (gate before the norm, one group)

The full-sequence scan is ``kernels.ops.ssd`` (the chunked SSD kernel on a
TPU). Per-layer decode state: conv tail (B, width − 1, d_inner + 2N) in
the compute dtype and ssm (B, H, P, N) float32; ``decode_step`` advances it
one token by the recurrence itself.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .layers import ParamStore, causal_conv1d, dense, rmsnorm

__all__ = ["init_mamba2", "init_mamba2_state", "mamba2_mixer"]


def _sizes(cfg) -> Tuple[int, int, int, int]:
    return cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, \
        cfg.mamba_n_heads * cfg.mamba_d_head


def init_mamba2(store: ParamStore, name: str, cfg) -> None:
    sub = store.sub(name)
    H, P, N, di = _sizes(cfg)
    conv_dim = di + 2 * N
    sub.param("in_proj", (cfg.d_model, 2 * di + 2 * N + H), ("embed", "ssm_in"))
    sub.param("conv_w", (cfg.conv1d_width, conv_dim), (None, "ssm_conv"), scale=0.3)
    sub.param("conv_b", (conv_dim,), ("ssm_conv",), init="zeros")
    # Mamba-2's initialisation: A uniform in [1, 16], softplus(dt_bias)
    # log-uniform in [1e-3, 1e-1], D = 1
    a = jax.random.uniform(sub.next_rng(), (H,), jnp.float32, 1.0, 16.0)
    sub.param("A_log", (H,), ("ssm_heads",), init="zeros")
    sub.params["A_log"] = jnp.log(a).astype(sub.dtype)
    dt = jnp.exp(jax.random.uniform(sub.next_rng(), (H,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    sub.param("dt_bias", (H,), ("ssm_heads",), init="zeros")
    sub.params["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(sub.dtype)
    sub.param("D", (H,), ("ssm_heads",), init="ones")
    sub.sub("norm").param("scale", (di,), ("ssm_inner",), init="ones")
    sub.param("out_proj", (di, cfg.d_model), ("ssm_inner", "embed"))


def init_mamba2_state(cfg, batch: int, dtype) -> Dict[str, Any]:
    H, P, N, di = _sizes(cfg)
    return {"conv": jnp.zeros((batch, cfg.conv1d_width - 1, di + 2 * N), dtype),
            "ssm": jnp.zeros((batch, H, P, N), jnp.float32)}


def mamba2_mixer(x: jax.Array, p: Dict[str, Any], cfg, *,
                 state: Optional[Dict[str, Any]] = None
                 ) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    """x: (B, T, d). With ``state``, continues from it and returns the next
    one (T == 1 steps the recurrence; longer T runs the scan from it)."""
    Bt, T, _ = x.shape
    H, P, N, di = _sizes(cfg)
    zxbcdt = dense(x, p["in_proj"])
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * N], zxbcdt[..., 2 * di + 2 * N:]
    tail = state["conv"] if state is not None else None
    xbc, new_tail = causal_conv1d(xbc, p["conv_w"], p["conv_b"], tail)
    xbc = jax.nn.silu(xbc)
    xs, b, c = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # (B,T,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xs.reshape(Bt, T, H, P)
    h0 = state["ssm"] if state is not None else None
    # one token steps the recurrence itself (``ref.ssd_ref``): no chunk to pad
    impl = "dense" if state is not None and T == 1 else cfg.attn_impl
    y, h = ops.ssd(jnp.moveaxis(xh, 2, 1), jnp.moveaxis(dt, 2, 1), A, b, c,
                   chunk=cfg.mamba_chunk_size, initial_state=h0, impl=impl)
    y = jnp.moveaxis(y, 1, 2).astype(jnp.float32)                        # (B,T,H,P)
    y = y + p["D"].astype(jnp.float32)[:, None] * xh.astype(jnp.float32)
    y = y.reshape(Bt, T, di) * jax.nn.silu(z.astype(jnp.float32))
    y = rmsnorm(y, p["norm"]["scale"], cfg.norm_eps).astype(x.dtype)
    out = dense(y, p["out_proj"])
    new_state = {"conv": new_tail, "ssm": h} if state is not None else None
    return out, new_state
