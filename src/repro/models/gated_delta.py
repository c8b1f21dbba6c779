"""Gated DeltaNet mixer (Yang, Kautz & Hatamizadeh, arXiv:2412.06464; fla's
``GatedDeltaNet`` with its short convolutions and output gate).

    q, k, v = q_proj x, k_proj x, v_proj x
        → causal depthwise conv1d (width conv1d_width, no bias) → SiLU
    q, k L2-normalised per head, q scaled by key_dim ** -0.5
    β = sigmoid(b_proj x), doubled with gdn_neg_eigval (Grazzi et al.,
        arXiv:2411.12537: eigenvalues of I − β k kᵀ in [−1, 1])
    g = −exp(A_log) · softplus(a_proj x + dt_bias);   α = exp(g)   (per head)
    S_t = α_t S_{t−1} (I − β_t k_t k_tᵀ) + β_t v_t k_tᵀ;   o_t = S_t q_t
    out = o_proj(rmsnorm_per_head(o) · silu(g_proj x))

The full-sequence recurrence is ``kernels.ops.gated_delta`` (the chunked
Pallas kernel on a TPU). Per-layer decode state: conv tail (B, width − 1,
2 H K + H V) of q, k and v in the compute dtype, and delta (B, H, V, K)
float32; ``decode_step`` advances it one token by the recurrence itself.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .layers import ParamStore, causal_conv1d, dense, rmsnorm

__all__ = ["init_gdn", "init_gdn_state", "gdn_mixer"]

_L2_EPS = 1e-6  # fla's l2norm: x / sqrt(sum(x²) + eps)
#: steps of one chunk of the kernel: 64 x 64 products, the block inverse's
#: six levels, and a partial last chunk no longer than a short prompt
_CHUNK = 64


def _sizes(cfg) -> Tuple[int, int, int]:
    return cfg.gdn_n_heads, cfg.gdn_key_dim, cfg.gdn_value_dim


def init_gdn(store: ParamStore, name: str, cfg) -> None:
    sub = store.sub(name)
    d = cfg.d_model
    H, K, V = _sizes(cfg)
    sub.param("q_proj", (d, H * K), ("embed", "heads"))
    sub.param("k_proj", (d, H * K), ("embed", "heads"))
    sub.param("v_proj", (d, H * V), ("embed", "heads"))
    sub.param("a_proj", (d, H), ("embed", "ssm_heads"))
    sub.param("b_proj", (d, H), ("embed", "ssm_heads"))
    sub.param("g_proj", (d, H * V), ("embed", "heads"))
    sub.param("conv_w", (cfg.conv1d_width, 2 * H * K + H * V), (None, "ssm_conv"), scale=0.3)
    # fla's initialisation: A uniform in (0, 16), softplus(dt_bias)
    # log-uniform in [1e-3, 1e-1]
    a = jax.random.uniform(sub.next_rng(), (H,), jnp.float32, 1e-4, 16.0)
    sub.param("A_log", (H,), ("ssm_heads",), init="zeros")
    sub.params["A_log"] = jnp.log(a).astype(sub.dtype)
    dt = jnp.exp(jax.random.uniform(sub.next_rng(), (H,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    sub.param("dt_bias", (H,), ("ssm_heads",), init="zeros")
    sub.params["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(sub.dtype)
    sub.sub("norm").param("scale", (V,), (None,), init="ones")
    sub.param("o_proj", (H * V, d), ("heads", "embed"))


def init_gdn_state(cfg, batch: int, dtype) -> Dict[str, Any]:
    H, K, V = _sizes(cfg)
    return {"conv": jnp.zeros((batch, cfg.conv1d_width - 1, 2 * H * K + H * V), dtype),
            "delta": jnp.zeros((batch, H, V, K), jnp.float32)}


def _l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def gdn_mixer(x: jax.Array, p: Dict[str, Any], cfg, *,
              state: Optional[Dict[str, Any]] = None
              ) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    """x: (B, T, d). With ``state``, continues from it and returns the next
    one (T == 1 steps the recurrence; longer T runs the kernel from it)."""
    Bt, T, _ = x.shape
    H, K, V = _sizes(cfg)
    f32 = jnp.float32
    tail = state["conv"] if state is not None else None
    widths = (("q_proj", H * K), ("k_proj", H * K), ("v_proj", H * V))
    parts, tails, lo = [], [], 0
    for name, w in widths:  # one conv each: no (B, T, 2HK + HV) concatenation
        y, t = causal_conv1d(dense(x, p[name]), p["conv_w"][:, lo:lo + w], None,
                             None if tail is None else tail[..., lo:lo + w])
        parts.append(jax.nn.silu(y.astype(f32)))
        tails.append(t)
        lo += w
    q = _l2norm(parts[0].reshape(Bt, T, H, K)) * K ** -0.5
    k = _l2norm(parts[1].reshape(Bt, T, H, K))
    v = parts[2].reshape(Bt, T, H, V)
    # the per-head gates from float32 products (H outputs: no MXU saving)
    beta = jax.nn.sigmoid(jnp.einsum("btd,dh->bth", x, p["b_proj"].astype(x.dtype),
                                     preferred_element_type=f32))
    if cfg.gdn_neg_eigval:
        beta = 2.0 * beta
    a = jnp.einsum("btd,dh->bth", x, p["a_proj"].astype(x.dtype), preferred_element_type=f32)
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(a + p["dt_bias"].astype(f32))
    heads = lambda t: jnp.moveaxis(t, 2, 1)                              # (B,H,T,·)
    s0 = state["delta"] if state is not None else None
    # one token steps the recurrence itself (``ref.gated_delta_ref``)
    impl = "dense" if state is not None and T == 1 else cfg.attn_impl
    o, s = ops.gated_delta(heads(q.astype(x.dtype)), heads(k.astype(x.dtype)),
                           heads(v.astype(x.dtype)), heads(g), heads(beta),
                           chunk=_CHUNK, initial_state=s0, impl=impl)
    o = rmsnorm(jnp.moveaxis(o, 1, 2).astype(f32), p["norm"]["scale"], cfg.norm_eps)
    gate = dense(x, p["g_proj"]).reshape(Bt, T, H, V).astype(f32)
    y = (o * jax.nn.silu(gate)).reshape(Bt, T, H * V).astype(x.dtype)
    out = dense(y, p["o_proj"])
    new_state = None if state is None else \
        {"conv": jnp.concatenate(tails, axis=-1), "delta": s}
    return out, new_state
