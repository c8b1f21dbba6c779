"""Shared neural-net substrate: norms, RoPE, GLU MLPs, embeddings, param init.

Params are plain nested dicts. Every leaf is created through ``param()``,
which also records a *logical axis* tuple in a parallel annotation tree —
the sharding rule engine (sharding/specs.py) maps logical axes to mesh axes.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["ParamStore", "rmsnorm", "layernorm", "apply_norm", "norm_param",
           "dense", "rope", "glu_mlp", "init_glu_mlp", "shard_activation",
           "set_activation_sharder", "softcap", "causal_conv1d", "DTYPES"]

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


# --------------------------------------------------------------------------
# param creation with logical-axis annotations
# --------------------------------------------------------------------------

class ParamStore:
    """Collects params + logical-axis annotations during init."""

    def __init__(self, rng: jax.Array, dtype: jnp.dtype):
        self._rng = rng
        self.dtype = dtype
        self.params: Dict[str, Any] = {}
        self.axes: Dict[str, Any] = {}

    def next_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def sub(self, name: str) -> "ParamStore":
        child = ParamStore(self.next_rng(), self.dtype)
        self.params[name] = child.params
        self.axes[name] = child.axes
        return child

    def param(self, name: str, shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
              init: str = "normal", scale: Optional[float] = None) -> jax.Array:
        assert len(shape) == len(axes), (name, shape, axes)
        if init == "zeros":
            val = jnp.zeros(shape, self.dtype)
        elif init == "ones":
            val = jnp.ones(shape, self.dtype)
        elif init == "normal":
            fan_in = shape[0] if len(shape) >= 2 else max(shape[0], 1)
            std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
            val = (jax.random.truncated_normal(self.next_rng(), -2, 2, shape,
                                               jnp.float32) * std).astype(self.dtype)
        elif init == "embed":
            std = scale if scale is not None else 0.02
            val = (jax.random.truncated_normal(self.next_rng(), -2, 2, shape,
                                               jnp.float32) * std).astype(self.dtype)
        else:
            raise ValueError(init)
        self.params[name] = val
        self.axes[name] = axes
        return val


# --------------------------------------------------------------------------
# activation-sharding + mesh hooks (installed by the launcher; no-op otherwise)
# --------------------------------------------------------------------------
_ACT_SHARDER: Optional[Callable[[jax.Array, str], jax.Array]] = None
_MESH_CONTEXT: Optional[Dict[str, Any]] = None  # {"mesh", "dp_axes", "model_axis"}


def set_activation_sharder(fn: Optional[Callable[[jax.Array, str], jax.Array]]) -> None:
    global _ACT_SHARDER
    _ACT_SHARDER = fn


def shard_activation(x: jax.Array, kind: str) -> jax.Array:
    """kind ∈ {tokens_bsd, tokens_bsd_seq, heads_bhsd, logits_bsv, moe_egcd, ...}."""
    if _ACT_SHARDER is None:
        return x
    return _ACT_SHARDER(x, kind)


def set_mesh_context(ctx: Optional[Dict[str, Any]]) -> None:
    """Mesh info for layers that use explicit shard_map collectives (MoE a2a)."""
    global _MESH_CONTEXT
    _MESH_CONTEXT = ctx


def get_mesh_context() -> Optional[Dict[str, Any]]:
    return _MESH_CONTEXT


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def norm_param(store: ParamStore, name: str, dim: int, kind: str) -> None:
    sub = store.sub(name)
    sub.param("scale", (dim,), ("embed",), init="ones")
    if kind == "layernorm":
        sub.param("bias", (dim,), ("embed",), init="zeros")


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32) \
        + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def apply_norm(x: jax.Array, p: Dict[str, jax.Array], kind: str,
               eps: float = 1e-6) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"], eps)
    return layernorm(x, p["scale"], p["bias"], eps)


# --------------------------------------------------------------------------
# dense / matmul with f32 accumulation
# --------------------------------------------------------------------------

def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    # Output in the compute dtype: the MXU accumulates in f32 internally
    # regardless, but keeping the *result* (and therefore any cross-chip
    # TP partial-sum all-reduce GSPMD inserts) in bf16 halves collective
    # bytes — the standard Megatron-style trade. Logit matmuls that need
    # f32 results use explicit einsums in model.py.
    out = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if b is not None:
        out = out + b.astype(out.dtype)
    return out


def causal_conv1d(x: jax.Array, weight: jax.Array, bias: jax.Array,
                  tail: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. x: (B,T,W); weight: (K,W); tail: the last K-1
    inputs before x, zeros when None. Returns (y, new_tail)."""
    B, T, W = x.shape
    K = weight.shape[0]
    if tail is None:
        tail = jnp.zeros((B, K - 1, W), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)            # (B, T+K-1, W)
    y = jnp.zeros((B, T, W), jnp.float32)
    for i in range(K):  # K is tiny (4): unrolled taps, no conv primitive needed
        y = y + xp[:, i: i + T, :].astype(jnp.float32) * weight[i].astype(jnp.float32)
    y = (y + bias.astype(jnp.float32)).astype(x.dtype)
    return y, xp[:, T:, :]


def softcap(logits: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return logits
    return cap * jnp.tanh(logits / cap)


# --------------------------------------------------------------------------
# rotary position embedding (partial fraction + arbitrary positions)
# --------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, *, theta: float = 10_000.0,
         fraction: float = 1.0) -> jax.Array:
    """x: (..., S, D) with positions (..., S) or (S,). Rotates first
    ``fraction·D`` dims (StableLM partial rotary), rest pass through."""
    D = x.shape[-1]
    rot = int(D * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # broadcast cos/sin over any head dims between batch and S
    while cos.ndim < x_rot.ndim:
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1) if rot < D \
        else out.astype(x.dtype)


# --------------------------------------------------------------------------
# (G)LU MLP
# --------------------------------------------------------------------------

def init_glu_mlp(store: ParamStore, name: str, d_model: int, d_ff: int,
                 glu: bool = True) -> None:
    sub = store.sub(name)
    if glu:
        sub.param("w_gate", (d_model, d_ff), ("embed", "mlp"))
    sub.param("w_up", (d_model, d_ff), ("embed", "mlp"))
    sub.param("w_down", (d_ff, d_model), ("mlp", "embed"))


def glu_mlp(x: jax.Array, p: Dict[str, jax.Array], act: str = "silu",
            glu: bool = True) -> jax.Array:
    actf = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[act]
    up = dense(x, p["w_up"])
    h = actf(dense(x, p["w_gate"])) * up if glu else actf(up)
    h = shard_activation(h, "mlp_bsf")
    return dense(h, p["w_down"])
