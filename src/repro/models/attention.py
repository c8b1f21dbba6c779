"""Attention blocks: GQA (qkv-bias, qk-norm, partial RoPE, local window) + MLA.

Two call modes:
  - full-sequence (train / prefill): uses kernels.ops.flash_attention
  - cached decode (Sq == 1 against a fixed-size cache + running position)

Cache layout (per layer, managed by the caller / scan):
  GQA: {"k": (B, S, Hkv, D), "v": (B, S, Hkv, D), "pos": ()} — pos is GLOBAL.
  MLA: {"ckv": (B, S, kv_lora), "krope": (B, S, rope_dim), "pos": ()}
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .layers import ParamStore, dense, norm_param, apply_norm, rope, rmsnorm, \
    shard_activation

__all__ = ["init_gqa", "gqa_attention", "init_mla", "mla_attention",
           "init_gqa_cache", "init_mla_cache"]


# ==========================================================================
# GQA
# ==========================================================================

def init_gqa(store: ParamStore, name: str, cfg) -> None:
    sub = store.sub(name)
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    sub.param("wq", (d, H * hd), ("embed", "heads"))
    sub.param("wk", (d, KV * hd), ("embed", "kv_heads"))
    sub.param("wv", (d, KV * hd), ("embed", "kv_heads"))
    sub.param("wo", (H * hd, d), ("heads", "embed"))
    if cfg.qkv_bias:
        sub.param("bq", (H * hd,), ("heads",), init="zeros")
        sub.param("bk", (KV * hd,), ("kv_heads",), init="zeros")
        sub.param("bv", (KV * hd,), ("kv_heads",), init="zeros")
    if cfg.qk_norm:
        sub.param("q_norm", (hd,), (None,), init="ones")
        sub.param("k_norm", (hd,), (None,), init="ones")


def init_gqa_cache(cfg, batch: int, seq_len: int, dtype) -> Dict[str, Any]:
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, seq_len, KV, hd), dtype),
            "v": jnp.zeros((batch, seq_len, KV, hd), dtype),
            "pos": jnp.zeros((batch,), jnp.int32)}  # per-sequence positions


def _project_qkv(x, p, cfg, positions):
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(x, p["wq"], p.get("bq"))
    k = dense(x, p["wk"], p.get("bk"))
    v = dense(x, p["wv"], p.get("bv"))
    q = q.reshape(B, -1, H, hd)
    k = k.reshape(B, -1, KV, hd)
    v = v.reshape(B, -1, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    # (B, H, S, D) layout for the kernel; rope over positions
    q = jnp.moveaxis(q, 1, 2)
    k = jnp.moveaxis(k, 1, 2)
    v = jnp.moveaxis(v, 1, 2)
    q = rope(q, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    k = rope(k, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    return q, k, v


def gqa_attention(x: jax.Array, p: Dict[str, Any], cfg, *,
                  positions: jax.Array,
                  cache: Optional[Dict[str, Any]] = None,
                  causal: bool = True,
                  window: Optional[int] = None,
                  cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                  ) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    """Returns (out (B,S,d), updated cache). Modes:
       - cross_kv given: encoder-decoder cross attention (no cache update);
       - cache given:    single-token decode (S == 1);
       - else:           full-sequence self attention."""
    B, S, d = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    scale = cfg.attn_scale or None   # None: the kernel's head_dim ** -0.5

    if cross_kv is not None:
        k, v = cross_kv  # (B, Hkv, Ssrc, hd) — precomputed, already roped/plain
        q = dense(x, p["wq"], p.get("bq")).reshape(B, S, H, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        q = jnp.moveaxis(q, 1, 2)
        out = ops.flash_attention(q, k, v, causal=False, scale=scale, impl=cfg.attn_impl)
        out = jnp.moveaxis(out, 1, 2).reshape(B, S, H * hd)
        return dense(out, p["wo"]), None

    q, k, v = _project_qkv(x, p, cfg, positions)
    q = shard_activation(q, "heads_bhsd")

    if cache is None:
        out = ops.flash_attention(q, k, v, causal=causal, window=window,
                                  scale=scale, impl=cfg.attn_impl)
        out = jnp.moveaxis(out, 1, 2).reshape(B, S, H * hd)
        return dense(out, p["wo"]), None

    # ---- cached decode: S == 1, per-sequence insert at cache["pos"] ----------
    pos = cache["pos"]                 # (B,) — slots may be at different steps
    k_new = jnp.moveaxis(k, 1, 2)      # (B, 1, KV, hd)
    v_new = jnp.moveaxis(v, 1, 2)
    Sc = cache["k"].shape[1]
    if window and window > 0 and Sc == window:
        slot = jnp.mod(pos, window)    # ring buffer for local attention
    else:
        slot = jnp.minimum(pos, Sc - 1)
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, slot].set(
        k_new[:, 0].astype(cache["k"].dtype))
    v_cache = cache["v"].at[bidx, slot].set(
        v_new[:, 0].astype(cache["v"].dtype))
    kq = jnp.moveaxis(k_cache, 1, 2)   # (B, KV, Sc, hd)
    vq = jnp.moveaxis(v_cache, 1, 2)
    kq = shard_activation(kq, "cache_bhsd")
    vq = shard_activation(vq, "cache_bhsd")
    g = H // cfg.num_kv_heads
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(kq, g, axis=1).astype(jnp.float32)
    vf = jnp.repeat(vq, g, axis=1).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * (scale or hd ** -0.5)
    idx = jnp.arange(Sc)
    if window and window > 0 and Sc == window:
        ages = jnp.mod(pos[:, None] - idx[None, :], window)  # (B, Sc)
        valid = ages < jnp.minimum(pos + 1, window)[:, None]
    else:
        valid = idx[None, :] <= pos[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf).astype(x.dtype)
    out = jnp.moveaxis(out, 1, 2).reshape(B, S, H * hd)
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos + 1}
    return dense(out, p["wo"]), new_cache


# ==========================================================================
# MLA — DeepSeek-V3 multi-head latent attention
# ==========================================================================

def init_mla(store: ParamStore, name: str, cfg) -> None:
    sub = store.sub(name)
    d, H = cfg.d_model, cfg.num_heads
    qn, qr, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    # query low-rank path
    sub.param("wq_a", (d, cfg.q_lora_rank), ("embed", "lora"))
    norm_param(sub, "q_norm", cfg.q_lora_rank, "rmsnorm")
    sub.param("wq_b", (cfg.q_lora_rank, H * (qn + qr)), ("lora", "heads"))
    # kv low-rank path: compressed latent + shared rope key
    sub.param("wkv_a", (d, cfg.kv_lora_rank + qr), ("embed", "lora"))
    norm_param(sub, "kv_norm", cfg.kv_lora_rank, "rmsnorm")
    sub.param("wkv_b", (cfg.kv_lora_rank, H * (qn + vh)), ("lora", "heads"))
    sub.param("wo", (H * vh, d), ("heads", "embed"))


def init_mla_cache(cfg, batch: int, seq_len: int, dtype) -> Dict[str, Any]:
    return {"ckv": jnp.zeros((batch, seq_len, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, seq_len, cfg.qk_rope_head_dim), dtype),
            "pos": jnp.zeros((batch,), jnp.int32)}


def _mla_q(x, p, cfg, positions):
    B, S, _ = x.shape
    H = cfg.num_heads
    qn, qr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = apply_norm(dense(x, p["wq_a"]), p["q_norm"], "rmsnorm", cfg.norm_eps)
    q = dense(cq, p["wq_b"]).reshape(B, S, H, qn + qr)
    q = jnp.moveaxis(q, 1, 2)                        # (B,H,S,qn+qr)
    q_nope, q_rope = q[..., :qn], q[..., qn:]
    q_rope = rope(q_rope, positions, theta=cfg.rope_theta)
    return jnp.concatenate([q_nope, q_rope], axis=-1)


def _mla_expand_kv(ckv, krope, p, cfg):
    """latent (B,S,r) + shared rope key (B,S,qr) → per-head K,V (B,H,S,·)."""
    B, S, _ = ckv.shape
    H = cfg.num_heads
    qn, qr, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kv = dense(ckv, p["wkv_b"]).reshape(B, S, H, qn + vh)
    kv = jnp.moveaxis(kv, 1, 2)
    k_nope, v = kv[..., :qn], kv[..., qn:]
    k_rope = jnp.broadcast_to(krope[:, None], (B, H, S, qr))
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    return k, v


def mla_attention(x: jax.Array, p: Dict[str, Any], cfg, *,
                  positions: jax.Array,
                  cache: Optional[Dict[str, Any]] = None,
                  ) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    B, S, d = x.shape
    H = cfg.num_heads
    qn, qr, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scale = (qn + qr) ** -0.5

    q = _mla_q(x, p, cfg, positions)                 # (B,H,S,qn+qr)
    kv_a = dense(x, p["wkv_a"])                       # (B,S,r+qr)
    ckv = apply_norm(kv_a[..., :cfg.kv_lora_rank], p["kv_norm"], "rmsnorm",
                     cfg.norm_eps)
    krope = rope(kv_a[..., cfg.kv_lora_rank:], positions, theta=cfg.rope_theta)

    if cache is None:
        k, v = _mla_expand_kv(ckv, krope, p, cfg)
        out = ops.flash_attention(q, k, v, causal=True, scale=scale,
                                  impl=cfg.attn_impl)
        out = jnp.moveaxis(out, 1, 2).reshape(B, S, H * vh)
        return dense(out, p["wo"]), None

    # cached decode: ABSORBED attention — stay in the compressed latent space
    # (never materialize per-head K/V over the 32k cache):
    #   logits = (q_nope · W_uk) · ckv + q_rope · k_rope
    #   out    = (probs · ckv) · W_uv
    pos = cache["pos"]                         # (B,) per-sequence positions
    Sc = cache["ckv"].shape[1]
    slot = jnp.minimum(pos, Sc - 1)
    bidx = jnp.arange(B)
    ckv_c = cache["ckv"].at[bidx, slot].set(ckv[:, 0].astype(cache["ckv"].dtype))
    krope_c = cache["krope"].at[bidx, slot].set(
        krope[:, 0].astype(cache["krope"].dtype))
    ckv_s = shard_activation(ckv_c, "cache_bsr")
    krope_s = shard_activation(krope_c, "cache_bsr")
    r = cfg.kv_lora_rank
    wkv_b = p["wkv_b"].reshape(r, H, qn + vh)
    w_uk, w_uv = wkv_b[..., :qn], wkv_b[..., qn:]       # (r,H,qn), (r,H,vh)
    q_nope, q_rope = q[..., :qn], q[..., qn:]            # (B,H,1,·)
    q_lat = jnp.einsum("bhqn,rhn->bhqr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))         # (B,H,1,r)
    logits = (jnp.einsum("bhqr,bsr->bhqs", q_lat, ckv_s.astype(jnp.float32))
              + jnp.einsum("bhqe,bse->bhqs", q_rope.astype(jnp.float32),
                           krope_s.astype(jnp.float32))) * scale
    valid = jnp.arange(Sc)[None, :] <= pos[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out_lat = jnp.einsum("bhqs,bsr->bhqr", probs, ckv_s.astype(jnp.float32))
    out = jnp.einsum("bhqr,rhv->bhqv", out_lat,
                     w_uv.astype(jnp.float32)).astype(x.dtype)
    out = jnp.moveaxis(out, 1, 2).reshape(B, S, H * vh)
    return dense(out, p["wo"]), {"ckv": ckv_c, "krope": krope_c, "pos": pos + 1}
