"""Plain float32 decoder: StableLM 2 and Qwen3 as their model cards define them.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no cache,
no batching tricks. One layer is pre-norm attention then pre-norm SwiGLU MLP,
each added to the residual (StableLM 2: LayerNorm, partial rotary, q/k/v
bias, untied head; Qwen3: RMSNorm, per-head RMSNorm of q and k, GQA, tied
head). Rotary embedding rotates the first ``partial_rotary_factor`` of each
head's dims with the half-split convention of the published code.

It reads the parameter tree that ``layout`` of ``bench/families/dense.py``
describes. The ``weight_dtype`` option rounds every matrix to a lower
precision before use: that is the control, which must come out as not
correct.
"""
from __future__ import annotations

import functools
import json
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("...d,df->...f", x, w.astype(F32), precision=HIGHEST)


def quantize(w: jax.Array, how: Optional[str]) -> jax.Array:
    """``w`` as a lower precision would hold it (identity for ``None``).

    ``int8``: symmetric, one scale per output column. ``float8``: e4m3 with
    one scale per tensor. ``bfloat16``: plain rounding.
    """
    w = w.astype(F32)
    if how is None:
        return w
    if how == "bfloat16":
        return w.astype(jnp.bfloat16).astype(F32)
    if how == "int8":
        s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return jnp.round(w / s).clip(-127, 127) * s
    if how == "float8":
        s = jnp.max(jnp.abs(w)) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown precision {how!r}")


class Decoder:
    """The reference for one configuration file."""

    def __init__(self, cfg: Dict[str, Any], weight_dtype: Optional[str] = None):
        self.cfg = cfg
        self.d = cfg["hidden_size"]
        self.H = cfg["num_attention_heads"]
        self.KV = cfg["num_key_value_heads"]
        self.hd = cfg.get("head_dim", self.d // self.H)
        self.L = cfg["num_hidden_layers"]
        self.V = cfg["vocab_size"]
        self.layernorm = cfg["norm"] == "layernorm"
        self.eps = float(cfg.get("layer_norm_eps", cfg.get("rms_norm_eps", 1e-6)))
        self.theta = float(cfg["rope_theta"])
        self.rot = int(self.hd * float(cfg.get("partial_rotary_factor", 1.0)))
        self.rot -= self.rot % 2
        self.qk_norm = bool(cfg.get("qk_layernorm", False) or cfg.get("qk_norm", False))
        self.tied = bool(cfg["tie_word_embeddings"])
        self.z_coef = float(cfg.get("z_loss_coef", 0.0))
        self.wq = weight_dtype

    # -- pieces ---------------------------------------------------------------
    def norm(self, x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
        x = x.astype(F32)
        if self.layernorm:
            mu = x.mean(-1, keepdims=True)
            var = ((x - mu) ** 2).mean(-1, keepdims=True)
            return (x - mu) / jnp.sqrt(var + self.eps) * p["scale"].astype(F32) \
                + p["bias"].astype(F32)
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + self.eps) * p["scale"].astype(F32)

    def _rms(self, x: jax.Array, scale: jax.Array) -> jax.Array:
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + self.eps) * scale.astype(F32)

    def rope(self, x: jax.Array, pos: jax.Array) -> jax.Array:
        """x: (B, S, heads, hd); pos: (S,)."""
        if self.rot == 0:
            return x
        half = self.rot // 2
        inv = self.theta ** (-jnp.arange(half, dtype=F32) / half)
        ang = pos.astype(F32)[:, None] * inv[None, :]            # (S, half)
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        x1, x2 = x[..., :half], x[..., half:self.rot]
        rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return jnp.concatenate([rotated, x[..., self.rot:]], axis=-1)

    def w(self, a: jax.Array) -> jax.Array:
        return quantize(a, self.wq)

    def attention(self, x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
        B, S, _ = x.shape
        q = _mm(x, self.w(p["wq"]))
        k = _mm(x, self.w(p["wk"]))
        v = _mm(x, self.w(p["wv"]))
        if "bq" in p:
            q, k, v = q + p["bq"].astype(F32), k + p["bk"].astype(F32), v + p["bv"].astype(F32)
        q = q.reshape(B, S, self.H, self.hd)
        k = k.reshape(B, S, self.KV, self.hd)
        v = v.reshape(B, S, self.KV, self.hd)
        if self.qk_norm:
            q, k = self._rms(q, p["q_norm"]), self._rms(k, p["k_norm"])
        pos = jnp.arange(S)
        q, k = self.rope(q, pos), self.rope(k, pos)
        g = self.H // self.KV
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        causal = jnp.tril(jnp.ones((S, S), bool))

        def one_head(qkv):  # one head at a time keeps the (S, S) scores small
            qh, kh, vh = qkv
            s = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=HIGHEST) / jnp.sqrt(F32(self.hd))
            a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", a, vh, precision=HIGHEST)

        heads = jax.lax.map(one_head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
        o = jnp.moveaxis(heads, 0, 2).reshape(B, S, self.H * self.hd)
        return _mm(o, self.w(p["wo"]))

    def mlp(self, x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
        return _mm(jax.nn.silu(_mm(x, self.w(p["w_gate"]))) * _mm(x, self.w(p["w_up"])),
                   self.w(p["w_down"]))

    def layer(self, h: jax.Array, p: Dict[str, Any]) -> jax.Array:
        h = h + self.attention(self.norm(h, p["ln1"]), p["attn"])
        return h + self.mlp(self.norm(h, p["ln2"]), p["mlp"])

    # -- whole model ------------------------------------------------------------
    def hidden(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """Final-normed hidden states (B, S, d) in float32."""
        h = params["embed"]["table"][tokens].astype(F32)
        stacked = params["seg0"]["u0"]
        body = jax.checkpoint(self.layer)
        for i in range(self.L):
            h = body(h, jax.tree.map(lambda a, i=i: a[i], stacked))
        return self.norm(h, params["final_norm"])

    def logits(self, params: Dict[str, Any], h: jax.Array) -> jax.Array:
        if self.tied:
            head = self.w(params["embed"]["table"][: self.V]).T
        else:
            head = self.w(params["unembed"][:, : self.V])
        return jnp.einsum("...d,dv->...v", h, head, precision=HIGHEST)

    def loss(self, params: Dict[str, Any], tokens: jax.Array, chunk: int = 512) -> jax.Array:
        """Mean next-token cross entropy plus ``z_loss_coef`` times mean lse²."""
        h = self.hidden(params, tokens)[:, :-1].reshape(-1, self.d)
        tgt = tokens[:, 1:].reshape(-1)
        n = h.shape[0]
        pad = (-n) % chunk
        h = jnp.pad(h, ((0, pad), (0, 0)))
        tgt = jnp.pad(tgt, (0, pad))
        valid = jnp.arange(n + pad) < n

        @jax.checkpoint
        def part(args):
            hc, tc, vc = args
            lg = self.logits(params, hc)
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
            return jnp.sum(jnp.where(vc, lse - gold, 0.0)), jnp.sum(jnp.where(vc, lse * lse, 0.0))

        ce, z = jax.lax.map(part, (h.reshape(-1, chunk, self.d), tgt.reshape(-1, chunk),
                                   valid.reshape(-1, chunk)))
        return ce.sum() / n + self.z_coef * z.sum() / n


def _key(cfg: Dict[str, Any]) -> str:
    return json.dumps(cfg, sort_keys=True)


@functools.lru_cache(maxsize=8)
def _compiled(cfg_key: str, weight_dtype: Optional[str], what: str) -> Callable:
    dec = Decoder(json.loads(cfg_key), weight_dtype)
    if what == "loss_and_grad":
        return jax.jit(jax.value_and_grad(dec.loss))
    if what == "position_logits":
        def position_logits(params, tokens, positions):
            h = dec.hidden(params, tokens)
            return dec.logits(params, h[0, positions])
        return jax.jit(position_logits)
    raise ValueError(what)


def loss_and_grad(cfg: Dict[str, Any], weight_dtype: Optional[str] = None) -> Callable:
    """Jitted ``(params, tokens) -> (loss, grads)`` in float32."""
    return _compiled(_key(cfg), weight_dtype, "loss_and_grad")


def position_logits(cfg: Dict[str, Any], weight_dtype: Optional[str] = None) -> Callable:
    """Jitted ``(params, tokens (1, S), positions (n,)) -> logits (n, vocab)``."""
    return _compiled(_key(cfg), weight_dtype, "position_logits")
