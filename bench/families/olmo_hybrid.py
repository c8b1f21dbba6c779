"""The Olmo-Hybrid family: Gated DeltaNet layers beside full attention.

``model_type`` ``olmo_hybrid``, as AllenAI's ``config.json`` for
Olmo-Hybrid-7B defines it: ``layer_types`` mixes ``linear_attention`` and
``full_attention`` layers, each followed by a dense SiLU GLU MLP of width
``intermediate_size``. The block is Olmo 2/3's (arXiv:2501.00656): no
pre-norm; an RMSNorm on each sub-layer's output before it is added to the
residual. The full-attention layers are multi-head attention with RMSNorm
over the whole q and k projections and no positional encoding
(``rope_theta`` null); the head is untied.

The linear-attention layer is fla's GatedDeltaNet (Yang, Kautz &
Hatamizadeh, arXiv:2412.06464), with ``linear_num_key_heads`` heads of key
width ``linear_key_head_dim`` and value width ``linear_value_head_dim``:

1. q, k and v are projections of the input, each through a causal
   depthwise conv of width ``linear_conv_kernel_dim`` (no bias), then SiLU;
2. q and k are L2-normalised per head, q is scaled by ``key_dim ** -0.5``;
3. ``β = sigmoid(b_proj x)``, times 2 with ``linear_allow_neg_eigval``
   (Grazzi et al., arXiv:2411.12537); ``g = −exp(A_log) softplus(a_proj x +
   dt_bias)`` and ``α = exp(g)``, per head;
4. per head, with S (value_dim x key_dim) from zero,
   ``S_t = α_t S_{t−1} (I − β_t k_t k_tᵀ) + β_t v_t k_tᵀ`` and
   ``o_t = S_t q_t``;
5. ``o_proj(rmsnorm_per_head(o) * silu(g_proj x))``.

The plain reference below computes step 4 token by token (a ``lax.scan``
over time), not by the chunked algorithm of the program's kernel, so that
the two cannot share an error, and full attention in blocks of queries so
that a 32k prompt fits. It departs from the published description in
nothing but what the cut removes: the layers held by the pipeline's other
stages (``deployment``). Its controls (``position_logits``'s ``control``)
are a precision of the matrices (``float8``, ``int8``, ``bfloat16``), the
recurrent state rounded to bfloat16 after every step (``bf16_state``), and
β without its factor 2 (``beta1``).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from bench import weights
from bench.harness import BenchError
from bench.reference.decoder import quantize

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: rows of queries the reference's attention takes at a time
QUERY_BLOCK = 512
#: rows the reference's position-wise products (the MLP, the gated
#: output) take at a time, so that a 32k prompt's float32 activations fit
ROW_BLOCK = 4096
#: weight precisions a control may take (``quantize``)
WEIGHT_CONTROLS = ("float8", "int8", "bfloat16")
L2_EPS = 1e-6


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------


def kinds(cfg: Dict[str, Any]) -> List[str]:
    """Layer kinds in order, ``linear`` or ``full``."""
    names = {"linear_attention": "linear", "full_attention": "full"}
    kinds = [names.get(k, k) for k in cfg["layer_types"]]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {"linear", "full"}:
        raise ValueError(f"layer_types {cfg['layer_types']} do not describe "
                         f"{cfg['num_hidden_layers']} layers")
    return kinds


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    s = {"d": d, "H": H, "KV": cfg["num_key_value_heads"], "hd": d // H,
         "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
         "lh": cfg["linear_num_key_heads"], "dk": cfg["linear_key_head_dim"],
         "dv": cfg["linear_value_head_dim"], "K": cfg["linear_conv_kernel_dim"]}
    if cfg["linear_num_value_heads"] != s["lh"] or cfg["attention_bias"] \
            or cfg["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("olmo_hybrid: as many value heads as key heads, no attention bias, "
                         "no rotary embedding")
    return s


def program_config(cfg: Dict[str, Any], *, attn_impl: str = "auto"):
    """The program's ``ModelConfig`` for a configuration file.

    Exits the run cleanly (``BenchError``) where the program has no Gated
    DeltaNet layer.
    """
    from repro.configs.base import ModelConfig

    if "gdn_n_heads" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise BenchError("the program has no gated delta rule layer kind")
    s = sizes(cfg)
    return ModelConfig(
        name=cfg["name"],
        family="hybrid",
        num_layers=cfg["num_hidden_layers"],
        d_model=s["d"],
        num_heads=s["H"],
        num_kv_heads=s["KV"],
        head_dim=s["hd"],
        d_ff=s["ff"],
        vocab_size=s["V"],
        block_pattern=tuple("gdn" if k == "linear" else "attn" for k in kinds(cfg)),
        qk_norm_whole=True,
        post_norm=True,
        rope_fraction=0.0,
        norm="rmsnorm",
        norm_eps=float(cfg["rms_norm_eps"]),
        act=cfg["hidden_act"],
        glu=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        gdn_n_heads=s["lh"],
        gdn_key_dim=s["dk"],
        gdn_value_dim=s["dv"],
        gdn_neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
        conv1d_width=s["K"],
        param_dtype=cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"],
        remat=cfg.get("remat", "none"),
        attn_impl=attn_impl,
    )


def padded_vocab(cfg: Dict[str, Any]) -> int:
    """Rows of the program's embedding table: the vocabulary padded to 512."""
    return ((cfg["vocab_size"] + 511) // 512) * 512


def segments(cfg: Dict[str, Any]):
    """How the program stacks the layers: ``[(unit kinds, repeats), ...]``,
    one ``seg<i>`` each, with a ``u<j>`` per position in the unit."""
    from repro.models.transformer import derive_segments

    return derive_segments(tuple(kinds(cfg)))


def layer_layout(cfg: Dict[str, Any], kind: str) -> Dict[str, Any]:
    s = sizes(cfg)
    d, ff, lh, dk, dv = s["d"], s["ff"], s["lh"], s["dk"], s["dv"]
    layer: Dict[str, Any] = {"ln1": {"scale": (d,)}, "ln2": {"scale": (d,)},
                             "mlp": {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}}
    if kind == "linear":
        layer["gdn"] = {"q_proj": (d, lh * dk), "k_proj": (d, lh * dk), "v_proj": (d, lh * dv),
                        "a_proj": (d, lh), "b_proj": (d, lh), "g_proj": (d, lh * dv),
                        "conv_w": (s["K"], 2 * lh * dk + lh * dv), "A_log": (lh,),
                        "dt_bias": (lh,), "norm": {"scale": (dv,)}, "o_proj": (lh * dv, d)}
    else:
        q, kv = s["H"] * s["hd"], s["KV"] * s["hd"]
        layer["attn"] = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
                         "q_norm": (q,), "k_norm": (kv,)}
    return layer


def layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree, as shapes."""
    d = cfg["hidden_size"]
    tree: Dict[str, Any] = {"embed": {"table": (padded_vocab(cfg), d)},
                            "final_norm": {"scale": (d,)}, "unembed": (d, padded_vocab(cfg))}
    for i, (unit, n) in enumerate(segments(cfg)):
        tree[f"seg{i}"] = {f"u{j}": jax.tree.map(lambda t, n=n: (n,) + t,
                                                 layer_layout(cfg, kind),
                                                 is_leaf=lambda x: isinstance(x, tuple))
                           for j, kind in enumerate(unit)}
    return tree


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _gdn_vectors(key: jax.Array, n: int, lh: int) -> Dict[str, jax.Array]:
    """fla's initialisation of A_log and dt_bias, for ``n`` stacked layers."""
    ka, kt = jax.random.split(key)
    dt = jnp.exp(jax.random.uniform(kt, (n, lh), F32, math.log(1e-3), math.log(1e-1)))
    return {"A_log": jnp.log(jax.random.uniform(ka, (n, lh), F32, 1e-4, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}   # softplus(dt_bias) = dt


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights from ``seed``, on the device, in the parameter dtype."""
    lh = sizes(cfg)["lh"]
    tree = weights.random_tree(layout(cfg), cfg["param_dtype"], seed)
    linear = [(f"seg{i}", f"u{j}") for i, (unit, _) in enumerate(segments(cfg))
              for j, kind in enumerate(unit) if kind == "linear"]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def gdn_vectors(tree, key):
        for i, (seg, u) in enumerate(linear):
            g = dict(tree[seg][u]["gdn"])
            new = _gdn_vectors(jax.random.fold_in(key, i), g["A_log"].shape[0], lh)
            g.update({k: v.astype(g[k].dtype) for k, v in new.items()})
            tree[seg] = dict(tree[seg], **{u: dict(tree[seg][u], gdn=g)})
        return tree

    return gdn_vectors(tree, jax.random.fold_in(jax.random.key(seed), 0x0D1))


def check_layout(cfg: Dict[str, Any], program_model) -> None:
    """Raise unless the program's own tree has this layout's shapes."""
    weights.check_tree(layout(cfg), program_model)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("...d,df->...f", x, w.astype(F32), precision=HIGHEST)


def _by_rows(f, *xs: jax.Array) -> jax.Array:
    """``f`` applied to (B, S, ...) inputs position by position, ``ROW_BLOCK``
    rows at a time."""
    B, S = xs[0].shape[:2]
    blk = min(ROW_BLOCK, S)
    n = -(-S // blk)
    pad = lambda x: jnp.pad(x, [(0, 0), (0, n * blk - S)] + [(0, 0)] * (x.ndim - 2))
    split = lambda x: jnp.moveaxis(pad(x).reshape((B, n, blk) + x.shape[2:]), 1, 0)
    out = jax.lax.map(lambda args: f(*args), tuple(split(x) for x in xs))
    return jnp.moveaxis(out, 0, 1).reshape((B, n * blk) + out.shape[3:])[:, :S]


class Olmo:
    """The reference for one configuration file, in float32."""

    def __init__(self, cfg: Dict[str, Any], control: Optional[str] = None):
        if control not in (None, "bf16_state", "beta1") + WEIGHT_CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        self.cfg = cfg
        self.s = sizes(cfg)
        self.eps = float(cfg["rms_norm_eps"])
        self.wq = control if control in WEIGHT_CONTROLS else None
        self.state_bf16 = control == "bf16_state"
        self.beta_scale = 2.0 if cfg["linear_allow_neg_eigval"] and control != "beta1" else 1.0

    def w(self, a: jax.Array) -> jax.Array:
        return quantize(a, self.wq)

    def rms(self, x: jax.Array, scale: jax.Array) -> jax.Array:
        x = x.astype(F32)
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + self.eps) * scale.astype(F32)

    def mlp(self, x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
        return _mm(jax.nn.silu(_mm(x, self.w(p["w_gate"]))) * _mm(x, self.w(p["w_up"])),
                   self.w(p["w_down"]))

    def attention(self, x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
        """Causal multi-head softmax attention with QK-norm over the whole
        projections, no positional encoding; queries in blocks of
        ``QUERY_BLOCK``, one head at a time."""
        B, S, _ = x.shape
        H, KV, hd = self.s["H"], self.s["KV"], self.s["hd"]
        q = self.rms(_mm(x, self.w(p["wq"])), p["q_norm"]).reshape(B, S, H, hd)
        k = self.rms(_mm(x, self.w(p["wk"])), p["k_norm"]).reshape(B, S, KV, hd)
        v = _mm(x, self.w(p["wv"])).reshape(B, S, KV, hd)
        k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
        blk = min(QUERY_BLOCK, S)
        n = -(-S // blk)
        qb = jnp.pad(q, ((0, 0), (0, n * blk - S), (0, 0), (0, 0))).reshape(B, n, blk, H, hd)
        kpos = jnp.arange(S)

        def one_block(args):
            qs, start = args                                          # (B, blk, H, hd)

            def one_head(qkv):
                qh, kh, vh = qkv                                      # (B, blk|S, hd)
                sc = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=HIGHEST) * hd ** -0.5
                causal = kpos[None, :] <= (start + jnp.arange(blk))[:, None]
                a = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
                return jnp.einsum("bqk,bkd->bqd", a, vh, precision=HIGHEST)

            heads = jax.lax.map(one_head, tuple(jnp.moveaxis(t, 2, 0) for t in (qs, k, v)))
            return jnp.moveaxis(heads, 0, 2)                          # (B, blk, H, hd)

        o = jax.lax.map(one_block, (jnp.moveaxis(qb, 1, 0), jnp.arange(n) * blk))
        o = jnp.moveaxis(o, 0, 1).reshape(B, n * blk, H * hd)[:, :S]
        return _mm(o, self.w(p["wo"]))

    def recurrence(self, q, k, v, g, beta) -> jax.Array:
        """o_t = S_t q_t with S_t = α_t S_{t−1} (I − β_t k_t k_tᵀ) + β_t v_t k_tᵀ,
        token by token from S_0 = 0. q, k: (B, S, H, dk); v: (B, S, H, dv);
        g, beta: (B, S, H)."""
        B, _, H, dk = q.shape
        dv = v.shape[-1]

        def step(st, inp):
            qt, kt, vt, gt, bt = inp
            st = jnp.exp(gt)[..., None, None] * st
            st = st - bt[..., None, None] * jnp.einsum("bhvk,bhk,bhj->bhvj", st, kt, kt,
                                                       precision=HIGHEST) \
                + (bt[..., None] * vt)[..., :, None] * kt[..., None, :]
            if self.state_bf16:
                st = st.astype(jnp.bfloat16).astype(F32)
            return st, jnp.einsum("bhvk,bhk->bhv", st, qt, precision=HIGHEST)

        _, os_ = jax.lax.scan(step, jnp.zeros((B, H, dv, dk), F32),
                              tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
        return jnp.moveaxis(os_, 0, 1)

    def gdn(self, x: jax.Array, p: Dict[str, Any]) -> jax.Array:
        B, S, _ = x.shape
        lh, dk, dv, K = self.s["lh"], self.s["dk"], self.s["dv"], self.s["K"]
        qkv = jnp.concatenate([_mm(x, self.w(p["q_proj"])), _mm(x, self.w(p["k_proj"])),
                               _mm(x, self.w(p["v_proj"]))], axis=-1)
        xp = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))            # causal: K-1 zeros before
        qkv = jax.nn.silu(sum(xp[:, i:i + S] * p["conv_w"][i].astype(F32) for i in range(K)))
        l2 = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + L2_EPS)
        q = l2(qkv[..., :lh * dk].reshape(B, S, lh, dk)) * dk ** -0.5
        k = l2(qkv[..., lh * dk:2 * lh * dk].reshape(B, S, lh, dk))
        v = qkv[..., 2 * lh * dk:].reshape(B, S, lh, dv)
        beta = self.beta_scale * jax.nn.sigmoid(_mm(x, self.w(p["b_proj"])))
        g = -jnp.exp(p["A_log"].astype(F32)) \
            * jax.nn.softplus(_mm(x, self.w(p["a_proj"])) + p["dt_bias"].astype(F32))
        o = self.recurrence(q, k, v, g, beta)

        def out(o, x):
            gate = _mm(x, self.w(p["g_proj"])).reshape(o.shape)
            y = self.rms(o, p["norm"]["scale"]) * jax.nn.silu(gate)
            return _mm(y.reshape(y.shape[:2] + (lh * dv,)), self.w(p["o_proj"]))

        return _by_rows(out, o, x)

    def layer(self, h: jax.Array, p: Dict[str, Any], kind: str) -> jax.Array:
        mix = self.gdn(h, p["gdn"]) if kind == "linear" else self.attention(h, p["attn"])
        h = h + self.rms(mix, p["ln1"]["scale"])
        return h + _by_rows(lambda x: self.rms(self.mlp(x, p["mlp"]), p["ln2"]["scale"]), h)

    # -- whole model ------------------------------------------------------------
    def hidden(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """Final-normed hidden states (B, S, d) in float32."""
        h = params["embed"]["table"][tokens].astype(F32)
        for i, (unit, n) in enumerate(segments(self.cfg)):
            for r in range(n):
                for j, kind in enumerate(unit):
                    p = jax.tree.map(lambda a, r=r: a[r], params[f"seg{i}"][f"u{j}"])
                    h = self.layer(h, p, kind)
        return self.rms(h, params["final_norm"]["scale"])

    def logits(self, params: Dict[str, Any], h: jax.Array) -> jax.Array:
        head = self.w(params["unembed"][:, : self.s["V"]])
        return jnp.einsum("...d,dv->...v", h, head, precision=HIGHEST)


@functools.lru_cache(maxsize=8)
def _position_logits(cfg_key: str, control: Optional[str]):
    ref = Olmo(json.loads(cfg_key), control)

    def position_logits(params, tokens, positions):
        return ref.logits(params, ref.hidden(params, tokens)[0, positions])

    return jax.jit(position_logits)


def position_logits(cfg: Dict[str, Any], control: Optional[str] = None):
    """Jitted ``(params, tokens (1, S), positions (n,)) -> logits (n, vocab)``
    of the float32 reference, or of its ``control``."""
    return _position_logits(json.dumps(cfg, sort_keys=True), control)
