"""The Granite 4.0-H hybrid family: Mamba-2 layers beside GQA attention.

``model_type`` ``granitemoehybrid`` with no experts (``num_local_experts``
0), as IBM's ``config.json`` for granite-4.0-h-micro defines it. Every layer
is pre-norm (RMSNorm): a mixer, either a Mamba-2 layer or a causal GQA
attention layer without positional encoding (``position_embedding_type``
"nope"), then a dense SiLU GLU MLP of width ``shared_intermediate_size``.
Both residual branches of a layer are scaled by ``residual_multiplier``,
the embeddings by ``embedding_multiplier``, the attention scores by
``attention_multiplier`` (in place of 1/sqrt(head_dim)), and the logits of
the tied head are divided by ``logits_scaling``.

The Mamba-2 layer (Dao & Gu, arXiv:2405.21060; ``Mamba2Mixer`` in HF
transformers), with ``d_inner = mamba_n_heads * mamba_d_head`` and one group
of B and C shared by every head:

1. ``in_proj`` maps the input to ``[z (d_inner), xBC (d_inner + 2N), dt (H)]``;
2. ``xBC`` goes through a causal depthwise conv1d of width ``mamba_d_conv``
   with a bias, then SiLU, and splits into ``x`` (H heads x P), ``B`` and
   ``C`` (N each);
3. ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, per head;
4. per head ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`` (P x N) and
   ``y_t = h_t C_t + D x_t``;
5. ``out_proj(rmsnorm(y * silu(z)))``: the gate before the norm, one group
   over the ``d_inner`` channels.

The plain reference below computes step 4 token by token (a ``lax.scan``
over time, rematerialized in blocks), not by the chunked algorithm of the
program's kernel, so that the two cannot share an error. It departs from
the published description in nothing but what the cut removes: the layers
and vocabulary rows held by other chips (``deployment``), so the loss is
over the sliced vocabulary. The loss adds ``z_loss_coef`` times the mean
squared log-partition, as the program's does (``assumed``).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from bench import weights
from bench.harness import BENCH_DIR, BenchError, load_module
from bench.reference.decoder import quantize

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: steps of the reference's recurrence kept between rematerialized blocks:
#: the backward of a block holds this many (B, H, P, N) states
SCAN_BLOCK = 64

_dense = load_module(BENCH_DIR / "families" / "dense.py")
padded_vocab = _dense.padded_vocab


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------


def kinds(cfg: Dict[str, Any]) -> List[str]:
    """Layer kinds in order, ``mamba`` or ``attention``."""
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {kinds} do not describe {cfg['num_hidden_layers']} layers")
    return kinds


def runs(cfg: Dict[str, Any]) -> List[Tuple[str, int]]:
    """Consecutive layers of one kind, as the program stacks them (``seg<k>``)."""
    out: List[Tuple[str, int]] = []
    for k in kinds(cfg):
        if out and out[-1][0] == k:
            out[-1] = (k, out[-1][1] + 1)
        else:
            out.append((k, 1))
    return out


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    s = {"d": d, "H": H, "KV": cfg["num_key_value_heads"], "hd": cfg.get("head_dim", d // H),
         "ff": cfg["shared_intermediate_size"], "V": cfg["vocab_size"],
         "mh": cfg["mamba_n_heads"], "P": cfg["mamba_d_head"], "N": cfg["mamba_d_state"],
         "K": cfg["mamba_d_conv"], "Q": cfg["mamba_chunk_size"]}
    s["di"] = s["mh"] * s["P"]
    if s["di"] != cfg["mamba_expand"] * d or cfg["mamba_n_groups"] != 1:
        raise ValueError("granite_hybrid: d_inner must be mamba_expand * hidden_size, one group")
    if cfg["num_local_experts"] or cfg["mamba_proj_bias"] or cfg["attention_bias"] \
            or not cfg["mamba_conv_bias"]:
        raise ValueError("granite_hybrid: no experts, no projection biases, a conv bias")
    return s


def program_config(cfg: Dict[str, Any], *, attn_impl: str = "auto"):
    """The program's ``ModelConfig`` for a configuration file.

    Exits the run cleanly (``BenchError``) where the program has no Mamba-2
    layer.
    """
    from repro.configs.base import ModelConfig

    if "mamba_n_heads" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise BenchError("the program has no mamba2 layer kind")
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
        block_pattern=tuple("mamba2" if k == "mamba" else "attn" for k in kinds(cfg)),
        rope_fraction=0.0 if cfg["position_embedding_type"] == "nope" else 1.0,
        rope_theta=float(cfg["rope_theta"]),
        norm="rmsnorm",
        norm_eps=float(cfg["rms_norm_eps"]),
        act=cfg["hidden_act"],
        glu=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        attn_scale=float(cfg["attention_multiplier"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        mamba_n_heads=s["mh"],
        mamba_d_head=s["P"],
        mamba_d_state=s["N"],
        mamba_chunk_size=s["Q"],
        conv1d_width=s["K"],
        param_dtype=cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"],
        remat=cfg.get("remat", "none"),
        z_loss_coef=float(cfg.get("z_loss_coef", 0.0)),
        attn_impl=attn_impl,
    )


def layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree, as shapes: one ``seg<k>/u0`` per run
    of layers of one kind, stacked along a leading axis."""
    s = sizes(cfg)
    d, ff, di, N, mh = s["d"], s["ff"], s["di"], s["N"], s["mh"]
    mlp = {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    mamba = {"in_proj": (d, 2 * di + 2 * N + mh), "conv_w": (s["K"], di + 2 * N),
             "conv_b": (di + 2 * N,), "A_log": (mh,), "dt_bias": (mh,), "D": (mh,),
             "norm": {"scale": (di,)}, "out_proj": (di, d)}
    attn = {"wq": (d, s["H"] * s["hd"]), "wk": (d, s["KV"] * s["hd"]),
            "wv": (d, s["KV"] * s["hd"]), "wo": (s["H"] * s["hd"], d)}
    tree: Dict[str, Any] = {"embed": {"table": (padded_vocab(cfg), d)},
                            "final_norm": {"scale": (d,)}}
    for i, (kind, n) in enumerate(runs(cfg)):
        layer = {"ln1": {"scale": (d,)}, "ln2": {"scale": (d,)}, "mlp": mlp}
        layer.update({"mamba": mamba} if kind == "mamba" else {"attn": attn})
        tree[f"seg{i}"] = {"u0": jax.tree.map(lambda t, n=n: (n,) + t, layer,
                                              is_leaf=lambda x: isinstance(x, tuple))}
    return tree


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _mamba_vectors(key: jax.Array, n: int, mh: int, conv: int) -> Dict[str, jax.Array]:
    """Mamba-2's initialisation of the per-head vectors and the conv bias,
    for ``n`` stacked layers (``weights.random_tree`` scales a stacked vector by
    1/sqrt(n), as if ``n`` were a fan-in)."""
    ka, kt, kc = jax.random.split(key, 3)
    dt = jnp.exp(jax.random.uniform(kt, (n, mh), F32, math.log(1e-3), math.log(1e-1)))
    return {"A_log": jnp.log(jax.random.uniform(ka, (n, mh), F32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus(dt_bias) = dt
            "D": jnp.ones((n, mh), F32),
            "conv_b": jax.random.uniform(kc, (n, conv), F32, -0.5, 0.5)}


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights from ``seed``, on the device, in the parameter dtype."""
    s = sizes(cfg)
    tree = weights.random_tree(layout(cfg), cfg["param_dtype"], seed)
    segs = [i for i, (kind, _) in enumerate(runs(cfg)) if kind == "mamba"]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def mamba_vectors(tree, key):
        for i in segs:
            m = dict(tree[f"seg{i}"]["u0"]["mamba"])
            n = m["A_log"].shape[0]
            new = _mamba_vectors(jax.random.fold_in(key, i), n, s["mh"], s["di"] + 2 * s["N"])
            m.update({k: v.astype(m[k].dtype) for k, v in new.items()})
            tree[f"seg{i}"]["u0"] = dict(tree[f"seg{i}"]["u0"], mamba=m)
        return tree

    return mamba_vectors(tree, jax.random.fold_in(jax.random.key(seed), 0x5A5))


def check_layout(cfg: Dict[str, Any], program_model) -> None:
    """Raise unless the program's own tree has this layout's shapes."""
    weights.check_tree(layout(cfg), program_model)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def ssd_calls(cfg: Dict[str, Any], rows: int, seq: int) -> Dict[str, Any]:
    """The shape of one call of the scan kernel in the step (every Mamba-2
    layer's call, and its recompute, has it)."""
    s = sizes(cfg)
    return {"batch": rows, "heads": s["mh"], "seq": seq, "head_dim": s["P"],
            "state": s["N"], "chunk": s["Q"], "dtype_bytes": 2}


def attention_calls(cfg: Dict[str, Any], rows: int, seq: int) -> Dict[str, Any]:
    """Shapes of one flash-attention call of the step, for the kernel counter."""
    s = sizes(cfg)
    return {"batch": rows, "heads": s["H"], "kv_heads": s["KV"], "q_len": seq,
            "kv_len": seq, "head_dim": s["hd"], "causal": True, "dtype_bytes": 2}


def flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Model FLOPs per trained token: 6 x matmul weights, 3 x the causal
    attention of the attention layers and 3 x the scan's forward, by
    ``bench/kernels/ssd.py`` at the published chunk size. Recomputation
    (remat), the conv1d and elementwise work are not counted."""
    s = sizes(cfg)
    d, ff, di, N, mh = s["d"], s["ff"], s["di"], s["N"], s["mh"]
    n_mamba = kinds(cfg).count("mamba")
    n_attn = len(kinds(cfg)) - n_mamba
    mamba = d * (2 * di + 2 * N + mh) + di * d + 3 * d * ff
    attn = d * s["H"] * s["hd"] * 2 + d * s["KV"] * s["hd"] * 2 + 3 * d * ff
    matmul_params = n_mamba * mamba + n_attn * attn + d * s["V"]
    attention = n_attn * 2 * 2 * s["H"] * s["hd"] * seq / 2
    k = load_module(BENCH_DIR / "kernels" / "ssd.py")
    scan = n_mamba * k.flops(ssd_calls(cfg, 1, seq)) / seq
    return 6.0 * matmul_params + 3.0 * attention + 3.0 * scan


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("...d,df->...f", x, w.astype(F32), precision=HIGHEST)


class Hybrid:
    """The reference for one configuration file, in float32."""

    def __init__(self, cfg: Dict[str, Any], weight_dtype: Optional[str] = None):
        self.cfg = cfg
        self.s = sizes(cfg)
        self.runs = runs(cfg)
        self.eps = float(cfg["rms_norm_eps"])
        self.res = float(cfg["residual_multiplier"])
        self.z_coef = float(cfg.get("z_loss_coef", 0.0))
        self.wq = weight_dtype

    def w(self, a: jax.Array) -> jax.Array:
        return quantize(a, self.wq)

    def rms(self, x: jax.Array, scale: jax.Array) -> jax.Array:
        x = x.astype(F32)
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + self.eps) * scale.astype(F32)

    def mlp(self, x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
        return _mm(jax.nn.silu(_mm(x, self.w(p["w_gate"]))) * _mm(x, self.w(p["w_up"])),
                   self.w(p["w_down"]))

    def attention(self, x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
        """Causal GQA softmax attention, no positional encoding."""
        B, S, _ = x.shape
        H, KV, hd = self.s["H"], self.s["KV"], self.s["hd"]
        q = _mm(x, self.w(p["wq"])).reshape(B, S, H, hd)
        k = jnp.repeat(_mm(x, self.w(p["wk"])).reshape(B, S, KV, hd), H // KV, axis=2)
        v = jnp.repeat(_mm(x, self.w(p["wv"])).reshape(B, S, KV, hd), H // KV, axis=2)
        causal = jnp.tril(jnp.ones((S, S), bool))
        scale = float(self.cfg["attention_multiplier"])

        def one_head(qkv):  # one head at a time keeps the (S, S) scores small
            qh, kh, vh = qkv
            s = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=HIGHEST) * scale
            a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", a, vh, precision=HIGHEST)

        heads = jax.lax.map(one_head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
        return _mm(jnp.moveaxis(heads, 0, 2).reshape(B, S, H * hd), self.w(p["wo"]))

    def recurrence(self, x, dt, A, Bm, Cm) -> jax.Array:
        """y_t = h_t C_t with h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,
        token by token from h_0 = 0. x: (B, S, H, P); dt: (B, S, H);
        Bm, Cm: (B, S, N). Blocks of ``SCAN_BLOCK`` steps are rematerialized."""
        Bt, S, H, P = x.shape
        N = Bm.shape[-1]
        blk = min(SCAN_BLOCK, S)
        pad = (-S) % blk
        seq = [jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in (x, dt, Bm, Cm)]

        def step(h, inp):
            xt, dtt, bt, ct = inp
            h = jnp.exp(dtt * A)[..., None, None] * h \
                + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
            return h, jnp.einsum("bhpn,bn->bhp", h, ct, precision=HIGHEST)

        @jax.checkpoint
        def block(h, inp):
            return jax.lax.scan(step, h, inp)

        n = (S + pad) // blk
        blocks = tuple(jnp.moveaxis(a.reshape((Bt, n, blk) + a.shape[2:]), (1, 2), (0, 1))
                       for a in seq)
        _, ys = jax.lax.scan(block, jnp.zeros((Bt, H, P, N), F32), blocks)
        return jnp.moveaxis(ys.reshape((n * blk, Bt, H, P)), 0, 1)[:, :S]

    def mamba(self, x: jax.Array, p: Dict[str, Any]) -> jax.Array:
        B, S, _ = x.shape
        mh, P, N, K, di = self.s["mh"], self.s["P"], self.s["N"], self.s["K"], self.s["di"]
        proj = _mm(x, self.w(p["in_proj"]))
        z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * N], proj[..., 2 * di + 2 * N:]
        xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))            # causal: K-1 zeros before
        conv = sum(xp[:, i:i + S] * p["conv_w"][i].astype(F32) for i in range(K))
        xbc = jax.nn.silu(conv + p["conv_b"].astype(F32))
        xs, Bm, Cm = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
        A = -jnp.exp(p["A_log"].astype(F32))
        xh = xs.reshape(B, S, mh, P)
        y = self.recurrence(xh, dt, A, Bm, Cm) + p["D"].astype(F32)[:, None] * xh
        y = y.reshape(B, S, di) * jax.nn.silu(z)
        return _mm(self.rms(y, p["norm"]["scale"]), self.w(p["out_proj"]))

    def layer(self, h: jax.Array, p: Dict[str, Any], kind: str) -> jax.Array:
        # the mixer and the MLP are rematerialized apart, so that the
        # backward of one never holds the other's float32 activations
        if kind == "mamba":
            mixer = jax.checkpoint(lambda x, q: self.mamba(x, q["mamba"]))
        else:
            mixer = jax.checkpoint(lambda x, q: self.attention(x, q["attn"]))
        h = h + self.res * mixer(self.rms(h, p["ln1"]["scale"]), p)
        return h + self.res * jax.checkpoint(self.mlp)(self.rms(h, p["ln2"]["scale"]), p["mlp"])

    # -- whole model ------------------------------------------------------------
    def hidden(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """Final-normed hidden states (B, S, d) in float32."""
        h = params["embed"]["table"][tokens].astype(F32) * float(self.cfg["embedding_multiplier"])
        for i, (kind, _) in enumerate(self.runs):
            # a scan takes each layer's weights from the stack inside the
            # rematerialized body, so no slice of them outlives its layer
            body = jax.checkpoint(functools.partial(self.layer, kind=kind))
            h, _ = jax.lax.scan(lambda h, p, body=body: (body(h, p), None), h,
                                params[f"seg{i}"]["u0"])
        return self.rms(h, params["final_norm"]["scale"])

    def logits(self, params: Dict[str, Any], h: jax.Array) -> jax.Array:
        head = self.w(params["embed"]["table"][: self.s["V"]]).T
        return jnp.einsum("...d,dv->...v", h, head, precision=HIGHEST) \
            / float(self.cfg["logits_scaling"])

    def loss(self, params: Dict[str, Any], tokens: jax.Array, chunk: int = 512) -> jax.Array:
        """Mean next-token cross entropy plus ``z_loss_coef`` times mean lse²."""
        d = self.s["d"]
        h = self.hidden(params, tokens)[:, :-1].reshape(-1, d)
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

        ce, z = jax.lax.map(part, (h.reshape(-1, chunk, d), tgt.reshape(-1, chunk),
                                   valid.reshape(-1, chunk)))
        return ce.sum() / n + self.z_coef * z.sum() / n


@functools.lru_cache(maxsize=8)
def _loss_and_grad(cfg_key: str, weight_dtype: Optional[str]) -> Callable:
    return jax.jit(jax.value_and_grad(Hybrid(json.loads(cfg_key), weight_dtype).loss))


def loss_and_grad(cfg: Dict[str, Any], weight_dtype: Optional[str] = None) -> Callable:
    """Jitted ``(params, tokens) -> (loss, grads)`` in float32 (training cells
    only: the family has no serving cell, so no ``position_logits``)."""
    return _loss_and_grad(json.dumps(cfg, sort_keys=True), weight_dtype)
