"""Public model API: build(cfg) → Model with init / loss / prefill / decode.

Batch conventions (all int32 tokens):
  decoder LM       train/prefill: {"tokens": (B, S)}
  vlm (internvl)   {"tokens": (B, S - Nv), "patch_embeds": (B, Nv, fd)}
  audio (seamless) {"frames": (B, Ssrc, fd), "tokens": (B, S)}
  decode (all)     {"token": (B,)} + cache

The loss is next-token CE (f32 logsumexp) + z-loss + MoE aux (+ MTP for
DeepSeek). ``prefill`` returns (last-position logits, cache). ``decode_step``
consumes one token per sequence against the cache.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import DTYPES, ParamStore, apply_norm, dense, norm_param, softcap, \
    shard_activation
from .transformer import (apply_layer, init_layer, init_stack, init_stack_cache,
                          layer_pattern, run_stack)

__all__ = ["Model", "build", "count_params_analytic", "param_count_from_tree"]


@dataclass
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], Tuple[Dict, Dict]]
    loss_fn: Callable[..., Tuple[jax.Array, Dict[str, jax.Array]]]
    prefill: Callable[..., Tuple[jax.Array, Dict]]
    decode_step: Callable[..., Tuple[jax.Array, Dict]]
    init_cache: Callable[..., Dict]
    segments: Any
    enc_segments: Any = None


# --------------------------------------------------------------------------
# builders
# --------------------------------------------------------------------------

def build(cfg: ModelConfig) -> Model:
    pattern = layer_pattern(cfg)
    from .transformer import derive_segments

    segments = derive_segments(pattern)
    enc_segments = derive_segments(("enc",) * cfg.encoder_layers) \
        if cfg.is_encdec else None
    pdtype = DTYPES[cfg.param_dtype]
    cdtype = DTYPES[cfg.compute_dtype]
    # vocab-parallel logits need an evenly shardable vocab: pad the embedding
    # tables to a multiple of 512 (16-way model axis × 32 lanes); pad ids are
    # masked out of every softmax/argmax. <0.1% extra params on all configs.
    vpad = ((cfg.vocab_size + 511) // 512) * 512

    # -- init ----------------------------------------------------------------
    def init(rng: jax.Array) -> Tuple[Dict, Dict]:
        store = ParamStore(rng, pdtype)
        store.sub("embed").param("table", (vpad, cfg.d_model),
                                 ("vocab", "embed"), init="embed")
        init_stack(store, cfg, pattern, prefix="seg")
        norm_param(store, "final_norm", cfg.d_model, cfg.norm)
        if not cfg.tie_embeddings:
            store.param("unembed", (cfg.d_model, vpad),
                        ("embed", "vocab"), scale=0.02)
        if cfg.is_encdec:
            enc = store.sub("encoder")
            enc.param("frontend_proj", (cfg.frontend_dim or cfg.d_model,
                                        cfg.d_model), (None, "embed"))
            init_stack(enc, cfg, ("enc",) * cfg.encoder_layers, prefix="seg")
            norm_param(enc, "final_norm", cfg.d_model, cfg.norm)
        if cfg.frontend == "vision_stub":
            fr = store.sub("frontend")
            fr.param("proj1", (cfg.frontend_dim, cfg.d_model), (None, "embed"))
            fr.param("proj2", (cfg.d_model, cfg.d_model), ("embed", "embed"))
        if cfg.mtp:
            mtp = store.sub("mtp")
            norm_param(mtp, "norm_h", cfg.d_model, cfg.norm)
            norm_param(mtp, "norm_e", cfg.d_model, cfg.norm)
            mtp.param("proj", (2 * cfg.d_model, cfg.d_model), (None, "embed"))
            init_layer(mtp.sub("layer"), cfg,
                       "dense" if not cfg.num_experts else "dense")
        return store.params, store.axes

    # -- embedding helpers -----------------------------------------------------
    def embed_tokens(params, tokens):
        h = params["embed"]["table"][tokens].astype(cdtype)
        if cfg.embedding_multiplier != 1.0:
            h = h * cfg.embedding_multiplier
        return h

    def unembed(params, h):
        h = apply_norm(h, params["final_norm"], cfg.norm, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum("...d,vd->...v", h, params["embed"]["table"],
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("...d,dv->...v", h, params["unembed"],
                                preferred_element_type=jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        logits = softcap(logits, cfg.logit_softcap)
        if vpad != cfg.vocab_size:  # mask pad-vocab slots out of softmax
            logits = jnp.where(jnp.arange(vpad) < cfg.vocab_size, logits,
                               -1e30)
        return logits

    def build_inputs(params, batch):
        """→ (h (B,S,d), positions (S,), enc_out or None, targets/None,
            loss_mask)."""
        enc_out = None
        if cfg.is_encdec:
            ep = params["encoder"]
            src = batch["frames"].astype(cdtype)
            eh = dense(src, ep["frontend_proj"])
            eh = shard_activation(eh, "tokens_bsd")
            pos_e = jnp.arange(src.shape[1])
            eh, _, _ = run_stack(eh, ep, cfg, enc_segments, positions=pos_e,
                                 mode="train", prefix="seg")
            enc_out = apply_norm(eh, ep["final_norm"], cfg.norm, cfg.norm_eps)
        tokens = batch["tokens"]
        h = embed_tokens(params, tokens)
        mask = jnp.ones(tokens.shape, bool)
        if cfg.frontend == "vision_stub":
            fr = params["frontend"]
            vis = batch["patch_embeds"].astype(cdtype)
            vis = dense(jax.nn.gelu(dense(vis, fr["proj1"])), fr["proj2"])
            h = jnp.concatenate([vis, h], axis=1)
            mask = jnp.concatenate(
                [jnp.zeros(vis.shape[:2], bool), mask], axis=1)
        h = shard_activation(h, "tokens_bsd")
        positions = jnp.arange(h.shape[1])
        return h, positions, enc_out, tokens, mask

    # -- loss ------------------------------------------------------------------
    def loss_fn(params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        h, positions, enc_out, tokens, mask = build_inputs(params, batch)
        h, _, aux = run_stack(h, params, cfg, segments, positions=positions,
                              mode="train", enc_out=enc_out, prefix="seg")
        logits = unembed(params, h)                      # (B, St, V) f32
        logits = shard_activation(logits, "logits_bsv")
        # next-token CE on the token (non-frontend) positions
        n_text = tokens.shape[1]
        logits_txt = logits[:, -n_text:, :]
        ce, z = _ce_loss(logits_txt[:, :-1], tokens[:, 1:])
        loss = ce + cfg.z_loss_coef * z + aux
        metrics = {"ce": ce, "z_loss": z, "aux_loss": aux, "loss": loss}
        if cfg.mtp:
            mtp_loss = _mtp_loss(params, h[:, -n_text:, :], tokens)
            loss = loss + cfg.mtp_coef * mtp_loss
            metrics["mtp_loss"] = mtp_loss
            metrics["loss"] = loss
        return loss, metrics

    def _ce_loss(logits, targets):
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        ce = jnp.mean(lse - gold)
        z = jnp.mean(jnp.square(lse))
        return ce, z

    def _mtp_loss(params, h, tokens):
        """DeepSeek-V3 MTP depth-1: predict t+2 from (h_t, emb(t+1))."""
        mp = params["mtp"]
        hh = apply_norm(h[:, :-2, :], mp["norm_h"], cfg.norm, cfg.norm_eps)
        ee = apply_norm(embed_tokens(params, tokens[:, 1:-1]), mp["norm_e"],
                        cfg.norm, cfg.norm_eps)
        x = dense(jnp.concatenate([hh, ee], axis=-1), mp["proj"])
        pos = jnp.arange(x.shape[1])
        x, _, _ = apply_layer(x, mp["layer"], cfg, "dense", positions=pos,
                              mode="train")
        logits = unembed(params, x)
        ce, _ = _ce_loss(logits, tokens[:, 2:])
        return ce

    # -- prefill ------------------------------------------------------------------
    def prefill(params, batch, pad_to: int = 0) -> Tuple[jax.Array, Dict]:
        h, positions, enc_out, tokens, _ = build_inputs(params, batch)
        h, cache, _ = run_stack(h, params, cfg, segments, positions=positions,
                                mode="prefill", enc_out=enc_out, prefix="seg")
        logits = unembed(params, h[:, -1:, :])[:, 0, :cfg.vocab_size]
        if pad_to:
            cache = _pad_cache(cache, pad_to, cfg)
        return logits, cache

    # -- decode -----------------------------------------------------------------
    def init_cache(batch_size: int, seq_len: int, *, src_len: int = 0) -> Dict:
        src = src_len or cfg.source_len_for_decode
        return init_stack_cache(cfg, segments, batch_size, seq_len, cdtype,
                                src_len=src if cfg.is_encdec else 0,
                                prefix="seg")

    def decode_step(params, cache, batch) -> Tuple[jax.Array, Dict]:
        tok = batch["token"]                                # (B,)
        h = embed_tokens(params, tok[:, None])              # (B,1,d)
        pos = _cache_pos(cache, tok.shape[0])               # (B,) per-seq
        positions = pos[:, None]                            # (B,1) for rope
        h, new_cache, _ = run_stack(h, params, cfg, segments,
                                    positions=positions, mode="decode",
                                    cache=cache, prefix="seg")
        logits = unembed(params, h[:, 0, :])[:, :cfg.vocab_size]
        return logits, new_cache

    return Model(cfg=cfg, init=init, loss_fn=loss_fn, prefill=prefill,
                 decode_step=decode_step, init_cache=init_cache,
                 segments=segments, enc_segments=enc_segments)


_PAD_AXIS = {"k": -3, "v": -3, "ckv": -2, "krope": -2}


def _pad_cache(cache, pad_to: int, cfg):
    """Grow a prefill cache to ``pad_to`` slots (decode appends after S).

    Ring (local-window) caches are already complete and are left alone.
    """

    def pad(path, x):
        key = path[-1] if path else ""
        if key not in _PAD_AXIS:
            return x
        ax = _PAD_AXIS[key] % x.ndim
        cur = x.shape[ax]
        if cur >= pad_to or (cfg.window and cur == cfg.window):
            return x
        widths = [(0, 0)] * x.ndim
        widths[ax] = (0, pad_to - cur)
        return jnp.pad(x, widths)

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        return pad(path, tree)

    return walk(cache, ())


def _cache_pos(cache, batch: int) -> jax.Array:
    """Per-sequence decode positions: max over 'pos' leaves → (B,).

    Leaves are (L, B) (stacked per segment); layers advance together so the
    max across layers is exact. RWKV/RG-LRU caches have no pos (O(1) state);
    fall back to zeros — their layers don't use positions."""
    poses = []

    def visit(path, x):
        if path and path[-1] == "pos":
            v = x
            while v.ndim > 1:
                v = v.max(axis=0)
            poses.append(jnp.broadcast_to(v, (batch,)))

    _walk(cache, (), visit)
    if not poses:
        return jnp.zeros((batch,), jnp.int32)
    out = poses[0]
    for p in poses[1:]:
        out = jnp.maximum(out, p)
    return out


def _walk(tree, path, visit):
    if isinstance(tree, dict):
        for k in sorted(tree):
            _walk(tree[k], path + (k,), visit)
    else:
        visit(path, tree)


# --------------------------------------------------------------------------
# analytic parameter counts (roofline 6ND)
# --------------------------------------------------------------------------

def param_count_from_tree(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


@functools.lru_cache(maxsize=64)
def _count_cache(cfg: ModelConfig, active_only: bool) -> int:
    model = build(cfg)
    shapes = jax.eval_shape(lambda r: model.init(r)[0], jax.random.key(0))
    total = 0
    routed = 0

    def visit(path, leaf):
        nonlocal total, routed
        total += leaf.size
        if "experts" in path:
            routed += leaf.size

    _walk_shapes(shapes, (), visit)
    if active_only and cfg.num_experts:
        k = cfg.num_experts_per_tok
        total = total - routed + routed * k // cfg.num_experts
    return int(total)


def _walk_shapes(tree, path, visit):
    if isinstance(tree, dict):
        for k in sorted(tree):
            _walk_shapes(tree[k], path + (k,), visit)
    else:
        visit(path, tree)


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    return _count_cache(cfg, active_only)
