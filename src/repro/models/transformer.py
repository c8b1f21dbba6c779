"""Transformer assembly: layer kinds, segment scan, encoder-decoder.

Layer kinds:
  dense : self-attention (GQA or MLA) + GLU MLP
  moe   : self-attention + MoE FFN
  rec   : Griffin recurrent block (conv1d + RG-LRU) + GLU MLP
  attn  : alias of dense used inside hybrid patterns (local window applies)
  rwkv  : RWKV6 time-mix + channel-mix
  mamba2: Mamba-2 mixer (conv1d + SSD scan, gated RMSNorm) + GLU MLP
  enc   : bidirectional encoder self-attention + MLP
  xattn : decoder self-attention + cross-attention + MLP (enc-dec)

The layer stack is compressed into SEGMENTS — (unit kinds, repeats) — and
each segment executes as ONE lax.scan over stacked params, so HLO size and
compile time are O(#distinct units), not O(num_layers). Caches are stacked
along the same leading axis and scanned together with the params.

Modes: "train" (no cache), "prefill" (build cache), "decode" (Sq=1, use
cache). Every apply returns (h, new_cache_or_None, aux_loss).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .attention import (gqa_attention, init_gqa, init_gqa_cache, init_mla,
                        init_mla_cache, mla_attention)
from .layers import (ParamStore, apply_norm, dense, glu_mlp, init_glu_mlp,
                     norm_param, shard_activation)
from .mamba2 import init_mamba2, init_mamba2_state, mamba2_mixer
from .moe import init_moe, moe_block
from .rglru import init_recurrent_block, init_rglru_state, recurrent_block
from .rwkv import (init_rwkv_layer, init_rwkv_state, rwkv_channel_mix,
                   rwkv_time_mix)

__all__ = ["derive_segments", "layer_pattern", "init_layer", "apply_layer",
           "init_layer_cache", "run_stack", "init_stack", "init_stack_cache"]

_UNROLL_MAX = 4  # segments this short run unrolled (exact cost accounting)


# --------------------------------------------------------------------------
# pattern → segments
# --------------------------------------------------------------------------

def layer_pattern(cfg) -> Tuple[str, ...]:
    if cfg.block_pattern:
        return tuple(cfg.block_pattern)
    if cfg.family == "ssm":
        return ("rwkv",) * cfg.num_layers
    if cfg.num_experts:
        return ("dense",) * cfg.first_k_dense + \
               ("moe",) * (cfg.num_layers - cfg.first_k_dense)
    if cfg.is_encdec:
        return ("xattn",) * cfg.num_layers  # decoder layers cross-attend
    return ("dense",) * cfg.num_layers


def derive_segments(pattern: Sequence[str], max_unit: int = 4
                    ) -> List[Tuple[Tuple[str, ...], int]]:
    """Greedy tiling: [(unit_kinds, repeats), ...] covering the pattern."""
    segments: List[Tuple[Tuple[str, ...], int]] = []
    i = 0
    n = len(pattern)
    while i < n:
        best: Tuple[int, int] = (1, 1)  # (unit_len, repeats)
        best_score = 0
        for ul in range(1, min(max_unit, n - i) + 1):
            unit = tuple(pattern[i: i + ul])
            r = 1
            while pattern[i + r * ul: i + (r + 1) * ul] == unit:
                r += 1
            # only true repetition wins coverage — a long non-repeating unit
            # must not swallow a repeatable prefix (e.g. d,d,d,m vs (d)×3)
            score = r * ul if r >= 2 else 1
            if score > best_score or (score == best_score and ul < best[0]):
                best, best_score = (ul, r), score
        ul, r = best
        segments.append((tuple(pattern[i: i + ul]), r))
        i += ul * r
    return segments


# --------------------------------------------------------------------------
# single-layer init / apply / cache
# --------------------------------------------------------------------------

def init_layer(store: ParamStore, cfg, kind: str) -> None:
    if kind == "rwkv":
        norm_param(store, "ln1", cfg.d_model, cfg.norm)
        norm_param(store, "ln2", cfg.d_model, cfg.norm)
        init_rwkv_layer(store, "rwkv", cfg)
        return
    if kind in ("rec", "mamba2"):
        norm_param(store, "ln1", cfg.d_model, cfg.norm)
        if kind == "rec":
            init_recurrent_block(store, "rec", cfg)
        else:
            init_mamba2(store, "mamba", cfg)
        norm_param(store, "ln2", cfg.d_model, cfg.norm)
        init_glu_mlp(store, "mlp", cfg.d_model, cfg.d_ff, cfg.glu)
        return
    # attention-bearing kinds
    norm_param(store, "ln1", cfg.d_model, cfg.norm)
    if cfg.mla:
        init_mla(store, "attn", cfg)
    else:
        init_gqa(store, "attn", cfg)
    if kind == "xattn":
        norm_param(store, "ln_x", cfg.d_model, cfg.norm)
        init_gqa(store, "xattn", cfg)
    norm_param(store, "ln2", cfg.d_model, cfg.norm)
    if kind == "moe":
        init_moe(store, "moe", cfg)
    else:
        init_glu_mlp(store, "mlp", cfg.d_model, cfg.d_ff, cfg.glu)


def init_layer_cache(cfg, kind: str, batch: int, seq_len: int, dtype,
                     src_len: int = 0) -> Any:
    if kind == "rwkv":
        return init_rwkv_state(cfg, batch, dtype)
    if kind == "rec":
        return init_rglru_state(cfg, batch, dtype)
    if kind == "mamba2":
        return init_mamba2_state(cfg, batch, dtype)
    size = min(cfg.window, seq_len) if (cfg.window and kind == "attn") else seq_len
    cache = init_mla_cache(cfg, batch, size, dtype) if cfg.mla \
        else init_gqa_cache(cfg, batch, size, dtype)
    if kind == "xattn":
        KV, hd = cfg.num_kv_heads, cfg.head_dim
        cache = {"self": cache,
                 "cross_k": jnp.zeros((batch, KV, src_len, hd), dtype),
                 "cross_v": jnp.zeros((batch, KV, src_len, hd), dtype)}
    return cache


def _self_attention(h, lp, cfg, kind, *, positions, cache, mode):
    window = cfg.window if (cfg.window and kind == "attn") else None
    causal = kind != "enc"
    if cfg.mla:
        return mla_attention(h, lp["attn"], cfg, positions=positions,
                             cache=cache if mode == "decode" else None)
    out, new_cache = gqa_attention(h, lp["attn"], cfg, positions=positions,
                                   cache=cache if mode == "decode" else None,
                                   causal=causal, window=window)
    return out, new_cache


def _prefill_cache_from_full(h_in, lp, cfg, kind, positions, seq_len):
    """Recompute k/v once more for cache building (prefill mode).

    Cheap relative to the full forward; keeps attention fns single-purpose.
    """
    from .attention import _project_qkv  # reuse projection
    from .layers import rope

    B = h_in.shape[0]
    pos_vec = jnp.full((B,), seq_len, jnp.int32)   # per-sequence positions
    if cfg.mla:
        kv_a = dense(h_in, lp["attn"]["wkv_a"])
        ckv = apply_norm(kv_a[..., :cfg.kv_lora_rank], lp["attn"]["kv_norm"],
                         "rmsnorm", cfg.norm_eps)
        krope = rope(kv_a[..., cfg.kv_lora_rank:], positions, theta=cfg.rope_theta)
        return {"ckv": ckv, "krope": krope, "pos": pos_vec}
    q, k, v = _project_qkv(h_in, lp["attn"], cfg, positions)
    k = jnp.moveaxis(k, 1, 2)  # (B,S,KV,hd)
    v = jnp.moveaxis(v, 1, 2)
    window = cfg.window if (cfg.window and kind == "attn") else 0
    if window and k.shape[1] > window:
        # ring-buffer invariant: slot i holds the kv of global pos ≡ i (mod W)
        k = jnp.roll(k[:, -window:], seq_len % window, axis=1)
        v = jnp.roll(v[:, -window:], seq_len % window, axis=1)
    return {"k": k, "v": v, "pos": pos_vec}


def _residual(h: jax.Array, branch: jax.Array, cfg) -> jax.Array:
    """h + branch, the branch scaled by ``residual_multiplier`` unless it is 1."""
    if cfg.residual_multiplier != 1.0:
        branch = branch * cfg.residual_multiplier
    return h + branch


def apply_layer(h: jax.Array, lp: Dict[str, Any], cfg, kind: str, *,
                positions: jax.Array, mode: str,
                cache: Optional[Any] = None,
                enc_out: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Optional[Any], jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    B = h.shape[0]
    seq_len = h.shape[1]
    if mode == "prefill" and cache is None and kind in ("rwkv", "rec", "mamba2"):
        cache = init_layer_cache(cfg, kind, B, seq_len, h.dtype)

    if kind == "rwkv":
        x1 = apply_norm(h, lp["ln1"], cfg.norm, cfg.norm_eps)
        tm_out, st = rwkv_time_mix(x1, lp["rwkv"], cfg,
                                   state=cache if mode != "train" else None)
        h = _residual(h, tm_out, cfg)
        x2 = apply_norm(h, lp["ln2"], cfg.norm, cfg.norm_eps)
        cm_out, st2 = rwkv_channel_mix(x2, lp["rwkv"], cfg, state=st)
        h = _residual(h, cm_out, cfg)
        if mode == "train":
            return h, None, aux
        if mode == "prefill":
            st2 = dict(st2)
            st2["tm_prev"] = x1[:, -1, :]
            st2["cm_prev"] = x2[:, -1, :]
        return h, st2, aux

    if kind in ("rec", "mamba2"):
        x1 = apply_norm(h, lp["ln1"], cfg.norm, cfg.norm_eps)
        state = cache if mode != "train" else None
        if kind == "rec":
            mix_out, st = recurrent_block(x1, lp["rec"], cfg, state=state)
        else:
            mix_out, st = mamba2_mixer(x1, lp["mamba"], cfg, state=state)
        h = _residual(h, mix_out, cfg)
        x2 = apply_norm(h, lp["ln2"], cfg.norm, cfg.norm_eps)
        h = _residual(h, glu_mlp(x2, lp["mlp"], cfg.act, cfg.glu), cfg)
        if mode == "train":
            return h, None, aux
        if mode == "prefill" and st is None:
            st = init_layer_cache(cfg, kind, B, seq_len, h.dtype)
        return h, st, aux

    # attention-bearing kinds ------------------------------------------------
    x1 = apply_norm(h, lp["ln1"], cfg.norm, cfg.norm_eps)
    attn_out, new_cache = _self_attention(
        x1, lp, cfg, kind, positions=positions,
        cache=(cache["self"] if kind == "xattn" else cache) if cache is not None
        else None,
        mode=mode)
    h = _residual(h, attn_out, cfg)
    if mode == "prefill":
        new_cache = _prefill_cache_from_full(x1, lp, cfg, kind, positions, seq_len)

    if kind == "xattn":
        xx = apply_norm(h, lp["ln_x"], cfg.norm, cfg.norm_eps)
        if mode == "decode":
            ck, cv = cache["cross_k"], cache["cross_v"]
        else:
            # project encoder output with this layer's cross weights
            xp = lp["xattn"]
            Bq, Ssrc, _ = enc_out.shape
            KV, hd = cfg.num_kv_heads, cfg.head_dim
            ck = dense(enc_out, xp["wk"], xp.get("bk")).reshape(Bq, Ssrc, KV, hd)
            cv = dense(enc_out, xp["wv"], xp.get("bv")).reshape(Bq, Ssrc, KV, hd)
            ck, cv = jnp.moveaxis(ck, 1, 2), jnp.moveaxis(cv, 1, 2)
        x_out, _ = gqa_attention(xx, lp["xattn"], cfg, positions=positions,
                                 cross_kv=(ck, cv))
        h = _residual(h, x_out, cfg)
        if mode == "prefill":
            new_cache = {"self": new_cache, "cross_k": ck, "cross_v": cv}
        elif mode == "decode":
            new_cache = {"self": new_cache, "cross_k": ck, "cross_v": cv}

    x2 = apply_norm(h, lp["ln2"], cfg.norm, cfg.norm_eps)
    if kind == "moe":
        ffn_out, aux = moe_block(x2, lp["moe"], cfg)
    else:
        ffn_out = glu_mlp(x2, lp["mlp"], cfg.act, cfg.glu)
    h = _residual(h, ffn_out, cfg)
    h = shard_activation(h, "tokens_bsd")
    return h, (new_cache if mode != "train" else None), aux


# --------------------------------------------------------------------------
# stacked segments: init + scan execution
# --------------------------------------------------------------------------

def init_stack(store: ParamStore, cfg, pattern: Sequence[str], prefix: str = "seg"
               ) -> List[Tuple[Tuple[str, ...], int]]:
    """Init all layers, stacked per segment-unit position. Returns segments."""
    segments = derive_segments(pattern)
    for si, (unit, repeats) in enumerate(segments):
        seg = store.sub(f"{prefix}{si}")
        for uj, kind in enumerate(unit):
            # init `repeats` copies and stack along axis 0
            copies = []
            axes_ref = None
            for _ in range(repeats):
                tmp = ParamStore(seg.next_rng(), seg.dtype)
                init_layer(tmp, cfg, kind)
                copies.append(tmp.params)
                axes_ref = tmp.axes
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *copies)
            seg.params[f"u{uj}"] = stacked
            seg.axes[f"u{uj}"] = jax.tree.map(
                lambda a: ("layers",) + a, axes_ref,
                is_leaf=lambda x: isinstance(x, tuple) and all(
                    isinstance(e, (str, type(None))) for e in x))
    return segments


def init_stack_cache(cfg, segments, batch: int, seq_len: int, dtype,
                     src_len: int = 0, prefix: str = "seg") -> Dict[str, Any]:
    cache: Dict[str, Any] = {}
    for si, (unit, repeats) in enumerate(segments):
        seg_cache = {}
        for uj, kind in enumerate(unit):
            one = init_layer_cache(cfg, kind, batch, seq_len, dtype, src_len)
            seg_cache[f"u{uj}"] = jax.tree.map(
                lambda x, r=repeats: jnp.broadcast_to(x, (r,) + x.shape).copy(),
                one)
        cache[f"{prefix}{si}"] = seg_cache
    return cache


def run_stack(h: jax.Array, params: Dict[str, Any], cfg, segments, *,
              positions: jax.Array, mode: str,
              cache: Optional[Dict[str, Any]] = None,
              enc_out: Optional[jax.Array] = None,
              prefix: str = "seg",
              ) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
    """Run all segments in order. Returns (h, new_cache, total_aux)."""
    total_aux = jnp.zeros((), jnp.float32)
    new_cache: Optional[Dict[str, Any]] = {} if cache is not None or \
        mode == "prefill" else None

    for si, (unit, repeats) in enumerate(segments):
        seg_params = params[f"{prefix}{si}"]
        seg_cache = cache.get(f"{prefix}{si}") if cache is not None else None

        def unit_body(carry, xs, _unit=unit):
            h_c, aux_c = carry
            up, uc = xs
            out_caches = {}
            for uj, kind in enumerate(_unit):
                h_c, c_new, a = apply_layer(
                    h_c, up[f"u{uj}"], cfg, kind, positions=positions,
                    mode=mode, cache=None if uc is None else uc[f"u{uj}"],
                    enc_out=enc_out)
                aux_c = aux_c + a
                if c_new is not None:
                    out_caches[f"u{uj}"] = c_new
            return (h_c, aux_c), (out_caches if out_caches else None)

        body = unit_body
        if mode == "train" and cfg.remat != "none":
            policy = None if cfg.remat == "full" else \
                jax.checkpoint_policies.checkpoint_dots
            body = jax.checkpoint(unit_body, policy=policy,
                                  prevent_cse=False)

        if repeats <= _UNROLL_MAX:
            # unrolled: exact XLA cost accounting (a scanned body is counted
            # once by cost_analysis) — this is what the roofline probes rely on
            outs = []
            for r in range(repeats):
                (h, total_aux), c_out = body(
                    (h, total_aux),
                    (jax.tree.map(lambda x, i=r: x[i], seg_params),
                     None if seg_cache is None else
                     jax.tree.map(lambda x, i=r: x[i], seg_cache)))
                outs.append(c_out)
            if new_cache is not None and outs and outs[0] is not None:
                new_cache[f"{prefix}{si}"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *outs)
        else:
            (h, total_aux), caches_out = jax.lax.scan(
                body, (h, total_aux), (seg_params, seg_cache))
            if new_cache is not None and caches_out is not None:
                new_cache[f"{prefix}{si}"] = caches_out
    return h, new_cache, total_aux
