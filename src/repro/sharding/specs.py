"""Sharding rule engine: logical axes → PartitionSpec over the production mesh.

Parameters carry logical-axis tuples (see models/layers.ParamStore). The rule
table maps logical axes to mesh axes; spec construction resolves conflicts
positionally (first dimension wins a mesh axis; later dims fall back to
replication) — this is what makes e.g. expert tensors (experts, embed,
moe_mlp) come out as (model, fsdp, None) without per-tensor special cases.

Activation constraint kinds (shard_activation call sites in models/):
  tokens_bsd   (B,S,d)        batch→dp [, seq→model when seq_parallel]
  heads_bhsd   (B,H,S,hd)     batch→dp, heads→model
  mlp_bsf      (B,S,ff)       batch→dp, ff→model
  logits_bsv   (B,S,V)        batch→dp, vocab→model
  cache_bhsd   (B,KV,S,hd)    batch→dp, KV→model if divisible else S→model
  cache_bsr    (B,S,r)        batch→dp, seq→model (MLA latent)
  moe_ecd/ecf  (E,T,d/f)      experts→model, tokens→dp
  lru_bsw      (B,S,W)        batch→dp, width→model
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingOptions", "ShardingRules"]

Axis = Union[str, Tuple[str, ...], None]


@dataclass(frozen=True)
class ShardingOptions:
    """Per-run distribution knobs (hillclimb levers)."""

    fsdp: bool = True              # shard params over dp axes (ZeRO-3)
    seq_parallel: bool = False     # shard activations' seq dim on model axis
    cache_seq_shard: str = "auto"  # auto | heads | seq — decode cache layout
    expert_parallel: bool = True   # experts on model axis (else fsdp-only)
    logical_overrides: Tuple[Tuple[str, Any], ...] = ()


class ShardingRules:
    def __init__(self, cfg, mesh: Mesh, options: "ShardingOptions | None" = None):
        self.cfg = cfg
        self.mesh = mesh
        self.opt = options if options is not None else ShardingOptions()
        names = mesh.axis_names
        self.dp_axes: Tuple[str, ...] = tuple(a for a in ("pod", "data")
                                              if a in names)
        self.model_axis = "model" if "model" in names else None
        self.model_size = mesh.shape["model"] if self.model_axis else 1
        dp: Axis = self.dp_axes if len(self.dp_axes) > 1 else \
            (self.dp_axes[0] if self.dp_axes else None)
        fsdp_axis: Axis = dp if self.opt.fsdp else None
        self.table: Dict[str, Axis] = {
            "layers": None,
            "vocab": self.model_axis,
            "embed": fsdp_axis,
            "heads": self.model_axis,
            "kv_heads": self.model_axis,
            "mlp": self.model_axis,
            "moe_mlp": self.model_axis,
            "experts": self.model_axis if self.opt.expert_parallel else fsdp_axis,
            "lru": self.model_axis,
            "lora": None,
            # Mamba-2: heads and the inner channels split on the model axis;
            # the fused in_proj output and the conv channels (x, B, C) whole
            "ssm_heads": self.model_axis,
            "ssm_inner": self.model_axis,
            "ssm_in": None,
            "ssm_conv": None,
        }
        for k, v in self.opt.logical_overrides:
            self.table[k] = v
        self.dp: Axis = dp

        kv = max(cfg.num_kv_heads, 1)
        if self.opt.cache_seq_shard == "heads":
            self.cache_on_heads = True
        elif self.opt.cache_seq_shard == "seq":
            self.cache_on_heads = False
        else:
            self.cache_on_heads = (kv % max(self.model_size, 1) == 0
                                   and not cfg.mla)

    # -- divisibility sanitizer -------------------------------------------------
    def _axis_size(self, axis: Axis) -> int:
        if axis is None:
            return 1
        if isinstance(axis, str):
            return int(self.mesh.shape[axis])
        out = 1
        for a in axis:
            out *= int(self.mesh.shape[a])
        return out

    def sanitize(self, spec: P, shape: Tuple[int, ...]) -> P:
        """Drop mesh axes that do not divide the dimension (pjit requires
        even tiling for input shardings). Partial drops keep the divisible
        prefix of a composite axis tuple."""
        out = []
        padded = tuple(spec) + (None,) * (len(shape) - len(spec))
        for dim, ax in zip(shape, padded, strict=True):
            if ax is None:
                out.append(None)
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            kept = []
            size = 1
            for a in axes:
                nxt = size * int(self.mesh.shape[a])
                if dim % nxt == 0:
                    kept.append(a)
                    size = nxt
            out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
        return P(*out)

    # -- params ---------------------------------------------------------------
    def param_spec(self, axes: Tuple[Optional[str], ...],
                   shape: Optional[Tuple[int, ...]] = None) -> P:
        used: set = set()
        out = []
        for ax in axes:
            mapped = self.table.get(ax) if ax is not None else None
            flat = (mapped,) if isinstance(mapped, str) else (mapped or ())
            flat = tuple(a for a in flat if a is not None and a not in used)
            if flat:
                used.update(flat)
                out.append(flat if len(flat) > 1 else flat[0])
            else:
                out.append(None)
        spec = P(*out)
        if shape is not None:
            spec = self.sanitize(spec, shape)
        return spec

    def param_sharding_tree(self, axes_tree, shapes_tree=None):
        is_axes = lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)
        if shapes_tree is None:
            return jax.tree.map(
                lambda a: NamedSharding(self.mesh, self.param_spec(a)),
                axes_tree, is_leaf=is_axes)
        return jax.tree.map(
            lambda a, s: NamedSharding(self.mesh,
                                       self.param_spec(a, tuple(s.shape))),
            axes_tree, shapes_tree, is_leaf=is_axes)

    # -- activations -------------------------------------------------------------
    def activation_spec(self, kind: str) -> P:
        dp, m = self.dp, self.model_axis
        if kind == "tokens_bsd":
            return P(dp, m if self.opt.seq_parallel else None, None)
        if kind == "heads_bhsd":
            return P(dp, m, None, None)
        if kind == "mlp_bsf":
            return P(dp, None, m)
        if kind == "logits_bsv":
            return P(dp, None, m)
        if kind == "cache_bhsd":
            return P(dp, m, None, None) if self.cache_on_heads \
                else P(dp, None, m, None)
        if kind == "cache_bsr":
            return P(dp, m, None)
        if kind in ("moe_ecd", "moe_ecf"):
            return P(m, dp, None)
        if kind == "lru_bsw":
            return P(dp, None, m)
        return P()

    def install(self) -> None:
        """Install the activation-constraint hook used inside model code."""
        from repro.models.layers import set_activation_sharder, set_mesh_context

        set_mesh_context({"mesh": self.mesh, "dp_axes": self.dp_axes,
                          "model_axis": self.model_axis})

        def sharder(x, kind):
            spec = self.activation_spec(kind)
            if len(spec) != x.ndim:
                return x
            spec = self.sanitize(spec, tuple(x.shape))
            if all(s is None for s in spec):
                return x
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec))

        set_activation_sharder(sharder)

    def uninstall(self) -> None:
        from repro.models.layers import set_activation_sharder, set_mesh_context

        set_activation_sharder(None)
        set_mesh_context(None)

    def __enter__(self) -> "ShardingRules":
        self.install()
        return self

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # -- batch / cache ------------------------------------------------------------
    def batch_spec(self, batch_tree) -> Any:
        def spec(x):
            nd = len(x.shape)
            p = self.sanitize(P(self.dp, *([None] * (nd - 1))), tuple(x.shape))
            return NamedSharding(self.mesh, p)

        return jax.tree.map(spec, batch_tree)

    def cache_sharding_tree(self, cache_tree) -> Any:
        """Cache leaves are keyed dicts; leading axis is the stacked-layers dim."""
        m = self.model_axis

        def walk(tree, path):
            if isinstance(tree, dict):
                return {k: walk(v, path + (k,)) for k, v in tree.items()}
            key = path[-1] if path else ""
            nd = len(tree.shape)
            if key in ("k", "v"):          # (L, B, S, KV, hd)
                if self.cache_on_heads:
                    spec = P(None, self.dp, None, m, None)
                else:
                    spec = P(None, self.dp, m, None, None)
            elif key in ("ckv", "krope"):  # (L, B, S, r)
                spec = P(None, self.dp, m, None)
            elif key in ("cross_k", "cross_v"):  # (L, B, KV, Ssrc, hd)
                spec = P(None, self.dp, m if self.cache_on_heads else None,
                         None, None)
            elif key in ("wkv", "ssm"):    # (L, B, H, K, V) / (L, B, H, P, N)
                spec = P(None, self.dp, m, None, None)
            elif key in ("h",):            # (L, B, W)
                spec = P(None, self.dp, m)
            elif key in ("conv",):         # (L, B, w-1, W)
                spec = P(None, self.dp, None, m)
            elif key in ("tm_prev", "cm_prev"):  # (L, B, d)
                spec = P(None, self.dp, None)
            else:                           # pos scalars etc.
                spec = P(*([None] * nd))
            if len(spec) != nd:
                spec = P(*([None] * nd))
            spec = self.sanitize(spec, tuple(tree.shape))
            return NamedSharding(self.mesh, spec)

        return walk(cache_tree, ())

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())
