"""Public kernel ops: jit'd wrappers that dispatch TPU→Pallas, CPU→reference.

Models import ONLY from this module. The dispatch decision is made once per
call site from the default backend (or forced via ``impl=``):

  impl="auto"      : pallas on TPU, blocked-jnp reference elsewhere
  impl="pallas"    : the compiled Pallas (Mosaic) kernel; TPU backend only
  impl="interpret" : the same kernel run by the Pallas interpreter (tests)
  impl="ref"       : force the blocked reference
  impl="dense"     : O(S²) dense oracle (tiny test shapes only)
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import ref as _ref

__all__ = ["flash_attention", "wkv6", "rglru", "ssd", "gated_delta", "default_impl"]

_IMPLS = ("auto", "pallas", "interpret", "ref", "dense")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_impl() -> str:
    return "pallas" if _on_tpu() else "ref"


def _resolve(impl: str) -> str:
    if impl not in _IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; expected one of {_IMPLS}")
    if impl == "auto":
        return default_impl()
    if impl == "pallas" and not _on_tpu():
        raise RuntimeError(
            f"impl='pallas' compiles a Mosaic kernel, which needs the TPU "
            f"backend (default backend: {jax.default_backend()!r}); pass "
            f"impl='interpret' to run the kernel in the Pallas interpreter")
    return impl


def _per_shard(kernel, args, in_dims, out_dims):
    """Call a Mosaic kernel once per shard of the installed mesh.

    XLA cannot partition a Mosaic call, so under a multi-device mesh (see
    ``repro.models.layers.set_mesh_context``) the kernel runs inside
    ``shard_map``. ``kernel`` returns a tuple. ``in_dims`` maps each
    argument, and ``out_dims`` each ``(ndim, dims)`` output, to its batch
    dim ``"b"`` (split over the data axes) and its head/width dim ``"m"``
    (split over the model axis). An axis that does not divide every such
    dim is not split.
    """
    from repro.models.layers import get_mesh_context

    ctx = get_mesh_context()
    if ctx is None or ctx["mesh"].size == 1:
        return kernel(*args)
    mesh = ctx["mesh"]
    axes = {"b": tuple(ctx["dp_axes"]) or None, "m": ctx["model_axis"]}
    for key, ax in list(axes.items()):
        size = math.prod(mesh.shape[a] for a in ((ax,) if isinstance(ax, str) else ax or ()))
        if size == 1 or any(x.shape[d[key]] % size
                            for x, d in zip(args, in_dims, strict=True) if key in d):
            axes[key] = None

    def spec(ndim, dims):
        parts = [None] * ndim
        for key, i in dims.items():
            parts[i] = axes[key]
        return P(*parts)

    in_specs = tuple(spec(x.ndim, d) for x, d in zip(args, in_dims, strict=True))
    out_specs = tuple(spec(n, d) for n, d in out_dims)
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)(*args)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None, impl: str = "auto",
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jnp.ndarray:
    """Causal/local GQA attention. q:(B,Hq,Sq,D) k,v:(B,Hkv,Sk,D) → (B,Hq,Sq,D).

    ``block_q``/``block_k`` None: the kernel chooses its tiles from the shape.
    """
    impl = _resolve(impl)
    if impl == "dense":
        return _ref.flash_attention_dense_ref(q, k, v, causal=causal, window=window,
                                              scale=scale)
    if impl == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                        scale=scale)
    from .flash_attention import flash_attention_pallas

    def kernel(q, k, v):
        return (flash_attention_pallas(q, k, v, causal=causal, window=window, scale=scale,
                                       block_q=block_q, block_k=block_k,
                                       interpret=impl == "interpret"),)

    bh = {"b": 0, "m": 1}
    return _per_shard(kernel, (q, k, v), (bh, bh, bh), ((4, bh),))[0]


# --------------------------------------------------------------------------
# RWKV6 WKV
# --------------------------------------------------------------------------

def wkv6(r, k, v, w, u, *, initial_state=None, chunk: int = 16,
         impl: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Data-dependent-decay linear attention (RWKV6 'Finch').

    r,k,w:(B,H,T,K) v:(B,H,T,V) u:(H,K) → (out (B,H,T,V), state (B,H,K,V)).
    Callers must guarantee log(w) ≥ -4 per step (see ref.wkv6_chunked_ref).
    """
    impl = _resolve(impl)
    if impl == "dense":
        return _ref.wkv6_ref(r, k, v, w, u, initial_state=initial_state)

    # pad T to a chunk multiple: r=k=0, w=1 pads are exact no-ops for both
    # the outputs (discarded) and the carried state.
    T = r.shape[2]
    pad = (-T) % chunk
    if pad:
        padT = lambda x, cval: jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)),
                                       constant_values=cval)
        r, k, v = padT(r, 0), padT(k, 0), padT(v, 0)
        w = padT(w, 1)
    if impl == "ref":
        out, state = _ref.wkv6_chunked_ref(r, k, v, w, u, chunk=chunk,
                                           initial_state=initial_state)
    else:
        from .rwkv6 import wkv6_pallas

        def kernel(r, k, v, w, u, *s0):
            return wkv6_pallas(r, k, v, w, u, initial_state=s0[0] if s0 else None,
                               chunk=chunk, interpret=impl == "interpret")

        s0 = () if initial_state is None else (initial_state,)
        bh = {"b": 0, "m": 1}
        out, state = _per_shard(kernel, (r, k, v, w, u, *s0),
                                (bh, bh, bh, bh, {"m": 0}) + (bh,) * len(s0),
                                ((4, bh), (4, bh)))
    if pad:
        out = out[:, :, :T, :]
    return out, state


# --------------------------------------------------------------------------
# RG-LRU
# --------------------------------------------------------------------------

def rglru(x, a, *, initial_state=None, impl: str = "auto",
          chunk: int = 256, block_w: int = 512) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RG-LRU diagonal recurrence. x,a:(B,T,D) → (h (B,T,D), state (B,D))."""
    impl = _resolve(impl)
    if impl == "dense":
        return _ref.rglru_ref(x, a, initial_state=initial_state)
    if impl == "ref":
        return _ref.rglru_scan_ref(x, a, initial_state=initial_state)
    from .rglru import rglru_pallas

    def kernel(x, a, *h0):
        return rglru_pallas(x, a, initial_state=h0[0] if h0 else None, chunk=chunk,
                            block_w=block_w, interpret=impl == "interpret")

    h0 = () if initial_state is None else (initial_state,)
    btw, bw = {"b": 0, "m": 2}, {"b": 0, "m": 1}
    return _per_shard(kernel, (x, a, *h0), (btw, btw) + (bw,) * len(h0),
                      ((3, btw), (2, bw)))


# --------------------------------------------------------------------------
# Mamba-2 SSD
# --------------------------------------------------------------------------

def ssd(x, dt, A, B, C, *, chunk: int = 256, initial_state=None,
        impl: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mamba-2 selective scan, one B/C group shared by every head.

    x:(Bt,H,T,P) dt:(Bt,H,T) A:(H,) B,C:(Bt,T,N) initial_state:(Bt,H,P,N)
    → (y (Bt,H,T,P), final state (Bt,H,P,N) float32); see ``ref.ssd_ref``.
    """
    impl = _resolve(impl)
    if impl == "dense":
        return _ref.ssd_ref(x, dt, A, B, C, initial_state=initial_state)
    if impl == "ref":
        return _ref.ssd_chunked_ref(x, dt, A, B, C, chunk=chunk,
                                    initial_state=initial_state)
    from .ssd import ssd_pallas

    Bt, H, _, P = x.shape
    h0 = initial_state if initial_state is not None else \
        jnp.zeros((Bt, H, P, B.shape[-1]), jnp.float32)

    def kernel(x, dt, A, B, C, h0):
        return ssd_pallas(x, dt, A, B, C, h0, chunk, impl == "interpret")

    bh, b = {"b": 0, "m": 1}, {"b": 0}
    return _per_shard(kernel, (x, dt, A, B, C, h0), (bh, bh, {"m": 0}, b, b, bh),
                      ((4, bh), (4, bh)))


# --------------------------------------------------------------------------
# gated delta rule (Gated DeltaNet)
# --------------------------------------------------------------------------

def gated_delta(q, k, v, g, beta, *, chunk: int = 64, initial_state=None,
                impl: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Linear attention with a gated delta rule, forward only.

    q,k:(Bt,H,T,K) v:(Bt,H,T,V) g,beta:(Bt,H,T) initial_state:(Bt,H,V,K)
    → (o (Bt,H,T,V), final state (Bt,H,V,K) float32); see
    ``ref.gated_delta_ref``. q and k come normalised (and q scaled).
    """
    impl = _resolve(impl)
    if impl == "dense":
        return _ref.gated_delta_ref(q, k, v, g, beta, initial_state=initial_state)
    if impl == "ref":
        return _ref.gated_delta_chunked_ref(q, k, v, g, beta, chunk=chunk,
                                            initial_state=initial_state)
    from .gated_delta import gated_delta_pallas

    Bt, H, _, K = q.shape
    h0 = initial_state if initial_state is not None else \
        jnp.zeros((Bt, H, v.shape[-1], K), jnp.float32)

    def kernel(q, k, v, g, beta, h0):
        return gated_delta_pallas(q, k, v, g, beta, h0, chunk=chunk,
                                  interpret=impl == "interpret")

    bh = {"b": 0, "m": 1}
    return _per_shard(kernel, (q, k, v, g, beta, h0), (bh,) * 6, ((4, bh), (4, bh)))
