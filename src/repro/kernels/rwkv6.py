"""Pallas TPU kernel for the RWKV6 WKV recurrence (chunked, data-dep decay).

TPU adaptation of the (inherently sequential) WKV scan:
  - grid (B, H, T/chunk); the chunk axis is LAST = sequential ("arbitrary"),
    so the per-(batch, head) state S ∈ R^{K×V} f32 lives in VMEM scratch and
    flows across chunk steps without HBM round trips.
  - inside a chunk the recurrence is re-associated into MXU matmuls
    (the rank-1-factorized chunked form of kernels/ref.wkv6_chunked_ref,
    same f32 range contract: |Σ_chunk log w| ≤ 80 ⇒ chunk=16 with the
    model-side clamp log w ≥ −4).
  - K, V = head_size (64): blocks are (chunk, 64) — the matmuls are small
    but batched across the (B, H) parallel grid dims, which is where v5e's
    8 parallel sublanes earn their keep; the win over a per-step scan is
    ~chunk× fewer sequential dependencies.
  - Mosaic layout rules shape the body: ``u`` arrives as (H, 1, K) so its
    block is a whole (1, K) tile; the in-chunk prefix sum of log w is a
    matmul with a lower-triangular ones matrix (Mosaic has no cumsum); the
    per-key decay column that scales S is a lane reduction of a diagonal
    (no (K,) → (K, 1) relayout).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6_pallas"]

_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, contract):
    """f32 matmul at full precision: exp() of a prefix sum amplifies error."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                 o_ref, sT_ref, S_scr, *, chunk: int, nt: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        S_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)          # (c, K)
    k = k_ref[0, 0].astype(jnp.float32)          # (c, K)
    v = v_ref[0, 0].astype(jnp.float32)          # (c, V)
    w = w_ref[0, 0].astype(jnp.float32)          # (c, K)
    u = u_ref[0].astype(jnp.float32)             # (1, K)
    S = S_scr[...]                                # (K, V)
    K = S.shape[0]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    logw = jnp.log(jnp.maximum(w, 1e-38))
    cum = _mm(jnp.where(col <= row, 1.0, 0.0), logw, ((1,), (0,)))   # (c, K)
    cum_last = cum[chunk - 1:chunk, :]            # (1, K)
    Dt = jnp.exp(cum)
    Dt_prev = jnp.exp(cum - logw)
    r_hat = r * Dt_prev
    k_hat = k / jnp.maximum(Dt, 1e-30)

    cross = _mm(r_hat, S, ((1,), (0,)))                              # (c, V)
    att = _mm(r_hat, k_hat, ((1,), (1,)))                            # (c, c)
    intra = _mm(jnp.where(col < row, att, 0.0), v, ((1,), (0,)))     # (c, V)
    diag = ((r * u) * k).sum(axis=1, keepdims=True) * v
    o_ref[0, 0] = (cross + intra + diag).astype(o_ref.dtype)

    # S ← diag(D_last) S + Σ_t (k_t ⊙ D_last / D_t) v_tᵀ
    eye = (jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (K, K), 1))
    d_col = jnp.where(eye, jnp.exp(cum_last), 0.0).sum(axis=1, keepdims=True)  # (K, 1)
    k_scaled = k * jnp.exp(cum_last - cum)        # (c, K)
    S_new = d_col * S + _mm(k_scaled, v, ((0,), (0,)))                # (K, V)
    S_scr[...] = S_new

    @pl.when(it == nt - 1)
    def _write_state():
        sT_ref[0, 0] = S_new


def wkv6_pallas(r, k, v, w, u, *, initial_state=None, chunk: int = 16,
                interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r,k,w: (B,H,T,K); v: (B,H,T,V); u: (H,K). T % chunk == 0 (ops pads)."""
    B, H, T, K = r.shape
    V = v.shape[-1]
    assert T % chunk == 0, "ops.wkv6 pads T to the chunk size"
    nt = T // chunk
    s0 = (jnp.zeros((B, H, K, V), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    kernel = functools.partial(_wkv6_kernel, chunk=chunk, nt=nt)
    out, sT = pl.pallas_call(
        kernel,
        grid=(B, H, nt),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, chunk, V), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, K), lambda b, h, t: (h, 0, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, V), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, V), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, K), s0)
    return out, sT
