"""Pallas TPU kernel for the gated delta rule (Gated DeltaNet), forward only.

Per head, with state S ∈ R^{V×K} and α_t = exp(g_t):
    S_t = α_t S_{t−1} (I − β_t k_t k_tᵀ) + β_t v_t k_tᵀ;   o_t = S_t q_t
(``ref.gated_delta_ref``). The kernel computes the chunked WY form
(``ref.gated_delta_chunked_ref``; DeltaNet, arXiv:2406.06484).

TPU-native design:
  - grid (B, H / hb, T / Q); the chunk axis is LAST = sequential
    ("arbitrary"), so each head's state S (float32, V × K) lives in VMEM
    scratch across chunk steps and never round-trips to HBM.
  - One step holds ``hb`` heads of one chunk. Per head, in VMEM: the decay
    matrix ``exp(γ_i − γ_j)`` (γ the in-chunk prefix sum of g, computed
    outside), the strictly lower ``A = β ∘ decay ∘ K Kᵀ``, its unit lower
    inverse ``T = (I + A)⁻¹`` by block doubling (below), the written values
    ``U = T (βV) − T (βΓK) Sᵀ``, the output ``Γ ∘ Q Sᵀ + (decay ∘ Q Kᵀ) U``
    and the next state ``Γ_Q S + Uᵀ (exp(γ_Q − γ) ∘ K)``.
  - ``T`` by doubling: with T_s the block-diagonal inverse of the s × s
    diagonal blocks of ``I + A`` (T_1 = I),
    ``T_2s = T_s − T_s (A ∘ M_2s) T_s``, where M_2s keeps the pairs in one
    2s-block and not in one s-block. That is block forward substitution
    (stable for any β ≤ 2), on whole Q × Q products for the MXU:
    log2(Q) − 1 pairs of them.
  - Precision: Q Kᵀ and K Kᵀ take the caller's operands (bf16 on the MXU
    for bf16 inputs) and accumulate in float32; the inverse, the products
    with it and every product with the state run in float32 at the MXU's
    highest precision; decays, β and the state stay float32. ``(decay ∘
    Q Kᵀ) U`` takes the caller's dtype, as the output is written in it.
  - g and β arrive as lane rows (1, Q) per head, laid out (B, H, T/Q, 1, Q)
    so that a block's last two dims are whole; the column (Q, 1) a row
    scaling needs is a lane reduction of a diagonal, as in the SSD kernel.
  - Each lowering adds its grid's (batch, head, chunk) steps to the
    ``repro_kernel_gdn_chunks_total{kind="visited"}`` counter of
    ``repro.obs.metrics()`` (trace time, never per step).

Serving needs no backward: the kernel has none.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.metrics import metrics

from . import ref as _ref

__all__ = ["gated_delta_pallas"]

_NEG_INF = -1e30
_MAX_HEADS_PER_STEP = 8
_HIGHEST = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ bᵀ
_TN = (((0,), (0,)), ((), ()))   # aᵀ @ b


def _heads_per_step(h: int) -> int:
    """The most heads of one step, up to 8, that divide the head count."""
    return max(d for d in range(1, min(h, _MAX_HEADS_PER_STEP) + 1) if h % d == 0)


def _column(row, eye):
    """(1, Q) row → (Q, 1) column: a lane reduction of its diagonal."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _dot(a, b, dims, precision=None):
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _unit_lower_inverse(a, row, col, eye, chunk: int):
    """(I + a)⁻¹ for a strictly lower (Q, Q) float32 ``a``, Q a power of two."""
    t = jnp.where(eye, 1.0, 0.0) - jnp.where((row >> 1) == (col >> 1), a, 0.0)
    s = 2
    while s < chunk:
        shift = s.bit_length() - 1          # log2(s)
        off = ((row >> (shift + 1)) == (col >> (shift + 1))) & ((row >> shift) != (col >> shift))
        x = _dot(jnp.where(off, a, 0.0), t, _NN, _HIGHEST)
        t = t - _dot(t, x, _NN, _HIGHEST)
        s *= 2
    return t


def _gdn_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, h0_ref, o_ref, hT_ref, s_scr, *,
                hb: int, chunk: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = h0_ref[0]

    f32 = jnp.float32
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    incl = col <= row
    strict = col < row
    eye = col == row
    is_last = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    for j in range(hb):
        q = q_ref[0, j]                                              # (Q, K)
        k = k_ref[0, j]
        v = v_ref[0, j]                                              # (Q, V)
        cdt = v.dtype
        g_r = g_ref[0, j, 0]                                         # (1, Q)
        b_r = b_ref[0, j, 0]
        g_c = _column(g_r, eye)                                      # (Q, 1)
        b_c = _column(b_r, eye)
        decay = jnp.exp(jnp.where(incl, g_c - g_r, _NEG_INF))       # (Q, Q)
        a = jnp.where(strict, b_c * decay * _dot(k, k, _NT), 0.0)
        t = _unit_lower_inverse(a, row, col, eye, chunk)
        kf = k.astype(f32)
        w = _dot(t, kf * (b_c * jnp.exp(g_c)), _NN, _HIGHEST)        # (Q, K)
        s = s_scr[j]                                                 # (V, K)
        u = _dot(t, v.astype(f32) * b_c, _NN, _HIGHEST) - _dot(w, s, _NT, _HIGHEST)
        p = (decay * _dot(q, k, _NT)).astype(cdt)
        o = jnp.exp(g_c) * _dot(q.astype(f32), s, _NT, _HIGHEST) + _dot(p, u.astype(cdt), _NN)
        o_ref[0, j] = o.astype(o_ref.dtype)
        last = jnp.sum(jnp.where(is_last, g_r, 0.0), axis=1, keepdims=True)   # (1, 1)
        kd = kf * jnp.exp(last - g_c)                                # (Q, K)
        s_scr[j] = jnp.exp(last) * s + _dot(u, kd, _TN, _HIGHEST)

    @pl.when(ic == nc - 1)
    def _write_state():
        hT_ref[0] = s_scr[...]


def gated_delta_pallas(q, k, v, g, beta, h0, *, chunk: int = 64, interpret: bool = False):
    """q, k: (Bt,H,T,K); v: (Bt,H,T,V); g, beta: (Bt,H,T); h0: (Bt,H,V,K).

    Returns (o (Bt,H,T,V) in v's dtype, final state (Bt,H,V,K) float32).
    ``chunk`` is a power of two.
    """
    if chunk < 2 or chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    Bt, H, T, K = q.shape
    V = v.shape[-1]
    qp, kp, vp, gp, bp = _ref._pad_chunks(q, k.astype(q.dtype), v, g.astype(jnp.float32),
                                          beta.astype(jnp.float32), chunk)
    Tp = qp.shape[2]
    nc = Tp // chunk
    cum = jnp.cumsum(gp.reshape(Bt, H, nc, 1, chunk), axis=-1)
    bp = bp.reshape(Bt, H, nc, 1, chunk)
    hb = _heads_per_step(H)
    metrics().counter("repro_kernel_gdn_chunks_total", kind="visited").inc(Bt * H * nc)

    keys = pl.BlockSpec((1, hb, chunk, K), lambda b, g, c: (b, g, c, 0))
    values = pl.BlockSpec((1, hb, chunk, V), lambda b, g, c: (b, g, c, 0))
    rows = pl.BlockSpec((1, hb, 1, 1, chunk), lambda b, g, c: (b, g, c, 0, 0))
    state = pl.BlockSpec((1, hb, V, K), lambda b, g, c: (b, g, 0, 0))
    kernel = functools.partial(_gdn_kernel, hb=hb, chunk=chunk, nc=nc)
    o, hT = pl.pallas_call(
        kernel,
        grid=(Bt, H // hb, nc),
        in_specs=[keys, keys, values, rows, rows, state],
        out_specs=[values, state],
        out_shape=[jax.ShapeDtypeStruct((Bt, H, Tp, V), v.dtype),
                   jax.ShapeDtypeStruct((Bt, H, V, K), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, V, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp, cum, bp, h0.astype(jnp.float32))
    return o[:, :, :T], hT
