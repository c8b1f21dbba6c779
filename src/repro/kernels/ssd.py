"""Pallas TPU kernel for the Mamba-2 selective scan (SSD, chunked dual form).

TPU-native design:
  - grid (B, H / hb, T / Q); the chunk axis is LAST = sequential
    ("arbitrary"), so each head's state h ∈ R^{P×N} (float32) lives in VMEM
    scratch across chunk steps and never round-trips to HBM.
  - One step holds ``hb`` heads of one chunk. B and C are one group shared
    by every head, so ``C·Bᵀ`` (Q×Q) is computed once per step and reused by
    the step's heads.
  - Per head, in VMEM: the decay matrix ``L_ij = exp(cum_i − cum_j)`` (i ≥ j,
    cum the in-chunk prefix sum of dt·A, computed outside), the intra-chunk
    output ``(L ∘ C·Bᵀ ∘ dt)·x``, the inter-chunk output
    ``exp(cum) ⊙ (C·hᵀ)`` and the chunk's state contribution
    ``(x ⊙ exp(cum_Q − cum) dt)ᵀ·B``. Every matmul takes its operands in the
    caller's dtype (bf16 on the MXU for bf16 inputs) and accumulates in
    float32; prefix sums, decays and the state stay float32.
  - Mosaic layout rules shape the body: dt and cum arrive as lane rows
    (1, Q) per head; the column (Q, 1) a row-scaling needs is a lane
    reduction of a diagonal (no (1, Q) → (Q, 1) relayout), as in the RWKV6
    kernel.
  - Each lowering adds its grid's (batch, head, chunk) steps to the
    ``repro_kernel_ssd_chunks_total{kind="visited"}`` counter of
    ``repro.obs.metrics()`` (trace time, never per step).

Backward: custom_vjp recomputing through the float32 chunked jnp form
(``ref.ssd_chunked_ref``), as the flash-attention kernel does.
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

__all__ = ["ssd_pallas"]

_NEG_INF = -1e30
_MAX_HEADS_PER_STEP = 8


def _heads_per_step(h: int) -> int:
    """The most heads of one step, up to 8, that divide the head count."""
    return max(d for d in range(1, min(h, _MAX_HEADS_PER_STEP) + 1) if h % d == 0)


def _column(row, eye):
    """(1, Q) row → (Q, 1) column: a lane reduction of its diagonal."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _ssd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, h0_ref, y_ref, hT_ref, h_scr, *,
                hb: int, chunk: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    bc = b_ref[0]                                                    # (Q, N)
    cc = c_ref[0]
    cdt = bc.dtype
    g = lax.dot_general(cc, bc, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)          # (Q, Q)
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = col <= row
    eye = col == row
    is_last = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    for j in range(hb):
        cum_r = cum_ref[0, j:j + 1, :]                               # (1, Q)
        dt_r = dt_ref[0, j:j + 1, :]
        cum_c = _column(cum_r, eye)                                  # (Q, 1)
        decay = jnp.exp(jnp.where(causal, cum_c - cum_r, _NEG_INF))  # (Q, Q)
        m = (decay * g * dt_r).astype(cdt)
        x = x_ref[0, j]                                              # (Q, P)
        h = h_scr[j]                                                 # (P, N)
        y = lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        y = y + jnp.exp(cum_c) * lax.dot_general(
            cc, h.astype(cdt), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        y_ref[0, j] = y.astype(y_ref.dtype)
        last = jnp.sum(jnp.where(is_last, cum_r, 0.0), axis=1, keepdims=True)   # (1, 1)
        w_c = _column(jnp.exp(last - cum_r) * dt_r, eye)             # (Q, 1)
        xw = (x.astype(jnp.float32) * w_c).astype(cdt)
        h_scr[j] = jnp.exp(last) * h + lax.dot_general(
            xw, bc, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ic == nc - 1)
    def _write_state():
        hT_ref[0] = h_scr[...]


def _fwd_impl(x, dt, A, B, C, h0, *, chunk: int, interpret: bool):
    Bt, H, T, P = x.shape
    N = B.shape[-1]
    xp, dtp, Bp, Cp = _ref._pad_time(x, dt.astype(jnp.float32), B.astype(x.dtype),
                                     C.astype(x.dtype), chunk)
    Tp = xp.shape[2]
    nc = Tp // chunk
    cum = jnp.cumsum((dtp * A.astype(jnp.float32)[None, :, None]).reshape(Bt, H, nc, chunk),
                     axis=-1).reshape(Bt, H, Tp)
    hb = _heads_per_step(H)
    metrics().counter("repro_kernel_ssd_chunks_total", kind="visited").inc(Bt * H * nc)

    head_rows = pl.BlockSpec((1, hb, chunk), lambda b, g, c: (b, g, c))
    time_rows = pl.BlockSpec((1, chunk, N), lambda b, g, c: (b, c, 0))
    heads_tile = pl.BlockSpec((1, hb, chunk, P), lambda b, g, c: (b, g, c, 0))
    state = pl.BlockSpec((1, hb, P, N), lambda b, g, c: (b, g, 0, 0))
    kernel = functools.partial(_ssd_kernel, hb=hb, chunk=chunk, nc=nc)
    y, hT = pl.pallas_call(
        kernel,
        grid=(Bt, H // hb, nc),
        in_specs=[heads_tile, head_rows, head_rows, time_rows, time_rows, state],
        out_specs=[heads_tile, state],
        out_shape=[jax.ShapeDtypeStruct((Bt, H, Tp, P), x.dtype),
                   jax.ShapeDtypeStruct((Bt, H, P, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, dtp, cum, Bp, Cp, h0.astype(jnp.float32))
    return y[:, :, :T], hT


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_pallas(x, dt, A, B, C, h0, chunk=256, interpret=False):
    """x: (Bt,H,T,P); dt: (Bt,H,T); A: (H,); B, C: (Bt,T,N); h0: (Bt,H,P,N).

    Returns (y (Bt,H,T,P) in x's dtype, final state (Bt,H,P,N) float32).
    """
    return _fwd_impl(x, dt, A, B, C, h0, chunk=chunk, interpret=interpret)


def _vjp_fwd(x, dt, A, B, C, h0, chunk, interpret):
    return _fwd_impl(x, dt, A, B, C, h0, chunk=chunk, interpret=interpret), \
        (x, dt, A, B, C, h0)


def _vjp_bwd(chunk, interpret, res, cot):
    dy, dh = cot
    f32 = jnp.float32

    def f(x, dt, A, B, C, h0):
        return _ref.ssd_chunked_ref(x.astype(f32), dt.astype(f32), A, B.astype(f32),
                                    C.astype(f32), initial_state=h0, chunk=chunk)

    _, vjp = jax.vjp(f, *res)
    return vjp((dy.astype(f32), dh.astype(f32)))


ssd_pallas.defvjp(_vjp_fwd, _vjp_bwd)
