"""Pallas TPU kernel for the RG-LRU diagonal gated recurrence.

h_t = a_t ⊙ h_{t-1} + sqrt(1−a_t²) ⊙ x_t        (x already input-gated)

TPU adaptation: the recurrence is diagonal (pure VPU, no MXU), so the kernel
is bandwidth-bound by design. Layout:
  - grid (B, W/bw, T/chunk); T sequential (last, "arbitrary"), carrying the
    h state (1, bw) in VMEM f32 scratch — one HBM read of x/a and one write
    of h per element, the bandwidth floor.
  - channel blocks bw = 512 lanes keep the VPU vectorized; within a chunk a
    fori_loop steps the recurrence (chunk × elementwise ops, no HBM traffic).
  - Mosaic layout rules shape the body: the loop reads a_t and the gated
    input row by row from f32 VMEM scratch and writes h_t into a third one
    (``pl.ds(t, 1)`` on refs; values cannot be sliced dynamically), and the
    state travels as (B, 1, W) so its block is a whole (1, bw) tile.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rglru_pallas"]


def _rglru_kernel(x_ref, a_ref, h0_ref, h_ref, hT_ref,
                  h_scr, a_scr, g_scr, o_scr, *, chunk: int, nt: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)             # (chunk, bw)
    a_scr[...] = a
    g_scr[...] = jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0)) * x_ref[0].astype(jnp.float32)

    def step(t, h):
        row = pl.ds(t, 1)
        h = a_scr[row, :] * h + g_scr[row, :]     # (1, bw)
        o_scr[row, :] = h
        return h

    h_last = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_ref[0] = o_scr[...].astype(h_ref.dtype)
    h_scr[...] = h_last

    @pl.when(it == nt - 1)
    def _write_state():
        hT_ref[0] = h_last


def rglru_pallas(x, a, *, initial_state=None, chunk: int = 256,
                 block_w: int = 512, interpret: bool = False
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x, a: (B, T, W) → (h (B,T,W), final state (B, W) f32)."""
    B, T, W = x.shape
    bw = min(block_w, W)
    padw = (-W) % bw
    padt = (-T) % chunk
    if padw:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, padw)))
        a = jnp.pad(a, ((0, 0), (0, 0), (0, padw)), constant_values=1.0)
    if padt:
        x = jnp.pad(x, ((0, 0), (0, padt), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, padt), (0, 0)), constant_values=1.0)
    Wp, Tp = x.shape[2], x.shape[1]
    nw, nt = Wp // bw, Tp // chunk
    h0 = (jnp.zeros((B, Wp), jnp.float32) if initial_state is None
          else jnp.pad(initial_state.astype(jnp.float32), ((0, 0), (0, padw))))

    kernel = functools.partial(_rglru_kernel, chunk=chunk, nt=nt)
    h, hT = pl.pallas_call(
        kernel,
        grid=(B, nw, nt),
        in_specs=[
            pl.BlockSpec((1, chunk, bw), lambda b, iw, it: (b, it, iw)),
            pl.BlockSpec((1, chunk, bw), lambda b, iw, it: (b, it, iw)),
            pl.BlockSpec((1, 1, bw), lambda b, iw, it: (b, 0, iw)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bw), lambda b, iw, it: (b, it, iw)),
            pl.BlockSpec((1, 1, bw), lambda b, iw, it: (b, 0, iw)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, Wp), x.dtype),
            jax.ShapeDtypeStruct((B, 1, Wp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32),
                        pltpu.VMEM((chunk, bw), jnp.float32),
                        pltpu.VMEM((chunk, bw), jnp.float32),
                        pltpu.VMEM((chunk, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, a, h0[:, None, :])
    return h[:, :T, :W], hT[:, 0, :W]
