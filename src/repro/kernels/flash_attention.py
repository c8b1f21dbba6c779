"""Pallas TPU flash attention (fwd) — causal / local-window, GQA, MLA-ready.

TPU-native design (not a CUDA port):
  - grid (B, Hq, Sq/bq, Sk/bk); the LAST grid dim is sequential on TPU
    ("arbitrary" semantics) so the online-softmax state lives in VMEM
    scratch across k-blocks — the accumulator never round-trips to HBM.
  - q/k/v blocks are MXU-aligned (bq, bk multiples of 128; D is the head
    dim, 64-256) and double-buffered by the Pallas pipeline from HBM.
  - GQA is an index_map trick: the kv block index is h // group, so kv
    tiles are fetched once per group from HBM (VMEM reuse across the group
    comes from the pipeline cache, no repeat() materialization).
  - causal/local masking is positional (right-aligned), enabling the same
    kernel for prefill (Sq == Sk) and windowed hybrids.

Backward: custom_vjp with a blocked pure-jnp recompute (flash-style, no S²
materialization). A fused bwd kernel is a possible further step; the fwd
kernel is where the roofline lives for the 32k prefill shapes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, window: Optional[int],
                block_q: int, block_k: int, sq: int, sk: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)                    # (bk, Dv)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
        + (sk - sq)                                        # right-aligned
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = kpos < sk
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]                                    # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _write():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def _fwd_impl(q, k, v, *, causal, window, scale, block_q, block_k, interpret):
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    padq = (-Sq) % bq
    padk = (-Sk) % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, padq), (0, 0))) if padq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, padk), (0, 0))) if padk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, padk), (0, 0))) if padk else v
    nq = qp.shape[2] // bq
    nk = kp.shape[2] // bk

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, sq=Sq, sk=Sk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik, g=g: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, iq, ik, g=g: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, nq * bq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :Sq, :]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_pallas(q, k, v, causal=True, window=None, scale=None,
                           block_q=128, block_k=128, interpret=False):
    return _fwd_impl(q, k, v, causal=causal, window=window, scale=scale,
                     block_q=block_q, block_k=block_k, interpret=interpret)


def _vjp_fwd(q, k, v, causal, window, scale, block_q, block_k, interpret):
    out = _fwd_impl(q, k, v, causal=causal, window=window, scale=scale,
                    block_q=block_q, block_k=block_k, interpret=interpret)
    return out, (q, k, v)


def _vjp_bwd(causal, window, scale, block_q, block_k, interpret, res, dout):
    q, k, v = res
    # blocked recompute bwd (pure jnp, flash-style memory profile)
    f = lambda q_, k_, v_: _ref.flash_attention_ref(
        q_, k_, v_, causal=causal, window=window, scale=scale,
        block_k=max(block_k, 128))
    _, vjp = jax.vjp(f, q, k, v)
    return vjp(dout)


flash_attention_pallas.defvjp(_vjp_fwd, _vjp_bwd)
