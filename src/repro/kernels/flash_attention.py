"""Pallas TPU flash attention (fwd) — causal / local-window, GQA, MLA-ready.

TPU-native design (not a CUDA port):
  - grid (B, Hq, Sq/bq, Sk/bk); the LAST grid dim is sequential on TPU
    ("arbitrary" semantics) so the online-softmax state lives in VMEM
    scratch across k-blocks — the accumulator never round-trips to HBM.
  - Tiles come from the call's shape (``_pick_blocks``): the largest
    ``bq``/``bk`` up to ``_MAX_BLOCK`` with the least padding, each a
    multiple of 128 or the whole length when that is shorter, within a VMEM
    budget that counts the double-buffered q/k/v/o tiles, the float32 s/p
    tile and the scratch. No option or model name chooses them; an explicit
    ``block_q``/``block_k`` still overrides.
  - Block plan (``_kv_range``, ``_whole_range``): per q block, the kv
    blocks holding any unmasked entry (right-aligned causal diagonal,
    window, key padding) and those holding no masked one, computed on the
    host into one int32 table that reaches the kernel by scalar prefetch
    (SMEM). A pair outside the first range is skipped under ``pl.when``,
    and the k/v ``index_map`` clamps the kv block index into it, so a
    skipped step repeats the block the pipeline already holds and issues no
    copy. Only a pair outside the second range builds the mask (a second
    ``pl.when`` body: a mask under ``lax.cond`` would copy the s tile).
    Init (first kv step) and the write (last kv step) run on every q block,
    so a window's row block starts from a clean state. The kernel's own
    scalar work is a few ``lax`` ops: tracing and lowering it is part of
    every eager prefill's latency.
  - Operands: ``q·kᵀ`` takes q (pre-scaled once per q block) and k in the
    caller's dtype, and ``p·v`` rounds p to v's dtype — for bfloat16 inputs
    one bf16 MXU pass each, accumulated in float32. The softmax state
    (m, l, acc) stays float32; float32 callers see float32 operands.
  - GQA is an index_map trick: the kv block index is h // group, so kv
    tiles are fetched once per group from HBM (no repeat() materialization).
  - Each lowering adds its grid's visited and total block pairs to the
    ``repro_kernel_flash_blocks_total{kind="visited"|"total"}`` counters of
    ``repro.obs.metrics()`` (trace time, never per step).

Backward: custom_vjp with a blocked pure-jnp recompute (flash-style, no S²
materialization) in float32.
"""
from __future__ import annotations

import functools
import operator
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.metrics import metrics

from . import ref as _ref

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30
_MAX_BLOCK = 1024                # largest tile per axis
_VMEM_BUDGET = 12 * 2**20        # bytes a call's tiles and scratch may take
_LANES = 128


# --------------------------------------------------------------------------
# tiles and block plan (pure functions of the shape, on the host)
# --------------------------------------------------------------------------

def _vmem_bytes(bq: int, bk: int, d: int, dv: int, itemsize: int) -> int:
    """VMEM of one call: double-buffered q/k/v/o tiles, the float32 s and p
    tiles, and the scratch (scaled q, m, l, acc), minor dims padded to lanes."""
    lane = lambda n: -(-n // _LANES) * _LANES
    tiles = 2 * itemsize * (bq * lane(d) + bk * lane(d) + bk * lane(dv) + bq * lane(dv))
    scores = 2 * 4 * bq * lane(bk)
    scratch = itemsize * bq * lane(d) + 4 * bq * (2 * _LANES + lane(dv))
    return tiles + scores + scratch


def _tile(n: int, cap: int) -> int:
    """The whole length if it is at most ``cap``, else the multiple of 128 up
    to ``cap`` that pads ``n`` least (the largest among equals)."""
    if n <= cap:
        return n
    return min(range(_LANES, cap + 1, _LANES), key=lambda t: ((-n) % t, -t))


def _pick_blocks(sq: int, sk: int, d: int, dv: int, itemsize: int):
    """(bq, bk) for a call's shape: the largest tiles that fit the budget."""
    for cap in range(_MAX_BLOCK, _LANES - 1, -_LANES):
        bq, bk = _tile(sq, cap), _tile(sk, cap)
        if _vmem_bytes(bq, bk, d, dv, itemsize) <= _VMEM_BUDGET:
            break
    return bq, bk


def _row_span(iq, bq, sq, sk):
    """Positions of q block ``iq``'s first and last real rows. Queries are
    right-aligned: row r sits at position r + sk - sq."""
    return iq * bq + sk - sq, min(iq * bq + bq, sq) - 1 + sk - sq


def _kv_range(iq, bq, bk, sq, sk, causal, window):
    """First and last kv block holding an unmasked entry for a real row of q
    block ``iq`` (last < first when there is none)."""
    first, last = _row_span(iq, bq, sq, sk)
    nk = -(-sk // bk)
    hi = min(-(-max(last + 1, 0) // bk), nk) - 1 if causal else nk - 1
    lo = max(first - window + 1, 0) // bk if window is not None else 0
    return lo, hi


def _whole_range(iq, bq, bk, sq, sk, causal, window):
    """First and last kv block whose entries are all unmasked for every real
    row of q block ``iq`` (last < first when there is none): no key padding,
    wholly below the causal diagonal and above the window's lower edge.
    Padded query rows are sliced off and need no mask."""
    first, last = _row_span(iq, bq, sq, sk)
    nk = -(-sk // bk)
    hi = nk - 1 if sk % bk == 0 else nk - 2
    if causal:
        hi = min(hi, (first + 1) // bk - 1)
    lo = max((last - window) // bk + 1, 0) if window is not None else 0
    return lo, hi


def _block_plan(nq, bq, bk, sq, sk, causal, window) -> np.ndarray:
    """Per q block (columns): the visited kv blocks' first and last, the last
    kept in range for the DMA clamp, and the first and last whole block."""
    plan = np.zeros((5, nq), np.int32)
    for iq in range(nq):
        lo, hi = _kv_range(iq, bq, bk, sq, sk, causal, window)
        plan[:, iq] = (lo, hi, max(hi, lo), *_whole_range(iq, bq, bk, sq, sk, causal, window))
    return plan


def _within(lo, x, hi):
    return lax.bitwise_and(lax.le(lo, x), lax.le(x, hi))


# --------------------------------------------------------------------------
# kernel
# --------------------------------------------------------------------------

def _fwd_kernel(plan_ref, q_ref, k_ref, v_ref, o_ref, q_scr, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, window: Optional[int],
                bq: int, bk: int, sq: int, sk: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        q_scr[...] = (q_ref[0, 0].astype(jnp.float32) * scale).astype(q_scr.dtype)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(masked: bool):
        s = lax.dot_general(q_scr[...], k_ref[0, 0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)      # (bq, bk)
        if masked:
            # kpos - qpos = (cols - rows) - shift, with right-aligned queries
            cols = lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            diff = cols - lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            shift = lax.sub(lax.add(lax.mul(iq, bq), sk - sq), lax.mul(ik, bk))
            keep = []
            if causal:
                keep.append(diff <= shift)
            if window is not None:
                keep.append(diff > shift - window)
            if sk % bk:
                keep.append(cols < lax.sub(sk, lax.mul(ik, bk)))
            s = jnp.where(functools.reduce(operator.and_, keep), s, _NEG_INF)
        m_prev = m_scr[...]                                           # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[0, 0]
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    visit = _within(plan_ref[0, iq], ik, plan_ref[1, iq])
    if causal or window is not None or sk % bk:
        whole = _within(plan_ref[3, iq], ik, plan_ref[4, iq])
        pl.when(lax.bitwise_and(visit, whole))(functools.partial(block, False))
        pl.when(lax.bitwise_and(visit, lax.bitwise_not(whole)))(functools.partial(block, True))
    else:
        pl.when(visit)(functools.partial(block, False))

    @pl.when(ik == nk - 1)
    def _write():
        inv = 1.0 / jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0] = (acc_scr[...] * inv).astype(o_ref.dtype)


def _fwd_impl(q, k, v, *, causal, window, scale, block_q, block_k, interpret):
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq, bk = _pick_blocks(Sq, Sk, D, Dv, q.dtype.itemsize)
    bq = min(block_q, Sq) if block_q is not None else bq
    bk = min(block_k, Sk) if block_k is not None else bk
    padq = (-Sq) % bq
    padk = (-Sk) % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, padq), (0, 0))) if padq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, padk), (0, 0))) if padk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, padk), (0, 0))) if padk else v
    nq = qp.shape[2] // bq
    nk = kp.shape[2] // bk
    shape = dict(bq=bq, bk=bk, sq=Sq, sk=Sk, causal=causal, window=window)
    plan = _block_plan(nq, **shape)

    reg = metrics()
    visited = int(np.maximum(plan[1] - plan[0] + 1, 0).sum())
    reg.counter("repro_kernel_flash_blocks_total", kind="visited").inc(B * Hq * visited)
    reg.counter("repro_kernel_flash_blocks_total", kind="total").inc(B * Hq * nq * nk)

    def q_index(b, h, iq, ik, plan):
        return b, h, iq, 0

    def kv_index(b, h, iq, ik, plan):
        # a skipped step repeats a block the pipeline holds: no new copy
        return b, lax.div(h, g), lax.clamp(plan[0, iq], ik, plan[2, iq]), 0

    kernel = functools.partial(_fwd_kernel, scale=scale, nk=nk, **shape)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hq, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), q_index),
                pl.BlockSpec((1, 1, bk, D), kv_index),
                pl.BlockSpec((1, 1, bk, Dv), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, Dv), q_index),
            scratch_shapes=[
                pltpu.VMEM((bq, D), q.dtype),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, nq * bq, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(plan), qp, kp, vp)
    return out[:, :, :Sq, :]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_pallas(q, k, v, causal=True, window=None, scale=None,
                           block_q=None, block_k=None, interpret=False):
    """``block_q``/``block_k`` None: tiles chosen from the shape."""
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
        block_k=max(block_k or 128, 128))
    _, vjp = jax.vjp(f, q, k, v)
    return vjp(dout)


flash_attention_pallas.defvjp(_vjp_fwd, _vjp_bwd)
