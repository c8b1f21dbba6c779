"""Pallas TPU flash attention — causal / local-window, GQA, MLA-ready.

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

Backward (custom_vjp, FlashAttention-2 style): the residuals are q, k, v and
the forward's output; ``delta = rowsum(dO ∘ O)`` is float32 jnp. Three
kernels on the backward's own tiles (``_pick_blocks`` with
``_bwd_vmem_bytes`` and its larger budget, under a raised scoped-VMEM
limit; the same override):
  - ``_lse_kernel`` recomputes the row log-sum-exp, grid (B, Hq, nq, nk)
    over sᵀ = k·qᵀ, so that the statistics lie along lanes;
  - ``_dq_kernel``, grid (B, Hq, nq, nk): p = exp(s − lse),
    ds = p ∘ (dO·vᵀ − delta), dq += ds·k in float32 scratch, written once;
  - ``_dkv_kernel``, grid (B, Hkv, nk, g, nq), sequential over the group's
    g query heads and the q blocks, on transposed tiles: dv += pᵀ·dO,
    dk += dsᵀ·q in float32 scratch, written once per kv block.
  Each skips and clamps as the forward does, by the forward's block plan
  (``_block_plan``) or its transpose (``_block_plan_t``: per kv block, the q
  blocks with any unmasked entry and with no masked one). A pair on the
  mask's edge is computed in halves along each axis (``_halves``), and a
  part holding no unmasked entry (``_any_kept``) is skipped: at 1024 x 1024
  the causal diagonal costs three quarters of a tile, not a whole one. q enters scaled
  in its dtype, as the forward scales it; q, k, v and dO enter the MXU in
  the caller's dtype, p and ds are rounded to it for their products, and
  every product, lse, delta and the accumulators are float32. Each lowering
  adds its visited and total block pairs to
  ``repro_kernel_flash_bwd_blocks_total{kind="visited"|"total"}``.
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

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30
_MAX_BLOCK = 1024                # largest tile per axis
_VMEM_BUDGET = 12 * 2**20        # bytes a call's tiles and scratch may take
_BWD_VMEM_BUDGET = 24 * 2**20    # the same for a backward kernel, by its estimate
_BWD_VMEM_LIMIT = 32 * 2**20     # scoped VMEM a backward kernel may take (v5e: 128 MiB)
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


def _pick_blocks(sq: int, sk: int, d: int, dv: int, itemsize: int,
                 vmem_bytes=_vmem_bytes, budget: int = _VMEM_BUDGET):
    """(bq, bk) for a call's shape: the largest tiles whose ``vmem_bytes``
    (the forward's by default) fit ``budget``."""
    for cap in range(_MAX_BLOCK, _LANES - 1, -_LANES):
        bq, bk = _tile(sq, cap), _tile(sk, cap)
        if vmem_bytes(bq, bk, d, dv, itemsize) <= budget:
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


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_NN = (((1,), (0,)), ((), ()))   # a · b


def _bwd_vmem_bytes(bq: int, bk: int, d: int, dv: int, itemsize: int) -> int:
    """VMEM of the larger backward kernel (dk/dv): double-buffered q, dO, k
    and v tiles in and dk, dv tiles out, the lse and delta rows, the float32
    (bk, bq) s/p, dp and ds tiles with p and ds in the operand dtype, and the
    float32 dk, dv scratch, minor dims padded to lanes."""
    lane = lambda n: -(-n // _LANES) * _LANES
    tiles = 2 * itemsize * (bq + 2 * bk) * (lane(d) + lane(dv))
    rows = 2 * 2 * 4 * 8 * lane(bq)
    scores = (3 * 4 + 2 * itemsize) * bk * lane(bq)
    scratch = 4 * bk * (lane(d) + lane(dv))
    return tiles + rows + scores + scratch


def _block_plan_t(nq, nk, bq, bk, sq, sk, causal, window) -> np.ndarray:
    """The block plan by kv block (columns): the first and last q block
    visiting it, the last kept in range for the DMA clamp, and the first and
    last q block for which it is whole. Each set is a run of q blocks, since
    the bounds of ``_kv_range`` and ``_whole_range`` rise with the q block."""
    by_q = _block_plan(nq, bq, bk, sq, sk, causal, window)
    plan = np.zeros((5, nk), np.int32)
    for ik in range(nk):
        runs = []
        for lo, hi in ((0, 1), (3, 4)):
            iqs = np.flatnonzero((by_q[lo] <= ik) & (ik <= by_q[hi]))
            runs.append((iqs[0], iqs[-1]) if iqs.size else (0, -1))
        (lo, hi), whole = runs
        plan[:, ik] = (lo, hi, max(hi, lo), *whole)
    return plan


def _halves(n: int):
    """Static (start, size) of the parts an edge tile of ``n`` splits into:
    two halves when ``n`` is a multiple of 256 of at least 512, else one."""
    return ((0, n // 2), (n // 2, n // 2)) if n >= 512 and n % 256 == 0 else ((0, n),)


def _offsets(iq, ik, rows, cols, *, bq, bk, sq, sk, **_):
    """Position of a sub-tile's first query and first key: rows ``rows``
    (start, size) of q block ``iq``, columns ``cols`` of kv block ``ik``;
    queries are right-aligned."""
    return (lax.add(lax.mul(iq, bq), rows[0] + sk - sq),
            lax.add(lax.mul(ik, bk), cols[0]))


def _any_kept(iq, ik, rows, cols, *, sk, causal, window, **shape):
    """Whether a sub-tile holds an unmasked entry: its keys kpos and query
    positions qpos span intervals, so kpos − qpos spans one, which must meet
    (−window, 0] and hold a real key."""
    q0, k0 = _offsets(iq, ik, rows, cols, sk=sk, **shape)
    k_last = lax.min(lax.add(k0, cols[1] - 1), sk - 1)
    keep = [lax.le(k0, k_last)]
    if causal:
        keep.append(lax.le(k0, lax.add(q0, rows[1] - 1)))
    if window is not None:
        keep.append(lax.gt(k_last, lax.sub(q0, window)))
    return functools.reduce(lax.bitwise_and, keep)


def _keep(iq, ik, rows, cols, transposed: bool, *, sk, causal, window, **shape):
    """Unmasked entries of a sub-tile, (rows, cols), or (cols, rows) when
    ``transposed``."""
    q0, k0 = _offsets(iq, ik, rows, cols, sk=sk, **shape)
    t = int(transposed)
    key = lax.broadcasted_iota(jnp.int32, (cols[1], 1) if t else (1, cols[1]), 1 - t)
    query = lax.broadcasted_iota(jnp.int32, (1, rows[1]) if t else (rows[1], 1), t)
    diff = key - query                                  # kpos − qpos + shift
    shift = lax.sub(q0, k0)
    keep = []
    if causal:
        keep.append(diff <= shift)
    if window is not None:
        keep.append(diff > shift - window)
    if sk % shape["bk"]:
        keep.append(key < lax.sub(sk, k0))
    return functools.reduce(operator.and_, keep)


def _visit(plan_ref, i, j, iq, ik, tile, **shape):
    """Run ``tile(rows, cols, masked)`` for step ``j`` of row ``i`` of a
    block plan: nothing outside its visited range, the whole tile unmasked
    inside its whole range, and elsewhere each sub-tile (``_halves``) that
    holds an unmasked entry, masked."""
    bq, bk = shape["bq"], shape["bk"]
    visit = _within(plan_ref[0, i], j, plan_ref[1, i])
    full = functools.partial(tile, (0, bq), (0, bk), False)
    if not (shape["causal"] or shape["window"] is not None or shape["sk"] % bk):
        pl.when(visit)(full)
        return
    whole = _within(plan_ref[3, i], j, plan_ref[4, i])
    pl.when(lax.bitwise_and(visit, whole))(full)

    @pl.when(lax.bitwise_and(visit, lax.bitwise_not(whole)))
    def _edge():
        for rows in _halves(bq):
            for cols in _halves(bk):
                pl.when(_any_kept(iq, ik, rows, cols, **shape))(
                    functools.partial(tile, rows, cols, True))


def _rows(ref, part):
    """Rows ``part`` (start, size) of a kernel's 2-D block ``ref[0, 0]``."""
    return ref[0, 0, pl.ds(part[0], part[1]), :]


def _lse_kernel(plan_ref, q_ref, k_ref, lse_ref, m_scr, l_scr, *, nk, **shape):
    """Row log-sum-exp of the scaled scores, over sᵀ = k·qᵀ so that the
    statistics lie along lanes, where the dk/dv kernel reads them."""
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def tile(rows, cols, masked: bool):
        s = lax.dot_general(_rows(k_ref, cols), _rows(q_ref, rows), _NT,
                            preferred_element_type=jnp.float32)       # (cols, rows)
        if masked:
            s = jnp.where(_keep(iq, ik, rows, cols, True, **shape), s, _NEG_INF)
        lanes = pl.ds(rows[0], rows[1])
        m_prev = m_scr[:, lanes]                                      # (1, rows)
        m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
        l_scr[:, lanes] = l_scr[:, lanes] * jnp.exp(m_prev - m_new) + jnp.exp(
            s - m_new).sum(axis=0, keepdims=True)
        m_scr[:, lanes] = m_new

    _visit(plan_ref, iq, ik, iq, ik, tile, **shape)

    @pl.when(ik == nk - 1)
    def _write():
        lse_ref[0, 0] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-37))


def _dq_kernel(plan_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               lse_scr, delta_scr, acc_scr, *, scale, nk, **shape):
    """dq of one q block, accumulated over its kv blocks:
    ds = p ∘ (dO·vᵀ − delta), dq = scale · ds·k."""
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        # the row statistics arrive along lanes; here a row is a sublane
        lse_scr[...] = lse_ref[0, 0, 0][:, None]
        delta_scr[...] = delta_ref[0, 0, 0][:, None]
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(rows, cols, masked: bool):
        k = _rows(k_ref, cols)
        s = lax.dot_general(_rows(q_ref, rows), k, _NT, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_keep(iq, ik, rows, cols, False, **shape), s, _NEG_INF)
        part = pl.ds(rows[0], rows[1])
        p = jnp.exp(s - lse_scr[part, :])                             # (rows, cols)
        dp = lax.dot_general(_rows(do_ref, rows), _rows(v_ref, cols), _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_scr[part, :])
        acc_scr[part, :] += lax.dot_general(ds.astype(k.dtype), k, _NN,
                                            preferred_element_type=jnp.float32)

    _visit(plan_ref, iq, ik, iq, ik, tile, **shape)

    @pl.when(ik == nk - 1)
    def _write():
        dq_ref[0, 0] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(plan_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, g, nq, **shape):
    """dk and dv of one kv block, accumulated over the q blocks of each of
    the group's g query heads, on transposed tiles: dv = pᵀ·dO, dk = dsᵀ·q
    (q arrives scaled)."""
    ik, ih, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when(lax.bitwise_and(ih == 0, iq == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(rows, cols, masked: bool):
        q, do = _rows(q_ref, rows), _rows(do_ref, rows)
        s = lax.dot_general(_rows(k_ref, cols), q, _NT, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_keep(iq, ik, rows, cols, True, **shape), s, _NEG_INF)
        lanes, part = pl.ds(rows[0], rows[1]), pl.ds(cols[0], cols[1])
        p = jnp.exp(s - lse_ref[0, 0, :, lanes])                      # (cols, rows)
        dv_scr[part, :] += lax.dot_general(p.astype(do.dtype), do, _NN,
                                           preferred_element_type=jnp.float32)
        dp = lax.dot_general(_rows(v_ref, cols), do, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, :, lanes])
        dk_scr[part, :] += lax.dot_general(ds.astype(q.dtype), q, _NN,
                                           preferred_element_type=jnp.float32)

    _visit(plan_ref, ik, iq, iq, ik, tile, **shape)

    @pl.when(lax.bitwise_and(ih == g - 1, iq == nq - 1))
    def _write():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, out, dout, *, causal, window, scale, block_q, block_k, interpret):
    """dq, dk, dv by three kernels on the backward's own tiles: the row
    log-sum-exp, then dq by q block, then dk and dv by kv block. Each skips
    the block pairs the mask leaves empty, as the forward does."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq, bk = _pick_blocks(Sq, Sk, D, Dv, q.dtype.itemsize, _bwd_vmem_bytes, _BWD_VMEM_BUDGET)
    bq = min(block_q, Sq) if block_q is not None else bq
    bk = min(block_k, Sk) if block_k is not None else bk
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    pad = lambda x, n: jnp.pad(x, ((0, 0), (0, 0), (0, n), (0, 0))) if n else x
    # q scaled once in its dtype, as the forward scales it; padded rows of
    # dO (and so of delta) are zero, so they add nothing to dk and dv
    qs = pad((q.astype(jnp.float32) * scale).astype(q.dtype), nq * bq - Sq)
    do = pad(dout.astype(q.dtype), nq * bq - Sq)
    kp, vp = pad(k, nk * bk - Sk), pad(v, nk * bk - Sk)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.pad(delta, ((0, 0), (0, 0), (0, nq * bq - Sq)))[:, :, None, :]
    shape = dict(bq=bq, bk=bk, sq=Sq, sk=Sk, causal=causal, window=window)
    plan = _block_plan(nq, **shape)

    reg = metrics()
    visited = int(np.maximum(plan[1] - plan[0] + 1, 0).sum())
    reg.counter("repro_kernel_flash_bwd_blocks_total", kind="visited").inc(B * Hq * visited)
    reg.counter("repro_kernel_flash_bwd_blocks_total", kind="total").inc(B * Hq * nq * nk)

    f32 = jnp.float32
    row = (1, 1, 1, bq)

    # by q block: the stats and dq kernels, grid (B, Hq, nq, nk)
    def q_index(b, h, iq, ik, plan):
        return b, h, iq, 0

    def row_index(b, h, iq, ik, plan):
        return b, h, 0, iq

    def kv_index(b, h, iq, ik, plan):
        # a skipped step repeats a block the pipeline holds: no new copy
        return b, lax.div(h, g), lax.clamp(plan[0, iq], ik, plan[2, iq]), 0

    by_q = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_BWD_VMEM_LIMIT)
    lse = pl.pallas_call(
        functools.partial(_lse_kernel, nk=nk, **shape),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hq, nq, nk),
            in_specs=[pl.BlockSpec((1, 1, bq, D), q_index),
                      pl.BlockSpec((1, 1, bk, D), kv_index)],
            out_specs=pl.BlockSpec(row, row_index),
            scratch_shapes=[pltpu.VMEM((1, bq), f32), pltpu.VMEM((1, bq), f32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, nq * bq), f32),
        compiler_params=by_q,
        interpret=interpret,
    )(jnp.asarray(plan), qs, kp)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, nk=nk, **shape),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hq, nq, nk),
            in_specs=[pl.BlockSpec((1, 1, bq, D), q_index),
                      pl.BlockSpec((1, 1, bk, D), kv_index),
                      pl.BlockSpec((1, 1, bk, Dv), kv_index),
                      pl.BlockSpec((1, 1, bq, Dv), q_index),
                      pl.BlockSpec(row, row_index),
                      pl.BlockSpec(row, row_index)],
            out_specs=pl.BlockSpec((1, 1, bq, D), q_index),
            scratch_shapes=[pltpu.VMEM((bq, 1), f32), pltpu.VMEM((bq, 1), f32),
                            pltpu.VMEM((bq, D), f32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, nq * bq, D), q.dtype),
        compiler_params=by_q,
        interpret=interpret,
    )(jnp.asarray(plan), qs, kp, vp, do, lse, delta)

    # by kv block: the dk/dv kernel, grid (B, Hkv, nk, g, nq)
    def qt_index(b, hk, ik, ih, iq, plan):
        return b, hk * g + ih, lax.clamp(plan[0, ik], iq, plan[2, ik]), 0

    def rowt_index(b, hk, ik, ih, iq, plan):
        return b, hk * g + ih, 0, lax.clamp(plan[0, ik], iq, plan[2, ik])

    def kvt_index(b, hk, ik, ih, iq, plan):
        return b, hk, ik, 0

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, g=g, nq=nq, **shape),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, nk, g, nq),
            in_specs=[pl.BlockSpec((1, 1, bq, D), qt_index),
                      pl.BlockSpec((1, 1, bk, D), kvt_index),
                      pl.BlockSpec((1, 1, bk, Dv), kvt_index),
                      pl.BlockSpec((1, 1, bq, Dv), qt_index),
                      pl.BlockSpec(row, rowt_index),
                      pl.BlockSpec(row, rowt_index)],
            out_specs=[pl.BlockSpec((1, 1, bk, D), kvt_index),
                       pl.BlockSpec((1, 1, bk, Dv), kvt_index)],
            scratch_shapes=[pltpu.VMEM((bk, D), f32), pltpu.VMEM((bk, Dv), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, nk * bk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, nk * bk, Dv), v.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.asarray(_block_plan_t(nq, nk, **shape)), qs, kp, vp, do, lse, delta)
    return dq[:, :, :Sq], dk[:, :, :Sk], dv[:, :, :Sk]


def _vjp_fwd(q, k, v, causal, window, scale, block_q, block_k, interpret):
    out = _fwd_impl(q, k, v, causal=causal, window=window, scale=scale,
                    block_q=block_q, block_k=block_k, interpret=interpret)
    return out, (q, k, v, out)


def _vjp_bwd(causal, window, scale, block_q, block_k, interpret, res, dout):
    return _bwd_impl(*res, dout, causal=causal, window=window, scale=scale,
                     block_q=block_q, block_k=block_k, interpret=interpret)


flash_attention_pallas.defvjp(_vjp_fwd, _vjp_bwd)
