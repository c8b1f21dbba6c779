"""Pallas kernels vs pure-jnp oracles (explicit interpret mode on CPU).

Sweeps shapes/dtypes per kernel; asserts allclose against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.kernels import ops, ref
from repro.kernels import flash_attention as fa
from repro.kernels.flash_attention import flash_attention_pallas
from repro.obs.metrics import metrics

RNG = np.random.default_rng(7)


def rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
FLASH_CASES = [
    # (B, Hq, Hkv, Sq, Sk, D, Dv, causal, window, dtype, block_q, block_k);
    # None tiles are chosen from the shape.
    (1, 2, 2, 128, 128, 64, 64, True, None, jnp.float32, 64, 64),
    (2, 4, 2, 128, 128, 64, 64, True, None, jnp.float32, 64, 64),    # GQA
    (1, 8, 1, 256, 256, 128, 128, True, None, jnp.float32, 64, 64),  # MQA
    (1, 2, 2, 128, 128, 64, 64, False, None, jnp.float32, 64, 64),   # bidirectional
    (1, 2, 2, 128, 128, 64, 64, True, 64, jnp.float32, 64, 64),      # local window
    (1, 2, 1, 100, 100, 32, 32, True, None, jnp.float32, 64, 64),    # ragged (pad path)
    (1, 2, 2, 64, 192, 32, 32, True, None, jnp.float32, 64, 64),     # Sq < Sk (chunked q)
    (1, 2, 2, 128, 128, 64, 64, True, None, jnp.bfloat16, 64, 64),
    # the block plan skips blocks, clamps the kv DMA, masks only edge blocks
    (1, 2, 2, 256, 256, 32, 32, True, None, jnp.float32, 64, 64),     # causal, 4 q blocks
    (1, 2, 2, 256, 256, 32, 32, True, None, jnp.float32, 32, 128),    # bq < bk
    (1, 2, 2, 256, 256, 32, 32, True, None, jnp.float32, 128, 32),    # bq > bk
    (1, 2, 2, 96, 256, 32, 32, True, None, jnp.float32, 32, 64),      # Sq < Sk, right-aligned
    (1, 2, 2, 90, 170, 32, 32, True, None, jnp.float32, 32, 64),      # neither a tile multiple
    (1, 2, 2, 256, 256, 32, 32, True, 20, jnp.float32, 64, 64),       # window < tile
    (1, 2, 2, 256, 256, 32, 32, True, 150, jnp.float32, 64, 64),      # window > tile
    (1, 2, 2, 100, 230, 32, 32, True, 70, jnp.float32, 32, 64),       # window, Sq < Sk, ragged
    (2, 4, 2, 192, 192, 32, 32, True, None, jnp.float32, 64, 64),     # GQA group 2
    (1, 8, 2, 192, 192, 32, 32, True, 40, jnp.float32, 64, 64),       # GQA group 4, window
    (1, 2, 2, 192, 192, 48, 32, True, None, jnp.float32, 64, 64),     # MLA: D != Dv
    (1, 2, 2, 64, 200, 32, 32, False, None, jnp.float32, 32, 64),     # cross: padded keys only
    (1, 2, 2, 128, 128, 32, 32, False, 40, jnp.float32, 32, 32),      # bidirectional window
    (1, 4, 2, 256, 256, 64, 64, True, None, jnp.bfloat16, 64, 64),    # bf16 operands
    (1, 2, 2, 200, 200, 64, 64, True, 50, jnp.bfloat16, 64, 64),      # bf16, window, ragged
    (1, 2, 1, 17, 17, 32, 32, True, None, jnp.bfloat16, None, None),  # whole length
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_kernel_matches_dense_oracle(case):
    B, Hq, Hkv, Sq, Sk, D, Dv, causal, window, dtype, bq, bk = case
    q = rand((B, Hq, Sq, D), dtype)
    k = rand((B, Hkv, Sk, D), dtype)
    v = rand((B, Hkv, Sk, Dv), dtype)
    out = flash_attention_pallas(q, k, v, causal, window, None, bq, bk, True)
    want = ref.flash_attention_dense_ref(q, k, v, causal=causal, window=window)
    assert out.shape == (B, Hq, Sq, Dv) and out.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_kernel_mla_head_dims():
    """k head dim ≠ v head dim (MLA): 48 vs 32."""
    q = rand((1, 2, 64, 48))
    k = rand((1, 2, 64, 48))
    v = rand((1, 2, 64, 32))
    out = flash_attention_pallas(q, k, v, True, None, None, 32, 32, True)
    want = ref.flash_attention_dense_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _dense_mask(sq, sk, causal, window):
    qpos = np.arange(sq)[:, None] + (sk - sq)
    kpos = np.arange(sk)[None, :]
    keep = np.ones((sq, sk), bool)
    if causal:
        keep &= kpos <= qpos
    if window is not None:
        keep &= kpos > qpos - window
    return keep


def _flash_blocks():
    reg = metrics()
    return (reg.counter("repro_kernel_flash_blocks_total", kind="visited").value,
            reg.counter("repro_kernel_flash_blocks_total", kind="total").value)


@pytest.mark.parametrize("causal, window", [(True, None), (True, 1), (True, 20), (True, 100),
                                            (False, None), (False, 30)])
def test_flash_block_plan_is_exact(causal, window):
    """A block pair is visited iff some entry of its real rows is unmasked,
    takes the unmasked path iff none is masked or padded, its clamped kv
    index is itself when visited and in range always, and the counter adds up."""
    for sq, sk in [(1, 1), (17, 17), (40, 96), (96, 96), (70, 130), (130, 130)]:
        keep = _dense_mask(sq, sk, causal, window)
        for bq, bk in [(16, 16), (16, 48), (48, 16), (32, 32)]:
            nq, nk = -(-sq // bq), -(-sk // bk)
            plan = fa._block_plan(nq, bq, bk, sq, sk, causal, window)
            for iq in range(nq):
                lo, hi, hi_dma, whole_lo, whole_hi = plan[:, iq]
                for ik in range(nk):
                    blk = keep[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk]
                    visit = lo <= ik <= hi
                    assert visit == blk.any(), (sq, sk, bq, bk, iq, ik)
                    whole = blk.all() and (ik + 1) * bk <= sk
                    assert (whole_lo <= ik <= whole_hi) == whole, (sq, sk, bq, bk, iq, ik)
                    clamped = min(max(ik, lo), hi_dma)
                    assert 0 <= clamped < nk and (clamped == ik or not visit)

    B, H, sq, sk, bq, bk = 2, 3, 70, 130, 32, 48
    before = _flash_blocks()
    x = jax.ShapeDtypeStruct((B, H, sq, 16), jnp.float32)
    kv = jax.ShapeDtypeStruct((B, H, sk, 16), jnp.float32)
    jax.eval_shape(lambda q, k, v: flash_attention_pallas(q, k, v, causal, window, None,
                                                          bq, bk, True), x, kv, kv)
    after = _flash_blocks()
    want = _dense_mask(sq, sk, causal, window)
    nq, nk = -(-sq // bq), -(-sk // bk)
    blocks = sum(want[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()
                 for i in range(nq) for j in range(nk))
    assert after[0] - before[0] == B * H * blocks
    assert after[1] - before[1] == B * H * nq * nk


def _flash_bwd_blocks():
    reg = metrics()
    return (reg.counter("repro_kernel_flash_bwd_blocks_total", kind="visited").value,
            reg.counter("repro_kernel_flash_bwd_blocks_total", kind="total").value)


@pytest.mark.parametrize("causal, window", [(True, None), (True, 1), (True, 20), (True, 100),
                                            (False, None), (False, 30)])
def test_flash_block_plan_by_kv_block_is_exact(causal, window):
    """The dk/dv kernel's plan: q block iq visits kv block ik iff some entry
    of its real rows is unmasked, the pair takes the unmasked path iff none
    is masked or padded, the clamped q index is itself when visited and in
    range always, and the backward's counter adds up."""
    for sq, sk in [(1, 1), (17, 17), (40, 96), (96, 96), (70, 130), (130, 130)]:
        keep = _dense_mask(sq, sk, causal, window)
        for bq, bk in [(16, 16), (16, 48), (48, 16), (32, 32)]:
            nq, nk = -(-sq // bq), -(-sk // bk)
            plan = fa._block_plan_t(nq, nk, bq, bk, sq, sk, causal, window)
            for ik in range(nk):
                lo, hi, hi_dma, whole_lo, whole_hi = plan[:, ik]
                for iq in range(nq):
                    blk = keep[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk]
                    visit = lo <= iq <= hi
                    assert visit == blk.any(), (sq, sk, bq, bk, iq, ik)
                    whole = blk.all() and (ik + 1) * bk <= sk
                    assert (whole_lo <= iq <= whole_hi) == whole, (sq, sk, bq, bk, iq, ik)
                    clamped = min(max(iq, lo), hi_dma)
                    assert 0 <= clamped < nq and (clamped == iq or not visit)

    B, H, sq, sk, bq, bk = 2, 3, 70, 130, 32, 48
    x = jax.ShapeDtypeStruct((B, H, sq, 16), jnp.float32)
    kv = jax.ShapeDtypeStruct((B, H, sk, 16), jnp.float32)
    f = lambda q, k, v: flash_attention_pallas(q, k, v, causal, window, None, bq, bk, True)
    before = _flash_bwd_blocks()
    jax.eval_shape(lambda q, k, v: jax.vjp(f, q, k, v)[1](q), x, kv, kv)
    after = _flash_bwd_blocks()
    want = _dense_mask(sq, sk, causal, window)
    nq, nk = -(-sq // bq), -(-sk // bk)
    blocks = sum(want[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()
                 for i in range(nq) for j in range(nk))
    assert after[0] - before[0] == B * H * blocks
    assert after[1] - before[1] == B * H * nq * nk
    if causal:
        assert blocks < nq * nk


def test_flash_backward_skips_masked_blocks_of_the_training_call():
    """At the training call's shape the backward's tiles are 1024 x 1024 and
    the causal skip leaves 3 of the 4 block pairs of each head."""
    x = jax.ShapeDtypeStruct((2, 32, 2048, 64), jnp.bfloat16)
    f = lambda q, k, v: flash_attention_pallas(q, k, v, True)
    before = _flash_bwd_blocks()
    jax.eval_shape(lambda q, k, v: jax.vjp(f, q, k, v)[1](q), x, x, x)
    after = _flash_bwd_blocks()
    assert (after[0] - before[0], after[1] - before[1]) == (64 * 3, 64 * 4)


@pytest.mark.parametrize("causal, window", [(True, None), (True, 1), (True, 100),
                                            (False, None), (False, 30)])
def test_flash_backward_sub_tiles_skip_only_masked_entries(causal, window):
    """An edge tile's part is skipped only when every entry of its real rows
    is masked or a padded key; one without padded rows is visited iff some
    entry is unmasked."""
    assert fa._halves(1024) == ((0, 512), (512, 512))
    assert fa._halves(768) == ((0, 384), (384, 384))
    assert fa._halves(640) == ((0, 640),) and fa._halves(256) == ((0, 256),)
    for sq, sk, bq, bk in [(96, 96, 32, 32), (70, 130, 32, 48), (40, 97, 32, 48),
                           (40, 200, 48, 64)]:
        keep = _dense_mask(sq, sk, causal, window)
        shape = dict(bq=bq, bk=bk, sq=sq, sk=sk, causal=causal, window=window)
        for iq in range(-(-sq // bq)):
            for ik in range(-(-sk // bk)):
                for rows in [(0, bq), (0, bq // 2), (bq // 2, bq // 2)]:
                    for cols in [(0, bk), (0, bk // 2), (bk // 2, bk // 2)]:
                        r0, c0 = iq * bq + rows[0], ik * bk + cols[0]
                        real = keep[r0:min(r0 + rows[1], sq), c0:c0 + cols[1]]
                        kept = bool(fa._any_kept(iq, ik, rows, cols, **shape))
                        if r0 + rows[1] <= sq:
                            assert kept == real.any(), (sq, sk, iq, ik, rows, cols)
                        else:
                            assert kept or not real.any(), (sq, sk, iq, ik, rows, cols)


@pytest.mark.parametrize("sq, sk, d, dv, itemsize, want", [
    (2048, 2048, 64, 64, 2, (1024, 1024)),      # stablelm-1.6b training call
    (2048, 2048, 64, 64, 4, (512, 512)),        # float32 tiles: budget binds
    (4096, 4096, 256, 256, 2, (512, 512)),      # RecurrentGemma's window layers
    (2048, 2048, 192, 128, 2, (1024, 1024)),    # MLA widths
    (17, 17, 128, 128, 2, (17, 17)),            # short, unaligned: whole length
    (300, 1000, 64, 64, 4, (300, 1000)),
    (8192, 8192, 1024, 1024, 4, None),          # the budget's floor
])
def test_pick_backward_blocks_from_shape(sq, sk, d, dv, itemsize, want):
    bq, bk = fa._pick_blocks(sq, sk, d, dv, itemsize, fa._bwd_vmem_bytes, fa._BWD_VMEM_BUDGET)
    if want is not None:
        assert (bq, bk) == want
    for n, t in ((sq, bq), (sk, bk)):
        assert t == n or (t % 128 == 0 and 0 <= (-n) % t < t)
    if (bq, bk) != (min(sq, 128), min(sk, 128)):
        assert fa._bwd_vmem_bytes(bq, bk, d, dv, itemsize) <= fa._BWD_VMEM_BUDGET


@pytest.mark.parametrize("sq, sk, d, dv, itemsize, want", [
    (2048, 2048, 64, 64, 2, (1024, 1024)),      # stablelm-1.6b training call
    (4096, 4096, 128, 128, 2, (1024, 1024)),    # qwen3-1.7b longest prefill
    (17, 17, 128, 128, 2, (17, 17)),            # short, unaligned: whole length
    (300, 1000, 64, 64, 4, (300, 1000)),
    (3000, 3000, 64, 64, 2, (1024, 1024)),      # pads 72, the least of any tile
    (2048, 2048, 64, 64, 4, (512, 512)),        # float32 tiles: budget binds
    (2048, 2048, 192, 128, 2, (512, 512)),      # MLA widths
    (1, 32768, 128, 128, 2, (1, 1024)),
    (8192, 8192, 1024, 1024, 4, None),          # the budget's floor
])
def test_pick_blocks_from_shape(sq, sk, d, dv, itemsize, want):
    bq, bk = fa._pick_blocks(sq, sk, d, dv, itemsize)
    if want is not None:
        assert (bq, bk) == want
    for n, t in ((sq, bq), (sk, bk)):
        assert t == n or (t % 128 == 0 and 0 <= (-n) % t < t)
        if t != n:
            assert (-n) % t == min((-n) % c for c in range(128, t + 1, 128))
    if (bq, bk) != (min(sq, 128), min(sk, 128)):
        assert fa._vmem_bytes(bq, bk, d, dv, itemsize) <= fa._VMEM_BUDGET


def test_flash_kernel_grad_matches_oracle_grad():
    q = rand((1, 2, 64, 32))
    k = rand((1, 2, 64, 32))
    v = rand((1, 2, 64, 32))

    def f_kernel(q, k, v):
        return flash_attention_pallas(q, k, v, True, None, None,
                                      32, 32, True).sum()

    def f_ref(q, k, v):
        return ref.flash_attention_dense_ref(q, k, v, causal=True).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def _grad_gaps(q, k, v, causal, window, bq, bk):
    """Largest gap of each of dq, dk, dv of the kernel from the dense
    oracle's gradient (in float32, on the same inputs), over the largest
    entry of the oracle's gradient. The loss weighs the output at random."""
    w = rand(q.shape[:3] + v.shape[3:])
    f32 = lambda x: x.astype(jnp.float32)

    def f_kernel(q, k, v):
        return (f32(flash_attention_pallas(q, k, v, causal, window, None, bq, bk, True))
                * w).sum()

    def f_ref(q, k, v):
        return (ref.flash_attention_dense_ref(f32(q), f32(k), f32(v), causal=causal,
                                              window=window) * w).sum()

    got = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, x in zip(got, (q, k, v), strict=True):
        assert a.shape == x.shape and a.dtype == x.dtype
    return [float(jnp.max(jnp.abs(f32(a) - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip(got, want, strict=True)]


FLASH_GRAD_CASES = [
    # (B, Hq, Hkv, Sq, Sk, D, Dv, causal, window, block_q, block_k)
    (1, 2, 2, 128, 128, 32, 32, True, None, 32, 32),     # causal, group 1
    (1, 2, 2, 128, 128, 32, 32, False, None, 32, 64),    # bidirectional
    (1, 2, 2, 128, 128, 32, 32, True, 40, 32, 32),       # local window
    (1, 8, 2, 128, 128, 32, 32, True, 40, 32, 32),       # GQA group 4, window
    (1, 4, 1, 128, 128, 32, 32, True, None, 64, 32),     # MQA, bq > bk
    (1, 2, 2, 128, 128, 48, 32, True, None, 32, 64),     # MLA: D != Dv, bq < bk
    (1, 2, 2, 96, 256, 32, 32, True, None, 32, 64),      # Sq < Sk, right-aligned
    (1, 2, 1, 90, 170, 32, 32, True, 70, 32, 64),        # window, q and k padded
    (1, 2, 2, 64, 200, 32, 32, False, None, 32, 64),     # bidirectional, keys padded
    (2, 4, 2, 40, 40, 16, 16, True, None, None, None),   # tiles from the shape
    (1, 2, 1, 1000, 1000, 32, 32, True, 300, 512, 512),  # edge tiles in halves, padded
    (1, 2, 2, 600, 1100, 32, 32, False, 200, 512, 768),  # halves of 256 and 384
]
#: float32: the kernel's own rounding; bfloat16: operands, p and ds rounded
#: to bf16 on the MXU against the float32 oracle
GRAD_TOL = {"f32": 1e-5, "bf16": 2e-2}


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("case", FLASH_GRAD_CASES)
def test_flash_kernel_grads_match_dense_oracle(case, dtype):
    B, Hq, Hkv, Sq, Sk, D, Dv, causal, window, bq, bk = case
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    q = rand((B, Hq, Sq, D), dt)
    k = rand((B, Hkv, Sk, D), dt)
    v = rand((B, Hkv, Sk, Dv), dt)
    gaps = _grad_gaps(q, k, v, causal, window, bq, bk)
    assert max(gaps) <= GRAD_TOL[dtype], gaps


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 3), st.integers(0, 2), st.integers(1, 4),
       st.booleans(), st.sampled_from([None, 32]))
def test_flash_kernel_property_sweep(b, hkv_pow, sq_blocks, causal, window):
    hkv = 2 ** hkv_pow
    hq = hkv * 2
    sq = 64 * sq_blocks
    q = rand((b, hq, sq, 32))
    k = rand((b, hkv, sq, 32))
    v = rand((b, hkv, sq, 32))
    out = flash_attention_pallas(q, k, v, causal, window, None, 64, 64, True)
    want = ref.flash_attention_dense_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    assert max(_grad_gaps(q, k, v, causal, window, 64, 64)) <= GRAD_TOL["f32"]


# ---------------------------------------------------------------------------
# RWKV6 WKV
# ---------------------------------------------------------------------------
def decays(shape):
    """per-step log decay within the documented clamp [-4, -1e-4]."""
    logw = -np.minimum(np.exp(RNG.normal(size=shape)), 4.0)
    return jnp.asarray(np.exp(np.minimum(logw, -1e-4)), jnp.float32)


WKV_CASES = [
    # (B, H, T, K, V, chunk, with_state)
    (1, 1, 32, 16, 16, 16, False),
    (2, 3, 64, 32, 32, 16, True),
    (1, 2, 128, 64, 64, 16, True),
    (2, 1, 48, 16, 32, 16, False),   # K != V
]


@pytest.mark.parametrize("case", WKV_CASES)
def test_wkv6_kernel_matches_sequential_oracle(case):
    B, H, T, K, V, chunk, with_state = case
    r = rand((B, H, T, K))
    k = rand((B, H, T, K))
    v = rand((B, H, T, V))
    w = decays((B, H, T, K))
    u = rand((H, K))
    s0 = rand((B, H, K, V)) if with_state else None
    out, sT = ops.wkv6(r, k, v, w, u, initial_state=s0, chunk=chunk,
                       impl="interpret")
    want, sT_want = ref.wkv6_ref(r, k, v, w, u, initial_state=s0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_want),
                               rtol=2e-4, atol=2e-4)


def test_wkv6_ops_pads_ragged_T():
    B, H, T, K = 1, 2, 21, 16     # T not a multiple of the chunk
    r = rand((B, H, T, K))
    k = rand((B, H, T, K))
    v = rand((B, H, T, K))
    w = decays((B, H, T, K))
    u = rand((H, K))
    out, sT = ops.wkv6(r, k, v, w, u, impl="interpret")
    want, sT_want = ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_want),
                               rtol=2e-4, atol=2e-4)


def test_wkv6_state_chaining_equals_one_shot():
    """Running two halves with carried state == one full pass (decode)."""
    B, H, T, K = 1, 2, 64, 16
    r, k, v = rand((B, H, T, K)), rand((B, H, T, K)), rand((B, H, T, K))
    w, u = decays((B, H, T, K)), rand((H, K))
    full, s_full = ops.wkv6(r, k, v, w, u, impl="ref")
    h = T // 2
    o1, s1 = ops.wkv6(r[:, :, :h], k[:, :, :h], v[:, :, :h], w[:, :, :h], u,
                      impl="ref")
    o2, s2 = ops.wkv6(r[:, :, h:], k[:, :, h:], v[:, :, h:], w[:, :, h:], u,
                      initial_state=s1, impl="ref")
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 2)),
                               np.asarray(full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------
RGLRU_CASES = [
    (1, 64, 32, 64, False),
    (2, 128, 96, 64, True),     # W > block → channel blocking
    (1, 100, 48, 32, True),     # ragged T and W (pad path)
]


@pytest.mark.parametrize("case", RGLRU_CASES)
def test_rglru_kernel_matches_sequential_oracle(case):
    B, T, W, chunk, with_state = case
    x = rand((B, T, W))
    a = jnp.asarray(1 / (1 + np.exp(-RNG.normal(size=(B, T, W)))), jnp.float32)
    h0 = rand((B, W)) if with_state else None
    h, hT = ops.rglru(x, a, initial_state=h0, chunk=chunk, block_w=64,
                      impl="interpret")
    want, hT_want = ref.rglru_ref(x, a, initial_state=h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_want),
                               rtol=2e-5, atol=2e-5)


def test_rglru_state_chaining_equals_one_shot():
    B, T, W = 2, 64, 32
    x = rand((B, T, W))
    a = jnp.asarray(1 / (1 + np.exp(-RNG.normal(size=(B, T, W)))), jnp.float32)
    full, s_full = ops.rglru(x, a, impl="ref")
    h = T // 2
    o1, s1 = ops.rglru(x[:, :h], a[:, :h], impl="ref")
    o2, s2 = ops.rglru(x[:, h:], a[:, h:], initial_state=s1, impl="ref")
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                               np.asarray(full), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Mamba-2 SSD
# ---------------------------------------------------------------------------
def _ssd_args(Bt, H, T, P, N, dtype=jnp.float32):
    """x, dt, A, B, C as the mixer makes them: dt = softplus(.) around its
    initial range, A = -U[1, 16]."""
    dt = jnp.asarray(np.log1p(np.exp(RNG.normal(size=(Bt, H, T)) - 3.0)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(1.0, 16.0, size=H), jnp.float32)
    return (rand((Bt, H, T, P), dtype), dt, A, rand((Bt, T, N), dtype), rand((Bt, T, N), dtype))


SSD_CASES = [
    # (Bt, H, T, P, N, chunk, with_state, dtype)
    (1, 2, 32, 16, 8, 16, False, jnp.float32),     # two whole chunks
    (2, 4, 40, 16, 8, 16, True, jnp.float32),      # ragged T, carried state
    (1, 16, 48, 8, 16, 16, True, jnp.float32),     # two steps of 8 heads
    (1, 3, 20, 16, 8, 32, True, jnp.float32),      # T under one chunk, odd heads
    (2, 4, 64, 32, 16, 32, True, jnp.bfloat16),    # bf16 operands on the MXU
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_kernel_matches_references(case):
    """Kernel (interpret) against the blocked jnp form and the sequential
    recurrence. float32: the three differ by reassociation only (2e-4,
    values of order 10). bfloat16: the kernel rounds C·Bᵀ's operands, the
    masked decay matrix and the state to bf16 (8 bits) before its matmuls,
    which the float32 references do not: a few % of the output's scale."""
    Bt, H, T, P, N, chunk, with_state, dtype = case
    x, dt, A, B, C = _ssd_args(Bt, H, T, P, N, dtype)
    h0 = rand((Bt, H, P, N)) if with_state else None
    before = metrics().counter("repro_kernel_ssd_chunks_total", kind="visited").value
    y, h = ops.ssd(x, dt, A, B, C, chunk=chunk, initial_state=h0, impl="interpret")
    visited = metrics().counter("repro_kernel_ssd_chunks_total", kind="visited").value - before
    assert visited == Bt * H * -(-T // chunk)
    y_seq, h_seq = ref.ssd_ref(x, dt, A, B, C, initial_state=h0)
    y_blk, h_blk = ref.ssd_chunked_ref(x, dt, A, B, C, chunk=chunk, initial_state=h0)
    assert y.shape == (Bt, H, T, P) and y.dtype == dtype and h.dtype == jnp.float32
    f32 = lambda a: np.asarray(a, np.float32)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2 * float(jnp.max(jnp.abs(f32(y_seq))))
    for got, want in ((y, y_seq), (h, h_seq), (y_blk, y_seq), (h_blk, h_seq)):
        np.testing.assert_allclose(f32(got), f32(want), rtol=2e-4, atol=tol)


def test_ssd_grad_matches_recurrence_grad():
    """The custom VJP (float32 chunked recompute) against autodiff of the
    sequential recurrence, for every input, the state's gradient included."""
    x, dt, A, B, C = _ssd_args(2, 4, 40, 16, 8)
    h0 = rand((2, 4, 16, 8))
    w = rand((2, 4, 40, 16))

    def loss(impl):
        def f(x, dt, A, B, C, h0):
            y, h = ops.ssd(x, dt, A, B, C, chunk=16, initial_state=h0, impl=impl)
            return jnp.sum(w * y) + jnp.sum(jnp.sin(h))
        return f

    got = jax.grad(loss("interpret"), argnums=range(6))(x, dt, A, B, C, h0)
    want = jax.grad(loss("dense"), argnums=range(6))(x, dt, A, B, C, h0)
    for g, v in zip(got, want, strict=True):
        scale = float(jnp.max(jnp.abs(v)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=1e-4, atol=1e-5 * scale)


def test_ssd_state_chaining_equals_one_shot():
    """Two calls, the second from the first's final state, equal one call:
    what prefill and a later prefill of the continuation rely on."""
    x, dt, A, B, C = _ssd_args(1, 4, 48, 16, 8)
    y, h = ops.ssd(x, dt, A, B, C, chunk=16, impl="interpret")
    y1, h1 = ops.ssd(x[:, :, :20], dt[:, :, :20], A, B[:, :20], C[:, :20], chunk=16,
                     impl="interpret")
    y2, h2 = ops.ssd(x[:, :, 20:], dt[:, :, 20:], A, B[:, 20:], C[:, 20:], chunk=16,
                     initial_state=h1, impl="interpret")
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 2)), np.asarray(y),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("op", ["flash_attention", "wkv6", "rglru", "ssd"])
def test_pallas_impl_refuses_non_tpu_backend(op, monkeypatch):
    """impl="pallas" means the compiled Mosaic kernel, never a silent
    fallback to the interpreter; off-TPU it must raise."""
    x = rand((1, 2, 16, 16))
    args = {"flash_attention": (x, x, x),
            "wkv6": (x, x, x, decays(x.shape), rand((2, 16))),
            "rglru": (x[0], decays(x[0].shape)),
            "ssd": _ssd_args(1, 2, 16, 16, 8)}[op]
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    with pytest.raises(RuntimeError, match="impl='interpret'"):
        getattr(ops, op)(*args, impl="pallas")


def _op_args(op):
    if op == "flash_attention":
        return (rand((2, 4, 64, 32)), rand((2, 2, 64, 32)), rand((2, 2, 64, 32))), {}
    if op == "wkv6":
        shape = (2, 4, 32, 16)
        return (rand(shape), rand(shape), rand(shape), decays(shape), rand((4, 16))), {
            "initial_state": rand((2, 4, 16, 16))}
    if op == "ssd":
        return _ssd_args(2, 4, 40, 16, 8), {"initial_state": rand((2, 4, 16, 8)),
                                            "chunk": 16}
    x = rand((2, 40, 32))
    a = jnp.asarray(1 / (1 + np.exp(-RNG.normal(size=x.shape))), jnp.float32)
    return (x, a), {"initial_state": rand((2, 32)), "chunk": 16, "block_w": 16}


@pytest.mark.parametrize("op", ["flash_attention", "wkv6", "rglru", "ssd"])
def test_ops_interpret_matches_ref(op):
    """The ops-level dispatch (argument plumbing, padding) around each kernel."""
    args, kw = _op_args(op)
    got = jax.tree.leaves(getattr(ops, op)(*args, impl="interpret", **kw))
    want = jax.tree.leaves(getattr(ops, op)(*args, impl="ref", **kw))
    for g, w in zip(got, want, strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4)


_PER_SHARD_SCRIPT = """
import jax, numpy as np
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models.layers import set_mesh_context
import test_kernels as tk

mesh = make_mesh((2, 2), ("data", "model"))
for op in ("flash_attention", "wkv6", "rglru", "ssd"):
    args, kw = tk._op_args(op)
    want = jax.tree.leaves(getattr(ops, op)(*args, impl="ref", **kw))
    set_mesh_context({"mesh": mesh, "dp_axes": ("data",), "model_axis": "model"})
    f = jax.jit(lambda *a, op=op, kw=kw: getattr(ops, op)(*a, impl="interpret", **kw))
    text = f.lower(*args).as_text()
    got = jax.tree.leaves(f(*args))
    set_mesh_context(None)
    assert "manual_computation" in text, op
    for g, w in zip(got, want, strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4)

# the flash backward's kernels run inside the same per-shard body as the forward
args, kw = tk._op_args("flash_attention")
loss = lambda impl: lambda *a: (ops.flash_attention(*a, impl=impl, **kw) ** 2).sum()
want = jax.grad(loss("ref"), argnums=(0, 1, 2))(*args)
set_mesh_context({"mesh": mesh, "dp_axes": ("data",), "model_axis": "model"})
f = jax.jit(jax.grad(loss("interpret"), argnums=(0, 1, 2)))
assert f.lower(*args).as_text().count("manual_computation") >= 2
got = f(*args)
set_mesh_context(None)
for g, w in zip(got, want, strict=True):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4)
print("per-shard ok")
"""


def test_kernels_run_per_shard_under_a_multi_device_mesh(tmp_path):
    """Mosaic calls cannot be partitioned by XLA: under a 2x2 mesh each op
    runs its kernel inside shard_map (batch on data, heads/width on model)
    and still matches the reference. Four host devices need their own
    process, since the device count is fixed when JAX starts."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(here, "..", "src"), here, os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", _PER_SHARD_SCRIPT], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "per-shard ok" in out.stdout


def test_unknown_impl_is_rejected():
    x = rand((1, 2, 16, 16))
    with pytest.raises(ValueError, match="unknown kernel impl"):
        ops.flash_attention(x, x, x, impl="mosaic")


def test_ref_blocked_equals_dense_large_window_cases():
    q = rand((1, 2, 96, 32))
    k = rand((1, 2, 96, 32))
    v = rand((1, 2, 96, 32))
    for window in (1, 16, 96, 200):
        a = ref.flash_attention_ref(q, k, v, causal=True, window=window,
                                    block_k=32)
        b = ref.flash_attention_dense_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)
