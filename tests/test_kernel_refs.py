"""Numeric equivalence of the blocked/chunked/scan reference forms vs the
sequential oracles in kernels/ref.py (jax-gated).

ref.py deliberately carries TWO forms of each recurrence: a sequential
oracle (ground truth) and the restructured form the Pallas kernel computes
(online-softmax blocks, chunked-parallel WKV, associative scan). This suite
pins the restructurings themselves — values AND gradients — so a kernel
regression can be bisected to "kernel vs ref" or "ref vs oracle".
"""

import pytest

jax = pytest.importorskip("jax", reason="kernel ref tests need jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ref  # noqa: E402

RNG = np.random.default_rng(11)


def rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


def close(a, b, *, rtol=2e-5, atol=2e-5):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=rtol, atol=atol
    )


# ---------------------------------------------------------------------------
# flash attention: blocked online-softmax vs dense
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Hq, Hkv, Sq, Sk, D, Dv, causal, window, block_k)
    (1, 2, 2, 96, 96, 32, 32, True, None, 32),       # multi-block causal
    (2, 4, 2, 96, 96, 32, 32, True, None, 32),       # GQA
    (1, 4, 1, 64, 64, 32, 32, True, None, 16),       # MQA
    (1, 2, 2, 96, 96, 32, 32, False, None, 32),      # bidirectional
    (1, 2, 2, 96, 96, 32, 32, True, 40, 32),         # local window
    (1, 2, 2, 100, 100, 32, 32, True, None, 32),     # ragged: Sk % block_k != 0
    (1, 2, 2, 32, 96, 32, 32, True, None, 32),       # Sq < Sk (decode chunk)
    (1, 2, 2, 64, 64, 48, 24, True, None, 32),       # MLA: Dv != D
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_blocked_matches_dense(case):
    B, Hq, Hkv, Sq, Sk, D, Dv, causal, window, block_k = case
    q = rand((B, Hq, Sq, D))
    k = rand((B, Hkv, Sk, D))
    v = rand((B, Hkv, Sk, Dv))
    dense = ref.flash_attention_dense_ref(q, k, v, causal=causal, window=window)
    blocked = ref.flash_attention_ref(
        q, k, v, causal=causal, window=window, block_k=block_k
    )
    close(blocked, dense)


def test_flash_blocked_matches_dense_grads():
    q = rand((1, 2, 48, 32))
    k = rand((1, 2, 48, 32))
    v = rand((1, 2, 48, 32))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v, causal=True) ** 2).sum()

    gd = jax.grad(loss(ref.flash_attention_dense_ref), argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(
        loss(lambda *a, **kw: ref.flash_attention_ref(*a, block_k=16, **kw)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for dense_g, blocked_g in zip(gd, gb, strict=True):
        close(blocked_g, dense_g, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# RWKV6 WKV: chunked-parallel vs sequential
# ---------------------------------------------------------------------------


def _wkv_inputs(B=2, H=2, T=32, K=16, V=24):
    r = rand((B, H, T, K), scale=0.5)
    k = rand((B, H, T, K), scale=0.5)
    v = rand((B, H, T, V), scale=0.5)
    # decay multiplier in (0,1], bounded below per the ref.py range contract
    w = jnp.exp(-jnp.exp(jnp.clip(rand((B, H, T, K)), -4.0, 1.0)))
    u = rand((H, K), scale=0.5)
    return r, k, v, w, u


@pytest.mark.parametrize("chunk", [8, 16])
@pytest.mark.parametrize("with_state", [False, True])
def test_wkv6_chunked_matches_sequential(chunk, with_state):
    r, k, v, w, u = _wkv_inputs()
    s0 = rand((2, 2, 16, 24), scale=0.3) if with_state else None
    out_seq, S_seq = ref.wkv6_ref(r, k, v, w, u, initial_state=s0)
    out_chk, S_chk = ref.wkv6_chunked_ref(r, k, v, w, u, chunk=chunk, initial_state=s0)
    close(out_chk, out_seq, rtol=1e-4, atol=1e-4)
    close(S_chk, S_seq, rtol=1e-4, atol=1e-4)


def test_wkv6_chunked_matches_sequential_grads():
    r, k, v, w, u = _wkv_inputs(B=1, H=1, T=16, K=8, V=8)

    def loss(fn):
        return lambda r, k, v, w: (fn(r, k, v, w, u)[0] ** 2).sum()

    gs = jax.grad(loss(ref.wkv6_ref), argnums=(0, 1, 2, 3))(r, k, v, w)
    gc = jax.grad(
        loss(lambda *a: ref.wkv6_chunked_ref(*a, chunk=8)), argnums=(0, 1, 2, 3)
    )(r, k, v, w)
    for seq_g, chk_g in zip(gs, gc, strict=True):
        close(chk_g, seq_g, rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# RG-LRU: associative scan vs sequential
# ---------------------------------------------------------------------------


def _rglru_inputs(B=2, T=33, D=16):
    x = rand((B, T, D))
    a = jnp.asarray(RNG.uniform(0.05, 0.98, size=(B, T, D)), jnp.float32)
    return x, a


@pytest.mark.parametrize("with_state", [False, True])
def test_rglru_scan_matches_sequential(with_state):
    x, a = _rglru_inputs()
    h0 = rand((2, 16), scale=0.5) if with_state else None
    h_seq, S_seq = ref.rglru_ref(x, a, initial_state=h0)
    h_scan, S_scan = ref.rglru_scan_ref(x, a, initial_state=h0)
    close(h_scan, h_seq)
    close(S_scan, S_seq)


def test_rglru_scan_matches_sequential_grads():
    x, a = _rglru_inputs(B=1, T=17, D=8)

    def loss(fn):
        return lambda x, a: (fn(x, a)[0] ** 2).sum()

    gx_s, ga_s = jax.grad(loss(ref.rglru_ref), argnums=(0, 1))(x, a)
    gx_p, ga_p = jax.grad(loss(ref.rglru_scan_ref), argnums=(0, 1))(x, a)
    close(gx_p, gx_s, rtol=1e-4, atol=1e-4)
    close(ga_p, ga_s, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Mamba-2 SSD: chunked dual form vs the sequential recurrence
# ---------------------------------------------------------------------------
def _ssd_inputs(Bt=2, H=3, T=50, P=8, N=6):
    r = np.random.default_rng(11)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    dt = jnp.asarray(np.log1p(np.exp(r.normal(size=(Bt, H, T)) - 2.0)), jnp.float32)
    A = -jnp.asarray(r.uniform(1.0, 16.0, size=H), jnp.float32)
    return f(Bt, H, T, P), dt, A, f(Bt, T, N), f(Bt, T, N), f(Bt, H, P, N)


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("with_state", [False, True])
def test_ssd_chunked_matches_sequential(chunk, with_state):
    x, dt, A, B, C, h0 = _ssd_inputs()
    h0 = h0 if with_state else None
    y, h = ref.ssd_chunked_ref(x, dt, A, B, C, chunk=chunk, initial_state=h0)
    ys, hs = ref.ssd_ref(x, dt, A, B, C, initial_state=h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ys), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hs), rtol=1e-4, atol=1e-4)


def test_ssd_chunked_matches_sequential_grads():
    args = _ssd_inputs(T=37)

    def loss(fn):
        return lambda *a: sum(jnp.sum(jnp.cos(o)) for o in fn(*a[:5], initial_state=a[5]))

    g1 = jax.grad(loss(lambda *a, **k: ref.ssd_chunked_ref(*a, chunk=16, **k)),
                  argnums=range(6))(*args)
    g2 = jax.grad(loss(ref.ssd_ref), argnums=range(6))(*args)
    for a, b in zip(g1, g2, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))
