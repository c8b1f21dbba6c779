"""Pure-jnp reference oracles for every Pallas kernel.

These are the ground truth the kernels are validated against AND the CPU
execution path of the model (ops.py dispatches here off-TPU). They are
written in the same *blocked/online* form as the kernels so that memory
behaviour under compilation (dry-run) is sane at 32k+ sequence lengths:
full S×S score materialization never happens.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_ref", "flash_attention_dense_ref", "wkv6_ref",
           "wkv6_chunked_ref", "rglru_ref", "rglru_scan_ref", "ssd_ref",
           "ssd_chunked_ref", "gated_delta_ref", "gated_delta_chunked_ref"]

_NEG_INF = -1e30


# ===========================================================================
# flash attention (causal / local-window, GQA)
# ===========================================================================

def flash_attention_dense_ref(q, k, v, *, causal: bool = True,
                              window: Optional[int] = None,
                              scale: Optional[float] = None) -> jnp.ndarray:
    """O(S²)-memory oracle — ONLY for small test shapes.

    q: (B, Hq, Sq, D), k/v: (B, Hkv, Sk, D). GQA: Hq % Hkv == 0.
    ``window``: each query attends to keys in (pos-window, pos] (local attn).
    """
    B, Hq, Sq, D = q.shape       # note: v may have a different head dim (MLA)
    Hkv = k.shape[1]
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    kx = jnp.repeat(k, g, axis=1)
    vx = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kx.astype(jnp.float32)) * scale
    Sk = k.shape[2]
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)  # right-aligned (decode-friendly)
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vx.astype(jnp.float32)).astype(q.dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None,
                        block_k: int = 512) -> jnp.ndarray:
    """Blocked online-softmax flash attention, pure jnp (the kernel oracle).

    Memory is O(Sq·D + block_k·D) per head — safe to *compile* at 32k/500k.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    Dv = v.shape[-1]             # may differ from D (MLA)
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    nblk = (Sk + block_k - 1) // block_k
    pad = nblk * block_k - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(B, Hkv, nblk, block_k, D)
    vb = v.reshape(B, Hkv, nblk, block_k, Dv)

    qf = q.astype(jnp.float32)
    qpos = jnp.arange(Sq) + (Sk - Sq)  # right-aligned positions

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, start = blk  # (B,Hkv,bk,D), (B,Hkv,bk,D), scalar
        kpos = start + jnp.arange(block_k)
        kq = jnp.repeat(kblk, g, axis=1).astype(jnp.float32)
        vq = jnp.repeat(vblk, g, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kq) * scale
        valid = kpos[None, :] < Sk
        msk = jnp.broadcast_to(valid, (Sq, block_k))
        if causal:
            msk &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            msk &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(msk[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vq)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hq, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hq, Sq, Dv), jnp.float32)
    starts = jnp.arange(nblk) * block_k
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0), starts))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.astype(q.dtype)


# ===========================================================================
# RWKV6 WKV: data-dependent-decay linear attention (Finch)
# ===========================================================================

def wkv6_ref(r, k, v, w, u, *, initial_state=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential oracle. Shapes:
      r,k,w: (B, H, T, K);  v: (B, H, T, V);  u: (H, K)
    Recurrence per head (S ∈ R^{K×V}):
      o_t = (r_t ⊙ u)ᵀ (k_t v_tᵀ)  +  r_tᵀ S_{t-1}
      S_t = diag(w_t) S_{t-1} + k_t v_tᵀ
    w is the *decay multiplier* in (0,1]: w_t = exp(-exp(log_w_t)).
    Returns (out (B,H,T,V), final_state (B,H,K,V)).
    """
    B, H, T, K = r.shape
    V = v.shape[-1]
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w))
    uf = u.astype(jnp.float32)
    S0 = (jnp.zeros((B, H, K, V), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(S, inp):
        rt, kt, vt, wt = inp  # (B,H,K),(B,H,K),(B,H,V),(B,H,K)
        kv = kt[..., :, None] * vt[..., None, :]            # (B,H,K,V)
        out = jnp.einsum("bhk,bhkv->bhv", rt * uf[None], kv) \
            + jnp.einsum("bhk,bhkv->bhv", rt, S)
        S_new = wt[..., :, None] * S + kv
        return S_new, out

    xs = (jnp.moveaxis(rf, 2, 0), jnp.moveaxis(kf, 2, 0),
          jnp.moveaxis(vf, 2, 0), jnp.moveaxis(wf, 2, 0))
    S, outs = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(outs, 0, 2).astype(r.dtype), S


def wkv6_chunked_ref(r, k, v, w, u, *, chunk: int = 16,
                     initial_state=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked-parallel form (what the TPU kernel computes): intra-chunk via
    masked matmuls (MXU-friendly), inter-chunk via carried state. Exactly
    equal to wkv6_ref in f32 (same order of ops per chunk).

    RANGE CONTRACT: the rank-1 factorization exp(cum_prev[c])·exp(-cum_s)
    is exact in f32 only while |Σ_chunk log w| ≲ 80. With the model-side
    clamp log w ≥ -4 (see models/rwkv.py) and chunk=16 the worst factored
    exponent is 4·15 = 60 — inside f32 range. Do not raise ``chunk`` without
    tightening the clamp.
    """
    B, H, T, K = r.shape
    V = v.shape[-1]
    assert T % chunk == 0, "pad T to a multiple of chunk"
    n = T // chunk
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w))
    uf = u.astype(jnp.float32)
    # per-chunk views: (n, B, H, c, ·)
    rc = jnp.moveaxis(rf.reshape(B, H, n, chunk, K), 2, 0)
    kc = jnp.moveaxis(kf.reshape(B, H, n, chunk, K), 2, 0)
    vc = jnp.moveaxis(vf.reshape(B, H, n, chunk, V), 2, 0)
    wc = jnp.moveaxis(wf.reshape(B, H, n, chunk, K), 2, 0)
    S0 = (jnp.zeros((B, H, K, V), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    tri_strict = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)

    def chunk_step(S, inp):
        rt, kt, vt, wt = inp                       # (B,H,c,·)
        logw = jnp.log(jnp.maximum(wt, 1e-38))
        cum = jnp.cumsum(logw, axis=2)             # D_t = Π_{τ≤t} w  (log)
        Dt = jnp.exp(cum)                          # (B,H,c,K)
        Dt_prev = jnp.exp(cum - logw)              # D_{t-1} = D_t / w_t
        r_hat = rt * Dt_prev                       # r_t ⊙ D_{t-1}
        k_hat = kt / jnp.maximum(Dt, 1e-30)        # k_s / D_s
        # cross-chunk: r̂ᵀ S0
        cross = jnp.einsum("bhck,bhkv->bhcv", r_hat, S)
        # intra-chunk strict-lower attention: (r̂ b̂ᵀ) masked
        att = jnp.einsum("bhck,bhsk->bhcs", r_hat, k_hat) * tri_strict[None, None]
        intra = jnp.einsum("bhcs,bhsv->bhcv", att, vt)
        # diagonal (bonus u) term
        diag = jnp.einsum("bhck,bhck,bhcv->bhcv", rt * uf[None, :, None, :], kt, vt) \
            if False else (rt * uf[None, :, None, :] * kt).sum(-1, keepdims=True) * vt
        out = cross + intra + diag
        # state update: S' = diag(D_c) S + Σ_s (D_c / D_s) k_s v_sᵀ
        D_last = Dt[:, :, -1, :]                   # (B,H,K)
        k_scaled = kt * jnp.exp(cum[:, :, -1:, :] - cum)  # (D_c / D_s) k_s
        S_new = D_last[..., :, None] * S + jnp.einsum("bhsk,bhsv->bhkv", k_scaled, vt)
        return S_new, out

    S, outs = jax.lax.scan(chunk_step, S0, (rc, kc, vc, wc))
    out = jnp.moveaxis(outs, 0, 2).reshape(B, H, T, V)
    return out.astype(r.dtype), S


# ===========================================================================
# RG-LRU (RecurrentGemma / Griffin)
# ===========================================================================

def rglru_ref(x, a, *, initial_state=None, reset_first: bool = False
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential oracle for the RG-LRU diagonal recurrence.

    x: (B, T, D) gated input  (already i_t ⊙ x_t);
    a: (B, T, D) per-step decay in (0,1)  (already a^{c·r_t});
    h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ x_t
    Returns (h (B,T,D), final_state (B,D)).
    """
    xf, af = x.astype(jnp.float32), a.astype(jnp.float32)
    h0 = (jnp.zeros(x.shape[::2], jnp.float32).reshape(x.shape[0], x.shape[2])
          if initial_state is None else initial_state.astype(jnp.float32))

    def step(h, inp):
        xt, at = inp
        h_new = at * h + jnp.sqrt(jnp.maximum(1.0 - at * at, 0.0)) * xt
        return h_new, h_new

    S, hs = jax.lax.scan(step, h0, (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(af, 1, 0)))
    return jnp.moveaxis(hs, 0, 1).astype(x.dtype), S


def rglru_scan_ref(x, a, *, initial_state=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Parallel associative-scan form (the kernel's math): identical result."""
    xf, af = x.astype(jnp.float32), a.astype(jnp.float32)
    gated = jnp.sqrt(jnp.maximum(1.0 - af * af, 0.0)) * xf
    if initial_state is not None:
        # fold h0 in as a virtual step 0: h_t = (Π a) h0 + scan(gated)
        pass

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    A, Bc = jax.lax.associative_scan(combine, (af, gated), axis=1)
    h = Bc
    if initial_state is not None:
        h = h + A * initial_state.astype(jnp.float32)[:, None, :]
    return h.astype(x.dtype), h[:, -1].astype(jnp.float32)


# ===========================================================================
# Mamba-2 SSD: selective state-space scan, scalar decay per head
# ===========================================================================

def ssd_ref(x, dt, A, B, C, *, initial_state=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential oracle. Shapes (one group of B/C shared by every head):
      x: (Bt, H, T, P);  dt: (Bt, H, T) step sizes > 0;  A: (H,) < 0;
      B, C: (Bt, T, N);  initial_state: (Bt, H, P, N).
    Per head, with h ∈ R^{P×N}:
      h_t = exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t;   y_t = h_t C_t
    Returns (y (Bt,H,T,P) in x's dtype, final state (Bt,H,P,N) float32).
    """
    Bt, H, T, P = x.shape
    N = B.shape[-1]
    xf, dtf, Bf, Cf = (a.astype(jnp.float32) for a in (x, dt, B, C))
    Af = A.astype(jnp.float32)
    h0 = (jnp.zeros((Bt, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(h, inp):
        xt, dtt, bt, ct = inp        # (Bt,H,P), (Bt,H), (Bt,N), (Bt,N)
        h = jnp.exp(dtt * Af)[..., None, None] * h \
            + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, ct)

    h, ys = jax.lax.scan(step, h0, (jnp.moveaxis(xf, 2, 0), jnp.moveaxis(dtf, 2, 0),
                                    jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0)))
    return jnp.moveaxis(ys, 0, 2).astype(x.dtype), h


def _pad_time(x, dt, B, C, chunk):
    """Pad T to a chunk multiple with dt = 0 steps: they neither decay nor
    add to the state, and their outputs are sliced off."""
    pad = (-x.shape[2]) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    return x, dt, B, C


def ssd_chunked_ref(x, dt, A, B, C, *, chunk: int = 256, initial_state=None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked dual form (what the TPU kernel computes), in float32 jnp.

    Per chunk of Q steps, with cum the in-chunk prefix sum of dt·A:
      y    = (L ∘ C Bᵀ ∘ dt) x + exp(cum) ⊙ (C h₀ᵀ),  L_ij = exp(cum_i − cum_j), i ≥ j
      h_Q  = exp(cum_Q) h₀ + Σ_j exp(cum_Q − cum_j) dt_j x_j ⊗ B_j
    The chunk's state is carried to the next in order. Each chunk step is
    rematerialized under differentiation, so its backward keeps one state
    per chunk, not the (Q, Q) decay matrices of every chunk.
    """
    Bt, H, T, P = x.shape
    N = B.shape[-1]
    xp, dtp, Bp, Cp = _pad_time(*(a.astype(jnp.float32) for a in (x, dt, B, C)), chunk)
    n = xp.shape[2] // chunk
    Af = A.astype(jnp.float32)
    h0 = (jnp.zeros((Bt, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    @jax.checkpoint
    def chunk_step(h, inp):
        xc, dtc, bc, cc = inp            # (Bt,H,Q,P), (Bt,H,Q), (Bt,Q,N), (Bt,Q,N)
        cum = jnp.cumsum(dtc * Af[None, :, None], axis=-1)             # (Bt,H,Q)
        seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf)
        g = jnp.einsum("bin,bjn->bij", cc, bc)                          # (Bt,Q,Q)
        m = jnp.exp(seg) * g[:, None] * dtc[..., None, :]               # (Bt,H,Q,Q)
        y = jnp.einsum("bhij,bhjp->bhip", m, xc) \
            + jnp.exp(cum)[..., None] * jnp.einsum("bin,bhpn->bhip", cc, h)
        last = cum[..., -1:]                                            # (Bt,H,1)
        w = jnp.exp(last - cum) * dtc                                   # (Bt,H,Q)
        h = jnp.exp(last)[..., None] * h + jnp.einsum("bhj,bhjp,bjn->bhpn", w, xc, bc)
        return h, y

    xs = (jnp.moveaxis(xp.reshape(Bt, H, n, chunk, P), 2, 0),
          jnp.moveaxis(dtp.reshape(Bt, H, n, chunk), 2, 0),
          jnp.moveaxis(Bp.reshape(Bt, n, chunk, N), 1, 0),
          jnp.moveaxis(Cp.reshape(Bt, n, chunk, N), 1, 0))
    h, ys = jax.lax.scan(chunk_step, h0, xs)
    y = jnp.moveaxis(ys, 0, 2).reshape(Bt, H, n * chunk, P)[:, :, :T]
    return y.astype(x.dtype), h


# ===========================================================================
# Gated delta rule: linear attention with a key-dependent erase
# ===========================================================================

def gated_delta_ref(q, k, v, g, beta, *, initial_state=None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential oracle (Yang, Kautz & Hatamizadeh, arXiv:2412.06464). Shapes:
      q, k: (B, H, T, K);  v: (B, H, T, V);  g: (B, H, T) log decay ≤ 0;
      beta: (B, H, T) in [0, 2];  initial_state: (B, H, V, K).
    Per head, with S ∈ R^{V×K} and α_t = exp(g_t):
      S_t = α_t S_{t-1} (I − β_t k_t k_tᵀ) + β_t v_t k_tᵀ;   o_t = S_t q_t
    Returns (o (B,H,T,V) in v's dtype, final state (B,H,V,K) float32).
    """
    Bt, H, T, K = q.shape
    V = v.shape[-1]
    qf, kf, vf, gf, bf = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    S0 = (jnp.zeros((Bt, H, V, K), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(S, inp):
        qt, kt, vt, gt, bt = inp     # (Bt,H,K), (Bt,H,K), (Bt,H,V), (Bt,H), (Bt,H)
        S = jnp.exp(gt)[..., None, None] * S
        u = bt[..., None] * (vt - jnp.einsum("bhvk,bhk->bhv", S, kt))
        S = S + u[..., :, None] * kt[..., None, :]
        return S, jnp.einsum("bhvk,bhk->bhv", S, qt)

    S, os_ = jax.lax.scan(step, S0, tuple(jnp.moveaxis(a, 2, 0) for a in (qf, kf, vf, gf, bf)))
    return jnp.moveaxis(os_, 0, 2).astype(v.dtype), S


def _pad_chunks(q, k, v, g, beta, chunk):
    """Pad T to a chunk multiple with steps that change nothing: g = 0
    (no decay), β = 0 and k = 0 (no write); their outputs are sliced off."""
    pad = (-q.shape[2]) % chunk
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, 0), (0, pad))) for a in (g, beta))
    return q, k, v, g, beta


def gated_delta_chunked_ref(q, k, v, g, beta, *, chunk: int = 64, initial_state=None
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked WY form (DeltaNet, arXiv:2406.06484), in float32 jnp.

    Per chunk of Q steps from state S₀, with γ the in-chunk prefix sum of g,
    Γ = exp(γ) and the strictly lower A_tj = β_t exp(γ_t − γ_j) k_t·k_j:
      U = (I + A)⁻¹ (diag(β) V − diag(βΓ) K S₀ᵀ)      (the written values)
      O = diag(Γ) Q S₀ᵀ + (M ∘ Q Kᵀ) U,  M_tj = exp(γ_t − γ_j), j ≤ t
      S_Q = Γ_Q S₀ + Uᵀ diag(exp(γ_Q − γ)) K
    The triangular system is solved directly here; the kernel inverts it by
    blocks. The chunk's state is carried to the next in order.
    """
    Bt, H, T, K = q.shape
    V = v.shape[-1]
    qp, kp, vp, gp, bp = _pad_chunks(
        *(a.astype(jnp.float32) for a in (q, k, v, g, beta)), chunk)
    n = qp.shape[2] // chunk
    S0 = (jnp.zeros((Bt, H, V, K), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    incl = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    eye = jnp.eye(chunk, dtype=jnp.float32)

    def chunk_step(S, inp):
        qc, kc, vc, gc, bc = inp     # (Bt,H,Q,K), (Bt,H,Q,K), (Bt,H,Q,V), (Bt,H,Q) x 2
        cum = jnp.cumsum(gc, axis=-1)
        decay = jnp.exp(jnp.where(incl, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        kk = jnp.einsum("bhik,bhjk->bhij", kc, kc)
        a = jnp.where(strict, bc[..., :, None] * decay * kk, 0.0)
        rhs = jnp.concatenate([bc[..., None] * vc,
                               (bc * jnp.exp(cum))[..., None] * kc], axis=-1)
        sol = jax.scipy.linalg.solve_triangular(eye + a, rhs, lower=True, unit_diagonal=True)
        u = sol[..., :V] - jnp.einsum("bhik,bhvk->bhiv", sol[..., V:], S)
        o = jnp.exp(cum)[..., None] * jnp.einsum("bhik,bhvk->bhiv", qc, S) \
            + jnp.einsum("bhij,bhjv->bhiv", decay * jnp.einsum("bhik,bhjk->bhij", qc, kc), u)
        last = cum[..., -1:]
        S = jnp.exp(last)[..., None] * S \
            + jnp.einsum("bhjv,bhjk->bhvk", u, jnp.exp(last - cum)[..., None] * kc)
        return S, o

    xs = tuple(jnp.moveaxis(a.reshape(a.shape[:2] + (n, chunk) + a.shape[3:]), 2, 0)
               for a in (qp, kp, vp, gp, bp))
    S, os_ = jax.lax.scan(chunk_step, S0, xs)
    o = jnp.moveaxis(os_, 0, 2).reshape(Bt, H, n * chunk, V)[:, :, :T]
    return o.astype(v.dtype), S
