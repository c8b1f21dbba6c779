"""The kernel counter against hand counts."""
from __future__ import annotations

import pytest

import bench_tiny
from bench.harness import BENCH_DIR, load_module, peaks_for

fa = load_module(BENCH_DIR / "kernels" / "flash_attention.py")
PEAKS = peaks_for("TPU v5 lite")


def call(**kw):
    c = {"batch": 1, "heads": 1, "kv_heads": 1, "q_len": 4, "kv_len": 4, "head_dim": 8,
         "causal": True, "dtype_bytes": 2}
    c.update(kw)
    return c


def test_pairs_by_hand():
    assert fa.pairs(4, 4, True) == 1 + 2 + 3 + 4
    assert fa.pairs(4, 4, False) == 16
    assert fa.pairs(2, 5, True) == 4 + 5  # right-aligned queries see the prefix
    assert fa.pairs(1, 7, True) == 7      # one decode query sees the whole cache


def test_flops_and_bytes_by_hand():
    # 10 kept pairs x 8 dims x 2 matmuls x 2 FLOPs
    assert fa.flops(call()) == 10 * 8 * 2 * 2
    # q and o: 4 x 8 each; k and v: 4 x 8 each; bf16
    assert fa.bytes_moved(call()) == (4 * 8 * 2 + 4 * 8 * 2) * 2
    # GQA: k/v bytes follow the kv heads
    c = call(heads=8, kv_heads=2)
    assert fa.bytes_moved(c) == (2 * 8 * 4 * 8 + 2 * 2 * 4 * 8) * 2
    assert fa.flops(c) == 8 * fa.flops(call())


def test_latent_attention_counts_v_at_its_own_width():
    # MLA: q and k 192 wide (128 + 64 rotary), v and o 128 wide
    c = call(heads=16, kv_heads=16, head_dim=192, v_head_dim=128)
    assert fa.flops(c) == 10 * 16 * (192 + 128) * 2
    q, o, k, v = (16 * 4 * w for w in (192, 128, 192, 128))
    assert fa.bytes_moved(c) == (q + o + k + v) * 2
    # without v_head_dim every count is at head_dim, as before
    same = call(heads=16, kv_heads=16, head_dim=192)
    assert fa.flops(same) == fa.flops(dict(same, v_head_dim=192)) == 10 * 16 * 192 * 2 * 2


def test_training_call_is_compute_bound():
    # the train cell's call: 2 x 32 heads x 2048 positions x 64 dims
    c = call(batch=2, heads=32, kv_heads=32, q_len=2048, kv_len=2048, head_dim=64)
    t, bound = fa.roofline_s(c, PEAKS)
    assert bound == "compute"
    assert t == pytest.approx(fa.flops(c) / 197e12)
    assert fa.flops(c) / fa.bytes_moved(c) > PEAKS["bf16_flops_per_s"] / PEAKS["hbm_bytes_per_s"]


def test_train_flops_per_token_by_hand():
    dense = load_module(BENCH_DIR / "families" / "dense.py")
    cfg = dict(bench_tiny.TRAIN_CFG)
    d, ff, V, L, S = 64, 128, 512, 2, 64
    matmul = L * (4 * d * d + 3 * d * ff) + d * V
    attn = L * 2 * d * S  # QK^T and PV over half the positions, 2 FLOPs each
    assert dense.flops_per_token(cfg, S) == 6 * matmul + 3 * attn
    assert dense.attention_calls(cfg, 2, S) == {
        "batch": 2, "heads": 4, "kv_heads": 4, "q_len": S, "kv_len": S, "head_dim": 16,
        "causal": True, "dtype_bytes": 2}
