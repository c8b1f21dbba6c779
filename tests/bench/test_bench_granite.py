"""The granite_hybrid family: its cut, its reference against the program, its
scan kernel's counts and matcher, and a tiny run of its cell on the CPU."""
from __future__ import annotations

import copy
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import compare, harness, inputs
from bench.harness import BENCH_DIR, load_module, peaks_for
from test_bench_spec import reduced_errors

CELL = "train.granite-4.0-h-micro.steady"
family = load_module(BENCH_DIR / "families" / "granite_hybrid.py")
ssd = load_module(BENCH_DIR / "kernels" / "ssd.py")

#: every mechanism of the configuration at widths a test can run: 4 Mamba-2
#: heads of 32 (expand 2), state 16, chunks of 16, GQA 4/2 without rotary,
#: the four multipliers, a tied vocabulary that pads to 512
TINY = {
    "name": "tiny-granite-h", "bench_family": "granite_hybrid", "hidden_size": 64,
    "intermediate_size": 96, "shared_intermediate_size": 96, "num_hidden_layers": 3,
    "layer_types": ["mamba", "attention", "mamba"], "num_attention_heads": 4,
    "num_key_value_heads": 2, "attention_bias": False, "attention_multiplier": 0.015625,
    "position_embedding_type": "nope", "rope_theta": 10000, "mamba_n_heads": 4,
    "mamba_d_head": 32, "mamba_expand": 2, "mamba_d_state": 16, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "mamba_chunk_size": 16, "num_local_experts": 0, "hidden_act": "silu",
    "rms_norm_eps": 1e-05, "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "tie_word_embeddings": True, "vocab_size": 500,
    "param_dtype": "float32", "compute_dtype": "float32", "remat": "full",
    "z_loss_coef": 0.0001, "guarantees": {"journal_sync": "batch"},
}


def _config():
    return harness.load_json(bench_tiny.ROOT / "bench/configs/granite-4.0-h-micro.json")


@pytest.mark.parametrize("vocab,ok", [(12544, True), (12543, False)],
                         ids=["an_eighth", "under_an_eighth"])
def test_the_cut_keeps_the_contract(vocab, ok):
    cfg = dict(_config(), vocab_size=vocab)
    reduced = ["num_hidden_layers", "layer_types", "vocab_size"]
    assert (reduced_errors(reduced, cfg) == []) is ok, reduced_errors(reduced, cfg)


def test_the_cut_is_one_whole_period():
    cfg = _config()
    published = cfg["published"]["layer_types"]
    assert cfg["layer_types"] == published[:10] == published[10:20]
    assert family.kinds(cfg).count("mamba") == 9
    assert family.runs(cfg) == [("mamba", 5), ("attention", 1), ("mamba", 4)]
    assert 8 * cfg["vocab_size"] == cfg["published"]["vocab_size"]


def test_parameters_and_flops_by_hand():
    cfg = _config()
    d, ff, di, N, H = 2048, 8192, 4096, 128, 64
    mamba = d * (2 * di + 2 * N + H) + di * d + 3 * d * ff
    attn = 2 * d * 2048 + 2 * d * 512 + 3 * d * ff
    head = d * 12544
    # with the conv (weights and bias), the per-head vectors and the norms
    assert mamba + 5 * (di + 2 * N) + 3 * H + di + 2 * d == 76_182_976
    per_token = family.flops_per_token(cfg, 2048)
    scan = ssd.flops(family.ssd_calls(cfg, 1, 2048)) / 2048
    assert per_token == pytest.approx(6 * (9 * mamba + attn + head) + 3 * 2 * 32 * 64 * 2048
                                      + 3 * 9 * scan)
    assert 4.6e9 < 6 * (9 * mamba + attn + head) < 4.7e9


def test_scan_counts_by_hand():
    c = {"batch": 1, "heads": 2, "seq": 8, "head_dim": 4, "state": 3, "chunk": 4,
         "dtype_bytes": 2}
    pairs = 4 * 5 / 2
    # two chunks: C Bᵀ once per chunk; per head (L∘CBᵀ)x and the two state products
    assert ssd.flops(c) == 2 * 2 * (3 * pairs + 2 * (4 * pairs + 2 * 4 * 3 * 4))
    assert ssd.bytes_moved(c) == 2 * 2 * 8 * 4 * 2 + 2 * 8 * 3 * 2 + 4 * 2 * 8 + 2 * 4 * 2 * 4 * 3
    assert ssd.visited(dict(c, seq=9)) == 2 * 3
    # the training call is memory-bound on a v5e: about 170 FLOPs a byte
    call = family.ssd_calls(_config(), 2, 2048)
    t, bound = ssd.roofline_s(call, peaks_for("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(ssd.bytes_moved(call) / 819e9)


def _event(result, operands):
    from bench import trace as tr

    return tr.Event(f"%custom-call.3 = {result} custom-call({operands}), "
                    f'custom_call_target="tpu_custom_call", kernel_metadata={{}}', 0.0, 10.0)


X = "bf16[2,64,2048,64]{3,2,1,0} %x, "
ROWS = "f32[2,64,2048]{2,1,0} %dt, f32[2,64,2048]{2,1,0} %cum, "
BC = "bf16[2,2048,128]{2,1,0} %b, bf16[2,2048,128]{2,1,0} %c, "
H0 = "f32[2,64,64,128]{3,2,1,0} %h0"
OUT = "(bf16[2,64,2048,64]{3,2,1,0}, f32[2,64,64,128]{3,2,1,0})"


@pytest.mark.parametrize("result,operands,forward", [
    (OUT, X + ROWS + BC + H0, True),
    # the flash forward of the same cell: one output, three operands
    ("bf16[2,32,2048,64]{3,2,1,0}", "bf16[2,32,2048,64]{3,2,1,0} %q, "
     "bf16[2,8,2048,64]{3,2,1,0} %k, bf16[2,8,2048,64]{3,2,1,0} %v", False),
    # another state width
    ("(bf16[2,64,2048,64]{3,2,1,0}, f32[2,64,64,64]{3,2,1,0})", X + ROWS + BC + H0, False),
    # a call that reads something more
    (OUT, X + ROWS + BC + H0 + ", f32[64]{0} %a", False),
], ids=["forward", "flash", "other_state", "seven_operands"])
def test_scan_matcher_takes_only_the_forward(result, operands, forward):
    call = family.ssd_calls(_config(), 2, 2048)
    assert ssd.matcher(call)(_event(result, operands)) is forward


def test_kernel_counter_matches_ssd_calls():
    """One lowering of the scan at the family's call shape adds the grid
    steps that ``bench/kernels/ssd.py`` counts for it."""
    from repro.kernels import ops
    from repro.obs.metrics import metrics

    call = family.ssd_calls(TINY, 2, 40)
    b, h, s, p, n = call["batch"], call["heads"], call["seq"], call["head_dim"], call["state"]
    r = np.random.default_rng(5)
    f = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    counter = metrics().counter("repro_kernel_ssd_chunks_total", kind="visited")
    before = counter.value
    ops.ssd(f(b, h, s, p), jnp.abs(f(b, h, s)) * 0.1, -jnp.abs(f(h)) - 1.0, f(b, s, n),
            f(b, s, n), chunk=call["chunk"], impl="interpret")
    assert counter.value - before == ssd.visited(call) == 2 * 4 * 3


def _program(cfg, impl="interpret"):
    from repro.models import build

    model = build(family.program_config(cfg, attn_impl=impl))
    family.check_layout(cfg, model)
    return model


def test_program_matches_the_reference_on_seeded_weights():
    """Loss and per-leaf gradient norms of the program (float32 compute,
    kernels interpreted) against the token-by-token float32 reference, on
    the family's seeded weights. They differ by float32 reassociation (the
    chunked scan, the flash recurrence): a relative 1e-4 of the loss and
    1e-3 of a leaf's norm (over the median leaf's, as ``grad_gap`` reads it)
    hold that with room, while a dropped multiplier or a wrong decay moves
    both by far more."""
    params = family.make_weights(TINY, 3_000_000_041)
    tokens = inputs.token_batch(3_000_000_041, 0, vocab=TINY["vocab_size"], seq_len=40, rows=2)
    model = _program(TINY)
    loss, grads = jax.value_and_grad(lambda p: model.loss_fn(p, {"tokens": tokens})[0])(params)
    ref_loss, ref_grads = family.loss_and_grad(TINY)(params, jnp.asarray(tokens))
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-4)
    got, want = compare.device_slice_norms(grads), compare.device_slice_norms(ref_grads)
    assert set(got) == set(want) and len(want) > 20
    gap, leaf = compare.norm_gap(got, want)
    assert gap < 1e-3, (gap, leaf)
    # the planted fault of the checks: a reference without the residual
    # multiplier is far outside that
    off = family.loss_and_grad(dict(TINY, residual_multiplier=1.0))(params, jnp.asarray(tokens))
    assert compare.norm_gap(compare.device_slice_norms(off[1]), want)[0] > 0.1


def test_weights_follow_mamba2_initialisation():
    cfg = dict(TINY, layer_types=["mamba", "mamba", "attention"])
    w = family.make_weights(cfg, 7)
    m = w["seg0"]["u0"]["mamba"]
    A = np.exp(np.asarray(m["A_log"]))
    dt = np.log1p(np.exp(np.asarray(m["dt_bias"])))
    assert m["A_log"].shape == (2, 4) and 1.0 <= A.min() and A.max() <= 16.0
    assert 1e-3 <= dt.min() and dt.max() <= 1e-1
    assert np.all(np.asarray(m["D"]) == 1.0)
    assert np.abs(np.asarray(m["conv_b"])).max() <= 0.5
    assert not np.allclose(np.asarray(m["A_log"])[0], np.asarray(m["A_log"])[1])
    again = family.make_weights(cfg, 7)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(again),
                                                   strict=True))


def test_a_program_without_the_layer_fails_cleanly(monkeypatch):
    """A program older than the Mamba-2 layer exits the run at once, with the
    harness's code for a cell it cannot run."""
    import dataclasses
    import types

    from repro.configs import base

    fields = [f for f in dataclasses.fields(base.ModelConfig) if not f.name.startswith("mamba_")]
    monkeypatch.setattr(family, "dataclasses", types.SimpleNamespace(fields=lambda cls: fields))
    with pytest.raises(harness.BenchError) as e:
        family.program_config(TINY)
    assert e.value.code == harness.EXIT_BAD_CELL


def _tiny_cell(tmp_path, seed):
    real = harness.find_cell(CELL)
    return harness.Cell(name=CELL, chips=1, config=copy.deepcopy(TINY),
                        traffic=dict(copy.deepcopy(bench_tiny.TRAIN_MIX), seq_len=40),
                        settings=copy.deepcopy(bench_tiny.TRAIN_SETTINGS),
                        end_to_end=real.end_to_end, per_layer=real.per_layer,
                        seed=seed, seconds=2.0, out_dir=tmp_path / "bench-out")


def _run(cell):
    return harness.run_cell(cell, t_start=time.perf_counter(), require_tpu=False,
                            compile_cache=False)


def test_tiny_cell_runs_through_client_train(tmp_path):
    real = harness.find_cell(CELL)
    assert {m["name"] for m in real.per_layer} >= {
        "train.ssd_fwd_roofline", "train.ssd_fwd_ms", "train.flash_fwd_roofline", "train.mfu"}
    line = _run(_tiny_cell(tmp_path, 3_000_000_043))
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    json.dumps(line)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_faults_under_the_timed_path_are_caught(tmp_path, monkeypatch, fault):
    from test_bench_train_driver import _broken_step

    monkeypatch.setattr("repro.train.trainer.make_train_step", _broken_step(fault))
    line = _run(_tiny_cell(tmp_path, 3_000_000_047))
    assert line["correct"] is False, line["checks"]


def test_control_fails_the_checks():
    """The reference with bfloat16 parameter storage reads above the limits."""
    from bench.reference import train as ref_train

    mix = bench_tiny.TRAIN_MIX
    batches = [inputs.token_batch(29, s, vocab=TINY["vocab_size"], seq_len=40,
                                  rows=mix["global_batch"]) for s in range(3)]
    ref = ref_train.run(family, TINY, 29, batches, mix["optimizer"])
    ctl = ref_train.run(family, TINY, 29, batches, mix["optimizer"], param_dtype="bfloat16")
    gaps = compare.train_gaps(ctl, ref)
    limits = bench_tiny.TRAIN_SETTINGS["limits"]
    assert any(gaps[k] > limits[k] for k in ("grad_gap", "change_gap")), gaps
