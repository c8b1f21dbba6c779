"""CPU rehearsal of the training driver, its faults and its control.

A tiny configuration with every mechanism of the real one runs the whole
driver: set-up, window, the reference check. With the timed path broken
underneath, ``correct`` must come out false.
"""
from __future__ import annotations

from pathlib import Path

import jax
import numpy as np
import pytest

import bench_tiny
from bench import compare, inputs
from bench.harness import BENCH_DIR, load_module
from bench.reference import train as ref_train

DENSE = load_module(BENCH_DIR / "families" / "dense.py")


def test_train_cell_runs_and_is_correct(tmp_path: Path):
    line = bench_tiny.run(bench_tiny.cell("train", tmp_path, seed=3_000_000_019))
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(line["checks"]) == {"grad_gap", "change_gap", "data_mismatch"}
    assert list(line)[-1] == "checks"
    assert not (tmp_path / "bench-out").exists()


def test_traced_run_reports_per_layer_metrics(tmp_path: Path):
    line = bench_tiny.run(bench_tiny.cell("train", tmp_path, seed=17, trace=True))
    assert line["correct"] is True
    assert "train.step_node_ms" in line["metrics"]
    assert "train_tokens_per_s" not in line["metrics"]
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _broken_step(kind):
    from repro.train import steps

    real = steps.make_train_step

    def make(model, opt):
        step = real(model, opt)

        def broken(params, opt_state, batch):
            if kind == "unchanged":
                _, _, metrics = step(params, opt_state, batch)
                return params, opt_state, metrics
            # half_batch: the mean over the first half of the rows; no_exchange:
            # each of four chips steps on its own quarter of the rows, with no
            # gradient exchange, and the state read back is the first chip's
            keep = batch["tokens"].shape[0] // (2 if kind == "half_batch" else 4)
            return step(params, opt_state, {"tokens": batch["tokens"][:keep]})

        return broken

    return make


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange"])
def test_faults_under_the_timed_path_are_caught(tmp_path: Path, monkeypatch, fault):
    monkeypatch.setattr("repro.train.trainer.make_train_step", _broken_step(fault))
    cell = bench_tiny.cell("train", tmp_path, seed=5)
    if fault == "no_exchange":  # the data-parallel cell's batch: 2 rows a chip
        cell.traffic["global_batch"] = 8
    line = bench_tiny.run(cell)
    assert line["correct"] is False, line["checks"]


def test_control_fails_the_checks():
    """The reference with bfloat16 parameter storage reads above the limits."""
    cfg, mix = bench_tiny.TRAIN_CFG, bench_tiny.TRAIN_MIX
    seed = 23
    batches = [inputs.token_batch(seed, s, vocab=cfg["vocab_size"], seq_len=mix["seq_len"],
                                  rows=mix["global_batch"]) for s in range(3)]
    ref = ref_train.run(DENSE, cfg, seed, batches, mix["optimizer"])
    ctl = ref_train.run(DENSE, cfg, seed, batches, mix["optimizer"], param_dtype="bfloat16")
    gaps = compare.train_gaps(ctl, ref)
    limits = bench_tiny.TRAIN_SETTINGS["limits"]
    assert any(gaps[k] > limits[k] for k in ("grad_gap", "change_gap")), gaps


def test_chunked_reference_is_the_batch_mean():
    """Eight rows in chunks of two give the whole batch's loss and gradient."""
    cfg = bench_tiny.TRAIN_CFG
    tokens = inputs.token_batch(31, 0, vocab=cfg["vocab_size"], seq_len=32, rows=8)
    params = DENSE.make_weights(cfg, 31)
    grad_fn = DENSE.loss_and_grad(cfg)
    loss, grads = grad_fn(params, tokens)
    c_loss, c_grads = ref_train.batch_loss_and_grad(grad_fn, params, tokens)
    assert ref_train.CHUNK_ROWS < len(tokens)
    np.testing.assert_allclose(float(c_loss), float(loss), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(c_grads), jax.tree.leaves(grads), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
