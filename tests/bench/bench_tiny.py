"""Tiny cells for rehearsing the benchmark on the CPU.

The configurations keep every mechanism of the real ones (LayerNorm,
partial rotary, q/k/v bias and an untied head for the training model;
RMSNorm, per-head q/k norm, GQA and a tied, padded vocabulary for the
served one) at widths a test can run, with the kernels in interpret mode.
"""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TRAIN_CFG = {
    "name": "tiny-stablelm", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 2,
    "vocab_size": 512, "partial_rotary_factor": 0.25, "rope_theta": 10000,
    "layer_norm_eps": 1e-05, "use_qkv_bias": True, "qk_layernorm": False,
    "tie_word_embeddings": False, "hidden_act": "silu", "norm": "layernorm",
    "param_dtype": "float32", "compute_dtype": "float32", "remat": "full",
    "z_loss_coef": 0.0001, "guarantees": {"journal_sync": "batch"},
}
SERVE_CFG = {
    "name": "tiny-qwen3", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "num_hidden_layers": 2, "vocab_size": 600, "rope_theta": 1000000,
    "rms_norm_eps": 1e-06, "attention_bias": False, "tie_word_embeddings": True,
    "hidden_act": "silu", "qk_norm": True, "norm": "rmsnorm",
    "param_dtype": "float32", "compute_dtype": "float32", "remat": "none",
}
TRAIN_MIX = {
    "driver": "train", "global_batch": 2, "seq_len": 64, "checkpoint_every": 1000,
    "optimizer": {"lr": 0.001, "b1": 0.9, "b2": 0.95, "eps": 1e-08, "weight_decay": 0.1,
                  "clip_norm": 1.0, "schedule": "cosine", "warmup_steps": 2,
                  "total_steps": 100},
}
SERVE_MIX = {
    "driver": "serve", "workers": 2, "allocation": ["context_affinity", "least_loaded"],
    "arrivals": "poisson", "schedule_seed": 7, "prompt_lengths": [16, 24], "prompt_weights": [0.5, 0.5],
    "new_tokens": 4, "sessions": 3,
}
TRAIN_SETTINGS = {"warm_steps": 5, "max_window_steps": 40, "trace_seconds": 1.0,
                  "attn_impl": "interpret",
                  "limits": {"grad_gap": 1e-3, "change_gap": 1e-3,
                             "data_mismatch": 0}}
SERVE_SETTINGS = {"rate": 4.0, "tail_percentile": 70, "check_requests": 3, "trace_seconds": 1.0,
                  "attn_impl": "interpret", "limits": {"logit_gap": 1e-3}}


def spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as fh:
        return json.load(fh)


def cell(kind: str, tmp: Path, *, seed: int = 3_000_000_019, seconds: float = 2.0,
         trace: bool = False) -> harness.Cell:
    """A tiny cell of ``kind`` (train | serve) running in ``tmp``."""
    name = {"train": "train.stablelm-1.6b.steady", "serve": "serve.qwen3-1.7b.agent"}[kind]
    real = harness.find_cell(name)
    cfg, mix, st = {"train": (TRAIN_CFG, TRAIN_MIX, TRAIN_SETTINGS),
                    "serve": (SERVE_CFG, SERVE_MIX, SERVE_SETTINGS)}[kind]
    c = harness.Cell(name=name, chips=1, config=copy.deepcopy(cfg), traffic=copy.deepcopy(mix),
                     settings=copy.deepcopy(st), end_to_end=real.end_to_end,
                     per_layer=real.per_layer, seed=seed, seconds=seconds, trace=trace,
                     out_dir=tmp / "bench-out")
    return c


def run(c: harness.Cell) -> dict:
    return harness.run_cell(c, t_start=time.perf_counter(), require_tpu=False,
                            compile_cache=False)
