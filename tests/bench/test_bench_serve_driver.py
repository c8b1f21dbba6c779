"""CPU rehearsal of the serving driver, its faults and its control."""
from __future__ import annotations

from pathlib import Path

import numpy as np

import bench_tiny
from bench import inputs
from bench.drivers import serve
from bench.harness import BENCH_DIR, load_module


def test_serve_cell_runs_and_is_correct(tmp_path: Path):
    line = bench_tiny.run(bench_tiny.cell("serve", tmp_path, seed=3_000_000_019,
                                          seconds=1.5))
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 6
    m = line["metrics"]
    assert m["serve_latency_p70_s"]["value"] > 0 and m["serve_tokens_per_s"]["value"] > 0
    assert list(line)[-1] == "checks"


def _wrap_registry(monkeypatch, fault):
    import repro.launch.serve as launch_serve

    real = launch_serve.build_registry

    def build(cfg, model, params):
        if fault == "unchanged":
            decode = model.decode_step

            def stale(p, cache, batch):
                logits, _ = decode(p, cache, batch)
                return logits, cache

            model = type(model)(**{**model.__dict__, "decode_step": stale})
        reg = real(cfg, model, params)
        if fault == "altered":
            gen = reg.get("generate")

            def altered(ctx, prompt, new_tokens):
                out = gen(ctx, prompt, new_tokens)
                toks = list(out["tokens"])
                toks[-1] = (toks[-1] + 1) % cfg.vocab_size
                return {"tokens": toks}

            reg.register("generate", altered)
        return reg

    monkeypatch.setattr(launch_serve, "build_registry", build)


def test_altered_token_is_caught(tmp_path: Path, monkeypatch):
    _wrap_registry(monkeypatch, "altered")
    line = bench_tiny.run(bench_tiny.cell("serve", tmp_path, seed=8, seconds=1.0))
    assert line["correct"] is False, line["checks"]


def test_decode_that_keeps_its_cache_is_caught(tmp_path: Path, monkeypatch):
    _wrap_registry(monkeypatch, "unchanged")
    line = bench_tiny.run(bench_tiny.cell("serve", tmp_path, seed=9, seconds=1.0))
    assert line["correct"] is False, line["checks"]


def test_control_fails_the_check():
    """The reference with float8 weights, put in the program's place, reads above.

    float8 is the serving control (int8 weights move the greedy token too
    rarely to separate from the program's gap; PERF.md gives the readings).
    """
    cfg = bench_tiny.SERVE_CFG
    reqs = inputs.requests(bench_tiny.SERVE_MIX, rate=4.0, seconds=2.0, seed=4,
                           vocab=cfg["vocab_size"])
    sample = [(r.prompt, list(range(1, 17))) for r in reqs[:4]]
    dense = load_module(BENCH_DIR / "families" / "dense.py")
    gaps = serve.check_sample(dense, cfg, 4, sample, controls=("float8",))
    assert gaps["control_float8"] > bench_tiny.SERVE_SETTINGS["limits"]["logit_gap"]


def test_same_work_for_every_seed():
    mix = dict(bench_tiny.SERVE_MIX, prompt_lengths=[10, 20, 40], prompt_weights=[0.5, 0.35, 0.15])
    a = inputs.requests(mix, rate=5.0, seconds=20.0, seed=1, vocab=100)
    b = inputs.requests(mix, rate=5.0, seconds=20.0, seed=3_000_000_001, vocab=100)
    assert len(a) == len(b) == 100
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert abs(max(r.due_s for r in a) - max(r.due_s for r in b)) < 1.0
    # one schedule, entered at another place: b's lengths and sessions are a's, rotated
    sa = [(len(r.prompt), r.session) for r in a]
    sb = [(len(r.prompt), r.session) for r in b]
    assert any(sa[k:] + sa[:k] == sb for k in range(len(a)))
    assert [r.prompt for r in a] == [r.prompt for r in inputs.requests(
        mix, rate=5.0, seconds=20.0, seed=1, vocab=100)]
