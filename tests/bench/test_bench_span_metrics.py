"""Readers of the program's spans, counters and host-plane annotations.

The readers are checked on hand-built spans and a synthetic trace, then on
tiny traced runs of both drivers on the CPU (the device-trace reader has no
device plane there).
"""
from __future__ import annotations

import math
from pathlib import Path

import pytest

import bench_tiny
from bench import harness
from bench import trace as tr

SPAN_METRICS = {
    "train": ["train.data_ms", "train.commit_ms"],
    "serve": ["serve.gateway_self_ms", "serve.prefill_ms", "serve.decode_token_ms",
              "serve.lowerings_per_request"],
}


def span(name, sid, parent="", dur=0.0, kind="internal", **attrs):
    return {"name": name, "trace": "t", "span": sid, "parent": parent, "kind": kind,
            "ts": 0.0, "dur": dur, "status": "ok", "attrs": attrs}


def read(metric, spans=(), trace=None):
    cell = harness.find_cell("serve.qwen3-1.7b.agent" if metric.startswith("serve.")
                             else "train.stablelm-1.6b.steady")
    obs = harness.Observations(cell=cell, spans=list(spans), trace=trace)
    return harness.load_reader(cell, metric)(obs)


def test_gateway_self_time_pairs_each_rpc_with_its_own_task():
    spans = [
        span("rpc:generate", "r1", dur=1.000, kind="rpc", queued_s=0.010),
        span("task:generate", "t1", parent="r1", dur=0.990, kind="task"),
        span("rpc:generate", "r2", dur=3.000, kind="rpc", queued_s=0.0),
        span("task:generate", "t2", parent="r2", dur=2.996, kind="task"),
        # an rpc whose task ended after the traced window: not paired
        span("rpc:generate", "r3", dur=9.0, kind="rpc", queued_s=5.0),
    ]
    # (0.010 + 0.010) and (0 + 0.004), in ms
    assert read("serve.gateway_self_ms", spans) == pytest.approx(12.0)
    # spans of a program whose task is not the rpc's child, or without queued_s
    assert read("serve.gateway_self_ms", [
        span("rpc:generate", "r1", parent="n", dur=1.0, kind="rpc", queued_s=0.0),
        span("task:generate", "t1", parent="n", dur=0.9, kind="task")]) is None
    assert read("serve.gateway_self_ms", [
        span("rpc:generate", "r1", dur=1.0, kind="rpc"),
        span("task:generate", "t1", parent="r1", dur=0.9, kind="task")]) is None


def test_serving_task_readers():
    spans = [
        span("task:generate", "t1", dur=1.0, kind="task", jax_lowerings=1, jax_compiles=1),
        span("task:generate", "t2", dur=1.0, kind="task", jax_lowerings=2, jax_compiles=0),
        span("serve.prefill", "p1", parent="t1", dur=0.3, prompt_tokens=1024),
        span("serve.prefill", "p2", parent="t2", dur=0.5, prompt_tokens=4096),
        span("serve.decode", "d1", parent="t1", dur=0.32, tokens=32),
        span("serve.decode", "d2", parent="t2", dur=0.64, tokens=32),
        span("serve.decode", "d3", parent="t2", dur=0.0, tokens=0),  # no token: left out
    ]
    assert read("serve.prefill_ms", spans) == pytest.approx(400.0)
    assert read("serve.decode_token_ms", spans) == pytest.approx(15.0)
    assert read("serve.lowerings_per_request", spans) == pytest.approx(1.5)
    bare = [span("task:generate", "t1", dur=1.0, kind="task")]
    for metric in SPAN_METRICS["serve"]:
        assert read(metric, bare) is None, metric


def test_training_readers_count_per_step():
    spans = [
        span("step@7", "s7", dur=0.40, kind="node"),
        span("step@8", "s8", dur=0.40, kind="node"),
        span("data@9", "f9", dur=0.004, kind="node"),
        span("train.batch", "b7", parent="s7", dur=0.010, rows=2),
        span("train.batch", "b8", parent="s8", dur=0.012, rows=2),
        span("train.batch", "b6", dur=0.011, rows=2),  # its step began before the window
        span("journal.append", "j1", parent="s7", dur=0.0002, kind="internal"),
        span("journal.append", "j2", parent="s7", dur=0.0003),
        span("journal.append", "j3", parent="s8", dur=0.0002),
        span("journal.flush", "j4", parent="s8", dur=0.0013),
    ]
    assert read("train.data_ms", spans) == pytest.approx((10 + 12 + 4) / 2)
    assert read("train.commit_ms", spans) == pytest.approx((0.2 + 0.3 + 0.2 + 1.3) / 2)
    steps_only = [s for s in spans if s["name"].startswith("step@")]
    for metric in SPAN_METRICS["train"]:
        assert read(metric, steps_only) is None, metric


def test_inflight_idle_counts_only_time_with_a_request_open():
    ev = tr.Event
    ops = {0: [ev("fusion", 100, 50), ev("fusion", 300, 100), ev("fusion", 900, 50)]}
    host = [ev("task:generate", 80, 220),   # [80, 300): busy 50 of 220
            ev("task:generate", 250, 200),  # [250, 450): overlaps; union [80, 450)
            ev("serve.prefill", 80, 100),   # other host events do not count
            ev("lower_sharding_computation", 600, 200)]
    summary = tr.TraceSummary(tr.RawTrace(ops, {}, host), window_s=1e-6)
    # union [80, 450): 370 long, busy [100,150) and [300,400): 150
    assert read("serve.inflight_idle", trace=summary) == pytest.approx(100.0 * 220 / 370)
    # the device idles outside requests too, which this metric leaves out
    assert 100.0 * summary.idle_share == pytest.approx(80.0)
    two = tr.TraceSummary(tr.RawTrace({0: ops[0], 1: [ev("f", 80, 370)]}, {}, host), 1e-6)
    assert read("serve.inflight_idle", trace=two) == pytest.approx(100.0 * 110 / 370)
    assert read("serve.inflight_idle", trace=tr.TraceSummary(
        tr.RawTrace(ops, {}, host[2:]), 1e-6)) is None
    assert read("serve.inflight_idle") is None


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_tiny_traced_runs_report_the_span_metrics(tmp_path: Path, kind):
    c = bench_tiny.cell(kind, tmp_path, seed=3_000_000_041, trace=True,
                        seconds=2.0 if kind == "train" else 1.5)
    if kind == "serve":
        # every request ends inside the trace, also on a loaded CPU, where each
        # request's prefill lowers in the window and takes seconds
        c.settings["trace_seconds"] = 25.0
    line = bench_tiny.run(c)
    assert line["correct"] is True, line["checks"]
    for metric in SPAN_METRICS[kind]:
        value = line["metrics"][metric]["value"]
        assert math.isfinite(value) and value >= 0.0, (metric, value)
    if kind == "serve":
        assert line["metrics"]["serve.lowerings_per_request"]["value"] >= 1.0
