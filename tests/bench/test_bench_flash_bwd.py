"""The flash-attention backward's counts and its two readers, on hand-built
shapes and trace events."""
from __future__ import annotations

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on the path)
from bench import harness
from bench import trace as tr
from bench.harness import BENCH_DIR, load_module, peaks_for

fb = load_module(BENCH_DIR / "kernels" / "flash_attention_bwd.py")
fa = load_module(BENCH_DIR / "kernels" / "flash_attention.py")
PEAKS = peaks_for("TPU v5 lite")
STEADY = "train.stablelm-1.6b.steady"


def call(**kw):
    c = {"batch": 1, "heads": 4, "kv_heads": 2, "q_len": 4, "kv_len": 4, "head_dim": 8,
         "causal": True, "dtype_bytes": 2}
    c.update(kw)
    return c


def test_flops_and_bytes_by_hand():
    # GQA 4/2, causal 4 x 4: 10 kept pairs a head; S, dQ, dK at 8 wide, dP, dV at 4
    c = call(v_head_dim=4)
    assert fb.flops(c) == 2 * 4 * (3 * 8 + 2 * 4) * 10
    # q and dq 4 heads x 4 x 8; o and dO 4 x 4 x 4; k, v, dk, dv 2 heads x 4 x (8 + 4)
    assert fb.bytes_moved(c) == (2 * 128 + 2 * 64 + 2 * 96) * 2
    # without v_head_dim every product is at head_dim: 5 products to the forward's 2
    assert fb.flops(call()) == fa.flops(call()) * 5 / 2


def test_training_call_is_compute_bound():
    c = call(batch=2, heads=32, kv_heads=32, q_len=2048, kv_len=2048, head_dim=64)
    assert fb.pairs(2048, 2048, True) == 2098176
    assert fb.flops(c) == pytest.approx(85.9e9, rel=1e-3)
    assert fb.bytes_moved(c) == 8 * 2 * 32 * 2048 * 64 * 2
    t, bound = fb.roofline_s(c, PEAKS)
    assert bound == "compute" and t == pytest.approx(fb.flops(c) / 197e12)


# a granite attention call: 2 x 32 query heads, 8 kv heads, 2048 x 64
Q = "bf16[2,32,2048,64]{3,2,1,0:T(8,128)(2,1)} %q"
KV = "bf16[2,8,2048,64]{3,2,1,0:T(8,128)(2,1)} %k, bf16[2,8,2048,64]{3,2,1,0} %v"
ROWS = "f32[2,32,1,2048]{3,2,1,0:T(1,128)}"
PLAN = "s32[5,4]{1,0} %plan, "
DO = "bf16[2,32,2048,64]{3,2,1,0} %do"
GRANITE = call(batch=2, heads=32, kv_heads=8, q_len=2048, kv_len=2048, head_dim=64)


def event(result, operands, dur=10.0):
    return tr.Event(f"%custom-call.3 = {result} custom-call({operands}), "
                    f'custom_call_target="tpu_custom_call", kernel_metadata={{}}', 0.0, dur)


CALLS = {
    "forward": event("bf16[2,32,2048,64]{3,2,1,0}", "s32[5,2]{1,0} %plan, " + Q + ", " + KV),
    "lse": event(ROWS, PLAN + Q + ", bf16[2,8,2048,64]{3,2,1,0} %k"),
    "dq": event("bf16[2,32,2048,64]{3,2,1,0}",
                PLAN + Q + ", " + KV + ", " + DO + f", {ROWS} %lse, {ROWS} %delta"),
    "dkv": event("(bf16[2,8,2048,64]{3,2,1,0}, bf16[2,8,2048,64]{3,2,1,0})",
                 PLAN + Q + ", " + KV + ", " + DO + f", {ROWS} %lse, {ROWS} %delta"),
    # the scan kernel of the same cell
    "ssd": event("(bf16[2,64,2048,64]{3,2,1,0}, f32[2,64,64,128]{3,2,1,0})",
                 "bf16[2,64,2048,64]{3,2,1,0} %x, f32[2,64,2048]{2,1,0} %dt, "
                 "f32[2,64,2048]{2,1,0} %cum, bf16[2,2048,128]{2,1,0} %b, "
                 "bf16[2,2048,128]{2,1,0} %c, f32[2,64,64,128]{3,2,1,0} %h0"),
    # an XLA op that reads the same rows
    "fusion": tr.Event(f"%fusion.2 = {ROWS} fusion({Q}), kind=kLoop", 0.0, 10.0),
}


@pytest.mark.parametrize("name, backward, dkv", [
    ("forward", False, False), ("lse", True, False), ("dq", True, False),
    ("dkv", True, True), ("ssd", False, False), ("fusion", False, False)])
def test_matchers_take_the_backward_and_only_it(name, backward, dkv):
    assert fb.matcher(GRANITE)(CALLS[name]) is backward
    assert fb.dkv_matcher(GRANITE)(CALLS[name]) is dkv
    # the forward's matcher takes none of the backward's kernels
    assert fa.matcher(GRANITE)(CALLS[name]) is (name == "forward")


def test_matchers_follow_the_call_shape():
    # at 4 chips each holds a quarter of the rows: the global shape matches nothing
    assert not fb.matcher(dict(GRANITE, batch=8))(CALLS["dq"])
    assert not fb.matcher(dict(GRANITE, head_dim=128))(CALLS["dkv"])
    assert not fb.matcher(dict(GRANITE, q_len=1024))(CALLS["lse"])


def observe(events=None, chips=1, steps=2, batch=2, peaks=PEAKS):
    cell = harness.find_cell(STEADY)
    trace = None
    if events is not None:
        trace = tr.TraceSummary(tr.RawTrace({d: list(events) for d in range(chips)}, {}, []),
                                1.0)
    counters = {"traced_steps": steps, "attention_calls": dict(GRANITE, batch=batch)}
    return harness.Observations(cell=cell, counters=counters, trace=trace, peaks=peaks,
                                chips=chips)


def read(metric, obs):
    return harness.load_reader(harness.find_cell(STEADY), metric)(obs)


def test_readers_on_one_backward_call_a_step():
    # two steps, each one call: the lse pass 1 ms, dq 2 ms, dk/dv 3 ms
    ms = {"lse": 1e6, "dq": 2e6, "dkv": 3e6}
    events = [tr.Event(CALLS[k].name, 0.0, d) for k, d in ms.items()] * 2
    events += [CALLS["forward"], CALLS["ssd"]]
    obs = observe(events)
    assert read("train.flash_bwd_ms", obs) == pytest.approx(6.0)
    least, _ = fb.roofline_s(GRANITE, PEAKS)
    assert read("train.flash_bwd_roofline", obs) == pytest.approx(100 * least * 2 / 12e-3)


def test_readers_average_over_chips():
    # four chips of 8 rows, 2 each: every chip's trace holds the per-chip call
    events = [tr.Event(CALLS[k].name, 0.0, 1e6) for k in ("lse", "dq", "dkv")]
    obs = observe(events, chips=4, steps=1, batch=8)
    assert read("train.flash_bwd_ms", obs) == pytest.approx(3.0)
    least, _ = fb.roofline_s(GRANITE, PEAKS)
    assert read("train.flash_bwd_roofline", obs) == pytest.approx(100 * least * 4 / 12e-3)


@pytest.mark.parametrize("obs", [
    observe(None),                                   # no trace
    observe([CALLS["forward"], CALLS["ssd"]]),       # a backward with no kernel
    observe([CALLS["lse"], CALLS["dq"]]),            # no dk/dv call, so no call counted
    observe([CALLS["dkv"]], batch=4),                # another shape
], ids=["no_trace", "no_kernel", "no_dkv", "other_shape"])
def test_readers_read_nothing_where_nothing_matches(obs):
    assert read("train.flash_bwd_ms", obs) is None
    assert read("train.flash_bwd_roofline", obs) is None


def test_roofline_needs_the_peaks():
    obs = observe([CALLS["dkv"]], peaks={})
    assert read("train.flash_bwd_roofline", obs) is None
    assert read("train.flash_bwd_ms", obs) == pytest.approx(1e-5 / 2)
