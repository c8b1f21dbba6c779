"""The trace reduction on hand-made events and on a recorded TPU trace."""
from __future__ import annotations

from pathlib import Path

import pytest

import bench_tiny  # noqa: F401
from bench import trace as tr

RECORDED = Path(__file__).parent / "data" / "flash_small.xplane.pb"


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


def summary(ops, host=(), window_s=1e-6, modules=None):
    return tr.TraceSummary(tr.RawTrace(ops, modules or {}, list(host)), window_s)


def test_merge_and_subtract():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.covered([(0, 3), (5, 8)]) == 6
    # [0,10) minus [2,4) and [6,7): 2 + 2 + 3
    assert tr.subtract([(0, 10)], [(2, 4), (6, 7)]) == 7
    assert tr.subtract([(0, 2), (5, 6)], [(1, 5.5)]) == 1.5


def test_busy_idle_and_op_names():
    s = summary({0: [ev("fusion.12", 0, 100), ev("fusion.7", 50, 100),
                     ev("convolution.3", 300, 200),
                     ev("%while.1 = (f32[8]) while(%t)", 300, 150)]}, window_s=1000e-9)
    assert s.busy_s == pytest.approx(350e-9)
    assert s.idle_share == pytest.approx(0.65)
    assert s.op_seconds() == pytest.approx({"fusion": 200e-9, "convolution": 200e-9})


def test_busy_is_averaged_over_devices():
    s = summary({0: [ev("a", 0, 100)], 1: [ev("a", 0, 300)]}, window_s=1000e-9)
    assert s.busy_s == pytest.approx(200e-9)
    assert s.op_seconds()["a"] == pytest.approx(200e-9)


def test_collective_time_not_hidden_by_compute():
    s = summary({0: [ev("%fusion.1 = f32[8] fusion(%all-reduce.4)", 0, 100),
                     ev("%all-reduce.4 = f32[8] all-reduce(%x)", 50, 100),
                     ev("%while.3 = (f32[8]) while(%t)", 0, 400),
                     ev("%all-reduce-start.2 = f32[8] all-reduce-start(%y)", 300, 50)]})
    # 50 of the first all-reduce overlap the fusion; the second is bare (the
    # while loop that holds it is no compute of its own)
    assert s.collective_exposed_s() == pytest.approx(100e-9)


def test_idle_gaps_are_labelled_by_the_innermost_host_event():
    ops = {0: [ev("f", 0, 10), ev("f", 20, 10), ev("f", 100, 10)]}
    host = [ev("outer", 0, 200), ev("journal_append", 40, 50)]
    gaps = dict(summary(ops, host).idle_gaps())
    # gap (10, 20) has its middle 15 only in "outer"; gap (30, 100) in both
    assert gaps == pytest.approx({"journal_append": 70e-9, "outer": 10e-9})
    b = summary(ops, host).breakdown()
    assert b["device_ops"] == [["f", pytest.approx(30e-9)]]


def test_stable_names():
    assert tr.stable_name("fusion.123") == "fusion"
    assert tr.stable_name("%copy-start.4.1") == "copy-start"
    assert tr.stable_name('%closed_call.16 = bf16[2,32,2048,64] custom-call(%a, %b), '
                          'custom_call_target="tpu_custom_call"') == "closed_call:tpu_custom_call"
    assert tr.stable_name("%fusion.7 = f32[8] fusion(%p), kind=kLoop, calls=%f.1") == "fusion"
    assert tr.is_container("%while.169 = (s32[], f32[8]) while(%t), condition=%c, body=%b")
    assert not tr.is_container("%fusion.7 = f32[8] fusion(%call.2), kind=kLoop, calls=%f.1")


def test_recorded_trace_of_the_flash_kernel():
    """Three calls of a small flash-attention program (q = k = v of shape
    (1, 4, 256, 64), then x 2), traced on one TPU v5e."""
    s = tr.summarize(RECORDED, window_s=1.0)
    assert s.devices == [0]
    ops = s.op_seconds()
    assert set(ops) == {"copy", "_lambda_:tpu_custom_call", "broadcast_multiply_fusion"}
    assert s.busy_s == pytest.approx(sum(ops.values()))
    from bench.harness import BENCH_DIR, load_module

    fa = load_module(BENCH_DIR / "kernels" / "flash_attention.py")
    call = {"batch": 1, "heads": 4, "kv_heads": 4, "q_len": 256, "kv_len": 256,
            "head_dim": 64, "causal": True, "dtype_bytes": 2}
    kernel = s.events(fa.matcher(call))
    assert len(kernel) == 3
    assert not s.events(fa.matcher(dict(call, q_len=128)))
    # the op text names no kernel (its metadata is empty): the forward is told
    # apart by its one output and its three operands, q first
    assert all("kernel_metadata={}" in e.name for e in kernel)
    assert not s.events(fa.matcher(dict(call, head_dim=128, v_head_dim=64)))
    assert sum(e.dur_ns for e in kernel) / 1e9 == pytest.approx(ops["_lambda_:tpu_custom_call"])
    assert s.breakdown()["device_ops"][0][0] == "_lambda_:tpu_custom_call"
    labels = [g[0] for g in s.idle_gaps()]
    assert "probe_step" in labels


def _call(result, operands):
    return ev(f"%fusion.3 = {result}{{3,2,1,0}} custom-call({operands}), "
              f'custom_call_target="tpu_custom_call", operand_layout_constraints={{}}', 0, 10)


@pytest.mark.parametrize("result,operands,forward", [
    # the forward since the block plan: an s32 plan table, then q, k, v
    ("bf16[1,16,8192,128]", "s32[3,8]{1,0} %plan, bf16[1,16,8192,192]{3,2,1,0} %q, "
     "bf16[1,16,8192,192]{3,2,1,0} %k, bf16[1,16,8192,128]{3,2,1,0} %v", True),
    # a backward's dq: same output shape, reads o and its gradient too
    ("bf16[1,16,8192,128]", "bf16[1,16,8192,192]{3,2,1,0} %q, bf16[1,16,8192,192]{3,2,1,0} %k, "
     "bf16[1,16,8192,128]{3,2,1,0} %v, bf16[1,16,8192,128]{3,2,1,0} %o, "
     "bf16[1,16,8192,128]{3,2,1,0} %do, f32[1,16,8192]{2,1,0} %lse", False),
    # a backward writing several gradients
    ("(bf16[1,16,8192,128], bf16[1,16,8192,192])", "bf16[1,16,8192,192]{3,2,1,0} %q, "
     "bf16[1,16,8192,192]{3,2,1,0} %k, bf16[1,16,8192,128]{3,2,1,0} %v", False),
    # a call of the forward's output shape whose q is not 192 wide
    ("bf16[1,16,8192,128]", "bf16[1,16,8192,128]{3,2,1,0} %a, "
     "bf16[1,16,8192,128]{3,2,1,0} %b, bf16[1,16,8192,128]{3,2,1,0} %c", False),
], ids=["forward", "dq", "tuple_out", "other_q_width"])
def test_flash_matcher_tells_the_forward_apart(result, operands, forward):
    from bench.harness import BENCH_DIR, load_module

    fa = load_module(BENCH_DIR / "kernels" / "flash_attention.py")
    mla = {"batch": 1, "heads": 16, "kv_heads": 16, "q_len": 8192, "kv_len": 8192,
           "head_dim": 192, "v_head_dim": 128, "causal": True, "dtype_bytes": 2}
    assert fa.matcher(mla)(_call(result, operands)) is forward
