"""Flash-attention forward kernel: roofline time over measured kernel time, in %.

Layer: kernels (``kernels/flash_attention``). Operations and bytes come
from ``bench/kernels/flash_attention.py`` for the step's call shape; every
call of the step (the forward and its recompute) has that shape. On one
chip the call is compute-bound (about 500 FLOPs per byte at head size 64
and 2048 positions, against 240 for the chip).
"""
from bench.harness import BENCH_DIR, load_module


def read(obs):
    shape = obs.counters.get("attention_calls")
    if obs.trace is None or not shape or not obs.peaks:
        return None
    k = load_module(BENCH_DIR / "kernels" / "flash_attention.py")
    shard = dict(shape, batch=shape["batch"] // obs.chips)
    events = obs.trace.events(k.matcher(shard))
    if not events:
        return None
    least, _ = k.roofline_s(shard, obs.peaks)
    measured = sum(e.dur_ns for e in events) / 1e9
    return 100.0 * least * len(events) / measured
