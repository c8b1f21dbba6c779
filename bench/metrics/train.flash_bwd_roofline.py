"""Flash-attention backward kernels: roofline time over measured kernel time, in %.

Layer: kernels (``kernels/flash_attention``). Operations and bytes of one
backward call come from ``bench/kernels/flash_attention_bwd.py`` for the
step's call shape (``attention_calls``), which every call of the step has.
The measured time is that of every Mosaic call its matcher takes (the
log-sum-exp pass, dq, dk/dv); a backward call is one dk/dv call. Nothing to
read where the backward runs no kernel.
"""
from bench.harness import BENCH_DIR, load_module


def calls(obs):
    """(the per-chip call, its backward kernels' events, the number of
    backward calls) or None."""
    shape = obs.counters.get("attention_calls")
    if obs.trace is None or not shape:
        return None
    k = load_module(BENCH_DIR / "kernels" / "flash_attention_bwd.py")
    shard = dict(shape, batch=shape["batch"] // obs.chips)
    events = obs.trace.events(k.matcher(shard))
    n = sum(map(k.dkv_matcher(shard), events))
    return (shard, events, n) if n else None


def read(obs):
    found = calls(obs)
    if found is None or not obs.peaks:
        return None
    shard, events, n = found
    k = load_module(BENCH_DIR / "kernels" / "flash_attention_bwd.py")
    least, _ = k.roofline_s(shard, obs.peaks)
    measured = sum(e.dur_ns for e in events) / 1e9
    return 100.0 * least * n / measured
