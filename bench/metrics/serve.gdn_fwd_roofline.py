"""Gated delta rule forward kernel: roofline time over measured kernel time, in %.

Layer: kernels (``kernels/gated_delta``). For each call of the kernel in
the traced window, the least time from its own shape (every prompt length
gives another) by ``bench/kernels/gated_delta.py``, summed, over the
calls' measured device time. Nothing to read where the program runs no such
kernel.
"""
from bench.harness import BENCH_DIR, load_module


def calls(obs):
    """[(call, event)] of the kernel's forward calls in the trace, or None."""
    if obs.trace is None:
        return None
    k = load_module(BENCH_DIR / "kernels" / "gated_delta.py")
    found = [(k.call_of(e), e) for e in obs.trace.events(lambda e: k.call_of(e) is not None)]
    return found or None


def read(obs):
    found = calls(obs)
    if found is None or not obs.peaks:
        return None
    k = load_module(BENCH_DIR / "kernels" / "gated_delta.py")
    least = sum(k.roofline_s(call, obs.peaks)[0] for call, _ in found)
    measured = sum(e.dur_ns for _, e in found) / 1e9
    return 100.0 * least / measured
