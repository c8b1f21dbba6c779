"""Mamba-2 scan forward kernel: roofline time over measured kernel time, in %.

Layer: kernels (``kernels/ssd``). The call's shape comes from the cell's
family (``ssd_calls``) and traffic; operations and bytes from
``bench/kernels/ssd.py``. Every forward call of the step (each Mamba-2
layer's, and each recompute that full remat leaves) has that shape.
Nothing to read in a cell whose family has no scan.
"""
from bench.harness import BENCH_DIR, load_family, load_module


def calls(obs):
    """(the per-chip call, its events in the trace) or None."""
    family = load_family(obs.cell)
    if obs.trace is None or not hasattr(family, "ssd_calls"):
        return None
    mix = obs.cell.traffic
    call = family.ssd_calls(obs.cell.config, int(mix["global_batch"]) // obs.chips,
                            int(mix["seq_len"]))
    k = load_module(BENCH_DIR / "kernels" / "ssd.py")
    events = obs.trace.events(k.matcher(call))
    return (call, events) if events else None


def read(obs):
    found = calls(obs)
    if found is None or not obs.peaks:
        return None
    call, events = found
    least, _ = load_module(BENCH_DIR / "kernels" / "ssd.py").roofline_s(call, obs.peaks)
    measured = sum(e.dur_ns for e in events) / 1e9
    return 100.0 * least * len(events) / measured
