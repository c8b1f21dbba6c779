"""Mamba-2 scan forward kernel: device time per traced train step, in ms.

Layer: kernels (``kernels/ssd``). Sums the device time of the calls that
``bench/metrics/train.ssd_fwd_roofline.py`` finds (every forward call of
the step, recomputes included) over the traced steps.
"""
from bench.harness import BENCH_DIR, load_module


def read(obs):
    steps = obs.counters.get("traced_steps")
    found = load_module(BENCH_DIR / "metrics" / "train.ssd_fwd_roofline.py").calls(obs)
    if not steps or found is None:
        return None
    return 1e3 * sum(e.dur_ns for e in found[1]) / 1e9 / steps
