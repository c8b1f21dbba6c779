"""Flash-attention backward kernels: device time per traced train step, in ms.

Layer: kernels (``kernels/flash_attention``). Sums the device time of the
calls that ``bench/metrics/train.flash_bwd_roofline.py`` finds (every
backward kernel of the step) over the traced steps, averaged over chips.
"""
from bench.harness import BENCH_DIR, load_module


def read(obs):
    steps = obs.counters.get("traced_steps")
    found = load_module(BENCH_DIR / "metrics" / "train.flash_bwd_roofline.py").calls(obs)
    if not steps or found is None:
        return None
    return 1e3 * sum(e.dur_ns for e in found[1]) / 1e9 / steps / obs.chips
