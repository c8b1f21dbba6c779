"""Gated delta rule forward kernel: device time per 1,000 prompt tokens, in ms.

Layer: kernels (``kernels/gated_delta``). The device time of the calls that
``bench/metrics/serve.gdn_fwd_roofline.py`` finds in the traced window, over
the prompt tokens of the ``serve.prefill`` spans of that window (their
``prompt_tokens``), per thousand.
"""
from bench.harness import BENCH_DIR, load_module


def read(obs):
    found = load_module(BENCH_DIR / "metrics" / "serve.gdn_fwd_roofline.py").calls(obs)
    tokens = sum(s["attrs"].get("prompt_tokens", 0) for s in obs.spans
                 if s["name"] == "serve.prefill")
    if found is None or not tokens:
        return None
    return 1e3 * sum(e.dur_ns for _, e in found) / 1e9 / (tokens / 1e3)
