"""Model FLOPs utilisation over the profiled steps, in %.

Model FLOPs per token (6 x matmul weights + causal attention, no
recompute) times the tokens per second of the steps the profiler saw (the
time to write the trace out is not theirs), over chips x bf16 peak.
"""


def read(obs):
    rate = obs.counters.get("traced_tokens_per_s")
    per_token = obs.counters.get("flops_per_token")
    if not rate or not per_token or not obs.peaks:
        return None
    return 100.0 * per_token * rate / (obs.chips * obs.peaks["bf16_flops_per_s"])
