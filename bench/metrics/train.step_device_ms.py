"""Device busy time per train step in the traced window (profiler trace).

Layer: jitted step (``train/steps``, ``models/``).
"""


def read(obs):
    steps = obs.counters.get("traced_steps")
    if obs.trace is None or not steps or not obs.trace.devices:
        return None
    return 1e3 * obs.trace.busy_s / steps
