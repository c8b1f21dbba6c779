"""Device time of one jitted decode step (``models/model.decode_step``), in ms.

Mean duration of the ``decode_step`` program's executions in the trace.
"""


def read(obs):
    if obs.trace is None:
        return None
    runs = obs.trace.module_events(lambda name: "decode_step" in name)
    if not runs:
        return None
    return 1e3 * sum(e.dur_ns for e in runs) / 1e9 / len(runs)
