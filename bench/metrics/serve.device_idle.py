"""Share of the traced window in which no op ran on the device, in %.

1 - (union of device op intervals) / (traced window), averaged over chips.
"""


def read(obs):
    if obs.trace is None or obs.trace.idle_share is None:
        return None
    return 100.0 * obs.trace.idle_share
