"""Collective time per training step that no compute on the same chip hides, in ms.

Layer: gradient exchange (the all-reduce that XLA puts into the
data-parallel step). Over the traced window: the union of a chip's
collective op intervals less the time in which another op (not a container)
runs on that chip, averaged over chips (``TraceSummary.collective_exposed_s``),
per traced step. A trace with no collective op has nothing to read.
"""
from bench import trace as tr


def read(obs):
    steps = obs.counters.get("traced_steps")
    if obs.trace is None or not steps:
        return None
    if not obs.trace.events(lambda e: tr.COLLECTIVE.match(tr.stable_name(e.name))):
        return None
    return 1e3 * obs.trace.collective_exposed_s() / steps
