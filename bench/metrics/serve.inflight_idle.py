"""Share of the time with a request in flight in which the device was idle, in %.

Layer: device. On the profiler's host plane each ``task:generate`` span is an
annotation of its own (``repro.obs.trace``); the union of them is the time in
which at least one request ran on a worker. Of that time, the share in which
no op ran on the device, averaged over chips. What is left of
``serve.device_idle`` is time in which the server had no work.
"""
from bench import trace as tr


def read(obs):
    if obs.trace is None or not obs.trace.devices:
        return None
    inflight = tr.merge((e.start_ns, e.end_ns) for e in obs.trace.raw.host
                        if e.name == "task:generate")
    open_ns = tr.covered(inflight)
    if not open_ns:
        return None
    devs = obs.trace.devices
    idle = sum(tr.subtract(inflight, obs.trace.busy_intervals(d)) for d in devs) / len(devs)
    return 100.0 * idle / open_ns
