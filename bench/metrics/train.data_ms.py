"""Host time spent making each training step's data, in ms.

Layer: control plane (``Trainer._round_graph``'s ``data@N`` fetch node and
``Trainer.device_batch``). Over the ``step@N`` node spans of the traced
window: their ``train.batch`` children (the batch regenerated and put on the
mesh) plus every ``data@N`` node span of the window, per step.
"""


def read(obs):
    steps = {s["span"] for s in obs.spans if s["kind"] == "node" and s["name"].startswith("step@")}
    batch = [s["dur"] for s in obs.spans if s["name"] == "train.batch" and s["parent"] in steps]
    if not steps or not batch:
        return None
    fetch = [s["dur"] for s in obs.spans if s["kind"] == "node" and s["name"].startswith("data@")]
    return 1e3 * (sum(batch) + sum(fetch)) / len(steps)
