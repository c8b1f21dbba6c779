"""Mean wall time of the executor's ``step@N`` node spans in the traced window.

Layer: control plane (``core/executor``, ``Trainer._round_graph``). The
span covers the whole node: batch regeneration, the step, the host sync of
its metrics and the digest; the journal commit follows it.
"""


def read(obs):
    durs = [s["dur"] for s in obs.spans if s["kind"] == "node" and s["name"].startswith("step@")]
    return 1e3 * sum(durs) / len(durs) if durs else None
