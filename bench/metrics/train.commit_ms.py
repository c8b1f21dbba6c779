"""Host time spent writing the journal for each training step, in ms.

Layer: control plane (``core/durable.Journal``). The ``journal.append`` and
``journal.flush`` spans of the traced window (each node's start and commit
records, and the flushes of the sync policy), per ``step@N`` node span.
"""


def read(obs):
    steps = sum(1 for s in obs.spans if s["kind"] == "node" and s["name"].startswith("step@"))
    journal = [s["dur"] for s in obs.spans if s["name"] in ("journal.append", "journal.flush")]
    if not steps or not journal:
        return None
    return 1e3 * sum(journal) / steps
