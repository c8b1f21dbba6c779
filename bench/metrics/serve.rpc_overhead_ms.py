"""Gateway and HTTP transport time per request, in ms.

Mean of the gateway's ``rpc:generate`` spans minus mean of the workers'
``task:generate`` spans in the traced window (``core/gateway``,
``core/server``): dispatch, encoding, the HTTP round trip and queueing in
front of a worker.
"""


def read(obs):
    rpc = [s["dur"] for s in obs.spans if s["name"] == "rpc:generate"]
    task = [s["dur"] for s in obs.spans if s["name"] == "task:generate"]
    if not rpc or not task:
        return None
    return 1e3 * (sum(rpc) / len(rpc) - sum(task) / len(task))
