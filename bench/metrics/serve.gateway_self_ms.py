"""Time a request spends in the gateway and the transport, in ms.

Layer: gateway and transport (``core/gateway``, ``core/server``, ``wire/``).
Per request, the time it waited in the gateway's queue (``queued_s`` on its
``rpc:generate`` span, from submit to dispatch) plus that span minus its own
``task:generate`` child, paired by span id: queueing, dispatch, encoding and
the HTTP round trip. The mean over the requests whose two spans ended in the
traced window.
"""


def read(obs):
    task = {s["parent"]: s["dur"] for s in obs.spans if s["name"] == "task:generate"}
    per_request = [s["attrs"]["queued_s"] + s["dur"] - task[s["span"]] for s in obs.spans
                   if s["name"] == "rpc:generate" and s["span"] in task
                   and "queued_s" in s["attrs"]]
    if not per_request:
        return None
    return 1e3 * sum(per_request) / len(per_request)
