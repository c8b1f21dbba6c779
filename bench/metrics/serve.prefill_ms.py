"""Host time of one request's prefill, in ms.

Layer: serving task (``launch/serve.build_registry``'s ``generate``). Mean of
the ``serve.prefill`` spans in the traced window: from the prompt on the host
to its first token on the host, with the eager prefill's lowering.
"""


def read(obs):
    durs = [s["dur"] for s in obs.spans if s["name"] == "serve.prefill"]
    return 1e3 * sum(durs) / len(durs) if durs else None
