"""Host time of one decoded token, in ms.

Layer: jitted step (the per-token ``decode_step`` and its host sync). Mean
over the ``serve.decode`` spans of the traced window of the span's duration
over its ``tokens``.
"""


def read(obs):
    per_token = [s["dur"] / s["attrs"]["tokens"] for s in obs.spans
                 if s["name"] == "serve.decode" and s["attrs"].get("tokens")]
    return 1e3 * sum(per_token) / len(per_token) if per_token else None
