"""JAX lowerings per served request.

Layer: serving task. Mean of the ``jax_lowerings`` attribute of the
``task:generate`` spans in the traced window: jaxprs lowered to MLIR on the
worker's thread while the request ran (``repro.obs.trace`` counts them).
"""


def read(obs):
    counts = [s["attrs"]["jax_lowerings"] for s in obs.spans
              if s["name"] == "task:generate" and "jax_lowerings" in s["attrs"]]
    return sum(counts) / len(counts) if counts else None
