"""Operations and bytes of one call of the Mamba-2 scan (SSD) forward kernel.

A call is ``{"batch", "heads", "seq", "head_dim" (P), "state" (N), "chunk"
(Q), "dtype_bytes"}``: ``x`` of shape ``[batch, heads, seq, P]``, one group
of ``B`` and ``C`` (``[batch, seq, N]``) shared by every head. The counts
are what the chunked algorithm needs for the call, from its shapes, over
``n = ceil(seq / Q)`` chunks of ``Q`` steps:

* operations, two FLOPs per multiply-add: ``C Bᵀ`` over the causal pairs of
  each chunk, once per (batch, chunk) since the heads share it; per head,
  ``(L ∘ C Bᵀ) x`` over those pairs at width P, the previous state's output
  ``C hᵀ`` and the chunk's state ``xᵀ B`` (Q x N x P each);
* bytes: ``x`` and ``y`` in the call's dtype, ``B`` and ``C`` once, ``dt``
  in float32, and the first and last state (float32) read and written once.

The kernel (``repro.kernels.ssd``) also builds the decay matrix and its
column scalings elementwise, computes ``C Bᵀ`` whole for every step of
``hb`` heads, and reads ``B`` and ``C`` again for every such step: costs of
the kernel, not of the algorithm, which show as a lower share.
"""
from __future__ import annotations

import re
from typing import Dict, Tuple

#: one floating-point operand of a custom call, as HLO text prints it
FLOAT_OPERAND = re.compile(r"(?:^|,\s*)(?:bf16|f16|f32|f64|f8\w*)\[([\d,]*)\]")


def chunks(c: Dict[str, int]) -> int:
    return -(-c["seq"] // c["chunk"])


def visited(c: Dict[str, int]) -> int:
    """Grid steps of (batch, head, chunk) one call visits."""
    return c["batch"] * c["heads"] * chunks(c)


def flops(c: Dict[str, int]) -> float:
    Q, P, N = c["chunk"], c["head_dim"], c["state"]
    pairs = Q * (Q + 1) / 2
    n = c["batch"] * chunks(c)
    return 2.0 * n * (N * pairs + c["heads"] * (P * pairs + 2 * Q * N * P))


def bytes_moved(c: Dict[str, int]) -> float:
    b, h, s, P, N = c["batch"], c["heads"], c["seq"], c["head_dim"], c["state"]
    xy = 2 * b * h * s * P * c["dtype_bytes"]
    bc = 2 * b * s * N * c["dtype_bytes"]
    return float(xy + bc + 4 * b * h * s + 2 * 4 * b * h * P * N)


def roofline_s(c: Dict[str, int], peaks: Dict[str, float]) -> Tuple[float, str]:
    """Least time the chip could take for one call, and which bound sets it."""
    t_compute = flops(c) / peaks["bf16_flops_per_s"]
    t_memory = bytes_moved(c) / peaks["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")


def matcher(c: Dict[str, int]):
    """Whether a device op is this call's forward: a Mosaic custom call with
    two outputs, ``y`` of shape ``[batch, heads, seq padded to the chunk,
    head_dim]`` and the float32 state ``[batch, heads, head_dim, state]``,
    and six floating-point operands (x, dt, the prefix sums of dt·A, B, C
    and the first state). The backward recomputes in XLA and calls no
    kernel."""
    b, h, P, N = c["batch"], c["heads"], c["head_dim"], c["state"]
    y = f"[{b},{h},{chunks(c) * c['chunk']},{P}]"
    state = f"f32[{b},{h},{P},{N}]"

    def match(event) -> bool:
        if 'custom_call_target="tpu_custom_call"' not in event.name:
            return False
        head, _, rest = event.name.partition(" custom-call(")
        result = head.split(" = ", 1)[-1]
        if not result.startswith("("):
            return False
        outs = FLOAT_OPERAND.findall(result[1:])
        if len(outs) != 2 or f"[{outs[0]}]" != y or f"f32[{outs[1]}]" != state:
            return False
        return len(FLOAT_OPERAND.findall(rest.split("), custom_call_target", 1)[0])) == 6

    return match
