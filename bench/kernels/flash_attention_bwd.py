"""Operations and bytes of one call of the flash-attention backward.

The counts are what the algorithm needs for one backward call, from the
forward call's shapes (``attention_calls``):

* operations: five matrix products over the (query, key) pairs that the
  mask keeps, two FLOPs per multiply-add: ``S = Q K^T``, ``dQ = dS K`` and
  ``dK = dS^T Q`` at ``head_dim``, ``dP = dO V^T`` and ``dV = P^T dO`` at
  ``v_head_dim``;
* bytes: ``q``, ``k``, ``v``, ``o`` and ``dO`` read once, and ``dq``,
  ``dk`` and ``dv`` written once, in the call's dtype.

The kernels (``repro.kernels.flash_attention``) recompute the row
log-sum-exp in a pass of their own and ``S`` and ``dP`` once for ``dq`` and
once for ``dk``/``dv``, compute whole blocks on the mask's edge, and read
the row statistics besides: costs of the kernels, not of the algorithm,
which show as a lower share.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path
from typing import Dict, Tuple

#: one floating-point operand or output of a custom call, as HLO text prints it
FLOAT_OPERAND = re.compile(r"(?:^|,\s*|\()(f32|bf16|f16|f64|f8\w*)\[([\d,]*)\]")

# the forward's counts of the same call: its kept pairs and v_head_dim
_spec = importlib.util.spec_from_file_location(
    "bench_kernels_flash_attention", Path(__file__).with_name("flash_attention.py"))
_forward = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_forward)
pairs, v_dim = _forward.pairs, _forward.v_dim


def flops(c: Dict[str, object]) -> float:
    return 2.0 * c["batch"] * c["heads"] * (3 * c["head_dim"] + 2 * v_dim(c)) * pairs(
        c["q_len"], c["kv_len"], c["causal"])


def bytes_moved(c: Dict[str, object]) -> float:
    q = c["batch"] * c["heads"] * c["q_len"] * c["head_dim"]
    o = c["batch"] * c["heads"] * c["q_len"] * v_dim(c)
    kv = c["batch"] * c["kv_heads"] * c["kv_len"] * (c["head_dim"] + v_dim(c))
    # q, o, dO, k, v read; dq, dk, dv written
    return float((2 * q + 2 * o + 2 * kv) * c["dtype_bytes"])


def roofline_s(c: Dict[str, object], peaks: Dict[str, float]) -> Tuple[float, str]:
    """Least time the chip could take for one backward call, and which bound sets it."""
    t_compute = flops(c) / peaks["bf16_flops_per_s"]
    t_memory = bytes_moved(c) / peaks["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")


def _parts(event):
    """(float results, float operands) of a Mosaic custom call, each a list
    of (dtype, dims), or None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in event.name:
        return None
    head, _, rest = event.name.partition(" custom-call(")
    result = head.split(" = ", 1)[-1]
    operands = rest.split("), custom_call_target", 1)[0]
    return FLOAT_OPERAND.findall(result), FLOAT_OPERAND.findall(operands)


def matcher(c: Dict[str, object]):
    """Whether a device op is one of this call's backward kernels: a Mosaic
    custom call whose first floating-point operand is the scaled ``q``
    (``[batch, heads, q_len, head_dim]``) and which writes or reads the
    float32 row statistics ``[batch, heads, 1, q_len]``: the log-sum-exp pass
    writes them, the dq and dk/dv kernels read them with delta. The forward
    (``q``, ``k``, ``v`` in, ``o`` out) touches no row statistics."""
    q = f"{c['batch']},{c['heads']},{c['q_len']},{c['head_dim']}"
    rows = ("f32", f"{c['batch']},{c['heads']},1,{c['q_len']}")

    def match(event) -> bool:
        parts = _parts(event)
        if parts is None:
            return False
        results, operands = parts
        if not operands or operands[0][1] != q:
            return False
        return rows in results or rows in operands

    return match


def dkv_matcher(c: Dict[str, object]):
    """Whether a device op is this call's dk/dv kernel, one a backward call:
    a backward kernel with two results, ``dk`` and ``dv``."""
    backward = matcher(c)

    def match(event) -> bool:
        return backward(event) and len(_parts(event)[0]) == 2

    return match
