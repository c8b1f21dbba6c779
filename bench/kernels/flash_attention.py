"""Operations and bytes of one call of the flash-attention forward kernel.

The counts are what the algorithm needs for the call, from its shapes:

* operations: ``Q K^T`` at ``head_dim`` and ``P V`` at ``v_head_dim``
  (``head_dim`` unless the call says otherwise, as latent attention does),
  two FLOPs per multiply-add, over the (query, key) pairs that the mask
  keeps (for a causal square call, ``S (S + 1) / 2`` pairs per head);
* bytes: ``q`` and ``k`` at ``head_dim``, ``v`` at ``v_head_dim`` read once,
  and ``o`` at ``v_head_dim`` written once, in the call's dtype.

The kernel itself (``repro.kernels.flash_attention``) skips the block pairs
that the mask leaves empty, and their copies, but computes whole blocks on
the mask's edge and reads each k/v block it visits once per q block; those
are costs of the kernel, not of the algorithm, and show as a lower share.
"""
from __future__ import annotations

import re
from typing import Dict, Tuple

#: one operand of a custom call in floating point, as HLO text prints it
FLOAT_OPERAND = re.compile(r"(?:^|,\s*)(?:bf16|f16|f32|f64|f8\w*)\[([\d,]*)\]")


def v_dim(c: Dict[str, object]) -> int:
    return int(c.get("v_head_dim", c["head_dim"]))


def pairs(q_len: int, kv_len: int, causal: bool) -> float:
    """(query, key) pairs the mask keeps; queries are right-aligned."""
    if not causal:
        return float(q_len) * kv_len
    off = kv_len - q_len
    return float(sum(min(kv_len, off + i + 1) for i in range(q_len)))


def flops(c: Dict[str, object]) -> float:
    return 2.0 * c["batch"] * c["heads"] * (c["head_dim"] + v_dim(c)) * pairs(
        c["q_len"], c["kv_len"], c["causal"])


def bytes_moved(c: Dict[str, object]) -> float:
    q = c["batch"] * c["heads"] * c["q_len"] * c["head_dim"]
    o = c["batch"] * c["heads"] * c["q_len"] * v_dim(c)
    kv = c["batch"] * c["kv_heads"] * c["kv_len"] * (c["head_dim"] + v_dim(c))
    return float((q + o + kv) * c["dtype_bytes"])


def roofline_s(c: Dict[str, object], peaks: Dict[str, float]) -> Tuple[float, str]:
    """Least time the chip could take for one call, and which bound sets it."""
    t_compute = flops(c) / peaks["bf16_flops_per_s"]
    t_memory = bytes_moved(c) / peaks["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")


def matcher(c: Dict[str, object]):
    """Whether a device op is this call's forward: a Mosaic custom call with
    one output, of shape ``[batch, heads, q_len, v_head_dim]``, and three
    floating-point operands, ``q`` of shape ``[batch, heads, q_len,
    head_dim]`` first. Integer operands (the kernel's block plan) do not
    count. A backward kernel reads ``o`` or its gradient besides ``q``,
    ``k`` and ``v``, or writes several gradients, so it is not matched even
    where one of its outputs has the forward's shape."""
    out = f"[{c['batch']},{c['heads']},{c['q_len']},{v_dim(c)}]"
    q = f"{c['batch']},{c['heads']},{c['q_len']},{c['head_dim']}"

    def match(event) -> bool:
        if 'custom_call_target="tpu_custom_call"' not in event.name:
            return False
        head, _, rest = event.name.partition(" custom-call(")
        result = head.split(" = ", 1)[-1]
        if result.startswith("(") or not result.split("{", 1)[0].endswith(out):
            return False
        floats = FLOAT_OPERAND.findall(rest.split("), custom_call_target", 1)[0])
        return len(floats) == 3 and floats[0] == q

    return match
