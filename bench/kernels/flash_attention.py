"""Operations and bytes of one call of the flash-attention forward kernel.

The counts are what the algorithm needs for the call, from its shapes:

* operations: ``Q K^T`` and ``P V``, two FLOPs per multiply-add, over the
  (query, key) pairs that the mask keeps (for a causal square call,
  ``S (S + 1) / 2`` pairs per head);
* bytes: ``q``, ``k`` and ``v`` read once and ``o`` written once, in the
  call's dtype.

The kernel itself (``repro.kernels.flash_attention``) visits every block,
masked or not, and reads each k/v block once per q block; both are costs
of the kernel, not of the algorithm, and show as a lower share.
"""
from __future__ import annotations

from typing import Dict, Tuple



def pairs(q_len: int, kv_len: int, causal: bool) -> float:
    """(query, key) pairs the mask keeps; queries are right-aligned."""
    if not causal:
        return float(q_len) * kv_len
    off = kv_len - q_len
    return float(sum(min(kv_len, off + i + 1) for i in range(q_len)))


def flops(c: Dict[str, object]) -> float:
    return 2.0 * 2.0 * c["batch"] * c["heads"] * c["head_dim"] * pairs(
        c["q_len"], c["kv_len"], c["causal"])


def bytes_moved(c: Dict[str, object]) -> float:
    q = c["batch"] * c["heads"] * c["q_len"] * c["head_dim"]
    kv = 2 * c["batch"] * c["kv_heads"] * c["kv_len"] * c["head_dim"]
    return float((2 * q + kv) * c["dtype_bytes"])


def roofline_s(c: Dict[str, object], peaks: Dict[str, float]) -> Tuple[float, str]:
    """Least time the chip could take for one call, and which bound sets it."""
    t_compute = flops(c) / peaks["bf16_flops_per_s"]
    t_memory = bytes_moved(c) / peaks["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")


def matcher(c: Dict[str, object]):
    """Whether a device op is this call: a Mosaic custom call whose output
    has the call's query shape ``[batch, heads, q_len, head_dim]``."""
    shape = f"[{c['batch']},{c['heads']},{c['q_len']},{c['head_dim']}]"

    def match(event) -> bool:
        head = event.name.split(" custom-call(", 1)[0]
        return 'custom_call_target="tpu_custom_call"' in event.name and shape in head

    return match
