"""Operations and bytes of one call of the gated delta rule forward kernel.

A call is ``{"batch", "heads", "seq", "key_dim" (K), "value_dim" (V),
"chunk" (Q), "dtype_bytes"}``: q and k of shape ``[batch, heads, seq, K]``,
v and the output ``[batch, heads, seq, V]``, the state ``[batch, heads, V,
K]`` in float32. The counts are what the chunked algorithm needs for the
call, from its shapes, over ``n = ceil(seq / Q)`` chunks of ``Q`` steps.
Per (batch, head, chunk), two FLOPs per multiply-add:

* ``K Kᵀ`` over the strictly lower pairs, and the unit lower triangular
  solve for the written values and their state weights (``(K + V)`` wide
  over those pairs);
* the state's three products, each ``Q x K x V``: its read by the weights
  (``W Sᵀ``), by the queries (``Q Sᵀ``), and the chunk's write
  (``Uᵀ K``);
* ``Q Kᵀ`` and the intra-chunk output ``(decay ∘ Q Kᵀ) U`` over the causal
  pairs, diagonal included.

Bytes: q, k, v and the output once in the call's dtype, g and β in
float32, and the first and last state (float32) read and written once.

The kernel (``repro.kernels.gated_delta``) inverts the triangular matrix by
blocks on whole ``Q x Q`` products and builds the decay matrix elementwise:
costs of the kernel, not of the algorithm, which show as a lower share.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

#: one floating-point operand of a custom call, as HLO text prints it
FLOAT_OPERAND = re.compile(r"(?:^|,\s*)(bf16|f16|f32|f64|f8\w*)\[([\d,]*)\]")
DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8}


def chunks(c: Dict[str, int]) -> int:
    return -(-c["seq"] // c["chunk"])


def visited(c: Dict[str, int]) -> int:
    """Grid steps of (batch, head, chunk) one call visits."""
    return c["batch"] * c["heads"] * chunks(c)


def flops(c: Dict[str, int]) -> float:
    Q, K, V = c["chunk"], c["key_dim"], c["value_dim"]
    strict, causal = Q * (Q - 1) / 2, Q * (Q + 1) / 2
    per_chunk = K * strict + (K + V) * strict + 3 * Q * K * V + (K + V) * causal
    return 2.0 * c["batch"] * c["heads"] * chunks(c) * per_chunk


def bytes_moved(c: Dict[str, int]) -> float:
    b, h, s, K, V = c["batch"], c["heads"], c["seq"], c["key_dim"], c["value_dim"]
    return float(b * h * s * (2 * K + 2 * V) * c["dtype_bytes"] + 2 * 4 * b * h * s
                 + 2 * 4 * b * h * K * V)


def roofline_s(c: Dict[str, int], peaks: Dict[str, float]) -> Tuple[float, str]:
    """Least time the chip could take for one call, and which bound sets it."""
    t_compute = flops(c) / peaks["bf16_flops_per_s"]
    t_memory = bytes_moved(c) / peaks["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")


def _dims(text: str):
    return [(dt, tuple(int(x) for x in shape.split(",") if x))
            for dt, shape in FLOAT_OPERAND.findall(text)]


def call_of(event) -> Optional[Dict[str, Any]]:
    """The call a device op is, if it is this kernel's forward: a Mosaic
    custom call with two outputs, ``o [b, h, T, V]`` and the float32 state
    ``[b, h, V, K]``, and six floating-point operands: q and k ``[b, h, T,
    K]``, v ``[b, h, T, V]``, the float32 rows of g's in-chunk prefix sums
    and of β ``[b, h, T / Q, 1, Q]``, and the first state. None otherwise.
    ``T`` is the sequence padded to the chunk."""
    if 'custom_call_target="tpu_custom_call"' not in event.name:
        return None
    head, _, rest = event.name.partition(" custom-call(")
    result = head.split(" = ", 1)[-1]
    if not result.startswith("("):
        return None
    outs = _dims(result[1:])
    ins = _dims(rest.split("), custom_call_target", 1)[0])
    if len(outs) != 2 or len(ins) != 6:
        return None
    (odt, o), (sdt, st) = outs
    if sdt != "f32" or len(o) != 4 or len(st) != 4 or odt not in DTYPE_BYTES:
        return None
    b, h, T, V = o
    K = st[3]
    rows = ins[3][1]
    want = [(b, h, T, K), (b, h, T, K), (b, h, T, V), rows, rows, (b, h, V, K)]
    if st != (b, h, V, K) or len(rows) != 5 or [s for _, s in ins] != want \
            or [d for d, _ in ins[3:]] != ["f32"] * 3 or rows[:3] != (b, h, rows[2]) \
            or rows[2] * rows[4] != T or rows[3] != 1:
        return None
    return {"batch": b, "heads": h, "seq": T, "key_dim": K, "value_dim": V,
            "chunk": rows[4], "dtype_bytes": DTYPE_BYTES[odt]}
