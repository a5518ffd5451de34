"""What dense causal attention over grouped key/value heads has to do
for one pass over a layer's sequence, from the MODEL's work: a query
attends its ``t + 1`` causal keys, whatever tiles a kernel walks or
masks to cover them. Arithmetic only; the time comes from the device
trace.

``products``: matrix products over the ``T (T + 1) / 2`` causal (query,
key) pairs of each of ``Hq`` heads of size ``D``: forward 2 (``q k^T``,
``p v``), backward 5 (the logits again, ``do v^T``, and dv, dk, dq).
Bytes: the queries' side read or written once a pass (``rows`` arrays of
``[T, Hq, D]``) and every causal key and value tile once a query block
(``block`` queries: block ``c`` sees the keys up to its last query).
"""

from __future__ import annotations


def cost(T: int, Hq: int, Hkv: int, D: int, block: int, products: int,
         rows: int, passes: float, itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of ``passes`` passes over a sequence of
    ``T``."""
    causal = T * (T + 1) // 2
    blocks = -(-T // block)
    keys_read = sum(min((c + 1) * block, T) for c in range(blocks))
    flops = products * 2 * Hq * D * causal
    nbytes = rows * T * Hq * D * itemsize \
        + 2 * keys_read * Hkv * D * itemsize
    return {"flops": passes * flops, "bytes": passes * nbytes}
