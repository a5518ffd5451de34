"""What causal attention under a window over grouped key/value heads has
to do for one pass over a layer's sequence, from the MODEL's work: query
``t`` attends the keys ``s`` with ``0 <= t - s < W``, whatever tiles a
kernel walks or masks to cover them. Arithmetic only; the time comes
from the device trace.

``products``: matrix products over the ``W (W + 1) / 2 + (T - W) W``
attended (query, key) pairs of each of ``Hq`` heads of size ``D``:
forward 2 (``q k^T``, ``p v``), backward 5 (the logits again, ``do
v^T``, and dv, dk, dq). Bytes: the queries' side read or written once a
pass (``rows`` arrays of ``[T, Hq, D]``) and every key and value once a
query block that sees it (``block`` queries: block ``c`` sees the keys
from ``W - 1`` before its first query up to its last). ``W >= T`` is
dense causal attention and gives ``kernels/causal_attn.cost``'s numbers.
"""

from __future__ import annotations


def cost(T: int, W: int, Hq: int, Hkv: int, D: int, block: int,
         products: int, rows: int, passes: float, itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of ``passes`` passes over a sequence of
    ``T`` under a window of ``W``."""
    W = min(W, T)
    pairs = W * (W + 1) // 2 + (T - W) * W
    blocks = -(-T // block)
    keys_read = sum(min((c + 1) * block, T) - max(c * block - W + 1, 0)
                    for c in range(blocks))
    flops = products * 2 * Hq * D * pairs
    nbytes = rows * T * Hq * D * itemsize \
        + 2 * keys_read * Hkv * D * itemsize
    return {"flops": passes * flops, "bytes": passes * nbytes}
