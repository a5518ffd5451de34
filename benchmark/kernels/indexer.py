"""What the backward kernel of the indexer's scores
(``ops/sparse_attention``'s ``indexer_bwd``, one call a query chunk) has
to do for one pass over a layer's sequence, from the MODEL's work by
the convention of ``kernels/sparse_attn``: the cotangent of the scores
is zero off a query's ``min(t + 1, topk)`` selected keys, whatever
tiles of the causal mask the kernel walks. Arithmetic only; the time
comes from the device trace.

``products``: the matrix products over the (query, selected key) pairs
of each of the ``Hi`` heads: 3 (the head's products again, and those
for ``dqi`` and ``dki``). Bytes: ``qi`` read and ``dqi`` written once a
pass, every causal tile of ``ki`` read and of ``dki`` written once a
chunk, the scores' cotangent in float32 over the causal keys.
"""

from __future__ import annotations

from kernels.sparse_attn import selected_pairs


def cost(T: int, Hi: int, Di: int, topk: int, chunk: int,
         products: int = 3, passes: float = 1.0, itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of ``passes`` passes over a sequence of
    ``T``."""
    causal = T * (T + 1) // 2
    chunks = -(-T // chunk)
    keys_walked = sum(min((c + 1) * chunk, T) for c in range(chunks))
    flops = products * 2 * Hi * Di * selected_pairs(T, topk)
    nbytes = 2 * T * Hi * Di * itemsize + 2 * keys_walked * Di * itemsize \
        + 4 * causal
    return {"flops": passes * flops, "bytes": passes * nbytes}
