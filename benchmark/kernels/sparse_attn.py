"""What the three kernels of ``ops/sparse_attention`` have to do for one
pass over a layer's sequence (one call a query chunk), from the MODEL's
work: a query attends its ``min(t + 1, topk)`` selected keys, whatever
tiles of the causal mask the kernel walks to find them. Arithmetic only;
the time comes from the device trace.

``products``: matrix products over the (query, selected key) pairs that
the kernel cannot do without: forward 2 (``q k^T``, ``p v``), backward 5
(the logits again, ``do v^T``, and dv, dk, dq), the heads' summed
probabilities 1. Bytes: the queries' side read and written once a pass
(``rows`` arrays of ``[T, Hq, D]``), every causal key and value tile
once a chunk, the mask once, and for the summed probabilities their
float32 ``[queries, causal keys]`` output.
"""

from __future__ import annotations


def selected_pairs(T: int, topk: int) -> int:
    full = max(T - topk, 0)
    short = min(T, topk)
    return short * (short + 1) // 2 + full * topk


def cost(T: int, Hq: int, Hkv: int, D: int, topk: int, chunk: int,
         products: int, rows: int, passes: float,
         probs_out: bool = False, itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of ``passes`` passes over a sequence of
    ``T``."""
    causal = T * (T + 1) // 2
    chunks = -(-T // chunk)
    keys_read = sum(min((c + 1) * chunk, T) for c in range(chunks))
    flops = products * 2 * Hq * D * selected_pairs(T, topk)
    nbytes = rows * T * Hq * D * itemsize \
        + 2 * keys_read * Hkv * D * itemsize + causal \
        + (4 * causal if probs_out else 0)
    return {"flops": passes * flops, "bytes": passes * nbytes}
