"""What the gated delta rule has to do for one pass over a layer's
sequence, from the MODEL's work: the chunked algorithm of
arXiv:2412.06464 at ITS chunk (``CHUNK``, the paper's 64: a constant of
this count and no key of a configuration), whatever chunk, tile or order
of products a kernel runs. Arithmetic only; the time comes from the device
trace.

Forward, a chunk of ``C`` tokens of one of ``H`` heads with keys of
``dk`` and values of ``dv``, eight matrix products: ``q k^T`` and ``k
k^T`` (``2 C^2 dk`` each), the unit lower-triangular system applied to
the keys (``2 C^2 dk``) and to the values (``2 C^2 dv``), ``w S`` and ``q
S`` against the state (``2 C dk dv`` each), the intra-chunk ``p v`` (``2
C^2 dv``) and the state's update (``2 C dk dv``): ``2 C^2 (3 dk + 2 dv)
+ 6 C dk dv``. Solving the system itself is not counted (forward
substitution is ``C^2 / 2`` multiply-adds a column, inside the
"applied" terms' bound). Backward: TWICE the forward's (each product's
two cotangent products; what a kernel makes again of the forward pass
is its own affair). Bytes: ``q``, ``k``, ``v`` read (``itemsize`` each
entry), ``g`` and ``beta`` read (float32), ``o`` written, once a forward
pass; the backward reads those and ``do`` and writes the five
cotangents, once.
"""

from __future__ import annotations

CHUNK = 64


def cost(T: int, H: int, dk: int, dv: int, passes: float,
         backward: bool = False, itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of ``passes`` passes over a sequence of
    ``T``."""
    C = CHUNK
    chunks = -(-T // C)
    flops = H * chunks * (2 * C * C * (3 * dk + 2 * dv) + 6 * C * dk * dv)
    operands = T * H * ((2 * dk + dv) * itemsize + 2 * 4)
    out = T * H * dv * itemsize
    if backward:
        flops, nbytes = 2 * flops, 2 * operands + out
    else:
        nbytes = operands + out
    return {"flops": passes * flops, "bytes": passes * nbytes}
