"""What the grouped matrix products of the routed experts
(``ops/moe.routed_experts`` through ``megablox.gmm``, the Mosaic calls
named ``gmm.N``) have to do in one training step on one device:
operations and bytes from the MODEL's work, the rows routed to the held
experts, not from the worst-case buffers the calls are handed.
Arithmetic only; the time comes from the device trace.

A call multiplies the ``rows`` routed here, grouped by expert, by each
group's ``[D, F]`` matrix (or its transpose: the down projection and the
two backward products with respect to the rows have the same count).
Bytes: the rows read, the result written, and every held expert's
matrix fetched once.
"""

from __future__ import annotations


def cost(rows: float, held: int, D: int, F: int, calls: float,
         itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of ``calls`` products over ``rows`` rows
    (the step's calls together: layers x products a layer)."""
    return {"flops": calls * 2 * rows * D * F,
            "bytes": calls * itemsize * (rows * D + rows * F + held * D * F)}
