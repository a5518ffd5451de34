"""What the experts' weight-gradient products (``megablox.tgmm``, the
Mosaic calls named ``tgmm.N``, the backward of ``ops/moe.routed_experts``)
have to do in one training step on one device: for each held expert the
transposed rows routed to it times their cotangents, ``[D, rows_e] x
[rows_e, F]``. The count is the grouped product's (``kernels/moe_gmm``):
the same operations over the rows routed here, both operands read once,
every held expert's ``[D, F]`` result written where that one fetches a
matrix. Arithmetic only; the time comes from the device trace.
"""

from kernels.moe_gmm import cost  # noqa: F401
