"""What the two LSTM kernels (``ops/pallas_lstm``: the recurrence
forward, and its time-reversed backward) have to do for one training
step on one device: operations from the shapes, bytes from the kernels'
block structure. Arithmetic only; the time comes from the device trace.

The bytes are ``ops/pallas_lstm.kernel_hbm_bytes``'s account, copied:
the forward reads the hoisted input projection ``xw`` and writes the
projected state and the residuals (gate activations, cell trajectory);
the backward reads the cotangent, the gates and the cell trajectory and
writes ``d_xw`` and ``dh``; each call fetches the recurrent weights
``w_h`` and ``w_proj`` once. The operations are the matrix products
inside the kernels: forward ``h @ w_h`` and ``h_full @ w_proj``,
backward their two transposes. The weight gradients and the input
projection are XLA's and are not the kernels'.
"""

from __future__ import annotations


def cost(T: int, B: int, E: int, H: int, P: int, x_itemsize: int = 2,
         w_itemsize: int = 2, g_itemsize: int = 4) -> dict:
    """``{"flops", "bytes"}`` of the forward and backward kernel
    together, for ``T`` steps of ``B`` sequences on this device."""
    wbytes = (P * 4 * H + H * P) * w_itemsize
    fwd_stream = T * B * (4 * H + P) * x_itemsize      # xw in, out
    fwd_stream += T * B * (4 * H + H) * x_itemsize     # gates, c trajectory
    bwd_stream = T * B * (P * g_itemsize               # cotangent
                          + 4 * H * x_itemsize         # gates
                          + 2 * H * x_itemsize         # c and c_prev
                          + 4 * H * x_itemsize         # d_xw
                          + P * 4)                     # dh
    matmul = 2 * T * B * (P * 4 * H + H * P)
    return {"flops": 2 * matmul,
            "bytes": fwd_stream + bwd_stream + 2 * wbytes}
