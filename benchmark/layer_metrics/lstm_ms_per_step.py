"""LSTM kernels: device time of one step under the scope ``lstm``
(``ops/pallas_lstm.lstm_scan``): the hoisted input product, the two
kernels (``lstm_kernel_ms_per_step`` is their part), the backward
epilogue's weight products. Own time by layer (``lib/layer_account``),
first device."""

from lib import layer_account


def read(ctx):
    return layer_account.layer_ms_per_step(ctx, "lstm")
