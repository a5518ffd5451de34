"""Step layer: the share of the device's busy time that the account
puts under a declared layer (``parallax_tpu/obs/xprof.LAYER_SCOPES``),
%: 100 less the unscoped and unknown parts. First device."""

from lib import layer_account


def read(ctx):
    return layer_account.coverage_percent(ctx)
