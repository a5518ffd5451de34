"""The device peak table: published bf16 FLOP/s of one chip, by kind.

The tuner's cost model, the session's step-FLOPs gauge and
``chip_smoke.py`` divide by it. A model's own FLOP count is not kept
here: the benchmark counts a configuration's operations beside its
reference (``benchmark/reference/``), and XLA's ``cost_analysis`` gives
the compiled step's.
"""

from __future__ import annotations

from typing import Optional


# Published per-chip bf16 peak (dense, no sparsity), FLOP/s. Keyed by
# substrings of jax's Device.device_kind (lowercased); order matters —
# first match wins, so the more specific names come first.
_TPU_PEAK_BF16 = (
    ("v6 lite", 918e12),   # Trillium / v6e
    ("v6e", 918e12),
    ("v5 lite", 197e12),   # v5e
    ("v5e", 197e12),
    ("v5litepod", 197e12),
    ("v5p", 459e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_per_chip(device_kind: str) -> Optional[float]:
    """bf16 peak FLOP/s for one chip of this ``device_kind``, or None
    when the kind matches nothing in the table."""
    k = (device_kind or "").lower()
    for sub, peak in _TPU_PEAK_BF16:
        if sub in k:
            return peak
    return None


def device_peak_flops(platform: str, device_kind: str
                      ) -> Optional[float]:
    """Per-chip bf16 peak FLOP/s for the RUNNING backend.

    The one platform gate shared by chip_smoke.py, the tuner and the
    forensics timeline: off the TPU (``platform != "tpu"``) there is
    no peak and the answer is None — never a fabricated TPU number.
    On the TPU the kind resolves against the published per-chip table
    (v2..v6e), and a kind the table does not hold RAISES: a utilization
    silently reported as null on the chip it was meant for is a wrong
    record, so new hardware gets a table row, not a default.
    """
    if platform != "tpu":
        return None
    peak = peak_flops_per_chip(device_kind)
    if peak is None:
        raise ValueError(
            f"no published bf16 peak for TPU device_kind "
            f"{device_kind!r}; add it to common/flops._TPU_PEAK_BF16 "
            f"(known: {', '.join(k for k, _ in _TPU_PEAK_BF16)})")
    return peak

