"""Analytic FLOPs accounting + device peak table -> MFU.

The reference never measured utilization (its throughput story is
words/sec charts, reference README.md:29-41); on TPU the judged metric is
MFU, so the framework carries its own model-FLOPs math: matmul FLOPs are
counted analytically per word (2*M*N*K per [M,K]x[K,N] matmul, backward
= 2x forward for the two grad matmuls per layer), and MFU divides the
achieved FLOP rate by the chip's published bf16 peak.

Elementwise/gather work (LSTM activations, embedding lookups, sampled-
softmax log-probs) is deliberately excluded: MFU is a matmul-utilization
metric — counting non-MXU FLOPs would inflate it.
"""

from __future__ import annotations

from typing import Optional


def lm1b_matmul_flops_per_word(cfg, full_softmax: bool = False) -> int:
    """Fwd+bwd matmul FLOPs per predicted word for the LM1B LSTM LM.

    Per token the forward runs (models/lm1b.py):
      * the fused gate matmul  [1, E+P] x [E+P, 4H]   (2*(E+P)*4H)
      * the projection         [1, H]   x [H, P]      (2*H*P)
      * softmax logits         [1, P]   x [P, S+1]    (sampled: S
        candidates + the true label; full: the whole padded vocab)
    Backward costs 2x forward (each matmul contributes dL/dW and dL/dx).
    """
    E, H, P = cfg.emb_dim, cfg.hidden_dim, cfg.proj_dim
    fwd = 2 * (E + P) * 4 * H + 2 * H * P
    if full_softmax:
        fwd += 2 * P * cfg.padded_vocab
    else:
        fwd += 2 * P * (cfg.num_samples + 1)
    return 3 * fwd


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

    The one platform gate shared by bench.py, the tuner and the
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


def mfu(flops_per_word: float, words_per_sec_per_chip: float,
        peak: Optional[float]) -> Optional[float]:
    """Model-FLOPs utilization of one chip, or None when the peak is
    unknown — an unknown peak must yield no number, never a wrong one."""
    if not peak:
        return None
    return flops_per_word * words_per_sec_per_chip / peak
