"""A kernel's share of its roofline: the least time the chip could take
for its operations and bytes, over the time the trace shows."""

from __future__ import annotations


def least_seconds(cost: dict, peaks: dict) -> dict:
    by_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}


def share_percent(cost: dict, peaks: dict, kernel_seconds: float):
    if not kernel_seconds or kernel_seconds <= 0:
        return None
    return 100.0 * least_seconds(cost, peaks)["seconds"] / kernel_seconds
