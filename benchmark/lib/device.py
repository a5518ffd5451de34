"""What the run stands on: the device stamp, the chip's published peaks
and the peak memory. A measurement path that finds no chip fails; it
never falls back to the CPU."""

from __future__ import annotations

import json
import os
import sys

from lib.cell import BENCH_DIR


def stamp(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def require(chips: int, rehearse: bool):
    """The devices the cell runs on. Off the TPU, or with another
    number of chips than the cell is written for, this exits non-zero
    before anything compiles and prints no result. (The program's
    sessions span every device JAX reports, so a machine with more
    chips than the cell asks for would run another cell.)"""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform == "tpu":
            sys.exit("benchmark: --rehearse-cpu on a TPU would stamp a "
                     "chip run as a rehearsal; drop the flag")
        if len(devices) != chips:
            sys.exit(f"benchmark: the rehearsal of a {chips}-chip cell "
                     f"needs XLA_FLAGS=--xla_force_host_platform_device_"
                     f"count={chips} (found {len(devices)} device(s))")
        return devices
    if platform != "tpu":
        sys.exit(f"benchmark: platform is {platform!r}, not 'tpu': no "
                 f"result is printed off the chip (a CPU rehearsal is "
                 f"--rehearse-cpu, and it prints counts only)")
    if len(devices) != chips:
        sys.exit(f"benchmark: the cell is written for {chips} chip(s) "
                 f"and JAX reports {len(devices)}")
    return devices


def peaks(kind: str) -> dict:
    """The published peaks of one chip of this ``device_kind``. A kind
    that is not in the table is an error, not a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["chips"]
    if kind not in table:
        raise KeyError(
            f"benchmark/peaks.json has no row for device_kind {kind!r} "
            f"(known: {sorted(table)}); add the chip's published peaks "
            f"with their source")
    return table[kind]


def memory_peak_bytes(devices):
    """``peak_bytes_in_use`` on the fullest chip, or None where the
    backend reports nothing (XLA:CPU)."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return int(max(peaks_)) if peaks_ else None
