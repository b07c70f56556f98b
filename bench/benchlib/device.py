"""The device gate and the table of peaks.

A measurement that finds no TPU, or fewer chips than its cell asks for,
fails: it never falls back to the CPU.  Peaks are keyed by JAX's
``device_kind``; a device that is not in the table is an error, not a
default.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List


class DeviceError(Exception):
    """The run is not on the device the benchmark measures."""


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: float       # bytes of device memory
    source: str


# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 16 GB HBM2 at 819 GB/s per chip.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_s=819e9,
                         hbm_bytes=16e9,
                         source="Google Cloud, 'TPU v5e' system architecture"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise DeviceError(
            f"no peaks known for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def gate(chips: int, platform: str = "tpu") -> List:
    """The devices of this run; raises unless JAX's devices are ``chips``
    or more of ``platform`` and the retrieve kernels run compiled."""
    mode = os.environ.get("REPRO_KERNEL_MODE")
    if platform == "tpu" and mode not in (None, "", "pallas"):
        raise DeviceError(f"REPRO_KERNEL_MODE={mode!r}: the benchmark runs "
                          f"the compiled kernels only")
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise DeviceError(f"no {platform.upper()} found: JAX's first device "
                          f"is {devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise DeviceError(f"the cell needs {chips} chip(s), JAX finds "
                          f"{len(devs)}")
    return devs[:chips]


def describe(devs) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(devs) -> int:
    """The high-water mark of the fullest chip so far (0 where the backend
    reports none)."""
    out = 0
    for d in devs:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out
