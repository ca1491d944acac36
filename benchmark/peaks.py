"""The one table of chip peaks the benchmark's utilisation and roofline
numbers are taken against. Source: Google Cloud documentation, "TPU v5e"
system architecture page (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB per chip).
Keyed by a substring of PJRT's ``device_kind``; a device that is not here is
an error, never a default."""
from __future__ import annotations

from typing import Dict

PEAKS = (
    # (device_kind substring, bf16 FLOP/s, HBM bytes/s)
    ("v5 lite", 197e12, 819e9),
    ("v5e", 197e12, 819e9),
)


def chip_peaks(device_kind: str) -> Dict[str, float]:
    kind = device_kind.lower()
    for sub, flops, bandwidth in PEAKS:
        if sub in kind:
            return {"flops_per_s": flops, "bytes_per_s": bandwidth}
    raise KeyError(
        f"no peaks on record for device_kind {device_kind!r}; add it to "
        "benchmark/peaks.py with its source"
    )
