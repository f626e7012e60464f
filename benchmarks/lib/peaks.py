"""Per-chip peaks by TPU generation, matched as a substring of
``device_kind``. Source: Google Cloud TPU documentation, the
per-generation "System architecture" pages (v5e: 197 TFLOP/s bf16,
819 GB/s HBM, 16 GB; v5p: 459, 2765; v4: 275, 1228; v6e: 918, 1638).
Copied from ``bench.py``'s ``PEAK_FLOPS`` / ``PEAK_HBM_BW`` so that the
yardstick lies where later PRs cannot edit it. A device that is not in
the table is an error, never a default."""

from __future__ import annotations

PEAKS = {
    # key: (bf16 FLOP/s, HBM bytes/s)
    "v5e": (197e12, 819e9),
    "v5 lite": (197e12, 819e9),     # v5e reports "TPU v5 lite"
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v6e": (918e12, 1638e9),
    "v6 lite": (918e12, 1638e9),
}


def peaks(device_kind: str) -> tuple[float, float]:
    kind = device_kind.lower()
    for key, value in PEAKS.items():
        if key in kind:
            return value
    raise ValueError(
        f"no peak on record for device_kind {device_kind!r}; known: "
        f"{sorted(PEAKS)}. Add the device with its source to "
        "benchmarks/lib/peaks.py instead of assuming one")
