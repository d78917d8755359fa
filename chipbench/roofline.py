"""Peaks by device kind, and a kernel's share of its roofline.

The peaks live in ``peaks.json``, keyed by JAX's ``device_kind``, with
their source. A device that is not in the table is an error.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str, key: str) -> float:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return float(table[device_kind][key])


def share_pct(*, device_kind: str, seconds: float, bytes_moved: float, flops: float = 0.0):
    """Least time the chip could take for the work, over the time taken,
    in percent; None when nothing was timed."""
    if seconds <= 0:
        return None
    least = max(
        bytes_moved / peak(device_kind, "hbm_bytes_per_s"),
        flops / peak(device_kind, "bf16_flops_per_s"),
    )
    return 100.0 * least / seconds
