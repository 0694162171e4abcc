"""Peaks of the chips the benchmark runs on, and the work a GROUP-BY needs.

The work is a function of the batch's ``N`` rows, ``G`` groups and ``V``
value columns alone, so it reads the same whatever kernel implements the
aggregation: ``4N`` key bytes and ``4NV`` value bytes read, ``4GV`` bytes of
partial written, and ``NV`` adds.  No padding is counted.
"""
from __future__ import annotations

from typing import Tuple

#: device_kind -> (peak FLOP/s, peak HBM bytes/s, source).
PEAKS = {
    "TPU v5 lite": (197e12, 819e9,
                    "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                    "819 GB/s HBM"),
}


def peaks(device_kind: str) -> Tuple[float, float]:
    """(FLOP/s, bytes/s) of one chip; a device not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    flops, bw, _ = PEAKS[device_kind]
    return flops, bw


def groupby_work(n: int, g: int, v: int = 1) -> Tuple[float, float]:
    """(operations, bytes) one GROUP-BY of ``n`` rows into ``g`` groups of
    ``v`` values needs."""
    return float(n * v), 4.0 * n + 4.0 * n * v + 4.0 * g * v


def least_seconds(n: int, g: int, v: int, device_kind: str) -> float:
    """The least time one chip could take for that work: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    flops, bw = peaks(device_kind)
    ops, nbytes = groupby_work(n, g, v)
    return max(ops / flops, nbytes / bw)
