"""Shared arithmetic of the metric readers in ``metrics/``: spans, batches
and device busy time inside the measured window."""
from __future__ import annotations

from typing import List, Optional, Tuple

import device_trace


def spans(run, name: str) -> List[Tuple[float, float]]:
    """``name`` spans that start inside the measured window."""
    return [(s, e) for n, s, e in run.rec.spans
            if n == name and run.lo <= s < run.hi]


def batches(run) -> list:
    """Batches that start inside the measured window."""
    return [b for b in run.rec.batches if run.lo <= b.start < run.hi]


def device_seconds(run, intervals) -> Optional[float]:
    """Device busy seconds inside the disjoint ``intervals``, summed over
    the chips; None without a trace or without device operations in it."""
    if not run.busy or not any(run.busy):
        return None
    return sum(device_trace.covered_in(b, intervals) for b in run.busy)


def host_share(run) -> Optional[float]:
    """% of ``batch`` span time in which the device ran no operation
    (averaged over the chips)."""
    inside = spans(run, "batch")
    total = sum(e - s for s, e in inside)
    dev = device_seconds(run, inside)
    if dev is None or total <= 0:
        return None
    return 100.0 * (1.0 - dev / (run.chips * total))


def measured(run, left_out: Tuple[str, ...] = ("check",)) -> List[Tuple[float, float]]:
    """The measured window less the spans named in ``left_out``: by default
    the reference checks between rounds, in which the clock stops."""
    off = [iv for name in left_out for iv in spans(run, name)]
    return device_trace.gaps(device_trace.union(off), run.lo, run.hi)


def idle_share(run, without_waits: bool) -> Optional[float]:
    """% of the measured window in which the device ran no operation
    (averaged over the chips); the checks between rounds leave the window,
    and with ``without_waits`` the pacing waits too."""
    live = measured(run, ("check", "wait") if without_waits else ("check",))
    total = sum(e - s for s, e in live)
    dev = device_seconds(run, live)
    if dev is None or total <= 0:
        return None
    return 100.0 * (1.0 - dev / (run.chips * total))
