"""The profiler's trace of the measured window, reduced to device busy
intervals on the host's ``time.perf_counter`` clock.

``Profile`` starts ``jax.profiler`` (Python tracing off), writes a
``bench_sync`` annotation whose trace timestamp is matched with
``perf_counter``, and after ``stop`` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData``.  A device's operations are the events of the
``XLA Ops`` line of its plane (``/device:TPU:<k>``).  The trace directory
is deleted once read.

The functions below the class are the reduction, on plain interval lists:
union, coverage inside spans, idle gaps and the host span each gap fell in.
"""
from __future__ import annotations

import pathlib
import re
import shutil
import sys
import time
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

TRACE_ROOT = pathlib.Path(__file__).resolve().parent / "traces"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SYNC = "bench_sync"

Interval = Tuple[float, float]


class Profile:
    def __init__(self, name: str):
        self.dir = TRACE_ROOT / name
        self.t_sync = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        # The first device operation under the profiler pays its set-up.
        jax.numpy.zeros(8).block_until_ready()
        with jax.profiler.TraceAnnotation(SYNC):
            self.t_sync = time.perf_counter()

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def read(self) -> Tuple[Dict[int, List[tuple]], Dict[str, List[str]]]:
        """({device id: [(start, end, op name)]} on the perf_counter clock,
        {plane: [line names]} for the record); deletes the trace."""
        import jax
        try:
            path = next(self.dir.rglob("*.xplane.pb"))
            data = jax.profiler.ProfileData.from_file(str(path))
            sync_ns, ops, layout = None, {}, {}
            for plane in data.planes:
                lines = list(plane.lines)
                layout[plane.name] = [f"{l.name}:{sum(1 for _ in l.events)}"
                                      for l in lines]
                m = DEVICE_PLANE.match(plane.name)
                for line in lines:
                    if m and line.name == OPS_LINE:
                        ops[int(m.group(1))] = [
                            (e.start_ns, e.start_ns + e.duration_ns,
                             op_name(e.name)) for e in line.events]
                    elif not m and sync_ns is None:
                        for e in line.events:
                            if e.name == SYNC:
                                sync_ns = e.start_ns
                                break
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        if sync_ns is None:
            raise RuntimeError(f"no {SYNC} annotation in the trace")
        shift = self.t_sync - sync_ns * 1e-9
        return ({d: [(s * 1e-9 + shift, e * 1e-9 + shift, n) for s, e, n in evs]
                 for d, evs in ops.items()}, layout)


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[8,128]{1,0} fusion(...)`` -> ``fusion.3 f32[8,128]``:
    the instruction and its result shape, which tell modules apart."""
    name, _, rest = hlo.partition(" = ")
    return f"{name.lstrip('%')} {rest.split('{', 1)[0].split(' ', 1)[0]}".strip()


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the disjoint ``merged`` intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def covered_in(merged: Sequence[Interval], spans: Sequence[Interval]) -> float:
    """Seconds of the disjoint ``spans`` that ``merged`` covers."""
    return sum(covered(merged, s, e) for s, e in spans)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label(gap: Interval, spans: Sequence[tuple]) -> str:
    """Name of the host span covering most of ``gap`` ("none" outside all)."""
    best, name = 0.0, "none"
    for n, s, e in spans:
        o = min(e, gap[1]) - max(s, gap[0])
        if o > best:
            best, name = o, n
    return name


def breakdown(ops: Dict[int, List[tuple]], spans: Sequence[tuple],
              live: Sequence[Interval], top: int = 10) -> dict:
    """Device operations that took most time in the disjoint ``live``
    intervals (the measured window), summed by name over the chips, and the
    longest gaps in them in which no chip ran an operation, each named by
    the host span it fell in."""
    by_name: Dict[str, float] = defaultdict(float)
    for evs in ops.values():
        for s, e, n in evs:
            by_name[n] += covered(live, s, e)
    busiest = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    every = union([iv for evs in ops.values() for iv in evs])
    lo, hi = live[0][0], live[-1][1]
    inside = sorted(((n, s, e) for n, s, e in spans if e > lo and s < hi),
                    key=lambda x: x[1])
    idle = [g for s, e in live for g in gaps(every, s, e)]
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t] for n, t in busiest],
            "idle_gaps": [[label(g, inside), g[1] - g[0]] for g in longest]}


def print_layout(layout: Dict[str, List[str]]) -> None:
    for plane, lines in layout.items():
        print(f"trace plane {plane}: {' '.join(lines)}", file=sys.stderr)
