"""The program's own spans (``repro.tracing``) reduced to per-layer readings,
on the same ``perf_counter`` clock as the proxy's spans and the device
trace.

A program span is ``(name, start, end, id, parent, request)``, as
``repro.tracing.Span`` has it.  The spans of one thread nest, so the time
axis splits into pieces each owned by the innermost span open over it
(``innermost``).  A device-idle gap is then named by the proxy's ``wait``
where the benchmark was pacing, else by the innermost program span over
it, else by the proxy span it fell in (``idle_by_span``).

``scope_of`` reads the named scope (``jax.named_scope``) of each device
operation from its trace event's stats; ``ScopedProfile`` keeps them next
to ``device_trace.Profile``'s ``(start, end, name)`` tuples.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import device_trace

#: The program's device-side scopes (``kernels/segagg/ops.py``,
#: ``dist/mesh.py``).
SCOPES = ("segagg.pad", "segagg.kernel", "mesh.merge")
#: Trace event stats that can carry an operation's scope path.
SCOPE_STATS = ("tf_op", "long_name", "name")
WAIT = "wait"


def spans_named(spans: Sequence, name: str, lo: float, hi: float) -> list:
    """Program spans called ``name`` that start inside [lo, hi)."""
    return [s for s in spans if s[0] == name and lo <= s[1] < hi]


def per_count_ms(spans: Sequence, name: str, count: int, lo: float,
                 hi: float) -> Optional[float]:
    """Milliseconds of ``name`` spans started in [lo, hi), per ``count``
    (batches, windows); None when nothing was counted or recorded."""
    mine = spans_named(spans, name, lo, hi)
    if count <= 0 or not mine:
        return None
    return 1e3 * sum(s[2] - s[1] for s in mine) / count


def innermost(spans: Sequence) -> List[Tuple[float, float, str]]:
    """Disjoint, sorted pieces of the time axis, each named by the deepest
    of the nested ``spans`` open over it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []      # (name, end), innermost last
    t = 0.0

    def close_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][1] <= limit:
            name, end = stack.pop()
            if end > t:
                out.append((t, end, name))
            t = max(t, end)

    for name, s, e, *_ in sorted(spans, key=lambda x: (x[1], -x[2])):
        close_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1][0]))
        stack.append((name, e))
        t = s
    close_until(float("inf"))
    return out


def self_seconds(spans: Sequence) -> List[Tuple[str, float, float]]:
    """(name, seconds not covered by a child span, start) of each span."""
    inner: Dict[int, float] = defaultdict(float)
    for s in spans:
        if s[4] is not None:
            inner[s[4]] += s[2] - s[1]
    return [(s[0], (s[2] - s[1]) - inner[s[3]], s[1]) for s in spans]


def longest_self(spans: Sequence, lo: float, hi: float,
                 waits: Sequence[Tuple[float, float]] = ()) -> Dict[str, list]:
    """Per span name, the instance with the most time of its own (less its
    children and the disjoint sorted ``waits``) inside [lo, hi):
    {name: [seconds, start - lo]}.  A host stall shows in the innermost
    span that was open while the host stood still."""
    starts = [s for s, _ in waits]
    best: Dict[str, list] = {}
    inside = [s for s in spans if lo <= s[1] < hi]
    for (name, own, start), s in zip(self_seconds(inside), inside):
        own -= overlap((s[1], s[2]), waits, starts)
        if own > best.get(name, [-1.0])[0]:
            best[name] = [own, start - lo]
    return dict(sorted(best.items()))


def _name_pieces(a: float, b: float, pieces: Sequence[tuple],
                 starts: Sequence[float], out: Dict[str, float]
                 ) -> List[Tuple[float, float]]:
    """Add the seconds of [a, b) under each named piece to ``out``; return
    the parts of [a, b) that some piece covered."""
    i = max(0, bisect.bisect_right(starts, a) - 1)
    named = []
    while i < len(pieces) and pieces[i][0] < b:
        s, e, name = pieces[i]
        o0, o1 = max(s, a), min(e, b)
        if o1 > o0:
            out[name] += o1 - o0
            named.append((o0, o1))
        i += 1
    return named


def idle_by_span(busy: Sequence[Tuple[float, float]], program: Sequence,
                 proxy: Sequence[tuple], lo: float, hi: float,
                 top: int = 10) -> List[list]:
    """Seconds of [lo, hi) in which no chip ran an operation, summed by
    what the host was doing: ``wait`` (the proxy's pacing), else the
    innermost program span, else the proxy span the time fell in (or
    "none").  ``busy``: the union of device busy intervals over the chips.
    Top ``top``, longest first."""
    waits = device_trace.union([(s, e) for n, s, e in proxy if n == WAIT])
    inner = innermost([s for s in program if s[2] > lo and s[1] < hi])
    outer = innermost([s for s in proxy if s[0] != WAIT])
    inner_starts = [p[0] for p in inner]
    outer_starts = [p[0] for p in outer]
    out: Dict[str, float] = defaultdict(float)
    for g0, g1 in device_trace.gaps(busy, lo, hi):
        out[WAIT] += device_trace.covered(waits, g0, g1)
        for a, b in device_trace.gaps(waits, g0, g1):
            named = _name_pieces(a, b, inner, inner_starts, out)
            for c, d in device_trace.gaps(named, a, b):
                got = _name_pieces(c, d, outer, outer_starts, out)
                out["none"] += (d - c) - sum(y - x for x, y in got)
    return [[n, t] for n, t in sorted(out.items(), key=lambda kv: -kv[1])
            if t > 1e-12][:top]


def scope_of(stats: Dict[str, object]) -> str:
    """The program's named scope an operation ran under ('' for none),
    from its trace event's stats."""
    for key in SCOPE_STATS:
        value = stats.get(key)
        if isinstance(value, str):
            for scope in SCOPES:
                if scope in value:
                    return scope
    return ""


def overlap(iv: Tuple[float, float], inside: Sequence[Tuple[float, float]],
            starts: Sequence[float]) -> float:
    """Seconds of ``iv`` inside the disjoint sorted ``inside`` intervals
    (``starts``: their starts, for the search)."""
    s, e = iv
    i, t = max(0, bisect.bisect_right(starts, s) - 1), 0.0
    while i < len(inside) and inside[i][0] < e:
        t += max(0.0, min(inside[i][1], e) - max(inside[i][0], s))
        i += 1
    return t


def scope_share(ops: Sequence[tuple], scopes: Sequence[str], scope: str,
                inside: Sequence[Tuple[float, float]]) -> Optional[float]:
    """% of the device time inside the disjoint sorted ``inside`` spans
    that ops of ``scope`` took; ``ops`` one chip's ``(start, end, name)``,
    ``scopes`` their scopes.  None when no op ran inside."""
    starts = [s for s, _ in inside]
    total = mine = 0.0
    for (s, e, _), sc in zip(ops, scopes):
        t = overlap((s, e), inside, starts)
        total += t
        if sc == scope:
            mine += t
    if total <= 0:
        return None
    return 100.0 * mine / total


class ScopedProfile(device_trace.Profile):
    """``device_trace.Profile`` that also keeps each device operation's
    scope: ``self.scopes[device id]`` runs parallel to that device's
    ``(start, end, name)`` list from ``read``."""

    def read(self):
        import jax
        self.scopes: Dict[int, List[str]] = {}
        self.stats_by_op: Dict[str, dict] = {}
        path = next(self.dir.rglob("*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(str(path))
        for plane in data.planes:
            m = device_trace.DEVICE_PLANE.match(plane.name)
            if not m:
                continue
            for line in plane.lines:
                if line.name != device_trace.OPS_LINE:
                    continue
                row = []
                for e in line.events:
                    stats = {k: v for k, v in e.stats}
                    row.append(scope_of(stats))
                    self.stats_by_op.setdefault(
                        device_trace.op_name(e.name),
                        {k: str(v)[:300] for k, v in stats.items()})
                self.scopes[int(m.group(1))] = row
        return super().read()
