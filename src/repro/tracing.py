"""In-program spans on the host's ``time.perf_counter`` clock.

The session loop, the policy, the executors and the mesh mark where each
layer's work happens with ``span(name)``.  Off by default: a span is then
one flag check that returns a shared no-op context manager.  An operator
(or a benchmark) turns the tracer on around the part of a run it wants to
see, and drains what was recorded::

    from repro import tracing

    tracing.enable()
    session.run()
    tracing.disable()
    spans = tracing.drain()   # [Span(name, start, end, id, parent, request)]

While on, each span also enters ``jax.profiler.TraceAnnotation(name)``, so
a profiler trace taken at the same time shows the program's spans on its
host plane next to the device operations.  ``perf_counter`` is the clock a
caller maps a profiler trace onto, so spans and device operations compare
with no further work.

Counters (``count(name)``) tally how often a step takes one path or
another, under the same switch: off, a count is one flag check; on, it
adds one to ``name``; ``drain_counts()`` returns and clears the tallies.
The kernel layer counts its dispatches per formulation
(``segagg.matmul``, ``segagg.scatter``, ``segagg.hbm_scatter``).

Parents come from a stack: the session loop is single-threaded.  A span's
``request`` is the window's ``query_id`` where the caller knows one, else
its parent's.  This is the wall-clock view of a run; ``SessionTrace``
stays the modelled-clock event log, and neither is copied into the other.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

__all__ = ["Span", "span", "count", "enable", "disable", "drain",
           "drain_counts"]


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float              # time.perf_counter seconds
    end: float
    id: int
    parent: Optional[int]     # id of the enclosing span, None at the top
    request: Optional[str]    # the window's query_id, where one exists


class _Off:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()
_on = False
_annotation = None            # jax.profiler.TraceAnnotation, bound by enable()
_recorded: List[Span] = []
_counts: Dict[str, int] = {}
_open: List["_Live"] = []
_next_id = 0


class _Live:
    __slots__ = ("name", "request", "id", "parent", "start", "_ann")

    def __init__(self, name: str, request: Optional[str]):
        self.name = name
        self.request = request

    def __enter__(self):
        global _next_id
        outer = _open[-1] if _open else None
        self.parent = outer.id if outer is not None else None
        if self.request is None and outer is not None:
            self.request = outer.request
        self.id = _next_id
        _next_id += 1
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        _open.append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        _open.pop()
        self._ann.__exit__(*exc)
        _recorded.append(Span(self.name, self.start, end, self.id,
                              self.parent, self.request))
        return False


def span(name: str, request: Optional[str] = None):
    """Context manager marking one step of the program's work."""
    if not _on:
        return _OFF
    return _Live(name, request)


def count(name: str) -> None:
    """Add one to the counter ``name`` (nothing while tracing is off)."""
    if _on:
        _counts[name] = _counts.get(name, 0) + 1


def enable() -> None:
    """Record spans from now on (and annotate the profiler's trace)."""
    global _on, _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    _on = True


def disable() -> None:
    """Stop recording; spans still open close and are kept."""
    global _on
    _on = False


def drain() -> List[Span]:
    """The spans closed since the last drain, in the order they closed."""
    global _recorded
    out, _recorded = _recorded, []
    return out


def drain_counts() -> Dict[str, int]:
    """The counters tallied since the last ``drain_counts``."""
    global _counts
    out, _counts = _counts, {}
    return out
