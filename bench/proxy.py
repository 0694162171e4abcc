"""A delegating proxy over the program's ``Executor`` protocol: host spans,
per-batch counts and, for paced traffic, the tie between the session's
modelled clock and the wall.

The session's clock is modelled: a batch runs when that clock reaches it.
With ``paced`` the proxy holds each ``submit_batch``, ``submit_shard_group``
and ``finalize`` until ``t0 + clock()`` on the wall, so no batch reads a file
before it was due; when the path falls behind, calls run late and the
lateness is recorded.

Spans, recorded on the host clock and, while the profiler runs, into its
trace (``jax.profiler.TraceAnnotation``): ``batch`` (a submit call),
``finalize``, ``wait`` (pacing) and ``decide`` (the rest of the serving loop,
from the end of one call to the start of the next).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Batch:
    start: float
    rows: int
    groups: int


@dataclasses.dataclass
class Recorder:
    """Everything the proxy saw, on ``time.perf_counter`` seconds."""

    spans: List[tuple] = dataclasses.field(default_factory=list)
    batches: List[Batch] = dataclasses.field(default_factory=list)
    emitted: Dict[str, float] = dataclasses.field(default_factory=dict)
    lateness: List[float] = dataclasses.field(default_factory=list)
    late_at: List[float] = dataclasses.field(default_factory=list)

    def span_seconds(self, name: str, lo: float, hi: float) -> float:
        """Seconds of ``name`` spans inside [lo, hi]."""
        return sum(max(0.0, min(e, hi) - max(s, lo))
                   for n, s, e in self.spans if n == name)


class PacedExecutor:
    """Wraps one executor (or ``ExecutorPool``) of the program; every other
    attribute is the wrapped object's own."""

    def __init__(self, inner, rec: Recorder, rows_of: Callable[[str, int, int], int],
                 groups_of: Callable[[str], int], paced: bool):
        self._inner = inner
        self._rec = rec
        self._rows_of = rows_of
        self._groups_of = groups_of
        self._paced = paced
        self._t0: Optional[float] = None
        self._decide: Optional[tuple] = None

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def start(self, t0: float) -> None:
        """Anchor modelled time 0 at wall instant ``t0`` and open ``decide``."""
        self._t0 = t0
        self._open_decide()

    def stop(self) -> None:
        self._close_decide()

    # -- spans -------------------------------------------------------------
    def _open_decide(self) -> None:
        ann = TraceAnnotation("decide")
        ann.__enter__()
        self._decide = (ann, time.perf_counter())

    def _close_decide(self) -> None:
        if self._decide is not None:
            ann, t = self._decide
            ann.__exit__(None, None, None)
            self._rec.spans.append(("decide", t, time.perf_counter()))
            self._decide = None

    def _enter(self) -> None:
        self._close_decide()
        if not self._paced:
            return
        due = self._t0 + self._inner.clock()
        now = time.perf_counter()
        if now < due:
            with TraceAnnotation("wait"):
                time.sleep(due - now)
            self._rec.spans.append(("wait", now, time.perf_counter()))
        else:
            self._rec.lateness.append(now - due)
            self._rec.late_at.append(now)

    def _timed(self, name: str, fn, *args, **kw):
        self._enter()
        t = time.perf_counter()
        with TraceAnnotation(name):
            out = fn(*args, **kw)
        end = time.perf_counter()
        self._rec.spans.append((name, t, end))
        self._open_decide()
        return out, t, end

    # -- Executor protocol (clock, advance, reset pass through) ------------
    def submit_batch(self, query, num_tuples: int, offset: int, **kw):
        out, t, _ = self._timed("batch", self._inner.submit_batch,
                                  query, num_tuples, offset, **kw)
        self._note_batch(query.query_id, t, num_tuples, offset)
        return out

    def submit_shard_group(self, query, sizes, base_offset: int):
        out, t, _ = self._timed("batch", self._inner.submit_shard_group,
                                  query, sizes, base_offset)
        self._note_batch(query.query_id, t, sum(sizes), base_offset)
        return out

    def finalize(self, query, num_batches: int):
        out, _, end = self._timed("finalize", self._inner.finalize,
                                  query, num_batches)
        self._rec.emitted[query.query_id] = end
        return out

    def _note_batch(self, qid: str, t: float, n: int, offset: int) -> None:
        self._rec.batches.append(
            Batch(t, self._rows_of(qid, offset, n), self._groups_of(qid)))
