"""Set-up and rounds of one cell: the program's own session path, driven
through ``proxy.PacedExecutor``.

    repro.core.Session(policy="llf-dynamic", calibrate=True)
      -> AnalyticsRuntimeExecutor (one chip)
         | ExecutorPool(worker_backend=MeshAnalyticsBackend(DeviceMesh(k)))
      -> segagg -> partials -> final aggregation (-> mesh merge)

A round is one fresh ``Session`` over ``n`` tumbling windows of every query
of the configuration, each window's ticks drawn from the pool in the seeded
order.  Paced traffic places tick ``j`` of a round at ``(j + 1) / rate``
seconds of modelled time; unpaced traffic places every tick at the start, so
the whole round is a backlog.

Between rounds the clock stops: the round's answers are compared with the
plain reference and let go, as a deployment lets an answer go once it has
been emitted, so the harness never holds more than one round's answers.
"""
from __future__ import annotations

import dataclasses
import math
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import tpch_stream
from proxy import PacedExecutor, Recorder

#: Modelled seconds between the ticks of an unpaced round: all are present
#: before the first decision.
BACKLOG_TICK_S = 1e-6
#: Deadline of unpaced windows, after their close: beyond any run.
BACKLOG_DEADLINE_S = 1e6


@dataclasses.dataclass
class Window:
    query: str          # the configuration's query name
    ticks: List[int]    # pool tick indices
    rows: int
    close: float        # wall instant the last tick was due
    deadline: float     # wall instant the answer was due
    emitted: Optional[float] = None
    result: Optional[np.ndarray] = None
    batches: int = 0


class Cell:
    """A configuration, a traffic mix and the pool, with the program's
    queries and cost models once ``calibrate`` has run."""

    def __init__(self, config: dict, traffic: dict, seed: int, scale: float,
                 backend: str, rate: Optional[float] = None):
        self.config, self.traffic = config, traffic
        self.scale, self.backend = scale, backend
        self.chips = config["shard_across"]
        self.window_ticks = config["window_ticks"]
        self.rate = rate if rate is not None else traffic["ticks_per_s"]
        self.pool = tpch_stream.make_pool(config, seed, scale)
        self.order = tpch_stream.tick_order(seed, config["pool_ticks"])
        self.queries = {q["name"]: q for q in config["queries"]}
        self.program = {name: tpch_stream.program_query(q, config, scale)
                        for name, q in self.queries.items()}
        self.groups = {name: tpch_stream.num_groups(q, config, scale)
                       for name, q in self.queries.items()}
        self.cost_models: Dict[str, object] = {}
        self.mesh = None
        self.windows: List[Window] = []
        self.rec = Recorder()

    @property
    def tick_s(self) -> float:
        return 1.0 / self.rate if self.rate else BACKLOG_TICK_S

    @property
    def period(self) -> float:
        return self.window_ticks * self.tick_s

    def calibrate(self) -> None:
        from repro.core import ShardedCostModel
        from repro.data.tpch import StreamScale
        from repro.serve.analytics import measure_cost_model

        if self.chips > 1:
            from repro.dist import DeviceMesh
            self.mesh = DeviceMesh(self.chips)
        # The program's calibration on the pool's first ticks, at the
        # configuration's batch sizes: sizes up to the window pin the slope
        # that MinBatch is sized from, and so the batches a run picks.
        cal = self.config["calibration"]
        for name, aq in self.program.items():
            files = self.pool[aq.stream][:cal["ticks"]]
            cm = measure_cost_model(aq, files, StreamScale(self.scale),
                                    batch_sizes=tuple(cal["batch_sizes"]),
                                    backend=self.backend)
            self.cost_models[name] = (ShardedCostModel(cm, self.chips)
                                      if self.chips > 1 else cm)

    def run_round(self, n: int, record: bool = True) -> float:
        """Run one session over ``n`` windows of every query; returns the
        wall instant its modelled time 0 was anchored at."""
        from repro.core import Query, RecurringQuerySpec, Session, TraceArrival
        from repro.core.runtime import ExecutorPool
        from repro.data.tpch import StreamScale
        from repro.serve.analytics import (AnalyticsRuntimeExecutor,
                                           MeshAnalyticsBackend)

        W, tick = self.window_ticks, self.tick_s
        ticks = [[next(self.order) for _ in range(W)] for _ in range(n)]
        stamps = [[(w * W + j + 1) * tick for j in range(W)] for w in range(n)]
        offset = (self.traffic["deadline_windows"] * self.period
                  if self.traffic["deadline_windows"] is not None
                  else BACKLOG_DEADLINE_S)
        specs, jobs, files_of, groups_of = [], {}, {}, {}
        for name, aq in self.program.items():
            arr = TraceArrival(timestamps=tuple(stamps[0]))
            base = Query(query_id=name, wind_start=arr.wind_start,
                         wind_end=arr.wind_end, deadline=arr.wind_end + offset,
                         num_tuples_total=W, cost_model=self.cost_models[name],
                         arrival=arr)
            truths = [TraceArrival(timestamps=tuple(s)) for s in stamps]
            spec = RecurringQuerySpec(
                base=base, period=self.period, num_windows=n,
                deadline_offset=offset, truth_factory=truths.__getitem__,
                num_groups=self.groups[name])
            specs.append(spec)
            for w in range(n):
                qid = spec.window_query(w).query_id
                files = [self.pool[aq.stream][t] for t in ticks[w]]
                jobs[qid] = (aq, files)
                files_of[qid] = files
                groups_of[qid] = self.groups[name]
        scale = StreamScale(self.scale)
        if self.mesh is None:
            physical = AnalyticsRuntimeExecutor(jobs, scale, self.backend)
            executor = physical
        else:
            physical = MeshAnalyticsBackend(jobs, scale, self.mesh, self.backend)
            executor = ExecutorPool(worker_backend=physical)

        def rows_of(qid, off, k):
            return sum(len(f["ts"]) for f in files_of[qid][off:off + k])

        rec = self.rec if record else Recorder()
        proxy = PacedExecutor(executor, rec, rows_of, groups_of.__getitem__,
                              paced=bool(self.rate))
        kw = {"shard_across": self.chips} if self.chips > 1 else {}
        session = Session(policy="llf-dynamic", executor=proxy,
                          calibrate=True, **kw)
        for spec in specs:
            if not session.submit(spec):
                print(f"bench: the session refused {spec.base_id}; its windows "
                      f"will have no answer", file=sys.stderr)
        t0 = time.perf_counter()
        proxy.start(t0)
        trace = session.run()
        proxy.stop()
        if not record:
            return t0
        for spec in specs:
            name = spec.base_id
            series = {o.query_id: o for o in trace.outcome_series(name)}
            for w in range(n):
                qid = spec.window_query(w).query_id
                out = series.get(qid)
                self.windows.append(Window(
                    query=name, ticks=ticks[w],
                    rows=rows_of(qid, 0, W),
                    close=t0 + stamps[w][-1],
                    deadline=t0 + stamps[w][-1] + offset,
                    emitted=rec.emitted.get(qid),
                    result=physical.results.pop(qid, None),
                    batches=out.num_batches if out is not None else 0))
        return t0

    def measure(self, seconds: float,
                check: Callable[[List[Window]], None]) -> tuple:
        """Rounds until ``seconds`` of their own time have passed.  The clock
        starts at the first round's anchor and runs through each later round
        from the start of its set-up, so building its ``Session`` counts.  It
        stops from a round's last answer until the next round starts: there,
        ``check`` compares the round's windows due in the window with the
        reference, and their answers are let go (a ``check`` span).  Returns
        the measured window ``(lo, hi)`` on the wall; ``hi - lo`` is
        ``seconds`` and the checks before ``hi``.  Paced traffic has one
        round, sized to cover ``seconds``."""
        per_round = self.traffic["windows_per_round"]
        lo, paused = None, 0.0
        while True:
            n = per_round or math.ceil(seconds / self.period) + 1
            first = len(self.windows)
            t0 = self.run_round(n)
            lo = t0 if lo is None else lo
            hi = lo + paused + seconds
            new = self.windows[first:]
            end = max((w.emitted for w in new if w.emitted is not None),
                      default=time.perf_counter())
            check([w for w in new if w.close <= hi])
            for w in new:
                w.result = None
            if per_round is None or end >= hi:
                return lo, hi
            resume = time.perf_counter()
            self.rec.spans.append(("check", end, resume))
            paused += resume - end

    def warm_up(self) -> None:
        """One untimed round: compiles the batch shapes the scheduler picks."""
        self.run_round(self.traffic["warmup_windows"], record=False)
