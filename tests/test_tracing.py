"""The in-program tracer (``repro.tracing``) and the spans the session,
the executors and the mesh put where each layer's work happens.

Pinned properties:

* off (the default) records nothing and enters no profiler annotation;
  on, nested spans get their parent and inherit the request id, and
  ``drain`` empties the record;
* the segagg formulation counter: nothing while off; on, one count per
  ``segagg`` call under the formulation that ran;
* tracing is an observer: a session over the real segagg executor gives
  the same answers and the same ``SessionTrace`` with it on and off, and
  every ``executor.batch`` holds the scan's five steps;
* the mesh path (4 virtual CPU devices, in a child process so the device
  count is set before jax starts) emits ``executor.batch`` and
  ``finalize.merge``.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import LinearCostModel
from repro.data.tpch import PAPER_QUERIES, StreamScale, stream_files
from repro.kernels.segagg.ops import segagg
from repro.kernels.segagg.ref import segagg_numpy
from repro.serve.analytics import concat_files, run_session

SCAN_STEPS = ("prep.concat", "prep.extract", "transfer", "kernel.dispatch",
              "spill")


class Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``; counts entries."""

    entered = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        Annotations.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def tracer(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotations)
    Annotations.entered = []
    tracing.disable()
    tracing.drain()
    tracing.drain_counts()
    yield tracing
    tracing.disable()
    tracing.drain()
    tracing.drain_counts()


def test_off_records_nothing_and_enters_no_annotation(tracer):
    a, b = tracer.span("x"), tracer.span("y", "q-w0")
    assert a is b                       # one shared no-op
    with a:
        with b:
            pass
    assert tracer.drain() == []
    assert Annotations.entered == []


def test_on_nests_parents_and_request_ids(tracer):
    tracer.enable()
    with tracer.span("step"):
        with tracer.span("batch", "q-w3"):
            with tracer.span("prep"):
                pass
            with tracer.span("spill"):
                pass
        with tracer.span("decide"):
            pass
    spans = {s.name: s for s in tracer.drain()}
    assert Annotations.entered == ["step", "batch", "prep", "spill", "decide"]
    step, batch = spans["step"], spans["batch"]
    assert step.parent is None and step.request is None
    assert batch.parent == step.id and batch.request == "q-w3"
    for child in ("prep", "spill"):
        assert spans[child].parent == batch.id
        assert spans[child].request == "q-w3"     # inherited
    assert spans["decide"].parent == step.id and spans["decide"].request is None
    assert step.start <= batch.start <= spans["prep"].start
    assert spans["spill"].end <= batch.end <= step.end
    assert tracer.drain() == []                   # drained


def test_span_open_at_disable_is_kept(tracer):
    tracer.enable()
    with tracer.span("outer"):
        tracer.disable()
        with tracer.span("after"):
            pass
    assert [s.name for s in tracer.drain()] == ["outer"]


def _segagg_calls(groups, calls=2):
    keys = np.arange(300, dtype=np.int32) % groups
    for _ in range(calls):
        segagg(keys, np.ones((300, 1), np.float32), groups,
               backend="interpret")


def test_counter_off_counts_nothing(tracer):
    _segagg_calls(20_000)
    tracer.count("x")
    assert tracer.drain_counts() == {}


@pytest.mark.parametrize("groups,form", [
    (5, "matmul"), (4_096, "scatter"), (20_000, "hbm_scatter")])
def test_counter_counts_each_dispatch_under_its_formulation(
        tracer, groups, form):
    tracer.enable()
    _segagg_calls(groups, calls=3)
    tracer.disable()
    _segagg_calls(groups)                 # off again: not counted
    assert tracer.drain_counts() == {f"segagg.{form}": 3}
    assert tracer.drain_counts() == {}    # drained


SCALE = StreamScale(scale=0.005)


def _session(traced: bool):
    aq = PAPER_QUERIES[1]  # CQ2: 5 groups
    windows, stamps = [], []
    for w in range(2):
        files, times = [], []
        for t, o, line in stream_files(seed=20 + w, num_files=6, sc=SCALE):
            files.append(line if aq.stream == "lineitem" else o)
            times.append(t + w * 10.0)
        windows.append(files)
        stamps.append(times)
    cm = LinearCostModel(tuple_cost=0.4, overhead=0.3, agg_per_batch=0.2)
    if traced:
        tracing.enable()
    try:
        results, trace = run_session(aq, windows, stamps, SCALE, cm,
                                     period=10.0, calibrate=False,
                                     backend="interpret")
    finally:
        tracing.disable()
    return aq, windows, results, trace, tracing.drain()


@pytest.fixture(scope="module")
def sessions():
    tracing.disable()
    tracing.drain()
    return _session(False), _session(True)


def test_tracing_leaves_results_and_session_trace_alone(sessions):
    (aq, windows, plain, trace_off, none), (_, _, traced, trace_on, spans) = sessions
    assert none == [] and spans
    assert sorted(plain) == sorted(traced) == [0, 1]
    for w in plain:
        assert np.array_equal(plain[w], traced[w])
        records = concat_files(windows[w])
        want = segagg_numpy(aq.key_fn(records), aq.value_fn(records),
                            aq.num_groups(SCALE))
        assert np.array_equal(traced[w], want)
    assert trace_on == trace_off


@pytest.mark.parametrize("step", SCAN_STEPS)
def test_every_batch_holds_each_scan_step(sessions, step):
    spans = sessions[1][4]
    batches = [s for s in spans if s.name == "executor.batch"]
    assert batches
    for b in batches:
        inside = [s for s in spans if s.parent == b.id and s.name == step]
        assert len(inside) == 1, (step, b)
        assert b.start <= inside[0].start <= inside[0].end <= b.end
        assert inside[0].request == b.request and b.request.startswith(
            PAPER_QUERIES[1].query_id)


@pytest.mark.parametrize("name,parent", [
    ("session.step", None),
    ("policy.decide", "session.step"),
    ("session.observe", "session.step"),
    ("executor.batch", "session.step"),
    ("executor.finalize", "session.step"),
    ("finalize.merge", "executor.finalize"),
])
def test_layer_spans_sit_under_their_parent(sessions, name, parent):
    spans = sessions[1][4]
    by_id = {s.id: s for s in spans}
    mine = [s for s in spans if s.name == name]
    assert mine
    for s in mine:
        got = by_id[s.parent].name if s.parent is not None else None
        assert got == parent


MESH_CHILD = textwrap.dedent("""
    import json
    from repro import tracing
    from repro.core import (ExecutorPool, LinearCostModel, Query,
                            ShardedCostModel, TraceArrival, get_policy, run)
    from repro.data.tpch import PAPER_QUERIES, StreamScale, stream_files
    from repro.dist import DeviceMesh
    from repro.serve.analytics import MeshAnalyticsBackend

    scale = StreamScale(scale=0.005)
    aq = PAPER_QUERIES[1]
    files = [(line if aq.stream == "lineitem" else o)
             for _, o, line in stream_files(seed=5, num_files=16, sc=scale)]
    wb = MeshAnalyticsBackend({"q0": (aq, files)}, scale, DeviceMesh(4))
    arr = TraceArrival(timestamps=tuple(float(t) for t in range(16)))
    cm = ShardedCostModel(LinearCostModel(tuple_cost=1.0, overhead=1.0), 4)
    q = Query(query_id="q0", wind_start=arr.wind_start, wind_end=arr.wind_end,
              deadline=arr.wind_end + 50.0, num_tuples_total=16,
              cost_model=cm, arrival=arr)
    tracing.enable()
    trace = run(get_policy("llf-dynamic", shard_across=4), [q],
                ExecutorPool(worker_backend=wb))
    tracing.disable()
    spans = tracing.drain()
    print(json.dumps({"complete": trace.outcome("q0").complete,
                      "spans": [[s.name, s.id, s.parent, s.request]
                                for s in spans]}))
""")


@pytest.fixture(scope="module")
def mesh_spans():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.abspath(src), env.get("PYTHONPATH")) if p)
    out = subprocess.run([sys.executable, "-c", MESH_CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name,parent", [
    ("executor.batch", None),
    ("prep.concat", "executor.batch"),
    ("prep.extract", "executor.batch"),
    ("transfer", "executor.batch"),
    ("kernel.dispatch", "executor.batch"),
    ("spill", "executor.batch"),
    ("executor.finalize", None),
    ("finalize.merge", "executor.finalize"),
])
def test_mesh_path_emits_batch_and_merge_spans(mesh_spans, name, parent):
    assert mesh_spans["complete"]
    by_id = {i: n for n, i, _, _ in mesh_spans["spans"]}
    mine = [(p, r) for n, _, p, r in mesh_spans["spans"] if n == name]
    assert mine
    for p, r in mine:
        assert (by_id[p] if p is not None else None) == parent
        assert r == "q0"
