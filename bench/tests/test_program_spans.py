"""The reduction of the program's own spans (``program_spans.py``), on
hand-worked intervals.  Run by path: ``python -m pytest bench/tests``."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import program_spans as ps  # noqa: E402

# (name, start, end, id, parent, request), as repro.tracing.Span has it
PROGRAM = [
    ("prep.concat", 1.0, 2.0, 2, 1, "q-w0"),
    ("spill", 4.0, 6.0, 3, 1, "q-w0"),
    ("executor.batch", 1.0, 6.0, 1, 0, "q-w0"),
    ("policy.decide", 7.0, 8.0, 4, 0, None),
    ("session.step", 0.0, 10.0, 0, None, None),
]
PROXY = [("batch", 0.5, 6.5), ("decide", 6.5, 11.0), ("wait", 8.5, 9.5)]


def test_innermost_names_each_piece_by_the_deepest_span():
    assert ps.innermost(PROGRAM) == [
        (0.0, 1.0, "session.step"), (1.0, 2.0, "prep.concat"),
        (2.0, 4.0, "executor.batch"), (4.0, 6.0, "spill"),
        (6.0, 7.0, "session.step"), (7.0, 8.0, "policy.decide"),
        (8.0, 10.0, "session.step")]


def test_innermost_of_siblings_and_gaps():
    spans = [("a", 0.0, 1.0), ("b", 2.0, 3.0), ("c", 2.0, 2.5)]
    assert ps.innermost(spans) == [(0.0, 1.0, "a"), (2.0, 2.5, "c"),
                                   (2.5, 3.0, "b")]


def test_own_time_leaves_out_children_and_waits():
    own = {n: t for n, t, _ in ps.self_seconds(PROGRAM)}
    assert own == {"prep.concat": 1.0, "spill": 2.0, "executor.batch": 2.0,
                   "policy.decide": 1.0, "session.step": 4.0}
    longest = ps.longest_self(PROGRAM, 0.0, 11.0, [(8.5, 9.5)])
    assert longest["session.step"] == [3.0, 0.0]
    assert longest["spill"] == [2.0, 4.0]
    # spans that start outside the window are not counted
    assert "session.step" not in ps.longest_self(PROGRAM, 0.5, 11.0)


def test_idle_time_is_named_by_wait_then_program_then_proxy_span():
    busy = [(2.0, 3.0), (5.0, 6.0)]
    got = dict(ps.idle_by_span(busy, PROGRAM, PROXY, 0.0, 11.0))
    # gaps (0, 2), (3, 5), (6, 11); (8.5, 9.5) is pacing; (10, 11) is
    # outside every program span, inside the proxy's decide
    assert got == pytest.approx({
        "session.step": 3.0, "prep.concat": 1.0, "executor.batch": 1.0,
        "spill": 1.0, "policy.decide": 1.0, "wait": 1.0, "decide": 1.0})
    assert sum(got.values()) == pytest.approx(9.0)
    top = ps.idle_by_span(busy, PROGRAM, PROXY, 0.0, 11.0, top=1)
    assert top == [["session.step", pytest.approx(3.0)]]


def test_idle_by_span_without_program_spans_falls_back_to_the_proxy():
    got = dict(ps.idle_by_span([(2.0, 3.0)], [], PROXY, 0.0, 7.0))
    assert got == pytest.approx({"batch": 5.0, "decide": 0.5, "none": 0.5})


def test_per_count_ms():
    assert ps.per_count_ms(PROGRAM, "spill", 4, 0.0, 11.0) == 500.0
    assert ps.per_count_ms(PROGRAM, "spill", 0, 0.0, 11.0) is None
    assert ps.per_count_ms(PROGRAM, "transfer", 4, 0.0, 11.0) is None
    assert ps.per_count_ms(PROGRAM, "spill", 4, 4.5, 11.0) is None


def test_scope_of_reads_the_op_path():
    assert ps.scope_of({"tf_op": "jit(f)/segagg.pad/dynamic_update_slice"}) \
        == "segagg.pad"
    assert ps.scope_of({"long_name": "x = f32[8] fusion(), metadata="
                        "{op_name=\"jit(m)/mesh.merge/reduce_sum\"}"}) \
        == "mesh.merge"
    assert ps.scope_of({"hlo_module": "jit_scatter"}) == ""


def test_scope_share_counts_device_time_inside_the_spans():
    ops = [(0.0, 1.0, "a"), (1.0, 3.0, "b"), (5.0, 6.0, "c"), (8.0, 9.0, "d")]
    scopes = ["segagg.pad", "segagg.kernel", "segagg.pad", "segagg.pad"]
    inside = [(0.5, 2.0), (5.5, 7.0)]
    # inside: a 0.5, b 1.0, c 0.5; d is outside every span
    assert ps.scope_share(ops, scopes, "segagg.pad", inside) == 50.0
    assert ps.scope_share(ops, scopes, "segagg.kernel", inside) == 50.0
    assert ps.scope_share(ops, scopes, "mesh.merge", inside) == 0.0
    assert ps.scope_share(ops, scopes, "segagg.pad", [(20.0, 21.0)]) is None


def recorded():
    """A trace recorded on a TPU v5e by ``record_span_trace.py``: two
    windows of TPC-Q6-like through the program's session path with the
    tracer on; every device operation with the scope read from its stats."""
    import json
    path = pathlib.Path(__file__).with_name("span_trace.json")
    return json.loads(path.read_text())


def test_recorded_batches_hold_the_scan_steps_and_account_for_the_batch():
    rec = recorded()
    spans = rec["spans"]
    batches = [s for s in spans if s[0] == "executor.batch"]
    assert len(batches) == 4
    for b in batches:
        kids = {s[0]: s for s in spans if s[4] == b[3]}
        assert set(kids) == {"prep.concat", "prep.extract", "transfer",
                             "kernel.dispatch", "spill"}
        assert all(k[5] == b[5] for k in kids.values())
        inside = sum(k[2] - k[1] for k in kids.values())
        assert 0.95 * (b[2] - b[1]) <= inside <= b[2] - b[1]


def test_recorded_device_ops_carry_no_scope():
    """Why ``pad_share.stream`` is left out: on the chip, an ``XLA Ops``
    event's stats hold none of the keys a scope could be read from (the
    padding runs as eager one-op modules, outside any trace of
    ``ops.segagg``), so the reduction finds no ``segagg.pad`` op."""
    rec = recorded()
    assert rec["ops"] and all(sc == "" for *_, sc in rec["ops"])
    assert all(stats == {} for stats in rec["stats"].values())
    pad_ops = [o for o in rec["ops"] if o[2].startswith(
        ("dynamic-update-slice", "select_dynamic-update-slice"))]
    assert pad_ops     # the padding ran, unnamed
    inside = sorted((s[1], s[2]) for s in rec["spans"]
                    if s[0] == "executor.batch")
    ops = [tuple(o[:3]) for o in rec["ops"]]
    scopes = [o[3] for o in rec["ops"]]
    assert ps.scope_share(ops, scopes, "segagg.pad", inside) == 0.0
