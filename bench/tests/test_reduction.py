"""The trace reduction and the roofline count, on hand-worked intervals and
shapes.  Run by path: ``python -m pytest bench/tests``."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import device_trace  # noqa: E402
import roofline  # noqa: E402

OPS = [(0.0, 2.0, "a"), (1.0, 3.0, "b"), (5.0, 6.0, "a")]
SPANS = [("batch", 0.0, 4.5), ("finalize", 4.5, 5.0), ("decide", 5.0, 7.0)]


def test_union_covered_and_gaps():
    merged = device_trace.union(OPS)
    assert merged == [(0.0, 3.0), (5.0, 6.0)]
    assert device_trace.covered(merged, 1.0, 5.5) == 2.5
    assert device_trace.covered_in(merged, [(0.0, 1.0), (2.5, 5.5)]) == 2.0
    assert device_trace.gaps(merged, 0.0, 7.0) == [(3.0, 5.0), (6.0, 7.0)]
    assert device_trace.gaps(merged, 1.0, 2.0) == []


def test_gaps_are_named_by_the_span_they_fell_in():
    assert device_trace.label((3.0, 5.0), SPANS) == "batch"      # 1.5 s of 2
    assert device_trace.label((6.0, 7.0), SPANS) == "decide"
    assert device_trace.label((8.0, 9.0), SPANS) == "none"


@pytest.mark.parametrize("live,ops_s,idle", [
    # the whole window: every chip idle (3, 3.5) and (4, 5) in
    # batch/finalize, (6, 7) in decide
    ([(0.0, 7.0)], [["a", 3.0], ["b", 2.5]],
     [["batch", 1.0], ["decide", 1.0], ["batch", 0.5]]),
    # a check from 3.25 to 4.25 leaves the window, and chip 1's op with it
    ([(0.0, 3.25), (4.25, 7.0)], [["a", 3.0], ["b", 2.0]],
     [["decide", 1.0], ["finalize", 0.75], ["batch", 0.25]]),
])
def test_breakdown_sums_ops_by_name_and_lists_the_longest_gaps(live, ops_s, idle):
    ops = {0: OPS, 1: [(3.5, 4.0, "b")]}
    out = device_trace.breakdown(ops, SPANS, live)
    assert out["device_ops"] == ops_s
    assert out["idle_gaps"] == idle


def test_groupby_work_counts_rows_groups_and_values_only():
    ops, nbytes = roofline.groupby_work(1000, 10, 1)
    assert ops == 1000.0
    assert nbytes == 4 * 1000 + 4 * 1000 + 4 * 10
    assert roofline.groupby_work(1000, 10, 3) == (3000.0, 4000 + 12000 + 120)
    # bandwidth bounds it on a v5e: 8,040 bytes at 819 GB/s
    assert roofline.least_seconds(1000, 10, 1, "TPU v5 lite") == 8040 / 819e9


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def recorded():
    """A trace recorded on a TPU v5e by ``record_trace.py``: three
    GROUP-BYs in ``batch`` spans, between a 2 ms ``decide``, a 3 ms
    ``wait`` and a 1 ms ``decide`` pause."""
    import json
    path = pathlib.Path(__file__).with_name("small_trace.json")
    rec = json.loads(path.read_text())
    ops = {int(d): [tuple(e) for e in evs] for d, evs in rec["ops"].items()}
    return rec["window"], [tuple(s) for s in rec["spans"]], ops


def test_recorded_union_matches_a_raster():
    (lo, hi), _, ops = recorded()
    merged = device_trace.union(ops[0])
    step = 1e-6
    grid = [lo + (i + 0.5) * step for i in range(int((hi - lo) / step))]
    raster = step * sum(any(s <= t < e for s, e, _ in ops[0]) for t in grid)
    assert abs(device_trace.covered(merged, lo, hi) - raster) < 2 * step * len(merged) + step
    idle = sum(e - s for s, e in device_trace.gaps(merged, lo, hi))
    assert abs(idle + device_trace.covered(merged, lo, hi) - (hi - lo)) < 1e-9


def test_recorded_gaps_fall_in_the_host_pauses():
    (lo, hi), spans, ops = recorded()
    merged = device_trace.union(ops[0])
    for name, s, e in spans:   # the pauses hold at most a stray microsecond
        busy = device_trace.covered(merged, s, e)
        assert (busy > 0.01 * (e - s)) == (name == "batch"), (name, busy)
    out = device_trace.breakdown(ops, spans, [(lo, hi)])
    names = [name for name, _ in out["idle_gaps"]]
    # the 3 ms wait lies inside the longest gap, which is named after it
    wait = next((s, e) for name, s, e in spans if name == "wait")
    assert names[0] == "wait"
    assert out["idle_gaps"][0][1] >= wait[1] - wait[0]
    assert "decide" in names and "batch" in names
