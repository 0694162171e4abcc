"""The check between rounds: each round's answers are compared with the
reference, then let go.  A fault in the first round alone still fails the
run, and the harness never holds more than one round's answers.  Run by
path: ``python -m pytest bench/tests``."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import readings  # noqa: E402
from run import RunView  # noqa: E402


def rehearse(fault: str, seed: int) -> tuple:
    """(result line, ``held`` report) of a CPU rehearsal of wide.backlog."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--workload", "wide.backlog", "--seed", str(seed), "--seconds", "2",
            "--trace", "0", "--rehearse"]
    out = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "planted.py"), fault, "--", *args],
        env=env, capture_output=True, text=True, timeout=900, cwd=BENCH.parent)
    assert out.returncode == 0, out.stderr[-4000:]
    report = json.loads(out.stderr.strip().splitlines()[-1])
    return json.loads(out.stdout.strip().splitlines()[-1]), report


def test_a_fault_in_the_first_round_fails_after_its_answers_went():
    result, report = rehearse("first_round", 2**31 + 29)
    assert report["rounds"] >= 2
    assert result["checks"]["count_abs_err"]["value"] >= 1.0 or \
        result["checks"]["sum_err_f32_units"]["value"] > 16
    assert result["correct"] is False


def test_the_harness_holds_one_round_of_answers_at_most():
    result, report = rehearse("none", 2**31 + 41)
    assert result["correct"] is True and report["rounds"] >= 2
    held = report["held"]
    assert held["at_start"] == 0
    assert 0 < held["at_end"] <= held["round_windows"]


class Rec:
    def __init__(self, spans):
        self.spans = spans


class Win:
    def __init__(self, rows, emitted):
        self.rows, self.emitted = rows, emitted


def test_checks_leave_the_measured_window():
    # rounds (0, 2) and (3, 5.5) with a check from 2 to 3; hi = 5.5
    view = RunView(lo=0.0, hi=5.5, setup_s=1.0,
                   rec=Rec([("batch", 0.0, 1.5), ("check", 2.0, 3.0),
                            ("batch", 3.0, 5.0)]),
                   windows=[Win(100, 1.5), Win(200, 2.0), Win(300, 5.0),
                            Win(400, 6.0)],
                   chips=1, device_kind="TPU v5 lite",
                   busy=[[(0.5, 1.0), (2.2, 2.8), (4.0, 5.0)]])
    assert readings.measured(view) == [(0.0, 2.0), (3.0, 5.5)]
    # 600 rows answered by 5.0, after 4.0 measured seconds
    rows_per_s = run_metric("rows_per_s", view)
    assert rows_per_s == 600 / 4.0
    # device busy 1.5 of 4.5 measured seconds; the check's 0.6 s left out
    assert abs(readings.idle_share(view, without_waits=False)
               - 100 * (1 - 1.5 / 4.5)) < 1e-9


def run_metric(name, view):
    import run
    return run.read_metric(name, view)


WIDE = json.loads((BENCH / "configs" / "tpch-paper-wide.json").read_text())


class Answer:
    def __init__(self, query, ticks, result):
        self.query, self.ticks, self.result = query, ticks, result
        self.emitted = 1.0


def plain(ref, pool, name, ticks):
    """The window's float64 sums by one ``np.bincount``, as the reference
    read them before it worked in place."""
    import numpy as np
    import tpch_stream

    q = ref.queries[name]
    files = [pool[q["stream"]][t] for t in ticks]
    keys = np.concatenate([tpch_stream.keys_of(q, f) for f in files])
    vals = np.concatenate([tpch_stream.values_of(q, f, np.float64)
                           for f in files])
    return np.bincount(keys, weights=vals,
                       minlength=tpch_stream.num_groups(q, WIDE, SCALE))


SCALE = 0.01


@pytest.fixture(scope="module")
def wide():
    import reference
    import tpch_stream

    pool = tpch_stream.make_pool(WIDE, 2**31 + 53, SCALE)
    return reference.Reference(WIDE, pool, SCALE), pool


@pytest.mark.parametrize("name", [q["name"] for q in WIDE["queries"]])
def test_the_reference_sums_as_a_bincount_does(wide, name):
    ref, pool = wide
    ticks = [7, 3, 200, 3]
    want = plain(ref, pool, name, ticks)
    assert abs(ref.window(name, ticks)[0] - want).max() <= 1e-9 * abs(want).max()


@pytest.mark.parametrize("fault,number,reads", [
    (None, "sum_err_f32_units", lambda v: v < 16),
    ("count", "count_abs_err", lambda v: v == 1.0),
    ("nan", "sum_err_f32_units", lambda v: v == float("inf")),
])
def test_the_check_reads_each_window_and_folds_the_rounds(wide, fault, number,
                                                          reads):
    import numpy as np
    import reference

    ref, pool = wide
    check = reference.Check(ref)
    for r in range(3):   # three rounds; the fault in the first only
        answers = []
        for i, name in enumerate(ref.queries):
            ticks = list(range(12 * (3 * r + i), 12 * (3 * r + i) + 12))
            got = plain(ref, pool, name, ticks).astype(np.float32)[:, None]
            if r == 0 and fault == "count" and name == "CQ3":
                got[1, 0] += 1
            if r == 0 and fault == "nan" and name == "TPC-Q15-like":
                got[2, 0] = np.nan
            answers.append(Answer(name, ticks, got))
        check(answers)
    assert check.numbers["missing_windows"] == 0
    assert reads(check.numbers[number]), check.numbers


def test_a_check_allocates_nothing_of_an_answers_size():
    import tracemalloc

    import numpy as np
    import reference
    import tpch_stream

    pool = tpch_stream.make_pool(WIDE, 2**31 + 59, 1.0)
    ref = reference.Reference(WIDE, pool, 1.0)
    answers = [Answer(name, list(range(12)),
                      np.zeros((len(ref.scratch[name][0]), 1), np.float32))
               for name in ref.queries]
    tracemalloc.start()
    try:
        reference.compare(ref, answers)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    # the smallest answer, CQ3's 360,000 float32 sums, is 1.44 MB
    assert peak < 256 * 1024
