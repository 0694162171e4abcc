"""The plain reference and the comparison that decides ``correct``.

The reference imports nothing of the program: it sums a window's rows by
group in float64, tick by tick, with ``np.add.at`` over the pool's columns.

The check runs between a cell's rounds, inside the process whose heap the
program churns, so it must leave that heap as the program left it: every
array it uses is made once at set-up (the pool's key and value columns of
each query laid end to end, and per query a float64 sum, sum of absolute
values and gap, each of the query's group count) and reused for every
window.  A window's check allocates no block of the size of its answer.

Numbers compared, each with the configuration's limit:

* ``missing_windows``: windows due in the measured window with no answer;
* ``count_abs_err``: the largest gap between an answered count and the
  reference's, over every group of every such window (exact: limit 0);
* ``sum_err_f32_units``: the largest gap of a float sum, in units of
  float32's unit roundoff times the group's sum of absolute values,
  ``|got - ref| / (2**-24 * sum |v|)``.  Float32 sums of a few roundings
  read a few units; a bfloat16 input rounding reads up to 2**16 on one
  value, and hundreds on a sum of many.  A NaN reads as infinite.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

import tpch_stream

F32_UNIT = 2.0 ** -24


class Reference:
    def __init__(self, config: dict, pool: dict, scale: float):
        self.queries = {q["name"]: q for q in config["queries"]}
        self.columns, self.scratch = {}, {}
        for name, q in self.queries.items():
            files = pool[q["stream"]]
            keys = np.concatenate([tpch_stream.keys_of(q, f) for f in files])
            vals = np.concatenate([tpch_stream.values_of(q, f, np.float64)
                                   for f in files])
            starts = np.cumsum([0] + [len(f["ts"]) for f in files]).tolist()
            self.columns[name] = (keys, vals, np.abs(vals), starts)
            groups = tpch_stream.num_groups(q, config, scale)
            self.scratch[name] = (np.zeros(groups), np.zeros(groups),
                                  np.zeros(groups), np.zeros(groups, bool))

    def window(self, name: str, ticks: List[int]) -> Tuple[np.ndarray, np.ndarray]:
        """(sums, sums of absolute values) by group, float64, in the query's
        scratch: valid until the next window of the query."""
        keys, vals, abs_vals, starts = self.columns[name]
        sums, abs_sums = self.scratch[name][:2]
        count = tpch_stream.is_count(self.queries[name])   # every value is 1
        sums.fill(0.0)
        if not count:
            abs_sums.fill(0.0)
        for t in ticks:
            s, e = starts[t], starts[t + 1]
            np.add.at(sums, keys[s:e], vals[s:e])
            if not count:
                np.add.at(abs_sums, keys[s:e], abs_vals[s:e])
        return sums, (sums if count else abs_sums)


def worst(a: float, b: float) -> float:
    """The larger gap, a NaN reading as infinite."""
    return math.inf if math.isnan(b) else max(a, b)


def compare(ref: Reference, windows) -> Dict[str, float]:
    """The compared numbers over ``windows`` (``drive.Window``s)."""
    missing, count_err, sum_err = 0, 0.0, 0.0
    for w in windows:
        if w.result is None or w.emitted is None:
            missing += 1
            continue
        want, abs_sum = ref.window(w.query, w.ticks)
        got = np.asarray(w.result)
        if got.shape != (len(want), 1):
            missing += 1
            continue
        gap, nonzero = ref.scratch[w.query][2:]
        np.subtract(got[:, 0], want, out=gap)
        np.abs(gap, out=gap)
        if tpch_stream.is_count(ref.queries[w.query]):
            count_err = worst(count_err, float(gap.max()))
            continue
        np.not_equal(gap, 0.0, out=nonzero)
        with np.errstate(divide="ignore", invalid="ignore"):
            np.divide(gap, abs_sum, out=gap, where=nonzero)
        sum_err = worst(sum_err, float(gap.max()) / F32_UNIT)
    return {"missing_windows": missing, "count_abs_err": count_err,
            "sum_err_f32_units": sum_err}


class Check:
    """The running check of a measured window, called with each round's
    windows: a count of the missing windows and the largest gaps so far."""

    def __init__(self, ref: Reference):
        self.ref = ref
        self.numbers = compare(ref, [])

    def __call__(self, windows) -> None:
        new, old = compare(self.ref, windows), self.numbers
        self.numbers = {
            "missing_windows": old["missing_windows"] + new["missing_windows"],
            "count_abs_err": worst(old["count_abs_err"], new["count_abs_err"]),
            "sum_err_f32_units": worst(old["sum_err_f32_units"],
                                       new["sum_err_f32_units"])}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in numbers)
