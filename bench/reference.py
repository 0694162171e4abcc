"""The plain reference and the comparison that decides ``correct``.

The reference imports nothing of the program: for every tick of a window it
sums the rows by group in float64 with ``np.bincount`` over the pool's
columns, and adds the ticks' sums.

Numbers compared, each with the configuration's limit:

* ``missing_windows``: windows due in the measured window with no answer;
* ``count_abs_err``: the largest gap between an answered count and the
  reference's, over every group of every such window (exact: limit 0);
* ``sum_err_f32_units``: the largest gap of a float sum, in units of
  float32's unit roundoff times the group's sum of absolute values,
  ``|got - ref| / (2**-24 * sum |v|)``.  Float32 sums of a few roundings
  read a few units; a bfloat16 input rounding reads up to 2**16 on one
  value, and hundreds on a sum of many.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import tpch_stream

F32_UNIT = 2.0 ** -24


class Reference:
    def __init__(self, config: dict, pool: dict, scale: float):
        self.config, self.pool, self.scale = config, pool, scale
        self.queries = {q["name"]: q for q in config["queries"]}

    def window(self, name: str, ticks: List[int]) -> Tuple[np.ndarray, np.ndarray]:
        """(sums, sums of absolute values) by group, float64."""
        q = self.queries[name]
        groups = tpch_stream.num_groups(q, self.config, self.scale)
        sums, abs_sums = np.zeros(groups), np.zeros(groups)
        for t in ticks:
            records = self.pool[q["stream"]][t]
            keys = tpch_stream.keys_of(q, records)
            vals = tpch_stream.values_of(q, records, np.float64)
            sums += np.bincount(keys, weights=vals, minlength=groups)
            abs_sums += np.bincount(keys, weights=np.abs(vals), minlength=groups)
        return sums, abs_sums


def compare(ref: Reference, windows) -> Dict[str, float]:
    """The compared numbers over ``windows`` (``drive.Window``s)."""
    missing, count_err, sum_err = 0, 0.0, 0.0
    for w in windows:
        if w.result is None or w.emitted is None:
            missing += 1
            continue
        want, abs_sum = ref.window(w.query, w.ticks)
        got = np.asarray(w.result, np.float64)
        if got.shape != (len(want), 1):
            missing += 1
            continue
        gap = np.abs(got[:, 0] - want)
        if tpch_stream.is_count(ref.queries[w.query]):
            count_err = max(count_err, float(gap.max()))
        else:
            with np.errstate(divide="ignore", invalid="ignore"):
                units = np.where(gap == 0, 0.0, gap / (F32_UNIT * abs_sum))
            sum_err = max(sum_err, float(units.max()))
    return {"missing_windows": missing, "count_abs_err": count_err,
            "sum_err_f32_units": sum_err}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in numbers)
