"""Seeded TPC-H ticks and the GROUP-BY queries over them, read from a
configuration file.

A tick is one orders file and one lineitem file, as in the paper's stream
(arXiv 2306.06678, sec. 7.1).  The column distributions are those of the
program's own generator, copied into the configuration files so that the
yardstick cannot move with the program.  Ticks are made once per run, in
bulk, as a pool; windows draw their ticks from it in a seeded order.

A query is data: a key (a constant, a column, or a column modulo m) and a
value (a count, or a product of columns under an optional filter).  The same
description feeds the program (float32 values, as the program's queries
compute them) and the plain reference (float64).
"""
from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np

#: Rehearsal scale floor, as the program's ``StreamScale`` keeps one.
MIN_ROWS, MIN_DOMAIN = 16, 16


def scaled_rows(stream: dict, scale: float) -> int:
    n = stream["rows_per_tick"]
    return n if scale == 1.0 else max(int(n * scale), MIN_ROWS)


def column_domain(stream: dict, column: str, scale: float) -> int:
    """Upper end (exclusive) of an integer column's key domain."""
    low, high = stream["columns"][column]["int"]
    if scale != 1.0 and stream["columns"][column].get("scales"):
        high = max(int(high * scale), MIN_DOMAIN)
    return high


def _draw(rng: np.random.Generator, spec: dict, n: int, high: int):
    if "int" in spec:
        return rng.integers(spec["int"][0], high, n).astype(spec["dtype"])
    if "gamma" in spec:
        shape, scale = spec["gamma"]
        return rng.gamma(shape, scale, n).astype(spec["dtype"])
    raise ValueError(f"unknown column distribution: {spec}")


def make_pool(config: dict, seed: int, scale: float = 1.0
              ) -> Dict[str, List[Dict[str, np.ndarray]]]:
    """{stream: [tick files]} for every stream a query of ``config`` reads.
    Each file holds ``rows_per_tick`` rows of every column of the stream's
    schema, plus ``ts``: arrival instants inside the tick, sorted."""
    ticks = config["pool_ticks"]
    rng = np.random.default_rng(seed)
    pool = {}
    for name in sorted({q["stream"] for q in config["queries"]}):
        stream = config["streams"][name]
        n = scaled_rows(stream, scale)
        cols = {}
        for col, spec in stream["columns"].items():
            high = (column_domain(stream, col, scale) if "int" in spec
                    else None)
            cols[col] = _draw(rng, spec, ticks * n, high)
        ts = np.sort(rng.uniform(0.0, 1.0, (ticks, n)), axis=1)
        cols["ts"] = (ts + np.arange(ticks)[:, None]).ravel()
        pool[name] = [{c: v[i * n:(i + 1) * n] for c, v in cols.items()}
                      for i in range(ticks)]
    return pool


def tick_order(seed: int, pool_ticks: int) -> Iterator[int]:
    """Endless seeded sequence of pool tick indices: one permutation of the
    pool after another, so every tick is used equally often."""
    rng = np.random.default_rng([seed, 1])
    while True:
        yield from rng.permutation(pool_ticks).tolist()


def num_groups(query: dict, config: dict, scale: float) -> int:
    key = query["key"]
    if "mod" in key:
        return key["mod"]
    if "column" in key:
        stream = config["streams"][query["stream"]]
        return column_domain(stream, key["column"], scale)
    return 1


def keys_of(query: dict, records: Dict[str, np.ndarray]) -> np.ndarray:
    key = query["key"]
    if "column" not in key:
        return np.zeros(len(records["ts"]), np.int64)
    k = records[key["column"]].astype(np.int64)
    return k % key["mod"] if "mod" in key else k


def values_of(query: dict, records: Dict[str, np.ndarray], dtype) -> np.ndarray:
    """(N,) values in ``dtype``: ones for a count, else the product of the
    columns (each cast to ``dtype`` first), zeroed where the filter fails."""
    value = query["value"]
    n = len(records["ts"])
    if value.get("count"):
        return np.ones(n, dtype)
    out = np.ones(n, dtype)
    for col in value["product"]:
        out = out * records[col].astype(dtype)
    where = value.get("where")
    if where is not None:
        out = out * (records[where["column"]] < where["below"]).astype(dtype)
    return out


def is_count(query: dict) -> bool:
    return bool(query["value"].get("count"))


def program_query(query: dict, config: dict, scale: float):
    """The query as the program takes it: a ``repro`` ``AnalyticsQuery``."""
    from repro.data.tpch import AnalyticsQuery

    groups = num_groups(query, config, scale)
    return AnalyticsQuery(
        query["name"], query["stream"], lambda sc: groups,
        key_fn=lambda b: keys_of(query, b),
        value_fn=lambda b: values_of(query, b, np.float32)[:, None],
        description=query.get("sql", ""),
    )
