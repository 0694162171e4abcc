"""Tuned launch parameters for the segagg kernels.

``benchmarks/hillclimb.py --segagg`` measures candidate (block_n, block_g)
pairs and the matmul-vs-scatter crossover per (backend, shape-class) and
persists the winners to ``tuned_blocks.json`` next to this module; the
dispatch layer (``ops.segagg``) reads them at call time.  Shape classes
bucket call shapes coarsely — rows below/above ``_N_SMALL`` x groups
below/above ``_G_NARROW`` — so one tuned entry covers a regime, not an
exact shape (an exact-shape table would never hit on real workloads).

Missing file / missing entry falls back to the compiled-in defaults
(``segagg.BLOCK_N`` / ``segagg.BLOCK_G``, crossover ``DEFAULT_MATMUL_MAX_G``),
so the package works untuned.
"""
from __future__ import annotations

import functools
import json
import pathlib
from typing import Dict, Optional, Tuple

from .segagg import BLOCK_G, BLOCK_N, SCATTER_VMEM_BYTES

TUNED_PATH = pathlib.Path(__file__).resolve().parent / "tuned_blocks.json"

# Shape-class boundaries (rows / groups).
_N_SMALL = 32_768
_G_NARROW = 1_024

# Below this group count the one-hot matmul's O(N·G) FLOPs are cheaper than
# the scatter pass's serial row loop; above it scatter-add wins.  Overridden
# per backend by the tuned table ("crossover" section).
DEFAULT_MATMUL_MAX_G = 256


def shape_class(n: int, g: int) -> str:
    """Coarse (rows x groups) regime bucket: small/large x narrow/wide."""
    rows = "small" if n <= _N_SMALL else "large"
    width = "narrow" if g <= _G_NARROW else "wide"
    return f"{rows}-{width}"


@functools.lru_cache(maxsize=1)
def _load() -> Dict:
    try:
        return json.loads(TUNED_PATH.read_text())
    except (OSError, ValueError):
        return {}


def reload() -> None:
    """Drop the cached table (after hillclimb rewrites the file)."""
    _load.cache_clear()


def tuned_blocks(backend: str, n: int, g: int) -> Tuple[int, int]:
    """(block_n, block_g) for a call shape, tuned entry or defaults."""
    entry = _load().get("blocks", {}).get(f"{backend}:{shape_class(n, g)}")
    if entry:
        return int(entry["block_n"]), int(entry["block_g"])
    return BLOCK_N, BLOCK_G


def matmul_max_g(backend: str) -> int:
    """Largest group count at which the one-hot matmul formulation is still
    selected (the measured matmul/scatter crossover for ``backend``)."""
    entry = _load().get("crossover", {}).get(backend)
    if entry:
        return int(entry["matmul_max_g"])
    return DEFAULT_MATMUL_MAX_G


FORMULATIONS = ("matmul", "scatter", "hbm_scatter")


def pick_formulation(backend: str, n: int, g: int, v: int,
                     override: Optional[str] = None) -> str:
    """The formulation ``ops.segagg`` runs for ``n`` rows of width ``v``
    into ``g`` groups on a resolved ``backend``, from the shape alone.  The
    Pallas kernels see the width padded to 128 lanes: that is what their
    scatter accumulator keeps resident.

    * ``g <= matmul_max_g(backend)``: the one-hot matmul.
    * Otherwise, on ``"pallas"``/``"interpret"``: the Pallas scatter-add
      while its resident (G, V) accumulator fits ``SCATTER_VMEM_BYTES``,
      else ``"hbm_scatter"``, XLA's scatter-add into an accumulator in
      HBM.  A wide G padded to 128 lanes outgrows VMEM long before the
      one-hot matmul's O(N·G·V) MXU work stops mattering, so the matmul is
      never the fallback.
    * Otherwise, on ``"xla"``: the XLA scatter-add (its accumulator is in
      HBM already).
    """
    if override is not None:
        if override not in FORMULATIONS:
            raise ValueError(f"unknown segagg formulation: {override!r} "
                             f"(expected one of {FORMULATIONS})")
        return override
    if g <= matmul_max_g(backend):
        return "matmul"
    lanes = -(-v // 128) * 128
    if backend in ("pallas", "interpret") and g * lanes * 4 > SCATTER_VMEM_BYTES:
        return "hbm_scatter"
    return "scatter"


def save(table: Dict) -> pathlib.Path:
    """Persist a tuned table (hillclimb writes through this) and reload."""
    TUNED_PATH.write_text(json.dumps(table, indent=2, sort_keys=True) + "\n")
    reload()
    return TUNED_PATH
