"""Run one benchmark run with the program's timed path broken underneath.

    python3 bench/tests/planted.py <fault> -- <bench/run.py arguments>

``none`` plants nothing.  Each fault is planted in the program's GROUP-BY
before the run starts; the session, scheduler and executor above it stay as
they are:

* ``unchanged``: a batch leaves its partial unchanged (all zeros);
* ``half``: half of the batch's rows are left out and the rest counted
  twice, a scaled estimate of the whole;
* ``altered``: one group's answer is altered where it is produced;
* ``no_exchange``: the merge across chips keeps the first chip's partial
  only (mesh cells);
* ``control``: the plain reference in the kernel's place, in the nearest
  precision below the configuration's float32: values rounded to bfloat16,
  summed in float32.
"""
from __future__ import annotations

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp

    import repro.dist.mesh as mesh
    import repro.serve.analytics as analytics

    program = analytics.segagg

    def unchanged(keys, values, num_groups, *a, **kw):
        return jnp.zeros_like(program(keys, values, num_groups, *a, **kw))

    def half(keys, values, num_groups, *a, **kw):
        n = keys.shape[0] // 2
        return 2.0 * program(keys[:n], values[:n], num_groups, *a, **kw)

    def altered(keys, values, num_groups, *a, **kw):
        return program(keys, values, num_groups, *a, **kw).at[0, 0].add(1.0)

    def control(keys, values, num_groups, *a, **kw):
        v = jnp.asarray(values).astype(jnp.bfloat16).astype(jnp.float32)
        if v.ndim == 1:
            v = v[:, None]
        return jax.ops.segment_sum(v, jnp.asarray(keys), num_segments=num_groups)

    if fault == "none":
        return
    if fault == "no_exchange":
        mesh.merge_panes = lambda parts: parts[0]
        return
    fn = {"unchanged": unchanged, "half": half, "altered": altered,
          "control": control}[fault]
    analytics.segagg = fn
    mesh.segagg = fn


def main(argv) -> int:
    fault, sep, rest = argv[0], argv[1], argv[2:]
    if sep != "--":
        raise SystemExit(__doc__)
    plant(fault)
    import run
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
