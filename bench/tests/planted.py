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
* ``first_round``: as ``altered``, in the first measured round only; the
  rounds after it are sound, so only the check of that round, made before
  its answers are let go, can see it;
* ``control``: the plain reference in the kernel's place, in the nearest
  precision below the configuration's float32: values rounded to bfloat16,
  summed in float32.

Whatever the fault, the last line of standard error counts what the harness
held across its measured rounds: ``held`` JSON with the most answers held
at a round's start and at its end, the most windows of one round, and the
rounds.
"""
from __future__ import annotations

import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def held(cell) -> int:
    """Windows of ``cell`` whose answer the harness still holds."""
    return sum(w.result is not None for w in cell.windows)


class Rounds:
    """Wraps ``drive.Cell.run_round``: arms a fault for the first measured
    round and counts the answers held at each round's edges."""

    def __init__(self):
        import drive

        self.armed, self.rounds = False, 0
        self.held = {"at_start": 0, "at_end": 0, "round_windows": 0}
        run_round = drive.Cell.run_round

        def watched(cell, n, record=True):
            if not record:
                return run_round(cell, n, record)
            self._note("at_start", held(cell))
            self.armed = self.rounds == 0
            first = len(cell.windows)
            try:
                return run_round(cell, n, record)
            finally:
                self.armed = False
                self.rounds += 1
                self._note("at_end", held(cell))
                self._note("round_windows", len(cell.windows) - first)

        drive.Cell.run_round = watched

    def _note(self, key: str, value: int) -> None:
        self.held[key] = max(self.held[key], value)

    def report(self) -> str:
        return json.dumps({"held": self.held, "rounds": self.rounds})


def plant(fault: str, rounds: Rounds) -> None:
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

    def first_round(keys, values, num_groups, *a, **kw):
        out = program(keys, values, num_groups, *a, **kw)
        return out.at[0, 0].add(1.0) if rounds.armed else out

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
          "first_round": first_round, "control": control}[fault]
    analytics.segagg = fn
    mesh.segagg = fn


def main(argv) -> int:
    fault, sep, rest = argv[0], argv[1], argv[2:]
    if sep != "--":
        raise SystemExit(__doc__)
    import run   # puts src/ on the path
    rounds = Rounds()
    plant(fault, rounds)
    try:
        return run.main(rest)
    finally:
        print(rounds.report(), file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
