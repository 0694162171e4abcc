"""The comparison that decides ``correct``, shown to fail: each fault a cell
can have, and the bfloat16 control, planted under a CPU rehearsal run
(``planted.py``), must come out ``correct: false``; the sound run must come
out true.  Run by path: ``python -m pytest bench/tests``."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
FAULTS = ("unchanged", "half", "altered", "control")
CASES = [(cell, f) for cell in ("wide.backlog", "narrow.stream")
         for f in ("none",) + FAULTS]


def rehearse(cell: str, fault: str, seed: int = 2**31 + 17) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--workload", cell, "--seed", str(seed), "--seconds", "2",
            "--trace", "0", "--rehearse"]
    if cell.startswith("narrow"):
        args += ["--rate", "100"]  # the interpreter's pace, not the chip's
    out = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "planted.py"), fault, "--", *args],
        env=env, capture_output=True, text=True, timeout=900, cwd=BENCH.parent)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_fails_the_check(cell, fault):
    result = rehearse(cell, fault)
    assert result["attempted"] > 0
    assert result["correct"] is (fault == "none"), result["checks"]
