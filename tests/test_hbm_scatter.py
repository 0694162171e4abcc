"""The segagg dispatch rule and the HBM-resident scatter-add formulation
(``"hbm_scatter"``) that serves group domains too wide for the Pallas
scatter's VMEM accumulator.

Pinned properties:

* the rule, by shape alone, on ``"pallas"`` and ``"interpret"``: narrow G
  runs the one-hot matmul, a G whose 128-lane accumulator fits
  ``SCATTER_VMEM_BYTES`` the Pallas scatter, a wider G ``"hbm_scatter"``;
  ``"xla"`` keeps its own crossover;
* ``"hbm_scatter"`` equals the float64 numpy reference (duplicate keys,
  empty groups, rows off the block multiple, V = 1 and 2), through
  ``segagg``, ``pane_segagg`` with a composite G over the budget, and
  ``DeviceMesh(4).segagg`` on four virtual CPU devices (a child process, so
  the device count is set before jax starts);
* the padding is jitted; the Pallas kernel's program is keyed on the padded
  shape, shared by the row counts of one row block.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.segagg import ops, tuning
from repro.kernels.segagg.ops import pane_segagg, segagg
from repro.kernels.segagg.ref import segagg_numpy
from repro.kernels.segagg.segagg import SCATTER_VMEM_BYTES

# Widest G whose Pallas scatter accumulator (G x 128 lanes x f32) fits VMEM.
VMEM_MAX_G = SCATTER_VMEM_BYTES // (128 * 4)
WIDE_G = VMEM_MAX_G + 3_619   # past the budget, not a block multiple


@pytest.mark.parametrize("backend", ["pallas", "interpret"])
@pytest.mark.parametrize("groups,v,expect", [
    (1, 1, "matmul"),                      # CQ1, TPC-Q6-like
    (5, 1, "matmul"),                      # CQ2, TPC-Q4-like
    (4_096, 1, "scatter"),
    (VMEM_MAX_G, 1, "scatter"),
    (VMEM_MAX_G + 1, 1, "hbm_scatter"),
    (VMEM_MAX_G // 2 + 1, 129, "hbm_scatter"),   # two lane tiles wide
    (360_000, 1, "hbm_scatter"),           # CQ3, TPC-Q15-like
    (1_500_000, 1, "hbm_scatter"),         # CQ4
])
def test_dispatch_rule_by_shape(backend, groups, v, expect):
    if expect == "matmul":
        assert groups <= tuning.matmul_max_g(backend)
    else:
        assert groups > tuning.matmul_max_g(backend)
    assert tuning.pick_formulation(backend, 156_000, groups, v) == expect


@pytest.mark.parametrize("groups,programs", [
    (5, ["_pad_rows", "_segagg_pallas_sliced"]),        # matmul
    (4_096, ["_pad_rows", "_segagg_pallas_sliced"]),    # Pallas scatter
    (WIDE_G, ["_segagg_xla_scatter"]),                  # hbm_scatter
])
def test_padding_is_jitted(groups, programs):
    """A call enqueues jitted programs, not a chain of eager padding ops."""
    keys = jnp.zeros((1_000,), jnp.int32)
    values = jnp.ones((1_000, 1), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda k, v: segagg(k, v, groups, backend="interpret"))(keys, values)
    assert [(e.primitive.name, e.params.get("name")) for e in jaxpr.eqns] == [
        ("jit", name) for name in programs]


def test_pallas_kernel_program_shared_within_a_row_block():
    """Row counts in one row block pad to the same shape and share the
    kernel's compiled program; only the padding compiles per row count."""
    groups = 7
    block_n, _ = tuning.tuned_blocks("interpret", 3_000, groups)
    rows = [2 * block_n + 1, 3 * block_n - 1]
    assert {-(-n // block_n) for n in rows} == {3}
    before = ops._segagg_pallas_sliced._cache_size()
    for n in rows:
        keys = jnp.asarray(np.arange(n) % groups, jnp.int32)
        got = segagg(keys, jnp.ones((n, 1), jnp.float32), groups,
                     backend="interpret")
        np.testing.assert_array_equal(
            np.asarray(got)[:, 0], np.bincount(np.arange(n) % groups))
    assert ops._segagg_pallas_sliced._cache_size() == before + 1


@pytest.mark.parametrize("groups", [4_096, 360_000, 1_500_000])
def test_xla_keeps_its_own_crossover(groups):
    assert tuning.pick_formulation("xla", 156_000, groups, 1) == "scatter"


def _rows(seed, n, groups, v):
    """Keys over a few hundred distinct groups (duplicates, most groups
    empty), the first and last group among them."""
    rng = np.random.default_rng(seed)
    used = np.concatenate([[0, groups - 1],
                           rng.choice(groups, 300, replace=False)])
    keys = rng.choice(used, n).astype(np.int32)
    vals = rng.gamma(2.0, 30.0, (n, v)).astype(np.float32)
    return keys, vals


def _assert_matches_numpy(got, keys, vals, groups):
    want = segagg_numpy(keys, vals, groups)
    assert got.shape == want.shape
    assert np.all(got[want == 0] == 0)          # empty groups stay empty
    # A group's sum of c values takes at most c + 1 f32 roundings.
    counts = segagg_numpy(keys, np.ones((len(keys), 1)), groups)
    bound = (counts + 1) * 2.0 ** -24 * segagg_numpy(keys, np.abs(vals),
                                                     groups)
    assert np.all(np.abs(got - want) <= bound)


@pytest.mark.parametrize("v", [1, 2])
@pytest.mark.parametrize("n", [1, 3_001, 4_096])
@pytest.mark.parametrize("backend,formulation", [
    ("interpret", None),                   # dispatched by the rule
    ("xla", "hbm_scatter"),                # explicit override
])
def test_hbm_scatter_matches_numpy(backend, formulation, n, v):
    keys, vals = _rows(n + v, n, WIDE_G, v)
    got = segagg(jnp.asarray(keys), jnp.asarray(vals), WIDE_G,
                 backend=backend, formulation=formulation)
    _assert_matches_numpy(np.asarray(got, np.float64), keys, vals, WIDE_G)
    ones = np.ones((n, 1), np.float32)
    counts = segagg(jnp.asarray(keys), jnp.asarray(ones), WIDE_G,
                    backend=backend, formulation=formulation)
    np.testing.assert_array_equal(np.asarray(counts),
                                  segagg_numpy(keys, ones, WIDE_G))


@pytest.mark.parametrize("v", [1, 2])
def test_pane_segagg_composite_over_budget(v):
    panes, groups, n = 7, 3_001, 2_500
    assert tuning.pick_formulation("interpret", n, panes * groups, v) == "hbm_scatter"
    keys, vals = _rows(v, n, groups, v)
    pane_ids = np.sort(np.random.default_rng(v).integers(0, panes, n))
    got = pane_segagg(jnp.asarray(keys), jnp.asarray(vals),
                      jnp.asarray(pane_ids.astype(np.int32)), panes, groups,
                      backend="interpret")
    assert got.shape == (panes, groups, v)
    composite = pane_ids * groups + keys
    _assert_matches_numpy(np.asarray(got, np.float64).reshape(-1, v),
                          composite, vals, panes * groups)


MESH_CHILD = textwrap.dedent("""
    import json
    import numpy as np
    from repro import tracing
    from repro.dist import DeviceMesh
    from repro.kernels.segagg.ref import segagg_numpy

    groups, out = {groups}, {{}}
    mesh = DeviceMesh(4)
    rng = np.random.default_rng(7)
    for v in (1, 2):
        for n in (1, 3001, 4096):
            used = np.concatenate([[0, groups - 1],
                                   rng.choice(groups, 300, replace=False)])
            keys = rng.choice(used, n).astype(np.int32)
            # Integer-valued: sums are exact whatever the order of adds.
            vals = rng.integers(0, 8, (n, v)).astype(np.float32)
            tracing.enable()
            got = np.asarray(mesh.segagg(keys, vals, groups,
                                         backend="interpret"))
            tracing.disable()
            out[f"{{n}}x{{v}}"] = bool(
                np.array_equal(got, segagg_numpy(keys, vals, groups)))
    panes, pg = 7, 3001
    keys = rng.integers(0, pg, 2500).astype(np.int32)
    pane_ids = np.sort(rng.integers(0, panes, 2500)).astype(np.int32)
    vals = rng.integers(0, 8, (2500, 2)).astype(np.float32)
    got = np.asarray(mesh.pane_segagg(keys, vals, pane_ids, panes, pg,
                                      backend="interpret"))
    want = segagg_numpy(pane_ids * pg + keys, vals, panes * pg)
    out["panes"] = bool(np.array_equal(got.reshape(-1, 2), want))
    out["counts"] = tracing.drain_counts()
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh_results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.abspath(src), env.get("PYTHONPATH")) if p)
    out = subprocess.run(
        [sys.executable, "-c", MESH_CHILD.format(groups=WIDE_G)], env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["1x1", "3001x1", "4096x1", "1x2",
                                  "3001x2", "4096x2", "panes"])
def test_mesh_hbm_scatter_matches_numpy(mesh_results, case):
    assert mesh_results[case]


def test_mesh_counts_one_dispatch_per_call(mesh_results):
    # Six traced segagg calls, each one program on four devices whose
    # per-device rows run the HBM scatter.
    assert mesh_results["counts"] == {"segagg.hbm_scatter": 6}
