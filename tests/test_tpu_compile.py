"""Compiles of the segagg kernels for a described TPU v5e (2x2), at the
shapes the TPC-H session path dispatches.  Nothing runs: each test lowers
and compiles for the chip, so Mosaic refuses here what it would refuse
there (block layouts, memory spaces, VMEM use).  The topology is described
inside a fixture, never at import, so that only the worker that runs this
file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.data.tpch import LINEITEMS_PER_FILE, ORDERS_PER_FILE, StreamScale
from repro.dist import DeviceMesh
from repro.kernels.segagg import tuning
from repro.kernels.segagg.ops import segagg

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def on_tpu(monkeypatch):
    """Dispatch as it would on the chip: ``backend="pallas"`` checks the
    default backend, which here is the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    return compiled.as_text(), used


SCALE = StreamScale(1.0)


@pytest.mark.parametrize("rows,groups,formulation", [
    (16 * ORDERS_PER_FILE, 5, "matmul"),                          # CQ2 width
    (4 * LINEITEMS_PER_FILE, SCALE.num_suppkeys, "hbm_scatter"),  # CQ3 width
    (12 * LINEITEMS_PER_FILE, SCALE.num_partkeys, "hbm_scatter"),  # CQ4 width
    (60 * LINEITEMS_PER_FILE, 4096, "scatter"),                   # mid-width G
], ids=["cq2", "cq3", "cq4", "scatter-g4096"])
def test_segagg_compiles_for_v5e(topo, on_tpu, rows, groups, formulation):
    assert tuning.pick_formulation("pallas", rows, groups, 128) == formulation
    one_chip = SingleDeviceSharding(topo.devices[0])
    keys = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((rows, 1), jnp.float32, sharding=one_chip)
    text, used = _compile(
        lambda k, v: segagg(k, v, groups, backend="pallas"), keys, vals)
    # The HBM scatter is XLA's own scatter, not a Pallas kernel.
    assert ("tpu_custom_call" in text) == (formulation != "hbm_scatter")
    assert used < HBM_BYTES


def test_sharded_segagg_compiles_for_v5e_2x2(topo, on_tpu):
    mesh = DeviceMesh(list(topo.devices))
    assert mesh.num_devices == 4
    rows = 16 * ORDERS_PER_FILE
    keys = jax.ShapeDtypeStruct((rows,), jnp.int32,
                                sharding=NamedSharding(mesh.mesh, P("data")))
    vals = jax.ShapeDtypeStruct(
        (rows, 1), jnp.float32,
        sharding=NamedSharding(mesh.mesh, P("data", None)))
    text, used = _compile(mesh._sharded_segagg(5, "pallas"), keys, vals)
    assert "tpu_custom_call" in text
    assert "all-reduce" in text  # the cross-device merge of the partials
    assert used < HBM_BYTES
