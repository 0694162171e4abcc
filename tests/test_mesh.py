"""Device-mesh execution layer (repro.dist.mesh + the WorkerBackend seam).

Pinned properties:

* extents <-> spec consistency — ``batch_shard_extents`` over the device
  count produces exactly the per-device row splits ``batch_spec`` encodes
  when divisible, and the replicated fallback fires a ``sharding_fallback``
  event when it does not;
* shard_map parity — ``DeviceMesh.segagg``/``pane_segagg`` are exactly
  equal (integer-valued f32) to the single-device references on 1-, 2- and
  8-device meshes (multi-device cases skip unless the host exposes the
  devices; CI forces 8 via XLA_FLAGS);
* the pool's dispatch seam — ``ExecutorPool(worker_backend=...)`` delegates
  to any ``WorkerBackend`` while the legacy modelled path stays identical;
* weighted sharding + per-worker calibration — largest-remainder extents,
  ``CalibratingCostModel.worker_scale``/``worker_weights``, and
  ``MeshBackend``'s measured-heterogeneity gate.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import (
    ExecutorPool,
    LinearCostModel,
    Query,
    ShardedCostModel,
    SimulatedExecutor,
    TraceArrival,
    get_policy,
    run,
)
from repro.core.cost_model import CalibratingCostModel
from repro.core.runtime import Dispatch, ModelledWorkerBackend, WorkerBackend
from repro.data.tpch import PAPER_QUERIES, StreamScale, stream_files
from repro.dist import (
    DeviceMesh,
    MeshBackend,
    on_fallback,
    weighted_shard_extents,
)
from repro.dist.sharding import batch_shard_extents, batch_spec
from repro.kernels.segagg.ref import pane_segagg_ref, segagg_numpy, segagg_ref
from repro.serve.analytics import MeshAnalyticsBackend, concat_files

NDEV = jax.device_count()


def needs_devices(k: int):
    return pytest.mark.skipif(
        NDEV < k,
        reason=f"needs {k} jax devices (have {NDEV}); set "
               f"XLA_FLAGS=--xla_force_host_platform_device_count={k}",
    )


def int_valued(rng, n, v=3):
    """Integer-valued f32 rows: sums are exact regardless of association,
    so mesh-vs-reference parity can assert EXACT equality."""
    return rng.integers(0, 8, size=(n, v)).astype(np.float32)


# ---------------------------------------------------------------------------
# extents <-> batch_spec consistency
# ---------------------------------------------------------------------------


class TestExtents:
    @pytest.mark.parametrize("n,ways,expect", [
        (8, 2, ((0, 4), (4, 4))),
        (7, 2, ((0, 4), (4, 3))),
        (3, 8, ((0, 1), (1, 1), (2, 1))),   # empty shards dropped
        (0, 4, ()),
    ])
    def test_batch_shard_extents(self, n, ways, expect):
        assert batch_shard_extents(n, ways) == expect

    @pytest.mark.parametrize("n", [0, 1, 7, 8, 64, 100])
    @pytest.mark.parametrize("ways", [1, 2, 3, 8])
    def test_equal_weights_reduce_to_unweighted(self, n, ways):
        weighted = tuple(e for e in weighted_shard_extents(n, [1.0] * ways)
                         if e[1] > 0)
        assert weighted == batch_shard_extents(n, ways)

    def test_weighted_proportions_and_alignment(self):
        # ideal 7.5 / 2.5 -> floors 7/2, leftover to the tied-earliest.
        assert weighted_shard_extents(10, [3.0, 1.0]) == ((0, 8), (8, 2))
        # zero-weight workers keep their (empty) slot for 1:1 zipping.
        ext = weighted_shard_extents(6, [1.0, 0.0, 2.0])
        assert ext == ((0, 2), (2, 0), (2, 4))
        assert sum(s for _, s in ext) == 6

    def test_weighted_validation(self):
        with pytest.raises(ValueError):
            weighted_shard_extents(-1, [1.0])
        with pytest.raises(ValueError):
            weighted_shard_extents(4, [])
        with pytest.raises(ValueError):
            weighted_shard_extents(4, [0.0, 0.0])
        with pytest.raises(ValueError):
            weighted_shard_extents(4, [1.0, -1.0])


class TestExtentsSpecConsistency:
    """The pool's 1-D splits and the mesh's NamedShardings agree."""

    @pytest.mark.parametrize("devices", [1, 2, 8])
    def test_divisible_rows_match_spec_shards(self, devices):
        if NDEV < devices:
            pytest.skip(f"needs {devices} devices")
        mesh = DeviceMesh(devices)
        n = devices * 6
        extents = mesh.shard_extents(n)
        assert len(extents) == devices
        assert all(size == n // devices for _, size in extents)
        # batch_spec shards dim 0 over the data axis for the same rows.
        spec = batch_spec(mesh.mesh, n, 2)
        assert spec[0] == "data" and spec[1] is None
        sharding = mesh.batch_sharding(n, 2)
        assert mesh.events == []  # no fallback on the divisible path
        # Per-device row ranges of the NamedSharding == the pool extents.
        if devices > 1:
            idx = sharding.addressable_devices_indices_map((n, 3))
            rows = sorted(
                (sl[0].start or 0, (sl[0].stop or n) - (sl[0].start or 0))
                for sl in idx.values()
            )
            assert tuple(rows) == extents

    @needs_devices(2)
    def test_non_divisible_rows_fall_back_with_event(self):
        seen = []
        mesh = DeviceMesh(2, on_event=seen.append)
        sharding = mesh.batch_sharding(7, 2)
        # Replicated: nothing sharded, and the fallback was reported.
        assert sharding.spec == jax.sharding.PartitionSpec(None, None)
        assert [e["kind"] for e in mesh.events] == ["sharding_fallback"]
        assert seen == mesh.events
        # ...while the pool extents still cover all 7 tuples unevenly.
        assert mesh.shard_extents(7) == ((0, 4), (4, 3))

    def test_on_fallback_unsubscribe(self):
        events = []
        unsub = on_fallback(events.append)
        unsub()
        unsub()  # idempotent
        mesh = DeviceMesh(1)
        mesh.batch_sharding(7, 1)
        assert events == []


# ---------------------------------------------------------------------------
# shard_map parity
# ---------------------------------------------------------------------------


class TestDeviceMeshParity:
    @pytest.mark.parametrize("devices", [1, 2, 8])
    @pytest.mark.parametrize("n", [64, 100])  # 100: padding path on 8 dev
    def test_segagg_matches_reference(self, devices, n):
        if NDEV < devices:
            pytest.skip(f"needs {devices} devices")
        rng = np.random.default_rng(devices * 1000 + n)
        G = 16
        keys = rng.integers(0, G, size=n).astype(np.int32)
        vals = int_valued(rng, n)
        ref = np.asarray(segagg_ref(keys, vals, G))
        got = np.asarray(DeviceMesh(devices).segagg(keys, vals.copy(), G))
        assert np.array_equal(got, ref)

    @pytest.mark.parametrize("devices", [1, 2, 8])
    def test_pane_segagg_matches_reference(self, devices):
        if NDEV < devices:
            pytest.skip(f"needs {devices} devices")
        rng = np.random.default_rng(7)
        n, P, G = 90, 5, 8
        keys = rng.integers(0, G, size=n).astype(np.int32)
        panes = rng.integers(0, P, size=n).astype(np.int32)
        vals = int_valued(rng, n, v=2)
        ref = np.asarray(pane_segagg_ref(keys, vals, panes, P, G))
        got = np.asarray(
            DeviceMesh(devices).pane_segagg(keys, vals.copy(), panes, P, G)
        )
        assert np.array_equal(got, ref)

    def test_1d_values_and_empty_batch(self):
        mesh = DeviceMesh(1)
        out = np.asarray(mesh.segagg(
            np.array([0, 1, 1], np.int32), np.array([1.0, 2.0, 3.0]), 4))
        assert out.shape == (4, 1)
        assert np.array_equal(out[:, 0], [1.0, 5.0, 0.0, 0.0])

    def test_device_count_validation(self):
        with pytest.raises(ValueError):
            DeviceMesh(0)
        with pytest.raises(ValueError, match="xla_force_host_platform"):
            DeviceMesh(NDEV + 1)
        with pytest.raises(ValueError):
            DeviceMesh([])


# ---------------------------------------------------------------------------
# the WorkerBackend seam
# ---------------------------------------------------------------------------


class StubBackend(WorkerBackend):
    """Deterministic WorkerBackend: every batch takes ``dur`` modelled
    seconds, aggregation is free; records every physical call."""

    def __init__(self, names, dur=2.0):
        super().__init__(names)
        self.dur = dur
        self.calls = []

    def run_batch(self, query, num_tuples, offset, worker):
        self.calls.append(("batch", worker, num_tuples, offset))
        start = self._clocks[worker]
        end = start + self.dur
        self._clocks[worker] = end
        return Dispatch(worker=worker, start=start, end=end), self.dur

    def run_agg(self, query, num_batches, worker, start, barrier):
        self.calls.append(("agg", worker, num_batches))
        return Dispatch(worker=worker, start=barrier, end=barrier), 0.0


def fixed_query(qid="q0", n=8, slack=3.0):
    arr = TraceArrival(timestamps=tuple(float(i) for i in range(n)))
    cm = LinearCostModel(tuple_cost=0.4, overhead=0.3, agg_per_batch=0.2)
    return Query(qid, arr.wind_start, arr.wind_end,
                 arr.wind_end + slack * cm.cost(n), n, cm, arr)


class TestPoolSeam:
    def test_worker_backend_exclusive_with_legacy_args(self):
        wb = StubBackend(("a", "b"))
        with pytest.raises(TypeError, match="not both"):
            ExecutorPool(backend=SimulatedExecutor(), worker_backend=wb)
        with pytest.raises(ValueError, match="declares its own workers"):
            ExecutorPool(workers=2, worker_backend=wb)
        with pytest.raises(ValueError, match="declares its own workers"):
            ExecutorPool(names=("x",), worker_backend=wb)

    def test_legacy_pool_uses_modelled_backend(self):
        pool = ExecutorPool(workers=2)
        assert isinstance(pool.worker_backend, ModelledWorkerBackend)
        assert pool.prefers_group_dispatch is False
        assert pool.worker_weights == (1.0, 1.0)

    def test_stub_backend_drives_the_loop(self):
        wb = StubBackend(("a", "b"))
        pool = ExecutorPool(worker_backend=wb)
        assert pool.worker_names == ("a", "b")
        trace = run(get_policy("llf-dynamic"), [fixed_query()], pool)
        assert trace.outcome("q0").complete
        kinds = {c[0] for c in wb.calls}
        assert kinds == {"batch", "agg"}
        # every modelled batch costs exactly the stub duration
        batches = [e for e in trace.executions if e.kind == "batch"]
        assert all(abs((e.end - e.start) - wb.dur) < 1e-12 for e in batches)

    def test_default_shard_group_is_sequential_batches(self):
        wb = StubBackend(("a", "b", "c"))
        dispatches = wb.run_shard_group(
            fixed_query(), (3, 3, 2), 0, ("a", "b", "c"))
        assert [d.worker for d in dispatches] == ["a", "b", "c"]
        assert [c[0] for c in wb.calls] == ["batch"] * 3
        offsets = [c[3] for c in wb.calls]
        assert offsets == [0, 3, 6]

    def test_requeue_is_noop_by_default(self):
        wb = StubBackend(("a",))
        wb.requeue_batch(fixed_query(), 4, 0)  # must not raise
        assert wb.calls == []


class TestShardedCostModel:
    def test_planning_cost_divides_rounding_up(self):
        base = LinearCostModel(tuple_cost=1.0, overhead=1.0)
        cm = ShardedCostModel(base, 4)
        assert cm.cost(8) == base.cost(2)
        assert cm.cost(9) == base.cost(3)     # ceil division
        assert cm.cost(0) == base.cost(0)
        assert cm.shard_cost(8) == base.cost(8)  # modelled clock charge
        assert cm.agg_cost(3) == base.agg_cost(3)

    def test_ways_one_is_identity(self):
        base = LinearCostModel(tuple_cost=0.5, overhead=0.1)
        cm = ShardedCostModel(base, 1)
        for n in (0, 1, 7, 64):
            assert cm.cost(n) == base.cost(n)

    def test_validation(self):
        with pytest.raises(ValueError):
            ShardedCostModel(LinearCostModel(tuple_cost=1.0), 0)


# ---------------------------------------------------------------------------
# per-worker calibration -> weighted shards
# ---------------------------------------------------------------------------


class TestWorkerCalibration:
    def _calibrated(self):
        cal = CalibratingCostModel(LinearCostModel(tuple_cost=1.0))
        for _ in range(6):
            cal.observe(10, 10.0, worker="fast")
            cal.observe(10, 20.0, worker="slow")  # consistently 2x the cost
        return cal

    def test_worker_scale_and_cost(self):
        cal = self._calibrated()
        # The pooled fit absorbs the level; the 2x speed skew survives in
        # the RATIO of the per-worker scales.
        assert cal.worker_scale("slow") == pytest.approx(
            2 * cal.worker_scale("fast"), rel=1e-6)
        assert cal.worker_cost(10, "slow") > cal.worker_cost(10, "fast")
        assert cal.worker_scale("unseen") == 1.0

    def test_worker_weights_inverse_normalized(self):
        cal = self._calibrated()
        w = cal.worker_weights(("fast", "slow"))
        assert sum(w) == pytest.approx(len(w))
        assert w[0] == pytest.approx(2 * w[1], rel=1e-6)

    def test_under_two_samples_stays_neutral(self):
        cal = CalibratingCostModel(LinearCostModel(tuple_cost=1.0))
        cal.observe(10, 30.0, worker="w")
        assert cal.worker_scale("w") == 1.0


class TestMeshBackendWeights:
    class _FakeMesh:
        """num_devices is all MeshBackend.__init__ reads off the mesh."""

        def __init__(self, n):
            self.num_devices = n

    def make(self, solo):
        wb = MeshBackend(self._FakeMesh(len(solo)), names=tuple(solo))
        for name, (tuples, secs) in solo.items():
            wb._solo_tuples[name] = tuples
            wb._solo_secs[name] = secs
        return wb

    def test_no_solo_data_is_neutral(self):
        wb = self.make({"a": (0.0, 0.0), "b": (0.0, 0.0)})
        assert wb.worker_weights == (1.0, 1.0)

    def test_below_threshold_noise_is_neutral(self):
        wb = self.make({"a": (100.0, 1.0), "b": (100.0, 1.1)})
        assert wb.worker_weights == (1.0, 1.0)

    def test_heterogeneous_weights_normalize_to_mean_one(self):
        wb = self.make({"a": (100.0, 1.0), "b": (100.0, 2.0)})
        w = wb.worker_weights
        assert sum(w) == pytest.approx(len(w))
        assert w[0] == pytest.approx(2 * w[1], rel=1e-6)

    def test_name_count_must_match_devices(self):
        with pytest.raises(ValueError, match="names"):
            MeshBackend(DeviceMesh(1), names=("a", "b"))


# ---------------------------------------------------------------------------
# MeshBackend end-to-end: real segagg work under the scheduler
# ---------------------------------------------------------------------------


class TestMeshBackendEndToEnd:
    SCALE = StreamScale(scale=0.005)

    def _run(self, devices):
        aq = PAPER_QUERIES[1]  # CQ2: 5 groups
        files = [(line if aq.stream == "lineitem" else o)
                 for _, o, line in
                 stream_files(seed=5, num_files=16, sc=self.SCALE)]
        mesh = DeviceMesh(devices)
        wb = MeshAnalyticsBackend({"q0": (aq, files)}, self.SCALE, mesh)
        pool = ExecutorPool(worker_backend=wb)
        base = LinearCostModel(tuple_cost=1.0, overhead=1.0)
        cm = ShardedCostModel(base, devices) if devices > 1 else base
        query = dataclasses.replace(
            fixed_query("q0", n=16, slack=50.0), cost_model=cm)
        trace = run(get_policy("llf-dynamic", shard_across=devices),
                    [query], pool)
        assert trace.outcome("q0").complete
        return wb, trace

    def test_single_device_matches_oneshot(self):
        wb, _ = self._run(1)
        aq = PAPER_QUERIES[1]
        files = [(line if aq.stream == "lineitem" else o)
                 for _, o, line in
                 stream_files(seed=5, num_files=16, sc=self.SCALE)]
        records = concat_files(files)
        ref = segagg_numpy(aq.key_fn(records), aq.value_fn(records),
                           aq.num_groups(self.SCALE))
        assert np.array_equal(wb.results["q0"], ref)

    @needs_devices(2)
    def test_sharded_run_is_exact_and_fused(self):
        wb1, _ = self._run(1)
        wbN, trace = self._run(min(NDEV, 8))
        assert np.array_equal(wbN.results["q0"], wb1.results["q0"])
        # Group dispatch: sharded batches share one fused start/end per
        # group, and every mesh worker participates.
        batches = [e for e in trace.executions if e.kind == "batch"]
        starts = {e.start for e in batches}
        assert len(starts) < len(batches)
        assert {e.worker for e in batches} == set(wbN.worker_names)

    def test_wall_clock_bookkeeping(self):
        wb, _ = self._run(1)
        assert wb.wall_seconds["q0"] > 0.0
