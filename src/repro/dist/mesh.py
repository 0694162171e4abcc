"""DeviceMesh: the scheduling-side facade over real jax devices.

Everything the scheduler proved on modelled clocks — MinBatch sizing,
shard dispatch, the C_max blocking bound — is only half-validated until
the shards run on REAL devices.  This module is the bridge:

* ``DeviceMesh`` — a 1-D ``jax.sharding.Mesh`` over the scheduling data
  axis.  It maps ``batch_shard_extents`` (the pool's 1-D batch splits)
  onto per-device ``NamedSharding``s, and runs ``segagg``/``pane_segagg``
  as ONE fused ``shard_map`` call across the axis with a final
  cross-device ``merge_panes`` combine.  On CPU, set
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
  initializes to get an N-device host mesh (CI does exactly this).

* ``MeshBackend`` — a ``repro.core.runtime.WorkerBackend`` with one
  worker per mesh device whose clocks are stitched from MEASURED wall
  seconds instead of cost-model predictions.  It prefers GROUP dispatch:
  a ``PolicyDecision``'s whole shard group becomes one fused mesh call,
  so per-dispatch overhead is paid once per logical batch instead of once
  per shard — the paper's overhead-amortization argument applied to
  dispatch fan-out (see ``ShardedCostModel`` for the planning-side view).

Donation invariants: the sharded segagg jit donates its VALUES operand
(the large buffer) so XLA may overlap the host→device transfer of the
next batch with compute and reuse the donated pages for the output.
Callers must therefore treat the values array as CONSUMED — pass a fresh
(or numpy-backed) array per call, never reuse a jax array across calls.
Keys are small and not donated.  Padding rows (to make N divisible by the
device count) carry ``key == num_groups``: dropped by the scatter path,
an all-zero one-hot row in the matmul path, the sacrificial group in the
Pallas path — numerics are unaffected on every backend.
"""
from __future__ import annotations

import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import tracing
from ..core.runtime import Dispatch, WorkerBackend
from ..kernels.segagg import tuning
from ..kernels.segagg.ops import (
    merge_panes,
    pane_composite_groups,
    resolve_backend,
    segagg,
)
from .sharding import batch_shard_extents, batch_spec, on_fallback

# Donation is a best-effort hint: platforms without buffer aliasing (CPU)
# warn per compile that the donated operand was not usable.  The fallback
# (a copy) is correct, and the warning would fire on every cache miss of
# the sharded-segagg jit, so silence exactly that message.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)


class DeviceMesh:
    """A 1-D device mesh over the scheduling data axis.

    ``devices`` may be an int (the first k of ``jax.devices()``), an
    explicit device sequence, or None for every visible device.  The axis
    is named ``"data"`` so ``dist.sharding``'s data-parallel rules
    (``batch_spec``, ``constrain(x, "batch")``) resolve against it
    unchanged.

    ``on_event`` (plus the ``events`` list) receives ``sharding_fallback``
    dicts whenever a batch dim stays replicated because the device count
    does not divide it — under-sharding is correct but slow, so it is
    reported, never silent.
    """

    def __init__(
        self,
        devices: Union[int, Sequence, None] = None,
        *,
        axis: str = "data",
        on_event: Optional[Callable[[Dict], None]] = None,
    ):
        if devices is None:
            devs = list(jax.devices())
        elif isinstance(devices, int):
            if devices < 1:
                raise ValueError(f"need at least one device, got {devices}")
            visible = list(jax.devices())
            if len(visible) < devices:
                raise ValueError(
                    f"need {devices} devices but jax sees {len(visible)}; "
                    f"on CPU set XLA_FLAGS=--xla_force_host_platform_"
                    f"device_count={devices} in the environment BEFORE jax "
                    f"initializes (first import wins)"
                )
            devs = visible[:devices]
        else:
            devs = list(devices)
            if not devs:
                raise ValueError("need at least one device")
        self.axis = axis
        self.mesh = Mesh(np.array(devs), (axis,))
        self.events: List[Dict] = []
        self._on_event = on_event
        self._jit_cache: Dict[Tuple, Callable] = {}
        #: rows sent to each device by ``segagg``, keyed by device id
        self.rows_placed: Dict[int, int] = {}

    # -- introspection ----------------------------------------------------
    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        kind = self.mesh.devices.flat[0].platform
        return f"DeviceMesh({self.num_devices}x{kind}, axis={self.axis!r})"

    def _emit(self, event: Dict) -> None:
        self.events.append(event)
        if self._on_event is not None:
            self._on_event(event)

    # -- extents <-> shardings --------------------------------------------
    def shard_extents(self, num_tuples: int) -> Tuple[Tuple[int, int], ...]:
        """The pool's 1-D batch split for this mesh: ``batch_shard_extents``
        over the device count.  When the count divides ``num_tuples`` these
        extents are EXACTLY the per-device rows of ``batch_sharding`` (the
        consistency the tests pin)."""
        return batch_shard_extents(num_tuples, self.num_devices)

    def batch_sharding(self, batch_rows: int, ndim: int) -> NamedSharding:
        """NamedSharding for a ``(batch_rows, ...)`` array of rank ``ndim``:
        dim 0 split over the data axis when divisible, replicated (with a
        ``sharding_fallback`` event) otherwise."""
        unsub = on_fallback(self._emit)
        try:
            spec = P(*batch_spec(self.mesh, batch_rows, ndim))
        finally:
            unsub()
        return NamedSharding(self.mesh, spec)

    # -- sharded kernels ---------------------------------------------------
    def _sharded_segagg(self, num_groups: int, backend: Optional[str]):
        key = (num_groups, backend)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn
        axis = self.axis

        def per_shard(k: jax.Array, v: jax.Array) -> jax.Array:
            # Each device runs the SAME compiled single-device kernel over
            # its rows; the leading length-1 axis makes the stacked result
            # (D, G, V) — shaped exactly like pane partials, so the final
            # cross-device combine IS merge_panes.
            return segagg(k, v, num_groups, backend=backend)[None]

        sharded = jax.shard_map(
            per_shard,
            mesh=self.mesh,
            in_specs=(P(axis), P(axis, None)),
            out_specs=P(axis, None, None),
        )
        def merged(k: jax.Array, v: jax.Array) -> jax.Array:
            parts = sharded(k, v)
            with jax.named_scope("mesh.merge"):
                return merge_panes(parts)

        fn = jax.jit(merged, donate_argnums=(1,))
        self._jit_cache[key] = fn
        return fn

    def segagg(
        self,
        keys: jax.Array,
        values: jax.Array,
        num_groups: int,
        *,
        backend: Optional[str] = None,
    ) -> jax.Array:
        """GROUP-BY partial aggregation sharded across the mesh: rows split
        over the data axis, one ``segagg`` per device, partials merged.
        Bit-compatible with the single-device op for integer-valued f32
        inputs; ``values`` is donated (see the module docstring).

        Rows are padded and split on the host and each device is sent only
        its own rows; ``rows_placed`` counts them per device."""
        keys = np.asarray(keys).astype(np.int32)
        values = np.asarray(values)
        if values.ndim == 1:
            values = values[:, None]
        D = self.num_devices
        if D == 1:
            with tracing.span("transfer"):
                k, v = jnp.asarray(keys), jnp.asarray(values)
            with tracing.span("kernel.dispatch"):
                return segagg(k, v, num_groups, backend=backend)
        N, V = keys.shape[0], values.shape[1]
        Np = -(-max(N, 1) // D) * D
        with tracing.span("transfer"):
            if Np != N:
                keys = np.concatenate(
                    [keys, np.full((Np - N,), num_groups, np.int32)])
                values = np.concatenate(
                    [values, np.zeros((Np - N, V), values.dtype)])
            k = jax.device_put(keys, self.batch_sharding(Np, 1))
            v = jax.device_put(values, self.batch_sharding(Np, 2))
        for shard in k.addressable_shards:
            self.rows_placed[shard.device.id] = (
                self.rows_placed.get(shard.device.id, 0) + shard.data.shape[0])
        # One dispatch of one program; each device runs the formulation of
        # its own rows.
        tracing.count("segagg." + tuning.pick_formulation(
            resolve_backend(backend), Np // D, num_groups, V))
        with tracing.span("kernel.dispatch"):
            return self._sharded_segagg(num_groups, backend)(k, v)

    def pane_segagg(
        self,
        keys: jax.Array,
        values: jax.Array,
        pane_ids: jax.Array,
        num_panes: int,
        num_groups: int,
        *,
        backend: Optional[str] = None,
    ) -> jax.Array:
        """Pane-partial aggregation sharded across the mesh, via the same
        composite-key reduction as the single-device op: (N,) keys +
        pane_ids -> (num_panes, num_groups, V) per-pane group sums."""
        values = np.asarray(values)
        if values.ndim == 1:
            values = values[:, None]
        total = pane_composite_groups(num_panes, num_groups)
        composite = (
            np.asarray(pane_ids).astype(np.int32) * num_groups
            + np.asarray(keys).astype(np.int32)
        )
        flat = self.segagg(composite, values, total, backend=backend)
        return flat.reshape(num_panes, num_groups, values.shape[1])


class MeshBackend(WorkerBackend):
    """Worker backend over a ``DeviceMesh``: one worker per device, clocks
    stitched from MEASURED wall seconds.

    The worker clocks still form the scheduling timeline (decision
    instants, waits, deadlines) — but every dispatch advances them by the
    measured duration of the real mesh call instead of a cost-model
    prediction, so traces ARE wall-clock and the cost models can be
    validated against them.

    ``prefers_group_dispatch``: the runtime loop hands a whole shard group
    to ``run_shard_group``, which runs the covering tuple range as ONE
    fused ``shard_map`` call (``_group_execute``) — all claimed workers
    share its start/end.  Subclasses implement the three physical hooks
    (``_batch_execute``/``_group_execute``/``_agg_execute``); see
    ``repro.serve.analytics.MeshAnalyticsBackend`` for the serving one.

    ``worker_weights`` reports measured per-worker throughput ratios from
    SOLO dispatches (group calls are indivisible, so they do not
    attribute).  A homogeneous host mesh stays all-1.0 (below the
    heterogeneity threshold), which keeps shard splits on the balanced
    default path.
    """

    prefers_group_dispatch = True

    #: measured max/min throughput ratio above which the mesh is reported
    #: heterogeneous (weighted shard extents kick in).  Below it, noise.
    heterogeneity_threshold = 1.25

    def __init__(self, mesh: DeviceMesh, names: Optional[Sequence[str]] = None):
        self.mesh = mesh
        if names is None:
            names = tuple(f"d{i}" for i in range(mesh.num_devices))
        elif len(names) != mesh.num_devices:
            raise ValueError(
                f"{len(names)} names for {mesh.num_devices} devices"
            )
        super().__init__(names)
        self._solo_tuples: Dict[str, float] = {n: 0.0 for n in names}
        self._solo_secs: Dict[str, float] = {n: 0.0 for n in names}

    # -- measured heterogeneity -------------------------------------------
    @property
    def worker_weights(self) -> Tuple[float, ...]:
        tp = []
        for n in self.worker_names:
            if self._solo_secs[n] <= 0.0 or self._solo_tuples[n] <= 0.0:
                return (1.0,) * len(self.worker_names)
            tp.append(self._solo_tuples[n] / self._solo_secs[n])
        if max(tp) < self.heterogeneity_threshold * min(tp):
            return (1.0,) * len(self.worker_names)
        mean = sum(tp) / len(tp)
        return tuple(t / mean for t in tp)

    # -- dispatch ----------------------------------------------------------
    def _charge(self, query, dt: float) -> None:
        self.wall_seconds[query.query_id] = (
            self.wall_seconds.get(query.query_id, 0.0) + dt
        )

    def run_batch(self, query, num_tuples, offset, worker):
        start = self._clocks[worker]
        with tracing.span("executor.batch", query.query_id):
            t0 = time.perf_counter()
            self._batch_execute(query, num_tuples, offset)
            dt = time.perf_counter() - t0
        self.last_batch_wall = dt
        self._charge(query, dt)
        self._solo_tuples[worker] += num_tuples
        self._solo_secs[worker] += dt
        end = start + dt
        self._clocks[worker] = end
        return Dispatch(worker=worker, start=start, end=end), dt

    def run_shard_group(self, query, sizes, base_offset, workers):
        # The fused call cannot start before the LAST claimed worker frees
        # (all devices participate in the shard_map).
        start = max(self._clocks[w] for w in workers)
        with tracing.span("executor.batch", query.query_id):
            t0 = time.perf_counter()
            self._group_execute(query, sizes, base_offset, workers)
            dt = time.perf_counter() - t0
        self.last_batch_wall = dt
        self._charge(query, dt)
        end = start + dt
        for w in workers:
            self._clocks[w] = end
        return tuple(
            Dispatch(worker=w, start=start, end=end) for w in workers
        )

    def run_agg(self, query, num_batches, worker, start, barrier):
        with tracing.span("executor.finalize", query.query_id):
            t0 = time.perf_counter()
            self._agg_execute(query, num_batches)
            dt = time.perf_counter() - t0
        self.last_agg_wall = dt
        self._charge(query, dt)
        if dt > 0:
            self._clocks[worker] = start + dt
            return Dispatch(worker=worker, start=start, end=start + dt), dt
        return Dispatch(worker=worker, start=barrier, end=barrier), dt

    # -- physical hooks ----------------------------------------------------
    def _batch_execute(self, query, num_tuples: int, offset: int) -> None:
        """Process tuples [offset, offset + num_tuples) on the mesh (solo
        dispatch: one shard)."""
        raise NotImplementedError

    def _group_execute(
        self,
        query,
        sizes: Tuple[int, ...],
        base_offset: int,
        workers: Tuple[str, ...],
    ) -> None:
        """Process the covering range [base_offset, base_offset +
        sum(sizes)) as ONE fused mesh call."""
        raise NotImplementedError

    def _agg_execute(self, query, num_batches: int) -> None:
        """Combine the query's partials into its final result."""
        raise NotImplementedError
