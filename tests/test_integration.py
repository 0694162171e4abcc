"""Integration tests: real executors driven by the paper's scheduler,
fault-tolerant checkpointing, and the end-to-end training driver."""
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Planner, Query, Strategy, TraceArrival, UniformWindowArrival
from repro.data.tpch import PAPER_QUERIES, StreamScale, stream_files
from repro.kernels.segagg.ref import segagg_numpy
from repro.serve.analytics import (
    AnalyticsExecutor,
    concat_files,
    measure_cost_model,
    run_batched,
    run_plan,
)

SCALE = StreamScale(scale=0.005)


def _files(stream: str, n: int = 48, seed: int = 3):
    files, times = [], []
    for t, o, l in stream_files(seed=seed, num_files=n, sc=SCALE):
        files.append(l if stream == "lineitem" else o)
        times.append(t)
    return files, times


def _numpy_groupby(query, files):
    """Independent reference: the query's keys and values over all files,
    aggregated by float64 ``np.bincount``."""
    records = concat_files(files)
    return segagg_numpy(query.key_fn(records), query.value_fn(records),
                        query.num_groups(SCALE))


class TestAnalyticsExecutor:
    @pytest.mark.parametrize("query", PAPER_QUERIES, ids=lambda q: q.query_id)
    def test_partials_equal_oneshot(self, query):
        files, _ = _files(query.stream, 24)
        one, _, _ = run_batched(query, files, 24, SCALE)
        many, _, nb = run_batched(query, files, 5, SCALE)
        assert nb == 5
        np.testing.assert_allclose(one, many, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(many, _numpy_groupby(query, files),
                                   rtol=1e-5, atol=1e-5)

    def test_kernel_path_matches_ref_path(self):
        query = PAPER_QUERIES[1]  # CQ2, 5 groups
        files, _ = _files(query.stream, 8)
        ker, _, _ = run_batched(query, files, 4, SCALE)
        assert np.array_equal(ker, _numpy_groupby(query, files))

    def test_scheduled_plan_executes_and_meets_deadline(self):
        query = PAPER_QUERIES[2]
        files, times = _files(query.stream, 48)
        cm = measure_cost_model(query, files, SCALE)
        arr = TraceArrival(timestamps=tuple(times))
        q = Query("it", arr.wind_start, arr.wind_end,
                  arr.wind_end + 1.5 * cm.cost(48), 48, cm, arr)
        plan = Planner(policy="single").schedule(q)
        result, log, agg_s = run_plan(query, files, plan, SCALE)
        assert np.array_equal(result, _numpy_groupby(query, files))
        assert sum(b.num_records for b in log) == sum(
            len(f["ts"]) for f in files)

    def test_jit_cache_shared_across_executors(self):
        """Regression: a per-instance ``jax.jit(lambda ...)`` recompiled the
        segagg kernel for EVERY AnalyticsExecutor; the module-level jitted
        kernel must compile once per (num_groups, shape)."""
        from repro.kernels.segagg.ops import _segagg_xla_matmul

        query = PAPER_QUERIES[1]  # CQ2: 5 groups -> the XLA matmul on CPU
        files, _ = _files(query.stream, 6)
        batch = concat_files(files[:2])
        before = _segagg_xla_matmul._cache_size()
        for _ in range(3):
            ex = AnalyticsExecutor(query, SCALE, backend="xla")
            ex.process_batch(batch)
            ex.process_batch(batch)
        after = _segagg_xla_matmul._cache_size()
        assert after - before <= 1  # ONE new entry at most, not one per executor

    def test_recurring_session_real_backend(self):
        """Session mode over real segagg batches: per-window results equal
        the one-shot reference, wall-second feedback calibrates the model."""
        from repro.core import LinearCostModel
        from repro.serve.analytics import run_session

        aq = PAPER_QUERIES[1]  # CQ2: 5 groups
        nw, nf = 2, 6
        windows, wts = [], []
        for w in range(nw):
            files, times = _files(aq.stream, nf, seed=10 + w)
            windows.append(files)
            wts.append([t + w * 10.0 for t in times])
        cm = LinearCostModel(tuple_cost=0.4, overhead=0.3, agg_per_batch=0.2)
        results, trace = run_session(aq, windows, wts, SCALE, cm,
                                     period=10.0, calibrate=True)
        assert sorted(results) == [0, 1]
        for w in range(nw):
            assert np.array_equal(results[w], _numpy_groupby(aq, windows[w]))
        series = trace.outcome_series(aq.query_id)
        assert [o.complete for o in series] == [True, True]
        kinds = [e.kind for e in trace.events]
        assert kinds.count("window_open") == nw

    def test_straggler_requeue_real_backend(self):
        """C_max straggler re-queue on a REAL backend: a slow ``_execute``
        gets every batch flagged + re-dispatched, and the offset-keyed
        partials make the retry overwrite instead of double-count."""
        from repro.core import LinearCostModel, get_policy, run
        from repro.serve.analytics import AnalyticsRuntimeExecutor

        class SlowAnalytics(AnalyticsRuntimeExecutor):
            def _execute(self, query, num_tuples, offset):
                super()._execute(query, num_tuples, offset)
                return 10.0  # every real batch blows C_max

        query = PAPER_QUERIES[1]
        n = 12
        files, times = _files(query.stream, n)
        cm = LinearCostModel(tuple_cost=0.4, overhead=0.3, agg_per_batch=0.2)
        arr = TraceArrival(timestamps=tuple(times))
        q = Query("st", arr.wind_start, arr.wind_end,
                  arr.wind_end + 5.0 * cm.cost(n), n, cm, arr)
        slow = SlowAnalytics({q.query_id: (query, files)}, SCALE)
        trace = run(get_policy("llf-dynamic", delta_rsf=0.5, c_max=2.0),
                    [q], slow)
        phys = slow.physical(q.query_id)
        n_batches = sum(1 for e in trace.executions if e.kind == "batch")
        assert n_batches > 0
        assert trace.stragglers.count(q.query_id) == n_batches
        # re-dispatch executed each batch twice...
        assert len(phys.batch_log) == 2 * n_batches
        # ...but the offset-keyed partials were overwritten, not appended
        assert phys.num_batches == n_batches
        # and the combined result is exactly the clean answer
        assert np.array_equal(slow.results[q.query_id],
                              _numpy_groupby(query, files))


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        from repro.train.checkpoint import (
            latest_valid, restore_checkpoint, save_checkpoint)

        tree = {"a/w": jnp.arange(12.0).reshape(3, 4),
                "b/x": jnp.ones((5,), jnp.int32)}
        save_checkpoint(tmp_path, 7, tree, extra={"note": "hi"})
        ckpt = latest_valid(tmp_path)
        assert ckpt is not None
        step, restored, extra = restore_checkpoint(ckpt)
        assert step == 7 and extra["note"] == "hi"
        np.testing.assert_array_equal(restored["a/w"], tree["a/w"])

    def test_corrupted_checkpoint_is_skipped(self, tmp_path):
        from repro.train.checkpoint import latest_valid, save_checkpoint

        tree = {"w": jnp.ones((4, 4))}
        save_checkpoint(tmp_path, 1, tree)
        save_checkpoint(tmp_path, 2, tree)
        # corrupt the newest (simulates a node dying mid-write)
        victim = sorted(tmp_path.glob("step_*"))[-1] / "w.npy"
        victim.write_bytes(b"garbage")
        ckpt = latest_valid(tmp_path)
        assert ckpt is not None and ckpt.name == "step_00000001"

    def test_partial_checkpoint_is_skipped(self, tmp_path):
        from repro.train.checkpoint import latest_valid, save_checkpoint

        tree = {"w": jnp.ones((4, 4)), "v": jnp.zeros((2,))}
        save_checkpoint(tmp_path, 1, tree)
        save_checkpoint(tmp_path, 2, tree)
        (sorted(tmp_path.glob("step_*"))[-1] / "v.npy").unlink()
        assert latest_valid(tmp_path).name == "step_00000001"


class TestServingEngine:
    def test_multi_job_llf_serves_all(self):
        from repro.models.base import get_config
        from repro.models.lm import build_specs
        from repro.models.params import init_params
        from repro.serve.engine import (
            PrefillExecutor, WindowJob, serve_multi_jobs)
        from repro.core import LinearCostModel

        cfg = dataclasses.replace(get_config("yi_6b").reduced(),
                                  vocab_size=512)
        params = init_params(build_specs(cfg), jax.random.PRNGKey(0))
        ex = PrefillExecutor(cfg, params, buckets=(1, 2, 4, 8))
        cm = LinearCostModel(tuple_cost=0.02, overhead=0.05)
        rng = np.random.default_rng(0)
        jobs = [
            WindowJob(
                job_id=f"j{i}",
                prompts=rng.integers(0, cfg.vocab_size, (n, 16)).astype(np.int32),
                arrival=UniformWindowArrival(0.0, 10.0, n),
                deadline=10.0 + 3.0 * cm.cost(n),
            )
            for i, n in enumerate((6, 10))
        ]
        report = serve_multi_jobs(jobs, ex, cm, Strategy.LLF,
                                  delta_rsf=0.5, c_max=2.0)
        for j in jobs:
            assert report[j.job_id]["processed"] == j.num_requests
            assert report[j.job_id]["met_modelled"]
            got = np.concatenate(j.results)
            assert got.shape == (j.num_requests, cfg.vocab_size)
            assert np.all(np.isfinite(got))

    def test_serve_session_online_admission(self):
        """Jobs join the continuously running engine one by one; every
        admitted request is served; the session clock carries over."""
        from repro.core import LinearCostModel, UniformWindowArrival
        from repro.models.base import get_config
        from repro.models.lm import build_specs
        from repro.models.params import init_params
        from repro.serve.engine import (
            PrefillExecutor, WindowJob, serve_session)

        cfg = dataclasses.replace(get_config("yi_6b").reduced(),
                                  vocab_size=128)
        params = init_params(build_specs(cfg), jax.random.PRNGKey(0))
        ex = PrefillExecutor(cfg, params, buckets=(1, 2, 4, 8))
        cm = LinearCostModel(tuple_cost=0.02, overhead=0.05)
        rng = np.random.default_rng(0)
        jobs = [
            WindowJob(job_id=f"j{i}",
                      prompts=rng.integers(0, cfg.vocab_size, (n, 8)).astype(
                          np.int32),
                      arrival=UniformWindowArrival(i * 2.0, i * 2.0 + 10.0, n),
                      deadline=i * 2.0 + 10.0 + 3.0 * cm.cost(n))
            for i, n in enumerate((5, 7))
        ]
        report, session = serve_session(jobs, ex, cm, policy="llf-dynamic",
                                        c_max=2.0)
        for j in jobs:
            row = report[j.job_id]
            assert row["admitted"] and row["completed"]
            assert row["processed"] == j.num_requests
            assert row["shortfall"] == 0
            got = np.concatenate(j.results)
            assert got.shape == (j.num_requests, cfg.vocab_size)
        assert session.now >= max(r["completion"] for r in report.values())

    def test_oversized_batch_split_into_bucket_sized_subbatches(self):
        """Regression: n above the largest bucket used to crash run_batch
        with a broadcast ValueError (``padded[:n] = prompts`` with n > b);
        it must split into bucket-sized sub-batches and sum the wall time."""
        from repro.models.base import get_config
        from repro.models.lm import build_specs
        from repro.models.params import init_params
        from repro.serve.engine import PrefillExecutor

        cfg = dataclasses.replace(get_config("yi_6b").reduced(),
                                  vocab_size=128)
        params = init_params(build_specs(cfg), jax.random.PRNGKey(0))
        prefill = PrefillExecutor(cfg, params, buckets=(1, 2, 4, 8, 16, 32))
        rng = np.random.default_rng(1)
        prompts = rng.integers(0, cfg.vocab_size, (40, 8)).astype(np.int32)
        out, dt = prefill.run_batch(prompts)  # n=40 > max bucket 32
        assert out.shape == (40, cfg.vocab_size)
        assert dt > 0.0
        # identical logits to running the same rows in small batches
        # (prefill rows are independent; padding must not leak)
        ref, _ = prefill.run_batch(prompts[32:])
        np.testing.assert_allclose(out[32:], ref, rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # full train-driver loop: the single heaviest test
def test_train_driver_loss_improves(tmp_path):
    """End-to-end driver: a few real steps, loss goes down, checkpoint
    written, resume works (run in-process via main())."""
    import repro.launch.train as trainer

    argv = sys.argv
    sys.argv = ["train", "--arch", "mamba2_370m", "--steps", "8",
                "--batch", "4", "--seq", "32", "--lr", "5e-3",
                "--ckpt-dir", str(tmp_path), "--ckpt-every", "4"]
    try:
        trainer.main()
    finally:
        sys.argv = argv
    from repro.train.checkpoint import latest_valid

    assert latest_valid(tmp_path) is not None
