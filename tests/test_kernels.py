"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracle,
across shapes and dtypes, plus cross-checks against the model layers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru.ops import rglru as rglru_kernel
from repro.kernels.rglru.ref import rglru_rec_ref
from repro.kernels.rglru.rglru import rglru_pallas
from repro.kernels.segagg import tuning
from repro.kernels.segagg.ops import (
    group_count,
    merge_panes,
    pane_composite_groups,
    pane_segagg,
    resolve_backend,
    segagg,
)
from repro.kernels.segagg.ref import combine_ref, pane_segagg_ref, segagg_ref
from repro.kernels.ssd.ops import ssd as ssd_kernel
from repro.kernels.ssd.ref import ssd_rec_ref

# Kernel-vs-reference parity sweeps compile many shapes: excluded from the
# fast CI selection (-m "not slow"); the full-suite job still runs them.
pytestmark = pytest.mark.slow

# Compiled-path backends available on this host: the XLA formulations are
# always compilable; the compiled Pallas kernel needs a TPU/GPU.
SEGAGG_BACKENDS = ["xla", "interpret"]
if jax.default_backend() in ("tpu", "gpu"):
    SEGAGG_BACKENDS.append("pallas")


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


class TestSegAgg:
    @pytest.mark.parametrize("n,groups,width", [
        (100, 7, 1), (1000, 37, 3), (4096, 256, 4), (513, 300, 1),
        (2048, 1, 2), (64, 1000, 1),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_segment_sum(self, n, groups, width, dtype):
        key = jax.random.PRNGKey(n + groups)
        keys = jax.random.randint(key, (n,), 0, groups)
        vals = jax.random.normal(key, (n, width)).astype(dtype)
        got = segagg(keys, vals, groups)   # default dispatch (backend=auto)
        want = segagg_ref(keys, vals, groups)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **_tol(dtype))

    def test_count_and_combine(self):
        key = jax.random.PRNGKey(0)
        keys = jax.random.randint(key, (5000,), 0, 64)
        counts = group_count(keys, 64)
        assert float(counts.sum()) == 5000.0
        # partial aggregation over batches == single-batch aggregation
        parts = jnp.stack([
            segagg(keys[i * 1000:(i + 1) * 1000],
                   jnp.ones((1000, 1)), 64) for i in range(5)
        ])
        total = combine_ref(parts)
        np.testing.assert_allclose(np.asarray(total[:, 0]),
                                   np.asarray(counts), rtol=1e-6)

    @pytest.mark.parametrize("n,panes,groups,width", [
        (300, 5, 7, 3), (1024, 8, 16, 1), (777, 3, 41, 2),
    ])
    def test_pane_segagg_matches_ref(self, n, panes, groups, width):
        key = jax.random.PRNGKey(n + panes)
        keys = jax.random.randint(key, (n,), 0, groups)
        pane_ids = jnp.sort(jax.random.randint(key, (n,), 0, panes))
        vals = jax.random.normal(key, (n, width))
        got = pane_segagg(keys, vals, pane_ids, panes, groups)
        want = pane_segagg_ref(keys, vals, pane_ids, panes, groups)
        assert got.shape == (panes, groups, width)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **_tol(jnp.float32))

    def test_pane_merge_equals_whole_range_scan(self):
        # The shared-execution identity: per-pane partials merged over the
        # pane axis == one direct scan of the whole range.
        key = jax.random.PRNGKey(3)
        keys = jax.random.randint(key, (2000,), 0, 31)
        pane_ids = jnp.repeat(jnp.arange(8), 250)
        vals = jax.random.normal(key, (2000, 2))
        parts = pane_segagg(keys, vals, pane_ids, 8, 31)
        np.testing.assert_allclose(
            np.asarray(merge_panes(parts)),
            np.asarray(segagg(keys, vals, 31)),
            rtol=1e-4, atol=1e-4,
        )
        # ...and any window (a contiguous subset of panes) merges to the
        # scan of exactly its tuples.
        window = merge_panes(parts[2:6])
        direct = segagg(keys[500:1500], vals[500:1500], 31)
        np.testing.assert_allclose(np.asarray(window), np.asarray(direct),
                                   rtol=1e-4, atol=1e-4)


class TestSegAggBackends:
    """Compiled-vs-interpret-vs-ref parity across the dispatch layer."""

    # Shapes chosen to cross every padding seam: non-block-multiple N, G
    # and V, G below/above the default crossover, tiny and skinny extremes.
    SHAPES = [
        (100, 7, 1), (1000, 37, 3), (513, 300, 1), (64, 1000, 1),
        (2048, 1, 2), (1531, 129, 5),
    ]

    @pytest.mark.parametrize("backend", SEGAGG_BACKENDS)
    @pytest.mark.parametrize("n,groups,width", SHAPES)
    def test_float_sums_allclose_to_ref(self, backend, n, groups, width):
        key = jax.random.PRNGKey(n * 31 + groups)
        keys = jax.random.randint(key, (n,), 0, groups)
        vals = jax.random.normal(key, (n, width))
        got = segagg(keys, vals, groups, backend=backend)
        want = segagg_ref(keys, vals, groups)
        assert got.shape == (groups, width)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("backend", SEGAGG_BACKENDS)
    @pytest.mark.parametrize("n,groups", [(1000, 37), (513, 300), (4096, 64)])
    def test_counts_exact(self, backend, n, groups):
        """COUNT(*) is integer-valued: every backend must be bit-exact
        against the oracle (f32 adds of 1.0 are exact below 2^24)."""
        keys = jax.random.randint(jax.random.PRNGKey(n), (n,), 0, groups)
        got = group_count(keys, groups, backend=backend)
        want = segagg_ref(keys, jnp.ones((n, 1)), groups)[:, 0]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert float(got.sum()) == float(n)

    @pytest.mark.parametrize("backend", SEGAGG_BACKENDS)
    def test_empty_input(self, backend):
        got = segagg(jnp.zeros((0,), jnp.int32), jnp.zeros((0, 3)), 11,
                     backend=backend)
        assert got.shape == (11, 3)
        assert float(jnp.abs(got).sum()) == 0.0

    @pytest.mark.parametrize("backend", SEGAGG_BACKENDS)
    def test_sacrificial_padding_group(self, backend):
        """Padded rows are routed to group num_groups and sliced away: with
        every real key in the LAST group and N far off block multiples,
        nothing may leak into other groups or get lost."""
        n, groups = 777, 13
        keys = jnp.full((n,), groups - 1, jnp.int32)
        vals = jnp.ones((n, 1), jnp.float32)
        got = np.asarray(segagg(keys, vals, groups, backend=backend))
        assert got[groups - 1, 0] == float(n)
        assert got.sum() == float(n)

    @pytest.mark.parametrize("backend", SEGAGG_BACKENDS)
    @pytest.mark.parametrize("formulation", ["matmul", "scatter", "hbm_scatter"])
    def test_formulation_override_parity(self, backend, formulation):
        key = jax.random.PRNGKey(5)
        keys = jax.random.randint(key, (900,), 0, 41)
        vals = jax.random.normal(key, (900, 2))
        got = segagg(keys, vals, 41, backend=backend,
                     formulation=formulation)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(segagg_ref(keys, vals, 41)),
                                   rtol=2e-5, atol=2e-5)

    def test_crossover_boundary(self):
        """The matmul/scatter crossover must be seamless: G at the measured
        boundary and one past it give identical results, and the selected
        formulations actually differ across it."""
        max_g = tuning.matmul_max_g("xla")
        for g in (max_g, max_g + 1):
            keys = jax.random.randint(jax.random.PRNGKey(g), (2048,), 0, g)
            vals = jax.random.normal(jax.random.PRNGKey(g + 1), (2048, 2))
            got = segagg(keys, vals, g, backend="xla")
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(segagg_ref(keys, vals, g)),
                                       rtol=2e-5, atol=2e-5)
        assert tuning.pick_formulation("xla", 2048, max_g, 2) == "matmul"
        assert tuning.pick_formulation("xla", 2048, max_g + 1, 2) == "scatter"

    @pytest.mark.parametrize("backend", SEGAGG_BACKENDS)
    def test_pane_segagg_backend_parity(self, backend):
        key = jax.random.PRNGKey(9)
        keys = jax.random.randint(key, (700,), 0, 23)
        pane_ids = jnp.sort(jax.random.randint(key, (700,), 0, 6))
        vals = jax.random.normal(key, (700, 2))
        got = pane_segagg(keys, vals, pane_ids, 6, 23, backend=backend)
        want = pane_segagg_ref(keys, vals, pane_ids, 6, 23)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_legacy_interpret_flag_still_dispatches(self):
        """Pre-PR-8 call sites pass interpret=True positionally."""
        keys = jax.random.randint(jax.random.PRNGKey(1), (300,), 0, 17)
        vals = jnp.ones((300, 1))
        np.testing.assert_allclose(
            np.asarray(segagg(keys, vals, 17, True)),
            np.asarray(segagg_ref(keys, vals, 17)), rtol=1e-6)


class TestSegAggDispatch:
    def test_auto_resolves_to_compiled(self):
        be = resolve_backend()
        if jax.default_backend() in ("tpu", "gpu"):
            assert be == "pallas"
        else:
            assert be == "xla"

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown segagg backend"):
            resolve_backend("mkl")

    def test_both_knobs_rejected(self):
        with pytest.raises(ValueError, match="not both"):
            resolve_backend("xla", interpret=True)

    @pytest.mark.skipif(jax.default_backend() in ("tpu", "gpu"),
                        reason="pallas IS compilable here")
    def test_pallas_on_cpu_rejected(self):
        with pytest.raises(ValueError, match="needs a TPU/GPU"):
            resolve_backend("pallas")
        with pytest.raises(ValueError, match="needs a TPU/GPU"):
            segagg(jnp.zeros((8,), jnp.int32), jnp.ones((8, 1)), 4,
                   interpret=False)

    def test_bad_formulation_rejected(self):
        with pytest.raises(ValueError, match="unknown segagg formulation"):
            segagg(jnp.zeros((8,), jnp.int32), jnp.ones((8, 1)), 4,
                   backend="xla", formulation="hash")

    def test_shape_class_buckets(self):
        assert tuning.shape_class(1_000, 64) == "small-narrow"
        assert tuning.shape_class(1_000, 50_000) == "small-wide"
        assert tuning.shape_class(500_000, 64) == "large-narrow"
        assert tuning.shape_class(500_000, 50_000) == "large-wide"

    def test_tuned_blocks_fallback(self):
        # unknown backend key -> compiled-in defaults, never a KeyError
        from repro.kernels.segagg.segagg import BLOCK_G, BLOCK_N

        assert tuning.tuned_blocks("no-such-backend", 100, 10) == \
            (BLOCK_N, BLOCK_G)


class TestPaneSegAggOverflow:
    def test_composite_within_int32_ok(self):
        assert pane_composite_groups(2, 3) == 6
        assert pane_composite_groups(1, 2**31 - 1) == 2**31 - 1

    def test_composite_overflow_raises(self):
        with pytest.raises(ValueError, match="exceeds int32"):
            pane_composite_groups(2**16, 2**15)

    def test_pane_segagg_overflow_raises_before_compute(self):
        keys = jnp.zeros((4,), jnp.int32)
        vals = jnp.ones((4, 1))
        pane_ids = jnp.zeros((4,), jnp.int32)
        with pytest.raises(ValueError, match="exceeds int32"):
            pane_segagg(keys, vals, pane_ids, 2**20, 2**20, backend="xla")


class TestFlashAttention:
    @pytest.mark.parametrize("shape", [
        # (B, Sq, Sk, H, Hkv, D)
        (1, 128, 128, 4, 4, 32),
        (2, 64, 64, 4, 2, 16),
        (1, 256, 256, 8, 1, 64),   # MQA
        (2, 100, 100, 4, 4, 32),   # non-block-multiple seq (padding path)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, shape, dtype, causal):
        B, Sq, Sk, H, Hkv, D = shape
        ks = jax.random.split(jax.random.PRNGKey(42), 3)
        q = jax.random.normal(ks[0], (B, Sq, H, D)).astype(dtype)
        k = jax.random.normal(ks[1], (B, Sk, Hkv, D)).astype(dtype)
        v = jax.random.normal(ks[2], (B, Sk, Hkv, D)).astype(dtype)
        got = flash_attention(q, k, v, causal=causal)
        want = attention_ref(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **_tol(dtype))

    @pytest.mark.parametrize("window", [16, 64])
    def test_sliding_window(self, window):
        B, S, H, D = 1, 128, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, H, D))
        v = jax.random.normal(ks[2], (B, S, H, D))
        got = flash_attention(q, k, v, causal=True, window=window)
        want = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=True,
                             window=window).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_logit_cap(self):
        B, S, H, D = 1, 64, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = 5.0 * jax.random.normal(ks[0], (B, S, H, D))
        k = 5.0 * jax.random.normal(ks[1], (B, S, H, D))
        v = jax.random.normal(ks[2], (B, S, H, D))
        got = flash_attention(q, k, v, causal=True, logit_cap=50.0)
        want = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=True,
                             logit_cap=50.0).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_model_layer(self):
        """Kernel vs the jnp chunked_attention used by the models."""
        from repro.layers.attention import AttnSpec, chunked_attention

        B, S, H, Hkv, D = 2, 96, 4, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, Hkv, D))
        v = jax.random.normal(ks[2], (B, S, Hkv, D))
        got = flash_attention(q, k, v, causal=True)
        want = chunked_attention(q, k, v, AttnSpec(causal=True, chunk=32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestRGLRU:
    @pytest.mark.parametrize("shape", [(1, 256, 128), (2, 300, 200),
                                       (1, 1024, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_recurrence_matches_ref(self, shape, dtype):
        B, S, N = shape
        from repro.kernels.rglru.rglru import BLOCK_N, BLOCK_S

        ks = jax.random.split(jax.random.PRNGKey(5), 2)
        log_a = -jnp.abs(jax.random.normal(ks[0], (B, S, N))) * 0.1
        u = jax.random.normal(ks[1], (B, S, N)) * 0.1
        h0 = jnp.zeros((B, N), jnp.float32)
        pad_s, pad_n = -S % BLOCK_S, -N % BLOCK_N
        la_p = jnp.pad(log_a, ((0, 0), (0, pad_s), (0, pad_n)))
        u_p = jnp.pad(u, ((0, 0), (0, pad_s), (0, pad_n)))
        h0_p = jnp.pad(h0, ((0, 0), (0, pad_n)))
        y, h_last = rglru_pallas(la_p.astype(dtype), u_p.astype(dtype), h0_p)
        y_ref, h_ref = rglru_rec_ref(la_p.astype(dtype), u_p.astype(dtype), h0_p)
        np.testing.assert_allclose(np.asarray(y[:, :S, :N], np.float32),
                                   np.asarray(y_ref[:, :S, :N], np.float32),
                                   **_tol(dtype))
        np.testing.assert_allclose(np.asarray(h_last[:, :N]),
                                   np.asarray(h_ref[:, :N]),
                                   **_tol(dtype))

    def test_full_op_matches_model_layer(self):
        from repro.layers.rglru import rglru_scan

        B, S, N = 2, 160, 96
        ks = jax.random.split(jax.random.PRNGKey(11), 4)
        x = jax.random.normal(ks[0], (B, S, N))
        r = jax.nn.sigmoid(jax.random.normal(ks[1], (B, S, N)))
        i = jax.nn.sigmoid(jax.random.normal(ks[2], (B, S, N)))
        a_param = jax.random.normal(ks[3], (N,))
        y_k, h_k = rglru_kernel(x, r, i, a_param)
        y_l, h_l = rglru_scan(x, r, i, a_param)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_l),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_l),
                                   rtol=2e-4, atol=2e-4)


class TestSSD:
    @pytest.mark.parametrize("shape", [
        # (B, S, H, P, N)
        (1, 256, 2, 16, 8),
        (2, 200, 4, 32, 16),
        (1, 512, 1, 64, 32),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_sequential_ref(self, shape, dtype):
        B, S, H, P, N = shape
        ks = jax.random.split(jax.random.PRNGKey(13), 4)
        x = (jax.random.normal(ks[0], (B, S, H, P)) * 0.5).astype(dtype)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        A = -jnp.abs(jax.random.normal(ks[2], (H,))) - 0.1
        Bm = jax.random.normal(ks[3], (B, S, H, N)) * 0.3
        Cm = jax.random.normal(ks[0], (B, S, H, N)) * 0.3
        D = jnp.ones((H,))
        y_k, h_k = ssd_kernel(x, dt, A, Bm, Cm, D)
        # oracle: sequential recurrence on dt-weighted inputs + D skip
        la = dt * A[None, None, :]
        xw = x.astype(jnp.float32) * dt[..., None]
        y_r, h_r = ssd_rec_ref(xw, la, Bm, Cm)
        y_r = y_r.astype(jnp.float32) + x.astype(jnp.float32) * D[None, None, :, None]
        bf16 = dtype == jnp.bfloat16
        tol = dict(rtol=3e-2, atol=3e-2) if bf16 else dict(rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32), **tol)
        np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                                   rtol=2e-3, atol=5e-3 if bf16 else 2e-3)

    def test_matches_model_layer(self):
        from repro.layers.ssd import ssd_chunked

        B, S, H, P, N = 1, 256, 2, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(17), 4)
        x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        A = -jnp.abs(jax.random.normal(ks[2], (H,))) - 0.1
        Bm = jax.random.normal(ks[3], (B, S, H, N)) * 0.3
        Cm = jax.random.normal(ks[0], (B, S, H, N)) * 0.3
        D = jnp.ones((H,))
        y_k, h_k = ssd_kernel(x, dt, A, Bm, Cm, D)
        y_l, h_l = ssd_chunked(x, dt, A, Bm, Cm, D, chunk=64)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_l),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_l),
                                   rtol=2e-3, atol=2e-3)
