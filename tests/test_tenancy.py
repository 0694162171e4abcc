"""Multi-tenant arbitration tests (PR "Multi-tenant eventstream").

Covers the tenancy layer end to end: quota/fairness math
(``fair_shares``, ``zipf_*``, ``partition_stream``), the per-tenant
admission condition and its incremental ``DemandLedger`` twin
(verdicts AND reason strings byte-equal under shed / renegotiate /
withdraw deltas), the tenant-aware shedding planner's no-starvation
property (hypothesis-gated with a deterministic fallback), per-query
error-bound stamping (the pooled-bound and double-count regressions),
cascaded rollups (``Query.upstream`` gating, withdraw-ungating, the
static-path progress guard), runtime quota changes
(``Session.set_quota``), and the headline inertness guarantee:
``tenant=None`` sessions are trace byte-identical with tenancy
configured, for every registered policy on both runtime cores.
"""
import dataclasses

import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    HAVE_HYPOTHESIS = False

from repro.core import (
    DemandLedger,
    LinearCostModel,
    OverloadConfig,
    Query,
    QueryOutcome,
    RecurringQuerySpec,
    Session,
    TenancyConfig,
    TenantQuota,
    UniformWindowArrival,
    apply_shed,
    demand_by_tenant,
    edf_order,
    fair_shares,
    list_policies,
    partition_stream,
    plan_shedding,
    shed_error_bound,
    tenant_quota_condition,
    tenant_summary,
    zipf_counts,
    zipf_shares,
    zipf_traffic,
)

CM = LinearCostModel(tuple_cost=1.0, overhead=0.0, agg_per_batch=0.0)
SPAN = 50.0


def tq(qid: str, tenant, n: int, start: float = 0.0, deadline: float = None,
       tier: int = 0, shed: bool = True) -> Query:
    """One window of ``n`` unit-cost tuples: demand == n exactly, so the
    fairness arithmetic in these tests is integer-checkable."""
    arr = UniformWindowArrival(wind_start=start, wind_end=start + SPAN,
                               num_tuples_total=n)
    return Query(query_id=qid, wind_start=start, wind_end=start + SPAN,
                 deadline=start + SPAN + 10.0 if deadline is None else deadline,
                 num_tuples_total=n, cost_model=CM, arrival=arr,
                 tier=tier, shed=shed, tenant=tenant)


# ---------------------------------------------------------------------------
# Quota / config units
# ---------------------------------------------------------------------------


class TestTenantQuota:
    def test_defaults_leave_everything_uncapped(self):
        q = TenantQuota()
        assert q.weight == 1.0 and q.capacity is None and q.rate is None

    @pytest.mark.parametrize("kwargs", [
        {"weight": -0.1}, {"capacity": -1.0}, {"rate": -5.0},
    ])
    def test_negative_values_rejected(self, kwargs):
        with pytest.raises(ValueError):
            TenantQuota(**kwargs)

    def test_config_weight_falls_back_to_default(self):
        cfg = TenancyConfig(quotas={"a": TenantQuota(weight=3.0)},
                            default_weight=2.0)
        assert cfg.weight("a") == 3.0
        assert cfg.weight("unquoted") == 2.0
        assert cfg.weight(None) == 2.0
        assert cfg.quota(None) is None

    def test_spec_tenant_mirror_syncs_both_ways(self):
        base = tq("r", None, 4)
        spec = RecurringQuerySpec(base=base, period=SPAN, num_windows=2,
                                  tenant="acme")
        assert spec.base.tenant == "acme"
        spec2 = RecurringQuerySpec(base=tq("r2", "acme", 4), period=SPAN,
                                   num_windows=2)
        assert spec2.tenant == "acme"
        with pytest.raises(ValueError, match="conflicts"):
            RecurringQuerySpec(base=tq("r3", "acme", 4), period=SPAN,
                               num_windows=2, tenant="other")


# ---------------------------------------------------------------------------
# Weighted max-min fairness
# ---------------------------------------------------------------------------


def check_fair_shares(demand, weights, capacity):
    """The water-filling invariants any fair division must satisfy."""
    share = fair_shares(demand, weights, capacity)
    assert set(share) == set(demand)
    total_alloc = sum(share.values())
    assert total_alloc <= capacity + 1e-6
    active = {t for t, d in demand.items()
              if d > 1e-9 and weights.get(t, 0.0) > 0}
    # Weights relative to the largest, so that subnormal weights do not
    # round the first-round slice past the capacity.
    wmax = max((weights[t] for t in active), default=0.0)
    rel = {t: weights[t] / wmax for t in active}
    wsum = sum(rel.values())
    for t, d in demand.items():
        assert -1e-9 <= share[t] <= d + 1e-6
        if t not in active:
            assert share[t] == 0.0
        elif wsum > 0:
            # Progressive filling only ever ADDS capacity to an unsatisfied
            # tenant, so everyone keeps at least the first-round slice.
            floor = min(d, capacity * rel[t] / wsum)
            assert share[t] >= floor - 1e-6
    if sum(demand[t] for t in active) <= capacity + 1e-9:
        for t in active:
            assert share[t] == pytest.approx(demand[t])


class TestFairShares:
    CASES = [
        ({"a": 10.0, "b": 90.0}, {"a": 1.0, "b": 1.0}, 60.0),
        ({"a": 10.0, "b": 90.0, "c": 40.0}, {"a": 2.0, "b": 1.0, "c": 1.0},
         100.0),
        ({"a": 5.0, "b": 5.0}, {"a": 1.0, "b": 1.0}, 100.0),
        ({"a": 50.0, "b": 50.0, "c": 0.0}, {"a": 1.0, "b": 0.0, "c": 1.0},
         30.0),
        ({"a": 7.0}, {"a": 4.0}, 0.0),
    ]

    @pytest.mark.parametrize("demand,weights,capacity", CASES)
    def test_invariants_deterministic(self, demand, weights, capacity):
        check_fair_shares(demand, weights, capacity)

    def test_saturated_capacity_is_redistributed(self):
        # a saturates at 10; its unused 20 flows to b.
        share = fair_shares({"a": 10.0, "b": 90.0}, {"a": 1.0, "b": 1.0},
                            60.0)
        assert share["a"] == pytest.approx(10.0)
        assert share["b"] == pytest.approx(50.0)

    def test_weights_scale_the_slices(self):
        share = fair_shares({"a": 90.0, "b": 90.0}, {"a": 2.0, "b": 1.0},
                            60.0)
        assert share["a"] == pytest.approx(40.0)
        assert share["b"] == pytest.approx(20.0)

    def test_uniform_weights_when_none(self):
        share = fair_shares({"a": 90.0, "b": 90.0}, None, 60.0)
        assert share["a"] == share["b"] == pytest.approx(30.0)

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    def test_invariants_property(self):
        rows = st.lists(
            st.tuples(st.floats(min_value=0.0, max_value=100.0),
                      st.floats(min_value=0.0, max_value=8.0)),
            min_size=1, max_size=6)

        @settings(max_examples=120, deadline=None)
        @given(rows=rows, capacity=st.floats(min_value=0.0, max_value=250.0))
        def check(rows, capacity):
            demand = {f"t{i}": d for i, (d, _) in enumerate(rows)}
            weights = {f"t{i}": w for i, (_, w) in enumerate(rows)}
            check_fair_shares(demand, weights, capacity)

        check()


# ---------------------------------------------------------------------------
# Zipf traffic + stream partitioning
# ---------------------------------------------------------------------------


class TestZipfTraffic:
    def test_shares_are_normalized_and_monotone(self):
        shares = zipf_shares(5, skew=1.0)
        assert sum(shares) == pytest.approx(1.0)
        assert shares == sorted(shares, reverse=True)
        assert zipf_shares(4, skew=0.0) == pytest.approx([0.25] * 4)
        with pytest.raises(ValueError):
            zipf_shares(0)

    def test_counts_sum_and_floor(self):
        counts = zipf_counts(100, 4, skew=1.0, min_each=2)
        assert sum(counts) == 100
        assert all(c >= 2 for c in counts)
        assert counts == sorted(counts, reverse=True)
        with pytest.raises(ValueError):
            zipf_counts(5, 4, min_each=2)

    def test_traffic_interleaves_and_stamps_tenants(self):
        qs = zipf_traffic(7, ["a", "b"],
                          lambda t, i, g: tq(f"{t}-{i}", None, 4))
        assert len(qs) == 7
        assert [q.tenant for q in qs[:4]] == ["a", "b", "a", "b"]
        by = demand_by_tenant(qs)
        assert by["a"] > by["b"]  # Zipf head gets more queries

    def test_traffic_rejects_mismatched_factory_stamp(self):
        with pytest.raises(ValueError, match="stamped tenant"):
            zipf_traffic(4, ["a", "b"],
                         lambda t, i, g: tq(f"q{g}", "a", 4))

    def test_partition_stream_views_anchor_to_base_window(self):
        base = UniformWindowArrival(wind_start=0.0, wind_end=SPAN,
                                    num_tuples_total=100)
        parts = partition_stream(base, [60, 25, 10])
        assert [p.num_tuples_total for p in parts] == [60, 25, 10]
        for p in parts:
            assert p.base is base
            assert p.wind_end == base.wind_end
            # Every partition closes with the stream (keeps the last tuple).
            assert p.input_time(p.num_tuples_total) == pytest.approx(
                base.input_time(base.num_tuples_total))


# ---------------------------------------------------------------------------
# Per-tenant quota condition: snapshot path + incremental ledger twin
# ---------------------------------------------------------------------------


class TestTenantQuotaCondition:
    def test_no_quotas_is_trivially_feasible(self):
        cfg = TenancyConfig()
        rep = tenant_quota_condition([tq("a1", "a", 40)], cfg, now=0.0)
        assert rep.feasible and rep.reasons == ()

    def test_tenantless_rows_never_flagged(self):
        cfg = TenancyConfig(quotas={"a": TenantQuota(capacity=0.01)})
        rep = tenant_quota_condition([tq("x", None, 500)], cfg, now=0.0)
        assert rep.feasible

    def test_capacity_quota_binds(self):
        cfg = TenancyConfig(quotas={"a": TenantQuota(capacity=0.25)})
        # budget 60, share 15 < work 40.
        rep = tenant_quota_condition([tq("a1", "a", 40)], cfg, now=0.0)
        assert not rep.feasible
        assert "tenant a" in rep.reasons[0]
        assert "capacity share" in rep.reasons[0]

    def test_rate_quota_binds(self):
        cfg = TenancyConfig(quotas={"a": TenantQuota(rate=0.5)})
        rep = tenant_quota_condition([tq("a1", "a", 40)], cfg, now=0.0)
        assert not rep.feasible
        assert "rate quota" in rep.reasons[0]

    def test_reasons_sorted_by_tenant(self):
        cfg = TenancyConfig(quotas={"a": TenantQuota(capacity=0.01),
                                    "b": TenantQuota(capacity=0.01)})
        rep = tenant_quota_condition(
            [tq("b1", "b", 40), tq("a1", "a", 40)], cfg, now=0.0)
        assert [r.split()[1] for r in rep.reasons[:2]] == ["a", "b"]


class TestLedgerTenantCheck:
    """Satellite: the incremental path's verdicts AND reason strings stay
    byte-equal to the snapshot path while rows shed, renegotiate and
    withdraw — exactly the deltas a live session applies."""

    def _config(self):
        return TenancyConfig(quotas={"a": TenantQuota(capacity=0.3),
                                     "b": TenantQuota(rate=0.9)})

    def _rows(self):
        return [tq("a1", "a", 30, start=0.0, deadline=70.0),
                tq("a2", "a", 25, start=10.0, deadline=75.0),
                tq("b1", "b", 40, start=0.0, deadline=80.0),
                tq("n1", None, 10, start=0.0, deadline=90.0)]

    def _assert_twin(self, ledger, live, cfg):
        for now in (None, 5.0, 40.0):
            inc = ledger.tenant_check(now=now, config=cfg)
            snap = tenant_quota_condition(edf_order(live), cfg, now=now)
            assert inc.feasible == snap.feasible
            assert inc.reasons == snap.reasons

    def test_deltas_stay_byte_equal_when_quotas_bind(self):
        cfg = self._config()
        rows = self._rows()
        ledger = DemandLedger()
        live = []
        for q in rows:
            ledger.add(q)
            live.append(q)
        base = ledger.tenant_check(now=0.0, config=cfg)
        assert not base.feasible and base.reasons  # the quotas DO bind
        self._assert_twin(ledger, live, cfg)

        # Tenant-scoped SHED: a thinned replacement row.
        thin, _, _ = apply_shed(live[0], 0.6)
        ledger.update(thin)
        live[0] = thin
        self._assert_twin(ledger, live, cfg)

        # RENEGOTIATE: deadline extension of the rate-capped tenant's row.
        ren = dataclasses.replace(live[2], deadline=live[2].deadline + 25.0)
        ledger.update(ren)
        live[2] = ren
        self._assert_twin(ledger, live, cfg)

        # WITHDRAW: drop one tenant-a row entirely.
        ledger.discard("a2")
        live = [q for q in live if q.query_id != "a2"]
        self._assert_twin(ledger, live, cfg)

    def test_extra_merge_matches_snapshot(self):
        cfg = self._config()
        rows = self._rows()
        ledger = DemandLedger(rows[:2])
        inc = ledger.tenant_check(extra=rows[2:], now=0.0, config=cfg)
        snap = tenant_quota_condition(edf_order(rows), cfg, now=0.0)
        assert inc.feasible == snap.feasible
        assert inc.reasons == snap.reasons

    def test_none_config_is_trivially_feasible(self):
        ledger = DemandLedger(self._rows())
        rep = ledger.tenant_check(now=0.0, config=None)
        assert rep.feasible and rep.reasons == ()


# ---------------------------------------------------------------------------
# No-starvation property of the tenant-aware planner
# ---------------------------------------------------------------------------


def check_no_starvation(victim_n, burst_ns, deadline):
    """A within-entitlement victim is never shed while over-entitlement
    bursters still have shed budget (their budget suffices by
    construction: keeping 5% of every burster + the whole victim fits the
    horizon)."""
    cfg = TenancyConfig(quotas={"v": TenantQuota(weight=2.0)})
    queries = [tq("v-0", "v", victim_n, deadline=deadline)]
    queries += [tq(f"b{i}-0", f"b{i}", n, deadline=deadline)
                for i, n in enumerate(burst_ns)]
    plan = plan_shedding(
        queries, now=0.0,
        config=OverloadConfig(max_shed=0.95, max_error_bound=float("inf")),
        tenancy=cfg)
    assert plan.feasible, plan.report.reasons
    assert "v-0" not in plan.fractions, (
        f"victim shed {plan.fractions} with burster budget left")
    # The minimal plan recruits bursters one group at a time, so not every
    # burster need shed — but SOMEONE did, and only bursters ever do.
    assert plan.fractions
    assert all(qid.startswith("b") for qid in plan.fractions)


class TestNoStarvation:
    DETERMINISTIC = [
        (10, (40, 40), 60.0),
        (25, (200, 40), 80.0),
        (5, (120, 120), 55.0),
        (20, (40, 200), 75.0),
    ]

    @pytest.mark.parametrize("victim_n,burst_ns,deadline", DETERMINISTIC)
    def test_victim_never_shed_deterministic(self, victim_n, burst_ns,
                                             deadline):
        check_no_starvation(victim_n, burst_ns, deadline)

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    def test_victim_never_shed_property(self):
        @settings(max_examples=60, deadline=None)
        @given(victim_n=st.integers(min_value=5, max_value=25),
               burst_ns=st.tuples(st.integers(min_value=40, max_value=200),
                                  st.integers(min_value=40, max_value=200)),
               deadline=st.floats(min_value=55.0, max_value=80.0))
        def check(victim_n, burst_ns, deadline):
            check_no_starvation(victim_n, burst_ns, deadline)

        check()

    def test_over_entitlement_drains_most_over_first(self):
        """With only ONE burster over entitlement, the other burster (also
        within entitlement but weight 1) is recruited before the weight-2
        victim — weight buys protection within the under bucket."""
        cfg = TenancyConfig(quotas={"v": TenantQuota(weight=2.0)})
        queries = [tq("v-0", "v", 20, deadline=80.0),
                   tq("b1-0", "b1", 200, deadline=80.0),
                   tq("b2-0", "b2", 15, deadline=80.0)]
        plan = plan_shedding(
            queries, now=0.0,
            config=OverloadConfig(max_shed=0.95,
                                  max_error_bound=float("inf")),
            tenancy=cfg)
        assert plan.feasible
        assert "v-0" not in plan.fractions
        assert plan.fractions.get("b1-0", 0.0) > 0.0

    def test_tenantless_queries_keep_planner_inert(self):
        """tenancy= configured but every query untagged: the plan must be
        byte-identical to the single-principal planner (the structural
        guarantee behind the session-level trace identity)."""
        queries = [tq(f"q{i}", None, 60, tier=i % 2, deadline=70.0)
                   for i in range(4)]
        cfg = OverloadConfig(max_shed=0.9, max_error_bound=5.0)
        legacy = plan_shedding(queries, now=0.0, config=cfg)
        tenanted = plan_shedding(
            queries, now=0.0, config=cfg,
            tenancy=TenancyConfig(quotas={"ghost": TenantQuota(weight=9.0)}))
        assert legacy.fractions == tenanted.fractions
        assert legacy.error_bounds == tenanted.error_bounds
        assert legacy.feasible == tenanted.feasible
        assert legacy.report == tenanted.report


# ---------------------------------------------------------------------------
# Per-query error bounds (bugfix guard) + the double-count regression
# ---------------------------------------------------------------------------


class TestPerQueryBounds:
    def test_bound_stamped_from_each_querys_own_kept_count(self):
        """Two same-tenant, same-tier queries shed at one group level must
        report DIFFERENT bounds when their kept counts differ — the bound
        comes from each query's own sample, never the pooled totals."""
        queries = [tq("big", "b", 400, deadline=110.0),
                   tq("small", "b", 40, deadline=110.0)]
        plan = plan_shedding(
            queries, now=0.0,
            config=OverloadConfig(max_shed=0.9, max_error_bound=float("inf")),
            tenancy=TenancyConfig())
        assert plan.feasible
        assert set(plan.fractions) == {"big", "small"}
        for q in queries:
            f = plan.fractions[q.query_id]
            thin, cum, _ = apply_shed(q, f)
            expect = shed_error_bound(cum, thin.num_tuples_total)
            assert plan.error_bounds[q.query_id] == pytest.approx(expect)
        assert (plan.error_bounds["small"]
                > plan.error_bounds["big"])  # smaller sample, wider bound

    def test_rethinned_cap_not_double_counted(self):
        """A query thinned in an earlier round (ThinnedArrival chain
        retained, prior_shed recorded) keeps its FULL remaining shed
        budget: composing apply_shed's cumulative fraction with prior_shed
        again used to collapse the cap and recruit the protected query."""
        base = Query(query_id="burst", wind_start=0.0, wind_end=30.0,
                     deadline=40.0, num_tuples_total=100, cost_model=CM,
                     arrival=UniformWindowArrival(wind_start=0.0,
                                                  wind_end=30.0,
                                                  num_tuples_total=100),
                     tier=1, shed=True)
        thin, cum, _ = apply_shed(base, 0.5)  # 50 kept, chain retained
        assert cum == pytest.approx(0.5)
        victim = Query(query_id="keep", wind_start=0.0, wind_end=30.0,
                       deadline=40.0, num_tuples_total=10, cost_model=CM,
                       arrival=UniformWindowArrival(wind_start=0.0,
                                                    wind_end=30.0,
                                                    num_tuples_total=10),
                       tier=0, shed=True)
        # Feasibility needs burst kept <= ~30: cumulative 0.7 <= 0.8 cap.
        # The double-count bug computed 0.5 + 0.5*(cumulative 0.7) = 0.85
        # > 0.8, starving the burster's budget and shedding the victim.
        plan = plan_shedding(
            [victim, thin], now=0.0,
            config=OverloadConfig(max_shed=0.8,
                                  max_error_bound=float("inf")),
            prior_shed={"burst": cum})
        assert plan.feasible
        assert "keep" not in plan.fractions
        assert plan.fractions.get("burst", 0.0) > 0.0


# ---------------------------------------------------------------------------
# Sessions: quota admission, runtime quota changes, trace identity
# ---------------------------------------------------------------------------


def _session_workload():
    specs = []
    for i in range(3):
        n = 6
        arr = UniformWindowArrival(wind_start=2.0 * i,
                                   wind_end=2.0 * i + 10.0,
                                   num_tuples_total=n)
        base = Query(query_id=f"r{i}", wind_start=2.0 * i,
                     wind_end=2.0 * i + 10.0, deadline=2.0 * i + 22.0,
                     num_tuples_total=n,
                     cost_model=LinearCostModel(tuple_cost=0.4, overhead=0.3,
                                                agg_per_batch=0.2),
                     arrival=arr, tier=i % 2)
        specs.append(RecurringQuerySpec(base=base, period=30.0,
                                        num_windows=2))
    return specs


def _identity_trace(policy, runtime, tenancy):
    session = Session(policy=policy, runtime=runtime, overload=True,
                      tenancy=tenancy)
    for spec in _session_workload():
        session.submit(spec)
    return session.run_until(90.0)


GHOST = {"ghost": TenantQuota(weight=7.0, capacity=0.5)}


class TestSessionTenancy:
    @pytest.mark.parametrize("runtime", ["scan", "heap"])
    @pytest.mark.parametrize("policy", ["llf-dynamic", "single"])
    def test_tenantless_trace_identity_fast(self, policy, runtime):
        plain = _identity_trace(policy, runtime, None)
        cfgd = _identity_trace(policy, runtime, TenancyConfig(quotas=GHOST))
        assert plain.executions == cfgd.executions
        assert plain.outcomes == cfgd.outcomes

    @pytest.mark.slow
    @pytest.mark.parametrize("runtime", ["scan", "heap"])
    @pytest.mark.parametrize("policy", sorted(list_policies()))
    def test_tenantless_trace_identity_full_matrix(self, policy, runtime):
        plain = _identity_trace(policy, runtime, None)
        cfgd = _identity_trace(policy, runtime, TenancyConfig(quotas=GHOST))
        assert plain.executions == cfgd.executions
        assert plain.outcomes == cfgd.outcomes

    def test_quota_rejection_reasons_identical_across_admission_paths(self):
        def submit(admission):
            session = Session(
                policy="llf-dynamic", admission=admission,
                tenancy={"a": TenantQuota(capacity=0.05)})
            ok = session.submit(tq("a-ok", "a", 2))
            bad = session.submit(tq("a-big", "a", 200, start=10.0,
                                    deadline=70.0))
            return ok, bad

        snap_ok, snap_bad = submit("snapshot")
        incr_ok, incr_bad = submit("incremental")
        assert snap_ok.admitted and incr_ok.admitted
        assert not snap_bad.admitted and not incr_bad.admitted
        assert any("tenant a" in r for r in snap_bad.report.reasons)
        assert snap_bad.report.reasons == incr_bad.report.reasons

    def test_outcomes_carry_tenant_for_rollups(self):
        session = Session(policy="llf-dynamic")
        session.submit(tq("a-0", "acme", 4))
        trace = session.run()
        assert [o.tenant for o in trace.outcomes] == ["acme"]
        summary = tenant_summary(trace.outcomes)
        assert summary["acme"]["windows"] == 1
        assert summary["acme"]["met_rate"] == 1.0

    def test_set_quota_sheds_only_that_tenant(self):
        session = Session(
            policy="llf-dynamic",
            overload=OverloadConfig(max_shed=0.9,
                                    max_error_bound=float("inf")))
        session.submit(tq("a-0", "a", 10, deadline=200.0))
        session.submit(tq("b-0", "b", 40, deadline=200.0))
        plan = session.set_quota("b", TenantQuota(capacity=0.1))
        assert plan is not None and plan.fractions
        assert all(qid.startswith("b") for qid in plan.fractions)
        events = session.trace.events_for("quota")
        assert len(events) == 1 and events[0].query_id == "b"
        assert "capacity=0.1" in events[0].detail
        session.set_quota("b", None)
        removed = [e for e in session.trace.events_for("quota")
                   if e.detail == "removed"]
        assert len(removed) == 1

    def test_set_quota_enables_tenancy_on_first_use(self):
        session = Session(policy="llf-dynamic", overload=True)
        assert session._runtime.tenancy is None
        session.set_quota("a", TenantQuota(weight=2.0))
        assert session._runtime.tenancy is not None
        assert session._runtime.tenancy.quotas["a"].weight == 2.0


class TestTenantSummary:
    def test_rollup_math(self):
        def outcome(tenant, met, shed, bound):
            return QueryOutcome(
                query_id="q", completion_time=5.0 if met else 30.0,
                deadline=10.0, total_cost=1.0, num_batches=1,
                tuples_processed=4, num_tuples_total=4,
                shed_fraction=shed, error_bound=bound, tenant=tenant)

        rows = [outcome("a", True, 0.0, 0.0), outcome("a", False, 0.2, 0.3),
                outcome(None, True, 0.0, 0.0)]
        summary = tenant_summary(rows)
        assert summary["a"] == {"windows": 2, "met": 1, "exact": 1,
                                "max_error_bound": 0.3, "met_rate": 0.5}
        assert summary[None]["met_rate"] == 1.0

    def test_empty(self):
        assert tenant_summary([]) == {}


# ---------------------------------------------------------------------------
# Cascaded rollups (Query.upstream)
# ---------------------------------------------------------------------------


def _cascade_session(policy="llf-dynamic", gold_windows=2, silver_windows=4):
    cm = LinearCostModel(tuple_cost=1.0, overhead=0.05, agg_per_batch=0.05)
    silver = Query(query_id="silver", wind_start=0.0, wind_end=SPAN,
                   deadline=SPAN + 30.0, num_tuples_total=10, cost_model=cm,
                   arrival=UniformWindowArrival(wind_start=0.0, wind_end=SPAN,
                                                num_tuples_total=10),
                   tenant="silver")
    gold = Query(query_id="gold", wind_start=0.0, wind_end=2 * SPAN,
                 deadline=2 * SPAN + 120.0, num_tuples_total=6, cost_model=cm,
                 arrival=UniformWindowArrival(wind_start=0.0, wind_end=2 * SPAN,
                                              num_tuples_total=6),
                 tenant="gold", upstream="silver")
    session = Session(policy=policy, c_max=20.0)
    session.submit(RecurringQuerySpec(base=silver, period=SPAN,
                                      num_windows=silver_windows))
    session.submit(RecurringQuerySpec(base=gold, period=2 * SPAN,
                                      num_windows=gold_windows,
                                      deadline_offset=120.0))
    return session


class TestCascade:
    def test_gold_defers_until_covered_silver_windows_close(self):
        session = _cascade_session()
        trace = session.run()
        assert len(trace.events_for("cascade_defer")) >= 1
        for k, kmax in ((0, 1), (1, 3)):
            gold_start = min(e.start for e in trace.executions
                             if e.query_id == f"gold#w{k}")
            silver_end = max(e.end for e in trace.executions
                             if e.query_id in {f"silver#w{j}"
                                               for j in range(kmax + 1)})
            assert gold_start >= silver_end - 1e-9
        summary = tenant_summary(trace.outcomes)
        assert summary["gold"]["windows"] == 2
        assert summary["gold"]["met_rate"] == 1.0

    def test_static_policy_replenish_guard_terminates(self):
        """The static path's progress guard: a cascade-deferred window must
        not spin ``_replenish``; the session still completes every window
        once the upstream closes."""
        trace = _cascade_session(policy="single").run()
        gold = [o for o in trace.outcomes if o.query_id.startswith("gold")]
        assert len(gold) == 2
        for k, kmax in ((0, 1), (1, 3)):
            gold_start = min(e.start for e in trace.executions
                             if e.query_id == f"gold#w{k}")
            silver_end = max(e.end for e in trace.executions
                             if e.query_id in {f"silver#w{j}"
                                               for j in range(kmax + 1)})
            assert gold_start >= silver_end - 1e-9

    def test_withdrawn_upstream_ungates(self):
        session = _cascade_session()
        session.run_until(30.0)
        session.withdraw("silver")
        trace = session.run()
        gold = [o for o in trace.outcomes if o.query_id.startswith("gold")]
        assert len(gold) == 2  # nothing left to wait for

    def test_unknown_upstream_never_defers(self):
        q = dataclasses.replace(tq("lone", "g", 4), upstream="no-such-spec")
        session = Session(policy="llf-dynamic")
        session.submit(q)
        trace = session.run()
        assert not trace.events_for("cascade_defer")
        assert [o.query_id for o in trace.outcomes] == ["lone"]

    def test_self_reference_rejected(self):
        session = Session(policy="llf-dynamic")
        with pytest.raises(ValueError, match="upstream"):
            session.submit(dataclasses.replace(tq("loop", "g", 4),
                                               upstream="loop"))
