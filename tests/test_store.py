"""Paged hierarchical posterior store (repro.core.store): a paged store
at any occupancy must answer ticks bitwise-f64 equal to the dense
identity-mode service on the same logical rows (spill/fault-in is an
exact f64 round-trip), capacity-doubling insert/evict churn must never
recompile the jit'd tick/scatter/gather executables, the free-list must
recycle evicted ids, and the empirical-Bayes bucket hyperpriors must
make planted-p* cold starts strictly tighter than the fixed taxonomy
prior while converging to the same posterior as evidence accumulates."""
import numpy as np
import pytest
from hypothesis import assume, given, settings
from hypothesis import strategies as st
from jax import enable_x64

from repro.core import online as online_mod
from repro.core.calibration import seed_store_from_replay
from repro.core.decision import DecisionInputs, evaluate
from repro.core.drift import DriftMonitor
from repro.core.online import (
    OnlineDecisionService,
    online_calibration_batch,
    shadow_mode_batch,
)
from repro.core.posterior import BetaPosterior
from repro.core.store import PosteriorStore, _gather_rows, _scatter_rows
from repro.core.taxonomy import DependencyType, prior_params


def _register_rows(svc, n, tenant_every=None):
    for i in range(n):
        svc.register_edge(
            ("u", f"v{i}"),
            tenant=(f"t{i % tenant_every}" if tenant_every else None),
            dep_type=DependencyType.ROUTER_K_WAY,
            k=2 + i % 5,
            discount=(0.95 if i % 3 == 0 else 1.0),
            floor_C_spec_usd=0.01,
            floor_L_value_usd=0.05,
        )


def _requests(rng, B, rows):
    return dict(
        rows=rng.choice(rows, B),
        alpha=rng.uniform(0, 1, B),
        lam=rng.uniform(1e-4, 0.5, B),
        lat=rng.uniform(0.01, 5.0, B),
        in_tok=rng.integers(1, 2000, B).astype(float),
        out_tok=rng.uniform(1, 2000, B),
        in_price=rng.uniform(1e-8, 1e-4, B),
        out_price=rng.uniform(1e-8, 1e-4, B),
    )


def _tick(svc, req, **kw):
    return svc.tick(
        req["rows"], alpha=req["alpha"], lambda_usd_per_s=req["lam"],
        latency_s=req["lat"], input_tokens=req["in_tok"],
        output_tokens=req["out_tok"], input_price=req["in_price"],
        output_price=req["out_price"], **kw)


# ---------------------------------------------------------------------------
# paged vs dense bitwise parity (the tentpole contract)
# ---------------------------------------------------------------------------
def test_paged_store_bitwise_matches_dense_service_under_churn():
    """A paged store holding only 8 of 40 rows on device — ticks cycling
    through every row force constant LRU spill / fault-in — answers every
    decision, settles every outcome, and runs every drift step bitwise
    -f64 identical to the dense identity-mode service."""
    with enable_x64():
        n = 40
        dense = OnlineDecisionService(use_lower_bound=True)
        paged = OnlineDecisionService(use_lower_bound=True, resident_rows=8,
                                      min_rows=8)
        _register_rows(dense, n)
        _register_rows(paged, n)
        rng_seq = np.random.default_rng(7)
        for t in range(12):
            rows = np.arange((t * 7) % n, (t * 7) % n + 6) % n
            req = _requests(np.random.default_rng(100 + t), 6, rows)
            outcomes = [(int(r), bool(rng_seq.integers(2)))
                        for r in rng_seq.choice(rows, 4)]
            dd = _tick(dense, req, outcomes=outcomes, check_drift=True)
            dp = _tick(paged, req, outcomes=outcomes, check_drift=True)
            assert np.array_equal(dd.speculate, dp.speculate)
            assert np.array_equal(dd.EV_usd, dp.EV_usd)
            assert np.array_equal(dd.threshold_usd, dp.threshold_usd)
            assert np.array_equal(dd.margin_usd, dp.margin_usd)
            assert np.array_equal(dd.P_used, dp.P_used)
            assert np.array_equal(dd.drift_triggered[:n],
                                  dp.drift_triggered[:n])
        assert paged.store.stats["spills"] > 0
        assert paged.store.stats["fault_ins"] > paged.store.capacity
        assert paged.store.n_resident <= paged.store.capacity == 8
        # the composed snapshots (device + shelf + unborn tiers) agree
        # bitwise, as do the kill-switch flags riding through the shelf
        assert np.array_equal(dense.posterior_snapshot(),
                              paged.posterior_snapshot())
        assert np.array_equal(dense.breach_runs(), paged.breach_runs())
        assert np.array_equal(dense.enabled_snapshot(),
                              paged.enabled_snapshot())


def test_paged_decisions_bitwise_equal_scalar_evaluate():
    """Spilled-then-faulted rows answer bitwise-f64 equal to the scalar
    decision.evaluate mean path (the acceptance contract, small-scale —
    benchmarks/store_scale.py asserts it at 1M logical rows)."""
    with enable_x64():
        svc = OnlineDecisionService(resident_rows=4, min_rows=4)
        _register_rows(svc, 16)
        rng = np.random.default_rng(3)
        # touch all rows so everything spills at least once
        for start in range(0, 16, 4):
            _tick(svc, _requests(rng, 4, np.arange(start, start + 4)),
                  outcomes=[(start, True), (start + 1, False)])
        snap = svc.posterior_snapshot()
        for start in range(0, 16, 4):
            rows = np.arange(start, start + 4)
            req = _requests(np.random.default_rng(40 + start), 4, rows)
            req["rows"] = rows
            d = _tick(svc, req)
            for j, i in enumerate(rows):
                a, b = snap[i]
                ref = evaluate(DecisionInputs(
                    P=BetaPosterior(alpha=float(a), beta=float(b)).mean,
                    alpha=float(req["alpha"][j]),
                    lambda_usd_per_s=float(req["lam"][j]),
                    latency_seconds=float(req["lat"][j]),
                    input_tokens=int(req["in_tok"][j]),
                    output_tokens=float(req["out_tok"][j]),
                    input_price=float(req["in_price"][j]),
                    output_price=float(req["out_price"][j]),
                ))
                assert d.EV_usd[j] == ref.EV_usd
                assert d.threshold_usd[j] == ref.threshold_usd
                assert d.P_used[j] == ref.P_used


# ---------------------------------------------------------------------------
# zero recompiles across capacity-doubling churn
# ---------------------------------------------------------------------------
def test_paged_churn_never_recompiles():
    """Insert/evict churn that doubles the logical registry capacity
    multiple times leaves every jit cache exactly where warm-up put it:
    the physical table shape is fixed, so growth is host-only."""
    with enable_x64():
        svc = OnlineDecisionService(resident_rows=8, min_rows=8)
        _register_rows(svc, 16)
        rng = np.random.default_rng(11)
        _tick(svc, _requests(rng, 4, np.arange(4)),
              outcomes=[(0, True)], check_drift=True)   # tick executables
        # warm every power-of-two scatter/gather pad bucket the churn can
        # reach (the store's shape-bucketing contract: a bounded, finite
        # executable set, all compiled during warm-up)
        svc.store.ensure_resident(np.arange(8, 16))     # 8-row fault+spill
        svc.store.ensure_resident(np.arange(0, 4))      # 4-row
        svc.store.ensure_resident(np.arange(4, 6))      # 2-row
        svc.store.ensure_resident(np.arange(6, 7))      # 1-row
        caches = lambda: (
            online_mod._tick._cache_size(),
            _scatter_rows._cache_size(),
            _gather_rows._cache_size(),
        )
        warm = caches()
        live = list(range(16))
        next_edge = 16
        for step in range(40):              # 16 logical rows -> 130+
            for _ in range(3):
                live.append(svc.register_edge(
                    ("u", f"v{next_edge}"), dep_type=DependencyType.ALWAYS_PRODUCES_OUTPUT))
                next_edge += 1
            if step % 4 == 0:
                svc.store.evict_row(live.pop(int(rng.integers(len(live)))))
            rows = rng.choice(np.asarray(live), 4, replace=False)
            _tick(svc, _requests(rng, 4, rows),
                  outcomes=[(int(rows[0]), True)], check_drift=True)
        assert svc.store.n_rows > 120          # logical capacity doubled 3x
        assert caches() == warm                # zero recompiles
        assert svc.store.stats["rebuilds"] == 1
        assert svc.store.capacity == 8         # physical shape never moved


# ---------------------------------------------------------------------------
# free-list, eviction semantics, LRU order
# ---------------------------------------------------------------------------
def test_free_list_reuses_evicted_ids_and_dead_rows_raise():
    with enable_x64():
        svc = OnlineDecisionService(resident_rows=4, min_rows=4)
        _register_rows(svc, 6, tenant_every=3)
        _tick(svc, _requests(np.random.default_rng(0), 4, np.arange(4)))
        svc.evict_edge(("u", "v4"), tenant="t1")
        with pytest.raises(KeyError):
            svc.row_key(4)
        with pytest.raises(IndexError, match="outcome row out of range"):
            svc.observe(4, True)
        with pytest.raises(IndexError, match="request row out of range"):
            _tick(svc, _requests(np.random.default_rng(1), 2,
                                 np.asarray([4])))
        # the freed id is recycled by the next registration
        new = svc.register_edge(("u", "v9"), dep_type=DependencyType.ALWAYS_PRODUCES_OUTPUT)
        assert new == 4
        assert svc.row_key(4) == (None, ("u", "v9"))
        # the recycled row starts from its own prior, not the dead row's
        a0, b0 = prior_params(DependencyType.ALWAYS_PRODUCES_OUTPUT)
        assert tuple(svc.posterior_snapshot()[4]) == (a0, b0)
        # tenant-level eviction drops both of t2's rows in one call
        assert svc.store.evict_tenant("t2") == 2
        assert svc.store.n_alive == 4


def test_lru_spills_least_recently_touched():
    with enable_x64():
        store = PosteriorStore(resident_rows=4, min_rows=4)
        for i in range(8):
            store.register(("u", f"v{i}"), dep_type=DependencyType.ALWAYS_PRODUCES_OUTPUT)
        store.device_tables("float64")
        store.ensure_resident(np.asarray([0, 1, 2, 3]))
        store.ensure_resident(np.asarray([1]))       # 0 now the coldest
        store.ensure_resident(np.asarray([4]))       # needs one victim
        assert set(store.resident_ids()) == {1, 2, 3, 4}
        store.ensure_resident(np.asarray([5, 6]))    # 2, 3 next coldest
        assert set(store.resident_ids()) == {1, 4, 5, 6}
        # a tick touching more distinct rows than capacity must refuse
        with pytest.raises(RuntimeError, match="resident capacity"):
            store.ensure_resident(np.arange(8))


def test_dtype_switch_and_set_posterior_reach_spilled_rows():
    """A spilled row keeps exact f64 state across an f32 <-> f64 switch,
    and set_posterior faults the row in transparently."""
    svc = OnlineDecisionService(resident_rows=4, min_rows=4)
    _register_rows(svc, 8)
    _tick(svc, _requests(np.random.default_rng(0), 4, np.arange(4)),
          outcomes=[(0, True), (0, True)])            # f32 tables
    svc.store.ensure_resident(np.arange(4, 8))        # spill rows 0-3
    snap32 = svc.posterior_snapshot().astype(np.float64)
    with enable_x64():
        assert np.array_equal(svc.posterior_snapshot(), snap32)
        svc.set_posterior(1, 7.5, 2.5)                # row 1 is spilled
        assert tuple(svc.posterior_snapshot()[1]) == (7.5, 2.5)
        assert 1 in set(svc.store.resident_ids())     # faulted in to write


# ---------------------------------------------------------------------------
# drift-monitor lifecycle wiring (satellite)
# ---------------------------------------------------------------------------
def test_drift_monitor_evicts_and_reseeds_with_store():
    with enable_x64():
        svc = OnlineDecisionService(resident_rows=4, min_rows=4)
        mon = DriftMonitor()
        svc.attach_drift_monitor(mon)
        _register_rows(svc, 8, tenant_every=4)
        for i in range(8):
            tenant, edge = svc.row_key(i)
            for _ in range(120):
                mon.observe_posterior_mean(edge, 0.9, tenant=tenant)
        assert len(mon.edges) == 8
        # eviction drops the monitor's host state for exactly that row
        svc.evict_edge(("u", "v7"), tenant="t3")
        assert mon._key(("u", "v7"), "t3") not in mon.edges
        assert len(mon.edges) == 7
        # birth is not a fault-in: first residency keeps the histories
        svc.state                                     # build device tables
        svc.store.ensure_resident(np.arange(4))
        assert all(len(st.posterior_means) == 120
                   for st in mon.edges.values())
        # 4-6 faulting in evicts 0-2 to the shelf; pulling 0-2 back is a
        # genuine shelf fault-in and re-seeds their trigger-1 baselines
        svc.store.ensure_resident(np.asarray([4, 5, 6]))
        svc.store.ensure_resident(np.asarray([0, 1, 2]))
        for i in (0, 1, 2):
            tenant, edge = svc.row_key(i)
            assert mon.state(edge, tenant).posterior_means == []
        # row 3 spilled but has not returned yet: history intact (reseed
        # happens on fault-in, not on spill)
        tenant, edge = svc.row_key(3)
        assert len(mon.state(edge, tenant).posterior_means) == 120


# ---------------------------------------------------------------------------
# empirical-Bayes pooled cold start (satellite: planted-p* property test)
# ---------------------------------------------------------------------------
def _planted_store(p_star, n_warm, trials, seed):
    """A store whose warm LLM_CALL rows each saw `trials` Bernoulli(p*)
    outcomes, then an EB fit over the resident table."""
    store = PosteriorStore(resident_rows=256)
    rng = np.random.default_rng(seed)
    for i in range(n_warm):
        store.register(("u", f"w{i}"), dep_type=DependencyType.ALWAYS_PRODUCES_OUTPUT)
    store.device_tables("float64")
    store.ensure_resident(np.arange(n_warm))
    a0, b0 = prior_params(DependencyType.ALWAYS_PRODUCES_OUTPUT)
    succ = rng.binomial(trials, p_star, n_warm)
    vals = np.stack([a0 + succ, b0 + (trials - succ)], 1).astype(float)
    store.set_rows(np.arange(n_warm), vals)
    store.fit_hyperpriors(min_evidence=5.0, strength_cap=200.0)
    return store


@settings(max_examples=15, deadline=None)
@given(
    p_star=st.floats(min_value=0.15, max_value=0.92),
    n_warm=st.integers(min_value=12, max_value=40),
    seed=st.integers(min_value=0, max_value=10_000),
)
def test_pooled_prior_recovers_planted_p_faster_than_fixed(
        p_star, n_warm, seed):
    """Cold-start acceptance: a brand-new row born from its bucket's
    fitted hyperprior starts strictly closer to the planted p* than the
    fixed taxonomy prior, and both posteriors converge to the same belief
    as conjugate evidence accumulates."""
    a_fix, b_fix = prior_params(DependencyType.ALWAYS_PRODUCES_OUTPUT)
    # "strictly tighter" is only a meaningful claim when the planted rate
    # actually differs from the fixed prior's guess by more than the
    # pooled estimate's own sampling noise
    assume(abs(a_fix / (a_fix + b_fix) - p_star) > 0.1)
    store = _planted_store(p_star, n_warm, trials=80, seed=seed)
    hp = store.hyperpriors[PosteriorStore.bucket_label(
        DependencyType.ALWAYS_PRODUCES_OUTPUT)]
    cold = store.register(("u", "cold"), dep_type=DependencyType.ALWAYS_PRODUCES_OUTPUT)
    a0, b0 = prior_params(DependencyType.ALWAYS_PRODUCES_OUTPUT)
    fixed_mean = a0 / (a0 + b0)
    pooled = BetaPosterior(alpha=hp.alpha, beta=hp.beta)
    assert tuple(store.rows_snapshot([cold])[0]) == (hp.alpha, hp.beta)
    # strictly tighter cold start (the taxonomy prior knows nothing of
    # this bucket's planted rate; the pooled one estimated it)
    assert abs(pooled.mean - p_star) < abs(fixed_mean - p_star)
    # shrinkage fades: after enough shared evidence the pooled and fixed
    # rows hold (a) nearly identical beliefs that are (b) near p*
    fixed = BetaPosterior(alpha=a0, beta=b0)
    rng = np.random.default_rng(seed + 1)
    outcomes = rng.random(4000) < p_star
    for x in outcomes:
        pooled.update(bool(x))
        fixed.update(bool(x))
    assert abs(pooled.mean - fixed.mean) < 0.02
    assert abs(pooled.mean - p_star) < 0.05


def test_eb_fit_is_per_bucket_and_ignores_thin_buckets():
    with enable_x64():
        store = PosteriorStore(resident_rows=64)
        for i in range(10):
            store.register(("u", f"a{i}"), dep_type=DependencyType.ALWAYS_PRODUCES_OUTPUT)
        for i in range(10):
            store.register(("u", f"b{i}"),
                           dep_type=DependencyType.ROUTER_K_WAY, k=3)
        store.register(("u", "solo"), dep_type=DependencyType.CONDITIONAL_OUTPUT)
        store.device_tables("float64")
        store.ensure_resident(np.arange(21))
        ids = np.arange(20)
        vals = np.zeros((20, 2))
        vals[:10] = (90.0, 10.0)    # bucket a: ~0.9
        vals[10:] = (20.0, 80.0)    # bucket b: ~0.2
        store.set_rows(ids, vals)
        hps = store.fit_hyperpriors(min_evidence=5.0, min_bucket_rows=2)
        lab_a = PosteriorStore.bucket_label(DependencyType.ALWAYS_PRODUCES_OUTPUT)
        lab_b = PosteriorStore.bucket_label(DependencyType.ROUTER_K_WAY, 3)
        assert hps[lab_a].mean == pytest.approx(0.9, abs=1e-9)
        assert hps[lab_b].mean == pytest.approx(0.2, abs=1e-9)
        assert hps[lab_a].n_rows == hps[lab_b].n_rows == 10
        # the single RETRIEVAL row never clears min_bucket_rows: its
        # registrations keep the fixed taxonomy prior
        assert PosteriorStore.bucket_label(DependencyType.CONDITIONAL_OUTPUT) not in hps
        new = store.register(("u", "solo2"), dep_type=DependencyType.CONDITIONAL_OUTPUT)
        assert tuple(store.rows_snapshot([new])[0]) == \
            prior_params(DependencyType.CONDITIONAL_OUTPUT)


# ---------------------------------------------------------------------------
# calibration stages through the store snapshot API
# ---------------------------------------------------------------------------
def test_shadow_and_online_calibration_reroute_through_store():
    with enable_x64():
        svc = OnlineDecisionService(resident_rows=4, min_rows=4)
        _register_rows(svc, 8)
        rng = np.random.default_rng(5)
        for start in (0, 4):
            _tick(svc, _requests(rng, 4, np.arange(start, start + 4)),
                  outcomes=[(start, True), (start + 1, False)])
        edges = [svc.row_key(i)[1] for i in range(8)]
        trials = [[(f"x{t}", f"x{t}" if (i + t) % 3 else f"y{t}")
                   for t in range(6)] for i in range(8)]
        # the store route must match handing the composed snapshot + the
        # per-row discounts explicitly (rows 0-3 are spilled right now)
        via_store = shadow_mode_batch(edges, svc, trials)
        snap = svc.posterior_snapshot()
        discounts = [svc._rows[i].discount for i in range(8)]
        via_snap = shadow_mode_batch(edges, snap, trials,
                                     discounts=discounts)
        for rs, rr in zip(via_store, via_snap):
            assert rs.posterior.alpha == rr.posterior.alpha
            assert rs.posterior.beta == rr.posterior.beta
            assert rs.posterior.discount == rr.posterior.discount
        # §12.4 accepts the service/store in place of the row count
        rep_a = online_calibration_batch(
            svc, [0, 1, 1, 5], [0.9, 0.8, 0.8, 0.7],
            [True] * 4, [True, False, True, True])
        rep_b = online_calibration_batch(
            8, [0, 1, 1, 5], [0.9, 0.8, 0.8, 0.7],
            [True] * 4, [True, False, True, True])
        assert len(rep_a) == len(rep_b) == 8
        assert [r.buckets for r in rep_a] == [r.buckets for r in rep_b]


def test_seed_store_from_replay_upserts_fleet_rows():
    class _FakeReport:
        def final_posterior_rows(self, grid_index=0):
            keys = [("t0", ("u", "v0")), ("t1", ("u", "v1")),
                    (None, ("u", "v2"))]
            return keys, np.asarray([3.0, 5.0, 7.0]), \
                np.asarray([1.5, 2.5, 3.5])

    with enable_x64():
        store = PosteriorStore(resident_rows=4)
        # v1/t1 pre-exists: seeding must overwrite, not re-register
        store.register(("u", "v1"), tenant="t1",
                       dep_type=DependencyType.ALWAYS_PRODUCES_OUTPUT)
        rows = seed_store_from_replay(store, _FakeReport(), gamma=0.05)
        assert store.n_rows == 3 and rows == [1, 0, 2]
        got = store.rows_snapshot(rows)
        assert np.array_equal(got, [[3.0, 1.5], [5.0, 2.5], [7.0, 3.5]])
        # the freshly-registered rows carried the passthrough kwargs
        assert store.row_config(rows[0]).gamma == 0.05
