"""Online decision service (repro.core.online): batched per-tick decisions
must be bitwise-f64 equal to the scalar ``decision.evaluate`` (the
contraction-pinned D4 gate), posterior settlement must be bitwise the
``BetaPosterior.update`` recurrence, the in-graph kill-switch must match
``DriftMonitor.check_credible_bound`` step-for-step, and the §12.2–12.4
table-batched stages must match their scalar ``calibration`` twins on
identical logs (posteriors bitwise-f64, promotion/trigger flags exact)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from repro.core import online
from repro.core.betainc import betaincinv
from repro.core.calibration import canary, online_calibration, shadow_mode
from repro.core.decision import Decision, DecisionInputs, evaluate
from repro.core.drift import DriftMonitor
from repro.core.online import (
    OnlineDecisionService,
    ServiceState,
    TELEMETRY_FIELDS,
    _breached,
    canary_batch,
    online_calibration_batch,
    shadow_mode_batch,
)
from repro.core.posterior import BetaPosterior
from repro.core.taxonomy import DependencyType
from repro.core.telemetry import SpeculationDecision, TelemetryLog
from repro.serving.spec_bridge import EngineOp, ThreadedSpeculativeRunner

# Established fleet tolerances: the §7.5 jax betaincinv differs from the
# scalar scipy ppf by <= 1e-10 relative, which spreads into EV; everything
# that does not depend on the quantile is bitwise (the online gate pins
# fp contraction, unlike the fleet engine's fused lowering).
LB_EV = dict(rtol=1e-8, atol=1e-14)


def _random_requests(rng, B, n_rows):
    return dict(
        rows=rng.integers(0, n_rows, B),
        alpha=rng.uniform(0, 1, B),
        lam=rng.uniform(1e-4, 0.5, B),
        lat=rng.uniform(0.01, 5.0, B),
        in_tok=rng.integers(1, 2000, B).astype(float),
        out_tok=rng.uniform(1, 2000, B),
        in_price=rng.uniform(1e-8, 1e-4, B),
        out_price=rng.uniform(1e-8, 1e-4, B),
    )


def _service(n_rows=6, **kw):
    svc = OnlineDecisionService(**kw)
    for i in range(n_rows):
        svc.register_edge(
            ("u", f"v{i}"),
            dep_type=DependencyType.ROUTER_K_WAY,
            k=2 + i % 5,
            discount=(0.95 if i % 3 == 0 else 1.0),
        )
    return svc


def _scalar_reference(svc, req, *, use_lower_bound=False, gamma=0.1):
    snap = svc.posterior_snapshot()
    out = []
    for i in range(len(req["rows"])):
        r = int(req["rows"][i])
        a, b = snap[r]
        post = BetaPosterior(alpha=float(a), beta=float(b))
        out.append(evaluate(
            DecisionInputs(
                P=post.mean,
                alpha=float(req["alpha"][i]),
                lambda_usd_per_s=float(req["lam"][i]),
                latency_seconds=float(req["lat"][i]),
                input_tokens=int(req["in_tok"][i]),
                output_tokens=float(req["out_tok"][i]),
                input_price=float(req["in_price"][i]),
                output_price=float(req["out_price"][i]),
                P_lower_bound=(post.lower_bound(gamma)
                               if use_lower_bound else None),
            ),
            use_lower_bound=use_lower_bound,
        ))
    return out


def _tick(svc, req, **kw):
    return svc.tick(
        req["rows"], alpha=req["alpha"], lambda_usd_per_s=req["lam"],
        latency_s=req["lat"], input_tokens=req["in_tok"],
        output_tokens=req["out_tok"], input_price=req["in_price"],
        output_price=req["out_price"], **kw)


# ---------------------------------------------------------------------------
# D4 gate parity (the tentpole contract)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B", [1, 37, 301])
def test_tick_bitwise_equal_to_scalar_evaluate(B):
    """Batched mean-path decisions — flag, EV, threshold, margin — are
    bitwise-f64 equal to decision.evaluate on randomized inputs (the
    runtime-zero contraction pin; no FMA ULP allowance needed)."""
    with enable_x64():
        svc = _service()
        rng = np.random.default_rng(100 + B)
        req = _random_requests(rng, B, svc.n_rows)
        refs = _scalar_reference(svc, req)
        d = _tick(svc, req)
        for i, ref in enumerate(refs):
            assert bool(d.flag[i]) == (ref.decision is Decision.SPECULATE)
            assert d.EV_usd[i] == ref.EV_usd
            assert d.threshold_usd[i] == ref.threshold_usd
            assert d.margin_usd[i] == ref.margin_usd
            assert d.C_spec_usd[i] == ref.C_spec_usd
            assert d.P_used[i] == ref.P_used


def test_tick_lower_bound_parity():
    """§7.5 gating: decision flags match the scipy-backed scalar path; EV
    and P_used carry the established betaincinv-vs-ppf allowance; the
    threshold does not depend on the quantile and stays bitwise."""
    with enable_x64():
        svc = _service(use_lower_bound=True)
        rng = np.random.default_rng(5)
        req = _random_requests(rng, 128, svc.n_rows)
        refs = _scalar_reference(svc, req, use_lower_bound=True)
        d = _tick(svc, req)
        for i, ref in enumerate(refs):
            assert bool(d.flag[i]) == (ref.decision is Decision.SPECULATE)
            assert d.threshold_usd[i] == ref.threshold_usd
            np.testing.assert_allclose(d.P_used[i], ref.P_used, rtol=1e-9)
            np.testing.assert_allclose(d.EV_usd[i], ref.EV_usd, **LB_EV)


def test_tie_breaks_to_speculate():
    """EV == threshold exactly -> SPECULATE (§6.1), matching the scalar
    tie-break bitwise: zero prices make both sides +0.0."""
    with enable_x64():
        svc = _service()
        d = svc.tick([0], alpha=1.0, lambda_usd_per_s=0.0, latency_s=0.0,
                     input_tokens=0, output_tokens=0, input_price=0.0,
                     output_price=0.0)
        assert d.EV_usd[0] == 0.0 and d.threshold_usd[0] == 0.0
        assert bool(d.flag[0])


# ---------------------------------------------------------------------------
# spec_bridge routing (satellite: scalar path kept, parity pinned)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("use_lower_bound", [False, True])
def test_spec_bridge_service_route_matches_scalar(use_lower_bound):
    with enable_x64():
        svc = OnlineDecisionService()
        op = EngineOp("drafter", engine=None, max_new_tokens=160)
        routed = ThreadedSpeculativeRunner(
            lambda: (None, None), op, service=svc, edge=("clf", "drafter"))
        scalar = ThreadedSpeculativeRunner(lambda: (None, None), op)
        assert routed.service_row is not None
        rng = np.random.default_rng(17)
        for _ in range(100):
            post = BetaPosterior(alpha=float(rng.uniform(0.1, 40)),
                                 beta=float(rng.uniform(0.1, 40)))
            args = (post, float(rng.uniform(0, 1)),
                    float(rng.uniform(1e-3, 0.5)), float(rng.uniform(0.01, 5)))
            got = routed.decide_full(*args, use_lower_bound=use_lower_bound)
            ref = scalar.decide_full(*args, use_lower_bound=use_lower_bound)
            assert got.decision == ref.decision
            assert got.threshold_usd == ref.threshold_usd
            assert got.C_spec_usd == ref.C_spec_usd
            if use_lower_bound:
                np.testing.assert_allclose(got.EV_usd, ref.EV_usd, **LB_EV)
                np.testing.assert_allclose(
                    got.margin_usd, ref.margin_usd, rtol=1e-8, atol=1e-12)
            else:
                assert got.EV_usd == ref.EV_usd
                assert got.margin_usd == ref.margin_usd


def test_spec_bridge_reuses_registered_row():
    svc = OnlineDecisionService()
    op = EngineOp("drafter", engine=None)
    r1 = ThreadedSpeculativeRunner(lambda: (None, None), op, service=svc,
                                   edge=("clf", "drafter"))
    r2 = ThreadedSpeculativeRunner(lambda: (None, None), op, service=svc,
                                   edge=("clf", "drafter"))
    assert r1.service_row == r2.service_row
    r3 = ThreadedSpeculativeRunner(lambda: (None, None), op, service=svc,
                                   edge=("clf", "drafter"), tenant="acme")
    assert r3.service_row != r1.service_row
    r1.observe(True)
    svc.apply_outcomes()
    snap = svc.posterior_snapshot()
    assert snap[r1.service_row, 0] == pytest.approx(2.0)   # 1+1 successes
    # reusing a registered row with a different gamma would silently
    # diverge from the scalar §7.5 route -> must refuse loudly
    with pytest.raises(ValueError, match="gamma"):
        ThreadedSpeculativeRunner(lambda: (None, None), op, service=svc,
                                  edge=("clf", "drafter"), gamma=0.3)


# ---------------------------------------------------------------------------
# outcome settlement (discount recurrence)
# ---------------------------------------------------------------------------
def test_outcome_settlement_bitwise_matches_update_many():
    """Settled outcomes apply the exact BetaPosterior.update recurrence —
    bitwise at f64, including discount < 1 and repeated same-row outcomes
    within one tick (arrival order)."""
    with enable_x64():
        svc = _service(n_rows=4)
        rng = np.random.default_rng(9)
        refs = {r: svc.posterior(r) for r in range(4)}
        for _ in range(5):
            outs = [(int(rng.integers(0, 4)), bool(rng.integers(0, 2)))
                    for _ in range(int(rng.integers(1, 12)))]
            svc.apply_outcomes(outs)
            for r, s in outs:
                refs[r].update(s)
        snap = svc.posterior_snapshot()
        for r in range(4):
            assert snap[r, 0] == refs[r].alpha
            assert snap[r, 1] == refs[r].beta


def test_outcomes_settle_before_decisions():
    """Tick order contract: this tick's outcomes are visible to this
    tick's decisions (freshest-belief serving)."""
    with enable_x64():
        svc = _service(n_rows=1)
        ref = svc.posterior(0)
        ref.update(True)
        req = _random_requests(np.random.default_rng(2), 4, 1)
        d = _tick(svc, req, outcomes=[(0, True)])
        assert np.all(d.P_mean == ref.mean)


def test_observe_queue_and_bounds():
    svc = _service(n_rows=2)
    svc.observe(1, True)
    svc.apply_outcomes()
    assert svc.posterior_snapshot()[1, 0] > svc.posterior_snapshot()[0, 0]
    with pytest.raises(IndexError):
        svc.apply_outcomes([(7, True)])
    with pytest.raises(IndexError):
        svc.tick([99], alpha=0.5, lambda_usd_per_s=0.01, latency_s=1.0,
                 input_tokens=1, output_tokens=1, input_price=0.0,
                 output_price=0.0)


# ---------------------------------------------------------------------------
# drift / kill-switch
# ---------------------------------------------------------------------------
def test_drift_matches_scalar_monitor_and_gates_serving():
    """The in-graph trigger-2 step matches DriftMonitor.check_credible_bound
    tick-for-tick (run counts, trigger instant, reset-and-count-again), the
    kill-switch forces WAIT, and ingest_online_triggers folds the state
    back into a scalar monitor."""
    with enable_x64():
        svc = _service(n_rows=2, credible_consecutive_n=3)
        # re-register row 1 with a breaching floor
        svc2 = OnlineDecisionService(credible_consecutive_n=3)
        svc2.register_edge(("u", "v0"), dep_type=DependencyType.ROUTER_K_WAY, k=2)
        C, Lv, al = 0.01, 0.002, 0.5
        svc2.register_edge(("u", "v1"), dep_type=DependencyType.ROUTER_K_WAY,
                           k=5, floor_alpha=al, floor_C_spec_usd=C,
                           floor_L_value_usd=Lv)
        mon = DriftMonitor(credible_consecutive_n=3)
        post = BetaPosterior.from_dependency_type(
            DependencyType.ROUTER_K_WAY, k=5)
        sink = DriftMonitor(credible_consecutive_n=3)
        for t in range(7):
            d = svc2.tick([1], alpha=0.5, lambda_usd_per_s=0.01, latency_s=1.0,
                          input_tokens=10, output_tokens=10, input_price=1e-6,
                          output_price=1e-5, check_drift=True)
            ev = mon.check_credible_bound(("u", "v1"), post, al, C, Lv)
            assert bool(d.drift_triggered[1]) == (ev is not None)
            assert svc2.breach_runs()[1] == mon._credible_breach_run[("u", "v1")]
            assert bool(svc2.enabled_snapshot()[1]) == mon.edge_enabled(("u", "v1"))
            # untouched row 0 never ticks its run
            assert svc2.breach_runs()[0] == 0 and svc2.enabled_snapshot()[0]
            got = sink.ingest_online_triggers(
                [svc2.row_key(i) for i in range(2)],
                d.drift_triggered[:2], svc2.breach_runs())
            assert (len(got) == 1) == (ev is not None)
        assert not sink.edge_enabled(("u", "v1"))
        assert sink.state(("u", "v1")).needs_shadow_rerun
        # the killed row serves WAIT even on a clearly-positive gate
        res = svc2.decide(("u", "v1"), alpha=1.0, lambda_usd_per_s=10.0,
                          latency_s=10.0, input_tokens=1, output_tokens=1,
                          input_price=1e-9, output_price=1e-9)
        assert res.decision is Decision.WAIT and res.EV_usd > res.threshold_usd


def test_drift_trips_match_quantile_replay_over_many_ticks():
    """f32 table, many rows (some floored, with spare zero slots in the
    table): over 24 ticks of repeated and partial batches with settles,
    breach runs, enable bits and trigger pulses equal a NumPy replay of
    the quantile rule ``beta.ppf(gamma, a, b) < floor`` on the
    post-settlement posteriors, tick by tick."""
    from scipy import stats

    n_rows, cn = 40, 3
    svc = OnlineDecisionService(credible_consecutive_n=cn)
    gamma = np.where(np.arange(n_rows) % 4 == 0, 0.05, 0.1)
    gamma[5::6] = 0.5
    floor = np.full(n_rows, -np.inf)
    for i in range(n_rows):
        floored = i % 2 == 0
        # floor (1 - fa) * C / (L + C): 0.5 for fa = 0, 0.25 for fa = 0.5
        fa = 0.0 if i % 4 == 0 else 0.5
        svc.register_edge(
            ("u", f"v{i}"), dep_type=DependencyType.ROUTER_K_WAY,
            k=2 + i % 3, discount=(0.9 if i % 3 == 0 else 1.0),
            gamma=float(gamma[i]), floor_alpha=fa,
            floor_C_spec_usd=(0.01 if floored else None),
            floor_L_value_usd=(0.01 if floored else None))
        if floored:
            floor[i] = (1.0 - fa) * 0.5
    assert np.asarray(svc.posterior_snapshot()).dtype == np.float32
    rng = np.random.default_rng(23)
    p_true = rng.uniform(0.05, 0.95, n_rows)
    run = np.zeros(n_rows, np.int64)
    enabled = np.ones(n_rows, bool)
    trips, breaches, margin = 0, 0, np.inf
    for t in range(24):
        B = int(rng.integers(1, 30))
        # repeats inside a batch, and a hot set of rows touched often
        rows = np.where(rng.random(B) < 0.6, rng.integers(0, 8, B),
                        rng.integers(0, n_rows, B))
        settle = rng.integers(0, n_rows, int(rng.integers(0, 20)))
        outcomes = [(int(r), bool(rng.random() < p_true[r])) for r in settle]
        req = _random_requests(rng, B, n_rows)
        req["rows"] = rows
        d = _tick(svc, req, outcomes=outcomes, check_drift=True)
        post = np.asarray(svc.posterior_snapshot(), np.float64)[:n_rows]
        touched = np.zeros(n_rows, bool)
        touched[rows] = True
        ppf = stats.beta.ppf(gamma, post[:, 0], post[:, 1])
        fin = touched & np.isfinite(floor)
        margin = min(margin, np.abs(ppf - floor)[fin].min(initial=np.inf))
        breached = touched & (ppf < floor)
        run = np.where(touched, np.where(breached, run + 1, 0), run)
        triggered = touched & (run >= cn)
        enabled &= ~triggered
        run = np.where(triggered, 0, run)
        trips += int(triggered.sum())
        breaches += int(breached.sum())
        np.testing.assert_array_equal(d.drift_triggered[:n_rows], triggered)
        np.testing.assert_array_equal(svc.breach_runs()[:n_rows], run)
        np.testing.assert_array_equal(svc.enabled_snapshot()[:n_rows],
                                      enabled)
    # the replay met no tie, and the rule both breached and tripped
    assert margin > 1e-4
    assert trips >= 2 and breaches > trips


# the kill switch's breach test against the quantile it replaces: a grid
# of small discounted and long-history posteriors (a or b under 1
# included), floors at 0 and below, inside (0, 1), and at 1 and above
_AB = (0.3, 0.7, 1.0, 2.5, 6.0, 150.0, 900.0, 2500.0, 4000.0)
_FLOORS = (-np.inf, -0.5, 0.0, 0.02, 0.1, 0.25, 0.4, 0.5, 0.6, 0.75, 0.9,
           0.98, 1.0, 1.5)


def _breach_grid(dtype):
    return tuple(x.ravel().astype(dtype) for x in
                 np.meshgrid(_AB, _AB, _FLOORS, indexing="ij"))


@pytest.mark.parametrize("gamma", [0.05, 0.1, 0.5])
@pytest.mark.parametrize("x64", [False, True], ids=["f32", "f64"])
def test_breach_cdf_test_equals_quantile_rule(x64, gamma):
    """``I_floor(a, b) > gamma`` breaches exactly where the quantile rule
    ``betaincinv(a, b, gamma) < floor`` does, at f32 and f64, away from
    ties (lanes within 1e-5 of the floor by SciPy's quantile)."""
    from scipy import stats

    dtype = np.float64 if x64 else np.float32
    with enable_x64(x64):
        a, b, f = _breach_grid(dtype)
        g = np.full_like(a, gamma)
        touched = np.ones(a.shape, bool)
        got = np.asarray(jax.jit(_breached)(a, b, g, f, touched))
        want = np.asarray(betaincinv(a, b, g) < f)
    ppf = stats.beta.ppf(gamma, a.astype(np.float64), b.astype(np.float64))
    far = ~(np.abs(ppf - f) <= 1e-5)
    assert far.sum() > 0.95 * far.size
    np.testing.assert_array_equal(got[far], want[far])
    np.testing.assert_array_equal(got[far], (ppf < f)[far])
    # the edges: a floor at or under 0 never breaches, one at or over 1
    # always does, and the interior has both answers
    assert not got[f <= 0].any() and got[f >= 1].all()
    inner = (f > 0) & (f < 1)
    assert got[inner].any() and not got[inner].all()


@pytest.mark.parametrize("x64", [False, True], ids=["f32", "f64"])
def test_breach_untouched_and_empty_slots_are_inert(x64):
    """Untouched lanes never breach, whatever they hold (an empty slot's
    zeros, NaN, a long history), and do not change the touched lanes'
    answers."""
    dtype = np.float64 if x64 else np.float32
    with enable_x64(x64):
        a, b, f = _breach_grid(dtype)
        g = np.full_like(a, 0.1)
        alone = np.asarray(_breached(a, b, g, f, np.ones(a.shape, bool)))
        n = a.size
        junk_a = np.resize(np.array([0.0, np.nan, 4000.0, 0.3], dtype), n)
        junk_b = np.resize(np.array([0.0, 2.0, 0.3, np.nan], dtype), n)
        # touched lanes interleaved with untouched junk lanes
        mixed = lambda x, j: np.stack([x, j], 1).ravel()  # noqa: E731
        touched = np.tile([True, False], n)
        got = np.asarray(_breached(
            mixed(a, junk_a), mixed(b, junk_b), np.repeat(g, 2),
            mixed(f, np.resize(np.array([0.5, 1.5], dtype), n)), touched))
    assert not got[~touched].any()
    np.testing.assert_array_equal(got[touched], alone)


def test_drift_step_adds_one_loop_to_the_tick():
    """The drift step is one CDF evaluation: the lowered tick with the
    drift check on holds at most one ``while`` more than with it off (the
    CDF's continued fraction), so the quantile's Halley loop around it
    cannot come back to this path.  Counted after lowering, where the
    CDF's loop is visible; in the jaxpr it is one primitive."""
    s = jax.ShapeDtypeStruct
    f, i = jnp.float32, jnp.int32
    rows, batch, settles = 1024, 64, 64
    state = ServiceState(post=s((rows, 2), f), rowcfg=s((rows, 3), f),
                         flags=s((rows, 2), i), roll=s((rows, 6), i),
                         tel=s((256, 10), f))
    args = (state, s((), f), s((batch,), i), s((batch,), i),
            s((batch, 7), f), s((0, 1), f), s((0,), i), s((settles,), i),
            s((settles,), f), s((), i), s((9,), i))

    def loops(check_drift):
        lowered = online._tick.trace(*args, use_lower_bound=False,
                                     check_drift=check_drift,
                                     use_rollout=False,
                                     use_beam=False).lower()
        return lowered.as_text().count("stablehlo.while")

    off, on = loops(False), loops(True)
    assert off == 1                       # the settle scan
    assert off < on <= off + 1


# ---------------------------------------------------------------------------
# telemetry ring (D2: every decision logged in dollars, flushed per tick)
# ---------------------------------------------------------------------------
def test_telemetry_ring_rows_and_wraparound():
    with enable_x64():
        svc = _service(n_rows=3, telemetry_capacity=32)
        rng = np.random.default_rng(11)
        req = _random_requests(rng, 20, 3)
        d1 = _tick(svc, req)
        tb = svc.drain_telemetry()
        assert set(tb.fields) == set(TELEMETRY_FIELDS)
        assert len(tb) == 20 and tb.dropped == 0
        np.testing.assert_array_equal(tb.fields["EV_usd"], d1.EV_usd)
        np.testing.assert_array_equal(tb.fields["margin_usd"], d1.margin_usd)
        np.testing.assert_array_equal(tb.fields["row"].astype(int), req["rows"])
        np.testing.assert_array_equal(
            tb.fields["speculate"].astype(bool), d1.speculate)
        rows = tb.rows()
        assert rows[0]["EV_usd"] == float(d1.EV_usd[0])
        # overflow the 32-slot ring: 3 ticks x 20 rows (bucketed to 32
        # slots each), one drain -> only the last tick's rows survive,
        # the 40 evicted real rows are reported as dropped
        evs = [_tick(svc, req).EV_usd for _ in range(3)]
        tb = svc.drain_telemetry()
        assert len(tb) == 20 and tb.dropped == 40
        np.testing.assert_array_equal(tb.fields["EV_usd"], evs[-1])


# ---------------------------------------------------------------------------
# table growth, dtype switch, sharding fallback
# ---------------------------------------------------------------------------
def test_registry_growth_preserves_live_state():
    with enable_x64():
        svc = _service(n_rows=2)
        svc.apply_outcomes([(0, True), (1, False)])
        before = svc.posterior_snapshot()
        for i in range(40):                      # force a table growth
            svc.register_edge(("g", f"v{i}"),
                              dep_type=DependencyType.CONDITIONAL_OUTPUT)
        after = svc.posterior_snapshot()
        assert after.shape[0] == 42
        np.testing.assert_array_equal(after[:2], before)
        assert svc.state.post.shape[0] == 64     # power-of-two padding


def test_dtype_switch_rebuilds_state():
    svc = _service(n_rows=2)
    req = _random_requests(np.random.default_rng(0), 4, 2)
    _tick(svc, req)
    assert svc.state.post.dtype == np.float32
    with enable_x64():
        d = _tick(svc, req)
        assert svc.state.post.dtype == np.float64
        assert d.EV_usd.dtype == np.float64


def test_mesh_without_fleet_axis_falls_back_unsharded():
    with enable_x64():
        import jax

        mesh = jax.make_mesh((1,), ("model",))   # no "fleet" axis
        svc = _service(n_rows=3, mesh=mesh)
        base = _service(n_rows=3)
        rng = np.random.default_rng(21)
        req = _random_requests(rng, 16, 3)
        d1, d0 = _tick(svc, req), _tick(base, req)
        np.testing.assert_array_equal(d1.EV_usd, d0.EV_usd)
        np.testing.assert_array_equal(
            svc.posterior_snapshot(), base.posterior_snapshot())


def test_tick_packed_matches_validating_tick():
    """The zero-copy hot path (packed request block, the benchmarked
    entry point) answers identically to the validating tick(), including
    pending-outcome flushes and padding sentinels."""
    with enable_x64():
        a = _service(n_rows=4)
        b = _service(n_rows=4)
        rng = np.random.default_rng(23)
        for _ in range(3):
            B = int(rng.integers(1, 40))
            req = _random_requests(rng, B, 4)
            outs = [(int(r), bool(s)) for r, s in zip(
                rng.integers(0, 4, 3), rng.integers(0, 2, 3))]
            for r, s in outs:
                a.observe(r, s)
                b.observe(r, s)
            da = _tick(a, req, check_drift=True)
            Bp = max(1, 1 << (B - 1).bit_length())
            row = np.full(Bp, -1, np.int32)
            row[:B] = req["rows"]
            reqs = np.zeros((Bp, 7), np.float64)
            for j, key in enumerate(("alpha", "lam", "lat", "in_tok",
                                     "out_tok", "in_price", "out_price")):
                reqs[:B, j] = req[key]
            db = b.tick_packed(row, reqs, batch=B, check_drift=True)
            assert db.batch == B
            np.testing.assert_array_equal(da.EV_usd, db.EV_usd)
            np.testing.assert_array_equal(da.margin_usd, db.margin_usd)
            np.testing.assert_array_equal(da.speculate, db.speculate)
        np.testing.assert_array_equal(
            a.posterior_snapshot(), b.posterior_snapshot())
        np.testing.assert_array_equal(a.breach_runs(), b.breach_runs())
        # batch defaults to the valid (non-sentinel) count — padding
        # slots must never surface as decisions
        row = np.array([0, 1, -1, -1], np.int32)
        d = b.tick_packed(row, np.zeros((4, 7), np.float64))
        assert d.batch == 2 and d.speculate.shape == (2,)


def test_donated_state_matches_default():
    """Opt-in donation (the HBM double-buffer mode) is numerically
    invisible: identical decisions and posterior trajectories."""
    with enable_x64():
        a = _service(n_rows=3)
        b = _service(n_rows=3, donate=True)
        rng = np.random.default_rng(13)
        for _ in range(3):
            req = _random_requests(rng, 24, 3)
            outs = [(int(r), bool(s)) for r, s in zip(
                rng.integers(0, 3, 5), rng.integers(0, 2, 5))]
            da = _tick(a, req, outcomes=outs, check_drift=True)
            db = _tick(b, req, outcomes=outs, check_drift=True)
            np.testing.assert_array_equal(da.EV_usd, db.EV_usd)
            np.testing.assert_array_equal(da.speculate, db.speculate)
        np.testing.assert_array_equal(
            a.posterior_snapshot(), b.posterior_snapshot())
        np.testing.assert_array_equal(
            a.drain_telemetry().fields["margin_usd"],
            b.drain_telemetry().fields["margin_usd"])


def test_decide_posterior_sync_and_snapshot_roundtrip():
    with enable_x64():
        svc = _service(n_rows=2)
        post = BetaPosterior(alpha=3.25, beta=1.5)
        res = svc.decide(("u", "v1"), posterior=post, alpha=0.4,
                         lambda_usd_per_s=0.08, latency_s=0.9,
                         input_tokens=32, output_tokens=160,
                         input_price=3e-6, output_price=15e-6)
        ref = evaluate(DecisionInputs(
            P=post.mean, alpha=0.4, lambda_usd_per_s=0.08,
            latency_seconds=0.9, input_tokens=32, output_tokens=160,
            input_price=3e-6, output_price=15e-6))
        assert (res.decision, res.EV_usd, res.threshold_usd) == (
            ref.decision, ref.EV_usd, ref.threshold_usd)
        got = svc.posterior(svc.row_index(("u", "v1")))
        assert got.as_row() == post.as_row()
        with pytest.raises(ValueError):
            svc.set_posterior(0, -1.0, 2.0)


# ---------------------------------------------------------------------------
# §12.2–12.4 folded onto the table (acceptance: scalar-stage parity)
# ---------------------------------------------------------------------------
def test_shadow_mode_batch_matches_scalar():
    rng = np.random.default_rng(31)
    R = 6
    posts = [BetaPosterior.from_prior_mean(
        float(rng.uniform(0.2, 0.8)),
        discount=(0.95 if r % 2 else 1.0)) for r in range(R)]
    trials = [[("billing" if rng.random() < 0.6 else "support", "billing")
               for _ in range(int(rng.integers(1, 120)))] for _ in range(R)]
    graded = [[("same text", "same text" if rng.random() < 0.5 else "other",
                bool(rng.integers(0, 2)))
               for _ in range(int(rng.integers(0, 8)))] for _ in range(R)]
    toks = [[float(x) for x in rng.uniform(10, 300, int(rng.integers(0, 9)))]
            for _ in range(R)]
    cancels = [[float(x) for x in rng.uniform(0, 1, int(rng.integers(0, 5)))]
               for _ in range(R)]
    edges = [("u", f"v{r}") for r in range(R)]
    batch = shadow_mode_batch(
        edges, posts, trials, graded_subsets=graded,
        output_token_counts=toks, cancel_fractions=cancels,
        n_shadow=40, stability_window=20)
    for r in range(R):
        ref = shadow_mode(
            edges[r], posts[r], trials[r], graded_subset=graded[r],
            output_token_counts=toks[r], cancel_fractions=cancels[r],
            n_shadow=40, stability_window=20)
        got = batch[r]
        assert got.posterior.alpha == ref.posterior.alpha      # bitwise f64
        assert got.posterior.beta == ref.posterior.beta
        assert got.posterior.successes == ref.posterior.successes
        assert got.posterior.failures == ref.posterior.failures
        assert got.converged == ref.converged
        assert got.best_tier2_threshold == ref.best_tier2_threshold
        assert got.tier2_f1 == ref.tier2_f1
        assert got.token_estimator.ema == ref.token_estimator.ema
        assert got.token_estimator.cov == ref.token_estimator.cov
        assert got.rho_mean == ref.rho_mean
        # zero exposure: the caller's posterior was never touched
        assert posts[r].n == 0


def test_shadow_mode_batch_from_table_snapshot():
    """The service-table entry point: raw (R, 2) snapshot + discounts."""
    svc = _service(n_rows=3)
    snap = svc.posterior_snapshot()
    disc = [svc._rows[r].discount for r in range(3)]
    trials = [[("a", "a")] * 4, [("a", "b")] * 2, []]
    batch = shadow_mode_batch(
        [svc.row_key(r)[1] for r in range(3)], snap, trials, discounts=disc)
    for r in range(3):
        ref = shadow_mode(
            ("x", "y"),
            BetaPosterior(alpha=float(snap[r, 0]), beta=float(snap[r, 1]),
                          discount=disc[r]),
            trials[r])
        assert batch[r].posterior.alpha == ref.posterior.alpha
        assert batch[r].posterior.beta == ref.posterior.beta


def test_canary_batch_matches_scalar():
    rng = np.random.default_rng(33)
    R = 8
    alphas = (0.1, 0.3, 0.5, 0.9)
    sweeps = [{a: (float(rng.uniform(0.5, 2.0)), float(rng.uniform(0.01, 0.05)))
               for a in alphas} for _ in range(R)]
    P = rng.uniform(0.05, 0.95, R)
    C = rng.uniform(0.001, 0.02, R)
    L = rng.uniform(0.5, 4.0, R)
    lam_dec = rng.uniform(0.001, 0.2, R)
    ctrl_lat = rng.uniform(0.5, 3.0, R)
    ctrl_cost = rng.uniform(0.01, 0.06, R)
    chosen = [float(rng.choice(alphas)) for _ in range(R)]
    batch = canary_batch(ctrl_lat, ctrl_cost, sweeps, chosen, P, C, L,
                         lam_dec, budget_guardrail_usd=0.04)
    for r in range(R):
        ref = canary(ctrl_lat[r], ctrl_cost[r], sweeps[r], chosen[r],
                     P[r], C[r], L[r], lam_dec[r], budget_guardrail_usd=0.04)
        got = batch[r]
        assert got.lambda_implied == ref.lambda_implied        # bitwise f64
        assert got.audit == ref.audit
        assert got.promote == ref.promote
        assert got.pareto_alphas == ref.pareto_alphas
        assert [(a.name, a.alpha, a.latency_s, a.cost_usd) for a in got.arms] \
            == [(a.name, a.alpha, a.latency_s, a.cost_usd) for a in ref.arms]
    with pytest.raises(ValueError):
        canary_batch(ctrl_lat, ctrl_cost, sweeps, chosen, np.zeros(R), C, L,
                     lam_dec)


def _telemetry_row(P_mean, succ, committed, t3, gen, est):
    return SpeculationDecision(
        decision_id="x", trace_id="t", edge=("u", "v"),
        dep_type="router_k_way", tenant="d", model_version=("m", "1"),
        alpha=0.5, lambda_usd_per_s=0.01, P_mean=P_mean, P_lower_bound=None,
        C_spec_est_usd=0.01, L_est_s=1.0, input_tokens_est=10,
        output_tokens_est=est, input_price=1e-6, output_price=1e-5,
        EV_usd=0.0, threshold_usd=0.0, decision="SPECULATE", phase="runtime",
        overrode="none", i_hat_source="modal", uncertain_cost_flag=False,
        enabled=True, budget_remaining_usd=None, tier1_match=succ,
        tier2_match=None, tier3_accept=t3,
        tokens_generated_before_cancel=gen, committed_speculative=committed)


def test_online_calibration_batch_matches_scalar():
    rng = np.random.default_rng(37)
    n_rows, M = 4, 600
    logs = [TelemetryLog() for _ in range(n_rows)]
    cols = {k: [] for k in ("row", "P", "has", "succ", "comm", "t3s", "t3a",
                            "gen", "est")}
    for _ in range(M):
        r = int(rng.integers(0, n_rows))
        P = float(rng.uniform(0, 1))
        know = bool(rng.random() < 0.9)
        s = bool(rng.random() < P * 0.7)
        cm = bool(rng.integers(0, 2))
        sampled = bool(rng.random() < 0.3)
        acc = bool(rng.integers(0, 2))
        has_tok = bool(rng.random() < 0.7)
        g = float(rng.integers(1, 300)) if has_tok else np.nan
        e = int(rng.integers(1, 200))
        logs[r].emit(_telemetry_row(
            P, s if know else None, cm, acc if sampled else None,
            int(g) if has_tok else None, e))
        for k, v in zip(cols, (r, P, know, s, cm, sampled, acc, g, e)):
            cols[k].append(v)
    batch = online_calibration_batch(
        n_rows, cols["row"], cols["P"], cols["has"], cols["succ"],
        committed=cols["comm"], tier3_sampled=cols["t3s"],
        tier3_accept=cols["t3a"], tokens_generated=cols["gen"],
        output_tokens_est=cols["est"], quarters_since_lambda_refresh=1)
    for r in range(n_rows):
        ref = online_calibration(logs[r], quarters_since_lambda_refresh=1)
        got = batch[r]
        assert len(got.buckets) == len(ref.buckets)
        for gb, rb in zip(got.buckets, ref.buckets):
            assert gb.midpoint == rb.midpoint
            assert gb.empirical_rate == rb.empirical_rate      # bitwise
            assert gb.n == rb.n
            assert gb.within_ci == rb.within_ci
        assert got.monotonic_overprediction == ref.monotonic_overprediction
        assert got.tier2_false_accept_rate == ref.tier2_false_accept_rate
        assert got.tier2_needs_tightening == ref.tier2_needs_tightening
        assert got.token_cov == ref.token_cov                  # bitwise
        assert got.uncertain_cost == ref.uncertain_cost
        assert got.lambda_refresh_due == ref.lambda_refresh_due


def test_online_calibration_batch_empty_signals():
    rep = online_calibration_batch(2, [0], [0.55], [True], [True])[0]
    assert rep.tier2_false_accept_rate is None
    assert rep.token_cov is None and not rep.uncertain_cost
    assert not rep.lambda_refresh_due


# ---------------------------------------------------------------------------
# fused Pallas tick (kernels.online_tick behind use_fused_tick) and the
# empty-settle dispatch skip
# ---------------------------------------------------------------------------
def test_fused_tick_defaults_off_and_matches_default_bitwise():
    """The fused settle+gate+drift kernel is opt-in (flag defaults off)
    and, when on, is numerically invisible: every decision field, the
    posterior table, the telemetry ring and the drift counters match the
    default XLA tick bitwise-f64 across a mixed tick stream."""
    with enable_x64():
        a = _service(n_rows=8)
        assert a.use_fused_tick is False
        b = _service(n_rows=8, use_fused_tick=True)
        rng = np.random.default_rng(7)
        for t in range(4):
            req = _random_requests(rng, 24, 8)
            outs = [(int(r), bool(s)) for r, s in zip(
                rng.integers(0, 8, 5), rng.integers(0, 2, 5))]
            da = _tick(a, req, outcomes=outs, check_drift=(t % 2 == 1))
            db = _tick(b, req, outcomes=outs, check_drift=(t % 2 == 1))
            np.testing.assert_array_equal(da.EV_usd, db.EV_usd)
            np.testing.assert_array_equal(da.threshold_usd, db.threshold_usd)
            np.testing.assert_array_equal(da.margin_usd, db.margin_usd)
            np.testing.assert_array_equal(da.speculate, db.speculate)
            np.testing.assert_array_equal(
                da.drift_triggered, db.drift_triggered)
        np.testing.assert_array_equal(
            a.posterior_snapshot(), b.posterior_snapshot())
        np.testing.assert_array_equal(np.asarray(a._tel), np.asarray(b._tel))
        np.testing.assert_array_equal(a.breach_runs(), b.breach_runs())


def test_fused_tick_lower_bound_flags_match():
    """§7.5 tier through the fused kernel: flags must agree exactly; EV
    inherits only the in-kernel-betainc vs XLA-custom-call allowance."""
    with enable_x64():
        a = _service(n_rows=8)
        b = _service(n_rows=8, use_fused_tick=True)
        rng = np.random.default_rng(11)
        req = _random_requests(rng, 32, 8)
        da = _tick(a, req, use_lower_bound=True)
        db = _tick(b, req, use_lower_bound=True)
        np.testing.assert_array_equal(da.speculate, db.speculate)
        np.testing.assert_allclose(da.EV_usd, db.EV_usd, rtol=1e-9)


def test_fused_tick_rollout_falls_back_to_xla():
    """Rollout ticks aren't fused: a fused-enabled service must answer
    them through the default executable, identically to a default
    service (a silent fused dispatch would diverge or crash here)."""
    with enable_x64():
        a = _service(n_rows=4)
        b = _service(n_rows=4, use_fused_tick=True)
        rng = np.random.default_rng(5)
        row = (np.arange(8) % 4).astype(np.int32)
        reqs = np.zeros((8, 7), np.float64)
        reqs[:, 0] = rng.uniform(0, 1, 8)
        reqs[:, 1] = rng.uniform(1e-3, 0.5, 8)
        reqs[:, 2] = rng.uniform(0.05, 4.0, 8)
        reqs[:, 3], reqs[:, 4] = 32, 160
        reqs[:, 5], reqs[:, 6] = 3e-6, 15e-6
        da = a.tick_packed(row, reqs, use_rollout=True, check_drift=True)
        db = b.tick_packed(row, reqs, use_rollout=True, check_drift=True)
        np.testing.assert_array_equal(da.EV_usd, db.EV_usd)
        np.testing.assert_array_equal(da.speculate, db.speculate)
        np.testing.assert_array_equal(
            a.posterior_snapshot(), b.posterior_snapshot())


def test_fused_tick_on_tpu_refuses_f64_state(monkeypatch):
    """Mosaic has no f64: a fused-tick service over an x64 (f64) table
    on the TPU raises at its first tick instead of quietly dropping back
    to the XLA executable or casting the table down."""
    from repro.kernels import ops

    with enable_x64():
        svc = _service(n_rows=4, use_fused_tick=True)
        req = _random_requests(np.random.default_rng(2), 4, 4)
        monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
        with pytest.raises(TypeError, match="float64"):
            _tick(svc, req)


def test_empty_settle_bucket_skipped_at_dispatch():
    """An all-padding settle bucket is substituted with the S=0 bucket
    before dispatch (S is part of the trace key, so this skips a whole
    scan trace + its per-tick cost), counted, and bitwise invisible."""
    with enable_x64():
        a = _service(n_rows=4)
        b = _service(n_rows=4)
        rng = np.random.default_rng(3)
        row = (np.arange(8) % 4).astype(np.int32)
        reqs = np.zeros((8, 7), np.float64)
        reqs[:, 0] = rng.uniform(0, 1, 8)
        reqs[:, 1] = rng.uniform(1e-3, 0.5, 8)
        reqs[:, 2] = rng.uniform(0.05, 4.0, 8)
        reqs[:, 3], reqs[:, 4] = 32, 160
        reqs[:, 5], reqs[:, 6] = 3e-6, 15e-6
        pad_row = np.full(6, -1, np.int32)
        pad_x = np.zeros(6, np.float64)
        da = a.tick_packed(row, reqs, out_row=pad_row, out_x=pad_x)
        db = b.tick_packed(row, reqs)
        assert a.empty_settles_skipped == 1
        assert b.empty_settles_skipped == 0
        np.testing.assert_array_equal(da.EV_usd, db.EV_usd)
        np.testing.assert_array_equal(da.speculate, db.speculate)
        np.testing.assert_array_equal(
            a.posterior_snapshot(), b.posterior_snapshot())
        np.testing.assert_array_equal(np.asarray(a._tel), np.asarray(b._tel))
        # a bucket with any real outcome must still dispatch the settle
        real_row = np.array([0, -1, -1, -1], np.int32)
        real_x = np.array([1.0, 0.0, 0.0, 0.0])
        a.tick_packed(row, reqs, out_row=real_row, out_x=real_x)
        assert a.empty_settles_skipped == 1
