"""Mini-hypothesis property sweep over the §12 five-stage calibration
pipeline (offline replay -> shadow -> canary -> online -> drift):

* ``shadow_mode`` never mutates the live posterior (§12.2 zero exposure
  now includes the belief state);
* ``canary`` arm promotion is monotone (upward-closed) in the observed
  speculation success rate;
* ``online_calibration`` bucket posteriors recover a planted p* within
  the §7.5 credible bound;
* ``TokenEstimator.uncertain_cost`` flips exactly at the documented CoV
  threshold (strict inequality);
* the million-row ``offline_replay`` reroute (log-axis-sharded grid)
  matches the default bucketed path.

Runs against the real ``hypothesis`` when present, else the
tests/_mini_hypothesis.py shim (see conftest.py).
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.calibration import (
    SequentialLogRecord,
    TokenEstimator,
    canary,
    offline_replay,
    online_calibration,
    shadow_mode,
)
from repro.core.posterior import BetaPosterior
from repro.core.predictor import HistoricalModalPredictor
from repro.core.telemetry import TelemetryLog

from test_calibration import make_row

MATCH = "billing"
MISS = "zzz-unrelated-output-999"


# ---------------------------------------------------------------- stage 2
class TestShadowModeNeverMutatesLivePosterior:
    @given(p=st.floats(min_value=0.05, max_value=0.95),
           n=st.integers(min_value=0, max_value=120),
           rate=st.floats(min_value=0.0, max_value=1.0))
    @settings(max_examples=25, deadline=None)
    def test_live_posterior_untouched(self, p, n, rate):
        """The caller's (live) posterior is frozen through a whole shadow
        run of any length/outcome mix; the returned shadow copy carries
        exactly the trial count on top of the live belief."""
        rng = np.random.default_rng(1 + n + int(rate * 997))
        live = BetaPosterior.from_prior_mean(p)
        snap = (live.alpha, live.beta, live.successes, live.failures)
        trials = [(MATCH, MATCH) if rng.random() < rate else (MISS, MATCH)
                  for _ in range(n)]
        rep = shadow_mode(("clf", "drafter"), live, trials)
        assert (live.alpha, live.beta,
                live.successes, live.failures) == snap
        assert rep.posterior is not live
        assert rep.posterior.n == live.n + n
        assert rep.trials == n

    def test_shadow_copy_still_learns(self):
        """The non-mutation fix must not freeze the shadow copy itself:
        its mean tracks the trial outcome rate."""
        live = BetaPosterior.from_prior_mean(0.5)
        trials = [(MATCH, MATCH)] * 80 + [(MISS, MATCH)] * 20
        rep = shadow_mode(("clf", "drafter"), live, trials)
        assert rep.posterior.mean == pytest.approx(0.8, abs=0.05)
        assert live.mean == pytest.approx(0.5)


# ---------------------------------------------------------------- stage 3
CONTROL_LAT = 1.6
CONTROL_COST = 0.015
BUDGET = CONTROL_COST + 0.002


def _promote_at(p: float) -> bool:
    """One canary arm synthesized from an observed success rate p:
    committed speculations reclaim upstream wait (latency falls with p),
    failed ones bill waste (cost falls with p)."""
    lat = CONTROL_LAT - 0.8 * p
    cost = CONTROL_COST + (1.0 - p) * 0.005
    rep = canary(
        CONTROL_LAT, CONTROL_COST, {0.5: (lat, cost)}, 0.5,
        P=max(p, 1e-6), C_spec=0.0135, L_upstream_s=0.8,
        lambda_declared=0.08, budget_guardrail_usd=BUDGET,
    )
    return rep.promote


class TestCanaryPromotionMonotone:
    @given(p_a=st.floats(min_value=0.0, max_value=1.0),
           p_b=st.floats(min_value=0.0, max_value=1.0))
    @settings(max_examples=40, deadline=None)
    def test_promotion_upward_closed_in_success_rate(self, p_a, p_b):
        """If an arm at success rate p promotes, every arm at a higher
        success rate (strictly better latency and cost vs the same
        control and budget) promotes too."""
        lo, hi = sorted((p_a, p_b))
        assert (not _promote_at(lo)) or _promote_at(hi)

    def test_promotion_actually_flips(self):
        """The monotone property is non-vacuous: low success rates bust
        the budget guardrail, high ones promote."""
        vals = [_promote_at(p) for p in np.linspace(0.0, 1.0, 101)]
        assert not vals[0] and vals[-1]
        assert vals == sorted(vals)   # exactly one upward flip, no churn


# ---------------------------------------------------------------- stage 4
class TestOnlineCalibrationRecoversPlantedRate:
    @given(p_star=st.floats(min_value=0.15, max_value=0.85))
    @settings(max_examples=12, deadline=None)
    def test_bucket_posterior_credibly_bounds_p_star(self, p_star):
        """Telemetry rows predict P = p* and commit with true rate p*:
        the §12.4 bucket recovers the planted rate, and the §7.5-style
        Beta posterior built from the bucket's (s, f) counts credibly
        bounds p* (99.9% central interval — wide enough that every
        deterministic seed's sampling error sits inside it; n = 400)."""
        n = 400
        rng = np.random.default_rng(int(p_star * 1e6) % (2**31))
        log = TelemetryLog()
        s = 0
        for i in range(n):
            ok = bool(rng.random() < p_star)
            s += int(ok)
            log.emit(make_row(i, P=p_star, committed=ok))
        f = n - s
        rep = online_calibration(log)
        populated = [b for b in rep.buckets if b.n > 0]
        assert len(populated) == 1 and populated[0].n == n
        bucket = populated[0]
        # empirical rate is the planted rate to sampling error (4 sigma)
        sig = np.sqrt(p_star * (1.0 - p_star) / n)
        assert abs(bucket.empirical_rate - p_star) <= 4.0 * sig
        assert bucket.empirical_rate == pytest.approx(s / n)
        # §7.5 credible containment of the planted rate
        post = BetaPosterior(alpha=1.0 + s, beta=1.0 + f)
        lo, hi = post.credible_interval(0.999)
        assert lo <= p_star <= hi
        # a well-calibrated stream must not trip the overprediction flag
        assert not rep.monotonic_overprediction


class TestTokenEstimatorThreshold:
    @given(vals=st.lists(st.floats(min_value=10.0, max_value=3000.0),
                         min_size=2, max_size=40),
           thr=st.floats(min_value=0.05, max_value=2.0))
    @settings(max_examples=40, deadline=None)
    def test_uncertain_cost_flips_exactly_at_cov_threshold(self, vals, thr):
        """uncertain_cost == (cov > cov_threshold), strict: equality at
        the documented threshold does NOT flag, one ULP above does."""
        est = TokenEstimator(cov_threshold=thr)
        for v in vals:
            est.observe(v)
        c = est.cov
        assert c is not None
        assert est.uncertain_cost == (c > thr)

        at = TokenEstimator(cov_threshold=c)
        for v in vals:
            at.observe(v)
        assert not at.uncertain_cost          # cov == threshold: no flag
        if c > 0.0:
            below = TokenEstimator(
                cov_threshold=float(np.nextafter(c, 0.0)))
            for v in vals:
                below.observe(v)
            assert below.uncertain_cost       # threshold one ULP under cov

    def test_under_two_observations_never_uncertain(self):
        est = TokenEstimator(cov_threshold=0.0)
        assert est.cov is None and not est.uncertain_cost
        est.observe(100.0)
        assert est.cov is None and not est.uncertain_cost


# ------------------------------------------------- stage 1 reroute parity
class TestOfflineReplayShardedReroute:
    def test_reroute_matches_default_path(self):
        """Forcing the log-axis-sharded path (tiny shard_threshold) must
        reproduce the default bucketed grid: identical go verdicts and
        default alphas, decision fractions to 1 ULP, expectations to
        float-reorder tolerance."""
        rng = np.random.default_rng(11)
        intents = rng.choice(["billing", "support", "sales"],
                             p=[0.7, 0.2, 0.1], size=300)
        logs = [SequentialLogRecord(
            "email", i, "x", "y", float(rng.uniform(0.5, 3.0)),
            float(rng.uniform(0.005, 0.03))) for i in intents]
        pred = HistoricalModalPredictor()
        pred.observe_many([("email", i) for i in intents])
        base = offline_replay(("clf", "drafter"), logs, {"modal": pred})
        rerouted = offline_replay(("clf", "drafter"), logs,
                                  {"modal": pred}, shard_threshold=50)
        assert rerouted.go == base.go
        assert rerouted.default_alpha == base.default_alpha
        assert rerouted.seeded_prior.alpha == base.seeded_prior.alpha
        assert len(rerouted.grid) == len(base.grid)
        for a, b in zip(rerouted.grid, base.grid):
            assert a.speculate_fraction == pytest.approx(
                b.speculate_fraction, rel=1e-12)
            assert a.expected_latency_s == pytest.approx(
                b.expected_latency_s, rel=1e-12)
            assert a.expected_cost_usd == pytest.approx(
                b.expected_cost_usd, rel=1e-12)
            assert a.expected_waste_usd == pytest.approx(
                b.expected_waste_usd, rel=1e-12, abs=1e-15)

    def test_sharded_grid_segments_share_one_executable(self):
        """Regression (review): the sharded grid buckets its segment
        length to a power of two, so a sweep over many ragged large logs
        — distinct row counts, same segmentation — reuses one compiled
        executable (the same guarantee offline_replay's unsharded branch
        gets from its power-of-two log bucketing), and rho sweeps never
        retrace."""
        from repro.core import batch_decision as bd

        rng = np.random.default_rng(3)
        alphas = np.array([0.0, 0.5, 1.0])
        lams = np.array([0.01, 0.08])
        fn = bd._grid_sharded_exec(None, "fleet")
        fn.clear_cache()
        for n in (197, 230, 256):       # all bucket to Nc = 64 at C = 4
            lat = rng.uniform(0.2, 3.0, n)
            cost = rng.uniform(0.001, 0.03, n)
            for rho in (0.1, 0.5, 0.9):
                bd.counterfactual_grid_sharded(
                    0.6, lat, cost, alphas, lams, rho=rho, segments=4)
        assert fn._cache_size() == 1

    def test_sharded_grid_per_row_rho_and_meshless_axis(self):
        """Regression (review): per-row rho must segment along with its
        rows (it used to broadcast-crash for segments > 1), and a mesh
        without the fleet axis must fall back to the unsharded
        executable instead of raising KeyError."""
        from jax import enable_x64

        from repro.core.batch_decision import (
            counterfactual_grid,
            counterfactual_grid_sharded,
        )
        from repro.launch.mesh import make_host_mesh

        rng = np.random.default_rng(5)
        n = 100
        lat = rng.uniform(0.2, 3.0, n)
        cost = rng.uniform(0.001, 0.03, n)
        rho_rows = rng.uniform(0.0, 1.0, n)
        alphas = np.array([0.0, 0.5, 1.0])
        lams = np.array([0.01, 0.08])
        with enable_x64():
            base = counterfactual_grid(0.62, lat, cost, alphas, lams,
                                       rho=rho_rows)
            for mesh in (None, make_host_mesh()):   # no "fleet" axis
                g = counterfactual_grid_sharded(
                    0.62, lat, cost, alphas, lams, rho=rho_rows,
                    segments=4, mesh=mesh)
                np.testing.assert_array_equal(
                    base["speculate_fraction"], g["speculate_fraction"])
                for k in ("expected_latency_s", "expected_cost_usd",
                          "expected_waste_usd"):
                    np.testing.assert_allclose(
                        base[k], g[k], rtol=1e-12, atol=1e-18,
                        err_msg=k)
