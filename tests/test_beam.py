"""Top-k beam speculation engine (repro.core.beam): the D4 generalization
must collapse bitwise-f64 onto every existing single-candidate path at
``width == 1`` — scalar ``decision.evaluate``, the fused ``d4_gate``, the
fleet replay and the online tick — before any wider-beam claim counts.
``width > 1`` is pinned against the pure-numpy ``reference_beam_replay``
twin, and the §7.6 self-limiting closed form extends to the critical-k
*surface* k_crit(alpha, w) = w (L + C) / ((w + 1 - alpha) C) exactly, in
the style of tests/test_self_limiting.py."""
import dataclasses

import numpy as np
import pytest
from jax import enable_x64

from repro.core import (
    DependencyType,
    Edge,
    Operation,
    PlannerParams,
    Workflow,
    beam_critical_k,
    beam_evaluate,
    beam_replay,
    expected_beam_waste,
    expected_speculation_waste,
    fleet_replay,
    hit_rank_from_success,
    lower_workflow,
    reference_beam_replay,
)
from repro.core.batch_decision import (
    beam_counterfactual_grid,
    beam_gate,
    counterfactual_grid,
    critical_k_grid,
    critical_k_surface,
    d4_gate,
)
from repro.core.beam import BeamDecisionResult, validate_confidences
from repro.core.decision import Decision, DecisionInputs, critical_k, evaluate
from repro.core.online import OnlineDecisionService
from repro.core.posterior import BetaPosterior
from repro.core.predictor import TemplatePredictor
from repro.core.pricing import TwoRateTokenCost

# the established fleet-parity allowance: everything contraction-free is
# compared bitwise; EV / threshold / waste (products feeding adds that
# XLA may fuse into FMAs) to 1 ULP
ULP = dict(rtol=1e-13, atol=1e-16)

GRID_ALPHAS = np.array([0.0, 0.5, 0.9])
GRID_LAMS = np.array([0.01, 0.08, 0.05])


def _inputs(P=0.7, alpha=0.5, lam=0.01, lat=5.0, in_tok=500, out_tok=1000,
            in_p=3e-6, out_p=15e-6, P_lb=None):
    return DecisionInputs(
        P=P, alpha=alpha, lambda_usd_per_s=lam, latency_seconds=lat,
        input_tokens=in_tok, output_tokens=out_tok, input_price=in_p,
        output_price=out_p, P_lower_bound=P_lb)


# ------------------------------------------------------------ scalar rule
class TestScalarRule:
    def test_w1_bitwise_equals_classic_evaluate(self):
        """width=1 with one certain candidate IS the classic rule —
        bitwise-f64 on every float field, across a parameter sweep."""
        rng = np.random.default_rng(0)
        for _ in range(200):
            inp = _inputs(
                P=float(rng.uniform(0, 1)), alpha=float(rng.uniform(0, 1)),
                lam=float(rng.uniform(1e-4, 0.5)),
                lat=float(rng.uniform(0.01, 5.0)),
                in_tok=int(rng.integers(1, 2000)),
                out_tok=float(rng.uniform(1, 2000)),
                in_p=float(rng.uniform(1e-8, 1e-4)),
                out_p=float(rng.uniform(1e-8, 1e-4)))
            ref = evaluate(inp)
            got = beam_evaluate(inp, (1.0,), 1)
            assert got.decision == ref.decision
            for f in ("EV_usd", "threshold_usd", "C_spec_usd",
                      "L_value_usd", "P_used"):
                assert getattr(got, f) == getattr(ref, f), f
            assert got.w_eff == 1
            assert got.launched == (1 if ref.decision == Decision.SPECULATE
                                    else 0)

    def test_w1_lower_bound_bitwise(self):
        inp = _inputs(P=0.9, P_lb=0.42)
        ref = evaluate(inp, use_lower_bound=True)
        got = beam_evaluate(inp, (1.0,), 1, use_lower_bound=True)
        assert (got.EV_usd, got.P_used) == (ref.EV_usd, ref.P_used)
        with pytest.raises(ValueError):
            beam_evaluate(_inputs(P_lb=None), (1.0,), 1,
                          use_lower_bound=True)

    def test_marginal_rule_trims_uneconomic_tail(self):
        """Candidates are admitted while p_j (L + C) - C >= 0; a weak
        tail candidate is excluded even when the width allows it."""
        # C = 0.0165, L_value = 0.05: candidate needs p_j >= C/(L+C) ~ 0.2481
        inp = _inputs(P=1.0, alpha=0.0)
        res = beam_evaluate(inp, (0.5, 0.3, 0.1), 3)
        assert res.included == (True, True, False)
        assert res.w_eff == 2
        assert res.P_used == pytest.approx(0.8)
        # the admitted beam is a prefix
        assert list(res.included) == sorted(res.included, reverse=True)

    def test_first_candidate_unconditional(self):
        """Candidate 1 is admitted even when its own marginal is
        negative (that case is the classic rule's WAIT territory)."""
        res = beam_evaluate(_inputs(P=0.05), (0.9, 0.1), 2)
        assert res.w_eff == 1
        assert res.included == (True, False)
        assert res.decision == Decision.WAIT
        assert res.launched == 0 and res.expected_losers == 0.0

    def test_width_caps_admission(self):
        inp = _inputs(P=1.0, alpha=0.0)
        r1 = beam_evaluate(inp, (0.5, 0.3, 0.1), 1)
        r2 = beam_evaluate(inp, (0.5, 0.3, 0.1), 2)
        assert (r1.w_eff, r2.w_eff) == (1, 2)
        assert r2.P_used > r1.P_used

    def test_shared_budget_ev(self):
        """EV = P_w L - (w_eff - P_w) C with P_w the beam-cumulative
        commit probability."""
        inp = _inputs(P=1.0, alpha=0.0)
        res = beam_evaluate(inp, (0.5, 0.3), 2)
        C, L = res.C_spec_usd, res.L_value_usd
        assert res.EV_usd == pytest.approx(0.8 * L - (2 - 0.8) * C)
        assert res.expected_losers == pytest.approx(2 - 0.8)

    def test_confidence_validation(self):
        inp = _inputs()
        with pytest.raises(ValueError):
            beam_evaluate(inp, (), 1)                    # empty
        with pytest.raises(ValueError):
            beam_evaluate(inp, (0.3, 0.5), 2)            # not sorted
        with pytest.raises(ValueError):
            beam_evaluate(inp, (0.8, 0.7), 2)            # sums past 1
        with pytest.raises(ValueError):
            beam_evaluate(inp, (1.2,), 1)                # out of [0, 1]
        with pytest.raises(ValueError):
            beam_evaluate(inp, (0.5,), 0)                # width < 1
        assert validate_confidences([0.5, 0.5]) == (0.5, 0.5)


# ----------------------------------------------------- §7.6 critical surface
# same synthetic edges as tests/test_self_limiting.py: k_crit lands at
# different, non-integer places per edge
EDGES = [
    (0.8, 0.08, 500, 800, 3e-6, 15e-6),
    (2.5, 0.08, 200, 400, 3e-6, 15e-6),
    (0.5, 0.01, 1500, 2000, 3e-6, 15e-6),
    (1.0, 0.02, 100, 150, 1e-6, 5e-6),
    (1.2, 0.02, 800, 1200, 2e-6, 10e-6),
]
KS = np.arange(1, 33)
ALPHAS = (0.0, 0.3, 0.5, 0.9, 1.0)
WIDTHS = (1, 2, 3, 4, 8)


def _edge_terms(edge):
    L, lam, in_tok, out_tok, in_p, out_p = edge
    return lam * L, in_tok * in_p + out_tok * out_p


class TestCriticalSurface:
    @pytest.mark.parametrize("alpha", ALPHAS)
    @pytest.mark.parametrize("width", WIDTHS)
    def test_beam_verdict_matches_surface_indicator(self, alpha, width):
        """Under the uniform prior (k branches, conf_j = 1/k, w <= k) the
        beam SPECULATE verdict is exactly the closed-form
        k <= k_crit(alpha, w) indicator — the §7.6 self-limiting law in
        both axes."""
        for edge in EDGES:
            L, lam, in_tok, out_tok, in_p, out_p = edge
            Lv, C = _edge_terms(edge)
            kc = beam_critical_k(Lv, C, alpha, width)
            assert abs(kc - round(kc)) > 1e-6, \
                "test edge parks k_crit on an integer; pick another edge"
            for k in KS[KS >= width]:
                res = beam_evaluate(
                    _inputs(P=1.0, alpha=alpha, lam=lam, lat=L,
                            in_tok=in_tok, out_tok=out_tok, in_p=in_p,
                            out_p=out_p),
                    (1.0 / k,) * int(k), width)
                spec = res.decision == Decision.SPECULATE
                if k <= (Lv + C) / C:
                    # marginal rule admits the full beam
                    assert res.w_eff == width
                    assert spec == (k <= kc)
                else:
                    # prefix rule trims to one candidate; the classic
                    # (always tighter) w=1 bound takes over
                    assert res.w_eff == 1
                    assert spec == (k <= critical_k(Lv, C, alpha))
                    assert not spec and k > kc

    @pytest.mark.parametrize("alpha", ALPHAS)
    def test_speculation_rate_self_limits_in_both_axes(self, alpha):
        """Population speculation rate is non-increasing in branching k
        at every width, non-decreasing in width at every k, and reaches
        zero inside the sweep at every width (the ceiling (L+C)/C is
        finite)."""
        rates = np.zeros((len(WIDTHS), len(KS)))
        for wi, w in enumerate(WIDTHS):
            for ki, k in enumerate(KS):
                decs = []
                for edge in EDGES:
                    if k < w:
                        continue
                    L, lam, in_tok, out_tok, in_p, out_p = edge
                    res = beam_evaluate(
                        _inputs(P=1.0, alpha=alpha, lam=lam, lat=L,
                                in_tok=in_tok, out_tok=out_tok,
                                in_p=in_p, out_p=out_p),
                        (1.0 / k,) * int(k), int(w))
                    decs.append(res.decision == Decision.SPECULATE)
                rates[wi, ki] = np.mean(decs) if decs else np.nan
        for wi in range(len(WIDTHS)):
            row = rates[wi][~np.isnan(rates[wi])]
            assert all(a >= b for a, b in zip(row, row[1:]))
            assert row[-1] == 0.0                    # k=32 self-limits
        # wider beams keep speculating at higher k (monotone in w)
        valid = ~np.isnan(rates).any(0)
        assert (np.diff(rates[:, valid], axis=0) >= 0.0).all()
        assert rates[-1, valid].max() >= rates[0, valid].max()

    def test_closed_form_properties(self):
        for edge in EDGES:
            Lv, C = _edge_terms(edge)
            for alpha in ALPHAS:
                # w=1 reduces to the classic critical_k
                assert beam_critical_k(Lv, C, alpha, 1) == pytest.approx(
                    critical_k(Lv, C, alpha), rel=1e-12)
                kcs = [beam_critical_k(Lv, C, alpha, w)
                       for w in range(1, 200)]
                # non-decreasing up to float wobble (exactly constant in
                # exact arithmetic at alpha = 1)
                assert all(a <= b + 1e-12 * abs(b)
                           for a, b in zip(kcs, kcs[1:]))
                assert all(kc <= (Lv + C) / C + 1e-12 for kc in kcs)
        with pytest.raises(ValueError):
            beam_critical_k(1.0, 0.0, 0.5, 2)
        with pytest.raises(ValueError):
            beam_critical_k(1.0, 0.1, 0.5, 0)

    def test_surface_grid_matches_scalar_closed_form(self):
        """critical_k_surface == scalar beam_critical_k over the full
        (width, alpha) cross; the w=1 row is critical_k_grid (f64)."""
        with enable_x64():
            alphas = np.asarray(ALPHAS)
            widths = np.asarray(WIDTHS)
            for edge in EDGES:
                Lv, C = _edge_terms(edge)
                surf = critical_k_surface(Lv, C, alphas, widths)
                assert surf.shape == (len(WIDTHS), len(ALPHAS))
                ref = np.array([[beam_critical_k(Lv, C, a, int(w))
                                 for a in alphas] for w in widths])
                np.testing.assert_allclose(surf, ref, rtol=1e-9, atol=0.0)
                np.testing.assert_allclose(
                    surf[0], critical_k_grid(Lv, C, alphas),
                    rtol=1e-12, atol=0.0)
        with pytest.raises(ValueError):
            critical_k_surface(0.05, 0.0165, alphas, [0])


# ------------------------------------------------------------- batch gate
class TestBatchGate:
    def test_beam_gate_w1_bitwise_equals_d4_gate(self):
        with enable_x64():
            rng = np.random.default_rng(1)
            B = 64
            P = rng.uniform(0, 1, B)
            args = (rng.uniform(0, 1, B), rng.uniform(1e-4, 0.5, B),
                    rng.uniform(0.01, 5.0, B),
                    rng.integers(1, 2000, B).astype(float),
                    rng.uniform(1, 2000, B), rng.uniform(1e-8, 1e-4, B),
                    rng.uniform(1e-8, 1e-4, B))
            ref = d4_gate(P, *args)
            got = beam_gate(P, np.ones((B, 1)), np.ones(B, np.int32),
                            *args)
            for r, g in zip(ref, got[:5]):
                np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
            np.testing.assert_array_equal(np.asarray(got[5]), np.ones(B))
            np.testing.assert_array_equal(np.asarray(got[6]), P)

    def test_beam_counterfactual_grid_w1_matches_classic(self):
        with enable_x64():
            rng = np.random.default_rng(2)
            N = 40
            P = rng.uniform(0, 1, N)
            lat = rng.uniform(0.1, 4.0, N)
            cost = rng.uniform(1e-4, 5e-2, N)
            ref = counterfactual_grid(P, lat, cost, GRID_ALPHAS, GRID_LAMS)
            got = beam_counterfactual_grid(
                P, np.ones((N, 1)), lat, cost, GRID_ALPHAS, GRID_LAMS, [1])
            assert set(got) == set(ref)
            for k in ref:
                np.testing.assert_allclose(got[k][0], ref[k], **ULP)

    def test_beam_counterfactual_grid_width_axis(self):
        """A wider beam never lowers the speculate fraction and never
        lowers the expected waste (more launched candidates)."""
        with enable_x64():
            rng = np.random.default_rng(3)
            N = 30
            conf = np.sort(rng.dirichlet(np.ones(3), N), 1)[:, ::-1] * 0.9
            out = beam_counterfactual_grid(
                rng.uniform(0, 1, N), conf, rng.uniform(0.1, 4.0, N),
                rng.uniform(1e-4, 5e-2, N), GRID_ALPHAS, GRID_LAMS,
                [1, 2, 3])
            assert out["speculate_fraction"].shape == (3, 3, 3)
            assert (np.diff(out["speculate_fraction"], axis=0)
                    >= -1e-15).all()
            assert (np.diff(out["expected_waste_usd"], axis=0)
                    >= -1e-15).all()
        with pytest.raises(ValueError):
            beam_counterfactual_grid(
                [0.5], [[0.3, 0.5]], [1.0], [0.01], GRID_ALPHAS,
                GRID_LAMS, [1])


# ------------------------------------------------------------ fleet replay
def build_lowered(beam_confidences=None, use_lower_bound=False):
    """4-op DAG with two speculation edges (one non-streaming downstream,
    one with predictor cost) — the shape the parity suite sweeps."""
    wf = Workflow("beam-dag")
    spec = dict(lat=(2.0, 3.0, 1.5, 2.5), in_tok=(100, 400, 800, 600),
                out_tok=(200, 900, 500, 1200),
                streams=(True, True, True, False))
    for i in range(4):
        wf.add_op(Operation(
            f"n{i}", run=lambda *a: "o", latency_est_s=spec["lat"][i],
            input_tokens_est=spec["in_tok"][i],
            output_tokens_est=spec["out_tok"][i],
            streams=spec["streams"][i], metadata={"input": f"in{i}"}))
    wf.add_edge(Edge("n0", "n1", dep_type=DependencyType.CONDITIONAL_OUTPUT))
    wf.add_edge(Edge("n0", "n2", enabled=False))
    wf.add_edge(Edge("n2", "n3",
                     dep_type=DependencyType.LIST_OUTPUT_VARIABLE_LENGTH))
    wf.freeze()
    params = PlannerParams(alpha=0.5, lambda_usd_per_s=0.01,
                           use_lower_bound=use_lower_bound)
    preds = {
        ("n0", "n1"): TemplatePredictor(template=lambda i, p=None: "x",
                                        cost_estimate_s=0.05),
        ("n2", "n3"): TemplatePredictor(template=lambda i, p=None: "x"),
    }
    return lower_workflow(wf, params, predictors=preds,
                          beam_confidences=beam_confidences)


SHARED_STATS = [
    "makespan_s", "total_cost_usd", "waste_usd", "launched", "committed",
    "EV_usd", "threshold_usd", "speculate", "edge_launched",
    "edge_committed", "edge_waste_usd", "start_s", "finish_s",
    "post_alpha", "post_beta",
]


def _hit_ranks(E, V, seed=7):
    rng = np.random.default_rng(seed)
    return rng.integers(-1, 3, (E, V)).astype(np.int32)


class TestFleetParity:
    @pytest.mark.parametrize("use_lower_bound", [False, True])
    def test_w1_bitwise_equals_fleet_replay(self, use_lower_bound):
        """The width=1 slice of the beam replay is bitwise-f64 identical
        to fleet_replay on every shared statistic, in both the posterior-
        mean and §7.5 lower-bound gating modes — asserted before the
        benchmark may claim any beam timing."""
        with enable_x64():
            lowered = build_lowered(use_lower_bound=use_lower_bound)
            E, V = 6, lowered.n_ops
            hit = _hit_ranks(E, V)
            success = hit == 0
            ref = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS)
            rep = beam_replay(lowered, hit, GRID_ALPHAS, GRID_LAMS, [1])
            sl = rep.width_slice(0)
            for k in SHARED_STATS:
                np.testing.assert_array_equal(
                    sl[k], getattr(ref, k), err_msg=k)
            # candidate attribution degenerates to the edge counts
            np.testing.assert_array_equal(sl["launched_candidates"],
                                          sl["launched"].astype(float))
            np.testing.assert_array_equal(sl["w_eff"][sl["speculate"]], 1)

    def test_default_conf_every_width_replays_classic(self):
        """Without beam_confidences the lowering carries one certain
        candidate, so every width slice equals the classic engine."""
        with enable_x64():
            lowered = build_lowered()
            hit = _hit_ranks(5, lowered.n_ops, seed=11)
            ref = fleet_replay(lowered, hit == 0, GRID_ALPHAS, GRID_LAMS)
            rep = beam_replay(lowered, hit, GRID_ALPHAS, GRID_LAMS,
                              [1, 2, 4])
            for wi in range(3):
                sl = rep.width_slice(wi)
                for k in SHARED_STATS:
                    np.testing.assert_array_equal(
                        sl[k], getattr(ref, k), err_msg=f"{k}@w{wi}")

    def test_wider_beam_matches_reference_twin(self):
        """width > 1 against the pure-numpy reference: decisions, counts,
        ranks and event times bitwise; EV / waste to 1 ULP."""
        with enable_x64():
            confs = {("n0", "n1"): (0.55, 0.25, 0.1),
                     ("n2", "n3"): (0.5, 0.3)}
            lowered = build_lowered(beam_confidences=confs)
            E, V = 6, lowered.n_ops
            hit = _hit_ranks(E, V, seed=13)
            widths = [1, 2, 3]
            rep = beam_replay(lowered, hit, GRID_ALPHAS, GRID_LAMS, widths)
            ref = reference_beam_replay(lowered, hit, GRID_ALPHAS,
                                        GRID_LAMS, widths)
            exact = ("speculate", "w_eff", "edge_launched",
                     "edge_committed", "launched", "committed",
                     "launched_candidates", "cancelled_candidates",
                     "start_s", "finish_s", "makespan_s", "post_alpha",
                     "post_beta")
            for k in exact:
                np.testing.assert_array_equal(
                    getattr(rep, k), ref[k], err_msg=k)
            for k in ("EV_usd", "threshold_usd", "edge_waste_usd",
                      "waste_usd", "total_cost_usd"):
                np.testing.assert_allclose(
                    getattr(rep, k), ref[k], err_msg=k, **ULP)

    def test_hit_rank_gates_commit_on_admitted_prefix(self):
        """A rank-1 hit commits only when the beam actually launched at
        least two candidates — widening the beam converts a tier failure
        into a commit on exactly those episodes."""
        with enable_x64():
            confs = {("n0", "n1"): (0.5, 0.4),
                     ("n2", "n3"): (0.5, 0.4)}
            lowered = build_lowered(beam_confidences=confs)
            V = lowered.n_ops
            hit = np.ones((4, V), np.int32)     # the runner-up always hits
            rep = beam_replay(lowered, hit, [0.5], [0.08], [1, 2])
            edge = np.asarray(lowered.has_edge) & np.asarray(
                lowered.has_pred)
            launched = rep.edge_launched[..., edge]
            committed = rep.edge_committed[..., edge]
            assert launched.any()
            # width 1 never commits a rank-1 hit; width 2 commits wherever
            # the marginal rule admitted the runner-up
            assert not committed[:, 0].any()
            w2 = rep.w_eff[..., edge][:, 1]
            assert committed[:, 1].sum() == (launched[:, 1] & (w2 >= 2)).sum()
            assert committed[:, 1].any()
            # every launched loser is billed: cancelled = launched - won
            np.testing.assert_array_equal(
                rep.cancelled_candidates,
                rep.launched_candidates
                - rep.committed)

    def test_hit_rank_from_success_and_validation(self):
        np.testing.assert_array_equal(
            hit_rank_from_success(np.array([[True, False]])),
            np.array([[0, -1]], np.int32))
        lowered = build_lowered()
        E, V = 3, lowered.n_ops
        ok = np.zeros((E, V), bool)
        # bool success arrays are accepted as the degenerate case
        rep = beam_replay(lowered, ok, [0.5], [0.01], [1])
        assert not rep.committed.any()
        with pytest.raises(ValueError):
            beam_replay(lowered, np.zeros((E, V + 1), np.int32),
                        [0.5], [0.01], [1])
        with pytest.raises(ValueError):
            beam_replay(lowered, ok, [0.5], [0.01], [])
        with pytest.raises(ValueError):
            beam_replay(lowered, ok, [0.5], [0.01], [0])
        with pytest.raises(ValueError):
            beam_replay(lowered, ok, [0.5], [0.01], [1.5])

    def test_ep_mask_freezes_masked_episodes(self):
        with enable_x64():
            lowered = build_lowered()
            hit = _hit_ranks(6, lowered.n_ops, seed=17)
            mask = np.array([True, False, True, True, False, True])
            full = beam_replay(lowered, hit, [0.5], [0.08], [1, 2])
            part = beam_replay(lowered, hit, [0.5], [0.08], [1, 2],
                               ep_mask=mask)
            assert not part.edge_launched[~mask].any()
            # masked episodes carry the prior forward unchanged
            np.testing.assert_array_equal(part.post_alpha[1],
                                          part.post_alpha[0])
            # pareto aggregation skips masked rows
            np.testing.assert_array_equal(
                part.pareto()["launched"],
                part.launched[mask].sum(0))
            assert full.pareto()["launched"].sum() >= \
                part.pareto()["launched"].sum()


# ------------------------------------------------------------ online tick
class TestOnlineBeam:
    def _service(self):
        svc = OnlineDecisionService()
        for i, p in enumerate((0.7, 0.35, 0.9)):
            svc.register_edge(("u", f"v{i}"),
                              posterior=BetaPosterior.from_prior_mean(p))
        return svc

    REQ = dict(alpha=0.4, lambda_usd_per_s=0.08, latency_s=2.0,
               input_tokens=500, output_tokens=1000, input_price=3e-6,
               output_price=15e-6)

    def test_decide_beam_bitwise_equals_beam_evaluate(self):
        with enable_x64():
            svc = self._service()
            conf = (0.6, 0.25, 0.1)
            for row, p in enumerate((0.7, 0.35, 0.9)):
                for width in (1, 2, 3):
                    got = svc.decide_beam(row=row, confidences=conf,
                                          width=width, **self.REQ)
                    ref = beam_evaluate(
                        _inputs(P=p, alpha=0.4, lam=0.08, lat=2.0),
                        conf, width)
                    assert isinstance(got, BeamDecisionResult)
                    assert got.decision == ref.decision
                    for f in ("EV_usd", "threshold_usd", "C_spec_usd",
                              "L_value_usd", "P_used"):
                        assert getattr(got, f) == getattr(ref, f), (f, width)
                    assert got.launched == ref.launched

    def test_tick_mixed_widths_and_telemetry_launched(self):
        with enable_x64():
            svc = self._service()
            bc = np.array([[0.6, 0.3, 0.1], [0.9, 0.05, 0.0]])
            d = svc.tick([0, 2], beam_confidences=bc, beam_width=[3, 2],
                         **self.REQ)
            assert d.launched.shape == (2,)
            # per-row reference through the scalar rule
            for i, (row_p, conf, w) in enumerate(
                    [(0.7, (0.6, 0.3, 0.1), 3),
                     (0.9, (0.9, 0.05, 0.0), 2)]):
                ref = beam_evaluate(
                    _inputs(P=row_p, alpha=0.4, lam=0.08, lat=2.0),
                    conf, w)
                assert bool(d.speculate[i]) == (
                    ref.decision == Decision.SPECULATE)
                assert (int(d.launched[i]) == ref.launched
                        or not d.speculate[i])
                assert float(d.P_used[i]) == ref.P_used
            tb = svc.drain_telemetry()
            launched = tb.fields["launched"]
            spec = tb.fields["speculate"].astype(bool)
            assert (launched[spec] >= 1).all()
            np.testing.assert_array_equal(
                launched, np.asarray(d.launched, float))

    def test_single_candidate_tick_unchanged(self):
        """A beam tick with one certain candidate answers exactly like
        the classic tick (same posterior, same request)."""
        with enable_x64():
            svc = self._service()
            ref = svc.tick([0, 1, 2], **self.REQ)
            svc2 = self._service()
            got = svc2.tick([0, 1, 2],
                            beam_confidences=np.ones((3, 1)), **self.REQ)
            for f in ("EV_usd", "threshold_usd", "P_used", "speculate"):
                np.testing.assert_array_equal(getattr(got, f),
                                              getattr(ref, f), err_msg=f)
            # classic ticks attribute one launched candidate per served row
            np.testing.assert_array_equal(np.asarray(ref.launched),
                                          np.asarray(got.launched))

    def test_beam_request_validation(self):
        svc = self._service()
        with pytest.raises(ValueError):
            svc.tick([0], beam_width=2, **self.REQ)
        with pytest.raises(ValueError):
            svc.tick([0], beam_confidences=np.array([[0.3, 0.5]]),
                     **self.REQ)
        with pytest.raises(ValueError):
            svc.tick([0], beam_confidences=np.array([[0.8, 0.7]]),
                     **self.REQ)
        with pytest.raises(ValueError):
            svc.tick([0, 1], beam_confidences=np.ones((1, 1)), **self.REQ)
        with pytest.raises(ValueError):
            svc.tick([0], beam_confidences=np.ones((1, 1)), beam_width=0,
                     **self.REQ)


# ------------------------------------------------------------ §9.3 waste
class TestExpectedBeamWaste:
    CM = TwoRateTokenCost(3e-6, 15e-6)

    def test_launched_one_is_classic_waste(self):
        for P in (0.0, 0.31, 1.0):
            assert expected_beam_waste(P, 1, self.CM, 500, 1000) == \
                expected_speculation_waste(P, self.CM, 500, 1000)

    def test_scales_with_losers_and_rho(self):
        w = expected_beam_waste(0.8, 3, self.CM, 500, 1000, rho=0.5)
        assert w == pytest.approx((3 - 0.8) * (500 * 3e-6 + 0.5 * 1000 * 15e-6))
        full = expected_beam_waste(0.8, 3, self.CM, 500, 1000,
                                   streaming=False)
        assert full > w                      # no cancel -> full C_out
        assert expected_beam_waste(0.0, 0, self.CM, 500, 1000) == 0.0

    def test_validation(self):
        with pytest.raises(ValueError):
            expected_beam_waste(0.5, -1, self.CM, 500, 1000)
        with pytest.raises(ValueError):
            expected_beam_waste(0.5, 0, self.CM, 500, 1000)   # P > launched
        with pytest.raises(ValueError):
            expected_beam_waste(1.2, 2, self.CM, 500, 1000)
        with pytest.raises(ValueError):
            expected_beam_waste(0.5, 2, self.CM, 500, 1000, rho=1.5)
