"""Paper §7.6 closed-form self-limiting behavior: under the uniform-mode
prior P = 1/k, the scalar D4 ``decision`` rule SPECULATEs iff
k <= k_crit(alpha) = (L_value + C_spec) / ((2 - alpha) * C_spec) — so the
speculation rate over a population of synthetic edges *falls* with the
upstream branching factor k, exactly where the analytic bound says it
does.  Sweeps k = 1..32 and compares EV / margin against the closed form
to 1e-9."""
import numpy as np
import pytest

from repro.core.batch_decision import critical_k_grid
from repro.core.decision import (
    Decision,
    DecisionInputs,
    critical_k,
    evaluate,
    p_threshold_crossing,
)

KS = np.arange(1, 33)
ALPHAS = (0.0, 0.3, 0.5, 0.9, 1.0)

# synthetic edges: (latency savings L [s], lambda [USD/s], in_tok,
# out_tok, in_price, out_price) — spread so k_crit lands at different,
# non-integer places per edge
EDGES = [
    (0.8, 0.08, 500, 800, 3e-6, 15e-6),
    (2.5, 0.08, 200, 400, 3e-6, 15e-6),
    (0.5, 0.01, 1500, 2000, 3e-6, 15e-6),
    (1.0, 0.02, 100, 150, 1e-6, 5e-6),
    (1.2, 0.02, 800, 1200, 2e-6, 10e-6),
]
# every edge must self-limit inside the k = 1..32 sweep even at the most
# latency-hungry dial, or the rate cannot reach zero (checked in-test)


def _edge_terms(edge):
    L, lam, in_tok, out_tok, in_p, out_p = edge
    C = in_tok * in_p + out_tok * out_p
    return lam * L, C


def _decide(edge, k, alpha) -> "tuple[bool, float, float]":
    L, lam, in_tok, out_tok, in_p, out_p = edge
    res = evaluate(DecisionInputs(
        P=1.0 / k, alpha=alpha, lambda_usd_per_s=lam, latency_seconds=L,
        input_tokens=in_tok, output_tokens=out_tok, input_price=in_p,
        output_price=out_p))
    return (res.decision == Decision.SPECULATE, res.EV_usd,
            res.threshold_usd)


@pytest.mark.parametrize("alpha", ALPHAS)
def test_decision_matches_critical_k_closed_form(alpha):
    """Per edge and per k: EV(1/k) equals the analytic
    (L_value + C)/k - C to 1e-9, and the SPECULATE verdict is exactly
    the closed-form k <= k_crit(alpha) indicator."""
    for edge in EDGES:
        Lv, C = _edge_terms(edge)
        kc = critical_k(Lv, C, alpha)
        assert abs(kc - round(kc)) > 1e-6, \
            "test edge parks k_crit on an integer; pick another edge"
        for k in KS:
            spec, EV, thr = _decide(edge, int(k), alpha)
            assert abs(EV - ((Lv + C) / k - C)) <= 1e-9
            assert abs(thr - (1.0 - alpha) * C) <= 1e-9
            assert spec == (k <= kc)
            # equivalent threshold-crossing form: P = 1/k vs P*(alpha)
            assert spec == (1.0 / k >= p_threshold_crossing(Lv, C, alpha)
                            - 1e-15)


@pytest.mark.parametrize("alpha", ALPHAS)
def test_speculation_rate_falls_with_branching_factor(alpha):
    """The population speculation rate at branching k equals the
    analytic fraction of edges with k <= k_crit, is non-increasing in k,
    and self-limits to zero once k clears every edge's k_crit."""
    kcs = np.array([critical_k(*_edge_terms(e), alpha) for e in EDGES])
    assert np.all(kcs < KS[-1]), \
        "every edge must self-limit inside the sweep"
    rates = []
    for k in KS:
        decisions = [_decide(e, int(k), alpha)[0] for e in EDGES]
        rate = float(np.mean(decisions))
        analytic = float(np.mean(k <= kcs))
        assert abs(rate - analytic) <= 1e-9
        rates.append(rate)
    assert all(a >= b for a, b in zip(rates, rates[1:]))   # monotone fall
    assert rates[0] > 0.0                                  # k=1 speculates
    assert rates[-1] == 0.0                                # k=32 self-limits
    # the fall is strict somewhere inside the sweep for every alpha
    assert rates[0] > rates[-1]


def test_critical_k_grid_matches_scalar_closed_form():
    """The vectorized k_crit grid (batch_decision) agrees with the
    scalar closed form to 1e-9 over the full (edge, alpha) cross (f64 —
    the analytic-curve contract runs at double precision)."""
    from jax import enable_x64

    alphas = np.asarray(ALPHAS)
    with enable_x64():
        for edge in EDGES:
            Lv, C = _edge_terms(edge)
            grid = critical_k_grid(Lv, C, alphas)
            ref = np.array([critical_k(Lv, C, a) for a in alphas])
            np.testing.assert_allclose(grid, ref, rtol=1e-9, atol=0.0)


def test_alpha_raises_the_self_limiting_point():
    """k_crit is monotone in alpha: a more latency-hungry dial keeps
    speculating at higher branching factors, but never past
    (L_value + C)/C (the alpha=1 ceiling)."""
    for edge in EDGES:
        Lv, C = _edge_terms(edge)
        kcs = [critical_k(Lv, C, a) for a in np.linspace(0.0, 1.0, 21)]
        assert all(a <= b + 1e-15 for a, b in zip(kcs, kcs[1:]))
        assert kcs[-1] == pytest.approx((Lv + C) / C, rel=1e-12)
