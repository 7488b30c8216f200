"""Jax-native inverse regularized incomplete beta (repro.core.betainc).

The §7.5 credible-bound fleet path stands on ``betaincinv`` agreeing with
``scipy.stats.beta.ppf``: the parity suite compares fleet decisions gated
on our inversion against the scalar executor gated on scipy's.  These
tests pin the agreement directly — a dense deterministic grid plus a
property-style sweep (mini-hypothesis shim when the real library is
absent) at <= 1e-10 relative error, and the scipy-documented special
values at the edges."""
import numpy as np
import pytest
from hypothesis import example, given, settings
from hypothesis import strategies as st
from jax import enable_x64
from scipy import stats

from repro.core.batch_decision import batch_lower_bound
from repro.core.betainc import betaincinv

RTOL = 1e-10

# Deterministic acceptance grid: spans a/b << 1 through a/b >> 1 and deep
# tails of gamma; roots reach ~1e-160 without leaving float64 range.
GRID_AB = (0.05, 0.1, 0.3, 0.7, 1.0, 1.5, 4.0, 12.0, 40.0, 150.0)
GRID_Q = (1e-8, 1e-6, 1e-4, 1e-2, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99,
          1.0 - 1e-4, 1.0 - 1e-6)


def _rel_err(ours, ref):
    return np.abs(ours - ref) / np.maximum(np.abs(ref), 1e-300)


def test_grid_vs_scipy_ppf():
    """Full (alpha, beta, gamma) cross product against scipy.stats.beta.ppf
    at float64: <= 1e-10 relative error everywhere the root is nonzero.

    scipy's own iteration carries ~1e-10-scale error at a handful of
    points (e.g. a=b=0.3, q=0.5, whose exact root is 0.5 by symmetry —
    we return 0.5, scipy returns 0.5 + 2e-10); such points pass when our
    root round-trips through scipy's forward CDF at least as accurately
    as scipy's own root does."""
    with enable_x64():
        A, B, Q = np.meshgrid(GRID_AB, GRID_AB, GRID_Q, indexing="ij")
        ours = np.asarray(betaincinv(A, B, Q))
        ref = stats.beta.ppf(Q, A, B)
        assert np.all(np.isfinite(ours))
        rel = _rel_err(ours, ref)
        for i, j, k in np.argwhere(rel >= RTOL):
            a, b, q = A[i, j, k], B[i, j, k], Q[i, j, k]
            ours_rt = abs(stats.beta.cdf(ours[i, j, k], a, b) - q)
            ref_rt = abs(stats.beta.cdf(ref[i, j, k], a, b) - q)
            assert ours_rt <= ref_rt, (a, b, q, ours[i, j, k], ref[i, j, k])


def test_special_values_and_domain():
    with enable_x64():
        # scipy-compatible edges: q=0 -> 0, q=1 -> 1 exactly
        np.testing.assert_array_equal(
            np.asarray(betaincinv(2.0, 3.0, np.array([0.0, 1.0]))),
            [0.0, 1.0])
        # out-of-domain q and non-positive parameters -> NaN
        bad = np.asarray(betaincinv(
            np.array([2.0, 2.0, -1.0, 2.0]),
            np.array([3.0, 3.0, 3.0, 0.0]),
            np.array([-0.1, 1.5, 0.5, 0.5])))
        assert np.all(np.isnan(bad))


def test_tiny_shape_parameters_deep_tail():
    """a or b << 1 with tail gamma: the power-law initial guess must land
    the bracketed iteration on roots far below bisection reach."""
    with enable_x64():
        cases = [
            (0.05, 0.05, 1e-6), (0.05, 25.0, 1e-4), (0.1, 0.5, 1e-2),
            (25.0, 0.05, 1.0 - 1e-4), (0.5, 0.1, 1.0 - 1e-2),
            (0.02, 3.0, 0.3),
        ]
        for a, b, q in cases:
            ours = float(betaincinv(a, b, q))
            ref = float(stats.beta.ppf(q, a, b))
            assert _rel_err(ours, ref) < RTOL, (a, b, q, ours, ref)


@settings(max_examples=120, deadline=None)
@given(
    a=st.floats(min_value=0.05, max_value=80.0),
    b=st.floats(min_value=0.05, max_value=80.0),
    q=st.floats(min_value=1e-6, max_value=1.0 - 1e-6),
)
@example(a=0.5, b=0.0625, q=0.875)
def test_property_matches_scipy_and_inverts_cdf(a, b, q):
    """Property sweep: betaincinv is scipy's quantile (<= 1e-10 rel) and a
    true right-inverse of the CDF wherever the draw lands, to the float64
    grid: q lies (within 1e-8) between the CDF at x's two neighbouring
    floats.  Where the CDF is steep at the root (a=0.5, b=0.0625, q=0.875
    has its root at 1 - 1.3e-14), one ulp of x moves the CDF by ~7e-5, so
    no float64 x is within 1e-8 of q, scipy's own root included."""
    with enable_x64():
        x = float(betaincinv(a, b, q))
        ref = float(stats.beta.ppf(q, a, b))
        assert 0.0 <= x <= 1.0
        assert _rel_err(np.asarray(x), np.asarray(ref)) < RTOL
        # round-trip through the forward CDF (scipy's, as the oracle)
        if 1e-300 < x < 1.0:
            lo = stats.beta.cdf(np.nextafter(x, 0.0), a, b)
            hi = stats.beta.cdf(np.nextafter(x, 1.0), a, b)
            assert lo - 1e-8 < q < hi + 1e-8, (x, lo - q, hi - q)


def test_monotone_in_q():
    """Quantiles are non-decreasing in gamma for fixed (a, b)."""
    with enable_x64():
        q = np.linspace(1e-6, 1.0 - 1e-6, 201)
        for a, b in [(0.3, 2.0), (5.0, 5.0), (0.1, 0.1), (40.0, 2.0)]:
            x = np.asarray(betaincinv(a, b, q))
            assert np.all(np.diff(x) >= 0.0)


def test_batch_lower_bound_matches_posterior_lower_bound():
    """batch_decision.batch_lower_bound == BetaPosterior.lower_bound
    (scipy) across a fleet of posterior parameters in one call."""
    from repro.core.posterior import beta_lower_bound

    with enable_x64():
        rng = np.random.default_rng(13)
        a = rng.uniform(0.2, 30.0, 256)
        b = rng.uniform(0.2, 30.0, 256)
        for gamma in (0.01, 0.1, 0.5):
            ours = batch_lower_bound(a, b, gamma)
            ref = np.array([beta_lower_bound(ai, bi, gamma)
                            for ai, bi in zip(a, b)])
            np.testing.assert_allclose(ours, ref, rtol=RTOL)


def test_float32_path_still_sane():
    """Without x64 the inversion runs at float32 (the _f convention);
    agreement degrades gracefully to f32-scale error, not garbage."""
    x = np.asarray(betaincinv(
        np.array([2.0, 0.5, 8.0]), np.array([3.0, 0.5, 1.0]), 0.1))
    ref = stats.beta.ppf(0.1, [2.0, 0.5, 8.0], [3.0, 0.5, 1.0])
    np.testing.assert_allclose(x, ref, rtol=5e-5)
