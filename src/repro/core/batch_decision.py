"""Vectorized JAX decision engine — the beyond-paper fast path.

The paper's D4 rule is a handful of multiplies per decision (§6.5).  At
fleet scale the hot paths are *batched*: the §12.1 counterfactual replay
over millions of logged decisions x an (alpha, lambda) grid, per-chunk
streaming re-evaluation across thousands of in-flight edges, and bulk
posterior updates.  This module jit-compiles those as single XLA calls.

Recorded in EXPERIMENTS.md §Perf as the optimized implementation next to
the paper-faithful scalar path (repro.core.decision), with identical
numerics (tests assert bitwise-comparable float64 results).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .betainc import betaincinv

__all__ = [
    "d4_gate",
    "batch_evaluate",
    "batch_lower_bound",
    "counterfactual_grid",
    "counterfactual_grid_sharded",
    "counterfactual_grid_tenants",
    "batch_posterior_update",
    "batch_implied_lambda",
    "critical_k_grid",
    "batch_chunk_cancel",
    "batch_fractional_waste",
    "beam_gate",
    "beam_counterfactual_grid",
    "critical_k_surface",
]


def d4_gate(P_gate, alpha, lam, latency_s, in_tok, out_tok, in_price,
            out_price, zero=None):
    """Traceable D4 gate core (§6.1): the one expression both the batch
    path and the online decision service lower.

    With ``zero=None`` the expressions match the historical fused lowering
    (XLA CPU contracts ``a*b + c`` into one FMA, so EV / threshold agree
    with the scalar ``decision.evaluate`` only to 1 ULP — the established
    fleet-parity tolerance).  With ``zero`` a *traced* runtime 0.0 scalar,
    every product feeding an add is pinned to its correctly-rounded value:
    ``x + zero`` either survives as ``round(x) + 0`` or contracts to
    ``fma(a, b, 0) == round(a*b)`` — either way the twice-rounded scalar
    result — making EV / threshold / margin **bitwise-f64 equal** to the
    scalar path.  (``zero`` must be traced; a literal would be folded
    away.  All products here are >= +0.0 in the decision domain, so the
    ``-0.0 + 0.0 -> +0.0`` edge of the trick cannot bite.)
    """
    rnd = (lambda x: x) if zero is None else (lambda x: x + zero)
    C_spec = rnd(in_tok * in_price) + rnd(out_tok * out_price)
    L_value = latency_s * lam
    EV = rnd(P_gate * L_value) - rnd((1.0 - P_gate) * C_spec)
    threshold = rnd((1.0 - alpha) * C_spec)
    return EV, threshold, EV >= threshold, C_spec, L_value


@functools.partial(jax.jit, static_argnames=())
def _batch_evaluate(P, alpha, lam, latency_s, in_tok, out_tok, in_price, out_price):
    return d4_gate(P, alpha, lam, latency_s, in_tok, out_tok, in_price, out_price)


@jax.jit
def _batch_evaluate_exact(P, alpha, lam, latency_s, in_tok, out_tok,
                          in_price, out_price, zero):
    return d4_gate(P, alpha, lam, latency_s, in_tok, out_tok, in_price,
                   out_price, zero)


def _f(x):
    """float array at the widest enabled precision (f64 under jax_enable_x64,
    f32 otherwise) — keeps numerics comparable to the scalar path."""
    return jnp.asarray(x, dtype=jnp.result_type(float))


def batch_evaluate(
    P, alpha, lam, latency_s, in_tok, out_tok, in_price, out_price,
    *, P_lower=None, exact=False,
):
    """Vectorized D4 gate.  All inputs broadcastable arrays.  Returns
    (EV, threshold, speculate_mask, C_spec, L_value).

    ``P_lower`` enables the §7.5 credible-bound variant: the gate (and the
    reported EV — matching ``decision.evaluate(use_lower_bound=True)``,
    whose ``P_used`` is the bound) runs on the one-sided lower credible
    bound instead of the posterior mean.  Compute it in bulk with
    :func:`batch_lower_bound`.

    ``exact=True`` runs the contraction-pinned lowering (see
    :func:`d4_gate`): EV / threshold / decision flags come out
    **bitwise-f64 equal** to the scalar ``decision.evaluate`` instead of
    the default 1-ULP FMA tolerance — the contract the online decision
    service (``repro.core.online``) serves under.
    """
    gate_P = P if P_lower is None else P_lower
    args = [_f(x) for x in (
        gate_P, alpha, lam, latency_s, in_tok, out_tok, in_price, out_price
    )]
    if exact:
        return _batch_evaluate_exact(*args, _f(0.0))
    return _batch_evaluate(*args)


@jax.jit
def _lower_bound(alpha, beta, gamma):
    return betaincinv(alpha, beta, gamma)


def _lower_bound_pallas(alpha, beta, gamma):
    # Not jitted here: betaincinv(use_pallas=True) dispatches to the
    # already-jitted kernel op, which resolves interpret/native outside
    # the trace (kernels.ops._interpret()).
    return betaincinv(alpha, beta, gamma, use_pallas=True)


def batch_lower_bound(alpha, beta, gamma=0.1, use_pallas: bool = False):
    """§7.5 one-sided (1-gamma) lower credible bound, vectorized.

    ``Beta^{-1}(gamma; alpha, beta)`` across whole fleets of posterior
    parameters in one XLA call — the jax-native equivalent of
    ``BetaPosterior.lower_bound`` / ``scipy.stats.beta.ppf`` (agreement
    pinned to <= 1e-10 relative by tests/test_betaincinv.py).

    ``use_pallas=True`` routes the inversion through the tiled Pallas
    kernel (``repro.kernels.betaincinv_pallas``) — same <= 1e-10 tier vs
    scipy, not bitwise vs the default path.
    """
    fn = _lower_bound_pallas if use_pallas else _lower_bound
    return np.asarray(fn(_f(alpha), _f(beta), _f(gamma)))


@jax.jit
def _grid(P, P_gate, lat, cost, alphas, lams, rho):
    # decisions[a, l, n] for n log rows at each (alpha, lambda) grid point;
    # the gate runs on P_gate (== P, or the §7.5 lower bound) while the
    # counterfactual expectations stay weighted by the posterior mean P.
    # rho is traced (not static): calibration sweeps vary it per call and
    # must not retrigger XLA compilation.
    L_value = lat[None, None, :] * lams[None, :, None]
    EV = P_gate * L_value - (1.0 - P_gate) * cost[None, None, :]
    thr = (1.0 - alphas[:, None, None]) * cost[None, None, :]
    spec = EV >= thr
    # bool .mean() yields f32 regardless of jax_enable_x64 — cast first so
    # the fraction carries the working precision (f64 under x64)
    frac = spec.astype(lat.dtype).mean(axis=-1)
    exp_lat = jnp.where(spec, lat[None, None, :] * (1.0 - P), lat[None, None, :]).mean(-1)
    waste = (spec * (1.0 - P) * cost[None, None, :] * rho).sum(-1)
    exp_cost = cost.sum() + waste
    return frac, exp_lat, exp_cost, waste


def counterfactual_grid(P, latencies, costs, alphas, lambdas, rho=0.5,
                        *, P_lower=None):
    """§12.1 counterfactual EV grid as one XLA call.

    Returns dict of (len(alphas), len(lambdas)) arrays:
    speculate_fraction, expected_latency, expected_cost, expected_waste.

    ``rho`` (scalar or per-row array) is traced, so sweeping it across a
    calibration grid reuses one compiled executable.  ``P_lower`` switches
    the SPECULATE gate to the §7.5 credible bound while the latency /
    waste expectations remain weighted by the posterior mean ``P``.
    """
    P = _f(P)
    P_gate = P if P_lower is None else _f(P_lower)
    frac, exp_lat, exp_cost, waste = _grid(
        P, P_gate, _f(latencies), _f(costs), _f(alphas), _f(lambdas),
        _f(rho),
    )
    return {
        "speculate_fraction": np.asarray(frac),
        "expected_latency_s": np.asarray(exp_lat),
        "expected_cost_usd": np.asarray(exp_cost),
        "expected_waste_usd": np.asarray(waste),
    }


@functools.lru_cache(maxsize=None)
def _grid_sharded_exec(mesh, axis_name):
    """Compile (and cache per mesh) the log-axis-sharded §12.1 grid: the
    N log rows are split into C contiguous segments (masked tail padding,
    same scheme as ``fleet.chunk_episodes``), each segment reduces to raw
    per-(alpha, lambda) partial sums, and the segment axis is optionally
    ``shard_map``'d over the 1-D fleet mesh — each device sees only its
    rows, with zero cross-device traffic until the final O(C·A·L)
    combine."""

    def run(P, P_gate, lat, cost, mask, alphas, lams, rho):
        # lat / cost / mask / rho: (C, Nc) segments; returns per-segment
        # raw sums (count, lat_sum, waste_sum, cost_sum) — the combine
        # happens outside so decision *counts* stay exact integers.
        def one(lat_c, cost_c, m_c, rho_c):
            m = m_c.astype(lat_c.dtype)
            L_value = lat_c[None, None, :] * lams[None, :, None]
            EV = P_gate * L_value - (1.0 - P_gate) * cost_c[None, None, :]
            thr = (1.0 - alphas[:, None, None]) * cost_c[None, None, :]
            spec = (EV >= thr) & m_c[None, None, :]
            count = spec.astype(lat_c.dtype).sum(-1)
            lat_sum = (
                jnp.where(spec, lat_c[None, None, :] * (1.0 - P),
                          lat_c[None, None, :]) * m[None, None, :]
            ).sum(-1)
            waste = (
                spec * (1.0 - P) * cost_c[None, None, :] * rho_c
            ).sum(-1)
            return count, lat_sum, waste, (cost_c * m).sum()

        return jax.vmap(one)(lat, cost, mask, rho)

    if mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec

        c = PartitionSpec(axis_name)
        r = PartitionSpec()
        run = shard_map(
            run, mesh=mesh,
            in_specs=(r, r, c, c, c, r, r, c),
            out_specs=c,
            check_vma=False,
        )
    return jax.jit(run)


def counterfactual_grid_sharded(P, latencies, costs, alphas, lambdas,
                                rho=0.5, *, P_lower=None, segments=None,
                                mesh=None, axis_name="fleet"):
    """§12.1 counterfactual EV grid with the *log-row axis sharded*.

    Same contract as :func:`counterfactual_grid` (including scalar *or*
    per-row ``rho``), for logs too large to want on one device: the N
    rows split into ``segments`` contiguous chunks (default: the mesh
    extent, or the visible device count), each chunk reduces
    independently, and raw partial sums combine at the end.  The segment
    length is bucketed to a power of two (masked zero rows are exact
    no-ops), so ragged large-log sweeps reuse one executable per
    (segments, bucket).  A mesh without the fleet axis, or one whose
    extent does not divide ``segments``, falls back to the unsharded
    executable (``sharding.rules.fleet_axis_spec``).

    ``speculate_fraction`` is **bitwise-identical** to the unsharded
    grid (decision counts are exact integers; one final division); the
    latency / cost / waste expectations differ only by float summation
    order (<= ~1e-15 relative, pinned by the --smoke parity gate).
    ``calibration.offline_replay`` reroutes here when the log count
    exceeds its ``shard_threshold``.
    """
    P = _f(P)
    P_gate = P if P_lower is None else _f(P_lower)
    lat = np.atleast_1d(np.asarray(latencies, float))
    cost = np.atleast_1d(np.asarray(costs, float))
    n = lat.shape[0]
    if n == 0:
        raise ValueError("counterfactual_grid_sharded requires >= 1 log row")
    if cost.shape != lat.shape:
        raise ValueError("latencies and costs must have the same length")
    # per-row rho (same contract as counterfactual_grid) segments along
    # with the rows; a scalar broadcasts to every row first
    rho_rows = np.broadcast_to(np.asarray(rho, float), lat.shape).copy()
    if segments is None:
        if mesh is not None and axis_name in mesh.shape:
            segments = mesh.shape[axis_name]
        else:
            segments = max(1, len(jax.devices()))
    C = int(segments)
    if C < 1:
        raise ValueError(f"segments must be >= 1, got {segments}")
    if mesh is not None:
        from ..sharding.rules import fleet_axis_spec

        if fleet_axis_spec(mesh, C, axis=axis_name) is None:
            mesh = None  # missing axis / indivisible: run unsharded
    # bucket the segment length to a power of two (masked zero rows are
    # exact no-ops in every sum) so a sweep over many ragged large logs
    # compiles one executable per (C, bucket) instead of one per
    # distinct log count — the sharded twin of offline_replay's
    # power-of-two bucketing on the unsharded path
    Nc_raw = -(-n // C)
    Nc = max(16, 1 << (Nc_raw - 1).bit_length())
    pad = C * Nc - n
    mask = np.ones(n, bool)

    def seg(x, fill):
        if pad:
            x = np.concatenate([x, np.full(pad, fill, x.dtype)])
        return x.reshape(C, Nc)

    fn = _grid_sharded_exec(mesh, axis_name)
    count, lat_sum, waste_sum, cost_sum = fn(
        P, P_gate, _f(seg(lat, 0.0)), _f(seg(cost, 0.0)),
        jnp.asarray(seg(mask, False)), _f(alphas), _f(lambdas),
        _f(seg(rho_rows, 0.0)),
    )
    count = np.asarray(count).sum(0)
    lat_sum = np.asarray(lat_sum).sum(0)
    waste = np.asarray(waste_sum).sum(0)
    cost_total = np.asarray(cost_sum).sum(0)
    # XLA lowers .mean() as sum * (1/n); mirror that here so the exact
    # integer decision counts divide to bitwise-identical fractions
    inv_n = np.asarray(_f(1.0)) / n
    return {
        "speculate_fraction": count * inv_n,
        "expected_latency_s": lat_sum * inv_n,
        "expected_cost_usd": cost_total + waste,
        "expected_waste_usd": waste,
    }


@jax.jit
def _grid_tenants(P, P_gate, lat, cost, mask, alphas, lams, rho):
    # tenant-batched §12.1 grid: P/P_gate are (T,) per-tenant seeded-prior
    # summaries, lat/cost/mask are (T, N) padded log rows.  Masked rows
    # contribute to nothing; means divide by the per-tenant real row count
    # (so a short tenant's grid equals its unpadded scalar grid).
    m = mask.astype(lat.dtype)
    n = jnp.maximum(m.sum(-1), 1.0)                       # (T,)
    lat_b = lat[:, None, None, :]
    cost_b = cost[:, None, None, :]
    m_b = m[:, None, None, :]
    P_b = P[:, None, None, None]
    L_value = lat_b * lams[None, None, :, None]
    EV = P_gate[:, None, None, None] * L_value - (1.0 - P_gate[:, None, None, None]) * cost_b
    thr = (1.0 - alphas[None, :, None, None]) * cost_b
    spec = (EV >= thr) & mask[:, None, None, :]
    frac = spec.sum(-1) / n[:, None, None]
    exp_lat = (
        jnp.where(spec, lat_b * (1.0 - P_b), lat_b) * m_b
    ).sum(-1) / n[:, None, None]
    waste = (spec * (1.0 - P_b) * cost_b * rho).sum(-1)
    exp_cost = (cost * m).sum(-1)[:, None, None] + waste
    return frac, exp_lat, exp_cost, waste


def counterfactual_grid_tenants(P, latencies, costs, mask, alphas, lambdas,
                                rho=0.5, *, P_lower=None):
    """§12.1 counterfactual EV grids for a whole fleet of tenants in one
    XLA call.

    ``P`` is the per-tenant seeded-prior mean (T,); ``latencies`` /
    ``costs`` / ``mask`` are (T, N) log rows padded to a common N with
    ``mask`` marking the real ones.  Returns dict of (T, A, L) arrays —
    ``counterfactual_grid`` stacked over tenants, with per-tenant means
    taken over each tenant's own row count.  ``P_lower`` switches the
    SPECULATE gate to the §7.5 credible bound per tenant, as in the
    single-tenant grid.
    """
    P = jnp.atleast_1d(_f(P))
    P_gate = P if P_lower is None else jnp.atleast_1d(_f(P_lower))
    lat = jnp.atleast_2d(_f(latencies))
    cost = jnp.atleast_2d(_f(costs))
    mask = jnp.atleast_2d(jnp.asarray(mask, bool))
    frac, exp_lat, exp_cost, waste = _grid_tenants(
        P, P_gate, lat, cost, mask, _f(alphas), _f(lambdas), _f(rho),
    )
    return {
        "speculate_fraction": np.asarray(frac),
        "expected_latency_s": np.asarray(exp_lat),
        "expected_cost_usd": np.asarray(exp_cost),
        "expected_waste_usd": np.asarray(waste),
    }


@jax.jit
def _post_update(alpha0, beta0, successes):
    # successes: (E, N) in {0, 1}; returns per-edge running posterior params
    s = successes.sum(-1)
    n = successes.shape[-1]
    return alpha0 + s, beta0 + (n - s)


@jax.jit
def _post_update_discounted(alpha0, beta0, successes, discount):
    # sequential over the trial axis so the exponential forgetting matches
    # BetaPosterior.update exactly (a <- a*d + x_i per observation)
    def step(ab, x):
        a, b = ab
        return (a * discount + x, b * discount + (1.0 - x)), None

    (a, b), _ = jax.lax.scan(
        step, (alpha0, beta0), jnp.moveaxis(successes, -1, 0)
    )
    return a, b


def batch_posterior_update(alpha0, beta0, outcomes, discount: float = 1.0):
    """Bulk conjugate update for E edges at once.

    ``discount=1`` is the paper's exact update, Beta(a0+s, b0+f), as one
    fused sum.  ``discount<1`` mirrors the exponential-forgetting branch of
    ``BetaPosterior.update`` (§14.3): a sequential ``lax.scan`` over the
    trial axis, vectorized across edges, bitwise-matching the scalar loop.
    """
    if discount == 1.0:
        a, b = _post_update(_f(alpha0), _f(beta0), _f(outcomes))
    else:
        a, b = _post_update_discounted(
            _f(alpha0), _f(beta0), _f(outcomes), _f(discount)
        )
    return np.asarray(a), np.asarray(b)


@functools.partial(jax.jit, static_argnames=("throttle_every",))
def _chunk_cancel(P_k, alpha, lam, latency_s, in_tok, out_tok,
                  in_price, out_price, throttle_every):
    C_spec = in_tok * in_price + out_tok * out_price
    L_value = latency_s * lam
    EV_k = P_k * L_value[..., None] - (1.0 - P_k) * C_spec[..., None]
    thr = ((1.0 - alpha) * C_spec)[..., None]
    K = P_k.shape[-1]
    valid = (jnp.arange(K) % throttle_every) == 0
    wait_k = valid & (EV_k < thr)
    cancelled = wait_k.any(-1)
    first = jnp.argmax(wait_k, axis=-1)
    return jnp.where(cancelled, first, -1), cancelled, EV_k, thr


def batch_chunk_cancel(
    P_chunks, alpha, lam, latency_s, in_tok, out_tok, in_price, out_price,
    *, throttle_every: int = 1,
):
    """Vectorized §9.1 per-chunk re-estimation across a fleet of in-flight
    edges: re-run the D4 gate at every streamed chunk and return the first
    WAIT verdict per stream.

    ``P_chunks``: (..., K) refined success probabilities P_k; scalar inputs
    broadcast.  Returns ``(first_cancel_idx, cancelled, EV_k, threshold)``
    where ``first_cancel_idx`` is -1 for streams that never cancel —
    matching ``StreamingReestimator.run`` chunk-for-chunk (throttled chunks
    are skipped, not evaluated, exactly as the scalar loop does).
    """
    P_chunks = _f(P_chunks)
    args = [jnp.broadcast_to(_f(x), P_chunks.shape[:-1]) for x in (
        alpha, lam, latency_s, in_tok, out_tok, in_price, out_price
    )]
    first, cancelled, EV_k, thr = _chunk_cancel(
        P_chunks, *args, throttle_every=int(throttle_every)
    )
    return (np.asarray(first), np.asarray(cancelled),
            np.asarray(EV_k), np.broadcast_to(np.asarray(thr), EV_k.shape))


@jax.jit
def _frac_waste(in_tok, out_tok, frac, in_price, out_price):
    # same expression order as streaming.fractional_waste:
    # c_in(full prompt) + c_out(frac * planned output); frac > 1 bills
    # actuals, exactly like the scalar path
    return in_tok * in_price + (frac * out_tok) * out_price


def batch_fractional_waste(in_tok, out_tok, frac, in_price, out_price):
    """Vectorized §9.3 C_spec_actual for cancelled speculations: full input
    cost plus only the output tokens actually emitted."""
    return np.asarray(_frac_waste(
        _f(in_tok), _f(out_tok), _f(frac), _f(in_price), _f(out_price)
    ))


@jax.jit
def _implied(P, C, alpha_star, L_up):
    return ((1.0 - alpha_star) * C + (1.0 - P) * C) / (P * L_up)


def batch_implied_lambda(P, C_spec, alpha_star, L_upstream_s):
    """§12.3 implied-lambda over arrays of observed operating points."""
    return np.asarray(_implied(_f(P), _f(C_spec), _f(alpha_star), _f(L_upstream_s)))


@jax.jit
def _kcrit(L_value, C_spec, alphas):
    return (L_value + C_spec) / ((2.0 - alphas) * C_spec)


def critical_k_grid(L_value, C_spec, alphas):
    """k_crit(alpha) over an alpha grid (§7.6) in one call."""
    return np.asarray(_kcrit(_f(L_value), _f(C_spec), _f(alphas)))


# --------------------------------------------------- top-k beam (repro.core.beam)
def beam_gate(P_gate, conf, width, alpha, lam, latency_s, in_tok, out_tok,
              in_price, out_price, zero=None):
    """Traceable top-k D4 gate — :func:`d4_gate` generalized to a beam of
    candidates over a shared dollar budget (repro.core.beam docstring).

    ``conf`` carries per-candidate confidences on a trailing axis (sorted
    non-increasing, summing to <= 1); ``width`` caps launches per row.
    Candidate 1 is admitted unconditionally and candidates ``j >= 2``
    while the marginal EV ``p_j (L_value + C_spec) - C_spec`` stays
    non-negative (tie -> include), so at ``width == 1`` with a certain
    first candidate the EV / threshold / flag come out bitwise equal to
    :func:`d4_gate` (same ``zero`` pinning contract).

    Returns ``(EV, threshold, speculate, C_spec, L_value, w_eff, p_cum)``
    where ``w_eff`` is the admitted beam width and ``p_cum`` the beam-
    cumulative commit probability the gate ran on.
    """
    rnd = (lambda x: x) if zero is None else (lambda x: x + zero)
    C_spec = rnd(in_tok * in_price) + rnd(out_tok * out_price)
    L_value = latency_s * lam
    p = conf * P_gate[..., None]
    j = jnp.arange(conf.shape[-1])
    marginal_ok = (
        p * (L_value + C_spec)[..., None] - C_spec[..., None] >= 0.0
    )
    inc = (j == 0) | marginal_ok
    prefix = jnp.cumsum(jnp.logical_not(inc), axis=-1) == 0
    sel = prefix & (j < width[..., None])
    w_eff = sel.sum(-1)
    w_eff_f = w_eff.astype(C_spec.dtype)
    p_cum = jnp.where(sel, p, 0.0).sum(-1)
    EV = rnd(p_cum * L_value) - rnd((w_eff_f - p_cum) * C_spec)
    threshold = rnd((1.0 - alpha) * C_spec)
    return EV, threshold, EV >= threshold, C_spec, L_value, w_eff, p_cum


@jax.jit
def _beam_grid(P, conf, lat, cost, alphas, lams, widths, rho):
    # decisions[w, a, l, n]: the §12.1 grid with beam width as a third
    # axis.  Candidate admission depends on lambda (through L_value) but
    # not alpha; selection is computed once per (lambda, row, candidate)
    # and broadcast over alpha / width.
    Lv = lat[None, :] * lams[:, None]                        # (L, N)
    p = conf * P[:, None]                                    # (N, W)
    j = jnp.arange(conf.shape[-1])
    marginal_ok = (
        p[None] * (Lv + cost[None, :])[:, :, None]
        - cost[None, :, None] >= 0.0
    )                                                        # (L, N, W)
    inc = (j == 0) | marginal_ok
    prefix = jnp.cumsum(jnp.logical_not(inc), axis=-1) == 0
    sel = prefix[None] & (j < widths[:, None, None, None])   # (Wd, L, N, W)
    w_eff = sel.sum(-1).astype(lat.dtype)                    # (Wd, L, N)
    p_cum = jnp.where(sel, p[None, None], 0.0).sum(-1)       # (Wd, L, N)
    EV = p_cum * Lv[None] - (w_eff - p_cum) * cost[None, None, :]
    thr = (1.0 - alphas[:, None, None]) * cost[None, None, :]  # (A, L, N)
    spec = EV[:, None] >= thr[None]                          # (Wd, A, L, N)
    frac = spec.astype(lat.dtype).mean(axis=-1)
    # any committed candidate saves the edge's latency; all launched
    # losers are billed at rho (§9.3 expected form)
    exp_lat = jnp.where(
        spec, (lat[None, :] * (1.0 - p_cum))[:, None], lat[None, None, None, :]
    ).mean(-1)
    waste = (spec * ((w_eff - p_cum)[:, None] * cost[None, None, None, :])
             * rho).sum(-1)
    exp_cost = cost.sum() + waste
    return frac, exp_lat, exp_cost, waste


def beam_counterfactual_grid(P, conf, latencies, costs, alphas, lambdas,
                             widths, rho=0.5):
    """§12.1 counterfactual grid with beam width as a third axis.

    ``conf`` is (N, W) per-row candidate confidences (rows sorted
    non-increasing); ``widths`` the beam widths to sweep.  Returns a dict
    of (len(widths), len(alphas), len(lambdas)) arrays under the same
    keys as :func:`counterfactual_grid`; the ``width == 1`` slice of a
    single-certain-candidate ``conf`` reproduces that grid exactly
    (pinned by tests/test_beam.py).
    """
    conf = np.asarray(conf, float)
    if conf.ndim != 2:
        raise ValueError("conf must be (N, W)")
    if (conf < 0).any() or (conf > 1).any():
        raise ValueError("candidate confidences must be in [0, 1]")
    if (conf[:, 1:] > conf[:, :-1]).any():
        raise ValueError("conf rows must be sorted non-increasing")
    if (conf.sum(1) > 1.0 + 1e-9).any():
        raise ValueError("conf rows must sum to <= 1")
    widths = np.atleast_1d(np.asarray(widths))
    if not np.issubdtype(widths.dtype, np.integer) or (widths < 1).any():
        raise ValueError("widths must be integers >= 1")
    frac, exp_lat, exp_cost, waste = _beam_grid(
        _f(P), _f(conf), _f(latencies), _f(costs), _f(alphas), _f(lambdas),
        jnp.asarray(widths, jnp.int32), _f(rho),
    )
    return {
        "speculate_fraction": np.asarray(frac),
        "expected_latency_s": np.asarray(exp_lat),
        "expected_cost_usd": np.asarray(exp_cost),
        "expected_waste_usd": np.asarray(waste),
    }


@jax.jit
def _kcrit_surface(L_value, C_spec, alphas, widths):
    w = widths[:, None]
    return w * (L_value + C_spec) / ((w + 1.0 - alphas[None, :]) * C_spec)


def critical_k_surface(L_value, C_spec, alphas, widths):
    """§7.6 self-limiting closed form extended to beam width: the
    (len(widths), len(alphas)) surface

        k_crit(alpha, w) = w (L + C) / ((w + 1 - alpha) C)

    (see ``repro.core.beam.beam_critical_k``).  The ``w == 1`` row equals
    :func:`critical_k_grid`; the surface is monotone in ``w`` with
    ceiling ``(L + C) / C``.
    """
    widths = np.atleast_1d(np.asarray(widths))
    if not np.issubdtype(widths.dtype, np.integer) or (widths < 1).any():
        raise ValueError("widths must be integers >= 1")
    return np.asarray(_kcrit_surface(
        _f(L_value), _f(C_spec), _f(alphas), _f(widths)))
