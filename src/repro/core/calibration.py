"""§12 — the five-stage calibration and evaluation pipeline.

Staged in order of increasing exposure:

  1. offline replay   — touches no production traffic
  2. shadow mode      — serves a decision but discards it
  3. canary           — live fraction + alpha sweep + implied-lambda recovery
  4. online           — steady-state continuous checks
  5. drift kill-switch — repro.core.drift (flips the enable bit)

Every §12 knob (dependency-type tag, p_structural, n0, alpha, lambda,
tier-2 threshold, token estimators, per-edge enable bit, credible gamma) is
set or kept honest by one of these stages (§12.6 knob-to-stage map).

Stages 2–4 also exist as table-batched twins over the online decision
service's posterior snapshot (``repro.core.online.shadow_mode_batch`` /
``canary_batch`` / ``online_calibration_batch``): one calibration round
for a whole fleet of edges as array ops, matching these scalar stages
bitwise at f64 (posteriors, implied lambdas, rates) and exactly
(promotion / trigger flags).
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Callable, Optional, Sequence

import numpy as np
from jax import enable_x64

from .decision import Decision, decision_threshold, expected_value, implied_lambda
from .posterior import BetaPosterior
from .predictor import InputPredictor
from .success import TierPolicy, check_success
from .taxonomy import DependencyType, auto_assign, effective_k
from .telemetry import SpeculationDecision, TelemetryLog

__all__ = [
    "SequentialLogRecord",
    "OfflineReplayReport",
    "offline_replay",
    "offline_replay_multi_tenant",
    "ShadowReport",
    "shadow_mode",
    "CanaryReport",
    "canary",
    "OnlineReport",
    "online_calibration",
    "TokenEstimator",
    "seed_store_from_replay",
]


# ---------------------------------------------------------------------------
# Stage 1: offline replay on sequential logs (§12.1)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SequentialLogRecord:
    """One logged tuple from a strictly-sequential deployment (§12.1)."""

    upstream_input: Any
    upstream_output: Any
    downstream_input: Any
    downstream_output: Any
    latency_s: float          # downstream latency (the reclaimable wait)
    cost_usd: float           # realized downstream cost
    tenant: str = "default"
    input_tokens: int = 500
    output_tokens: int = 1000


@dataclasses.dataclass
class GridPoint:
    alpha: float
    lambda_usd_per_s: float
    speculate_fraction: float
    expected_latency_s: float
    expected_cost_usd: float
    expected_waste_usd: float


@dataclasses.dataclass
class OfflineReplayReport:
    edge: tuple[str, str]
    k_raw: int
    p_mode: float
    k_eff: float
    dep_type: DependencyType
    seeded_prior: BetaPosterior
    predictor_match_rates: dict[str, float]
    grid: list[GridPoint]
    go: bool                  # per-edge go/no-go before any dollar of waste
    default_alpha: float


def _memoized_predictions(
    pred: InputPredictor, logs: Sequence[SequentialLogRecord]
) -> list:
    """One prediction per *distinct* upstream input.

    Production logs repeat inputs heavily (the AutoReply corpus is one
    prompt template over and over), and predictors can be arbitrarily
    expensive Python — so the replay memoizes ``pred.predict`` per input
    value instead of re-calling it per record.  Unhashable inputs fall
    back to a direct call.
    """
    cache: dict = {}
    out = []
    for r in logs:
        key = r.upstream_input
        try:
            hit = key in cache
        except TypeError:            # unhashable input: no memoization
            out.append(pred.predict(key))
            continue
        if not hit:
            cache[key] = pred.predict(key)
        out.append(cache[key])
    return out


def _seed_from_logs(
    logs: Sequence[SequentialLogRecord],
    predictors: dict[str, InputPredictor],
    tier_policy: TierPolicy,
):
    """§12.1 bootstrap: effective k, dependency type, per-predictor match
    rates and the data-seeded prior from the best predictor's (s, f)."""
    outputs = [r.upstream_output for r in logs]
    ek = effective_k(outputs)
    dep_type = auto_assign(outputs)

    match_rates: dict[str, float] = {}
    best_sf: tuple[int, int] = (0, len(logs))
    best_rate = -1.0
    for pname, pred in predictors.items():
        s = f = 0
        for r, p in zip(logs, _memoized_predictions(pred, logs)):
            if p is None:
                f += 1
                continue
            ok = check_success(r.upstream_output, p.i_hat, tier_policy).success
            s, f = s + int(ok), f + int(not ok)
        rate = s / max(1, s + f)
        match_rates[pname] = rate
        if rate > best_rate:
            best_rate, best_sf = rate, (s, f)
    seeded = BetaPosterior.data_seeded(dep_type, *best_sf, k=max(2, ek.k_raw))
    return ek, dep_type, match_rates, seeded


def _grid_points(g: dict, t: Optional[int], alphas, lambdas) -> list[GridPoint]:
    """Unpack a ``counterfactual_grid``(-``_tenants``) result dict into the
    row-major (alpha, lambda) GridPoint list the report carries."""
    sel = (lambda arr, i, j: arr[i, j]) if t is None else (
        lambda arr, i, j: arr[t, i, j])
    return [
        GridPoint(
            a, lam,
            float(sel(g["speculate_fraction"], i, j)),
            float(sel(g["expected_latency_s"], i, j)),
            float(sel(g["expected_cost_usd"], i, j)),
            float(sel(g["expected_waste_usd"], i, j)),
        )
        for i, a in enumerate(alphas)
        for j, lam in enumerate(lambdas)
    ]


def _go_and_default(
    grid: list[GridPoint], go_min_speculate_fraction: float
) -> tuple[bool, float]:
    # go/no-go: does any balanced-or-lower grid point speculate usefully?
    balanced = [g for g in grid if g.alpha <= 0.5]
    go = any(g.speculate_fraction >= go_min_speculate_fraction for g in balanced)
    # deployment default alpha: smallest alpha whose grid point speculates on
    # a majority of rows (cost-conservative default)
    default_alpha = next(
        (g.alpha for g in sorted(grid, key=lambda g: g.alpha)
         if g.speculate_fraction >= go_min_speculate_fraction),
        0.0,
    )
    return go, default_alpha


def offline_replay(
    edge: tuple[str, str],
    logs: Sequence[SequentialLogRecord],
    predictors: dict[str, InputPredictor],
    *,
    tier_policy: TierPolicy | None = None,
    alphas: Sequence[float] = (0.0, 0.25, 0.5, 0.75, 1.0),
    lambdas: Sequence[float] = (0.005, 0.01, 0.05, 0.1),
    rho: float = 0.5,
    go_min_speculate_fraction: float = 0.5,
    shard_threshold: int = 1 << 17,
    mesh=None,
) -> OfflineReplayReport:
    """§12.1: everything bootstrappable from sequential logs before any
    speculation is enabled.

    The counterfactual EV grid runs through the jit'd batch engine
    (``batch_decision.counterfactual_grid_tenants``, one XLA call for the
    whole (alpha, lambda) cross product) under float64, matching the
    historical per-cell Python loop to f64 rounding; predictor match
    rates memoize ``pred.predict`` per distinct upstream input.

    Million-row logs — the scale the episode-sharded fleet engine
    targets — reroute through the *log-axis-sharded* grid when
    ``len(logs)`` exceeds ``shard_threshold``:
    ``batch_decision.counterfactual_grid_sharded`` splits the rows into
    contiguous segments (``shard_map``'d over ``mesh`` when given, e.g.
    ``repro.launch.mesh.make_fleet_mesh()``), so one tenant's replay no
    longer funnels every row through a single device.  Decision
    fractions stay bitwise-identical to the unsharded
    ``counterfactual_grid`` (exact integer counts, one division);
    latency / waste expectations move only by float summation order
    (<= ~1e-15 relative).
    """
    if not logs:
        raise ValueError("offline replay requires at least one log record")
    tier_policy = tier_policy or TierPolicy()
    ek, dep_type, match_rates, seeded = _seed_from_logs(
        logs, predictors, tier_policy)

    n = len(logs)
    if n > shard_threshold:
        # episode-scale logs: segment the row axis across the fleet mesh
        from .batch_decision import counterfactual_grid_sharded

        lat = np.array([r.latency_s for r in logs])
        cost = np.array([r.cost_usd for r in logs])
        with enable_x64():
            g = counterfactual_grid_sharded(
                seeded.mean, lat, cost,
                np.asarray(alphas, float), np.asarray(lambdas, float),
                rho=rho, mesh=mesh,
            )
        grid = _grid_points(g, None, alphas, lambdas)
        go, default_alpha = _go_and_default(grid, go_min_speculate_fraction)
        return OfflineReplayReport(
            edge=edge, k_raw=ek.k_raw, p_mode=ek.p_mode, k_eff=ek.k_eff,
            dep_type=dep_type, seeded_prior=seeded,
            predictor_match_rates=match_rates, grid=grid, go=go,
            default_alpha=default_alpha,
        )

    # counterfactual EV grid (§12.1): replay D4 at each (alpha, lambda).
    # The log axis is padded to a power-of-two bucket under the masked
    # tenant kernel — padded rows contribute an exact 0.0 to every sum,
    # so results are bitwise-identical to the unpadded call, and a sweep
    # over hundreds of ragged per-edge log lists compiles one executable
    # per bucket instead of one per distinct log count.
    from .batch_decision import counterfactual_grid_tenants

    n_pad = max(16, 1 << (n - 1).bit_length())
    lat = np.zeros(n_pad)
    cost = np.zeros(n_pad)
    mask = np.zeros(n_pad, bool)
    lat[:n] = [r.latency_s for r in logs]
    cost[:n] = [r.cost_usd for r in logs]
    mask[:n] = True
    with enable_x64():
        g = counterfactual_grid_tenants(
            seeded.mean, lat[None], cost[None], mask[None],
            np.asarray(alphas, float), np.asarray(lambdas, float), rho=rho,
        )
    grid = _grid_points(g, 0, alphas, lambdas)
    go, default_alpha = _go_and_default(grid, go_min_speculate_fraction)
    return OfflineReplayReport(
        edge=edge,
        k_raw=ek.k_raw,
        p_mode=ek.p_mode,
        k_eff=ek.k_eff,
        dep_type=dep_type,
        seeded_prior=seeded,
        predictor_match_rates=match_rates,
        grid=grid,
        go=go,
        default_alpha=default_alpha,
    )


def offline_replay_multi_tenant(
    edge: tuple[str, str],
    logs: Sequence[SequentialLogRecord],
    predictors: dict[str, InputPredictor],
    *,
    tier_policy: TierPolicy | None = None,
    alphas: Sequence[float] = (0.0, 0.25, 0.5, 0.75, 1.0),
    lambdas: Sequence[float] = (0.005, 0.01, 0.05, 0.1),
    rho: float = 0.5,
    go_min_speculate_fraction: float = 0.5,
) -> dict[str, OfflineReplayReport]:
    """Fleet-backed §12.1: one report per tenant, one XLA grid call total.

    Records are grouped by ``SequentialLogRecord.tenant``; each tenant
    gets its own effective-k / dependency-type / data-seeded prior
    bootstrap (cheap, scalar-side), then every tenant's counterfactual EV
    grid is computed in a single jit'd
    ``batch_decision.counterfactual_grid_tenants`` call over the padded
    ``tenants x logs`` batch — the same move the multi-tenant replay
    engine makes for Phase-2 sweeps.  Per-tenant reports agree with
    running :func:`offline_replay` on each tenant's slice to f64 rounding.
    """
    if not logs:
        raise ValueError("offline replay requires at least one log record")
    tier_policy = tier_policy or TierPolicy()
    groups: dict[str, list[SequentialLogRecord]] = {}
    for r in logs:
        groups.setdefault(r.tenant, []).append(r)
    tenants = sorted(groups)

    seeds = {t: _seed_from_logs(groups[t], predictors, tier_policy)
             for t in tenants}

    from .batch_decision import counterfactual_grid_tenants

    n_max = max(len(groups[t]) for t in tenants)
    n_max = max(16, 1 << (n_max - 1).bit_length())  # bucket, as above
    T = len(tenants)
    P = np.array([seeds[t][3].mean for t in tenants])
    lat = np.zeros((T, n_max))
    cost = np.zeros((T, n_max))
    mask = np.zeros((T, n_max), bool)
    for i, t in enumerate(tenants):
        rows = groups[t]
        lat[i, : len(rows)] = [r.latency_s for r in rows]
        cost[i, : len(rows)] = [r.cost_usd for r in rows]
        mask[i, : len(rows)] = True
    with enable_x64():
        g = counterfactual_grid_tenants(
            P, lat, cost, mask,
            np.asarray(alphas, float), np.asarray(lambdas, float), rho=rho,
        )

    reports = {}
    for i, t in enumerate(tenants):
        ek, dep_type, match_rates, seeded = seeds[t]
        grid = _grid_points(g, i, alphas, lambdas)
        go, default_alpha = _go_and_default(grid, go_min_speculate_fraction)
        reports[t] = OfflineReplayReport(
            edge=edge,
            k_raw=ek.k_raw,
            p_mode=ek.p_mode,
            k_eff=ek.k_eff,
            dep_type=dep_type,
            seeded_prior=seeded,
            predictor_match_rates=match_rates,
            grid=grid,
            go=go,
            default_alpha=default_alpha,
        )
    return reports


# ---------------------------------------------------------------------------
# Stage 2: shadow mode (§12.2)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class TokenEstimator:
    """§4.2 EMA over historical output lengths, alpha_EMA = 0.2 default,
    plus the CoV-based uncertain_cost flag (§12.2/§12.4)."""

    ema: float = 0.0
    decay: float = 0.2
    n: int = 0
    history: list = dataclasses.field(default_factory=list)
    cov_threshold: float = 0.5

    def observe(self, output_tokens: float) -> float:
        self.history.append(output_tokens)
        self.ema = output_tokens if self.n == 0 else (
            self.decay * output_tokens + (1.0 - self.decay) * self.ema
        )
        self.n += 1
        return self.ema

    @property
    def cov(self) -> Optional[float]:
        if self.n < 2:
            return None
        m = statistics.fmean(self.history)
        return statistics.stdev(self.history) / m if m > 0 else None

    @property
    def uncertain_cost(self) -> bool:
        c = self.cov
        return c is not None and c > self.cov_threshold

    def estimate(self, sigma_ceiling: bool = False) -> float:
        """Point estimate; with sigma_ceiling, the §4.2 fixed-ceiling policy
        (estimated + 2*sigma)."""
        if sigma_ceiling and self.n >= 2:
            return self.ema + 2.0 * statistics.stdev(self.history)
        return self.ema


@dataclasses.dataclass
class ShadowReport:
    edge: tuple[str, str]
    trials: int
    posterior: BetaPosterior
    converged: bool
    best_tier2_threshold: float
    tier2_f1: float
    token_estimator: TokenEstimator
    rho_mean: float


def _stability_converged(means: Sequence[float], window: int, tol: float) -> bool:
    """§12.2 posterior-stability check: the trailing ``window`` means move
    by at most ``tol``.  Shared by the scalar stage and the table-batched
    twin (``repro.core.online.shadow_mode_batch``) so the two can never
    drift apart."""
    return (
        len(means) >= window
        and max(means[-window:]) - min(means[-window:]) <= tol
    )


def _tier2_threshold_sweep(
    graded_subset: Sequence[tuple[Any, Any, bool]],
    thresholds: Sequence[float],
) -> tuple[float, float]:
    """Tier-2 threshold grid sweep: maximize F1 against the human-graded
    subset (first strict improvement wins, matching the historical loop).
    Shared with ``repro.core.online.shadow_mode_batch``."""
    best_thr, best_f1 = 0.95, -1.0
    for thr in thresholds:
        tp = fp = fn = 0
        for i, i_hat, label in graded_subset:
            pred = check_success(i, i_hat, TierPolicy(similarity_threshold=thr)).success
            tp += int(pred and label)
            fp += int(pred and not label)
            fn += int((not pred) and label)
        denom = 2 * tp + fp + fn
        f1 = (2 * tp / denom) if denom else 0.0
        if f1 > best_f1:
            best_f1, best_thr = f1, thr
    return best_thr, best_f1


def shadow_mode(
    edge: tuple[str, str],
    posterior: BetaPosterior,
    trials: Sequence[tuple[Any, Any]],          # (i_actual, i_hat) per shadow trial
    *,
    graded_subset: Sequence[tuple[Any, Any, bool]] = (),  # (i, i_hat, human_label)
    thresholds: Sequence[float] = (0.80, 0.85, 0.90, 0.95, 0.99),
    output_token_counts: Sequence[float] = (),
    cancel_fractions: Sequence[float] = (),
    n_shadow: int = 100,
    stability_window: int = 50,
    stability_tol: float = 0.05,
) -> ShadowReport:
    """§12.2: speculative decisions served and discarded; posterior, tier-2
    threshold, token estimators, and rho tuned with zero user exposure.

    Zero exposure includes the *live* posterior: the caller's object is
    never mutated — shadow trials accumulate on an internal copy
    (returned as ``ShadowReport.posterior``), and the live belief only
    moves when the operator promotes the shadow result at stage
    boundaries (§12.6).  Previously the passed-in posterior was updated
    in place, which let a shadow run bleed into production gating.
    """
    posterior = posterior.copy()
    means: list[float] = []
    policy = TierPolicy()
    for i_actual, i_hat in trials:
        ok = check_success(i_actual, i_hat, policy).success
        posterior.update(ok)
        means.append(posterior.mean)

    converged = len(trials) >= n_shadow and _stability_converged(
        means, stability_window, stability_tol)

    # tier-2 threshold grid sweep: maximize F1 against the human-graded subset
    best_thr, best_f1 = _tier2_threshold_sweep(graded_subset, thresholds)

    est = TokenEstimator()
    for t in output_token_counts:
        est.observe(t)
    rho_mean = statistics.fmean(cancel_fractions) if cancel_fractions else 0.5
    return ShadowReport(
        edge=edge,
        trials=len(trials),
        posterior=posterior,
        converged=converged,
        best_tier2_threshold=best_thr,
        tier2_f1=max(best_f1, 0.0),
        token_estimator=est,
        rho_mean=rho_mean,
    )


# ---------------------------------------------------------------------------
# Stage 3: canary with alpha sweep + implied-lambda recovery (§12.3)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CanaryArm:
    name: str
    alpha: Optional[float]
    latency_s: float
    cost_usd: float
    waste_usd_per_hr: float = 0.0


@dataclasses.dataclass
class CanaryReport:
    arms: list[CanaryArm]
    pareto_alphas: list[float]
    lambda_implied: float
    lambda_declared: float
    audit: str                 # "refresh_lambda" | "consistent" | "inspect_declared"
    promote: bool              # go/no-go to full rollout


def _canary_sweep_eval(
    sweep: dict[float, tuple[float, float]],
    chosen_alpha: float,
    control_latency_s: float,
    control_cost_usd: float,
    budget_guardrail_usd: Optional[float],
) -> tuple[list[CanaryArm], list[float], bool]:
    """Arms list, Pareto frontier and promote verdict for one edge's
    canary sweep — shared by the scalar stage and the table-batched
    ``repro.core.online.canary_batch`` so the two can never drift."""
    arms = [CanaryArm("control", None, control_latency_s, control_cost_usd)]
    for a, (lat, cost) in sorted(sweep.items()):
        arms.append(CanaryArm(f"alpha={a}", a, lat, cost))

    # Pareto frontier over the sweep arms
    pts = sorted((lat, cost, a) for a, (lat, cost) in sweep.items())
    pareto: list[float] = []
    best_cost = float("inf")
    for lat, cost, a in pts:
        if cost < best_cost - 1e-12:
            pareto.append(a)
            best_cost = cost

    chosen = sweep.get(chosen_alpha)
    promote = False
    if chosen is not None:
        lat_ok = chosen[0] <= control_latency_s
        budget_ok = budget_guardrail_usd is None or chosen[1] <= budget_guardrail_usd
        # Pareto-dominates sequential: no worse on both, better on one
        dominates = (
            chosen[0] <= control_latency_s and chosen[1] <= control_cost_usd
            and (chosen[0] < control_latency_s or chosen[1] < control_cost_usd)
        ) or (lat_ok and budget_ok)
        promote = bool(lat_ok and budget_ok and dominates)
    return arms, pareto, promote


def canary(
    control_latency_s: float,
    control_cost_usd: float,
    sweep: dict[float, tuple[float, float]],     # alpha -> (latency, cost)
    chosen_alpha: float,
    P: float,
    C_spec: float,
    L_upstream_s: float,
    lambda_declared: float,
    *,
    budget_guardrail_usd: Optional[float] = None,
    consistency_band: float = 0.5,
) -> CanaryReport:
    """§12.3: percentage rollout with a held-out sequential control, the
    alpha sweep tracing the (latency, cost) Pareto frontier, and the
    implied-lambda audit at the chosen operating point."""
    arms, pareto, promote = _canary_sweep_eval(
        sweep, chosen_alpha, control_latency_s, control_cost_usd,
        budget_guardrail_usd)

    lam_imp = implied_lambda(P, C_spec, chosen_alpha, L_upstream_s)
    ratio = lam_imp / lambda_declared if lambda_declared > 0 else float("inf")
    if ratio > 1.0 + consistency_band:
        audit = "refresh_lambda"          # operators value latency MORE than priced
    elif ratio < 1.0 - consistency_band:
        audit = "inspect_declared"        # declared lambda over-values latency
    else:
        audit = "consistent"

    return CanaryReport(
        arms=arms,
        pareto_alphas=pareto,
        lambda_implied=lam_imp,
        lambda_declared=lambda_declared,
        audit=audit,
        promote=promote,
    )


# ---------------------------------------------------------------------------
# Stage 4: online calibration (§12.4)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CalibrationBucket:
    midpoint: float
    empirical_rate: float
    n: int
    within_ci: bool


@dataclasses.dataclass
class OnlineReport:
    buckets: list[CalibrationBucket]
    monotonic_overprediction: bool
    tier2_false_accept_rate: Optional[float]
    tier2_needs_tightening: bool
    token_cov: Optional[float]
    uncertain_cost: bool
    lambda_refresh_due: bool


def _calibration_bucket(
    mid: float, rate: float, n: int, bucket_width: float
) -> tuple[CalibrationBucket, bool]:
    """One §12.4 calibration bucket with its binomial-CI verdicts —
    shared by the scalar stage and the table-batched
    ``repro.core.online.online_calibration_batch``.  Returns
    (bucket, overpredicted)."""
    # binomial 95% CI half-width
    half = 1.96 * np.sqrt(max(rate * (1 - rate), 1e-9) / n) if n else 1.0
    within = abs(rate - mid) <= max(half, bucket_width / 2)
    return CalibrationBucket(mid, rate, n, within), rate < mid - half


def online_calibration(
    log: TelemetryLog,
    *,
    bucket_width: float = 0.1,
    tier2_tolerance: float = 0.05,
    cov_threshold: float = 0.5,
    quarters_since_lambda_refresh: int = 0,
) -> OnlineReport:
    """§12.4 four continuous checks, all derived from telemetry rows alone."""
    raw = log.calibration_buckets(bucket_width)
    buckets = []
    overpredicted = []
    for mid, (rate, n) in raw.items():
        bucket, over = _calibration_bucket(mid, rate, n, bucket_width)
        buckets.append(bucket)
        overpredicted.append(over)
    monotonic_over = len(overpredicted) >= 2 and all(overpredicted)

    far = log.tier2_false_accept_rate()
    cov = log.token_estimate_cov()
    return OnlineReport(
        buckets=buckets,
        monotonic_overprediction=monotonic_over,
        tier2_false_accept_rate=far,
        tier2_needs_tightening=far is not None and far > tier2_tolerance,
        token_cov=cov,
        uncertain_cost=cov is not None and cov > cov_threshold,
        lambda_refresh_due=quarters_since_lambda_refresh >= 1,
    )


# ---------------------------------------------------------------------------
# Fleet-replay -> posterior-store bridge (§12.1 deployment seeding at scale)
# ---------------------------------------------------------------------------
def seed_store_from_replay(
    store,
    report,
    grid_index: int = 0,
    **register_kwargs,
) -> list[int]:
    """Load one grid cell of a ``MultiTenantReport`` (the fleet replay
    engine's output) into a ``repro.core.store.PosteriorStore`` — the
    §12.1 "deploy with data-seeded priors" step, fleet-wide.

    Every (tenant, edge) row the replay produced is upserted: unknown
    keys register data-seeded from their final replay posterior (so the
    store's free-list / paging machinery owns them from birth), known
    keys get their alpha/beta overwritten in one batched scatter.
    Returns the logical row id per replay row, aligned with
    ``report.final_posterior_rows(grid_index)``.  Extra keyword
    arguments (``gamma=``, ``discount=``, ``floor_*=``...) pass through
    to ``PosteriorStore.register`` for the newly-created rows.
    """
    tenant_edges, alpha, beta = report.final_posterior_rows(grid_index)
    return store.adopt_posteriors(tenant_edges, alpha, beta,
                                  **register_kwargs)
