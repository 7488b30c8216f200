"""Vectorized fleet-scale replay engine (beyond-paper fast path).

The paper's calibration pipeline (§12: offline replay, shadow, canary,
online calibration) replays millions of logged decisions across an
(alpha, lambda) grid.  The paper-faithful discrete-event executor
(``repro.core.executor``) walks one episode at a time in Python; this
module lowers a frozen :class:`~repro.core.workflow.Workflow` DAG into
dense arrays and simulates

    episodes x (alpha, lambda) grid points x DAG ops

in a **single jit-compiled XLA call**: ``lax.scan`` over episodes (the
per-edge Beta posterior is the sequential carry, exactly as the scalar
path threads one ``BetaPosterior`` through a sweep), ``vmap`` over grid
points, and an inner ``lax.scan`` over ops in topological order (a
topological schedule of the DAG).

Semantics mirror ``executor.execute`` exactly — Phase-2 re-evaluation at
the upstream's start time, speculative launch/commit/cancel timing,
per-chunk streaming re-estimation (§9.1), fractional waste (§9.3),
discounted Beta updates (§14.3 / posterior.py) — and the parity suite
(tests/test_fleet_parity.py) asserts float64 agreement with the scalar
path on randomized DAGs: decisions, counts, event times and posterior
trajectories bitwise; EV/waste to 1 ULP (XLA contracts a*b + c into a
single FMA where CPython rounds twice).

Scope (checked at lowering time):

* at most one speculation-candidate edge per downstream op (the scalar
  executor has the same single-edge-per-op structure via its
  ``plan_edges`` map);
* constant alpha per grid point (no ``alpha_fn``); gating on either the
  posterior mean or the §7.5 one-sided credible bound
  (``use_lower_bound=True`` replaces ``a / (a + b)`` with the jax-native
  ``betaincinv(a, b, gamma)`` from ``repro.core.betainc`` inside the
  episode carry, so conservative-mode calibration sweeps stay one XLA
  call);
* predictions are summarized per episode as (exists, tier-success)
  booleans plus optional per-chunk confidences P_k — i.e. the replay
  consumes §7.4-labelled logs, it does not re-run predictors.

Recorded in EXPERIMENTS.md §Perf next to the scalar path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .admissibility import AdmissibilityTag
from .batch_decision import _f  # widest-enabled-float coercion, shared
from .betainc import betaincinv
from .planner import PlannerParams
from .workflow import Workflow

__all__ = [
    "EpisodeChunks",
    "FleetLowered",
    "FleetReport",
    "FleetStack",
    "MultiTenantReport",
    "lower_workflow",
    "fleet_replay",
    "chunk_episodes",
    "compose_segment_posteriors",
    "episode_sharded_replay",
    "stack_tenants",
    "multi_tenant_replay",
]


# ----------------------------------------------------------------- lowering
@dataclasses.dataclass(frozen=True)
class FleetLowered:
    """A frozen Workflow as dense arrays, ops indexed in topological order.

    Per-op edge fields describe the (unique) speculation-candidate edge
    into that op; ``has_edge`` masks ops without one.
    """

    names: tuple[str, ...]
    dur: np.ndarray            # (V,) simulated op duration (s)
    op_cost: np.ndarray        # (V,) base op cost (USD)
    parent_mask: np.ndarray    # (V, V) bool; parent_mask[v, u] = u -> v
    has_edge: np.ndarray       # (V,) bool: candidate edge into v exists
    u_onehot: np.ndarray       # (V, V) bool one-hot of the spec upstream
    u_streams: np.ndarray      # (V,) bool: upstream streams (enables §9)
    lat_save: np.ndarray       # (V,) latency savings L for the edge (s)
    in_tok: np.ndarray         # (V,) downstream input tokens
    out_tok: np.ndarray        # (V,) downstream output tokens
    in_price: np.ndarray       # (V,) USD / input token
    out_price: np.ndarray      # (V,) USD / output token
    pred_cost: np.ndarray      # (V,) predictor cost_estimate_s
    has_pred: np.ndarray       # (V,) bool: a predictor is attached
    streams: np.ndarray        # (V,) bool: downstream streams (cancel -> frac)
    has_refiner: np.ndarray    # (V,) bool: stream refiner attached (§9.1)
    n_chunks: np.ndarray       # (V,) upstream chunk count
    a0: np.ndarray             # (V,) prior Beta alpha per edge
    b0: np.ndarray             # (V,) prior Beta beta per edge
    discount: np.ndarray       # (V,) exponential forgetting factor
    # §7.5 credible-bound gating (from PlannerParams): gate the D4 rule on
    # Beta^{-1}(gamma; a, b) instead of the posterior mean
    use_lower_bound: bool = False
    gamma: float = 0.1
    # top-k beam speculation (repro.core.beam): per-op candidate predictor
    # confidences, sorted non-increasing along the row; None = the
    # single-candidate engine (equivalent to one certain candidate)
    beam_conf: Optional[np.ndarray] = None  # (V, W) or None

    @property
    def n_ops(self) -> int:
        return len(self.names)

    def edge_ops(self) -> list[int]:
        """Indices of ops with a speculation-candidate edge."""
        return [i for i in range(self.n_ops) if self.has_edge[i]]


def lower_workflow(
    wf: Workflow,
    params: PlannerParams,
    predictors: Optional[dict] = None,
    stream_refiners: Optional[dict] = None,
    *,
    default_chunks: int = 10,
    beam_confidences: Optional[dict] = None,
) -> FleetLowered:
    """Lower a frozen workflow + planner params to dense episode arrays.

    Mirrors the scalar path's per-edge inputs: latency savings default to
    min(lat_u, lat_v), prices come from the downstream op's pricing entry,
    priors from ``params.posterior_for`` (so data-seeded / discounted
    posteriors carry over).

    ``beam_confidences`` maps edge keys to per-candidate predictor
    confidence vectors (sorted non-increasing, summing to <= 1) for the
    top-k beam engine (``repro.core.beam``); edges without an entry keep
    the single-candidate default ``[1.0]``.  Omitting the mapping leaves
    ``beam_conf`` as None — the classic single-candidate lowering.

    §7.5 gating is taken from ``params.use_lower_bound`` / ``params.gamma``
    (the planner-side knobs).  The scalar executor reads its *own*
    ``ExecutorConfig.use_lower_bound`` / ``gamma`` for Phase-2, so when
    comparing fleet output against ``execute`` keep both objects set to
    the same values — the parity suite and benchmarks thread them in
    tandem.
    """
    from .pricing import get_pricing

    if not wf.frozen:
        raise ValueError("lower_workflow requires a frozen workflow")
    predictors = predictors or {}
    stream_refiners = stream_refiners or {}
    topo = wf.topo_order()
    idx = {n: i for i, n in enumerate(topo)}
    V = len(topo)

    dur = np.zeros(V)
    op_cost = np.zeros(V)
    parent_mask = np.zeros((V, V), bool)
    has_edge = np.zeros(V, bool)
    u_onehot = np.zeros((V, V), bool)
    u_streams = np.zeros(V, bool)
    lat_save = np.zeros(V)
    in_tok = np.zeros(V)
    out_tok = np.zeros(V)
    in_price = np.zeros(V)
    out_price = np.zeros(V)
    pred_cost = np.zeros(V)
    has_pred = np.zeros(V, bool)
    streams = np.zeros(V, bool)
    has_refiner = np.zeros(V, bool)
    n_chunks = np.zeros(V)
    a0 = np.ones(V)
    b0 = np.ones(V)
    discount = np.ones(V)

    candidates = {}
    for edge in wf.speculation_candidates():
        v = edge.downstream
        if v in candidates:
            raise NotImplementedError(
                f"op {v!r} has multiple speculation-candidate edges; the "
                "fleet lowering (like the scalar executor's plan_edges map) "
                "supports one per downstream op"
            )
        candidates[v] = edge

    for name, i in idx.items():
        op = wf.ops[name]
        dur[i] = float(op.metadata.get("sim_latency_s", op.latency_est_s))
        pricing = get_pricing(op.provider, op.model)
        op_cost[i] = (
            op.input_tokens_est * pricing.input_price_per_token
            + op.output_tokens_est * pricing.output_price_per_token
        )
        for p in wf.parents(name):
            parent_mask[i, idx[p]] = True
        edge = candidates.get(name)
        if edge is None:
            continue
        if op.admissibility == AdmissibilityTag.NON_SPECULABLE:
            continue  # speculation_candidates already excludes these
        u = edge.upstream
        up = wf.ops[u]
        has_edge[i] = True
        u_onehot[i, idx[u]] = True
        u_streams[i] = up.streams
        lat_save[i] = params.latency_savings_s.get(
            edge.key, min(up.latency_est_s, op.latency_est_s)
        )
        in_tok[i] = op.input_tokens_est
        out_tok[i] = op.output_tokens_est
        in_price[i] = pricing.input_price_per_token
        out_price[i] = pricing.output_price_per_token
        pred = predictors.get(edge.key)
        has_pred[i] = pred is not None
        pred_cost[i] = getattr(pred, "cost_estimate_s", 0.0) if pred else 0.0
        streams[i] = op.streams
        has_refiner[i] = edge.key in stream_refiners
        n_chunks[i] = float(up.metadata.get("chunks", default_chunks))
        post = params.posterior_for(edge)
        a0[i], b0[i] = post.alpha, post.beta
        discount[i] = post.discount

    beam_conf = None
    if beam_confidences:
        from .beam import validate_confidences

        rows = {}
        for key, confs in beam_confidences.items():
            v = key[1] if isinstance(key, tuple) else key
            if v not in idx:
                raise KeyError(f"beam_confidences names unknown op {v!r}")
            rows[idx[v]] = validate_confidences(confs)
        W = max(len(c) for c in rows.values())
        beam_conf = np.zeros((V, W))
        beam_conf[:, 0] = 1.0  # single certain candidate by default
        for i, confs in rows.items():
            beam_conf[i, : len(confs)] = confs

    return FleetLowered(
        names=tuple(topo), dur=dur, op_cost=op_cost, parent_mask=parent_mask,
        has_edge=has_edge, u_onehot=u_onehot, u_streams=u_streams,
        lat_save=lat_save, in_tok=in_tok, out_tok=out_tok, in_price=in_price,
        out_price=out_price, pred_cost=pred_cost, has_pred=has_pred,
        streams=streams, has_refiner=has_refiner, n_chunks=n_chunks,
        a0=a0, b0=b0, discount=discount,
        use_lower_bound=bool(params.use_lower_bound),
        gamma=float(params.gamma),
        beam_conf=beam_conf,
    )


# -------------------------------------------------------------------- report
@dataclasses.dataclass(frozen=True)
class FleetReport:
    """Aggregates plus full per-episode trajectories.

    All arrays are numpy; shapes use G = grid points, E = episodes,
    V = ops in topo order (per-edge fields valid where ``has_edge``).
    """

    alphas: np.ndarray          # (G,)
    lambdas: np.ndarray         # (G,)
    makespan_s: np.ndarray      # (E, G)
    total_cost_usd: np.ndarray  # (E, G)
    waste_usd: np.ndarray       # (E, G)
    launched: np.ndarray        # (E, G)
    committed: np.ndarray       # (E, G)
    cancelled: np.ndarray       # (E, G)
    EV_usd: np.ndarray          # (E, G, V) Phase-2 EV per candidate edge
    threshold_usd: np.ndarray   # (E, G, V)
    speculate: np.ndarray       # (E, G, V) Phase-2 D4 verdict
    edge_launched: np.ndarray   # (E, G, V)
    edge_committed: np.ndarray  # (E, G, V)
    edge_waste_usd: np.ndarray  # (E, G, V)
    start_s: np.ndarray         # (E, G, V)
    finish_s: np.ndarray        # (E, G, V)
    post_alpha: np.ndarray      # (E, G, V) posterior after each episode
    post_beta: np.ndarray       # (E, G, V)
    ep_mask: np.ndarray = None  # (E,) bool; False rows were identity
                                # (padded) episodes with zeroed stats

    def pareto(self) -> dict:
        """Per-grid-point mean (latency, cost, waste) — the §12.3 canary
        Pareto the calibration stage consumes.  Means are taken over the
        real episodes only (``ep_mask``), so padded identity rows do not
        dilute the statistics."""
        rows = slice(None) if self.ep_mask is None else np.asarray(
            self.ep_mask, bool)
        return {
            "alphas": self.alphas,
            "lambdas": self.lambdas,
            "latency_s": self.makespan_s[rows].mean(0),
            "cost_usd": self.total_cost_usd[rows].mean(0),
            "waste_usd": self.waste_usd[rows].mean(0),
            "launched": self.launched[rows].sum(0),
            "committed": self.committed[rows].sum(0),
        }


# -------------------------------------------------------------- fleet sweep
def _normalize_grid(alphas, lambdas):
    """Paired (alpha, lambda) grid points; a scalar lambda broadcasts."""
    alphas = np.atleast_1d(np.asarray(alphas, float))
    lambdas = np.atleast_1d(np.asarray(lambdas, float))
    if lambdas.shape[0] == 1 and alphas.shape[0] > 1:
        lambdas = np.broadcast_to(lambdas, alphas.shape).copy()
    if alphas.shape != lambdas.shape:
        raise ValueError("alphas and lambdas must be paired (same length)")
    return alphas, lambdas


def _normalize_episodes(lowered, success, pred_ok, chunk_P, ep_mask):
    """Defaulted / validated episode arrays shared by ``fleet_replay``,
    :func:`chunk_episodes` and :func:`episode_sharded_replay`: ``pred_ok``
    defaults to the lowering's predictor mask, ``chunk_P`` to a single
    unit chunk with streaming refiners disabled, ``ep_mask`` to all-real
    episodes."""
    success = np.asarray(success, bool)
    if success.ndim != 2:
        raise ValueError("success must have shape (E, V)")
    E, V = success.shape
    if V != lowered.n_ops:
        raise ValueError(f"success has {V} ops, workflow has {lowered.n_ops}")
    if pred_ok is None:
        pred_ok = np.broadcast_to(lowered.has_pred, (E, V)).copy()
    if chunk_P is None:
        K = 1
        chunk_P = np.ones((E, V, 1))
        has_refiner = np.zeros(V, bool)
    else:
        chunk_P = np.asarray(chunk_P, float)
        K = chunk_P.shape[-1]
        has_refiner = lowered.has_refiner
    if ep_mask is None:
        ep_mask = np.ones(E, bool)
    else:
        ep_mask = np.asarray(ep_mask, bool)
        if ep_mask.shape != (E,):
            raise ValueError(f"ep_mask must have shape ({E},)")
    return (success, np.asarray(pred_ok, bool), chunk_P, ep_mask,
            has_refiner, K)


def _normalize_replay_args(lowered, success, alphas, lambdas, pred_ok,
                           chunk_P, ep_mask):
    alphas, lambdas = _normalize_grid(alphas, lambdas)
    (success, pred_ok, chunk_P, ep_mask, has_refiner,
     K) = _normalize_episodes(lowered, success, pred_ok, chunk_P, ep_mask)
    return (alphas, lambdas, success, pred_ok, chunk_P, ep_mask,
            has_refiner, K)


def fleet_replay(
    lowered: FleetLowered,
    success: np.ndarray,
    alphas,
    lambdas,
    *,
    pred_ok: Optional[np.ndarray] = None,
    chunk_P: Optional[np.ndarray] = None,
    throttle_every: int = 1,
    ep_mask: Optional[np.ndarray] = None,
) -> FleetReport:
    """Replay E episodes x G grid points in one jit'd XLA call.

    Args:
      lowered: output of :func:`lower_workflow`.
      success: (E, V) bool — per-episode tier success of the candidate
        edge into op v (§7.4 label); ignored where ``has_edge`` is False.
      alphas / lambdas: length-G paired grid points (mesh them for a full
        §12.1 cross product); a scalar lambda broadcasts over alphas.
      pred_ok: (E, V) bool — a prediction existed at launch (default: the
        lowering's ``has_pred``).
      chunk_P: (E, V, K) refined per-chunk confidences P_k for §9.1
        mid-stream re-estimation; omit to disable streaming cancels.
      throttle_every: §9.1 throttling — re-evaluate every N chunks.
      ep_mask: (E,) bool — episodes with a False mask are identity scan
        steps: the posterior carry passes through unchanged, per-episode
        stats report as zero (posterior columns report the carried
        values).  This is what lets ragged per-tenant episode logs pad to
        a common length without perturbing anyone's trajectory.

    The per-edge Beta posterior is carried sequentially across episodes
    (scan), independently per grid point (vmap), exactly like running the
    scalar sweep once per grid point.  When the lowering carries
    ``use_lower_bound=True`` (§7.5), the Phase-2 gate inverts the carried
    posterior — ``betaincinv(a, b, gamma)`` — in place of the mean, so
    the conservative mode tracks the evolving counts exactly like the
    scalar executor's ``post.lower_bound(gamma)``.
    """
    (alphas, lambdas, success, pred_ok, chunk_P, ep_mask, has_refiner,
     K) = _normalize_replay_args(
        lowered, success, alphas, lambdas, pred_ok, chunk_P, ep_mask)
    ys = _fleet_scan(
        _pack_static(lowered, has_refiner),
        _f(lowered.a0), _f(lowered.b0), _f(lowered.discount),
        _f(alphas), _f(lambdas), _f(lowered.gamma),
        jnp.asarray(success), jnp.asarray(pred_ok, bool),
        _f(chunk_P), jnp.asarray(ep_mask), int(throttle_every), int(K),
        bool(lowered.use_lower_bound),
    )
    np_out = {k: np.asarray(v) for k, v in ys.items()}
    return FleetReport(alphas=alphas, lambdas=lambdas, ep_mask=ep_mask,
                       **np_out)


def _pack_static(lowered: FleetLowered, has_refiner: np.ndarray):
    return (
        jnp.asarray(lowered.parent_mask),
        jnp.asarray(lowered.u_onehot),
        _f(lowered.dur), _f(lowered.op_cost),
        jnp.asarray(lowered.has_edge),
        jnp.asarray(lowered.u_streams),
        _f(lowered.lat_save), _f(lowered.in_tok), _f(lowered.out_tok),
        _f(lowered.in_price), _f(lowered.out_price), _f(lowered.pred_cost),
        jnp.asarray(lowered.has_pred),
        jnp.asarray(lowered.streams),
        jnp.asarray(has_refiner),
        _f(lowered.n_chunks),
    )


def _scan_core(static, post0, discount, alphas, lambdas, gamma,
               success, pred_ok, chunk_P, ep_mask, throttle_every, K,
               use_lower_bound):
    """Episode scan for one workflow: carry (G, V, 2) posteriors across E
    episodes, vmapped over the G grid points.  ``ep_mask`` turns padded
    episodes into identity steps (unchanged carry, zeroed stats) so ragged
    per-tenant logs can share one scan length.  Returns the final carry —
    the donation target for repeated calibration rounds — plus the stats.
    """
    episode = functools.partial(
        _episode, static, discount, (K, throttle_every),
        use_lower_bound, gamma,
    )

    def ep_step(post_ab, xs):
        succ_e, pred_e, chunks_e, mask_e = xs
        # vmap over grid points: independent posterior trajectory each
        post_new, stats = jax.vmap(
            episode, in_axes=(0, 0, 0, None, None, None)
        )(post_ab, alphas, lambdas, succ_e, pred_e, chunks_e)
        post_new = jnp.where(mask_e, post_new, post_ab)
        # masked steps are identity updates: stats zero out, the posterior
        # columns keep reporting the carried (unchanged) values
        stats = {
            k: jnp.where(mask_e, v, jnp.zeros_like(v))
            for k, v in stats.items()
        }
        stats["post_alpha"] = jnp.where(mask_e, stats["post_alpha"],
                                        post_ab[..., 0])
        stats["post_beta"] = jnp.where(mask_e, stats["post_beta"],
                                       post_ab[..., 1])
        return post_new, stats

    return jax.lax.scan(ep_step, post0, (success, pred_ok, chunk_P, ep_mask))


@functools.partial(
    jax.jit, static_argnames=("throttle_every", "K", "use_lower_bound")
)
def _fleet_scan(static, a0, b0, discount, alphas, lambdas, gamma,
                success, pred_ok, chunk_P, ep_mask, throttle_every, K,
                use_lower_bound):
    G = alphas.shape[0]
    V = a0.shape[0]
    post0 = jnp.broadcast_to(jnp.stack([a0, b0], -1)[None], (G, V, 2))
    _, ys = _scan_core(static, post0, discount, alphas, lambdas, gamma,
                       success, pred_ok, chunk_P, ep_mask, throttle_every,
                       K, use_lower_bound)
    return ys


def _episode(static, discount, chunk_cfg, use_lower_bound, gamma,
             post_ab, alpha, lam, succ, pred_ok, chunk_P):
    """One episode at one grid point.  All per-op arrays have length V."""
    (parent_mask, u_onehot, dur, op_cost, has_edge, u_streams, lat_save,
     in_tok, out_tok, in_price, out_price, pred_cost, has_pred, streams,
     has_refiner, n_chunks) = static
    K, throttle_every = chunk_cfg
    V = dur.shape[0]
    a, b = post_ab[:, 0], post_ab[:, 1]
    if use_lower_bound:
        # §7.5 conservative gate: one-sided (1-gamma) lower credible
        # bound, inverted from the carried counts inside the scan —
        # mirrors the scalar path's post.lower_bound(gamma) per episode.
        P = betaincinv(a, b, gamma)
    else:
        P = a / (a + b)
    neg = jnp.asarray(-jnp.inf, dur.dtype)

    # Phase-2 D4 gate, identical expression order to decision.evaluate
    # (§6.1) so float64 results match the scalar path bitwise
    C_spec = in_tok * in_price + out_tok * out_price
    L_value = lat_save * lam
    EV = P * L_value - (1.0 - P) * C_spec
    threshold = (1.0 - alpha) * C_spec
    spec_dec = EV >= threshold
    c_in = in_tok * in_price

    k_idx = jnp.arange(K)

    def step(carry, xs):
        start, finish = carry
        (pmask, umask, dur_v, spec_v, pc_v, launch_gate_v, streams_v,
         u_streams_v, has_ref_v, nch_v, c_in_v, out_tok_v, out_price_v,
         Lval_v, Cspec_v, thr_v, succ_v, pred_ok_v, P_chunks_v, vmask) = xs
        # plain-path ready time: all parents finished
        t_ready = jnp.max(jnp.where(pmask, finish, neg), initial=0.0)
        start_u = jnp.sum(jnp.where(umask, start, 0.0))
        finish_u = jnp.sum(jnp.where(umask, finish, 0.0))
        other_ready = jnp.max(jnp.where(pmask & ~umask, finish, neg),
                              initial=0.0)
        launched = spec_v & launch_gate_v & pred_ok_v
        t_launch = jnp.maximum(start_u + pc_v, other_ready)

        # §9.1 vectorized per-chunk re-estimation: EV_k with refined P_k,
        # same L_value / C_spec / threshold; first WAIT verdict cancels
        u_dur = finish_u - start_u
        valid_k = (
            (k_idx < nch_v) & (k_idx % throttle_every == 0)
            & launched & u_streams_v & has_ref_v
        )
        EV_k = P_chunks_v * Lval_v - (1.0 - P_chunks_v) * Cspec_v
        cancel_k = valid_k & (EV_k < thr_v)
        cancelled = cancel_k.any()
        first_k = jnp.argmax(cancel_k)
        t_chunk = start_u + (first_k + 1.0) / jnp.maximum(nch_v, 1.0) * u_dur
        elapsed_c = jnp.maximum(0.0, t_chunk - t_launch)
        frac_c = jnp.where(dur_v > 0.0,
                           jnp.minimum(1.0, elapsed_c / dur_v), 1.0)

        committed = launched & succ_v & ~cancelled
        # timing mirrors executor.execute: commit at max(spec finish,
        # u finish); failure / cancel re-executes after u
        t1_commit = jnp.maximum(t_launch + dur_v, finish_u)
        t0 = jnp.where(committed, t_launch,
                       jnp.where(launched, finish_u, t_ready))
        t1 = jnp.where(committed, t1_commit,
                       jnp.where(launched, finish_u + dur_v,
                                 t_ready + dur_v))

        # §9.3 fractional waste (fractional_waste expression order:
        # c_in + (frac * out_tok) * out_price); non-streaming downstream
        # cannot cancel mid-generation -> full C_spec on tier failure
        elapsed_f = jnp.maximum(0.0, finish_u - t_launch)
        frac_f = jnp.where(dur_v > 0.0,
                           jnp.minimum(1.0, elapsed_f / dur_v), 1.0)
        frac_f = jnp.where(streams_v, frac_f, 1.0)
        frac = jnp.where(cancelled, frac_c, frac_f)
        waste_v = c_in_v + (frac * out_tok_v) * out_price_v
        waste_v = jnp.where(launched & ~committed, waste_v, 0.0)

        start = jnp.where(vmask, t0, start)
        finish = jnp.where(vmask, t1, finish)
        outs = (launched, committed, launched & cancelled, waste_v, t0, t1)
        return (start, finish), outs

    xs = (
        parent_mask, u_onehot, dur, spec_dec, pred_cost,
        has_edge & has_pred, streams, u_streams, has_refiner, n_chunks,
        c_in, out_tok, out_price, L_value, C_spec, threshold,
        succ, pred_ok, chunk_P, jnp.eye(V, dtype=bool),
    )
    init = (jnp.zeros(V, dur.dtype), jnp.zeros(V, dur.dtype))
    (start, finish), (launched, committed, cancelled, waste,
                      t0s, t1s) = jax.lax.scan(step, init, xs)

    # discounted conjugate update (BetaPosterior.update, §14.3): only
    # launched edges observe a Bernoulli trial; d=1 reduces to a+1 / b+1
    suc_f = committed.astype(a.dtype)
    a_new = jnp.where(launched, a * discount + suc_f, a)
    b_new = jnp.where(launched, b * discount + (1.0 - suc_f), b)
    post_new = jnp.stack([a_new, b_new], -1)

    waste_total = waste.sum()
    stats = {
        "makespan_s": jnp.max(finish, initial=0.0),
        "total_cost_usd": op_cost.sum() + waste_total,
        "waste_usd": waste_total,
        "launched": launched.sum(),
        "committed": committed.sum(),
        "cancelled": cancelled.sum(),
        "EV_usd": EV,
        "threshold_usd": threshold,
        "speculate": spec_dec,
        "edge_launched": launched,
        "edge_committed": committed,
        "edge_waste_usd": waste,
        "start_s": t0s,
        "finish_s": t1s,
        "post_alpha": a_new,
        "post_beta": b_new,
    }
    return post_new, stats


# ---------------------------------------------------------- multi-tenant
def _pad_lowered(lowered: FleetLowered, V: int) -> FleetLowered:
    """Pad a lowering to V ops with inert slots.

    Padded ops have zero duration/cost, no parents, no candidate edge and
    a unit Beta prior, so they never launch, never contribute to makespan,
    cost or waste, and their posterior carry is a fixed point — a tenant
    padded to a larger ``V_max`` replays bitwise-identically to its
    unpadded lowering on the real op columns.
    """
    pad = V - lowered.n_ops
    if pad < 0:
        raise ValueError(f"cannot pad {lowered.n_ops} ops down to {V}")
    if pad == 0:
        return lowered

    def zeros(x):
        return np.concatenate([x, np.zeros(pad, x.dtype)])

    def fill(x, value):
        return np.concatenate([x, np.full(pad, value, x.dtype)])

    def square(x):
        out = np.zeros((V, V), x.dtype)
        out[: lowered.n_ops, : lowered.n_ops] = x
        return out

    return FleetLowered(
        names=lowered.names + tuple(f"__pad{i}" for i in range(pad)),
        dur=zeros(lowered.dur), op_cost=zeros(lowered.op_cost),
        parent_mask=square(lowered.parent_mask),
        has_edge=zeros(lowered.has_edge),
        u_onehot=square(lowered.u_onehot),
        u_streams=zeros(lowered.u_streams),
        lat_save=zeros(lowered.lat_save),
        in_tok=zeros(lowered.in_tok), out_tok=zeros(lowered.out_tok),
        in_price=zeros(lowered.in_price), out_price=zeros(lowered.out_price),
        pred_cost=zeros(lowered.pred_cost), has_pred=zeros(lowered.has_pred),
        streams=zeros(lowered.streams), has_refiner=zeros(lowered.has_refiner),
        n_chunks=fill(lowered.n_chunks, 1.0),
        a0=fill(lowered.a0, 1.0), b0=fill(lowered.b0, 1.0),
        discount=fill(lowered.discount, 1.0),
        use_lower_bound=lowered.use_lower_bound, gamma=lowered.gamma,
        beam_conf=None if lowered.beam_conf is None else np.concatenate(
            [lowered.beam_conf,
             np.concatenate(
                 [np.ones((pad, 1)),
                  np.zeros((pad, lowered.beam_conf.shape[1] - 1))], axis=1)]
        ),
    )


@dataclasses.dataclass(frozen=True)
class FleetStack:
    """T tenants stacked along a new leading batch axis.

    Each tenant is a :class:`FleetLowered` padded to the common ``V_max``
    plus its episode log padded to the common ``E_max`` (``ep_mask`` marks
    the real episodes; padded ones are identity scan steps).  The stack is
    what :func:`multi_tenant_replay` partitions across devices.
    """

    tenants: tuple[str, ...]
    lowered: tuple[FleetLowered, ...]   # padded to the common V_max
    n_ops: tuple[int, ...]              # pre-padding op counts
    n_episodes: tuple[int, ...]         # pre-padding episode counts
    success: np.ndarray                 # (T, E_max, V_max) bool
    pred_ok: np.ndarray                 # (T, E_max, V_max) bool
    chunk_P: np.ndarray                 # (T, E_max, V_max, K)
    ep_mask: np.ndarray                 # (T, E_max) bool
    has_refiner: np.ndarray             # (T, V_max) bool (zeroed where the
                                        # tenant supplied no chunk_P)
    use_lower_bound: bool

    @property
    def T(self) -> int:
        return len(self.tenants)

    @property
    def V(self) -> int:
        return self.success.shape[2]

    @property
    def E(self) -> int:
        return self.success.shape[1]

    @property
    def K(self) -> int:
        return self.chunk_P.shape[-1]

    @property
    def gammas(self) -> np.ndarray:
        return np.array([l.gamma for l in self.lowered])

    def edge_keys(self) -> tuple[tuple[tuple[int, tuple[str, str]], ...], ...]:
        """Per tenant: (op index, (upstream, downstream)) for each
        speculation-candidate edge — the taxonomy keys the drift monitor
        and calibration stages address posteriors by."""
        out = []
        for low in self.lowered:
            keys = []
            for v in low.edge_ops():
                u = int(np.argmax(low.u_onehot[v]))
                keys.append((v, (low.names[u], low.names[v])))
            out.append(tuple(keys))
        return tuple(out)

    def device_args(self):
        """Device-side argument tuple for the replay executable, memoized
        per float dtype (the ``_f`` convention resolves f32/f64 from
        ``jax_enable_x64`` at call time).

        Repeated calibration rounds over an unchanged stack — the
        replay / re-gate / replay loop the donated posterior carry exists
        for — would otherwise re-run ~20 ``np.stack`` copies and
        host->device transfers per round (two V_max x V_max matrices per
        tenant among them); memoizing here makes every round after the
        first reuse the staged buffers.  The memo writes straight into
        ``__dict__`` (allowed on frozen dataclasses) and pins the arrays
        for the stack's lifetime.
        """
        key = f"_device_args_{jnp.result_type(float).name}"
        cached = self.__dict__.get(key)
        if cached is not None:
            return cached
        lows = self.lowered
        static = (
            jnp.asarray(np.stack([l.parent_mask for l in lows])),
            jnp.asarray(np.stack([l.u_onehot for l in lows])),
            _f(np.stack([l.dur for l in lows])),
            _f(np.stack([l.op_cost for l in lows])),
            jnp.asarray(np.stack([l.has_edge for l in lows])),
            jnp.asarray(np.stack([l.u_streams for l in lows])),
            _f(np.stack([l.lat_save for l in lows])),
            _f(np.stack([l.in_tok for l in lows])),
            _f(np.stack([l.out_tok for l in lows])),
            _f(np.stack([l.in_price for l in lows])),
            _f(np.stack([l.out_price for l in lows])),
            _f(np.stack([l.pred_cost for l in lows])),
            jnp.asarray(np.stack([l.has_pred for l in lows])),
            jnp.asarray(np.stack([l.streams for l in lows])),
            jnp.asarray(self.has_refiner),
            _f(np.stack([l.n_chunks for l in lows])),
        )
        cached = (
            static,
            _f(np.stack([l.a0 for l in lows])),
            _f(np.stack([l.b0 for l in lows])),
            _f(np.stack([l.discount for l in lows])),
            _f(self.gammas),
            jnp.asarray(self.success),
            jnp.asarray(self.pred_ok),
            _f(self.chunk_P),
            jnp.asarray(self.ep_mask),
        )
        self.__dict__[key] = cached
        return cached


def stack_tenants(
    lowereds,
    successes,
    *,
    pred_oks=None,
    chunk_Ps=None,
    tenants=None,
) -> FleetStack:
    """Stack per-tenant (lowering, episode log) pairs into one batch.

    Ragged shapes are padded: ops to ``V_max`` (inert slots, see
    :func:`_pad_lowered`), episodes to ``E_max`` (masked identity steps).
    Every tenant keeps its own taxonomy-keyed prior ``(a0, b0)``, discount
    and §7.5 gamma; ``use_lower_bound`` must agree across tenants because
    it selects the compiled gate expression.
    """
    T = len(lowereds)
    if T == 0:
        raise ValueError("stack_tenants requires at least one tenant")
    if len(successes) != T:
        raise ValueError("one success array per tenant required")
    if tenants is None:
        tenants = tuple(f"tenant{t}" for t in range(T))
    tenants = tuple(tenants)
    if len(set(tenants)) != T:
        raise ValueError("tenant names must be unique")
    pred_oks = list(pred_oks) if pred_oks is not None else [None] * T
    chunk_Ps = list(chunk_Ps) if chunk_Ps is not None else [None] * T
    if len(pred_oks) != T or len(chunk_Ps) != T:
        raise ValueError("pred_oks / chunk_Ps must align with tenants")
    use_lb = {bool(l.use_lower_bound) for l in lowereds}
    if len(use_lb) != 1:
        raise ValueError(
            "use_lower_bound must agree across stacked tenants (it selects "
            "the compiled §7.5 gate); split mixed fleets into two stacks"
        )

    n_ops = tuple(l.n_ops for l in lowereds)
    successes = [np.asarray(s, bool) for s in successes]
    for t, (low, suc) in enumerate(zip(lowereds, successes)):
        if suc.ndim != 2 or suc.shape[1] != low.n_ops:
            raise ValueError(
                f"tenant {tenants[t]!r}: success must be (E, {low.n_ops})"
            )
    n_eps = tuple(s.shape[0] for s in successes)
    V = max(n_ops)
    E = max(n_eps)
    provided_K = {np.asarray(c).shape[-1] for c in chunk_Ps if c is not None}
    if len(provided_K) > 1:
        raise ValueError("chunk_P K must agree across tenants that stream")
    K = provided_K.pop() if provided_K else 1

    padded = tuple(_pad_lowered(l, V) for l in lowereds)
    success = np.zeros((T, E, V), bool)
    pred_ok = np.zeros((T, E, V), bool)
    chunk_P = np.ones((T, E, V, K))
    ep_mask = np.zeros((T, E), bool)
    has_refiner = np.zeros((T, V), bool)
    for t, low in enumerate(lowereds):
        e_t, v_t = n_eps[t], n_ops[t]
        success[t, :e_t, :v_t] = successes[t]
        po = pred_oks[t]
        if po is None:
            po = np.broadcast_to(low.has_pred, (e_t, v_t))
        pred_ok[t, :e_t, :v_t] = np.asarray(po, bool)
        cp = chunk_Ps[t]
        if cp is not None:
            cp = np.asarray(cp, float)
            if cp.shape != (e_t, v_t, K):
                raise ValueError(
                    f"tenant {tenants[t]!r}: chunk_P must be "
                    f"({e_t}, {v_t}, {K})"
                )
            chunk_P[t, :e_t, :v_t] = cp
            has_refiner[t, :v_t] = low.has_refiner
        ep_mask[t, :e_t] = True

    return FleetStack(
        tenants=tenants, lowered=padded, n_ops=n_ops, n_episodes=n_eps,
        success=success, pred_ok=pred_ok, chunk_P=chunk_P, ep_mask=ep_mask,
        has_refiner=has_refiner, use_lower_bound=use_lb.pop(),
    )


@dataclasses.dataclass(frozen=True)
class MultiTenantReport:
    """Per-tenant fleet reports plus the donatable posterior carry.

    Stat arrays are numpy with a leading T axis over the stacked tenants
    (then E episodes, G grid points, V_max ops as in
    :class:`FleetReport`); rows past a tenant's real episode count are
    identity steps (zero stats, carried posteriors).  ``post_final`` stays
    a jax array — feed it back as ``post0`` (with ``donate=True``) so
    repeated calibration rounds reuse the same device buffer.
    """

    tenants: tuple[str, ...]
    alphas: np.ndarray
    lambdas: np.ndarray
    n_ops: tuple[int, ...]
    n_episodes: tuple[int, ...]
    ep_mask: np.ndarray
    edge_keys: tuple
    post_final: object          # jax (T, G, V, 2)
    makespan_s: np.ndarray      # (T, E, G)
    total_cost_usd: np.ndarray
    waste_usd: np.ndarray
    launched: np.ndarray
    committed: np.ndarray
    cancelled: np.ndarray
    EV_usd: np.ndarray          # (T, E, G, V)
    threshold_usd: np.ndarray
    speculate: np.ndarray
    edge_launched: np.ndarray
    edge_committed: np.ndarray
    edge_waste_usd: np.ndarray
    start_s: np.ndarray
    finish_s: np.ndarray
    post_alpha: np.ndarray
    post_beta: np.ndarray

    def tenant_report(self, t: int) -> FleetReport:
        """Slice tenant ``t`` back to a single-workflow :class:`FleetReport`
        (real episodes and ops only)."""
        e_t, v_t = self.n_episodes[t], self.n_ops[t]
        kw = {}
        for f in dataclasses.fields(FleetReport):
            if f.name in ("alphas", "lambdas"):
                continue
            arr = getattr(self, f.name)[t]
            kw[f.name] = arr[:e_t, :, :v_t] if arr.ndim == 3 else arr[:e_t]
        return FleetReport(alphas=self.alphas, lambdas=self.lambdas, **kw)

    def final_posterior_rows(self, grid_index: int = 0):
        """Flatten the final per-(tenant, edge) posteriors at one grid
        point into the row layout
        ``DriftMonitor.check_credible_bound_batch`` consumes:
        ``([(tenant, edge), ...], post_alpha, post_beta)``.

        ``grid_index`` must address one of the replay's G grid points;
        out-of-range (or negative) indices raise instead of silently
        wrapping — a wrapped index would hand the drift monitor a
        *different operating point's* posteriors, which is exactly the
        kind of row mixup the kill-switch exists to prevent."""
        G = self.post_final.shape[1]
        if not (0 <= int(grid_index) < G):
            raise IndexError(
                f"grid_index {grid_index} out of range: this report has "
                f"{G} grid point(s) (valid: 0..{G - 1})")
        post = np.asarray(self.post_final)
        tenant_edges, a, b = [], [], []
        for t, keys in enumerate(self.edge_keys):
            for v, key in keys:
                tenant_edges.append((self.tenants[t], key))
                a.append(post[t, grid_index, v, 0])
                b.append(post[t, grid_index, v, 1])
        return tenant_edges, np.asarray(a), np.asarray(b)

    def pareto(self) -> dict:
        """Per-tenant §12.3 Pareto dicts keyed by tenant name."""
        return {
            name: self.tenant_report(t).pareto()
            for t, name in enumerate(self.tenants)
        }


@functools.lru_cache(maxsize=None)
def _mt_executable(mesh, axis_name, throttle_every, K, use_lower_bound,
                   donate):
    """Compile (and cache) the tenant-vmapped, optionally shard_map'd
    episode scan.  The cache key carries the mesh object itself, so one
    process can serve sharded and unsharded fleets side by side."""

    def run(static, post0, discount, alphas, lambdas, gamma,
            success, pred_ok, chunk_P, ep_mask):
        def one(st, p0, d, g, s, pk, cp, em):
            return _scan_core(st, p0, d, alphas, lambdas, g, s, pk, cp, em,
                              throttle_every, K, use_lower_bound)

        return jax.vmap(one)(static, post0, discount, gamma,
                             success, pred_ok, chunk_P, ep_mask)

    if mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec

        t = PartitionSpec(axis_name)
        r = PartitionSpec()
        run = shard_map(
            run, mesh=mesh,
            # leading tenant axis partitioned; the (alpha, lambda) grid is
            # replicated and rides along under the per-shard vmap
            in_specs=(t, t, t, r, r, t, t, t, t, t),
            out_specs=t,
            check_vma=False,
        )
    return jax.jit(run, donate_argnums=(1,) if donate else ())


def multi_tenant_replay(
    stack: FleetStack,
    alphas,
    lambdas,
    *,
    throttle_every: int = 1,
    mesh=None,
    axis_name: str = "fleet",
    post0=None,
    donate: bool = False,
) -> MultiTenantReport:
    """Replay T tenants x E episodes x G grid points in one XLA call.

    The tenant axis is vmapped over per-tenant DAGs, priors, gammas and
    episode logs; with ``mesh`` (a 1-D mesh such as
    ``repro.launch.mesh.make_fleet_mesh()``) the ``tenants x grid`` work
    is partitioned across devices via ``shard_map`` along the tenant
    axis, each shard carrying its tenants' full grid sweep.  When T does
    not divide the mesh extent the call falls back to the unsharded
    executable (mirroring ``sharding.rules.shard_if_divisible``).

    ``post0`` (a previous report's ``post_final``) replaces the stacked
    priors as the scan carry; with ``donate=True`` its device buffer is
    donated to the new carry, so repeated calibration rounds — replay,
    re-gate, replay — update posteriors in place instead of reallocating
    per round.  Donation consumes the passed-in array: the previous
    report's ``post_final`` (including ``final_posterior_rows``) becomes
    unreadable afterwards, which is why it is opt-in — read the old
    round's posteriors (drift gating) *before* donating them into the
    next round.

    Per-tenant results are bitwise-identical (float64) to T independent
    :func:`fleet_replay` calls — pinned by tests/test_fleet_multitenant.py
    and the 8-device case in tests/test_multidevice.py.
    """
    alphas = np.atleast_1d(np.asarray(alphas, float))
    lambdas = np.atleast_1d(np.asarray(lambdas, float))
    if lambdas.shape[0] == 1 and alphas.shape[0] > 1:
        lambdas = np.broadcast_to(lambdas, alphas.shape).copy()
    if alphas.shape != lambdas.shape:
        raise ValueError("alphas and lambdas must be paired (same length)")
    T, G, V = stack.T, alphas.shape[0], stack.V

    if mesh is not None:
        from ..sharding.rules import fleet_axis_spec

        if fleet_axis_spec(mesh, T, axis=axis_name) is None:
            mesh = None  # indivisible tenant axis: replicate = don't shard

    (static, a0, b0, discount, gammas,
     success, pred_ok, chunk_P, ep_mask) = stack.device_args()
    if post0 is None:
        post0 = jnp.broadcast_to(
            jnp.stack([a0, b0], -1)[:, None], (T, G, V, 2)
        )
    else:
        if tuple(post0.shape) != (T, G, V, 2):
            raise ValueError(f"post0 must have shape ({T}, {G}, {V}, 2)")
        post0 = _f(post0)

    fn = _mt_executable(
        mesh, axis_name, int(throttle_every), int(stack.K),
        bool(stack.use_lower_bound), bool(donate),
    )
    post_final, ys = fn(
        static, post0, discount, _f(alphas), _f(lambdas), gammas,
        success, pred_ok, chunk_P, ep_mask,
    )
    np_out = {k: np.asarray(v) for k, v in ys.items()}
    return MultiTenantReport(
        tenants=stack.tenants, alphas=alphas, lambdas=lambdas,
        n_ops=stack.n_ops, n_episodes=stack.n_episodes,
        ep_mask=stack.ep_mask, edge_keys=stack.edge_keys(),
        post_final=post_final, **np_out,
    )


# -------------------------------------------------------- episode sharding
@dataclasses.dataclass(frozen=True)
class EpisodeChunks:
    """One tenant's episode log split into C contiguous segments.

    Segments share a common padded length S = ceil(E / C); the ragged
    tail is padded with masked identity scan steps (``ep_mask`` False),
    the same move :func:`stack_tenants` uses for ragged per-tenant logs —
    so every segment is a fixed-shape scan and the segment axis can be
    partitioned across devices.
    """

    n_episodes: int            # E, pre-padding
    success: np.ndarray        # (C, S, V) bool
    pred_ok: np.ndarray        # (C, S, V) bool
    chunk_P: np.ndarray        # (C, S, V, K)
    ep_mask: np.ndarray        # (C, S) bool; False rows are padding /
                               # caller-masked identity steps
    has_refiner: np.ndarray    # (V,) bool (zeroed when no chunk_P given)

    @property
    def n_segments(self) -> int:
        return self.success.shape[0]

    @property
    def seg_len(self) -> int:
        return self.success.shape[1]

    @property
    def K(self) -> int:
        return self.chunk_P.shape[-1]


def chunk_episodes(
    lowered: FleetLowered,
    success,
    n_segments: int,
    *,
    pred_ok=None,
    chunk_P=None,
    ep_mask=None,
) -> EpisodeChunks:
    """Split an (E, V) episode log into C contiguous fixed-shape segments.

    Defaults mirror :func:`fleet_replay` (``pred_ok`` from the lowering's
    predictor mask, streaming disabled without ``chunk_P``).  E = 0 is
    rejected outright: an empty log would chunk into an all-identity
    segment whose replay silently reports zero stats — callers with no
    episodes should not be replaying at all.
    """
    (success, pred_ok, chunk_P, ep_mask, has_refiner,
     _K) = _normalize_episodes(lowered, success, pred_ok, chunk_P, ep_mask)
    E = success.shape[0]
    if E == 0:
        raise ValueError(
            "chunk_episodes requires at least one episode: an E=0 log "
            "would emit an all-identity (fully masked) segment that "
            "replays to zero stats instead of failing loudly")
    C = int(n_segments)
    if C < 1:
        raise ValueError(f"n_segments must be >= 1, got {n_segments}")
    S = -(-E // C)
    pad = C * S - E

    def seg(x, fill):
        if pad:
            x = np.concatenate(
                [x, np.full((pad,) + x.shape[1:], fill, x.dtype)])
        return x.reshape((C, S) + x.shape[1:])

    return EpisodeChunks(
        n_episodes=E,
        success=seg(success, False),
        pred_ok=seg(pred_ok, False),
        chunk_P=seg(chunk_P, 1.0),
        ep_mask=seg(ep_mask, False),
        has_refiner=has_refiner,
    )


def _scan_posterior_only(static, post0, discount, alphas, lambdas, gamma,
                         success, pred_ok, chunk_P, ep_mask, throttle_every,
                         K, use_lower_bound):
    """The episode scan reduced to its carry: the identical per-episode
    arithmetic as ``_scan_core`` (same ``_episode`` body, same masked
    identity steps, so the carry evolves bitwise-equally), but no
    per-episode stats are stacked — jit DCE prunes the unused stat
    outputs, so a boundary pass over E episodes materializes O(G x V)
    instead of O(E x G x V)."""
    episode = functools.partial(
        _episode, static, discount, (K, throttle_every),
        use_lower_bound, gamma,
    )

    def ep_step(post_ab, xs):
        succ_e, pred_e, chunks_e, mask_e = xs
        post_new, _ = jax.vmap(
            episode, in_axes=(0, 0, 0, None, None, None)
        )(post_ab, alphas, lambdas, succ_e, pred_e, chunks_e)
        return jnp.where(mask_e, post_new, post_ab), None

    post, _ = jax.lax.scan(
        ep_step, post0, (success, pred_ok, chunk_P, ep_mask))
    return post


@functools.partial(
    jax.jit, static_argnames=("throttle_every", "K", "use_lower_bound")
)
def _boundary_scan(static, post0, discount, alphas, lambdas, gamma,
                   success, pred_ok, chunk_P, ep_mask, throttle_every, K,
                   use_lower_bound):
    """Posterior-handoff pass: a sequential ``lax.scan`` over the C
    segments, emitting the exact posterior carry at each segment *start*
    (plus the final carry).  Exact for every discount — see
    :func:`episode_sharded_replay` for why the handoff must be
    sequential when bitwise parity with the unsharded scan is the
    contract."""

    def seg_step(post_ab, xs):
        succ_c, pred_c, chunks_c, mask_c = xs
        post_end = _scan_posterior_only(
            static, post_ab, discount, alphas, lambdas, gamma,
            succ_c, pred_c, chunks_c, mask_c, throttle_every, K,
            use_lower_bound)
        return post_end, post_ab

    post_final, starts = jax.lax.scan(
        seg_step, post0, (success, pred_ok, chunk_P, ep_mask))
    return starts, post_final


# Per-segment entry points for the pipelined replay: the SAME scan bodies
# as the two-pass engine (`_scan_core` / `_scan_posterior_only`), jitted
# unvmapped so a host loop can interleave one segment's stats with the
# next segment's posterior handoff (see episode_sharded_replay).
_seg_stats_one = functools.partial(
    jax.jit, static_argnames=("throttle_every", "K", "use_lower_bound")
)(_scan_core)
_seg_posterior_one = functools.partial(
    jax.jit, static_argnames=("throttle_every", "K", "use_lower_bound")
)(_scan_posterior_only)


@functools.lru_cache(maxsize=None)
def _seg_executable(mesh, axis_name, throttle_every, K, use_lower_bound):
    """Compile (and cache) the segment-vmapped, optionally shard_map'd
    stats pass of the episode-sharded replay.  Mirrors ``_mt_executable``
    with segments in place of tenants: the workflow statics, grid and
    per-op discounts are replicated; the segment axis (boundary carries +
    episode arrays) is partitioned."""

    def run(static, starts, discount, alphas, lambdas, gamma,
            success, pred_ok, chunk_P, ep_mask):
        def one(p0, s, pk, cp, em):
            return _scan_core(static, p0, discount, alphas, lambdas, gamma,
                              s, pk, cp, em, throttle_every, K,
                              use_lower_bound)

        return jax.vmap(one)(starts, success, pred_ok, chunk_P, ep_mask)

    if mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec

        c = PartitionSpec(axis_name)
        r = PartitionSpec()
        run = shard_map(
            run, mesh=mesh,
            in_specs=(r, c, r, r, r, r, c, c, c, c),
            out_specs=c,
            check_vma=False,
        )
    return jax.jit(run)


def compose_segment_posteriors(a0, b0, seg_s, seg_f):
    """Closed-form conjugate composition of segment posteriors
    (``discount=1`` only).

    Under the undiscounted update the posterior after a segment is
    ``Beta(a + Δs, b + Δf)`` where (Δs, Δf) are the segment's success /
    failure *counts* on launched episodes — pure sufficient statistics.
    Composition is therefore associative, and one
    ``lax.associative_scan`` over the per-segment (Δs, Δf) rebuilds
    every segment-boundary posterior from the prior in O(log C) depth.

    This is the analytical cross-check for the sequential handoff pass,
    not its replacement: (1) the D4 gate reads the carry, so (Δs, Δf)
    themselves depend on the incoming posterior and must be *collected*
    along the exact trajectory, and (2) ``prior + Σcounts`` rounds once
    where the in-scan carry rounds per episode, so the composition
    matches the handoff to 1 ULP rather than bitwise (exact when the
    prior is integer-valued).  tests/test_episode_sharding.py pins the
    agreement.

    Args: ``a0`` / ``b0`` broadcastable to ``seg_s[0]``; ``seg_s`` /
    ``seg_f`` with a leading segment axis.  Returns the (C, ..., 2)
    posterior at each segment *start* (prior + exclusive prefix sums).
    """
    deltas = jnp.stack([_f(seg_s), _f(seg_f)], axis=-1)
    prefix = jax.lax.associative_scan(jnp.add, deltas, axis=0)
    excl = jnp.concatenate(
        [jnp.zeros_like(prefix[:1]), prefix[:-1]], axis=0)
    prior = jnp.stack(
        [jnp.broadcast_to(_f(a0), deltas.shape[1:-1]),
         jnp.broadcast_to(_f(b0), deltas.shape[1:-1])], axis=-1)
    return np.asarray(prior[None] + excl)


def episode_sharded_replay(
    lowered: FleetLowered,
    success,
    alphas,
    lambdas,
    *,
    n_segments: Optional[int] = None,
    pred_ok=None,
    chunk_P=None,
    throttle_every: int = 1,
    ep_mask=None,
    mesh=None,
    axis_name: str = "fleet",
    return_boundaries: bool = False,
    pipelined: bool = False,
) -> "FleetReport | tuple[FleetReport, np.ndarray]":
    """Replay a single tenant's E-episode log as C independent scan
    segments — the fleet engine's episode-axis analogue of
    :func:`multi_tenant_replay`'s tenant axis, for million-episode §12.1
    logs that one sequential scan would serialize.

    Two passes:

    1. **Posterior handoff** (:func:`_boundary_scan`): a sequential scan
       over segments carrying only the (G, V, 2) posterior, emitting the
       exact carry at every segment boundary.  O(E) sequential work but
       O(C·G·V) memory — none of the ~17 per-episode stat arrays are
       materialized, which is what dominates an unsharded million-episode
       replay.
    2. **Stats pass** (:func:`_seg_executable`): given its boundary
       carry, each segment is independent; the C segments run vmapped
       (and, with ``mesh`` — e.g. ``repro.launch.mesh.make_fleet_mesh()``
       — ``shard_map``'d along the 1-D fleet axis via
       ``sharding.rules.fleet_axis_spec``, falling back to the unsharded
       executable when C is indivisible) and materialize the full
       per-episode trajectories in parallel.

    Why the handoff is sequential in *both* discount regimes: the D4
    gate reads the carried posterior, so each segment's sufficient
    statistics depend on its incoming carry — a one-shot parallel
    composition would speculate on decisions and break the bitwise
    contract.  Under ``discount=1`` the conjugate closed form *does*
    compose associatively (:func:`compose_segment_posteriors`, one
    ``lax.associative_scan`` over per-segment (Δs, Δf)) and is pinned to
    the handoff to 1 ULP; under ``discount<1`` the forgetting recurrence
    makes the handoff of the (a, b) carry the only exact route, so the
    engine documents and uses this two-pass scheme for every discount.

    ``pipelined=True`` removes the two-pass *latency* without touching
    the handoff's sequential semantics: a host loop walks the segments
    in order, dispatching segment c's stats scan (``_seg_stats_one`` —
    the same ``_scan_core`` body the vmapped stats pass runs) the moment
    c's boundary carry exists, then immediately advancing the carry for
    segment c+1 (``_seg_posterior_one``).  JAX's async dispatch lets
    segment c's stats overlap segment c+1's handoff instead of
    completing ALL boundaries first, and the final segment needs no
    handoff at all — the boundary pass shrinks from C to C-1 segments
    and stops gating the first stats launch.  The carries are the same
    ``_scan_posterior_only`` recurrence, so boundaries and stats stay
    bitwise identical to the two-pass engine (asserted across the full
    C/discount/lower-bound/cancel matrix in
    tests/test_episode_sharding.py); ``mesh`` is ignored in this mode
    (the overlap already owns the device queue).  The trade: stats run
    one executable per segment rather than vmapped across segments, so
    on a single device with spare vector lanes (this container) the
    two-pass engine is faster — the mode pays off when segments can
    land on separate devices and the handoff is the critical path
    (EXPERIMENTS.md §Episode sharding).

    Parity contract (tests/test_episode_sharding.py): bitwise-f64 equal
    to :func:`fleet_replay` on the same log — decisions, flags, times,
    posteriors exactly; EV/waste to the established 1-ULP FMA allowance
    — for every (C, discount, lower-bound, streaming) combination.

    ``n_segments`` defaults to the mesh extent (or the visible device
    count without a mesh).  ``return_boundaries=True`` additionally
    returns the (C, G, V, 2) segment-start posteriors.
    """
    alphas, lambdas = _normalize_grid(alphas, lambdas)
    if n_segments is None:
        if mesh is not None and axis_name in mesh.shape:
            n_segments = mesh.shape[axis_name]
        else:
            n_segments = max(1, len(jax.devices()))
    chunks = chunk_episodes(
        lowered, success, n_segments,
        pred_ok=pred_ok, chunk_P=chunk_P, ep_mask=ep_mask)
    E, C = chunks.n_episodes, chunks.n_segments
    # the report's ep_mask keeps the caller's (E,) view, not the padded one
    ep_mask_full = chunks.ep_mask.reshape(-1)[:E]

    if mesh is not None:
        from ..sharding.rules import fleet_axis_spec

        if fleet_axis_spec(mesh, C, axis=axis_name) is None:
            mesh = None  # indivisible segment axis: run unsharded

    static = _pack_static(lowered, chunks.has_refiner)
    G = alphas.shape[0]
    V = lowered.n_ops
    post0 = jnp.broadcast_to(
        jnp.stack([_f(lowered.a0), _f(lowered.b0)], -1)[None], (G, V, 2))
    args = (
        _f(lowered.discount), _f(alphas), _f(lambdas), _f(lowered.gamma),
        jnp.asarray(chunks.success), jnp.asarray(chunks.pred_ok),
        _f(chunks.chunk_P), jnp.asarray(chunks.ep_mask),
    )
    throttle_every = int(throttle_every)
    K = int(chunks.K)
    use_lb = bool(lowered.use_lower_bound)

    if pipelined:
        (discount_j, alphas_j, lambdas_j, gamma_j,
         succ_j, pok_j, cP_j, em_j) = args
        carry = post0
        starts_list: list = []
        stats_list: list = []
        for c in range(C):
            xs = (succ_j[c], pok_j[c], cP_j[c], em_j[c])
            starts_list.append(carry)
            # dispatch the stats scan first (async — it runs while the
            # host enqueues the next handoff), then advance the carry,
            # which is all segment c+1 is actually waiting on
            _, ys_c = _seg_stats_one(
                static, carry, discount_j, alphas_j, lambdas_j, gamma_j,
                *xs, throttle_every=throttle_every, K=K,
                use_lower_bound=use_lb)
            stats_list.append(ys_c)
            if c + 1 < C:
                carry = _seg_posterior_one(
                    static, carry, discount_j, alphas_j, lambdas_j,
                    gamma_j, *xs, throttle_every=throttle_every, K=K,
                    use_lower_bound=use_lb)
        out = {}
        for k in stats_list[0]:
            out[k] = np.concatenate(
                [np.asarray(ys_c[k]) for ys_c in stats_list], axis=0)[:E]
        report = FleetReport(alphas=alphas, lambdas=lambdas,
                             ep_mask=ep_mask_full, **out)
        if return_boundaries:
            return report, np.asarray(jnp.stack(starts_list))
        return report

    starts, _ = _boundary_scan(static, post0, *args,
                               throttle_every=throttle_every, K=K,
                               use_lower_bound=use_lb)
    fn = _seg_executable(mesh, axis_name, throttle_every, K, use_lb)
    _, ys = fn(static, starts, *args)

    out = {}
    for k, v in ys.items():
        v = np.asarray(v)
        out[k] = v.reshape((C * chunks.seg_len,) + v.shape[2:])[:E]
    report = FleetReport(alphas=alphas, lambdas=lambdas,
                         ep_mask=ep_mask_full, **out)
    if return_boundaries:
        return report, np.asarray(starts)
    return report
