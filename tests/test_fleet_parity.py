"""Scalar <-> fleet parity: the vectorized replay engine (repro.core.fleet)
must reproduce the paper-faithful discrete-event executor bitwise at
float64 — decisions, EV, timing, waste, and posterior trajectories — on
randomized small DAGs, plus the batched streaming / posterior primitives
against their scalar counterparts."""
import dataclasses

import numpy as np
import pytest
from jax import enable_x64

from repro.core import (
    DependencyType,
    Edge,
    ExecutorConfig,
    Operation,
    PlannerParams,
    Workflow,
    execute,
    fleet_replay,
    lower_workflow,
    plan_workflow,
)
from repro.core.batch_decision import (
    batch_chunk_cancel,
    batch_fractional_waste,
    batch_posterior_update,
    counterfactual_grid,
)
from repro.core.decision import DecisionInputs
from repro.core.posterior import BetaPosterior
from repro.core.predictor import TemplatePredictor
from repro.core.pricing import TwoRateTokenCost
from repro.core.streaming import StreamingReestimator, fractional_waste

PRED = "predicted-topic-alpha"
MISS = "zzz-unrelated-output-999"


# ------------------------------------------------------------- DAG generator
@dataclasses.dataclass
class RandomDag:
    """A randomized DAG spec: topology + per-episode upstream outcomes.

    Ops are n0..n{V-1}; candidate (speculation) edges carry a predictor
    that predicts PRED; the upstream emits PRED on success episodes and
    MISS otherwise, so §7.4 tier-1 labels are fully controlled."""

    n_ops: int
    plain_parents: list[tuple[int, int]]      # enabled=False edges (u, v)
    spec_edges: list[tuple[int, int]]         # candidate edges (u, v)
    latency: np.ndarray                       # (V,)
    in_tok: np.ndarray
    out_tok: np.ndarray
    streams: np.ndarray                       # (V,) bool, downstream streaming
    pred_cost: np.ndarray                     # (V,) predictor cost (s)
    success: np.ndarray                       # (E, V) bool
    pred_ok: np.ndarray                       # (E, V) bool
    discount: float = 1.0
    use_lower_bound: bool = False             # §7.5 credible-bound gating
    gamma: float = 0.1

    def name(self, i: int) -> str:
        return f"n{i}"

    def build_workflow(self, episode: int) -> Workflow:
        wf = Workflow(f"rand-{self.n_ops}")
        # the UPSTREAM of a spec edge emits PRED/MISS; success is keyed by
        # the downstream op (each upstream serves at most one spec edge)
        spec_up = {u: v for (u, v) in self.spec_edges}
        for i in range(self.n_ops):
            v = spec_up.get(i)
            if v is None:
                out = f"out-{self.name(i)}"
            else:
                out = PRED if self.success[episode, v] else MISS
            wf.add_op(Operation(
                self.name(i),
                run=lambda *a, _o=out: _o,
                latency_est_s=float(self.latency[i]),
                input_tokens_est=int(self.in_tok[i]),
                output_tokens_est=int(self.out_tok[i]),
                streams=bool(self.streams[i]),
                metadata={"input": f"in-{self.name(i)}"},
            ))
        for (u, v) in self.plain_parents:
            wf.add_edge(Edge(self.name(u), self.name(v), enabled=False))
        for (u, v) in self.spec_edges:
            wf.add_edge(Edge(self.name(u), self.name(v),
                             dep_type=DependencyType.CONDITIONAL_OUTPUT))
        return wf.freeze()

    def predictors(self, episode: int) -> dict:
        preds = {}
        for (u, v) in self.spec_edges:
            ok = bool(self.pred_ok[episode, v])
            preds[(self.name(u), self.name(v))] = TemplatePredictor(
                template=lambda i, p=None, _ok=ok: (PRED if _ok else None),
                cost_estimate_s=float(self.pred_cost[v]),
            )
        return preds

    def fresh_params(self, alpha: float, lam: float) -> PlannerParams:
        posts = {}
        for (u, v) in self.spec_edges:
            posts[(self.name(u), self.name(v))] = (
                BetaPosterior.from_dependency_type(
                    DependencyType.CONDITIONAL_OUTPUT, discount=self.discount
                )
            )
        return PlannerParams(alpha=alpha, lambda_usd_per_s=lam,
                             posteriors=posts,
                             use_lower_bound=self.use_lower_bound,
                             gamma=self.gamma)


def make_random_dag(seed: int, episodes: int = 6,
                    discount: float = 1.0,
                    use_lower_bound: bool = False) -> RandomDag:
    rng = np.random.default_rng(seed)
    V = int(rng.integers(2, 6))
    plain, spec = [], []
    spec_ups = set()
    for v in range(1, V):
        parents = [u for u in range(v) if rng.random() < 0.6]
        if not parents and rng.random() < 0.7:
            parents = [int(rng.integers(0, v))]
        free = [u for u in parents if u not in spec_ups]
        if free and rng.random() < 0.8:
            u = int(rng.choice(free))
            spec.append((u, v))
            spec_ups.add(u)
            parents.remove(u)
        plain.extend((u, v) for u in parents)
    return RandomDag(
        n_ops=V,
        plain_parents=plain,
        spec_edges=spec,
        latency=rng.uniform(0.2, 3.0, V).round(3),
        in_tok=rng.integers(50, 2000, V),
        out_tok=rng.integers(50, 2000, V),
        streams=rng.random(V) < 0.7,
        pred_cost=np.where(rng.random(V) < 0.5, 0.0, 0.05),
        success=rng.random((episodes, V)) < 0.55,
        pred_ok=rng.random((episodes, V)) < 0.85,
        discount=discount,
        use_lower_bound=use_lower_bound,
    )


def run_scalar(dag: RandomDag, alphas, lams):
    """Episode loop through plan_workflow + execute, one posterior set per
    grid point (the §12.3 sweep exactly as workflow_sim does it)."""
    E = dag.success.shape[0]
    V = dag.n_ops
    G = len(alphas)
    shape = (E, G, V)
    out = {
        "EV": np.zeros(shape), "thr": np.zeros(shape),
        "spec": np.zeros(shape, bool), "launched": np.zeros(shape, bool),
        "committed": np.zeros(shape, bool), "waste": np.zeros(shape),
        "finish": np.zeros(shape), "post_a": np.zeros(shape),
        "post_b": np.zeros(shape), "makespan": np.zeros((E, G)),
        "waste_total": np.zeros((E, G)),
    }
    for g, (alpha, lam) in enumerate(zip(alphas, lams)):
        params = dag.fresh_params(alpha, lam)
        for e in range(E):
            wf = dag.build_workflow(e)
            plan, _ = plan_workflow(wf, params)
            cfg = ExecutorConfig(params=params, predictors=dag.predictors(e),
                                 use_lower_bound=dag.use_lower_bound,
                                 gamma=dag.gamma)
            rep = execute(wf, plan, cfg)
            by_edge = {r.edge: r for r in cfg.telemetry.rows
                       if r.phase == "runtime"}
            launched_edges = {o.edge: o for o in rep.outcomes}
            for (u, v) in dag.spec_edges:
                key = (dag.name(u), dag.name(v))
                row = by_edge[key]
                out["EV"][e, g, v] = row.EV_usd
                out["thr"][e, g, v] = row.threshold_usd
                out["spec"][e, g, v] = row.decision == "SPECULATE"
                o = launched_edges.get(key)
                out["launched"][e, g, v] = o is not None and o.launched
                out["committed"][e, g, v] = o is not None and o.committed
                out["waste"][e, g, v] = o.waste_usd if o is not None else 0.0
                post = params.posteriors[key]
                out["post_a"][e, g, v] = post.alpha
                out["post_b"][e, g, v] = post.beta
            for i in range(V):
                out["finish"][e, g, i] = rep.finish_times_s[dag.name(i)]
            out["makespan"][e, g] = rep.makespan_s
            out["waste_total"][e, g] = rep.waste_usd
    return out


def run_fleet(dag: RandomDag, alphas, lams):
    """Lower + replay, then re-index (E, G, V) outputs from the lowering's
    topological order back to the dag's op numbering."""
    params = dag.fresh_params(0.5, 0.01)  # priors only; grid comes from args
    wf = dag.build_workflow(0)
    preds = dag.predictors(0)
    lowered = lower_workflow(wf, params, predictors=preds)
    order = np.array([int(n[1:]) for n in lowered.names])  # lowered -> dag
    report = fleet_replay(
        lowered, dag.success[:, order], np.asarray(alphas),
        np.asarray(lams), pred_ok=dag.pred_ok[:, order],
    )
    inv = np.empty_like(order)
    inv[order] = np.arange(order.size)
    reindexed = {
        f.name: getattr(report, f.name)[:, :, inv]
        for f in dataclasses.fields(report)
        if getattr(report, f.name).ndim == 3
    }
    report = dataclasses.replace(report, **reindexed)
    edge_ops = sorted(order[j] for j in lowered.edge_ops())
    return edge_ops, report


GRID_ALPHAS = [0.0, 0.5, 0.9]
GRID_LAMS = [0.01, 0.08, 0.08]


# XLA CPU contracts a*b + c into a single FMA (one rounding) while CPython
# rounds twice, so products compared against the pure-Python scalar path can
# differ by 1 ULP.  Decisions, counts, posterior trajectories (discount=1)
# and event times (add/max chains) are contraction-free and compared
# bitwise; EV/threshold/waste use an ULP-level tolerance.
ULP = dict(rtol=1e-13, atol=1e-16)


@pytest.mark.parametrize("seed", range(8))
def test_random_dag_bitwise_parity(seed):
    """Decisions, timing and posterior trajectories match the scalar
    executor bitwise at float64 (EV/waste to 1 ULP, see note above)."""
    with enable_x64():
        dag = make_random_dag(seed)
        scalar = run_scalar(dag, GRID_ALPHAS, GRID_LAMS)
        edge_ops, fleet = run_fleet(dag, GRID_ALPHAS, GRID_LAMS)
        assert sorted(v for (_, v) in dag.spec_edges) == edge_ops
        sel = np.array(edge_ops, int)
        np.testing.assert_allclose(
            fleet.EV_usd[:, :, sel], scalar["EV"][:, :, sel], **ULP)
        np.testing.assert_allclose(
            fleet.threshold_usd[:, :, sel], scalar["thr"][:, :, sel], **ULP)
        np.testing.assert_array_equal(
            fleet.speculate[:, :, sel], scalar["spec"][:, :, sel])
        np.testing.assert_array_equal(
            fleet.edge_launched[:, :, sel], scalar["launched"][:, :, sel])
        np.testing.assert_array_equal(
            fleet.edge_committed[:, :, sel], scalar["committed"][:, :, sel])
        np.testing.assert_allclose(
            fleet.edge_waste_usd[:, :, sel], scalar["waste"][:, :, sel],
            **ULP)
        np.testing.assert_array_equal(fleet.finish_s, scalar["finish"])
        np.testing.assert_array_equal(fleet.makespan_s, scalar["makespan"])
        np.testing.assert_array_equal(
            fleet.post_alpha[:, :, sel], scalar["post_a"][:, :, sel])
        np.testing.assert_array_equal(
            fleet.post_beta[:, :, sel], scalar["post_b"][:, :, sel])
        np.testing.assert_allclose(
            fleet.waste_usd, scalar["waste_total"], rtol=1e-12, atol=1e-16)


@pytest.mark.parametrize("seed", [100, 101])
def test_random_dag_discounted_posterior_parity(seed):
    """Exponential-forgetting posteriors (§14.3) carry through the fleet
    scan identically to BetaPosterior.update."""
    with enable_x64():
        dag = make_random_dag(seed, discount=0.9)
        scalar = run_scalar(dag, GRID_ALPHAS, GRID_LAMS)
        edge_ops, fleet = run_fleet(dag, GRID_ALPHAS, GRID_LAMS)
        sel = np.array(edge_ops, int)
        if sel.size == 0:
            pytest.skip("degenerate draw: no candidate edges")
        # a*0.9 + x contracts to an FMA under XLA -> 1-ULP tolerance
        np.testing.assert_allclose(
            fleet.post_alpha[:, :, sel], scalar["post_a"][:, :, sel], **ULP)
        np.testing.assert_allclose(
            fleet.post_beta[:, :, sel], scalar["post_b"][:, :, sel], **ULP)


# The §7.5 EV is fed by two independent Beta-quantile implementations:
# the scalar path inverts through scipy.stats.beta.ppf, the fleet path
# through the jax-native betaincinv (tests/test_betaincinv.py pins their
# agreement at ~1e-13 relative over the posterior range).  EV inherits
# that spread on top of the FMA ULP, so it gets a little extra headroom;
# everything downstream of the *decisions* — launch/commit flags, event
# times, posterior trajectories — still matches bitwise, and waste /
# threshold stay at the plain ULP tolerance (they do not depend on P).
LB_ULP = dict(rtol=1e-11, atol=1e-14)


@pytest.mark.parametrize("seed", range(8))
def test_random_dag_lower_bound_parity(seed):
    """§7.5 credible-bound gating (use_lower_bound=True, gamma=0.1):
    fleet replay matches the scalar executor on randomized DAGs at
    float64 — decisions, flags, timing and posterior trajectories
    bitwise; EV to the cross-quantile tolerance; waste to 1 ULP."""
    with enable_x64():
        dag = make_random_dag(seed, use_lower_bound=True)
        scalar = run_scalar(dag, GRID_ALPHAS, GRID_LAMS)
        edge_ops, fleet = run_fleet(dag, GRID_ALPHAS, GRID_LAMS)
        assert sorted(v for (_, v) in dag.spec_edges) == edge_ops
        sel = np.array(edge_ops, int)
        np.testing.assert_allclose(
            fleet.EV_usd[:, :, sel], scalar["EV"][:, :, sel], **LB_ULP)
        np.testing.assert_allclose(
            fleet.threshold_usd[:, :, sel], scalar["thr"][:, :, sel], **ULP)
        np.testing.assert_array_equal(
            fleet.speculate[:, :, sel], scalar["spec"][:, :, sel])
        np.testing.assert_array_equal(
            fleet.edge_launched[:, :, sel], scalar["launched"][:, :, sel])
        np.testing.assert_array_equal(
            fleet.edge_committed[:, :, sel], scalar["committed"][:, :, sel])
        np.testing.assert_allclose(
            fleet.edge_waste_usd[:, :, sel], scalar["waste"][:, :, sel],
            **ULP)
        np.testing.assert_array_equal(fleet.finish_s, scalar["finish"])
        np.testing.assert_array_equal(fleet.makespan_s, scalar["makespan"])
        np.testing.assert_array_equal(
            fleet.post_alpha[:, :, sel], scalar["post_a"][:, :, sel])
        np.testing.assert_array_equal(
            fleet.post_beta[:, :, sel], scalar["post_b"][:, :, sel])
        np.testing.assert_allclose(
            fleet.waste_usd, scalar["waste_total"], rtol=1e-12, atol=1e-16)


@pytest.mark.parametrize("seed", [100, 101])
def test_random_dag_lower_bound_discounted_parity(seed):
    """Credible-bound gating composed with exponential-forgetting
    posteriors: the betaincinv inversion runs on the discounted
    (fractional) carry and must track scipy on the same trajectory."""
    with enable_x64():
        dag = make_random_dag(seed, discount=0.9, use_lower_bound=True)
        scalar = run_scalar(dag, GRID_ALPHAS, GRID_LAMS)
        edge_ops, fleet = run_fleet(dag, GRID_ALPHAS, GRID_LAMS)
        sel = np.array(edge_ops, int)
        if sel.size == 0:
            pytest.skip("degenerate draw: no candidate edges")
        np.testing.assert_array_equal(
            fleet.speculate[:, :, sel], scalar["spec"][:, :, sel])
        np.testing.assert_array_equal(
            fleet.edge_committed[:, :, sel], scalar["committed"][:, :, sel])
        np.testing.assert_allclose(
            fleet.EV_usd[:, :, sel], scalar["EV"][:, :, sel], **LB_ULP)
        # a*0.9 + x contracts to an FMA under XLA -> 1-ULP tolerance
        np.testing.assert_allclose(
            fleet.post_alpha[:, :, sel], scalar["post_a"][:, :, sel], **ULP)
        np.testing.assert_allclose(
            fleet.post_beta[:, :, sel], scalar["post_b"][:, :, sel], **ULP)


@pytest.mark.parametrize("use_lb", [False, True])
def test_streaming_cancel_parity(use_lb):
    """§9.1 mid-stream cancellation: fleet chunk path vs the scalar
    executor with a stream refiner, including fractional waste — under
    both posterior-mean and §7.5 credible-bound launch gating (chunk
    re-checks gate on the refined P_k either way, exactly like the
    scalar executor's evaluate(inputs_k))."""
    with enable_x64():
        E, K = 8, 4
        rng = np.random.default_rng(7)
        # chunk confidences: some episodes dip below the threshold mid-stream
        chunk_P = rng.uniform(0.05, 0.95, (E, K))
        alphas = [0.4]
        lams = [0.08]

        def build(episode):
            wf = Workflow("stream")
            wf.add_op(Operation(
                "u", run=lambda x: "chunked-output-string-for-u",
                latency_est_s=2.0, input_tokens_est=100, output_tokens_est=50,
                metadata={"input": "doc", "chunks": K},
            ))
            wf.add_op(Operation(
                "v", run=lambda i: f"v({i})", latency_est_s=1.5,
                input_tokens_est=400, output_tokens_est=900,
            ))
            wf.add_edge(Edge("u", "v"))
            return wf.freeze()

        key = ("u", "v")
        post_scalar = BetaPosterior.from_prior_mean(0.9)
        params = PlannerParams(alpha=alphas[0], lambda_usd_per_s=lams[0],
                               posteriors={key: post_scalar},
                               use_lower_bound=use_lb)
        scalar_waste = np.zeros(E)
        scalar_cancel = np.zeros(E, bool)
        scalar_finish = np.zeros(E)
        for e in range(E):
            wf = build(e)
            plan, _ = plan_workflow(wf, params)

            def refine(upstream_input, partial, _e=e):
                return None, float(chunk_P[_e, len(partial) - 1])

            cfg = ExecutorConfig(
                params=params,
                predictors={key: TemplatePredictor(
                    template=lambda i, p=None: "chunked-output-string-for-u")},
                stream_refiners={key: refine},
                use_lower_bound=use_lb,
            )
            rep = execute(wf, plan, cfg)
            scalar_waste[e] = rep.waste_usd
            scalar_cancel[e] = any(o.cancelled_mid_stream for o in rep.outcomes)
            scalar_finish[e] = rep.makespan_s

        params_f = PlannerParams(
            alpha=0.5, lambda_usd_per_s=0.01,
            posteriors={key: BetaPosterior.from_prior_mean(0.9)},
            use_lower_bound=use_lb,
        )
        wf = build(0)
        pred = {key: TemplatePredictor(
            template=lambda i, p=None: "chunked-output-string-for-u")}
        lowered = lower_workflow(
            wf, params_f, predictors=pred,
            stream_refiners={key: lambda i, p: (None, 0.0)},
        )
        vi = lowered.names.index("v")
        success = np.ones((E, lowered.n_ops), bool)  # prediction is exact
        cP = np.ones((E, lowered.n_ops, K))
        cP[:, vi, :] = chunk_P
        fleet = fleet_replay(lowered, success, alphas, lams, chunk_P=cP)
        assert scalar_cancel.any() and not scalar_cancel.all(), \
            "test vector should mix cancelled and surviving streams"
        np.testing.assert_array_equal(
            fleet.cancelled[:, 0].astype(bool), scalar_cancel)
        np.testing.assert_allclose(fleet.waste_usd[:, 0], scalar_waste, **ULP)
        np.testing.assert_allclose(
            fleet.makespan_s[:, 0], scalar_finish, **ULP)


def test_batch_chunk_cancel_matches_reestimator():
    """batch_chunk_cancel == StreamingReestimator.run chunk-for-chunk,
    including throttling."""
    with enable_x64():
        rng = np.random.default_rng(11)
        N, K = 32, 6
        P_chunks = rng.uniform(0.0, 1.0, (N, K))
        base = DecisionInputs(
            P=0.5, alpha=0.3, lambda_usd_per_s=0.08, latency_seconds=1.2,
            input_tokens=400, output_tokens=900,
            input_price=3e-6, output_price=15e-6,
        )
        for throttle in (1, 2, 3):
            first, cancelled, EV_k, thr = batch_chunk_cancel(
                P_chunks, base.alpha, base.lambda_usd_per_s,
                base.latency_seconds, base.input_tokens, base.output_tokens,
                base.input_price, base.output_price,
                throttle_every=throttle,
            )
            for i in range(N):
                table = {k: (None, float(P_chunks[i, k])) for k in range(K)}
                re = StreamingReestimator(
                    lambda inp, partial, _t=table: _t[len(partial) - 1],
                    base, throttle_every=throttle,
                )
                verdict, all_verdicts = re.run(None, ["c"] * K)
                assert cancelled[i] == (verdict is not None)
                if verdict is not None:
                    assert first[i] == verdict.chunk_index
                    np.testing.assert_allclose(
                        EV_k[i, verdict.chunk_index], verdict.EV_usd, **ULP)
                    np.testing.assert_allclose(
                        thr[i, verdict.chunk_index], verdict.threshold_usd,
                        **ULP)
                else:
                    assert first[i] == -1


def test_batch_posterior_discounted_matches_scalar():
    """batch_posterior_update(discount<1) == the discounted-update branch
    of BetaPosterior.update, bitwise at float64."""
    with enable_x64():
        rng = np.random.default_rng(5)
        E, N = 16, 64
        outcomes = rng.random((E, N)) < 0.6
        a0 = rng.uniform(0.5, 3.0, E)
        b0 = rng.uniform(0.5, 3.0, E)
        for d in (1.0, 0.95, 0.5):
            a, b = batch_posterior_update(a0, b0, outcomes.astype(float),
                                          discount=d)
            for i in range(E):
                post = BetaPosterior(alpha=float(a0[i]), beta=float(b0[i]),
                                     discount=d)
                post.update_many(outcomes[i])
                # d=1 uses the conjugate closed form a0+s (one rounding);
                # the scalar loop rounds per +1.0 step at fractional priors
                np.testing.assert_allclose(a[i], post.alpha, **ULP)
                np.testing.assert_allclose(b[i], post.beta, **ULP)


def test_batch_fractional_waste_matches_scalar():
    with enable_x64():
        rng = np.random.default_rng(9)
        n = 64
        in_tok = rng.integers(10, 2000, n)
        out_tok = rng.integers(10, 2000, n)
        frac = rng.uniform(0.0, 1.2, n)   # >1 bills actuals
        w = batch_fractional_waste(in_tok, out_tok, frac, 3e-6, 15e-6)
        cm = TwoRateTokenCost(3e-6, 15e-6)
        for i in range(n):
            np.testing.assert_allclose(
                w[i],
                fractional_waste(cm, int(in_tok[i]), float(out_tok[i]),
                                 frac[i] * float(out_tok[i])),
                **ULP)


def test_replay_grid_kernel_matches_oracle_and_batch():
    """The fused Pallas §12.1 grid kernel == pure-jnp oracle ==
    batch_decision.counterfactual_grid."""
    import jax.numpy as jnp

    from repro.kernels import replay_grid_op
    from repro.kernels.ref import reference_replay_grid

    rng = np.random.default_rng(3)
    n = 3000
    P = rng.uniform(0.1, 0.95, n).astype(np.float32)
    lat = rng.uniform(0.5, 3.0, n).astype(np.float32)
    cost = rng.uniform(0.005, 0.03, n).astype(np.float32)
    alphas = np.array([0.0, 0.25, 0.5, 0.75, 1.0], np.float32)
    lams = np.array([0.005, 0.01, 0.05, 0.1], np.float32)

    cnt, lsum, wsum = replay_grid_op(
        jnp.asarray(P), jnp.asarray(lat), jnp.asarray(cost),
        jnp.asarray(alphas), jnp.asarray(lams))
    rcnt, rlsum, rwsum = reference_replay_grid(
        jnp.asarray(P), jnp.asarray(lat), jnp.asarray(cost),
        jnp.asarray(alphas), jnp.asarray(lams))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))
    np.testing.assert_allclose(lsum, rlsum, rtol=1e-5)
    np.testing.assert_allclose(wsum, rwsum, rtol=1e-5)

    g = counterfactual_grid(P, lat, cost, alphas, lams)
    np.testing.assert_allclose(np.asarray(cnt) / n,
                               g["speculate_fraction"], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(lsum) / n,
                               g["expected_latency_s"], rtol=1e-4)
    np.testing.assert_allclose(np.asarray(wsum),
                               g["expected_waste_usd"], rtol=1e-4)


def test_counterfactual_grid_single_compile_across_rho():
    """Regression: rho sat in _grid's static_argnames, so every distinct
    float recompiled the XLA executable during §12.3 calibration sweeps.
    It is now a traced argument — one compile serves the whole rho sweep —
    and the lower-bound gate variant reuses the same executable."""
    from repro.core import batch_decision as bd

    rng = np.random.default_rng(21)
    n = 64
    P = rng.uniform(0.05, 0.95, n)
    lat = rng.uniform(0.2, 3.0, n)
    cost = rng.uniform(0.001, 0.03, n)
    alphas = np.array([0.0, 0.5, 1.0])
    lams = np.array([0.01, 0.08])
    bd._grid.clear_cache()
    base = None
    for rho in (0.0, 0.1, 0.25, 0.5, 0.77, 1.0):
        g = counterfactual_grid(P, lat, cost, alphas, lams, rho=rho)
        if base is None:
            base = g
        assert bd._grid._cache_size() == 1, \
            f"rho={rho} triggered a recompile"
    # the §7.5 gate variant shares the executable (same shapes/dtypes)
    P_low = bd.batch_lower_bound(2.0 * P, 2.0 * (1.0 - P), 0.1)
    counterfactual_grid(P, lat, cost, alphas, lams, rho=0.3, P_lower=P_low)
    assert bd._grid._cache_size() == 1
    # rho=0 zeroes expected waste but not the gate
    g0 = counterfactual_grid(P, lat, cost, alphas, lams, rho=0.0)
    np.testing.assert_array_equal(g0["expected_waste_usd"], 0.0)
    np.testing.assert_array_equal(
        g0["speculate_fraction"], base["speculate_fraction"])


def test_counterfactual_grid_lower_bound_gate_is_conservative():
    """With P_lower the SPECULATE gate runs on the credible bound (fewer
    or equal speculations than the mean gate) while latency / waste
    expectations stay weighted by the posterior mean."""
    with enable_x64():
        rng = np.random.default_rng(33)
        n = 200
        a = rng.uniform(0.5, 6.0, n)
        b = rng.uniform(0.5, 6.0, n)
        P = a / (a + b)
        from repro.core.batch_decision import batch_lower_bound
        P_low = batch_lower_bound(a, b, 0.1)
        assert np.all(P_low <= P)
        lat = rng.uniform(0.2, 3.0, n)
        cost = rng.uniform(0.001, 0.03, n)
        alphas = np.array([0.0, 0.3, 0.6, 0.9])
        lams = np.array([0.01, 0.08])
        g_mean = counterfactual_grid(P, lat, cost, alphas, lams)
        g_lb = counterfactual_grid(P, lat, cost, alphas, lams, P_lower=P_low)
        assert np.all(
            g_lb["speculate_fraction"] <= g_mean["speculate_fraction"])
        # and gating on P_lower directly == passing it as the gate
        g_direct = counterfactual_grid(P_low, lat, cost, alphas, lams)
        np.testing.assert_array_equal(
            g_lb["speculate_fraction"], g_direct["speculate_fraction"])


def test_batch_evaluate_lower_bound_matches_scalar_evaluate():
    """batch_evaluate(P_lower=...) == decision.evaluate(use_lower_bound=
    True) row-for-row: EV and the gate run on the bound (P_used)."""
    from repro.core.decision import evaluate

    with enable_x64():
        rng = np.random.default_rng(17)
        n = 48
        a = rng.uniform(0.5, 8.0, n)
        b = rng.uniform(0.5, 8.0, n)
        P = a / (a + b)
        from repro.core.batch_decision import batch_evaluate, batch_lower_bound
        P_low = batch_lower_bound(a, b, 0.1)
        lat = rng.uniform(0.2, 3.0, n)
        EV, thr, spec, C, L = batch_evaluate(
            P, 0.4, 0.08, lat, 400, 900, 3e-6, 15e-6, P_lower=P_low)
        # scalar alpha/token inputs broadcast the threshold down to 0-d
        thr = np.broadcast_to(np.asarray(thr), np.asarray(EV).shape)
        for i in range(n):
            res = evaluate(
                DecisionInputs(
                    P=float(P[i]), alpha=0.4, lambda_usd_per_s=0.08,
                    latency_seconds=float(lat[i]), input_tokens=400,
                    output_tokens=900, input_price=3e-6, output_price=15e-6,
                    P_lower_bound=float(P_low[i]),
                ),
                use_lower_bound=True,
            )
            np.testing.assert_allclose(EV[i], res.EV_usd, **ULP)
            np.testing.assert_allclose(thr[i], res.threshold_usd, **ULP)
            assert bool(spec[i]) == (res.decision.value == "SPECULATE")


def test_fleet_autoreply_pareto_matches_scalar_sweep():
    """End-to-end: the benchmark's AutoReply alpha sweep, scalar vs fleet,
    matching Pareto statistics (the §12.3 canary consumer contract)."""
    import sys
    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parents[1]))
    from benchmarks.workflow_sim import (
        DEFAULT_ALPHAS,
        assert_pareto_parity,
        fleet_sweep,
        sweep,
    )

    scalar = sweep(episodes=60)
    fleet = fleet_sweep(episodes=60)
    parity = assert_pareto_parity(scalar, fleet, DEFAULT_ALPHAS, rtol=1e-4)
    assert parity["max_rel_error"] < 1e-4
