"""End-to-end workflow simulation: the AutoReply scenario through the full
planner + executor, sweeping alpha (§12.3 canary sweep, simulated).

Two implementations of the same sweep:

* ``sweep``        — paper-faithful scalar path: one discrete-event
  ``execute`` call per episode (200 deterministic episodes per alpha; the
  upstream classifier emits an intent from a Zipf-ish 5-way distribution
  with p_mode = 0.62, §7.6's running example).
* ``fleet_sweep``  — the vectorized replay engine (repro.core.fleet): all
  episodes x all alphas in one jit-compiled XLA call.

``benchmarks()`` runs both, asserts the Pareto statistics agree, and
persists the speedup record to BENCH_fleet.json (machine-readable perf
trajectory across PRs; see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time

import numpy as np

from repro.core import (
    DependencyType,
    Edge,
    ExecutorConfig,
    Operation,
    PlannerParams,
    Workflow,
    episode_sharded_replay,
    execute,
    fleet_replay,
    lower_workflow,
    multi_tenant_replay,
    plan_workflow,
    stack_tenants,
)
from repro.core.posterior import BetaPosterior
from repro.core.predictor import HistoricalModalPredictor

INTENTS = ["billing", "support", "sales", "spam", "other"]
PROBS = [0.62, 0.12, 0.10, 0.09, 0.07]
DEFAULT_ALPHAS = (0.0, 0.1, 0.3, 0.5, 0.7, 0.9, 1.0)
LAMBDA_USD_PER_S = 0.08
# The beam record's latency-critical tier.  At the classic 0.08 the k=5
# router's cold prior (mean 0.2) times the 0.62 top-candidate confidence
# keeps beam EV below every threshold — nothing ever launches and the
# width axis is dead.  At 0.25 the alpha knee survives (alpha=0 stays in
# the cold-start trap) while the §7.6 marginal rule admits the runner-up
# once the posterior warms past ~0.53 and the third candidate past ~0.63,
# so the published Pareto actually exercises the width axis.
BEAM_LAMBDA_USD_PER_S = 0.25
SEED = 20260531
BENCH_PATH = pathlib.Path(__file__).resolve().parents[1] / "BENCH_fleet.json"


def build_workflow(intent: str) -> Workflow:
    wf = Workflow("autoreply")
    wf.add_op(Operation(
        "classifier", run=lambda x: intent, latency_est_s=0.8,
        input_tokens_est=200, output_tokens_est=10,
        metadata={"input": "email", "chunks": 8},
    ))
    wf.add_op(Operation(
        "drafter", run=lambda i: f"draft[{i}]", latency_est_s=0.8,
        input_tokens_est=500, output_tokens_est=800,
    ))
    wf.add_edge(Edge("classifier", "drafter",
                     dep_type=DependencyType.ROUTER_K_WAY, k=5))
    return wf.freeze()


def _draws(episodes: int, seed: int = SEED) -> np.ndarray:
    return np.random.default_rng(seed).choice(
        len(INTENTS), size=episodes, p=PROBS
    )


def sweep(alphas=DEFAULT_ALPHAS, episodes: int = 200,
          seed: int = SEED, *, use_lower_bound: bool = False,
          gamma: float = 0.1) -> dict:
    """Paper-faithful scalar sweep: plan + execute per episode.

    ``use_lower_bound=True`` runs the §7.5 conservative variant: both the
    planner and the Phase-2 runtime gate on the one-sided (1-gamma) lower
    credible bound instead of the posterior mean."""
    draws = _draws(episodes, seed)
    results = {}
    for alpha in alphas:
        post = BetaPosterior.from_dependency_type(DependencyType.ROUTER_K_WAY, k=5)
        lat, cost, waste, committed, launched = [], [], [], 0, 0
        for e in range(episodes):
            intent = INTENTS[draws[e]]
            wf = build_workflow(intent)
            params = PlannerParams(
                alpha=alpha, lambda_usd_per_s=LAMBDA_USD_PER_S,
                posteriors={("classifier", "drafter"): post},
                use_lower_bound=use_lower_bound, gamma=gamma,
            )
            plan, _ = plan_workflow(wf, params)
            pred = HistoricalModalPredictor()
            pred.observe("email", "billing")   # modal prediction
            cfg = ExecutorConfig(params=params,
                                 predictors={("classifier", "drafter"): pred},
                                 use_lower_bound=use_lower_bound,
                                 gamma=gamma)
            rep = execute(wf, plan, cfg)
            lat.append(rep.makespan_s)
            cost.append(rep.total_cost_usd)
            waste.append(rep.waste_usd)
            launched += sum(o.launched for o in rep.outcomes)
            committed += sum(o.committed for o in rep.outcomes)
        results[alpha] = {
            "latency_s": float(np.mean(lat)),
            "cost_usd": float(np.mean(cost)),
            "waste_usd": float(np.mean(waste)),
            "launched": launched,
            "committed": committed,
            "posterior_final": post.mean,
        }
    # sequential control arm
    wf = build_workflow("billing")
    results["control"] = {
        "latency_s": wf.sequential_latency(),
        "cost_usd": sum(
            op.input_tokens_est * 3e-6 + op.output_tokens_est * 15e-6
            for op in wf.ops.values()
        ),
        "waste_usd": 0.0,
    }
    return results


def _autoreply_fleet(episodes: int, seed: int = SEED, *,
                     use_lower_bound: bool = False, gamma: float = 0.1,
                     beam_confidences: dict | None = None):
    """The AutoReply workflow lowered for the fleet engine plus its
    synthetic episode log: returns (lowered, success, drafter_index).
    Shared by the fleet sweep, the episode-sharded record, the beam-width
    record and the multi-device tests."""
    draws = _draws(episodes, seed)
    wf = build_workflow("billing")
    edge_key = ("classifier", "drafter")
    params = PlannerParams(
        alpha=0.5, lambda_usd_per_s=LAMBDA_USD_PER_S,
        posteriors={edge_key: BetaPosterior.from_dependency_type(
            DependencyType.ROUTER_K_WAY, k=5)},
        use_lower_bound=use_lower_bound, gamma=gamma,
    )
    pred = HistoricalModalPredictor()
    pred.observe("email", "billing")
    lowered = lower_workflow(wf, params, predictors={edge_key: pred},
                             beam_confidences=beam_confidences)
    vi = lowered.names.index("drafter")
    success = np.zeros((episodes, lowered.n_ops), bool)
    success[:, vi] = draws == 0        # modal prediction is "billing"
    return lowered, success, vi


def fleet_sweep(alphas=DEFAULT_ALPHAS, episodes: int = 200,
                seed: int = SEED, *, use_lower_bound: bool = False,
                gamma: float = 0.1) -> dict:
    """The same sweep through the vectorized fleet replay engine: one
    XLA call for all episodes x alphas.  ``use_lower_bound=True`` gates
    on the jax-native betaincinv credible bound inside that same call."""
    lowered, success, vi = _autoreply_fleet(
        episodes, seed, use_lower_bound=use_lower_bound, gamma=gamma)
    report = fleet_replay(lowered, success, np.asarray(alphas),
                          LAMBDA_USD_PER_S)
    results = {}
    for gi, alpha in enumerate(alphas):
        results[alpha] = {
            "latency_s": float(report.makespan_s[:, gi].mean()),
            "cost_usd": float(report.total_cost_usd[:, gi].mean()),
            "waste_usd": float(report.waste_usd[:, gi].mean()),
            "launched": int(report.launched[:, gi].sum()),
            "committed": int(report.committed[:, gi].sum()),
            "posterior_final": float(
                report.post_alpha[-1, gi, vi]
                / (report.post_alpha[-1, gi, vi] + report.post_beta[-1, gi, vi])
            ),
        }
    return results


def assert_pareto_parity(scalar: dict, fleet: dict, alphas=DEFAULT_ALPHAS,
                         rtol: float = 1e-4) -> dict:
    """The fleet path must reproduce the scalar AutoReply Pareto: identical
    launch/commit counts, matching latency/cost/waste means."""
    worst = 0.0
    for alpha in alphas:
        s, f = scalar[alpha], fleet[alpha]
        if s["launched"] != f["launched"] or s["committed"] != f["committed"]:
            raise AssertionError(
                f"fleet/scalar divergence at alpha={alpha}: "
                f"launched {s['launched']}!={f['launched']} or committed "
                f"{s['committed']}!={f['committed']}"
            )
        for key in ("latency_s", "cost_usd", "waste_usd"):
            denom = max(abs(s[key]), 1e-12)
            rel = abs(s[key] - f[key]) / denom
            worst = max(worst, rel)
            if rel > rtol:
                raise AssertionError(
                    f"fleet/scalar divergence at alpha={alpha} {key}: "
                    f"{s[key]} vs {f[key]} (rel {rel:.2e})"
                )
    return {"max_rel_error": worst}


def _mt_stack(tenants: int = 8, episodes: int = 200, seed: int = SEED):
    """Stack ``tenants`` AutoReply variants: each tenant carries its own
    taxonomy-keyed prior (k-way router fan-out varies per tenant), its own
    intent draw stream, and its own episode log — the multi-tenant §12.1
    deployment shape (one edge name, many tenants)."""
    wf = build_workflow("billing")
    edge_key = ("classifier", "drafter")
    lowereds, succs, names = [], [], []
    for t in range(tenants):
        k = 3 + (t % 6)              # per-tenant router fan-out -> prior
        params = PlannerParams(
            alpha=0.5, lambda_usd_per_s=LAMBDA_USD_PER_S,
            posteriors={edge_key: BetaPosterior.from_dependency_type(
                DependencyType.ROUTER_K_WAY, k=k)},
        )
        pred = HistoricalModalPredictor()
        pred.observe("email", "billing")
        lowered = lower_workflow(wf, params, predictors={edge_key: pred})
        vi = lowered.names.index("drafter")
        draws = _draws(episodes, seed + t)
        success = np.zeros((episodes, lowered.n_ops), bool)
        success[:, vi] = draws == 0
        lowereds.append(lowered)
        succs.append(success)
        names.append(f"tenant{t}")
    return stack_tenants(lowereds, succs, tenants=names)


def _cpu_child_env(pythonpath: str) -> dict:
    """Environment of a scaling child: it times forced *host* devices, so
    it is pinned to the CPU and never reaches for an accelerator that the
    parent process already holds."""
    return {**os.environ, "PYTHONPATH": pythonpath, "JAX_PLATFORMS": "cpu"}


_SCALING_BODY = """
    import os, sys, time, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
    sys.path[:0] = {paths!r}
    import jax
    import numpy as np
    from benchmarks.workflow_sim import DEFAULT_ALPHAS, LAMBDA_USD_PER_S, _mt_stack
    from repro.core.fleet import multi_tenant_replay
    from repro.launch.mesh import make_fleet_mesh
    stack = _mt_stack(tenants={tenants}, episodes={episodes})
    alphas = np.asarray(DEFAULT_ALPHAS)
    mesh = make_fleet_mesh()
    multi_tenant_replay(stack, alphas, LAMBDA_USD_PER_S, mesh=mesh)  # warm-up
    t0 = time.perf_counter()
    rep = multi_tenant_replay(stack, alphas, LAMBDA_USD_PER_S, mesh=mesh)
    wall = time.perf_counter() - t0
    shards = len(rep.post_final.sharding.device_set)
    print(json.dumps({{"devices": len(jax.devices()), "shards": shards,
                       "wall_s": wall}}))
"""


def multi_tenant_scaling(devices=(1, 2, 4, 8), tenants: int = 8,
                         episodes: int = 200) -> list[dict]:
    """Time the sharded multi-tenant call under 1/2/4/8 forced host
    devices (fresh subprocess each — XLA_FLAGS must be set before the
    first jax import).  Wall-clock scaling on CPU is bounded by the
    physical core count (recorded as ``host_cpus``); the shard count
    verifies the tenants x grid axis really was partitioned."""
    root = str(pathlib.Path(__file__).resolve().parents[1])
    paths = [root, str(pathlib.Path(root) / "src")]
    rows = []
    for d in devices:
        code = textwrap.dedent(_SCALING_BODY.format(
            devices=d, paths=paths, tenants=tenants, episodes=episodes))
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=600, env=_cpu_child_env(paths[1]),
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"scaling subprocess ({d} devices) failed:\n"
                f"{proc.stderr[-2000:]}")
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        row["host_cpus"] = os.cpu_count()
        rows.append(row)
    return rows


def multi_tenant_record(tenants: int = 8, alphas=DEFAULT_ALPHAS,
                        episodes: int = 200, seed: int = SEED,
                        scaling_devices=(1, 2, 4, 8)) -> dict:
    """The BENCH_fleet.json ``multi_tenant`` section: ≥8 tenants x grid x
    episodes in one jit'd sharded call, bitwise (f64) per-tenant parity
    against T independent ``fleet_replay`` calls, one-call vs per-tenant
    wall times, and the forced-host-device scaling rows."""
    from jax import enable_x64

    alphas_arr = np.asarray(alphas)

    # --- parity first (f64, unsharded single device): every per-tenant
    # row block of the one-call report must equal its independent replay.
    # The single run replays the same padded lowering with the tenant's
    # episode mask, so the comparison stays bitwise even if the stack
    # ever goes ragged across episodes or op counts.
    with enable_x64():
        stack = _mt_stack(tenants, episodes, seed)
        report = multi_tenant_replay(stack, alphas_arr, LAMBDA_USD_PER_S)
        for t in range(tenants):
            single = fleet_replay(
                stack.lowered[t], stack.success[t], alphas_arr,
                LAMBDA_USD_PER_S, pred_ok=stack.pred_ok[t],
                ep_mask=stack.ep_mask[t])
            for f in dataclasses.fields(single):
                if f.name in ("alphas", "lambdas", "ep_mask"):
                    continue
                if not np.array_equal(getattr(single, f.name),
                                      getattr(report, f.name)[t]):
                    raise AssertionError(
                        f"multi-tenant parity broke: tenant {t} field "
                        f"{f.name}")

    # --- then speed (fleet default dtype, matching the other records)
    stack = _mt_stack(tenants, episodes, seed)
    multi_tenant_replay(stack, alphas_arr, LAMBDA_USD_PER_S)   # warm-up
    t0 = time.perf_counter()
    multi_tenant_replay(stack, alphas_arr, LAMBDA_USD_PER_S)
    one_call_s = time.perf_counter() - t0

    for t in range(tenants):                                   # warm-up
        fleet_replay(stack.lowered[t], stack.success[t], alphas_arr,
                     LAMBDA_USD_PER_S, pred_ok=stack.pred_ok[t])
    t0 = time.perf_counter()
    for t in range(tenants):
        fleet_replay(stack.lowered[t], stack.success[t], alphas_arr,
                     LAMBDA_USD_PER_S, pred_ok=stack.pred_ok[t])
    per_tenant_s = time.perf_counter() - t0

    record = {
        "benchmark": "autoreply_multi_tenant_replay",
        "tenants": tenants,
        "grid_points": len(alphas_arr),
        "episodes": episodes,
        "one_call_s": one_call_s,
        "per_tenant_calls_s": per_tenant_s,
        "speedup": per_tenant_s / one_call_s,
        "parity": {"bitwise_f64_vs_independent_fleet_replay": True},
        "scaling": multi_tenant_scaling(
            scaling_devices, tenants, episodes) if scaling_devices else [],
    }
    return record


def _episode_sharded_shards(lowered, success, alphas, mesh,
                            n_segments) -> int:
    """Count the devices the episode-sharded stats pass really
    partitioned over.  The public report is numpy, so the check reaches
    one level down: rebuild the executable's inputs and read the output
    sharding off the cached compiled call."""
    import jax.numpy as jnp

    from repro.core import fleet
    from repro.core.batch_decision import _f

    alphas = np.atleast_1d(np.asarray(alphas, float))
    lams = np.full_like(alphas, LAMBDA_USD_PER_S)
    chunks = fleet.chunk_episodes(lowered, success, n_segments)
    static = fleet._pack_static(lowered, chunks.has_refiner)
    post0 = jnp.broadcast_to(
        jnp.stack([_f(lowered.a0), _f(lowered.b0)], -1)[None],
        (alphas.shape[0], lowered.n_ops, 2))
    args = (_f(lowered.discount), _f(alphas), _f(lams), _f(lowered.gamma),
            jnp.asarray(chunks.success), jnp.asarray(chunks.pred_ok),
            _f(chunks.chunk_P), jnp.asarray(chunks.ep_mask))
    starts, _ = fleet._boundary_scan(static, post0, *args, throttle_every=1,
                                     K=1, use_lower_bound=False)
    fn = fleet._seg_executable(mesh, "fleet", 1, 1, False)
    _, ys = fn(static, starts, *args)
    return len(ys["makespan_s"].sharding.device_set)


_ES_SCALING_BODY = """
    import os, sys, time, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
    sys.path[:0] = {paths!r}
    import jax
    import numpy as np
    from benchmarks.workflow_sim import (
        DEFAULT_ALPHAS, LAMBDA_USD_PER_S, _autoreply_fleet,
        _episode_sharded_shards)
    from repro.core import episode_sharded_replay
    from repro.launch.mesh import make_fleet_mesh
    lowered, success, _ = _autoreply_fleet(episodes={episodes})
    alphas = np.asarray(DEFAULT_ALPHAS)
    mesh = make_fleet_mesh()
    kw = dict(n_segments={segments}, mesh=mesh)
    episode_sharded_replay(lowered, success, alphas, LAMBDA_USD_PER_S, **kw)
    t0 = time.perf_counter()
    episode_sharded_replay(lowered, success, alphas, LAMBDA_USD_PER_S, **kw)
    wall = time.perf_counter() - t0
    shards = _episode_sharded_shards(lowered, success, alphas, mesh,
                                     {segments})
    print(json.dumps({{"devices": len(jax.devices()), "shards": shards,
                       "wall_s": wall}}))
    sys.stdout.flush()
    os._exit(0)  # skip XLA teardown: it can segfault under forced device
                 # counts with GB-scale live buffers, after the row above
                 # has already been emitted
"""


def episode_sharded_scaling(devices=(1, 2, 4, 8), episodes: int = 1_000_000,
                            segments: int = 8) -> list[dict]:
    """Time the segment-sharded single-tenant replay under 1/2/4/8 forced
    host devices (fresh subprocess each, as in
    :func:`multi_tenant_scaling`).  Same 2-core caveat: wall-clock past
    the physical core count is overhead-bound; the ``shards`` column is
    what verifies the episode axis really was partitioned."""
    root = str(pathlib.Path(__file__).resolve().parents[1])
    paths = [root, str(pathlib.Path(root) / "src")]
    rows = []
    for d in devices:
        code = textwrap.dedent(_ES_SCALING_BODY.format(
            devices=d, paths=paths, episodes=episodes, segments=segments))
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=1200, env=_cpu_child_env(paths[1]),
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"episode-sharded scaling subprocess ({d} devices) "
                f"failed:\n{proc.stderr[-2000:]}")
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        row["host_cpus"] = os.cpu_count()
        rows.append(row)
    return rows


def episode_sharded_record(episodes: int = 1_000_000,
                           alphas=DEFAULT_ALPHAS, seed: int = SEED,
                           segments: int = 8,
                           scaling_devices=(1, 2, 4, 8)) -> dict:
    """The BENCH_fleet.json ``episode_sharded`` section: one tenant's
    E-episode AutoReply log replayed as C independent scan segments with
    the posterior-handoff boundary pass.  Bitwise-f64 parity against the
    unsharded ``fleet_replay`` is asserted at the full episode count
    *before* any timing is reported, as is the decision-fraction parity
    of the log-axis-sharded §12.1 counterfactual grid the calibration
    reroute rides on."""
    from jax import enable_x64

    from repro.core.batch_decision import (
        counterfactual_grid,
        counterfactual_grid_sharded,
    )

    alphas_arr = np.asarray(alphas)

    # --- parity first (f64, in-process): every field of the sharded
    # report must equal the sequential scan at the full episode count.
    with enable_x64():
        lowered, success, _ = _autoreply_fleet(episodes, seed)
        base = fleet_replay(lowered, success, alphas_arr, LAMBDA_USD_PER_S)
        sharded = episode_sharded_replay(
            lowered, success, alphas_arr, LAMBDA_USD_PER_S,
            n_segments=segments)
        for f in dataclasses.fields(base):
            if not np.array_equal(getattr(base, f.name),
                                  getattr(sharded, f.name)):
                raise AssertionError(
                    f"episode-sharded parity broke: field {f.name}")
        piped = episode_sharded_replay(
            lowered, success, alphas_arr, LAMBDA_USD_PER_S,
            n_segments=segments, pipelined=True)
        for f in dataclasses.fields(base):
            if not np.array_equal(getattr(base, f.name),
                                  getattr(piped, f.name)):
                raise AssertionError(
                    f"pipelined episode-sharded parity broke: field {f.name}")
        del base, sharded, piped

    # --- grid-reroute parity: the log-axis-sharded counterfactual grid
    # (what offline_replay uses past its shard_threshold) vs the
    # unsharded grid — decision fractions bitwise, float sums to reorder
    # tolerance.
    rng = np.random.default_rng(seed)
    n_rows = min(episodes, 4096)
    glat = rng.uniform(0.2, 3.0, n_rows)
    gcost = rng.uniform(0.001, 0.03, n_rows)
    g_alphas = np.array([0.0, 0.25, 0.5, 0.75, 1.0])
    g_lams = np.array([0.005, 0.01, 0.05, 0.1])
    with enable_x64():
        g0 = counterfactual_grid(0.62, glat, gcost, g_alphas, g_lams,
                                 rho=0.41)
        g1 = counterfactual_grid_sharded(0.62, glat, gcost, g_alphas,
                                         g_lams, rho=0.41,
                                         segments=max(2, segments))
    if not np.array_equal(g0["speculate_fraction"],
                          g1["speculate_fraction"]):
        raise AssertionError("sharded grid decision fractions diverged")
    grid_rel = max(
        float(np.max(np.abs(g0[k] - g1[k])
                     / np.maximum(np.abs(g0[k]), 1e-300)))
        for k in ("expected_latency_s", "expected_cost_usd",
                  "expected_waste_usd"))
    if grid_rel > 1e-12:
        raise AssertionError(
            f"sharded grid drifted past reorder tolerance: {grid_rel:.2e}")

    # --- then speed (fleet default dtype).  Even on one in-process
    # device the sharded path wins (~2x at 1M episodes): vmapping the
    # stats pass over C segments vectorizes the per-episode body across
    # the segment batch dim, cutting the sequential scan depth C-fold —
    # which more than repays the extra boundary pass.  The multi-device
    # story lives in the scaling rows (on this 2-core container in the
    # shards column rather than the wall-clock; EXPERIMENTS.md §Perf).
    lowered, success, _ = _autoreply_fleet(episodes, seed)
    fleet_replay(lowered, success, alphas_arr, LAMBDA_USD_PER_S)
    t0 = time.perf_counter()
    fleet_replay(lowered, success, alphas_arr, LAMBDA_USD_PER_S)
    unsharded_s = time.perf_counter() - t0

    episode_sharded_replay(lowered, success, alphas_arr, LAMBDA_USD_PER_S,
                           n_segments=segments)
    t0 = time.perf_counter()
    episode_sharded_replay(lowered, success, alphas_arr, LAMBDA_USD_PER_S,
                           n_segments=segments)
    sharded_s = time.perf_counter() - t0

    # Pipelined variant: same math, but segment c's stats dispatch
    # overlaps segment c+1's posterior handoff via JAX's async dispatch
    # (and skips the last segment's handoff outright).  The trade: stats
    # run one executable per segment instead of vmapped across segments,
    # so on this 2-core container (no devices to overlap onto) the row
    # records a *slower* wall than two-pass — kept as an honest baseline
    # for multi-device hosts, where per-segment stats land on their own
    # devices (parity was asserted above, pre-timing).
    episode_sharded_replay(lowered, success, alphas_arr, LAMBDA_USD_PER_S,
                           n_segments=segments, pipelined=True)
    t0 = time.perf_counter()
    episode_sharded_replay(lowered, success, alphas_arr, LAMBDA_USD_PER_S,
                           n_segments=segments, pipelined=True)
    pipelined_s = time.perf_counter() - t0

    return {
        "benchmark": "autoreply_episode_sharded_replay",
        "episodes": episodes,
        "segments": segments,
        "grid_points": len(alphas_arr),
        "unsharded_s": unsharded_s,
        "sharded_s": sharded_s,
        "speedup": unsharded_s / sharded_s,
        "parity": {
            "bitwise_f64_vs_fleet_replay": True,
            "grid_reroute_fraction_bitwise": True,
            "grid_reroute_max_rel_error": grid_rel,
        },
        "pipelined": {
            "pipelined_s": pipelined_s,
            "speedup_vs_two_pass": sharded_s / pipelined_s,
            "speedup_vs_unsharded": unsharded_s / pipelined_s,
            "parity": {"bitwise_f64_vs_fleet_replay": True},
        },
        "scaling": episode_sharded_scaling(
            scaling_devices, episodes, segments) if scaling_devices else [],
    }


def online_service_record(batch_sizes=(1, 64, 1024), n_rows: int = 64,
                          reps: int = 20, seed: int = SEED,
                          require_speedup: float | None = 20.0) -> dict:
    """The BENCH_fleet.json ``online_service`` section: the jit'd batched
    decision service (device-resident posterior table, one donated tick
    per batch) vs the scalar ``ThreadedSpeculativeRunner.decide`` loop.

    Parity is asserted before any timing: under ``enable_x64`` every
    batched decision (flag, EV, threshold, margin) must be bitwise equal
    to ``decision.evaluate`` on the same posterior rows — the
    contraction-pinned gate, not the fleet engine's 1-ULP FMA tolerance —
    and the §7.5 lower-bound tick must flag-match the scipy-backed scalar
    path.  Timing then runs at the fleet default dtype: per batch size B,
    ``reps`` warm ticks (each tick's flags pulled to host — the honest
    per-tick round-trip an online service pays) against B scalar
    ``decide`` calls per rep.  ``require_speedup`` (full runs) asserts
    the B=max per-decision speedup floor.
    """
    from jax import enable_x64

    from repro.core.decision import Decision
    from repro.core.online import OnlineDecisionService
    from repro.core.posterior import BetaPosterior
    from repro.serving.spec_bridge import EngineOp, ThreadedSpeculativeRunner

    rng = np.random.default_rng(seed)

    def build_service(**kw):
        svc = OnlineDecisionService(**kw)
        for i in range(n_rows):
            svc.register_edge(("classifier", f"drafter{i}"),
                              dep_type=DependencyType.ROUTER_K_WAY,
                              k=2 + i % 7)
        return svc

    op = EngineOp("drafter", engine=None, max_new_tokens=160)
    runner = ThreadedSpeculativeRunner(lambda: (None, None), op)
    pricing_in, pricing_out = 3e-6, 15e-6      # paper/frontier-default

    def requests(B):
        return dict(
            rows=rng.integers(0, n_rows, B),
            alpha=rng.uniform(0.0, 1.0, B),
            lam=rng.uniform(1e-3, 0.5, B),
            lat=rng.uniform(0.05, 4.0, B),
        )

    def svc_tick(svc, req, **kw):
        return svc.tick(
            req["rows"], alpha=req["alpha"], lambda_usd_per_s=req["lam"],
            latency_s=req["lat"], input_tokens=32, output_tokens=160,
            input_price=pricing_in, output_price=pricing_out, **kw)

    # --- parity first (f64): bitwise vs the scalar runner's evaluate
    with enable_x64():
        svc = build_service()
        B_par = max(batch_sizes)
        req = requests(B_par)
        snap = svc.posterior_snapshot()
        d = svc_tick(svc, req)
        for i in range(B_par):
            r = int(req["rows"][i])
            post = BetaPosterior(alpha=float(snap[r, 0]), beta=float(snap[r, 1]))
            ref = runner.decide_full(post, float(req["alpha"][i]),
                                     float(req["lam"][i]), float(req["lat"][i]))
            if (bool(d.flag[i]) != (ref.decision is Decision.SPECULATE)
                    or d.EV_usd[i] != ref.EV_usd
                    or d.threshold_usd[i] != ref.threshold_usd
                    or d.margin_usd[i] != ref.margin_usd):
                raise AssertionError(
                    f"online service / scalar decide divergence at row {i}")
        # §7.5 flag parity (EV inherits the betaincinv-vs-ppf allowance)
        d_lb = svc_tick(svc, req, use_lower_bound=True)
        for i in range(B_par):
            r = int(req["rows"][i])
            post = BetaPosterior(alpha=float(snap[r, 0]), beta=float(snap[r, 1]))
            ref = runner.decide_full(post, float(req["alpha"][i]),
                                     float(req["lam"][i]), float(req["lat"][i]),
                                     use_lower_bound=True)
            if bool(d_lb.flag[i]) != (ref.decision is Decision.SPECULATE):
                raise AssertionError(
                    f"online service lower-bound flag divergence at row {i}")

    # --- then speed (fleet default dtype).  This container's 2 cores are
    # shared with the harness, so each side takes the best of several
    # rounds — the standard noise-robust estimator; both sides get the
    # same treatment.
    svc = build_service()
    posts = [BetaPosterior(alpha=float(a), beta=float(b))
             for a, b in svc.posterior_snapshot()]
    rounds = 10
    batches = []
    for B in batch_sizes:
        # many short rounds: co-tenant CPU bursts last longer than one
        # round, so the min reliably lands in a quiet window
        reps_eff = max(4, min(reps, 4096 // max(1, B)))
        req = requests(B)
        # the packed hot path: a production batcher accumulates requests
        # into exactly this block between ticks, so the timed loop hands
        # it over zero-copy (the scalar loop likewise receives its
        # ready-made per-request args); the block is built in the
        # service's working dtype so the timed executable is the real
        # zero-copy one even under process-wide x64
        import jax

        fdtype = np.dtype(
            "float64" if jax.config.jax_enable_x64 else "float32")
        row_packed = req["rows"].astype(np.int32)
        reqs_packed = np.zeros((B, 7), fdtype)
        for j, key in enumerate(("alpha", "lam", "lat")):
            reqs_packed[:, j] = req[key]
        reqs_packed[:, 3], reqs_packed[:, 4] = 32, 160
        reqs_packed[:, 5], reqs_packed[:, 6] = pricing_in, pricing_out
        svc.tick_packed(row_packed, reqs_packed)    # warm the executable
        svc.tick_packed(row_packed, reqs_packed)
        tick_s = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(reps_eff):
                d = svc.tick_packed(row_packed, reqs_packed)
                d.speculate                     # per-tick host round-trip
            tick_s = min(tick_s, (time.perf_counter() - t0) / reps_eff)

        args = [(posts[int(req["rows"][i])], float(req["alpha"][i]),
                 float(req["lam"][i]), float(req["lat"][i]))
                for i in range(B)]
        for a in args[: min(B, 8)]:             # warm scalar caches
            runner.decide(*a)
        scalar_s = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(reps_eff):
                for a in args:
                    runner.decide(*a)
            scalar_s = min(scalar_s, (time.perf_counter() - t0) / reps_eff)

        batches.append({
            "B": int(B),
            "reps": reps_eff,               # actual warm reps per round
            "ticks_per_s": 1.0 / tick_s,
            "us_per_decision": tick_s / B * 1e6,
            "scalar_us_per_decision": scalar_s / B * 1e6,
            "speedup": scalar_s / tick_s,
        })

    record = {
        "benchmark": "online_decision_service",
        "rows": n_rows,
        "reps": reps,                   # requested cap; per-batch rows
        "rounds": rounds,               # carry the actual reps used
        "parity": {
            "bitwise_f64_vs_scalar_evaluate": True,
            "lower_bound_flags_match": True,
        },
        "batches": batches,
    }
    if require_speedup is not None:
        top = batches[-1]
        if top["speedup"] < require_speedup:
            raise AssertionError(
                f"online service speedup at B={top['B']} is "
                f"{top['speedup']:.1f}x < required {require_speedup}x")
    return record


_BEAM_SHARED_STATS = (
    "makespan_s", "total_cost_usd", "waste_usd", "launched", "committed",
    "EV_usd", "threshold_usd", "speculate", "edge_launched",
    "edge_committed", "edge_waste_usd", "start_s", "finish_s",
    "post_alpha", "post_beta",
)


def beam_record(alphas=DEFAULT_ALPHAS, episodes: int = 200,
                seed: int = SEED, widths=(1, 2, 4),
                candidates: int = 3) -> dict:
    """The BENCH_fleet.json ``beam`` section: the top-k speculation engine
    (repro.core.beam) on the AutoReply log, sweeping beam width as the
    third grid axis in one jit'd call.

    Two parity gates run before any timing is reported, mirroring the
    tier-1 suite (tests/test_beam.py):

    1. single-candidate discipline — the ``width == 1`` slice of the beam
       replay on the classic (no-beam-confidence) lowering is bitwise-f64
       equal to ``fleet_replay`` on every shared statistic;
    2. wide-beam twin — widths > 1 on the real top-``candidates`` intent
       beam (confidences = the Zipf head of the §7.6 running example)
       match the pure-numpy ``reference_beam_replay``: decisions, counts,
       ranks and event times bitwise, USD stats inside 1-ULP FMA
       tolerance.

    The hit rank of each episode is the drawn intent's index in the
    confidence-sorted candidate list (rank >= candidates -> miss), so a
    wider beam converts exactly the tail-intent episodes into commits —
    the Pareto rows published here attribute every launched candidate
    (``launched_candidates`` / ``cancelled_candidates``) in USD."""
    from jax import enable_x64

    from repro.core import (
        beam_replay,
        hit_rank_from_success,
        reference_beam_replay,
    )

    alphas_arr = np.asarray(alphas)
    widths = tuple(int(w) for w in widths)
    conf = {("classifier", "drafter"): tuple(PROBS[:candidates])}
    draws = _draws(episodes, seed)

    # --- parity gate 1 (f64): w=1 beam path bitwise vs fleet_replay on
    # the classic single-candidate lowering, before any timing claim.
    with enable_x64():
        lowered, success, _ = _autoreply_fleet(episodes, seed)
        ref = fleet_replay(lowered, success, alphas_arr, BEAM_LAMBDA_USD_PER_S)
        rep1 = beam_replay(lowered, hit_rank_from_success(success),
                           alphas_arr, BEAM_LAMBDA_USD_PER_S, [1])
        sl = rep1.width_slice(0)
        for name in _BEAM_SHARED_STATS:
            if not np.array_equal(sl[name], getattr(ref, name)):
                raise AssertionError(
                    f"beam w=1 parity broke vs fleet_replay: field {name}")
        del ref, rep1, sl

    # --- parity gate 2 (f64): the wide-beam sweep vs its pure-numpy
    # reference twin on the real intent beam.
    with enable_x64():
        lowered, _, vi = _autoreply_fleet(episodes, seed,
                                          beam_confidences=conf)
        hit = np.full((episodes, lowered.n_ops), -1, np.int32)
        hit[:, vi] = np.where(draws < candidates, draws, -1)
        rep = beam_replay(lowered, hit, alphas_arr, BEAM_LAMBDA_USD_PER_S,
                          list(widths))
        twin = reference_beam_replay(lowered, hit, alphas_arr,
                                     BEAM_LAMBDA_USD_PER_S, list(widths))
        for name in ("speculate", "w_eff", "edge_launched",
                     "edge_committed", "launched", "committed",
                     "launched_candidates", "cancelled_candidates",
                     "start_s", "finish_s", "makespan_s",
                     "post_alpha", "post_beta"):
            if not np.array_equal(getattr(rep, name), twin[name]):
                raise AssertionError(
                    f"beam reference parity broke: field {name}")
        ref_rel = 0.0
        for name in ("EV_usd", "threshold_usd", "edge_waste_usd",
                     "waste_usd", "total_cost_usd"):
            a, b = np.asarray(getattr(rep, name)), np.asarray(twin[name])
            rel = float(np.max(np.abs(a - b)
                               / np.maximum(np.abs(b), 1e-300)))
            ref_rel = max(ref_rel, rel)
            if rel > 1e-12:
                raise AssertionError(
                    f"beam reference drifted past ULP tolerance: "
                    f"{name} rel {rel:.2e}")
        pareto = rep.pareto()

    # --- then speed (fleet default dtype): one call sweeping all widths
    # vs one beam_replay call per width.
    lowered, _, vi = _autoreply_fleet(episodes, seed,
                                      beam_confidences=conf)
    beam_replay(lowered, hit, alphas_arr, BEAM_LAMBDA_USD_PER_S,
                list(widths))                                  # warm-up
    t0 = time.perf_counter()
    beam_replay(lowered, hit, alphas_arr, BEAM_LAMBDA_USD_PER_S, list(widths))
    one_call_s = time.perf_counter() - t0

    for w in widths:                                           # warm-up
        beam_replay(lowered, hit, alphas_arr, BEAM_LAMBDA_USD_PER_S, [w])
    t0 = time.perf_counter()
    for w in widths:
        beam_replay(lowered, hit, alphas_arr, BEAM_LAMBDA_USD_PER_S, [w])
    per_width_s = time.perf_counter() - t0

    return {
        "benchmark": "autoreply_beam_width_sweep",
        "widths": list(widths),
        "candidates": candidates,
        "confidences": list(PROBS[:candidates]),
        "lambda_usd_per_s": BEAM_LAMBDA_USD_PER_S,
        "episodes": episodes,
        "grid_points": len(alphas_arr),
        "one_call_s": one_call_s,
        "per_width_calls_s": per_width_s,
        "speedup": per_width_s / one_call_s,
        "parity": {
            "w1_bitwise_f64_vs_fleet_replay": True,
            "reference_decisions_bitwise": True,
            "reference_max_rel_error": ref_rel,
        },
        "pareto_dtype": "float64",
        "pareto": {
            str(w): {
                str(a): {
                    "latency_s": float(pareto["latency_s"][wi, gi]),
                    "cost_usd": float(pareto["cost_usd"][wi, gi]),
                    "waste_usd": float(pareto["waste_usd"][wi, gi]),
                    "launched": int(pareto["launched"][wi, gi]),
                    "committed": int(pareto["committed"][wi, gi]),
                    "launched_candidates": float(
                        pareto["launched_candidates"][wi, gi]),
                    "cancelled_candidates": float(
                        pareto["cancelled_candidates"][wi, gi]),
                }
                for gi, a in enumerate(alphas)
            }
            for wi, w in enumerate(widths)
        },
    }


def fleet_speedup(alphas=DEFAULT_ALPHAS, episodes: int = 200,
                  seed: int = SEED, *, write: bool = True,
                  tenants: int = 8, scaling_devices=(1, 2, 4, 8),
                  episode_sharded_episodes: int = 1_000_000,
                  episode_sharded_segments: int = 8,
                  online_batch_sizes=(1, 64, 1024),
                  online_rows: int = 64,
                  online_reps: int = 20,
                  online_require_speedup: float | None = 20.0,
                  beam_widths=(1, 2, 4)) -> dict:
    """Measure scalar vs fleet wall time on the identical sweep — both the
    posterior-mean gate and the §7.5 credible-bound gate — plus the
    multi-tenant sharded-engine and online-decision-service records, and
    persist everything to BENCH_fleet.json (``write=False`` returns the
    record without touching the file — the --smoke path).  Methodology
    (EXPERIMENTS.md §Perf): jit warm-up excluded, identical inputs, parity
    asserted before timing is reported.

    The published ``pareto_fleet`` rows (and the parity gate feeding them)
    run under ``enable_x64`` so the numbers sit in the same dtype tier as
    the bitwise-f64 parity claims next to them (``pareto_dtype`` labels
    the row); the *timed* sweeps stay at the fleet default dtype, matching
    every historical speedup row.  The cross-dtype launch/commit equality
    the timing relies on holds because this workload's decision margins —
    |EV - threshold| ~1e-2 relative — sit orders above both the f32 mean
    error and the ~1e-5 f32 quantile error."""
    from jax import enable_x64

    n_runs = len(alphas) * episodes

    t0 = time.perf_counter()
    scalar = sweep(alphas, episodes, seed)
    scalar_s = time.perf_counter() - t0

    # parity + published pareto rows at f64 (the scalar sweep is plain
    # Python/scipy and therefore dtype-independent — one run serves both
    # the timing above and this parity gate)
    with enable_x64():
        fleet = fleet_sweep(alphas, episodes, seed)
    parity = assert_pareto_parity(scalar, fleet, alphas)

    # warm up the jit cache at the timed shape (the episode count is a
    # traced scan length, so only a full-size call compiles the right
    # executable)
    fleet_sweep(alphas, episodes, seed)
    t0 = time.perf_counter()
    fleet32 = fleet_sweep(alphas, episodes, seed)
    fleet_s = time.perf_counter() - t0
    # the run that produced the published timing is itself parity-checked
    # at its own (f32) dtype — the f64 gate above covers the published
    # pareto rows, this one covers the timed executable
    parity_f32 = assert_pareto_parity(scalar, fleet32, alphas)

    # §7.5 conservative mode: the scalar path pays a scipy beta.ppf per
    # Phase-2 decision; the fleet path inverts in-XLA via betaincinv.
    t0 = time.perf_counter()
    scalar_lb = sweep(alphas, episodes, seed, use_lower_bound=True)
    scalar_lb_s = time.perf_counter() - t0

    with enable_x64():
        fleet_lb = fleet_sweep(alphas, episodes, seed, use_lower_bound=True)
    parity_lb = assert_pareto_parity(scalar_lb, fleet_lb, alphas)

    fleet_sweep(alphas, episodes, seed, use_lower_bound=True)  # warm-up
    t0 = time.perf_counter()
    fleet_lb32 = fleet_sweep(alphas, episodes, seed, use_lower_bound=True)
    fleet_lb_s = time.perf_counter() - t0
    parity_lb32 = assert_pareto_parity(scalar_lb, fleet_lb32, alphas)

    record = {
        "benchmark": "autoreply_alpha_sweep",
        "alphas": list(alphas),
        "lambda_usd_per_s": LAMBDA_USD_PER_S,
        "episodes": episodes,
        "grid_points": len(alphas),
        "scalar_total_s": scalar_s,
        "fleet_total_s": fleet_s,
        "scalar_us_per_episode": scalar_s / n_runs * 1e6,
        "fleet_us_per_episode": fleet_s / n_runs * 1e6,
        "speedup": scalar_s / fleet_s,
        "parity": {
            "max_rel_error": parity["max_rel_error"],
            "timed_f32_max_rel_error": parity_f32["max_rel_error"],
            "launched_match": True,
            "committed_match": True,
        },
        "pareto_dtype": "float64",
        "pareto_fleet": {
            str(a): fleet[a] for a in alphas
        },
        "credible_bound": {
            "benchmark": "autoreply_alpha_sweep_lower_bound",
            "gamma": 0.1,
            "scalar_total_s": scalar_lb_s,
            "fleet_total_s": fleet_lb_s,
            "scalar_us_per_episode": scalar_lb_s / n_runs * 1e6,
            "fleet_us_per_episode": fleet_lb_s / n_runs * 1e6,
            "speedup": scalar_lb_s / fleet_lb_s,
            "parity": {
                "max_rel_error": parity_lb["max_rel_error"],
                "timed_f32_max_rel_error": parity_lb32["max_rel_error"],
                "launched_match": True,
                "committed_match": True,
            },
            "pareto_dtype": "float64",
            "pareto_fleet": {
                str(a): fleet_lb[a] for a in alphas
            },
        },
        "multi_tenant": multi_tenant_record(
            tenants=tenants, alphas=alphas, episodes=episodes, seed=seed,
            scaling_devices=scaling_devices,
        ),
        "episode_sharded": episode_sharded_record(
            episodes=episode_sharded_episodes, alphas=alphas, seed=seed,
            segments=episode_sharded_segments,
            scaling_devices=scaling_devices,
        ),
        "online_service": online_service_record(
            batch_sizes=online_batch_sizes, n_rows=online_rows,
            reps=online_reps, seed=seed,
            require_speedup=online_require_speedup,
        ),
        "beam": beam_record(
            alphas=alphas, episodes=episodes, seed=seed,
            widths=beam_widths,
        ),
    }
    if write:
        BENCH_PATH.write_text(json.dumps(record, indent=2) + "\n")
    return record


def smoke() -> dict:
    """benchmarks/run.py --smoke: the full BENCH_fleet.json record shape at
    tiny episode counts — every parity gate runs (scalar<->fleet Pareto,
    bitwise multi-tenant), no timing claims are made, and nothing is
    written to disk.  Wired into a fast pytest
    (tests/test_benchmarks_smoke.py) so schema or parity drift breaks
    tier-1 instead of rotting until the next manual benchmark run."""
    return fleet_speedup(
        alphas=(0.0, 0.5, 0.9, 1.0), episodes=24,
        write=False, tenants=3, scaling_devices=(),
        episode_sharded_episodes=48, episode_sharded_segments=3,
        online_batch_sizes=(1, 8), online_rows=8, online_reps=3,
        online_require_speedup=None, beam_widths=(1, 2, 3),
    )


def benchmarks() -> list[tuple[str, float, str]]:
    rows = []
    t0 = time.perf_counter()
    res = sweep()
    dt = (time.perf_counter() - t0) * 1e6 / 200
    ctrl = res["control"]
    best = res[0.9]
    rows.append((
        "workflow_alpha_sweep", dt,
        f"control={ctrl['latency_s']:.2f}s alpha0.9={best['latency_s']:.2f}s "
        f"waste=${best['waste_usd']:.4f} committed={best['committed']}/{best['launched']}",
    ))
    record = fleet_speedup()
    rows.append((
        "workflow_fleet_replay", record["fleet_us_per_episode"],
        f"speedup={record['speedup']:.0f}x vs scalar "
        f"({record['scalar_us_per_episode']:.0f}us/ep -> "
        f"{record['fleet_us_per_episode']:.2f}us/ep), "
        f"parity max_rel={record['parity']['max_rel_error']:.1e}",
    ))
    lb = record["credible_bound"]
    rows.append((
        "workflow_fleet_replay_lower_bound", lb["fleet_us_per_episode"],
        f"speedup={lb['speedup']:.0f}x vs scalar "
        f"({lb['scalar_us_per_episode']:.0f}us/ep -> "
        f"{lb['fleet_us_per_episode']:.2f}us/ep), "
        f"parity max_rel={lb['parity']['max_rel_error']:.1e}",
    ))
    mt = record["multi_tenant"]
    n_ep = mt["tenants"] * mt["grid_points"] * mt["episodes"]
    scaling = " ".join(
        f"{r['devices']}dev={r['wall_s'] * 1e3:.0f}ms"
        for r in mt["scaling"]
    )
    rows.append((
        "workflow_multi_tenant_replay", mt["one_call_s"] / n_ep * 1e6,
        f"{mt['tenants']}T x {mt['grid_points']}G x {mt['episodes']}E in one "
        f"call; {mt['speedup']:.1f}x vs {mt['tenants']} fleet_replay calls; "
        f"bitwise-f64 per-tenant parity; scaling {scaling or 'n/a'}",
    ))
    es = record["episode_sharded"]
    n_es = es["episodes"] * es["grid_points"]
    es_scaling = " ".join(
        f"{r['devices']}dev={r['wall_s']:.1f}s" for r in es["scaling"]
    )
    rows.append((
        "workflow_episode_sharded_replay", es["sharded_s"] / n_es * 1e6,
        f"{es['episodes']}E x {es['grid_points']}G as {es['segments']} "
        f"segments; bitwise-f64 parity vs fleet_replay pre-timing; "
        f"{es['speedup']:.2f}x vs unsharded scan on one device (segment-"
        f"vmap cuts scan depth); scaling {es_scaling or 'n/a'}",
    ))
    os_rec = record["online_service"]
    top = os_rec["batches"][-1]
    per_b = " ".join(
        f"B{b['B']}={b['us_per_decision']:.2f}us/dec({b['speedup']:.0f}x)"
        for b in os_rec["batches"]
    )
    rows.append((
        "online_decision_service", top["us_per_decision"],
        f"{os_rec['rows']} rows; bitwise-f64 decide parity pre-timing; "
        f"{top['ticks_per_s']:.0f} ticks/s at B={top['B']}; {per_b} vs "
        f"scalar decide loop",
    ))
    bm = record["beam"]
    n_bm = bm["episodes"] * bm["grid_points"] * len(bm["widths"])
    w_hi, w_lo = str(max(bm["widths"])), str(min(bm["widths"]))
    mid_a = str(DEFAULT_ALPHAS[len(DEFAULT_ALPHAS) // 2])
    rows.append((
        "workflow_beam_width_sweep", bm["one_call_s"] / n_bm * 1e6,
        f"widths {bm['widths']} x {bm['grid_points']}G x {bm['episodes']}E "
        f"in one call; w=1 bitwise-f64 vs fleet_replay pre-timing; "
        f"{bm['speedup']:.1f}x vs per-width calls; committed@alpha{mid_a} "
        f"w{w_lo}->{w_hi}: {bm['pareto'][w_lo][mid_a]['committed']}->"
        f"{bm['pareto'][w_hi][mid_a]['committed']}",
    ))
    return rows
