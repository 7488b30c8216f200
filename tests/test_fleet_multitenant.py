"""Multi-tenant sharded fleet engine: T stacked tenants — each with its
own padded DAG, taxonomy-keyed prior, gamma and (ragged) episode log —
must replay bitwise-identically (float64) to T independent single-tenant
``fleet_replay`` calls, masked episodes must be identity scan steps, and
the donatable posterior carry must chain across calibration rounds.
(The 8-forced-device shard_map case lives in tests/test_multidevice.py.)
"""
import dataclasses

import numpy as np
import pytest
from jax import enable_x64

from repro.core import (
    fleet_replay,
    lower_workflow,
    multi_tenant_replay,
    stack_tenants,
)
from repro.core.drift import DriftMonitor, TriggerKind

from test_fleet_parity import make_random_dag

GRID_ALPHAS = np.array([0.0, 0.5, 0.9])
GRID_LAMS = np.array([0.01, 0.08, 0.08])


def _lower_dag(dag):
    """Lower a RandomDag and reorder its episode arrays to topo order."""
    params = dag.fresh_params(0.5, 0.01)
    wf = dag.build_workflow(0)
    lowered = lower_workflow(wf, params, predictors=dag.predictors(0))
    order = np.array([int(n[1:]) for n in lowered.names])
    return lowered, dag.success[:, order], dag.pred_ok[:, order]


def _stack_for(seeds, episodes=None, **dag_kw):
    lowereds, succs, preds = [], [], []
    for i, seed in enumerate(seeds):
        e = episodes[i] if episodes is not None else 6
        lowered, success, pred_ok = _lower_dag(
            make_random_dag(seed, episodes=e, **dag_kw))
        lowereds.append(lowered)
        succs.append(success)
        preds.append(pred_ok)
    return (stack_tenants(lowereds, succs, pred_oks=preds),
            lowereds, succs, preds)


def _assert_tenant_parity(report, lowereds, succs, preds, *, ev_ulp=False):
    """Every field bitwise; with ``ev_ulp`` the EV column gets a 1-ULP
    allowance — the batched betaincinv can fuse one multiply differently
    under the tenant vmap than the single-tenant executable (same
    convention as the §7.5 rows in tests/test_fleet_parity.py); decisions,
    flags, timing, waste and posteriors stay bitwise either way."""
    for t, (lowered, success, pred_ok) in enumerate(
            zip(lowereds, succs, preds)):
        single = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS,
                              pred_ok=pred_ok)
        tr = report.tenant_report(t)
        for f in dataclasses.fields(single):
            if ev_ulp and f.name == "EV_usd":
                np.testing.assert_allclose(
                    single.EV_usd, tr.EV_usd, rtol=1e-13, atol=1e-16,
                    err_msg=f"tenant {t} field EV_usd")
                continue
            np.testing.assert_array_equal(
                getattr(single, f.name), getattr(tr, f.name),
                err_msg=f"tenant {t} field {f.name}")


@pytest.mark.parametrize("seeds", [(0, 1, 2, 3), (4, 5, 6)])
def test_multi_tenant_bitwise_parity(seeds):
    """Randomized per-tenant DAGs + priors, ragged op counts: the stacked
    one-call replay slices back to each tenant's independent
    ``fleet_replay`` bitwise at float64."""
    with enable_x64():
        episodes = [5 + i for i in range(len(seeds))]   # ragged on purpose
        stack, lowereds, succs, preds = _stack_for(seeds, episodes)
        report = multi_tenant_replay(stack, GRID_ALPHAS, GRID_LAMS)
        _assert_tenant_parity(report, lowereds, succs, preds)


def test_multi_tenant_lower_bound_and_per_tenant_gamma():
    """§7.5 credible-bound gating with a *different* gamma per tenant:
    each tenant's slice must equal its own single-tenant run (which
    carries that tenant's gamma into betaincinv)."""
    with enable_x64():
        gammas = (0.05, 0.25)
        lowereds, succs, preds = [], [], []
        for seed, gamma in zip((0, 3), gammas):
            dag = make_random_dag(seed, episodes=5, use_lower_bound=True)
            dag.gamma = gamma
            lowered, success, pred_ok = _lower_dag(dag)
            assert lowered.use_lower_bound and lowered.gamma == gamma
            lowereds.append(lowered)
            succs.append(success)
            preds.append(pred_ok)
        stack = stack_tenants(lowereds, succs, pred_oks=preds)
        assert stack.use_lower_bound
        np.testing.assert_array_equal(stack.gammas, gammas)
        report = multi_tenant_replay(stack, GRID_ALPHAS, GRID_LAMS)
        _assert_tenant_parity(report, lowereds, succs, preds, ev_ulp=True)


def test_ragged_episodes_do_not_perturb_other_tenants():
    """Regression (satellite): a tenant with fewer logs must not change
    the posterior trajectory (or any stats) of tenants that have more —
    masked scan steps are identity updates."""
    with enable_x64():
        long_low, long_suc, long_pred = _lower_dag(
            make_random_dag(1, episodes=8))
        short_low, short_suc, short_pred = _lower_dag(
            make_random_dag(2, episodes=3))

        solo = multi_tenant_replay(
            stack_tenants([long_low], [long_suc], pred_oks=[long_pred]),
            GRID_ALPHAS, GRID_LAMS)
        both = multi_tenant_replay(
            stack_tenants([long_low, short_low], [long_suc, short_suc],
                          pred_oks=[long_pred, short_pred]),
            GRID_ALPHAS, GRID_LAMS)

        a = solo.tenant_report(0)
        b = both.tenant_report(0)
        for f in dataclasses.fields(a):
            np.testing.assert_array_equal(
                getattr(a, f.name), getattr(b, f.name), err_msg=f.name)

        # the short tenant's padded episodes: zero stats, carried posterior
        E_s = both.n_episodes[1]
        assert E_s == 3
        np.testing.assert_array_equal(both.launched[1, E_s:], 0)
        np.testing.assert_array_equal(both.makespan_s[1, E_s:], 0.0)
        np.testing.assert_array_equal(both.waste_usd[1, E_s:], 0.0)
        V_s = both.n_ops[1]
        carried_a = both.post_alpha[1, E_s - 1, :, :V_s]
        carried_b = both.post_beta[1, E_s - 1, :, :V_s]
        for e in range(E_s, both.post_alpha.shape[1]):
            np.testing.assert_array_equal(
                both.post_alpha[1, e, :, :V_s], carried_a)
            np.testing.assert_array_equal(
                both.post_beta[1, e, :, :V_s], carried_b)
        # and the final carry equals the last real episode's posterior
        np.testing.assert_array_equal(
            np.asarray(both.post_final)[1, :, :V_s, 0], carried_a)
        np.testing.assert_array_equal(
            np.asarray(both.post_final)[1, :, :V_s, 1], carried_b)


def test_posterior_carry_chains_across_rounds():
    """Two replay rounds chained through ``post0=report.post_final`` (the
    donation path) equal one run over the concatenated episode log —
    repeated calibration rounds continue the same trajectories."""
    with enable_x64():
        lowered, success, pred_ok = _lower_dag(make_random_dag(7, episodes=8))
        stack_all = stack_tenants([lowered], [success], pred_oks=[pred_ok])
        full = multi_tenant_replay(stack_all, GRID_ALPHAS, GRID_LAMS,
                                   donate=False)

        s1 = stack_tenants([lowered], [success[:5]], pred_oks=[pred_ok[:5]])
        s2 = stack_tenants([lowered], [success[5:]], pred_oks=[pred_ok[5:]])
        r1 = multi_tenant_replay(s1, GRID_ALPHAS, GRID_LAMS, donate=False)
        r2 = multi_tenant_replay(s2, GRID_ALPHAS, GRID_LAMS,
                                 post0=r1.post_final, donate=True)

        np.testing.assert_array_equal(
            full.post_alpha[:, 5:], r2.post_alpha)
        np.testing.assert_array_equal(
            full.post_beta[:, 5:], r2.post_beta)
        np.testing.assert_array_equal(full.makespan_s[:, 5:], r2.makespan_s)
        np.testing.assert_array_equal(
            full.edge_committed[:, 5:], r2.edge_committed)
        np.testing.assert_array_equal(
            np.asarray(full.post_final), np.asarray(r2.post_final))


def test_final_posterior_rows_bounds_checks_grid_index():
    """Regression (satellite): ``final_posterior_rows`` trusted the
    caller's ``grid_index`` — an out-of-range or negative index either
    crashed deep in numpy or silently wrapped to a *different operating
    point's* posteriors before feeding the kill-switch.  It now raises a
    clear IndexError at the boundary and still serves every valid
    index."""
    with enable_x64():
        stack, lowereds, succs, preds = _stack_for((0, 2), [4, 4])
        report = multi_tenant_replay(stack, GRID_ALPHAS, GRID_LAMS)
        G = len(GRID_ALPHAS)
        for g in range(G):
            rows, a, b = report.final_posterior_rows(g)
            assert len(rows) == len(a) == len(b) > 0
        for bad in (G, G + 5, -1, -G):
            with pytest.raises(IndexError, match="grid_index"):
                report.final_posterior_rows(bad)


def test_stack_rejects_mixed_lower_bound_and_bad_shapes():
    lowered, success, pred_ok = _lower_dag(make_random_dag(0, episodes=4))
    lb_low, lb_suc, lb_pred = _lower_dag(
        make_random_dag(3, episodes=4, use_lower_bound=True))
    with pytest.raises(ValueError, match="use_lower_bound"):
        stack_tenants([lowered, lb_low], [success, lb_suc])
    with pytest.raises(ValueError, match="success"):
        stack_tenants([lowered], [success[:, :1]])
    with pytest.raises(ValueError, match="unique"):
        stack_tenants([lowered, lowered], [success, success],
                      tenants=["a", "a"])


def test_fleet_replay_ep_mask_identity_steps():
    """Single-workflow ragged support: a masked suffix replays identically
    to truncating the episode log, and ``pareto()`` means are taken over
    the real episodes only (padded zero rows must not dilute the §12.3
    statistics)."""
    with enable_x64():
        lowered, success, pred_ok = _lower_dag(make_random_dag(4, episodes=6))
        mask = np.array([True] * 4 + [False] * 2)
        masked = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS,
                              pred_ok=pred_ok, ep_mask=mask)
        short = fleet_replay(lowered, success[:4], GRID_ALPHAS, GRID_LAMS,
                             pred_ok=pred_ok[:4])
        for f in dataclasses.fields(short):
            if f.name in ("alphas", "lambdas", "ep_mask"):
                continue
            np.testing.assert_array_equal(
                getattr(short, f.name), getattr(masked, f.name)[:4],
                err_msg=f.name)
        assert masked.launched[4:].sum() == 0
        np.testing.assert_array_equal(masked.waste_usd[4:], 0.0)
        p_short, p_masked = short.pareto(), masked.pareto()
        for k in ("latency_s", "cost_usd", "waste_usd", "launched",
                  "committed"):
            np.testing.assert_array_equal(p_short[k], p_masked[k],
                                          err_msg=f"pareto {k}")


def test_fleet_posteriors_feed_drift_monitor_in_one_call():
    """The sharded engine's posterior snapshot drives §12.5 trigger 2
    per (tenant, edge) in a single vectorized call: a drifting tenant's
    kill-switch flips without touching a healthy tenant sharing the same
    edge names."""
    with enable_x64():
        stack, lowereds, succs, preds = _stack_for((0, 2), [6, 6])
        report = multi_tenant_replay(stack, GRID_ALPHAS, GRID_LAMS)
        tenant_edges, post_a, post_b = report.final_posterior_rows(0)
        # both tenants must contribute rows for the isolation check to bite
        assert {t for t, _ in tenant_edges} == set(stack.tenants)
        assert np.all(post_a > 0) and np.all(post_b > 0)

        mon = DriftMonitor(credible_consecutive_n=2)
        # drive one tenant's rows into certain breach, keep the others safe
        rigged_a = np.where([t == "tenant0" for t, _ in tenant_edges],
                            0.5, 50.0)
        rigged_b = np.where([t == "tenant0" for t, _ in tenant_edges],
                            9.5, 1.0)
        for _ in range(2):
            evs = mon.check_credible_bound_fleet(
                tenant_edges, rigged_a, rigged_b,
                alpha=0.5, C_spec=0.0135, L_value=0.064)
        fired = [e for e in evs if e is not None]
        assert fired and all(
            e.kind == TriggerKind.CREDIBLE_BOUND_FLOOR and e.tenant == "tenant0"
            for e in fired)
        for tenant, edge in tenant_edges:
            assert mon.edge_enabled(edge, tenant=tenant) == (
                tenant != "tenant0")
