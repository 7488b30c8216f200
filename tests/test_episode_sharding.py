"""Episode-sharded replay engine: ``episode_sharded_replay`` must be
bitwise-f64 equal to the unsharded ``fleet_replay`` on the same log —
decisions, flags, times and posterior trajectories exactly, EV/waste to
the established 1-ULP FMA allowance — across segment counts (including a
ragged last chunk), discounted posteriors, §7.5 credible-bound gating and
streaming cancels.  Plus the ``chunk_episodes`` input contract and the
``lax.associative_scan`` closed-form composition of segment posteriors.
(The 8-forced-device shard_map row lives in tests/test_multidevice.py.)
"""
import dataclasses

import numpy as np
import pytest
from jax import enable_x64

from repro.core import (
    Edge,
    Operation,
    PlannerParams,
    Workflow,
    chunk_episodes,
    compose_segment_posteriors,
    episode_sharded_replay,
    fleet_replay,
    lower_workflow,
)
from repro.core.posterior import BetaPosterior
from repro.core.predictor import TemplatePredictor

from test_fleet_parity import ULP, make_random_dag
from test_fleet_multitenant import _lower_dag

GRID_ALPHAS = np.array([0.0, 0.5, 0.9])
GRID_LAMS = np.array([0.01, 0.08, 0.08])
SEGMENTS = (1, 2, 3, 7)   # 7 does not divide the 10-episode logs: ragged


def _assert_sharded_parity(base, sharded, *, ev_ulp=False):
    """Everything bitwise; ``ev_ulp`` gives the EV column the 1-ULP
    allowance (the segment-vmapped betaincinv can fuse one multiply
    differently than the unvmapped scan — same convention as the
    tenant-vmapped §7.5 rows in tests/test_fleet_multitenant.py)."""
    for f in dataclasses.fields(base):
        if ev_ulp and f.name == "EV_usd":
            np.testing.assert_allclose(
                base.EV_usd, sharded.EV_usd, **ULP, err_msg="EV_usd")
            continue
        np.testing.assert_array_equal(
            getattr(base, f.name), getattr(sharded, f.name),
            err_msg=f.name)


@pytest.mark.parametrize("n_segments", SEGMENTS)
@pytest.mark.parametrize("seed", range(4))
def test_random_dag_sharded_bitwise_parity(seed, n_segments):
    """Randomized DAGs, C ∈ {1, 2, 3, 7} over 10-episode logs (7 leaves a
    ragged last chunk): the two-pass sharded replay is bitwise-f64 equal
    to the single sequential scan."""
    with enable_x64():
        lowered, success, pred_ok = _lower_dag(
            make_random_dag(seed, episodes=10))
        base = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS,
                            pred_ok=pred_ok)
        sharded = episode_sharded_replay(
            lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
            n_segments=n_segments)
        _assert_sharded_parity(base, sharded)


@pytest.mark.parametrize("n_segments", SEGMENTS)
@pytest.mark.parametrize("seed", [100, 101])
def test_sharded_discounted_posterior_parity(seed, n_segments):
    """discount<1: the exponential-forgetting carry hands off exactly at
    segment boundaries (the sequential-handoff regime — there is no
    associative closed form to fall back on)."""
    with enable_x64():
        lowered, success, pred_ok = _lower_dag(
            make_random_dag(seed, episodes=10, discount=0.9))
        assert np.any(lowered.discount[lowered.has_edge] < 1.0)
        base = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS,
                            pred_ok=pred_ok)
        sharded = episode_sharded_replay(
            lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
            n_segments=n_segments)
        _assert_sharded_parity(base, sharded)


@pytest.mark.parametrize("n_segments", SEGMENTS)
@pytest.mark.parametrize("seed", range(4))
def test_sharded_lower_bound_parity(seed, n_segments):
    """§7.5 credible-bound gating: the betaincinv inversion runs on each
    segment's carried-in posterior and must track the unsharded scan —
    decisions, flags and posteriors bitwise, EV to 1 ULP."""
    with enable_x64():
        lowered, success, pred_ok = _lower_dag(
            make_random_dag(seed, episodes=10, use_lower_bound=True))
        assert lowered.use_lower_bound
        base = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS,
                            pred_ok=pred_ok)
        sharded = episode_sharded_replay(
            lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
            n_segments=n_segments)
        _assert_sharded_parity(base, sharded, ev_ulp=True)


def _make_stream_case(E=10, K=4):
    """The §9.1 streaming-cancel test vector: a chunked u -> v edge whose
    replay mixes cancelled and surviving streams.  Returns (lowered,
    success, cP)."""
    rng = np.random.default_rng(7)
    chunk_P = rng.uniform(0.05, 0.95, (E, K))

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
    wf = wf.freeze()
    key = ("u", "v")
    params = PlannerParams(
        alpha=0.4, lambda_usd_per_s=0.08,
        posteriors={key: BetaPosterior.from_prior_mean(0.9)},
    )
    pred = {key: TemplatePredictor(
        template=lambda i, p=None: "chunked-output-string-for-u")}
    lowered = lower_workflow(
        wf, params, predictors=pred,
        stream_refiners={key: lambda i, p: (None, 0.0)},
    )
    vi = lowered.names.index("v")
    success = np.ones((E, lowered.n_ops), bool)
    cP = np.ones((E, lowered.n_ops, K))
    cP[:, vi, :] = chunk_P
    return lowered, success, cP


@pytest.mark.parametrize("n_segments", SEGMENTS)
def test_sharded_streaming_cancel_parity(n_segments):
    """§9.1 mid-stream cancellation (chunk_P + stream refiner): chunk
    verdicts, fractional waste and makespans survive episode sharding
    bitwise — including when a cancel lands in a ragged last chunk."""
    with enable_x64():
        lowered, success, cP = _make_stream_case()
        base = fleet_replay(lowered, success, [0.4], [0.08], chunk_P=cP)
        assert base.cancelled.any() and not base.cancelled.all(), \
            "test vector should mix cancelled and surviving streams"
        sharded = episode_sharded_replay(
            lowered, success, [0.4], [0.08], chunk_P=cP,
            n_segments=n_segments)
        _assert_sharded_parity(base, sharded)


def test_sharded_respects_caller_ep_mask():
    """A caller-masked (identity) episode in the middle of the log stays
    an identity step in whichever segment it lands in."""
    with enable_x64():
        lowered, success, pred_ok = _lower_dag(
            make_random_dag(2, episodes=9))
        mask = np.ones(9, bool)
        mask[[2, 5, 6]] = False
        base = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS,
                            pred_ok=pred_ok, ep_mask=mask)
        sharded = episode_sharded_replay(
            lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
            ep_mask=mask, n_segments=3)
        _assert_sharded_parity(base, sharded)


def test_more_segments_than_episodes():
    """C > E leaves trailing all-masked segments — pure identity scans
    that must not perturb the stats or the final carry."""
    with enable_x64():
        lowered, success, pred_ok = _lower_dag(
            make_random_dag(4, episodes=3))
        base = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS,
                            pred_ok=pred_ok)
        sharded = episode_sharded_replay(
            lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
            n_segments=7)
        _assert_sharded_parity(base, sharded)


def test_associative_composition_matches_sequential_handoff():
    """discount=1 closed form: one ``lax.associative_scan`` over the
    per-segment (Δs, Δf) sufficient statistics rebuilds every
    segment-boundary posterior the sequential handoff produced (1-ULP:
    ``prior + Σcounts`` rounds once where the in-scan carry rounds per
    episode)."""
    with enable_x64():
        lowered, success, pred_ok = _lower_dag(
            make_random_dag(0, episodes=12))
        C = 4
        report, starts = episode_sharded_replay(
            lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
            n_segments=C, return_boundaries=True)
        chunks = chunk_episodes(lowered, success, C, pred_ok=pred_ok)
        S, E = chunks.seg_len, chunks.n_episodes
        pad = C * S - E

        def segs(x):
            if pad:
                x = np.concatenate(
                    [x, np.zeros((pad,) + x.shape[1:], x.dtype)])
            return x.reshape((C, S) + x.shape[1:])

        launched = report.edge_launched.astype(bool)
        committed = report.edge_committed.astype(bool)
        ds = segs((launched & committed).astype(float)).sum(1)
        df = segs((launched & ~committed).astype(float)).sum(1)
        composed = compose_segment_posteriors(lowered.a0, lowered.b0, ds, df)
        assert composed.shape == starts.shape == (
            C, len(GRID_ALPHAS), lowered.n_ops, 2)
        np.testing.assert_allclose(composed, starts, **ULP)


def test_chunk_episodes_rejects_empty_log_and_bad_segments():
    """Regression (satellite): an E=0 log used to be representable as an
    all-identity segment that replays to zero stats; it is now rejected
    loudly, as are non-positive segment counts."""
    lowered, success, pred_ok = _lower_dag(make_random_dag(0, episodes=4))
    with pytest.raises(ValueError, match="at least one episode"):
        chunk_episodes(lowered, success[:0], 2, pred_ok=pred_ok[:0])
    with pytest.raises(ValueError, match="n_segments"):
        chunk_episodes(lowered, success, 0, pred_ok=pred_ok)
    with pytest.raises(ValueError, match="success"):
        chunk_episodes(lowered, success[:, :1], 2)
    # ragged split: ceil sizing, padded tail masked off
    ch = chunk_episodes(lowered, success, 3, pred_ok=pred_ok)
    assert (ch.n_segments, ch.seg_len, ch.n_episodes) == (3, 2, 4)
    assert ch.ep_mask.sum() == 4 and not ch.ep_mask[-1, -1]


class TestPipelinedReplay:
    """``pipelined=True``: the host-loop overlap (segment c's stats
    dispatched the moment its boundary carry exists, the carry advanced
    immediately after) must not change a single bit relative to the
    two-pass engine or the unsharded scan — same per-segment scan
    bodies, same sequential handoff semantics, only the dispatch order
    differs."""

    @pytest.mark.parametrize("n_segments", SEGMENTS)
    @pytest.mark.parametrize("seed", range(2))
    def test_random_dag_bitwise(self, seed, n_segments):
        with enable_x64():
            lowered, success, pred_ok = _lower_dag(
                make_random_dag(seed, episodes=10))
            base = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS,
                                pred_ok=pred_ok)
            piped = episode_sharded_replay(
                lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
                n_segments=n_segments, pipelined=True)
            _assert_sharded_parity(base, piped)

    @pytest.mark.parametrize("n_segments", SEGMENTS)
    def test_discounted(self, n_segments):
        """discount<1: the forgetting carry hands off exactly — the
        regime with no associative fallback, so the pipelined handoff
        must be the same sequential recurrence."""
        with enable_x64():
            lowered, success, pred_ok = _lower_dag(
                make_random_dag(100, episodes=10, discount=0.9))
            assert np.any(lowered.discount[lowered.has_edge] < 1.0)
            base = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS,
                                pred_ok=pred_ok)
            piped = episode_sharded_replay(
                lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
                n_segments=n_segments, pipelined=True)
            _assert_sharded_parity(base, piped)

    @pytest.mark.parametrize("n_segments", SEGMENTS)
    def test_lower_bound(self, n_segments):
        """§7.5 credible-bound gating through the pipelined path: same
        EV convention as the two-pass engine (1 ULP for the betaincinv
        fusion), everything else bitwise."""
        with enable_x64():
            lowered, success, pred_ok = _lower_dag(
                make_random_dag(1, episodes=10, use_lower_bound=True))
            assert lowered.use_lower_bound
            base = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS,
                                pred_ok=pred_ok)
            piped = episode_sharded_replay(
                lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
                n_segments=n_segments, pipelined=True)
            _assert_sharded_parity(base, piped, ev_ulp=True)

    @pytest.mark.parametrize("n_segments", SEGMENTS)
    def test_streaming_cancel(self, n_segments):
        with enable_x64():
            lowered, success, cP = _make_stream_case()
            base = fleet_replay(lowered, success, [0.4], [0.08],
                                chunk_P=cP)
            piped = episode_sharded_replay(
                lowered, success, [0.4], [0.08], chunk_P=cP,
                n_segments=n_segments, pipelined=True)
            _assert_sharded_parity(base, piped)

    def test_boundaries_and_stats_match_two_pass(self):
        """Direct pipelined-vs-two-pass check: identical segment-start
        carries (return_boundaries) and identical stat blocks."""
        with enable_x64():
            lowered, success, pred_ok = _lower_dag(
                make_random_dag(5, episodes=12))
            two_pass, b2 = episode_sharded_replay(
                lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
                n_segments=4, return_boundaries=True)
            piped, bp = episode_sharded_replay(
                lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
                n_segments=4, pipelined=True, return_boundaries=True)
            np.testing.assert_array_equal(b2, bp)
            _assert_sharded_parity(two_pass, piped)


def test_sharded_pareto_matches_unsharded():
    """The §12.3 Pareto consumer contract survives sharding (means over
    real episodes only)."""
    with enable_x64():
        lowered, success, pred_ok = _lower_dag(
            make_random_dag(3, episodes=10))
        base = fleet_replay(lowered, success, GRID_ALPHAS, GRID_LAMS,
                            pred_ok=pred_ok)
        sharded = episode_sharded_replay(
            lowered, success, GRID_ALPHAS, GRID_LAMS, pred_ok=pred_ok,
            n_segments=3)
        pb, ps = base.pareto(), sharded.pareto()
        for k in ("latency_s", "cost_usd", "waste_usd", "launched",
                  "committed"):
            np.testing.assert_array_equal(pb[k], ps[k], err_msg=f"pareto {k}")
