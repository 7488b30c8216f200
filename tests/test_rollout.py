"""Staged-rollout lifecycle: unit, property and integration coverage.

The mini-hypothesis sweep asserts the issue's four properties directly
against the traced machine:

  (a) any kill-switch breach on an open row demotes within one tick,
  (b) re-entry (and serving) is impossible before the cooldown expires,
  (c) promotion is monotone in the observed success rate,
  (d) phase state survives paged spill/fault-in bitwise.
"""
from __future__ import annotations

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.online import OnlineDecisionService
from repro.core.posterior import BetaPosterior
from repro.core.rollout import (CANARY, DISABLED, FULL, ONLINE_CAL, SHADOW,
                                ReferenceLifecycle, RolloutConfig,
                                RolloutController, decode_transition,
                                rollout_advance, rollout_allow)
from repro.core.store import ROLL_COLS, PosteriorStore
from repro.core.telemetry import RESILIENCE_KINDS, ResilienceLog
from repro.serving.faults import DriftTrace, FaultInjector, FaultPlan

D4 = dict(alpha=0.5, lambda_usd_per_s=0.9, latency_s=3.0,
          input_tokens=500, output_tokens=300,
          input_price=3e-6, output_price=15e-6)


def _service(n_rows=1, consecutive_n=3, discount=0.9):
    svc = OnlineDecisionService(credible_consecutive_n=consecutive_n)
    for r in range(n_rows):
        svc.register_edge((f"a{r}", f"b{r}"), tenant=f"t{r % 2}",
                          posterior=BetaPosterior(alpha=16.0, beta=2.0),
                          discount=discount, floor_alpha=0.3,
                          floor_C_spec_usd=1.0, floor_L_value_usd=1.0)
    return svc


def _advance(roll, cfg, *, triggered=None, touched=None, n_out=0, s_out=0):
    """One rollout_advance step over a single-row table (numpy in/out)."""
    n = roll.shape[0]
    flags = np.stack([np.ones(n, np.int32), np.zeros(n, np.int32)], 1)
    trig = np.zeros(n, bool) if triggered is None else np.asarray(triggered)
    tch = np.ones(n, bool) if touched is None else np.asarray(touched)
    r1, f1, tr = rollout_advance(
        roll.astype(np.int32), flags, trig, tch,
        np.full(n, n_out, np.int32), np.full(n, s_out, np.int32),
        cfg.encode())
    return np.asarray(r1), np.asarray(f1), np.asarray(tr)


# ---------------------------------------------------------------------------
# config + encoding
# ---------------------------------------------------------------------------
def test_config_validates_and_encodes():
    cfg = RolloutConfig(cooldown_ticks=5, probe_budget=3, canary_period=4,
                        min_obs=(2, 3, 4), promote_rate=(0.5, 0.6, 0.7))
    assert cfg.encode().tolist() == [5, 3, 4, 2, 3, 4, 500, 600, 700]
    assert cfg.encode().dtype == np.int32
    for bad in (dict(cooldown_ticks=0), dict(probe_budget=0),
                dict(canary_period=0), dict(min_obs=(0, 1, 1)),
                dict(promote_rate=(0.5, 0.5, 1.5)),
                dict(min_obs=(1, 1))):
        with pytest.raises(ValueError):
            RolloutConfig(**bad)


def test_transition_codes_round_trip():
    for code, kind in [(1, "rollout_promote"), (2, "rollout_demote"),
                       (3, "rollout_reenter"), (4, "rollout_probe_fail")]:
        packed = code * 64 + SHADOW * 8 + CANARY
        k, old, new = decode_transition(packed)
        assert (k, old, new) == (kind, SHADOW, CANARY)
        assert kind in RESILIENCE_KINDS
    with pytest.raises(ValueError):
        decode_transition(0)


def test_serve_mask_per_phase():
    cfg = RolloutConfig(canary_period=3)
    # [phase, cd, pb, tip, n, s]
    roll = np.array([
        [DISABLED, 0, 0, 0, 0, 0],
        [SHADOW, 0, 0, 0, 0, 0],
        [CANARY, 0, 0, 0, 0, 0],      # tip 0 -> period tick, serves
        [CANARY, 0, 0, 1, 0, 0],      # off-period tick
        [ONLINE_CAL, 0, 0, 5, 0, 0],
        [FULL, 0, 0, 9, 0, 0],
        [FULL, 2, 0, 0, 0, 0],        # cooling down: never serves
    ], np.int32)
    allow = np.asarray(rollout_allow(roll, cfg.encode()))
    assert allow.tolist() == [False, False, True, False, True, True, False]


# ---------------------------------------------------------------------------
# property (a): any breach on an open row demotes within one tick
# ---------------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(phase=st.integers(min_value=SHADOW, max_value=FULL),
       pb=st.integers(min_value=0, max_value=8),
       tip=st.integers(min_value=0, max_value=40),
       n=st.integers(min_value=0, max_value=50),
       s=st.integers(min_value=0, max_value=50))
def test_breach_demotes_within_one_tick(phase, pb, tip, n, s):
    cfg = RolloutConfig(cooldown_ticks=4, probe_budget=4)
    roll = np.array([[phase, 0, pb, tip, n, min(s, n)]], np.int32)
    r1, _, tr = _advance(roll, cfg, triggered=[True])
    kind, old, new = decode_transition(int(tr[0]))
    assert kind == "rollout_demote" and old == phase and new == SHADOW
    assert r1[0, 0] == SHADOW
    assert r1[0, 1] == cfg.cooldown_ticks          # cooldown restarted
    assert r1[0, 4] == r1[0, 5] == 0               # evidence reset
    assert not np.asarray(rollout_allow(r1, cfg.encode()))[0]


def test_breach_mid_cooldown_is_absorbed():
    """An OPEN circuit doesn't re-open: triggers while cooling down are
    swallowed (no event, cooldown keeps counting)."""
    cfg = RolloutConfig(cooldown_ticks=5)
    roll = np.array([[SHADOW, 4, 0, 0, 0, 0]], np.int32)
    r1, _, tr = _advance(roll, cfg, triggered=[True])
    assert tr[0] == 0
    assert r1[0, 1] == 3


def test_breach_on_expiry_tick_demotes_not_reenters():
    """A trigger landing exactly when the cooldown hits zero restarts the
    cooldown (demote) instead of re-entering — no re-enable deadlock."""
    cfg = RolloutConfig(cooldown_ticks=5)
    roll = np.array([[SHADOW, 1, 0, 0, 0, 0]], np.int32)
    r1, _, tr = _advance(roll, cfg, triggered=[True])
    assert decode_transition(int(tr[0]))[0] == "rollout_demote"
    assert r1[0, 1] == cfg.cooldown_ticks


# ---------------------------------------------------------------------------
# property (b): no re-entry (or serving) before the cooldown expires
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(cooldown=st.integers(min_value=2, max_value=10),
       outcomes=st.integers(min_value=0, max_value=5))
def test_no_reentry_before_cooldown_expires(cooldown, outcomes):
    # promotion bar out of reach, so the expiry transition is isolated
    cfg = RolloutConfig(cooldown_ticks=cooldown, probe_budget=4,
                        min_obs=(1000, 1000, 1000))
    roll = np.array([[SHADOW, 0, 0, 0, 0, 0]], np.int32)
    r1, _, tr = _advance(roll, cfg, triggered=[True])     # demote now
    for k in range(cooldown - 1):
        assert not np.asarray(rollout_allow(r1, cfg.encode()))[0]
        r1, _, tr = _advance(r1, cfg, n_out=outcomes, s_out=outcomes)
        assert tr[0] == 0, f"transition escaped cooldown at step {k}"
        # evidence gathered during cooldown must not count
        assert r1[0, 4] == r1[0, 5] == 0
    # the cooldown-expiry tick re-enters with the full probe budget
    r1, f1, tr = _advance(r1, cfg, n_out=outcomes, s_out=outcomes)
    kind, old, new = decode_transition(int(tr[0]))
    assert kind == "rollout_reenter" and old == new == SHADOW
    assert r1[0, 2] == cfg.probe_budget
    assert f1[0, 0] == 1                                  # re-enabled


# ---------------------------------------------------------------------------
# property (c): promotion monotone in observed success rate
# ---------------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(phase=st.integers(min_value=SHADOW, max_value=ONLINE_CAL),
       n=st.integers(min_value=1, max_value=200),
       s=st.integers(min_value=0, max_value=200),
       rate=st.floats(min_value=0.0, max_value=1.0),
       min_obs=st.integers(min_value=1, max_value=50))
def test_promotion_monotone_in_success(phase, n, s, rate, min_obs):
    s = min(s, n)
    cfg = RolloutConfig(min_obs=(min_obs,) * 3, promote_rate=(rate,) * 3,
                        probe_budget=1000)
    def promoted(s_obs):
        roll = np.array([[phase, 0, 1000, 0, n, s_obs]], np.int32)
        _, _, tr = _advance(roll, cfg)
        return tr[0] > 0 and decode_transition(int(tr[0]))[0] == "rollout_promote"
    if promoted(s):
        # more observed successes can never un-promote
        for s_hi in {min(s + 1, n), n}:
            assert promoted(s_hi)
    else:
        for s_lo in {max(s - 1, 0), 0}:
            assert not promoted(s_lo)


# ---------------------------------------------------------------------------
# property (d): phase state survives paged spill/fault-in bitwise
# ---------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_roll_state_survives_spill_fault_in_bitwise(seed):
    from repro.core.taxonomy import DependencyType

    rng = np.random.default_rng(seed)
    store = PosteriorStore(resident_rows=4, min_rows=4)
    for i in range(8):
        store.register(("u", f"v{i}"),
                       dep_type=DependencyType.ALWAYS_PRODUCES_OUTPUT)
    store.device_tables("float32")
    want = rng.integers(0, 1000, size=(8, ROLL_COLS)).astype(np.int32)
    want[:, 0] = rng.integers(DISABLED, FULL + 1, size=8)
    # write in two paged halves, spilling each across the other
    store.set_roll_rows(np.arange(4), want[:4])
    store.ensure_resident(np.arange(4, 8))       # spills 0-3 to the shelf
    store.set_roll_rows(np.arange(4, 8), want[4:])
    snap = store.roll_snapshot()
    assert np.array_equal(snap, want)
    # churn residency both ways; the composed view must never change
    store.ensure_resident(np.arange(4))
    store.ensure_resident(np.arange(4, 8))
    assert np.array_equal(store.roll_snapshot(), want)
    assert store.roll_snapshot().dtype == np.int32


def test_roll_state_dense_vs_paged_identical_lifecycle():
    """The same tick stream produces bitwise-identical roll columns on an
    identity (dense) store and a paged store half its size."""
    cfg = RolloutConfig(cooldown_ticks=4, probe_budget=4, min_obs=(3, 3, 3))
    trace = DriftTrace.flip(15, rate1=0.02, revert_at=45)

    def run(paged: bool):
        kw = dict(credible_consecutive_n=3)
        if paged:
            kw.update(resident_rows=4, min_rows=4)
        svc = OnlineDecisionService(**kw)
        for r in range(6):
            svc.register_edge((f"a{r}", f"b{r}"), tenant="t0",
                              posterior=BetaPosterior(alpha=16.0, beta=2.0),
                              discount=0.9, floor_alpha=0.3,
                              floor_C_spec_usd=1.0, floor_L_value_usd=1.0)
        ctl = RolloutController(svc, cfg)
        inj = [FaultInjector(FaultPlan(trace=trace, seed=7 + r))
               for r in range(6)]
        sigs = []
        for i in range(70):
            rows = [i % 6, (i + 1) % 6]        # paged working set of 2
            d = ctl.tick(rows, outcomes=[(r, inj[r].outcome())
                                         for r in rows], **D4)
            sigs.append(tuple(int(c) for c in d.rollout_transitions))
        return sigs, np.asarray(svc.store.roll_snapshot())

    dense_sig, dense_roll = run(paged=False)
    paged_sig, paged_roll = run(paged=True)
    assert dense_sig == paged_sig
    assert np.array_equal(dense_roll, paged_roll)


# ---------------------------------------------------------------------------
# controller end-to-end: ladder, parity, billing, host paths
# ---------------------------------------------------------------------------
def test_promotion_ladder_and_reference_parity():
    svc = _service()
    log = ResilienceLog()
    cfg = RolloutConfig(cooldown_ticks=6, probe_budget=4, min_obs=(3, 3, 3))
    ctl = RolloutController(svc, cfg, resilience=log)
    ref = ReferenceLifecycle(1, cfg)
    inj = FaultInjector(FaultPlan(
        trace=DriftTrace.flip(20, rate1=0.02, revert_at=55), seed=7))
    for _ in range(140):
        ok = inj.outcome()
        d = ctl.tick([0], outcomes=[(0, ok)], **D4)
        ref_out = ref.tick([0], {0: (1, 1 if ok else 0)},
                           np.flatnonzero(d.drift_triggered))
        dev = {int(r): int(c)
               for r, c in enumerate(d.rollout_transitions) if c}
        assert dev == ref_out
        assert np.array_equal(np.asarray(svc.store.roll_snapshot()[0]),
                              np.asarray(ref.rows[0], np.int32))
    assert ctl.phases() == ["FULL"]
    kinds = log.by_kind()
    assert kinds["rollout_demote"] >= 1
    assert kinds["rollout_reenter"] >= 1
    assert kinds["rollout_promote"] >= 6       # initial ladder + recovery
    # demotions are billed the tick's forfeited L_value
    usd = log.usd_attribution()
    assert usd[("t0", "rollout_demote")] > 0.0
    assert usd[("t0", "rollout_promote")] == 0.0
    # transition events also landed in the device ring
    events = svc.drain_telemetry().events
    assert any(e["kind"] == "rollout_demote" for e in events)


def test_shadow_decides_but_never_serves():
    svc = _service()
    ctl = RolloutController(svc, RolloutConfig(min_obs=(1000, 1000, 1000)))
    for _ in range(10):
        d = ctl.tick([0], outcomes=[(0, True)], **D4)
        assert ctl.phases() == ["SHADOW"]
        assert bool(d.flag[0])                 # D4 itself says speculate
        assert not bool(d.speculate[0])        # ...but SHADOW answers WAIT
    # the posterior still learned from the settled outcomes: ten discounted
    # successes push the mean above the Beta(16, 2) prior's 16/18
    a, b = (float(v) for v in svc.posterior_snapshot()[0])
    assert a / (a + b) > 0.92


def test_canary_serves_only_period_ticks():
    svc = _service()
    cfg = RolloutConfig(canary_period=3, min_obs=(2, 1000, 1000),
                        promote_rate=(0.1, 0.9, 0.9), probe_budget=1000)
    ctl = RolloutController(svc, cfg)
    served = []
    for i in range(14):
        pre = ctl.phases()          # decisions gate on the PRE-tick phase
        d = ctl.tick([0], outcomes=[(0, True)], **D4)
        if pre == ["CANARY"]:
            served.append(bool(d.speculate[0]))
    # tip resets to 0 on promotion: the pattern is serve, skip, skip, ...
    assert served == [True, False, False] * (len(served) // 3) + \
        [True, False, False][: len(served) % 3]


def test_tier2_demote_and_revive():
    svc = _service()
    log = ResilienceLog()
    ctl = RolloutController(svc, RolloutConfig(min_obs=(1, 1, 1),
                                               promote_rate=(0.0,) * 3,
                                               probe_budget=64),
                            resilience=log)
    for _ in range(4):
        ctl.tick([0], outcomes=[(0, True)], **D4)
    assert ctl.phases() == ["FULL"]
    ctl.demote_tier2(0, usd=12.5)
    assert ctl.phases() == ["DISABLED"]
    assert log.usd_attribution()[("t0", "rollout_demote")] == 12.5
    # DISABLED never serves and never exits in-graph, even under healthy
    # traffic with the cooldown elapsed
    for _ in range(20):
        d = ctl.tick([0], outcomes=[(0, True)], **D4)
        assert not bool(d.speculate[0])
    assert ctl.phases() == ["DISABLED"]
    ctl.revive(0)
    assert ctl.phases() == ["SHADOW"]
    for _ in range(4):
        ctl.tick([0], outcomes=[(0, True)], **D4)
    assert ctl.phases() == ["FULL"]


def test_config_change_is_operand_not_recompile():
    from repro.core import online as online_mod

    svc = _service(n_rows=2)
    ctl = RolloutController(svc, RolloutConfig())
    for _ in range(3):
        ctl.tick([0, 1], outcomes=[(0, True), (1, True)], **D4)
    warm = online_mod._tick._cache_size()
    ctl2 = RolloutController(svc, RolloutConfig(cooldown_ticks=2,
                                                probe_budget=2,
                                                min_obs=(1, 1, 1)))
    for _ in range(3):
        ctl2.tick([0, 1], outcomes=[(0, True), (1, True)], **D4)
    assert online_mod._tick._cache_size() == warm
