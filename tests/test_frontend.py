"""Serving front-end unit tests: circuit breaker, bulkhead, batching,
fallback chain, ring-encoded resilience events — plus the spec_bridge
regressions (worker exceptions, upstream-failure cleanup, timeouts,
retry/backoff) and the online service's idle-tick fast path."""
import threading
import time

import numpy as np
import pytest

from repro.core.decision import Decision
from repro.core.online import OnlineDecisionService, TELEMETRY_FIELDS
from repro.core.posterior import BetaPosterior
from repro.core.telemetry import (
    RESILIENCE_KINDS,
    ResilienceEvent,
    ResilienceLog,
)
from repro.serving.engine import GenerationResult
from repro.serving.frontend import (
    BreakerState,
    CircuitBreaker,
    DecisionRequest,
    FrontendConfig,
    ServingFrontend,
    TenantBulkhead,
)
from repro.serving.spec_bridge import (
    SpeculationTimeout,
    ThreadedSpeculativeRunner,
    call_with_timeout,
    retry_with_backoff,
)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _service(n_edges=2, tenant="t0", **kw):
    svc = OnlineDecisionService(**kw)
    for e in range(n_edges):
        svc.register_edge(
            (f"u{e}", f"v{e}"), tenant=tenant,
            posterior=BetaPosterior(alpha=16.0, beta=2.0))
    return svc


def _req(row=0, tenant="t0", edge=("u0", "v0"), **kw):
    base = dict(alpha=0.5, lambda_usd_per_s=0.9, latency_s=3.0,
                input_tokens=500.0, output_tokens=300.0,
                input_price=3e-6, output_price=15e-6)
    base.update(kw)
    return DecisionRequest(row=row, tenant=tenant, edge=edge, **base)


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------
class TestCircuitBreaker:
    def test_opens_after_consecutive_failures(self):
        clk = FakeClock()
        br = CircuitBreaker(failure_threshold=3, cooldown_s=1.0, clock=clk)
        for _ in range(2):
            br.record_failure("k")
        assert br.state("k") is BreakerState.CLOSED and br.allow("k")
        br.record_failure("k")
        assert br.state("k") is BreakerState.OPEN
        assert not br.allow("k")

    def test_success_resets_failure_run(self):
        br = CircuitBreaker(failure_threshold=2, clock=FakeClock())
        br.record_failure("k")
        br.record_success("k")
        br.record_failure("k")
        assert br.state("k") is BreakerState.CLOSED

    def test_half_open_probe_budget_and_close(self):
        clk = FakeClock()
        br = CircuitBreaker(failure_threshold=1, cooldown_s=1.0,
                            half_open_probes=1, clock=clk)
        br.record_failure("k")
        assert not br.allow("k")              # open, inside cooldown
        clk.t = 1.5
        assert br.allow("k")                  # cooldown elapsed -> probe
        assert br.state("k") is BreakerState.HALF_OPEN
        assert not br.allow("k")              # probe budget exhausted
        br.record_success("k")
        assert br.state("k") is BreakerState.CLOSED
        assert br.allow("k")

    def test_half_open_probe_failure_reopens(self):
        clk = FakeClock()
        br = CircuitBreaker(failure_threshold=1, cooldown_s=1.0, clock=clk)
        br.record_failure("k")
        clk.t = 1.5
        assert br.allow("k")
        br.record_failure("k")
        assert br.state("k") is BreakerState.OPEN
        clk.t = 2.0                           # cooldown restarted at 1.5
        assert not br.allow("k")
        clk.t = 2.6
        assert br.allow("k")

    def test_trip_opens_immediately_and_keys_isolated(self):
        clk = FakeClock()
        br = CircuitBreaker(failure_threshold=5, cooldown_s=1.0, clock=clk)
        br.trip("a")
        assert br.state("a") is BreakerState.OPEN and br.trips == 1
        assert br.allow("b")                  # other keys unaffected

    def test_transition_callback_sequence(self):
        clk = FakeClock()
        seen = []
        br = CircuitBreaker(failure_threshold=1, cooldown_s=1.0, clock=clk,
                            on_transition=lambda k, s: seen.append(s))
        br.record_failure("k")
        clk.t = 1.5
        br.allow("k")
        br.record_success("k")
        assert seen == [BreakerState.OPEN, BreakerState.HALF_OPEN,
                        BreakerState.CLOSED]

    def test_validation(self):
        with pytest.raises(ValueError):
            CircuitBreaker(failure_threshold=0)
        with pytest.raises(ValueError):
            CircuitBreaker(half_open_probes=0)


class TestTenantBulkhead:
    def test_limit_and_release(self):
        bh = TenantBulkhead(2)
        assert bh.try_acquire("a") and bh.try_acquire("a")
        assert not bh.try_acquire("a")        # at limit
        assert bh.try_acquire("b")            # independent tenant
        bh.release("a")
        assert bh.try_acquire("a")
        assert bh.in_flight("a") == 2

    def test_release_without_acquire_raises(self):
        bh = TenantBulkhead(1)
        with pytest.raises(RuntimeError):
            bh.release("a")

    def test_limit_validated(self):
        with pytest.raises(ValueError):
            TenantBulkhead(0)


# ---------------------------------------------------------------------------
# the frontend chain
# ---------------------------------------------------------------------------
class TestFrontendChain:
    def test_pump_batches_and_answers_from_service(self):
        fe = ServingFrontend(_service(), FrontendConfig(max_batch=4),
                             autostart=False)
        tks = [fe.submit(_req()) for _ in range(3)]
        assert all(not t.done() for t in tks)     # accumulating
        assert fe.pump() == 3
        for t in tks:
            res = t.result(0)
            assert res.source == "service"
            if res.speculate:
                t.settle(True)
        assert fe.stats["deadline_ticks"] == 1    # partial batch

    def test_batch_full_pump_consumes_max_batch(self):
        fe = ServingFrontend(_service(), FrontendConfig(max_batch=2),
                             autostart=False)
        tks = [fe.submit(_req()) for _ in range(3)]
        assert fe.pump() == 2 and fe.stats["full_ticks"] == 1
        assert tks[0].done() and not tks[2].done()
        fe.pump()
        for t in tks:
            if t.result(0).speculate:
                t.settle(True)

    def test_bulkhead_shed_answers_conservative_with_usd_event(self):
        fe = ServingFrontend(_service(), FrontendConfig(bulkhead_limit=1),
                             autostart=False)
        t1, t2 = fe.submit(_req()), fe.submit(_req())
        res = t2.result(0)                        # shed synchronously
        assert res.source == "shed" and res.decision is Decision.WAIT
        ev = fe.resilience.events[-1]
        assert ev.kind == "shed" and ev.tenant == "t0"
        assert ev.usd == pytest.approx(3.0 * 0.9)  # L * lambda at stake
        fe.pump()
        if t1.result(0).speculate:
            t1.settle(True)

    def test_queue_limit_sheds(self):
        fe = ServingFrontend(
            _service(), FrontendConfig(max_queue=2, max_batch=64,
                                       bulkhead_limit=64),
            autostart=False)
        tks = [fe.submit(_req()) for _ in range(4)]
        sources = [t.result(0).source if t.done() else None for t in tks]
        assert sources[2:] == ["shed", "shed"]
        assert fe.stats["shed"] == 2

    def test_breaker_open_degrades_to_scalar_bitwise(self):
        from jax import enable_x64

        from repro.core.decision import DecisionInputs, evaluate

        with enable_x64():
            svc = _service()
            fe = ServingFrontend(svc, FrontendConfig(), autostart=False)
            snap = svc.posterior_snapshot()
            r = _req()
            fe.breaker.trip(r.key)
            tk = fe.submit(r)
            res = tk.result(0)                    # answered synchronously
            assert res.source == "scalar"
            post = BetaPosterior(alpha=float(snap[0, 0]),
                                 beta=float(snap[0, 1]))
            ref = evaluate(DecisionInputs(
                P=post.mean, alpha=r.alpha,
                lambda_usd_per_s=r.lambda_usd_per_s,
                latency_seconds=r.latency_s, input_tokens=r.input_tokens,
                output_tokens=r.output_tokens, input_price=r.input_price,
                output_price=r.output_price))
            assert res.decision is ref.decision
            assert res.EV_usd == ref.EV_usd
            assert res.threshold_usd == ref.threshold_usd
            assert res.P_used == ref.P_used
            if res.speculate:
                tk.release()
        kinds = fe.resilience.by_kind()
        assert kinds.get("fallback_scalar") == 1

    def test_terminal_conservative_stage(self):
        # an out-of-range alpha makes the scalar stage raise, so the
        # chain's terminal stage answers WAIT — the sequential path is
        # never blocked by a bad request on a degraded edge
        fe = ServingFrontend(_service(), FrontendConfig(), autostart=False)
        bad = _req(alpha=1.5)
        fe.breaker.trip(bad.key)
        res = fe.submit(bad).result(0)
        assert res.source == "conservative"
        assert res.decision is Decision.WAIT
        assert fe.resilience.by_kind().get("fallback_conservative") == 1

    def test_tick_exception_degrades_whole_batch_and_feeds_breaker(self):
        class Exploding:
            def __init__(self, svc):
                self._svc = svc

            def __getattr__(self, name):
                if name == "tick_packed":
                    raise_ = lambda *a, **k: (_ for _ in ()).throw(  # noqa: E731
                        RuntimeError("boom"))
                    return raise_
                return getattr(self._svc, name)

        fe = ServingFrontend(
            Exploding(_service()),
            FrontendConfig(max_batch=4, breaker_failure_threshold=1),
            autostart=False)
        tks = [fe.submit(_req()) for _ in range(2)]
        fe.pump()
        for t in tks:
            res = t.result(0)
            assert res.source == "scalar"
            if res.speculate:
                t.release()
        assert fe.stats["tick_faults"] == 1
        assert fe.breaker.state(("t0", ("u0", "v0"))) is BreakerState.OPEN
        kinds = fe.resilience.by_kind()
        assert kinds["exception"] == 2 and kinds["breaker_open"] == 1

    def test_settle_feeds_service_posterior(self):
        svc = _service()
        fe = ServingFrontend(svc, FrontendConfig(), autostart=False)
        before = svc.posterior_snapshot()[0].copy()
        tk = fe.submit(_req())
        fe.pump()
        assert tk.result(0).speculate
        tk.settle(False)
        assert fe.in_flight("t0") == 0            # slot released
        fe.submit(_req())
        fe.pump()                                 # settle applies pre-tick
        after = svc.posterior_snapshot()[0]
        assert after[1] == pytest.approx(before[1] + 1.0)  # one failure

    def test_settle_twice_raises(self):
        fe = ServingFrontend(_service(), FrontendConfig(), autostart=False)
        tk = fe.submit(_req())
        fe.pump()
        if tk.result(0).speculate:
            tk.settle(True)
            with pytest.raises(RuntimeError):
                tk.settle(True)

    def test_events_mirrored_to_device_ring(self):
        svc = _service()
        fe = ServingFrontend(svc, FrontendConfig(bulkhead_limit=1),
                             autostart=False)
        fe.submit(_req())
        fe.submit(_req())                         # shed -> ring event
        fe.pump()
        tb = svc.drain_telemetry()
        assert any(e["kind"] == "shed" and e["row"] == 0 for e in tb.events)
        # decision rows in the same window keep the full field schema
        assert set(tb.fields) == set(TELEMETRY_FIELDS)


# ---------------------------------------------------------------------------
# resilience event log + ring encoding
# ---------------------------------------------------------------------------
class TestDriftTripDedup:
    """A kill-switch breach trips the breaker once per breach *onset*:
    repeated triggered pulses while the row is down are swallowed, but a
    second breach after an observed recovery re-emits a fresh trip."""

    def _stack(self):
        from repro.core.rollout import RolloutConfig, RolloutController
        svc = OnlineDecisionService(credible_consecutive_n=2)
        svc.register_edge(
            ("u0", "v0"), tenant="t0",
            posterior=BetaPosterior(alpha=16.0, beta=2.0), discount=0.85,
            floor_alpha=0.3, floor_C_spec_usd=1.0, floor_L_value_usd=1.0)
        ctl = RolloutController(
            svc, RolloutConfig(cooldown_ticks=3, probe_budget=8,
                               canary_period=1, min_obs=(2, 2, 2),
                               promote_rate=(0.5, 0.5, 0.5)))
        clk = FakeClock()
        fe = ServingFrontend(
            ctl, FrontendConfig(max_batch=2, check_drift=True,
                                breaker_cooldown_s=0.2),
            clock=clk, autostart=False)
        return svc, ctl, fe, clk

    @staticmethod
    def _tick(fe, clk, ok):
        clk.t += 0.05
        tk = fe.submit(_req())
        fe.pump()
        tk.result(0)
        tk.settle(ok)

    def test_second_breach_after_recovery_reemits_trip(self):
        svc, ctl, fe, clk = self._stack()

        def trips():
            return sum(e.kind == "drift_trip" for e in fe.resilience.events)

        for _ in range(12):                       # climb to FULL
            self._tick(fe, clk, True)
        assert ctl.phases() == ["FULL"] and trips() == 0
        i = 0
        while trips() == 0 and i < 60:            # breach #1
            self._tick(fe, clk, False)
            i += 1
        assert trips() == 1
        for _ in range(6):                        # still down: no re-trip
            self._tick(fe, clk, False)
        assert trips() == 1
        j = 0
        while ctl.phases() != ["FULL"] and j < 200:   # recover
            self._tick(fe, clk, True)
            j += 1
        assert ctl.phases() == ["FULL"] and trips() == 1
        i = 0
        while trips() == 1 and i < 60:            # breach #2 re-emits
            self._tick(fe, clk, False)
            i += 1
        assert trips() == 2


class TestResilienceTelemetry:
    def test_event_kind_validated(self):
        with pytest.raises(ValueError):
            ResilienceEvent(kind="nonsense")

    def test_usd_attribution_sums_per_tenant_kind(self):
        log = ResilienceLog()
        log.emit(ResilienceEvent(kind="shed", tenant="a", usd=1.5))
        log.emit(ResilienceEvent(kind="shed", tenant="a", usd=0.5))
        log.emit(ResilienceEvent(kind="timeout", tenant="b", usd=2.0))
        att = log.usd_attribution()
        assert att[("a", "shed")] == pytest.approx(2.0)
        assert att[("b", "timeout")] == pytest.approx(2.0)
        assert log.by_kind() == {"shed": 2, "timeout": 1}

    def test_log_events_roundtrip_all_kinds(self):
        svc = _service()
        svc.log_events([(None, k, 0.25 * i)
                        for i, k in enumerate(RESILIENCE_KINDS)])
        svc.log_events([(1, "shed", 9.0)])
        tb = svc.drain_telemetry()
        assert [e["kind"] for e in tb.events[:-1]] == list(RESILIENCE_KINDS)
        assert tb.events[0]["row"] is None
        assert tb.events[-1] == {"kind": "shed", "row": 1, "usd": 9.0}
        assert tb.events_dropped == 0
        assert len(tb) == 0                       # no decision rows emitted

    def test_log_events_bad_row_raises(self):
        svc = _service()
        with pytest.raises(IndexError):
            svc.log_events([(99, "shed", 0.0)])

    def test_event_overflow_counted_dropped(self):
        svc = _service(telemetry_capacity=4)
        # a 6-event burst buckets to 8 slots; the 4-slot ring keeps the
        # newest slots (2 real events + the bucket's padding) and the
        # drain accounts for every evicted real event
        svc.log_events([(None, "shed", float(i)) for i in range(6)])
        tb = svc.drain_telemetry()
        assert len(tb.events) == 2
        assert tb.events_dropped == 4
        assert [e["usd"] for e in tb.events] == [4.0, 5.0]

    def test_decision_rows_and_events_share_window(self):
        svc = _service()
        svc.tick([0], alpha=0.5, lambda_usd_per_s=0.9, latency_s=3.0,
                 input_tokens=500, output_tokens=300, input_price=3e-6,
                 output_price=15e-6)
        svc.log_events([(0, "breaker_open", 0.01)])
        tb = svc.drain_telemetry()
        assert len(tb) == 1 and tb.dropped == 0   # the decision row
        assert [e["kind"] for e in tb.events] == ["breaker_open"]


# ---------------------------------------------------------------------------
# idle-tick fast path (PR 5 perf note)
# ---------------------------------------------------------------------------
class TestIdleTickFastPath:
    def test_idle_tick_skips_dispatch_and_preserves_state(self):
        svc = _service()
        svc.tick([0], alpha=0.5, lambda_usd_per_s=0.9, latency_s=3.0,
                 input_tokens=500, output_tokens=300, input_price=3e-6,
                 output_price=15e-6)
        snap = svc.posterior_snapshot()
        drained = svc.drain_telemetry()
        assert len(drained) == 1
        d = svc.tick_packed(np.zeros(0, np.int32),
                            np.zeros((0, 7), np.float64))
        assert svc.idle_ticks_skipped == 1
        assert d.speculate.shape == (0,)
        assert not d.drift_triggered.any()
        # bitwise: nothing moved, nothing new to drain
        assert np.array_equal(svc.posterior_snapshot(), snap)
        tb = svc.drain_telemetry()
        assert len(tb) == 0 and tb.dropped == 0 and tb.events == []

    def test_idle_sequence_parity_with_dispatching_service(self):
        # a service that sleeps through idle ticks must answer the next
        # real tick bitwise identically to one that never idled
        def run(idle_ticks):
            svc = _service()
            for _ in range(idle_ticks):
                svc.tick_packed(np.zeros(0, np.int32),
                                np.zeros((0, 7), svc.state.post.dtype))
            d = svc.tick([0, 1], alpha=0.5, lambda_usd_per_s=0.9,
                         latency_s=3.0, input_tokens=500, output_tokens=300,
                         input_price=3e-6, output_price=15e-6,
                         outcomes=[(0, True)], check_drift=True)
            return (np.asarray(d.EV_usd).copy(),
                    np.asarray(d.speculate).copy(),
                    svc.posterior_snapshot())

        ev0, sp0, post0 = run(0)
        ev5, sp5, post5 = run(5)
        assert np.array_equal(ev0, ev5)
        assert np.array_equal(sp0, sp5)
        assert np.array_equal(post0, post5)

    def test_pending_outcomes_defeat_fast_path(self):
        svc = _service()
        svc.observe(0, False)
        svc.tick_packed(np.zeros(0, np.int32), np.zeros((0, 7), np.float64))
        assert svc.idle_ticks_skipped == 0        # outcome had to settle
        assert svc.posterior_snapshot()[0, 1] == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# spec_bridge regressions
# ---------------------------------------------------------------------------
class _StubDownstream:
    """EngineOp-shaped double: scripted (exception | timeout | result)
    per call, cancel-aware."""

    name = "stub"
    provider = "paper"
    model = "frontier-default"
    max_new_tokens = 8

    def __init__(self, script):
        self.script = list(script)
        self.calls = 0
        self.saw_cancel = threading.Event()

    def run(self, upstream_output, cancel_event=None):
        self.calls += 1
        step = self.script.pop(0)
        if step == "hang_until_cancelled":
            assert cancel_event is not None
            assert cancel_event.wait(5.0), "speculative thread never cancelled"
            self.saw_cancel.set()
            return [1], GenerationResult(
                tokens=[1], cancelled=True, prompt_len=1,
                wall_time_s=0.01, tokens_generated=1)
        if isinstance(step, BaseException):
            raise step
        return step, GenerationResult(
            tokens=list(step), cancelled=False, prompt_len=1,
            wall_time_s=0.01, tokens_generated=len(step))


class TestSpecBridgeRegressions:
    def test_worker_exception_propagates_not_keyerror(self):
        # pre-fix: the thread died silently and join-time access raised
        # KeyError("out"); the defect must surface as the real exception
        runner = ThreadedSpeculativeRunner(
            lambda: ("match", None), _StubDownstream([RuntimeError("gpu")]))
        with pytest.raises(RuntimeError, match="gpu"):
            runner.run_speculative("match")

    def test_worker_exception_propagates_on_tier_failure_too(self):
        runner = ThreadedSpeculativeRunner(
            lambda: ("actual", None), _StubDownstream([RuntimeError("gpu")]))
        with pytest.raises(RuntimeError, match="gpu"):
            runner.run_speculative("a long and completely different i_hat")

    def test_upstream_failure_cancels_and_joins_speculation(self):
        # pre-fix: the upstream exception propagated while the worker
        # thread kept generating forever with nobody left to cancel it
        stub = _StubDownstream(["hang_until_cancelled"])

        def upstream():
            time.sleep(0.02)                  # let the worker start
            raise ConnectionError("upstream died")

        runner = ThreadedSpeculativeRunner(upstream, stub)
        with pytest.raises(ConnectionError):
            runner.run_speculative("anything")
        assert stub.saw_cancel.is_set()       # cancelled AND joined

    def test_timeout_settles_as_failed_speculation(self):
        svc = _service(n_edges=1)
        stub = _StubDownstream([SpeculationTimeout("deadline"), [7, 8]])
        runner = ThreadedSpeculativeRunner(
            lambda: ("match", None), stub,
            service=svc, edge=("u0", "v0"), tenant="t0")
        res = runner.run_speculative("match")
        assert res.timed_out and not res.committed and res.cancelled
        assert res.waste_usd > 0.0            # full planned output billed
        assert res.downstream_output == [7, 8]  # sequential re-execution
        assert stub.calls == 2
        # the failure observation reached the service's settle queue
        assert svc._pending == [(0, False)]

    def test_timeout_on_tier_failure_bills_plan(self):
        stub = _StubDownstream([SpeculationTimeout("deadline"), [9]])
        runner = ThreadedSpeculativeRunner(
            lambda: ("actual", None), stub)
        res = runner.run_speculative("a long and completely different i_hat")
        assert res.timed_out and res.cancelled and not res.committed
        assert res.waste_usd > 0.0

    def test_call_with_timeout(self):
        assert call_with_timeout(lambda: 42, 1.0) == 42
        with pytest.raises(SpeculationTimeout):
            call_with_timeout(lambda: time.sleep(0.5), 0.02)
        with pytest.raises(ZeroDivisionError):
            call_with_timeout(lambda: 1 / 0, 1.0)

    def test_retry_with_backoff_counts_and_sleeps(self):
        calls, sleeps = [], []
        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"
        assert retry_with_backoff(flaky, retries=3, backoff_s=0.1,
                                  sleep=sleeps.append) == "ok"
        assert len(calls) == 3
        assert sleeps == [pytest.approx(0.1), pytest.approx(0.2)]

    def test_retry_exhaustion_propagates_final_error(self):
        sleeps = []
        def always():
            raise OSError("down")
        with pytest.raises(OSError):
            retry_with_backoff(always, retries=2, backoff_s=0.01,
                               sleep=sleeps.append)
        assert len(sleeps) == 2               # no sleep after last attempt
        with pytest.raises(ValueError):
            retry_with_backoff(always, retries=-1)
