"""Fault tolerance: atomic checkpoints, kill+restart resume (bitwise), data
pipeline determinism, straggler monitor, grad compression, elastic reshard —
plus the serving front-end's fault matrix (breaker trip/recovery, bulkhead
shed under tenant flood, deadline batching, fallback-chain parity)."""
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # kill/restart + compression loops

from repro.checkpoint.io import (
    AsyncSaver,
    available_steps,
    latest_step,
    load_pytree,
    save_pytree,
)
from repro.configs import REGISTRY
from repro.training.data import DataConfig, SyntheticLMDataset
from repro.training.grad_compress import GradCompressor, dequantize_int8, quantize_int8
from repro.training.optimizer import OptimizerConfig
from repro.training.trainer import Trainer, TrainerConfig


def tiny_trainer(tmp_path, steps=12, ckpt_every=4, **kw) -> Trainer:
    cfg = REGISTRY["llama3.2-1b"].reduced()
    tcfg = TrainerConfig(
        steps=steps, checkpoint_every=ckpt_every,
        checkpoint_dir=str(tmp_path / "ckpt"),
        optimizer=OptimizerConfig(kind="adamw", peak_lr=1e-3, warmup_steps=2,
                                  total_steps=steps),
        data=DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=4),
        **kw,
    )
    return Trainer(cfg, tcfg)


class TestCheckpointIO:
    def test_atomic_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4)}}
        save_pytree(tmp_path, 5, tree, extra={"note": "x"})
        like = jax.tree.map(jnp.zeros_like, tree)
        loaded, extra = load_pytree(tmp_path, 5, like)
        assert extra == {"note": "x"}
        assert jnp.array_equal(loaded["a"], tree["a"])
        assert latest_step(tmp_path) == 5

    def test_crashed_save_never_shadows(self, tmp_path):
        tree = {"w": jnp.ones(3)}
        save_pytree(tmp_path, 1, tree)
        # simulate a crash mid-save: a stale .tmp directory left behind
        tmp = Path(tmp_path) / "step_00000002.tmp"
        tmp.mkdir()
        (tmp / "garbage").write_text("partial")
        assert available_steps(tmp_path) == [1]     # tmp ignored
        save_pytree(tmp_path, 2, tree)              # retry succeeds
        assert available_steps(tmp_path) == [1, 2]

    def test_async_saver_retention(self, tmp_path):
        saver = AsyncSaver(tmp_path, keep=2)
        for s in (1, 2, 3, 4):
            saver.save(s, {"w": jnp.full(2, float(s))})
        saver.wait()
        assert available_steps(tmp_path) == [3, 4]


class TestTrainerRestart:
    def test_kill_and_resume_bitwise(self, tmp_path):
        # uninterrupted run
        t1 = tiny_trainer(tmp_path / "a")
        rep1 = t1.run(resume=False)
        # interrupted run: stop after step 6 (checkpoint at 4), then resume
        t2 = tiny_trainer(tmp_path / "b")
        t2.run(resume=False, stop_after=6)
        t3 = tiny_trainer(tmp_path / "b")
        rep3 = t3.run(resume=True)
        assert rep3.resumed_from == 4
        # losses from the resumed segment match the uninterrupted run exactly
        assert rep1.losses[4:] == pytest.approx(rep3.losses, rel=0, abs=0)

    def test_straggler_monitor_counts(self, tmp_path):
        """Deterministic: inject a slow wrapped step; the EMA monitor flags
        it (timings via a real sleep inside the measured region)."""
        import time as _time
        t = tiny_trainer(tmp_path, steps=10, ckpt_every=100)
        orig = t._step
        counter = {"n": 0}

        def sometimes_slow(p, o, b):
            counter["n"] += 1
            out = orig(p, o, b)
            jax.block_until_ready(out[0])
            if counter["n"] == 8:
                _time.sleep(2.0)   # >> straggler_factor x EMA
            return out

        t._step = sometimes_slow
        rep = t.run(resume=False)
        assert rep.straggler_steps, "slow step not flagged"


class TestDataPipeline:
    def test_deterministic_and_resumable(self):
        cfg = DataConfig(vocab_size=1000, seq_len=32, global_batch=4)
        ds1 = SyntheticLMDataset(cfg)
        ds2 = SyntheticLMDataset(cfg)
        b5a = ds1.batch_at(5)["tokens"]
        b5b = ds2.batch_at(5)["tokens"]
        np.testing.assert_array_equal(b5a, b5b)
        it = ds1.iterate(start_step=5)
        np.testing.assert_array_equal(next(it)["tokens"], b5a)

    def test_learnable_structure(self):
        """Markov correlation gives sub-uniform perplexity headroom."""
        cfg = DataConfig(vocab_size=100, seq_len=256, global_batch=8,
                         markov_strength=0.5)
        ds = SyntheticLMDataset(cfg)
        toks = ds.batch_at(0)["tokens"]
        assert toks.min() >= 0 and toks.max() < 100
        # correlated pairs appear more often than chance
        perm_hits = (ds._perm[toks[:, :-1]] == toks[:, 1:]).mean()
        assert perm_hits > 0.2


class TestGradCompression:
    def test_quantize_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.key(0), (128, 64))
        q = quantize_int8(x, jax.random.key(1))
        err = jnp.abs(dequantize_int8(q) - x).max()
        assert float(err) <= float(q.scale) * 1.01

    def test_error_feedback_preserves_sum(self):
        """Residual accumulation: the long-run mean of compressed grads
        converges to the true mean (error feedback property)."""
        comp = GradCompressor.init({"w": jnp.zeros((64, 64))})
        g = {"w": 0.01 * jax.random.normal(jax.random.key(2), (64, 64))}
        total = jnp.zeros((64, 64))
        for _ in range(50):
            out, comp = comp.roundtrip(g)
            total = total + out["w"]
        mean_err = jnp.abs(total / 50 - g["w"]).mean()
        assert float(mean_err) < 5e-4

    def test_training_with_compression_runs(self, tmp_path):
        t = tiny_trainer(tmp_path, steps=4, ckpt_every=100, compress_grads=True)
        rep = t.run(resume=False)
        assert len(rep.losses) == 4
        assert all(np.isfinite(rep.losses))


class TestElasticReshard:
    def test_checkpoint_mesh_agnostic(self, tmp_path):
        """A checkpoint written unsharded loads onto any mesh (the shard
        layout lives in the load-time shardings, not the file)."""
        tree = {"w": jnp.arange(64.0).reshape(8, 8)}
        save_pytree(tmp_path, 1, tree)
        mesh = jax.make_mesh((1,), ("data",))
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = {"w": NamedSharding(mesh, P("data", None))}
        loaded, _ = load_pytree(tmp_path, 1, jax.tree.map(jnp.zeros_like, tree),
                                shardings=sh)
        assert jnp.array_equal(loaded["w"], tree["w"])
        assert loaded["w"].sharding == sh["w"]


# ---------------------------------------------------------------------------
# serving front-end fault matrix
# ---------------------------------------------------------------------------
def _frontend_service(n_tenants=2, edges=2):
    from repro.core.online import OnlineDecisionService
    from repro.core.posterior import BetaPosterior

    svc = OnlineDecisionService()
    for t in range(n_tenants):
        for e in range(edges):
            svc.register_edge((f"u{e}", f"v{e}"), tenant=f"t{t}",
                              posterior=BetaPosterior(alpha=16.0, beta=2.0))
    return svc


def _fe_req(row, tenant, edge, **kw):
    from repro.serving.frontend import DecisionRequest

    base = dict(alpha=0.5, lambda_usd_per_s=0.9, latency_s=3.0,
                input_tokens=500.0, output_tokens=300.0,
                input_price=3e-6, output_price=15e-6)
    base.update(kw)
    return DecisionRequest(row=row, tenant=tenant, edge=edge, **base)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestFrontendFaultMatrix:
    def test_breaker_trips_on_consecutive_tick_faults(self):
        from repro.serving.faults import FaultInjector, FaultPlan, FaultyService
        from repro.serving.frontend import (
            BreakerState, FrontendConfig, ServingFrontend)

        svc = _frontend_service(n_tenants=1, edges=1)
        inj = FaultInjector(FaultPlan(raise_from=0, raise_until=3))
        fe = ServingFrontend(
            FaultyService(svc, inj),
            FrontendConfig(max_batch=2, breaker_failure_threshold=3,
                           breaker_cooldown_s=10.0),
            clock=_Clock(), autostart=False)
        key = ("t0", ("u0", "v0"))
        for i in range(3):
            tk = fe.submit(_fe_req(0, "t0", ("u0", "v0")))
            fe.pump()
            res = tk.result(0)
            assert res.source == "scalar"      # degraded, never blocked
            if res.speculate:
                tk.release()
            want = (BreakerState.OPEN if i == 2 else BreakerState.CLOSED)
            assert fe.breaker.state(key) is want
        # while open, requests skip the (still-faulting) service entirely
        calls_before = inj.calls
        tk = fe.submit(_fe_req(0, "t0", ("u0", "v0")))
        assert tk.done() and tk.result(0).source == "scalar"
        if tk.result(0).speculate:
            tk.release()
        assert inj.calls == calls_before
        kinds = fe.resilience.by_kind()
        assert kinds["exception"] == 3
        assert kinds["breaker_open"] == 1
        assert kinds["fallback_scalar"] == 4

    def test_tick_exception_message_reaches_resilience_log(self):
        """A failing tick still degrades every request down the chain,
        but the exception's type and message are kept: on each
        ``exception`` event's detail and as ``last_tick_error``."""
        from repro.serving.faults import FaultInjector, FaultPlan, FaultyService
        from repro.serving.frontend import FrontendConfig, ServingFrontend

        svc = _frontend_service(n_tenants=1, edges=1)
        inj = FaultInjector(FaultPlan(raise_calls=frozenset({0})))
        fe = ServingFrontend(FaultyService(svc, inj),
                             FrontendConfig(max_batch=2), clock=_Clock(),
                             autostart=False)
        assert fe.last_tick_error is None
        tk = fe.submit(_fe_req(0, "t0", ("u0", "v0")))
        fe.pump()
        assert tk.result(0).source == "scalar"
        want = "InjectedFault: injected fault at call 0"
        assert fe.last_tick_error == want
        exc = [e for e in fe.resilience if e.kind == "exception"]
        assert [e.detail for e in exc] == [want]
        # the next, healthy tick leaves the newest error in place
        if tk.result(0).speculate:
            tk.release()
        tk = fe.submit(_fe_req(0, "t0", ("u0", "v0")))
        fe.pump()
        assert tk.result(0).source == "service"
        assert fe.last_tick_error == want

        class _Raising:
            def __init__(self, inner):
                self._inner = inner

            def __getattr__(self, name):
                return getattr(self._inner, name)

            def tick_packed(self, *a, **k):
                raise ValueError("x" * 5000)

        fe2 = ServingFrontend(_Raising(svc), FrontendConfig(max_batch=2),
                              clock=_Clock(), autostart=False)
        fe2.submit(_fe_req(0, "t0", ("u0", "v0")))
        fe2.pump()
        assert fe2.last_tick_error.startswith("ValueError: xxx")
        assert len(fe2.last_tick_error) == 500
        assert fe2.stats["tick_faults"] == 1

    def test_half_open_probe_recovers_service_path(self):
        from repro.serving.faults import FaultInjector, FaultPlan, FaultyService
        from repro.serving.frontend import (
            BreakerState, FrontendConfig, ServingFrontend)

        svc = _frontend_service(n_tenants=1, edges=1)
        inj = FaultInjector(FaultPlan(raise_from=0, raise_until=1))
        clock = _Clock()
        fe = ServingFrontend(
            FaultyService(svc, inj),
            FrontendConfig(max_batch=2, breaker_failure_threshold=1,
                           breaker_cooldown_s=0.5),
            clock=clock, autostart=False)
        key = ("t0", ("u0", "v0"))
        tk = fe.submit(_fe_req(0, "t0", ("u0", "v0")))
        fe.pump()                              # faulted -> breaker opens
        if tk.result(0).speculate:
            tk.release()
        assert fe.breaker.state(key) is BreakerState.OPEN
        clock.t = 1.0                          # cooldown elapses
        probe = fe.submit(_fe_req(0, "t0", ("u0", "v0")))
        assert fe.breaker.state(key) is BreakerState.HALF_OPEN
        fe.pump()                              # healthy tick closes it
        res = probe.result(0)
        assert res.source == "service"
        if res.speculate:
            probe.settle(True)
        assert fe.breaker.state(key) is BreakerState.CLOSED
        kinds = fe.resilience.by_kind()
        assert kinds["breaker_half_open"] == 1 and kinds["breaker_close"] == 1

    def test_bulkhead_sheds_flooding_tenant_only(self):
        from repro.core.decision import Decision
        from repro.serving.frontend import FrontendConfig, ServingFrontend

        fe = ServingFrontend(
            _frontend_service(),
            FrontendConfig(max_batch=64, bulkhead_limit=3),
            autostart=False)
        flood = [fe.submit(_fe_req(0, "t0", ("u0", "v0")))
                 for _ in range(10)]
        calm = [fe.submit(_fe_req(2, "t1", ("u0", "v0")))
                for _ in range(3)]
        fe.pump()
        shed = [t for t in flood if t.result(0).source == "shed"]
        assert len(shed) == 7                  # beyond the 3-slot bulkhead
        assert all(t.result(0).decision is Decision.WAIT for t in shed)
        assert all(t.result(0).source == "service" for t in calm)
        # every shed carries a USD-attributed event for the right tenant
        att = fe.resilience.usd_attribution()
        assert att[("t0", "shed")] == pytest.approx(7 * 3.0 * 0.9)
        assert ("t1", "shed") not in att
        for t in flood + calm:
            if t.result(0).speculate:
                t.settle(True)

    def test_deadline_tick_fires_with_partial_batch(self):
        """Real batcher thread: a single request (far below max_batch)
        must be answered after ~deadline_s, not held for batch-full."""
        import time as _time

        from repro.serving.frontend import FrontendConfig, ServingFrontend

        with ServingFrontend(
                _frontend_service(),
                FrontendConfig(max_batch=64, deadline_s=0.05)) as fe:
            tk = fe.submit(_fe_req(0, "t0", ("u0", "v0")))
            res = tk.result(10.0)              # jit compile on first tick
            assert res.source == "service"
            if res.speculate:
                tk.settle(True)
            # steady state: the deadline, not batch-full, fires the tick
            t0 = _time.perf_counter()
            tk2 = fe.submit(_fe_req(0, "t0", ("u0", "v0")))
            res2 = tk2.result(10.0)
            waited = _time.perf_counter() - t0
            if res2.speculate:
                tk2.settle(True)
            assert waited >= 0.04              # held for the window
            assert waited < 5.0
            assert fe.stats["deadline_ticks"] >= 2
            assert fe.stats["full_ticks"] == 0

    def test_fallback_chain_bitwise_matches_scalar_evaluate(self):
        """Both degraded stages answer with exactly decision.evaluate:
        tick faults (stage 2 via exception) and breaker-open (stage 2 via
        admission) under enable_x64 — bitwise EV/threshold/P."""
        from jax import enable_x64

        from repro.core.decision import DecisionInputs, evaluate
        from repro.core.posterior import BetaPosterior
        from repro.serving.faults import FaultInjector, FaultPlan, FaultyService
        from repro.serving.frontend import FrontendConfig, ServingFrontend

        with enable_x64():
            svc = _frontend_service(n_tenants=1, edges=2)
            inj = FaultInjector(FaultPlan(raise_from=0))   # every tick fails
            fe = ServingFrontend(
                FaultyService(svc, inj),
                FrontendConfig(max_batch=4, breaker_failure_threshold=2),
                autostart=False)
            snap = svc.posterior_snapshot()
            reqs = [_fe_req(r, "t0", (f"u{r}", f"v{r}"),
                            latency_s=1.0 + r, output_tokens=200.0 + r)
                    for r in range(2)]
            for round_ in range(3):            # rounds 0-1 fault, then open
                tks = [fe.submit(q) for q in reqs]
                fe.pump()
                for tk, q in zip(tks, reqs):
                    res = tk.result(0)
                    assert res.source == "scalar"
                    post = BetaPosterior(alpha=float(snap[q.row, 0]),
                                         beta=float(snap[q.row, 1]))
                    ref = evaluate(DecisionInputs(
                        P=post.mean, alpha=q.alpha,
                        lambda_usd_per_s=q.lambda_usd_per_s,
                        latency_seconds=q.latency_s,
                        input_tokens=q.input_tokens,
                        output_tokens=q.output_tokens,
                        input_price=q.input_price,
                        output_price=q.output_price))
                    assert res.decision is ref.decision
                    assert res.EV_usd == ref.EV_usd
                    assert res.threshold_usd == ref.threshold_usd
                    assert res.C_spec_usd == ref.C_spec_usd
                    assert res.L_value_usd == ref.L_value_usd
                    assert res.P_used == ref.P_used
                    if res.speculate:
                        tk.release()


# ---------------------------------------------------------------------------
# seeded drift-trace primitives (shared by scenarios.py and this file)
# ---------------------------------------------------------------------------
class TestDriftTracePrimitives:
    def test_flip_and_revert_rates(self):
        from repro.serving.faults import DriftTrace
        tr = DriftTrace.flip(10, rate0=0.9, rate1=0.1, revert_at=20)
        assert [tr.rate_at(i) for i in (0, 9, 10, 19, 20, 99)] == \
            [0.9, 0.9, 0.1, 0.1, 0.9, 0.9]

    def test_ramp_is_linear_between_endpoints(self):
        from repro.serving.faults import DriftTrace
        tr = DriftTrace.ramp(10, 20, rate0=1.0, rate1=0.0)
        assert tr.rate_at(9) == 1.0 and tr.rate_at(20) == 0.0
        assert tr.rate_at(15) == pytest.approx(0.5)
        mids = [tr.rate_at(i) for i in range(10, 20)]
        assert all(a >= b for a, b in zip(mids, mids[1:]))
        with pytest.raises(ValueError):
            DriftTrace.ramp(5, 5)

    def test_oscillation_square_wave(self):
        from repro.serving.faults import DriftTrace
        tr = DriftTrace.oscillation(3, rate0=0.9, rate1=0.1)
        assert [tr.rate_at(i) for i in range(7)] == \
            [0.9, 0.9, 0.9, 0.1, 0.1, 0.1, 0.9]
        shifted = DriftTrace.oscillation(3, rate0=0.9, rate1=0.1, phase=3)
        assert shifted.rate_at(0) == 0.1

    def test_injector_samples_trace_deterministically(self):
        from repro.serving.faults import DriftTrace, FaultInjector, FaultPlan
        tr = DriftTrace.flip(50, rate0=1.0, rate1=0.0)
        runs = []
        for _ in range(2):
            inj = FaultInjector(FaultPlan(trace=tr, seed=11))
            runs.append([inj.outcome() for _ in range(100)])
        assert runs[0] == runs[1]                  # same seed, same stream
        assert all(runs[0][:50]) and not any(runs[0][50:])
        other = FaultInjector(FaultPlan(trace=DriftTrace.constant(0.5),
                                        seed=12))
        got = [other.outcome() for _ in range(200)]
        assert 60 <= sum(got) <= 140               # actually stochastic

    def test_heavy_tail_tokens_seeded_capped(self):
        from repro.serving.faults import heavy_tail_tokens
        a = heavy_tail_tokens(3, 4096, median=256.0, cap=4096.0)
        b = heavy_tail_tokens(3, 4096, median=256.0, cap=4096.0)
        assert np.array_equal(a, b)
        assert a.min() >= 1.0 and a.max() <= 4096.0
        assert 150.0 < float(np.median(a)) < 400.0
        assert float(a.mean()) > float(np.median(a))   # heavy right tail
        with pytest.raises(ValueError):
            heavy_tail_tokens(0, 0)

    def test_correlated_flip_traces_jitter_and_determinism(self):
        from repro.serving.faults import correlated_flip_traces
        a = correlated_flip_traces(5, 30, seed=9, jitter=3, revert_at=60)
        b = correlated_flip_traces(5, 30, seed=9, jitter=3, revert_at=60)
        assert a == b
        assert all(27 <= tr.at <= 33 for tr in a)
        assert all(tr.until is not None and tr.until > tr.at for tr in a)
        exact = correlated_flip_traces(4, 30)      # jitter=0: perfect corr
        assert all(tr.at == 30 for tr in exact)
        with pytest.raises(ValueError):
            correlated_flip_traces(0, 10)
