"""Open-loop load + fault-matrix benchmark for the serving front-end.

Closed-loop benchmarks (PR 5's ``ticks_per_s``) measure the decision
core; they cannot see queueing.  This module offers *open-loop* load — a
seeded Poisson arrival process submits requests on its own clock,
independent of completions, the way real traffic does — against
``repro.serving.frontend.ServingFrontend`` and reports what an operator
would ask of the layer:

* sustained decisions/sec at the offered rate,
* shed rate (bulkhead + admission),
* p50/p99 submit→resolve latency (the deadline batcher's window plus
  one jit'd tick),

with the repo's standing discipline applied first: **parity before
timing**.  Under ``enable_x64`` the healthy path's decisions must be
bitwise equal to scalar ``decision.evaluate`` over the pre-tick
posterior snapshot, and the degraded path (breaker forced open) must be
bitwise the same scalar rule — only then is anything timed (at the
serving default dtype).

The fault matrix then drives the same front-end through injected
exception bursts, a hung tick under a watchdog timeout, a tenant flood,
and a §12.5 success-rate flip, asserting the three resilience
invariants from the issue: the sequential path is never blocked (every
ticket resolves), every shed/trip/fallback emits a USD-attributed
resilience event, and fallback decisions match the scalar rule.

Everything is persisted to ``BENCH_frontend.json`` (``write=False`` —
the --smoke path — returns the record without touching the file).
"""
from __future__ import annotations

import json
import pathlib
import time
from typing import Callable, Optional

import numpy as np

BENCH_PATH = pathlib.Path(__file__).resolve().parents[1] / "BENCH_frontend.json"

SEED = 0
LAMBDA_USD_PER_S = 0.9
PRICE_IN, PRICE_OUT = 3e-6, 15e-6


# --------------------------------------------------------------------------
# arrival process + request mix
# --------------------------------------------------------------------------
def poisson_arrivals(rate_hz: float, duration_s: float,
                     seed: int = SEED) -> np.ndarray:
    """Seeded open-loop arrival times in [0, duration): exponential
    inter-arrival gaps at ``rate_hz`` (the memoryless process a
    closed-loop driver cannot emulate — see EXPERIMENTS.md §Resilience)."""
    rng = np.random.default_rng(seed)
    n = max(16, int(rate_hz * duration_s * 2) + 64)
    t = np.cumsum(rng.exponential(1.0 / rate_hz, size=n))
    out = t[t < duration_s]
    if out.size == 0:
        raise ValueError("empty arrival trace; raise rate or duration")
    return out


def build_service(n_tenants: int = 4, edges_per_tenant: int = 4, *,
                  credible_consecutive_n: int = 5, seed: int = SEED):
    """A small multi-tenant registry with mixed priors and a credible
    floor on every row (so the §12.5 kill-switch can actually breach)."""
    from repro.core.online import OnlineDecisionService
    from repro.core.posterior import BetaPosterior

    rng = np.random.default_rng(seed)
    svc = OnlineDecisionService(
        credible_consecutive_n=credible_consecutive_n)
    for t in range(n_tenants):
        for e in range(edges_per_tenant):
            # priors with mean >= ~0.7 keep the credible bound comfortably
            # above the 0.35 floor under healthy traffic; only a §12.5
            # success-rate flip can walk it through the floor
            svc.register_edge(
                (f"agent{e}", f"agent{e + 1}"), tenant=f"tenant{t}",
                posterior=BetaPosterior(
                    alpha=float(rng.uniform(8.0, 24.0)),
                    beta=float(rng.uniform(1.0, 4.0))),
                floor_alpha=0.3, floor_C_spec_usd=1.0,
                floor_L_value_usd=1.0,   # floor = 0.7 * 1 / 2 = 0.35
            )
    return svc


def request_stream(svc, seed: int = SEED) -> Callable[[int], object]:
    """Deterministic request factory cycling the registry's rows with
    jittered D4 inputs."""
    from repro.serving.frontend import DecisionRequest

    rng = np.random.default_rng(seed + 1)
    n = svc.n_rows
    lat = rng.uniform(0.5, 5.0, size=4096)
    otok = rng.integers(64, 512, size=4096)

    def make(i: int) -> DecisionRequest:
        row = i % n
        tenant, edge = svc.row_key(row)
        j = i % 4096
        return DecisionRequest(
            row=row, tenant=tenant, edge=edge, alpha=0.5,
            lambda_usd_per_s=LAMBDA_USD_PER_S, latency_s=float(lat[j]),
            input_tokens=500.0, output_tokens=float(otok[j]),
            input_price=PRICE_IN, output_price=PRICE_OUT)

    return make


# --------------------------------------------------------------------------
# drivers
# --------------------------------------------------------------------------
class VirtualClock:
    """Injectable monotonic stand-in: tests/smoke advance it by hand."""

    def __init__(self, t: float = 0.0) -> None:
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def _prefix_settler(tickets: list, settle: Optional[Callable[[], bool]]):
    """Settle resolved tickets in submission order (batches are FIFO, so
    ``done()`` flips in prefix order); launched speculations settle as
    soon as their answer lands, releasing the bulkhead slot the way a
    live executor would."""
    cursor = [0]

    def run() -> None:
        while cursor[0] < len(tickets) and tickets[cursor[0]].done():
            tk = tickets[cursor[0]]
            cursor[0] += 1
            if tk.result(0).speculate:
                tk.settle(settle() if settle is not None else True)

    return run


def drive_virtual(frontend, clock: VirtualClock, arrivals: np.ndarray,
                  make_request, *, settle: Optional[Callable[[], bool]]
                  = None) -> list:
    """Deterministic replay of the batcher loop on the virtual clock:
    submissions land at their arrival times and a tick fires exactly at
    batch-full or deadline, whichever first — the same policy
    ``ServingFrontend._loop`` runs on the wall clock.  Requires
    ``autostart=False``.  Returns the resolved tickets."""
    deadline = frontend.config.deadline_s
    tickets: list = []
    settle_done = _prefix_settler(tickets, settle)

    def fire_due(now: float) -> None:
        while True:
            t0 = frontend.oldest_pending_t
            if t0 is None or t0 + deadline > now:
                return
            clock.t = t0 + deadline
            frontend.pump()
            settle_done()

    for i, ta in enumerate(arrivals):
        fire_due(float(ta))
        clock.t = float(ta)
        tickets.append(frontend.submit(make_request(i)))
        if frontend.pending_count >= frontend.config.max_batch:
            frontend.pump()
        settle_done()
    # drain the tail
    while frontend.pending_count:
        fire_due(clock.t + deadline + 1.0)
    settle_done()
    for tk in tickets:
        tk.result(0)                  # all resolved — never blocks
    return tickets


def drive_open_loop(frontend, arrivals: np.ndarray, make_request, *,
                    settle: Optional[Callable[[], bool]] = None,
                    result_timeout_s: float = 10.0) -> tuple[list, float]:
    """Real-time open-loop run against the live batcher thread: submit at
    the trace's arrival times regardless of completions (settling
    resolved tickets opportunistically between submissions), then resolve
    and settle the stragglers.  Returns (tickets, wall_s)."""
    tickets: list = []
    settle_done = _prefix_settler(tickets, settle)
    t0 = time.perf_counter()
    for i, ta in enumerate(arrivals):
        lag = float(ta) - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        tickets.append(frontend.submit(make_request(i)))
        settle_done()
    for tk in tickets:
        tk.result(result_timeout_s)
    settle_done()
    return tickets, time.perf_counter() - t0


# --------------------------------------------------------------------------
# parity gates (run before any timing — repo discipline)
# --------------------------------------------------------------------------
def assert_frontend_parity(n_requests: int = 32) -> dict:
    """Bitwise-f64 gates on both chain stages.

    healthy: a pumped batch's per-request floats equal scalar
    ``decision.evaluate`` over the pre-tick posterior snapshot.
    degraded: with every circuit forced open, answers come from the
    scalar stage and equal ``decision.evaluate`` over the mirror —
    by construction *and* re-checked value-by-value here.
    """
    from jax import enable_x64

    from repro.core.decision import Decision, DecisionInputs, evaluate
    from repro.core.posterior import BetaPosterior
    from repro.serving.frontend import FrontendConfig, ServingFrontend

    with enable_x64():
        svc = build_service()
        make = request_stream(svc)
        fe = ServingFrontend(svc, FrontendConfig(max_batch=n_requests),
                             autostart=False)
        snap = svc.posterior_snapshot()
        reqs = [make(i) for i in range(n_requests)]
        tickets = [fe.submit(r) for r in reqs]
        fe.pump()

        def scalar_ref(r):
            post = BetaPosterior(alpha=float(snap[r.row, 0]),
                                 beta=float(snap[r.row, 1]))
            return evaluate(DecisionInputs(
                P=post.mean, alpha=r.alpha,
                lambda_usd_per_s=r.lambda_usd_per_s,
                latency_seconds=r.latency_s, input_tokens=r.input_tokens,
                output_tokens=r.output_tokens, input_price=r.input_price,
                output_price=r.output_price))

        for tk, r in zip(tickets, reqs):
            res, ref = tk.result(0), scalar_ref(r)
            if res.source != "service":
                raise AssertionError("healthy parity batch left the service path")
            same = (res.decision is ref.decision
                    and res.EV_usd == ref.EV_usd
                    and res.threshold_usd == ref.threshold_usd
                    and res.C_spec_usd == ref.C_spec_usd
                    and res.L_value_usd == ref.L_value_usd
                    and res.P_used == ref.P_used)
            if not same:
                raise AssertionError(
                    f"service tick != scalar evaluate on row {r.row}: "
                    f"{res} vs {ref}")
            if res.speculate:
                tk.release()

        # degraded stage: force every circuit open; submissions now answer
        # synchronously through the scalar fallback over the mirror
        for r in reqs:
            fe.breaker.trip(r.key)
        fb = [fe.submit(r) for r in reqs]
        for tk, r in zip(fb, reqs):
            res, ref = tk.result(0), scalar_ref(r)
            if res.source != "scalar":
                raise AssertionError("breaker-open request escaped the fallback stage")
            if not (res.decision is ref.decision and res.EV_usd == ref.EV_usd
                    and res.threshold_usd == ref.threshold_usd
                    and res.P_used == ref.P_used):
                raise AssertionError(
                    f"scalar fallback != decision.evaluate on row {r.row}")
            if res.speculate:
                tk.release()
        n_spec = sum(
            1 for tk in fb if tk.result(0).decision is Decision.SPECULATE)
    return {
        "service_vs_scalar_bitwise_f64": True,
        "fallback_vs_scalar_bitwise_f64": True,
        "requests": n_requests,
        "fallback_speculates": n_spec,
    }


# --------------------------------------------------------------------------
# fault matrix
# --------------------------------------------------------------------------
def _events_cover(frontend, *kinds: str) -> None:
    got = frontend.resilience.by_kind()
    missing = [k for k in kinds if got.get(k, 0) < 1]
    if missing:
        raise AssertionError(f"fault run emitted no {missing}; got {got}")


def _all_resolved(tickets) -> None:
    unresolved = sum(0 if t.done() else 1 for t in tickets)
    if unresolved:
        raise AssertionError(
            f"{unresolved} tickets unresolved — sequential path blocked")


def fault_matrix(seed: int = SEED) -> dict:
    """Deterministic degraded-mode scenarios; each returns its event
    counts and the USD attribution so the record shows what degradation
    cost.  Invariants asserted per scenario: every ticket resolves and
    every degradation leaves a resilience event."""
    from repro.serving.faults import FaultInjector, FaultPlan, FaultyService
    from repro.serving.frontend import FrontendConfig, ServingFrontend

    out: dict[str, dict] = {}

    # -- 1. exception burst: breaker opens, scalar fallback answers,
    # cooldown elapses, probe closes the circuit
    svc = build_service(n_tenants=1, edges_per_tenant=2, seed=seed)
    make = request_stream(svc, seed)
    inj = FaultInjector(FaultPlan(raise_from=0, raise_until=2, seed=seed))
    clock = VirtualClock()
    fe = ServingFrontend(
        FaultyService(svc, inj),
        FrontendConfig(max_batch=4, breaker_failure_threshold=2,
                       breaker_cooldown_s=0.25, bulkhead_limit=64),
        clock=clock, autostart=False)
    tickets = []
    for burst in range(4):                  # 2 faulted ticks, then healthy
        batch = [fe.submit(make(i)) for i in range(4 * burst, 4 * burst + 4)]
        tickets += batch
        fe.pump()
        for tk in batch:
            if tk.result(0).speculate:
                tk.settle(True)
        clock.advance(0.3)                  # past cooldown between bursts
    _all_resolved(tickets)
    _events_cover(fe, "exception", "breaker_open", "fallback_scalar",
                  "breaker_half_open", "breaker_close")
    out["exception_burst"] = {
        "events": fe.resilience.by_kind(), "stats": dict(fe.stats)}

    # -- 2. hung tick under the watchdog: SpeculationTimeout degrades the
    # batch to the scalar stage (real clock — the timeout is wall time)
    svc = build_service(n_tenants=1, edges_per_tenant=2, seed=seed)
    make = request_stream(svc, seed)
    inj = FaultInjector(FaultPlan(hang_calls=frozenset({0}), hang_s=0.3,
                                  seed=seed))
    fe = ServingFrontend(
        FaultyService(svc, inj),
        FrontendConfig(max_batch=4, tick_timeout_s=0.05, bulkhead_limit=64),
        autostart=False)
    tickets = [fe.submit(make(i)) for i in range(4)]
    t0 = time.perf_counter()
    fe.pump()
    blocked_s = time.perf_counter() - t0
    _all_resolved(tickets)
    for tk in tickets:
        if tk.result(0).source != "scalar":
            raise AssertionError("timed-out tick did not degrade to scalar")
        if tk.result(0).speculate:
            tk.release()
    _events_cover(fe, "timeout", "fallback_scalar")
    if blocked_s > 0.25:                    # watchdog, not the 0.3 s hang
        raise AssertionError(f"timeout path blocked {blocked_s:.3f}s")
    out["hung_tick"] = {
        "events": fe.resilience.by_kind(), "blocked_s": round(blocked_s, 4)}

    # -- 3. tenant flood: one tenant saturates its bulkhead and is shed;
    # the quiet tenant's requests all pass admission
    svc = build_service(n_tenants=2, edges_per_tenant=2, seed=seed)
    make = request_stream(svc, seed)
    fe = ServingFrontend(svc, FrontendConfig(max_batch=64, bulkhead_limit=4),
                         autostart=False)
    noisy = [make(i) for i in range(64) if make(i).tenant == "tenant0"]
    quiet = [make(i) for i in range(64) if make(i).tenant == "tenant1"][:4]
    flood = [fe.submit(r) for r in noisy]
    calm = [fe.submit(r) for r in quiet]
    fe.pump()
    _all_resolved(flood + calm)
    shed = [t for t in flood if t.result(0).source == "shed"]
    if len(shed) != len(noisy) - fe.config.bulkhead_limit:
        raise AssertionError(
            f"expected {len(noisy) - 4} sheds, got {len(shed)}")
    if any(t.result(0).source == "shed" for t in calm):
        raise AssertionError("quiet tenant shed during the flood")
    for t in flood + calm:
        if t.result(0).speculate:
            t.settle(True)
    _events_cover(fe, "shed")
    attrib = {f"{t}|{k}": round(v, 6)
              for (t, k), v in fe.resilience.usd_attribution().items()}
    if not any(k.startswith("tenant0|shed") and v > 0
               for k, v in attrib.items()):
        raise AssertionError("sheds carried no USD attribution")
    out["tenant_flood"] = {
        "events": fe.resilience.by_kind(), "usd_attribution": attrib}

    # -- 4. §12.5 success-rate flip: the drifting outcome stream drives
    # the credible bound through the row's floor; the in-graph
    # kill-switch breach folds into the breaker as a trip
    svc = build_service(n_tenants=1, edges_per_tenant=1,
                        credible_consecutive_n=2, seed=seed)
    make = request_stream(svc, seed)
    inj = FaultInjector(FaultPlan(success_rate0=0.95, success_rate1=0.02,
                                  drift_at=0, seed=seed))
    fe = ServingFrontend(svc, FrontendConfig(max_batch=2, bulkhead_limit=256,
                                             check_drift=True),
                         autostart=False)
    tickets = []
    for i in range(120):
        tk = fe.submit(make(0))
        tickets.append(tk)
        fe.pump()
        res = tk.result(0)
        if res.speculate:
            tk.settle(inj.outcome())       # post-flip failures pile on
        if fe.resilience.by_kind().get("drift_trip", 0):
            break
    _all_resolved(tickets)
    _events_cover(fe, "drift_trip", "breaker_open")
    # after the trip the breaker answers without the service
    post = fe.submit(make(0))
    if post.result(0).source not in ("scalar", "conservative"):
        raise AssertionError("tripped edge still reached the service")
    if post.result(0).speculate:
        post.release()
    out["drift_flip"] = {
        "events": fe.resilience.by_kind(),
        "ticks_to_trip": fe.ticks,
        "post_trip_source": post.result(0).source,
    }
    return out


# --------------------------------------------------------------------------
# the record
# --------------------------------------------------------------------------
def frontend_record(*, rate_hz: float = 800.0, duration_s: float = 2.5,
                    max_batch: int = 64, deadline_s: float = 0.005,
                    bulkhead_limit: int = 24, seed: int = SEED,
                    write: bool = True) -> dict:
    """Parity gates → fault matrix → timed open-loop run →
    BENCH_frontend.json."""
    from repro.serving.frontend import FrontendConfig, ServingFrontend

    parity = assert_frontend_parity()
    faults = fault_matrix(seed)

    svc = build_service(seed=seed)
    make = request_stream(svc, seed)
    arrivals = poisson_arrivals(rate_hz, duration_s, seed)
    cfg = FrontendConfig(max_batch=max_batch, deadline_s=deadline_s,
                         bulkhead_limit=bulkhead_limit)
    with ServingFrontend(svc, cfg) as fe:
        # warm both tick executables off the clock (the frontend pads
        # every batch to max_batch, so there are exactly two: settle-free
        # and with the packed outcome block) — round 1 compiles the
        # former, its settles make round 2 compile the latter
        for _ in range(2):
            warm = [fe.submit(make(i)) for i in range(max_batch)]
            for tk in warm:
                if tk.result(10.0).speculate:
                    tk.settle(True)
        rng = np.random.default_rng(seed + 2)
        settle = lambda: bool(rng.random() < 0.9)         # noqa: E731
        tickets, wall_s = drive_open_loop(fe, arrivals, make, settle=settle)
        lat = np.array([t.latency_s for t in tickets])
        stats = dict(fe.stats)
        events = fe.resilience.by_kind()
        attrib = {f"{t}|{k}": round(v, 6)
                  for (t, k), v in fe.resilience.usd_attribution().items()}
        ticks = fe.ticks

    n = len(tickets)
    shed = sum(1 for t in tickets if t.result(0).source == "shed")
    record = {
        "benchmark": "serving_frontend_open_loop",
        "seed": seed,
        "offered_rate_hz": rate_hz,
        "duration_s": duration_s,
        "requests": n,
        "config": {"max_batch": max_batch, "deadline_s": deadline_s,
                   "bulkhead_limit": bulkhead_limit},
        "decisions_per_s": round(n / wall_s, 2),
        "shed_rate": round(shed / n, 6),
        "latency_ms": {
            "p50": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "p99": round(float(np.percentile(lat, 99)) * 1e3, 3),
            "max": round(float(lat.max()) * 1e3, 3),
        },
        "ticks": ticks,
        "deadline_ticks": stats["deadline_ticks"],
        "full_ticks": stats["full_ticks"],
        "stats": stats,
        "parity": parity,
        "fault_matrix": faults,
        "resilience_events": events,
        "usd_attribution": attrib,
    }
    if write:
        BENCH_PATH.write_text(json.dumps(record, indent=2) + "\n")
    return record


def smoke() -> dict:
    """The --smoke gate: both parity checks, the full fault matrix, and a
    deterministic virtual-clock open-loop trace (seeded Poisson arrivals,
    no wall-clock timing, nothing written).  The record keeps the full
    BENCH_frontend.json shape so schema drift breaks tier-1."""
    from repro.serving.frontend import FrontendConfig, ServingFrontend

    parity = assert_frontend_parity(n_requests=8)
    faults = fault_matrix(SEED)

    svc = build_service(n_tenants=2, edges_per_tenant=2)
    make = request_stream(svc)
    clock = VirtualClock()
    cfg = FrontendConfig(max_batch=8, deadline_s=0.002, bulkhead_limit=16)
    fe = ServingFrontend(svc, cfg, clock=clock, autostart=False)
    arrivals = poisson_arrivals(rate_hz=400.0, duration_s=0.25, seed=SEED)
    tickets = drive_virtual(fe, clock, arrivals, make)
    lat = np.array([t.latency_s for t in tickets])
    if lat.max() > cfg.deadline_s + 1e-9:
        raise AssertionError(
            "virtual-clock latency exceeded the deadline window")
    if fe.stats["deadline_ticks"] < 1:
        raise AssertionError("no deadline tick fired on a partial batch")
    n = len(tickets)
    shed = sum(1 for t in tickets if t.result(0).source == "shed")
    return {
        "benchmark": "serving_frontend_open_loop",
        "seed": SEED,
        "offered_rate_hz": 400.0,
        "duration_s": 0.25,
        "requests": n,
        "config": {"max_batch": cfg.max_batch, "deadline_s": cfg.deadline_s,
                   "bulkhead_limit": cfg.bulkhead_limit},
        "decisions_per_s": 0.0,            # no timing claims in smoke
        "shed_rate": round(shed / n, 6),
        "latency_ms": {
            "p50": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "p99": round(float(np.percentile(lat, 99)) * 1e3, 3),
            "max": round(float(lat.max()) * 1e3, 3),
        },
        "ticks": fe.ticks,
        "deadline_ticks": fe.stats["deadline_ticks"],
        "full_ticks": fe.stats["full_ticks"],
        "stats": dict(fe.stats),
        "parity": parity,
        "fault_matrix": faults,
        "resilience_events": fe.resilience.by_kind(),
        "usd_attribution": {
            f"{t}|{k}": round(v, 6)
            for (t, k), v in fe.resilience.usd_attribution().items()},
    }


def benchmarks() -> list[tuple[str, float, str]]:
    rec = frontend_record()
    lat = rec["latency_ms"]
    us_per_decision = 1e6 / rec["decisions_per_s"]
    return [(
        "frontend_open_loop",
        us_per_decision,
        (f"sustained {rec['decisions_per_s']:.0f}/s at offered "
         f"{rec['offered_rate_hz']:.0f}/s | shed {rec['shed_rate']:.3f} | "
         f"p50 {lat['p50']}ms p99 {lat['p99']}ms | "
         f"ticks {rec['ticks']} ({rec['deadline_ticks']} deadline)"),
    )]


if __name__ == "__main__":
    print(json.dumps(frontend_record(), indent=2))
