"""Async serving front-end for the jit'd online decision service.

PR 5 made the D4 decision path fast (``OnlineDecisionService.tick_packed``
answers B=1024 in one XLA call) but nothing *formed* the batch or survived
a misbehaving dependency.  This module is that missing layer — the piece
that turns the decision core into something that can face open-loop
traffic:

* **Deadline-driven batcher** — requests accumulate host-side and a tick
  fires on *batch-full OR deadline, whichever first* (``max_batch`` /
  ``deadline_s``).  Submission never blocks: the caller gets a
  :class:`FrontendTicket` immediately and the sequential path proceeds
  regardless of what the speculative machinery does.
* **Per-tenant bulkheads** — at most ``bulkhead_limit`` in-flight
  speculations per tenant; beyond it requests are *shed* with a
  conservative no-speculate answer (never queued, never blocking).  One
  flooding tenant cannot starve the fleet.
* **Circuit breaker + fallback chain** — a per-(tenant, edge)
  closed/open/half-open state machine folds host-side faults (tick
  exceptions, timeouts) and the service's in-graph kill-switch breach
  bits into one view.  Every request is answered through the chain
  *service tick → scalar ``decision.evaluate`` → conservative
  no-speculate*: an open breaker or failed tick degrades to the host
  scalar path over the last-known posterior mirror (bitwise-f64 the
  scalar rule — the same parity contract the service itself pins), and
  if even that is impossible the terminal stage answers WAIT.
* **Resilience telemetry** — every shed / trip / fallback emits a
  USD-attributed :class:`~repro.core.telemetry.ResilienceEvent` (host
  log) and an encoded event row on the service's device telemetry ring
  (``OnlineDecisionService.log_events``), so the cost of running
  degraded is an exportable number, not a log line.

Admissibility note: all of this decides *whether to launch* speculations;
a wrong answer in degraded mode can only cost money or latency, never
un-send an irreversible side effect — the paper's §4 admissibility
argument is exactly why shed-with-no-speculate is always safe.
"""
from __future__ import annotations

import dataclasses
import enum
import threading
import time
from typing import Any, Callable, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from ..core.decision import Decision, DecisionInputs, evaluate
from ..core.posterior import BetaPosterior
from ..core.telemetry import ResilienceEvent, ResilienceLog
from .spec_bridge import SpeculationTimeout, call_with_timeout

__all__ = [
    "BreakerState",
    "CircuitBreaker",
    "DecisionRequest",
    "FrontendConfig",
    "FrontendResult",
    "FrontendTicket",
    "ServingFrontend",
    "TenantBulkhead",
]


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    """Knobs of the serving front-end (all host-side)."""

    max_batch: int = 256              # tick fires at this many pending...
    deadline_s: float = 0.005         # ...or this long after the first
    max_queue: int = 4096             # admission bound on pending requests
    bulkhead_limit: int = 8           # in-flight speculations per tenant
    breaker_failure_threshold: int = 3
    breaker_cooldown_s: float = 0.5
    breaker_half_open_probes: int = 1
    tick_timeout_s: Optional[float] = None   # watchdog around the tick
    check_drift: bool = True          # run the in-graph kill-switch step
    snapshot_refresh_ticks: int = 8   # posterior-mirror refresh cadence
    ring_events: bool = True          # mirror events onto the device ring


class BreakerState(enum.Enum):
    CLOSED = "closed"
    OPEN = "open"
    HALF_OPEN = "half_open"


class _Breaker:
    __slots__ = ("state", "failures", "opened_at", "probes")

    def __init__(self) -> None:
        self.state = BreakerState.CLOSED
        self.failures = 0
        self.opened_at = 0.0
        self.probes = 0


class CircuitBreaker:
    """Per-key closed/open/half-open state machine with cooldown.

    ``allow`` is the admission check: CLOSED always passes; OPEN rejects
    until ``cooldown_s`` has elapsed, then transitions to HALF_OPEN and
    admits up to ``half_open_probes`` probe calls; a probe success closes
    the circuit, a probe failure re-opens it (cooldown restarts).  The
    clock is injectable so cooldown semantics are testable without real
    sleeps.  Thread-safe.
    """

    def __init__(self, *, failure_threshold: int = 3, cooldown_s: float = 0.5,
                 half_open_probes: int = 1,
                 clock: Callable[[], float] = time.monotonic,
                 on_transition: Optional[Callable[[Any, BreakerState], None]]
                 = None) -> None:
        if failure_threshold < 1:
            raise ValueError("failure_threshold must be >= 1")
        if half_open_probes < 1:
            raise ValueError("half_open_probes must be >= 1")
        self.failure_threshold = failure_threshold
        self.cooldown_s = cooldown_s
        self.half_open_probes = half_open_probes
        self.clock = clock
        self.on_transition = on_transition
        self._keys: dict[Any, _Breaker] = {}
        self._lock = threading.Lock()
        self.trips = 0

    def _get(self, key: Any) -> _Breaker:
        b = self._keys.get(key)
        if b is None:
            b = self._keys[key] = _Breaker()
        return b

    def _set_state(self, key: Any, b: _Breaker, state: BreakerState) -> None:
        if b.state is not state:
            b.state = state
            if self.on_transition is not None:
                self.on_transition(key, state)

    def state(self, key: Any) -> BreakerState:
        with self._lock:
            return self._get(key).state

    def allow(self, key: Any) -> bool:
        with self._lock:
            b = self._get(key)
            if b.state is BreakerState.CLOSED:
                return True
            if b.state is BreakerState.OPEN:
                if self.clock() - b.opened_at < self.cooldown_s:
                    return False
                self._set_state(key, b, BreakerState.HALF_OPEN)
                b.probes = 0
            # HALF_OPEN: admit a bounded number of probes
            if b.probes < self.half_open_probes:
                b.probes += 1
                return True
            return False

    def record_success(self, key: Any) -> None:
        with self._lock:
            b = self._get(key)
            b.failures = 0
            if b.state is not BreakerState.CLOSED:
                self._set_state(key, b, BreakerState.CLOSED)

    def record_failure(self, key: Any) -> None:
        with self._lock:
            b = self._get(key)
            if b.state is BreakerState.HALF_OPEN:
                self._open(key, b)
                return
            b.failures += 1
            if b.state is BreakerState.CLOSED and \
                    b.failures >= self.failure_threshold:
                self._open(key, b)

    def trip(self, key: Any) -> None:
        """Open immediately (kill-switch breach semantics)."""
        with self._lock:
            self._open(key, self._get(key))

    def _open(self, key: Any, b: _Breaker) -> None:
        b.failures = 0
        b.opened_at = self.clock()
        self.trips += 1
        self._set_state(key, b, BreakerState.OPEN)


class TenantBulkhead:
    """Bounded in-flight speculation slots per tenant (thread-safe)."""

    def __init__(self, limit: int) -> None:
        if limit < 1:
            raise ValueError("bulkhead limit must be >= 1")
        self.limit = limit
        self._in_flight: dict[Optional[str], int] = {}
        self._lock = threading.Lock()

    def try_acquire(self, tenant: Optional[str]) -> bool:
        with self._lock:
            n = self._in_flight.get(tenant, 0)
            if n >= self.limit:
                return False
            self._in_flight[tenant] = n + 1
            return True

    def release(self, tenant: Optional[str]) -> None:
        with self._lock:
            n = self._in_flight.get(tenant, 0)
            if n <= 0:
                raise RuntimeError(f"release without acquire: {tenant!r}")
            self._in_flight[tenant] = n - 1

    def in_flight(self, tenant: Optional[str]) -> int:
        with self._lock:
            return self._in_flight.get(tenant, 0)


@dataclasses.dataclass(frozen=True)
class DecisionRequest:
    """One decision ask: which (tenant, edge) row, plus the D4 inputs."""

    row: int
    tenant: Optional[str]
    edge: tuple[str, str]
    alpha: float
    lambda_usd_per_s: float
    latency_s: float
    input_tokens: float
    output_tokens: float
    input_price: float
    output_price: float

    @property
    def key(self) -> tuple[Optional[str], tuple[str, str]]:
        return (self.tenant, self.edge)

    @property
    def L_value_usd(self) -> float:
        return self.latency_s * self.lambda_usd_per_s

    @property
    def C_spec_usd(self) -> float:
        return (self.input_tokens * self.input_price
                + self.output_tokens * self.output_price)


@dataclasses.dataclass(frozen=True)
class FrontendResult:
    """The answer a ticket resolves to.  ``source`` names the chain stage
    that produced it: "service" | "scalar" | "conservative" | "shed"."""

    decision: Decision
    source: str
    EV_usd: float = 0.0
    threshold_usd: float = 0.0
    C_spec_usd: float = 0.0
    L_value_usd: float = 0.0
    P_used: float = 0.0

    @property
    def speculate(self) -> bool:
        return self.decision is Decision.SPECULATE

    @property
    def margin_usd(self) -> float:
        return self.EV_usd - self.threshold_usd


class FrontendTicket:
    """Handle for one submitted request.  ``result()`` blocks the *caller
    that wants the answer*; submission itself never blocks.  A SPECULATE
    answer holds the tenant's bulkhead slot until :meth:`settle`."""

    def __init__(self, frontend: "ServingFrontend",
                 request: DecisionRequest) -> None:
        self.request = request
        self._frontend = frontend
        self._event = threading.Event()
        self._result: Optional[FrontendResult] = None
        self.t_submit = frontend._clock()
        self.t_resolve: Optional[float] = None
        self._holds_slot = False
        self._settled = False

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> FrontendResult:
        if not self._event.wait(timeout):
            raise SpeculationTimeout("ticket unresolved within timeout")
        assert self._result is not None
        return self._result

    @property
    def latency_s(self) -> float:
        if self.t_resolve is None:
            raise RuntimeError("ticket not resolved yet")
        return self.t_resolve - self.t_submit

    def settle(self, success: bool) -> None:
        """Report the launched speculation's outcome: releases the
        bulkhead slot and queues the Bernoulli observation for the
        service's next tick."""
        if self._settled:
            raise RuntimeError("ticket already settled")
        self._settled = True
        if self._holds_slot:
            self._frontend._bulkhead.release(self.request.tenant)
            self._holds_slot = False
        self._frontend._observe(self.request.row, success)

    def release(self) -> None:
        """Give back the bulkhead slot without an observation (the caller
        decided not to launch despite a SPECULATE answer)."""
        if self._holds_slot:
            self._frontend._bulkhead.release(self.request.tenant)
            self._holds_slot = False

    # internal
    def _resolve(self, result: FrontendResult) -> None:
        self._result = result
        self.t_resolve = self._frontend._clock()
        self._event.set()


_CONSERVATIVE = FrontendResult(decision=Decision.WAIT, source="conservative")

# longest tick-exception message kept in an event's ``detail``
_DETAIL_CHARS = 500


class ServingFrontend:
    """Request-accumulation layer in front of an ``OnlineDecisionService``.

    Construct with ``autostart=True`` (default) to run the batcher
    thread, or ``autostart=False`` and drive :meth:`pump` manually — the
    deterministic mode the fault-matrix tests and benchmarks use.  The
    ``service`` may be wrapped (e.g. ``faults.FaultyService``); only the
    ``tick_packed`` / ``posterior_snapshot`` / ``row_gamma`` /
    ``use_lower_bound`` / ``observe`` / ``row_key`` surface is touched
    (plus the optional ``rows_snapshot`` lazy mirror-miss read, skipped
    when the wrapper does not expose it).
    """

    def __init__(
        self,
        service,
        config: FrontendConfig = FrontendConfig(),
        *,
        clock: Callable[[], float] = time.monotonic,
        resilience_log: Optional[ResilienceLog] = None,
        autostart: bool = True,
    ) -> None:
        self.service = service
        self.config = config
        self._clock = clock
        # identity check, not truthiness: an injected-but-still-empty log
        # is falsy (``__len__``) and ``or`` would silently replace it
        self.resilience = (resilience_log if resilience_log is not None
                           else ResilienceLog())
        self._bulkhead = TenantBulkhead(config.bulkhead_limit)
        self.breaker = CircuitBreaker(
            failure_threshold=config.breaker_failure_threshold,
            cooldown_s=config.breaker_cooldown_s,
            half_open_probes=config.breaker_half_open_probes,
            clock=clock,
            on_transition=self._on_breaker_transition,
        )
        self._cv = threading.Condition()
        self._pending: list[FrontendTicket] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._ticks = 0
        self._ticks_since_snapshot = 0
        self._breached: set[int] = set()
        self._settles: list[tuple[int, bool]] = []
        self._settle_lock = threading.Lock()
        # the scalar-fallback posterior mirror: last-known (n, 2) copy of
        # the service's composed store snapshot (device-resident, shelf
        # -spilled and unborn rows alike), refreshed while the service is
        # healthy.  Degraded-mode decisions run the scalar rule over this
        # mirror — stale beliefs, exact arithmetic.  Rows registered
        # after the last refresh fall through to a lazy per-row
        # ``rows_snapshot`` read (see _mirror_row).
        self._snapshot = np.asarray(service.posterior_snapshot(), np.float64)
        # "<Type>: <message>" of the newest tick exception (None while
        # every tick has succeeded); the same text is each fault event's
        # ``detail``
        self.last_tick_error: Optional[str] = None
        self.stats = {
            "submitted": 0, "service": 0, "scalar": 0, "conservative": 0,
            "shed": 0, "tick_faults": 0, "deadline_ticks": 0,
            "full_ticks": 0,
        }
        if autostart:
            self.start()

    # ------------------------------------------------------------- lifecycle
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="frontend-batcher", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        """Flush what's pending and join the batcher thread."""
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self.pump()                   # drain anything that raced the stop

    def __enter__(self) -> "ServingFrontend":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()

    # ------------------------------------------------------------ submission
    def submit(self, request: DecisionRequest) -> FrontendTicket:
        """Non-blocking admission: shed (bulkhead/queue bound) and
        breaker-open requests resolve immediately; everything else joins
        the current accumulation window."""
        ticket = FrontendTicket(self, request)
        self.stats["submitted"] += 1

        # -- bulkhead admission: a tenant at its in-flight limit is shed
        if not self._bulkhead.try_acquire(request.tenant):
            self._shed(ticket, "bulkhead at limit")
            return ticket
        ticket._holds_slot = True

        # -- breaker: an open circuit skips the service entirely and
        # degrades straight to the scalar stage of the chain
        if not self.breaker.allow(request.key):
            self._emit(request, "fallback_scalar", request.C_spec_usd,
                       detail="breaker open")
            self._resolve_fallback(ticket)
            return ticket

        with self._cv:
            if len(self._pending) < self.config.max_queue:
                self._pending.append(ticket)
                if len(self._pending) >= self.config.max_batch:
                    self._cv.notify_all()
                else:
                    self._cv.notify()
                return ticket
        # admission control: a full queue sheds rather than grows
        self._shed(ticket, "queue at limit")
        return ticket

    def submit_edge(self, edge: tuple[str, str], *, tenant: Optional[str]
                    = None, **params: float) -> FrontendTicket:
        """Convenience: look up the (tenant, edge) row and submit."""
        row = self.service.row_index(edge, tenant)
        return self.submit(DecisionRequest(
            row=row, tenant=tenant, edge=tuple(edge), **params))

    # ------------------------------------------------------------- the chain
    def _shed(self, ticket: FrontendTicket, detail: str) -> None:
        req = ticket.request
        ticket.release()
        self.stats["shed"] += 1
        # USD attribution: shedding forgoes the latency value at stake
        self._emit(req, "shed", req.L_value_usd, detail=detail)
        ticket._resolve(dataclasses.replace(_CONSERVATIVE, source="shed"))

    def _resolve_fallback(self, ticket: FrontendTicket) -> None:
        """Stages 2 and 3 of the chain: host scalar rule, then terminal
        conservative WAIT."""
        req = ticket.request
        try:
            res = self._scalar_decide(req)
        except Exception:
            self.stats["conservative"] += 1
            self._emit(req, "fallback_conservative", req.C_spec_usd)
            ticket.release()
            ticket._resolve(_CONSERVATIVE)
            return
        self.stats["scalar"] += 1
        if res.decision is not Decision.SPECULATE:
            ticket.release()
        ticket._resolve(res)

    def _mirror_row(self, row: int) -> np.ndarray:
        """The mirror's alpha/beta for one row, falling back to a lazy
        store read for rows registered after the last refresh (the mirror
        is a point-in-time copy; a paged store still answers any logical
        row without residency changes)."""
        if row < self._snapshot.shape[0]:
            return self._snapshot[row]
        rows_snapshot = getattr(self.service, "rows_snapshot", None)
        if rows_snapshot is None:
            return self._snapshot[row]      # historical IndexError contract
        return np.asarray(rows_snapshot([row]), np.float64)[0]

    def _scalar_decide(self, req: DecisionRequest) -> FrontendResult:
        """The paper-faithful scalar D4 gate over the posterior mirror —
        bitwise-f64 ``decision.evaluate`` by construction."""
        a, b = self._mirror_row(req.row)
        post = BetaPosterior(alpha=float(a), beta=float(b))
        use_lb = bool(getattr(self.service, "use_lower_bound", False))
        res = evaluate(DecisionInputs(
            P=post.mean,
            alpha=req.alpha,
            lambda_usd_per_s=req.lambda_usd_per_s,
            latency_seconds=req.latency_s,
            input_tokens=req.input_tokens,
            output_tokens=req.output_tokens,
            input_price=req.input_price,
            output_price=req.output_price,
            P_lower_bound=(post.lower_bound(self.service.row_gamma(req.row))
                           if use_lb else None),
        ), use_lower_bound=use_lb)
        return FrontendResult(
            decision=res.decision, source="scalar", EV_usd=res.EV_usd,
            threshold_usd=res.threshold_usd, C_spec_usd=res.C_spec_usd,
            L_value_usd=res.L_value_usd, P_used=res.P_used)

    # -------------------------------------------------------------- batching
    def _loop(self) -> None:
        cfg = self.config
        while not self._stop.is_set():
            with TraceAnnotation("frontend.wait_batch"), self._cv:
                while not self._pending and not self._stop.is_set():
                    self._cv.wait(timeout=0.1)
                if self._stop.is_set() and not self._pending:
                    return
                t_first = self._pending[0].t_submit
                while (len(self._pending) < cfg.max_batch
                       and not self._stop.is_set()):
                    remaining = cfg.deadline_s - (self._clock() - t_first)
                    if remaining <= 0.0:
                        break
                    self._cv.wait(timeout=remaining)
            self.pump()

    def pump(self, max_batch: Optional[int] = None) -> int:
        """Form one batch from the pending queue and tick it through the
        chain synchronously.  Returns the number of requests answered.
        This is the single flush path — the batcher thread calls it on
        batch-full/deadline; tests and benchmarks call it directly."""
        with self._cv:
            if not self._pending:
                return 0
            n = min(len(self._pending),
                    max_batch if max_batch is not None else
                    self.config.max_batch)
            batch, self._pending = self._pending[:n], self._pending[n:]
        if len(batch) >= self.config.max_batch:
            self.stats["full_ticks"] += 1
        else:
            self.stats["deadline_ticks"] += 1
        with TraceAnnotation("frontend.flush"):
            self._flush(batch)
        return len(batch)

    def _pack(self, batch: Sequence[FrontendTicket]):
        # pad to max_batch (not the nearest power of two): partial
        # deadline batches then share ONE tick executable with full
        # batches instead of compiling per bucket — under open-loop load
        # a mid-run XLA compile stalls the batcher and cascades into
        # sheds, so shape stability beats the padded FLOPs
        B = len(batch)
        Bp = max(self.config.max_batch, 1 << max(0, (B - 1).bit_length()))
        dtype = getattr(self.service, "_np_dtype", np.dtype(np.float64))
        row = np.full(Bp, -1, np.int32)
        reqs = np.zeros((Bp, 7), dtype)
        for i, t in enumerate(batch):
            r = t.request
            row[i] = r.row
            reqs[i] = (r.alpha, r.lambda_usd_per_s, r.latency_s,
                       r.input_tokens, r.output_tokens, r.input_price,
                       r.output_price)
        return row, reqs, B

    def _pack_settles(self, dtype):
        """Pop queued outcomes into a fixed-shape (Sp,) block — same
        shape-stability argument as :meth:`_pack`."""
        with self._settle_lock:
            if not self._settles:
                return None, None, []
            settles, self._settles = self._settles, []
        n = len(settles)
        Sp = max(self.config.max_batch, 1 << max(0, (n - 1).bit_length()))
        out_row = np.full(Sp, -1, np.int32)
        out_x = np.zeros(Sp, dtype)
        for i, (r, s) in enumerate(settles):
            out_row[i], out_x[i] = r, float(s)
        return out_row, out_x, settles

    def _flush(self, batch: Sequence[FrontendTicket]) -> None:
        cfg = self.config
        with TraceAnnotation("frontend.pack"):
            row, reqs, B = self._pack(batch)
            out_row, out_x, settles = self._pack_settles(reqs.dtype)
        tick = lambda: self.service.tick_packed(  # noqa: E731
            row, reqs, batch=B, out_row=out_row, out_x=out_x,
            check_drift=cfg.check_drift)
        fault_kind: Optional[str] = None
        fault_detail = ""
        decisions = None
        try:
            if cfg.tick_timeout_s is not None:
                decisions = call_with_timeout(tick, cfg.tick_timeout_s)
            else:
                decisions = tick()
        except SpeculationTimeout:
            fault_kind = "timeout"
        except Exception as e:
            # the chain still answers every request, but why the tick
            # failed (a compile error, a device fault) is kept, not lost
            fault_kind = "exception"
            fault_detail = f"{type(e).__name__}: {e}"[:_DETAIL_CHARS]
            self.last_tick_error = fault_detail

        self._ticks += 1
        if decisions is None:
            # tick-level fault: the unsettled outcomes go back on the
            # queue (applied by the next healthy tick), every key
            # involved records one failure, every request degrades down
            # the chain
            if settles:
                with self._settle_lock:
                    self._settles[:0] = settles
            self.stats["tick_faults"] += 1
            keys = {t.request.key for t in batch}
            for t in batch:
                self._emit(t.request, fault_kind, t.request.C_spec_usd,
                           detail=fault_detail)
            for key in keys:
                self.breaker.record_failure(key)
            for t in batch:
                self._emit(t.request, "fallback_scalar",
                           t.request.C_spec_usd, detail=f"tick {fault_kind}")
                self._resolve_fallback(t)
            return

        # healthy tick: distribute answers, close half-open circuits
        with TraceAnnotation("frontend.answer"):
            for key in {t.request.key for t in batch}:
                self.breaker.record_success(key)
            # in-graph kill-switch breaches fold into the breaker as
            # trips (once per breach onset, not re-tripped every tick
            # while down).  ``drift_triggered`` is a pulse — the in-graph
            # run resets after firing — so the breached set must
            # accumulate across ticks, and a row only leaves it once it is
            # observed serving enabled again (host re-enable / rollout
            # re-entry).  That way a *second* breach after a recovery
            # re-emits a fresh trip instead of being swallowed as a
            # duplicate.
            tripped = {int(r) for r in
                       np.flatnonzero(decisions.drift_triggered)}
            if self._breached:
                en = decisions.enabled
                for i in range(B):
                    r = int(row[i])
                    if (r >= 0 and r not in tripped and bool(en[i])
                            and r in self._breached):
                        self._breached.discard(r)
            for r in sorted(tripped - self._breached):
                tenant, edge = self.service.row_key(r)
                self.breaker.trip((tenant, edge))
                self._emit_raw(tenant, edge, r, "drift_trip", 0.0,
                               detail="kill-switch breach")
            self._breached |= tripped
            spec = decisions.speculate
            for i, t in enumerate(batch):
                self.stats["service"] += 1
                res = FrontendResult(
                    decision=(Decision.SPECULATE if bool(spec[i])
                              else Decision.WAIT),
                    source="service",
                    EV_usd=float(decisions.EV_usd[i]),
                    threshold_usd=float(decisions.threshold_usd[i]),
                    C_spec_usd=float(decisions.C_spec_usd[i]),
                    L_value_usd=float(decisions.L_value_usd[i]),
                    P_used=float(decisions.P_used[i]),
                )
                if res.decision is not Decision.SPECULATE:
                    t.release()
                t._resolve(res)
        self._ticks_since_snapshot += 1
        if self._ticks_since_snapshot >= cfg.snapshot_refresh_ticks:
            with TraceAnnotation("frontend.refresh_mirror"):
                self._refresh_snapshot()

    def _refresh_snapshot(self) -> None:
        try:
            self._snapshot = np.asarray(
                self.service.posterior_snapshot(), np.float64)
            self._ticks_since_snapshot = 0
        except Exception:
            # a failing service keeps the stale mirror — that is the point
            pass

    # ------------------------------------------------------------- telemetry
    def _on_breaker_transition(self, key: Any, state: BreakerState) -> None:
        tenant, edge = key
        kind = {
            BreakerState.OPEN: "breaker_open",
            BreakerState.HALF_OPEN: "breaker_half_open",
            BreakerState.CLOSED: "breaker_close",
        }[state]
        self._emit_raw(tenant, edge, None, kind, 0.0)

    def _emit(self, req: DecisionRequest, kind: str, usd: float,
              detail: str = "") -> None:
        self._emit_raw(req.tenant, req.edge, req.row, kind, usd, detail)

    def _emit_raw(self, tenant, edge, row, kind: str, usd: float,
                  detail: str = "") -> None:
        self.resilience.emit(ResilienceEvent(
            kind=kind, tenant=tenant, edge=edge, row=row, usd=usd,
            detail=detail))
        if self.config.ring_events:
            try:
                self.service.log_events([(row, kind, usd)])
            except Exception:
                pass              # the host log stays authoritative

    def _observe(self, row: int, success: bool) -> None:
        # settles queue frontend-side (not service.observe) so the flush
        # can hand them to the tick as one fixed-shape packed block
        if not (0 <= int(row) < self.service.n_rows):
            raise IndexError("outcome row out of range")
        with self._settle_lock:
            self._settles.append((int(row), bool(success)))

    # --------------------------------------------------------------- queries
    def in_flight(self, tenant: Optional[str]) -> int:
        return self._bulkhead.in_flight(tenant)

    @property
    def ticks(self) -> int:
        return self._ticks

    @property
    def pending_count(self) -> int:
        with self._cv:
            return len(self._pending)

    @property
    def oldest_pending_t(self) -> Optional[float]:
        """Submit time of the oldest queued request (deadline anchor)."""
        with self._cv:
            return self._pending[0].t_submit if self._pending else None
