"""Jit'd online decision service — the serving-side twin of the fleet
replay engine.

Four PRs vectorized the *offline* paths (fleet replay, multi-tenant and
episode sharding, §12.1 grids); this module is the first end-to-end jit'd
*request* path.  Posterior and drift state live on device as structure-of-
arrays tables instead of per-edge Python objects:

* the ``(N, 2)`` alpha/beta posterior table, per-row config (§7.5 gammas,
  §14.3 discounts, trigger-2 credible floors) and kill-switch flags are
  owned by :class:`repro.core.store.PosteriorStore` — the shared
  (tenant, edge) registry with free-list eviction, power-of-two capacity,
  LRU spill of cold rows to a host shelf and empirical-Bayes bucket
  hyperpriors.  The service holds a store (dense auto-grow by default;
  pass ``resident_rows=`` for the paged fixed-shape mode) and translates
  logical row ids to device slots per tick,
* drift bookkeeping (consecutive-breach run lengths, enable bits) rides
  in the store's flags table and spills/faults with the row,
* a fixed-size per-decision telemetry ring buffer (USD rows, flushed per
  tick — D2 without a host sync per decision) stays service-owned.

One double-buffered ``tick(requests) -> (decisions, state')`` call
(donation of the state buffers is opt-in, the same policy as
``multi_tenant_replay``) batches B concurrent decision requests: the D4
expected-value gate
(the :func:`repro.core.batch_decision.d4_gate` core, contraction-pinned so
EV / threshold / margin are **bitwise-f64 equal** to the scalar
``decision.evaluate``), the optional §7.5 lower bound via one vmapped
``betaincinv``, posterior updates from the tick's settled outcomes (the
exact discount recurrence of ``BetaPosterior.update``), and in-graph
kill-switch checks with ``DriftMonitor.check_credible_bound_batch``
semantics.  The row axis shards over the 1-D "fleet" mesh via
``sharding.rules.fleet_axis_spec`` with the established unsharded
fallback.

The §12.2–12.4 calibration stages fold onto the same table:
:func:`shadow_mode_batch`, :func:`canary_batch` and
:func:`online_calibration_batch` run a whole fleet's calibration round as
array ops over a posterior snapshot instead of per-record Python, with
results that match the scalar ``calibration.shadow_mode`` / ``canary`` /
``online_calibration`` bitwise at f64 (posteriors, implied lambdas) and
exactly (promotion / trigger flags).
"""
from __future__ import annotations

import dataclasses
import functools
import statistics
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.scipy.special import betainc

from .batch_decision import _f, beam_gate, d4_gate
from .betainc import betaincinv
from .calibration import (
    CanaryReport,
    OnlineReport,
    ShadowReport,
    TokenEstimator,
    _calibration_bucket,
    _canary_sweep_eval,
    _stability_converged,
    _tier2_threshold_sweep,
)
from .decision import Decision, DecisionResult
from .posterior import BetaPosterior
from .rollout import rollout_advance, rollout_allow
from .store import PosteriorStore, _RowConfig
from .success import TierPolicy, check_success
from .taxonomy import DEFAULT_N0, DependencyType
from .telemetry import RESILIENCE_KINDS, bucket_key

__all__ = [
    "OnlineDecisionService",
    "ServiceState",
    "TickDecisions",
    "TelemetryBatch",
    "TELEMETRY_FIELDS",
    "shadow_mode_batch",
    "canary_batch",
    "online_calibration_batch",
]

# Per-decision USD telemetry row layout (Appendix C distilled to the D2
# essentials): every served decision logged in dollars, one ring slot each.
TELEMETRY_FIELDS = (
    "row", "speculate", "P_used", "P_mean", "EV_usd", "threshold_usd",
    "margin_usd", "C_spec_usd", "L_value_usd", "launched",
)


_COL = {name: i for i, name in enumerate(TELEMETRY_FIELDS)}

# Resilience events share the ring with decision rows.  The "row" column
# is the discriminator: >= 0 is a decision, -1 an empty/padding slot, and
# <= -2 a resilience event for table row (-v - 3), with -2 meaning "no
# specific row".  Event rows reuse the "speculate" column for the kind
# code (1-based index into telemetry.RESILIENCE_KINDS) and the
# "C_spec_usd" column for the event's attributed USD.
_EVENT_CODE = {k: i + 1 for i, k in enumerate(RESILIENCE_KINDS)}
_EVENT_KIND = {i + 1: k for i, k in enumerate(RESILIENCE_KINDS)}


def _encode_event_row(row: Optional[int]) -> float:
    return -2.0 if row is None else float(-3 - int(row))


def _decode_event_row(v: float) -> Optional[int]:
    return None if v == -2.0 else int(-v) - 3


class ServiceState(NamedTuple):
    """Device-resident service state (a pytree of five packed arrays —
    few, large leaves keep per-tick dispatch overhead low on CPU)."""

    post: jax.Array      # (N, 2) posterior alpha/beta rows
    rowcfg: jax.Array    # (N, 3) per-row [gamma, discount, trigger-2 floor]
    flags: jax.Array     # (N, 2) int32 [enabled, breach_run]
    roll: jax.Array      # (N, 6) int32 rollout lifecycle columns
    tel: jax.Array       # (R, F) telemetry ring (last R slots, oldest first)


def _breached(a, b, gamma, floor, touched):
    """Trigger 2's breach test, ``betaincinv(a, b, gamma) < floor``, on
    the touched lanes.  ``I_x(a, b)`` increases strictly in ``x`` on
    (0, 1), so the quantile lies under the floor exactly when the CDF at
    the floor exceeds ``gamma``: one ``betainc`` instead of an inversion.
    Clipping the floor to [0, 1] keeps the edges: a floor <= 0 (or an
    unfloored row's -inf) never breaches, one >= 1 always does.

    Untouched lanes are pinned to an inert Beta(1, 1) at 0.5, as the
    §7.5 gate pins its padding: an empty slot's zeros (NaN in
    ``betainc``) or a slow lane cannot keep the all-lanes continued
    fraction iterating, which would move the touched lanes' bits."""
    one = jnp.ones_like(a)
    x = jnp.clip(floor, 0.0, 1.0)
    cdf = betainc(jnp.where(touched, a, one), jnp.where(touched, b, one),
                  jnp.where(touched, x, 0.5 * one))
    return touched & (cdf > gamma)


def _tick_impl(state, zero, row, logrow, reqs, bconf, bwidth, out_row,
               out_x, consecutive_n, rollcfg, use_lower_bound, check_drift,
               use_rollout, use_beam):
    """One service tick, entirely in-graph.

    ``row`` / ``out_row`` use -1 as the padding sentinel (shape buckets)
    and index the *physical* table; ``logrow`` carries the corresponding
    logical row ids for the telemetry rows (identical to ``row`` in the
    store's dense identity mode — the paged mode passes the pre-translate
    ids so drained telemetry reports stable logical rows).  ``reqs``
    packs the per-request floats as columns
    [alpha, lambda, latency_s, in_tok, out_tok, in_price, out_price].

    ``use_beam`` swaps the gate for the top-k beam rule
    (``batch_decision.beam_gate``): ``bconf`` (Bp, W) carries per-request
    candidate confidences and ``bwidth`` (Bp,) the beam width caps; the
    telemetry "P_used" column then reports the beam-cumulative commit
    probability the gate ran on, and "launched" the candidates launched
    (``w_eff`` on served rows).  Non-beam ticks pass fixed zero-size
    placeholders (never traced into the graph — the decision section of
    the default executable is exactly the pre-beam one) and log
    ``launched`` = served.

    Order (documented contract, mirrored by the parity tests):

      1. settle outcomes — sequential discount recurrence over the tick's
         settled (row, success) pairs, exactly ``BetaPosterior.update``
         applied in arrival order (same-row outcomes compose correctly);
      2. answer decisions against the settled table — D4 gate via the
         contraction-pinned :func:`batch_decision.d4_gate`, optionally on
         the §7.5 lower bound (one vmapped ``betaincinv``);
      3. drift/kill-switch — one ``check_credible_bound_batch``-semantics
         breach step per *touched* row (post-settlement posteriors),
         tested as the Beta CDF at the floor (:func:`_breached`) rather
         than by inverting the quantile;
      3b. rollout lifecycle (``use_rollout``) — the staged-rollout state
         machine advances on the same touched mask: serving is gated by
         the *pre-tick* phase (``rollout.rollout_allow``), demotion by
         this tick's kill-switch triggers, promotion by the accumulated
         outcome evidence (``rollout.rollout_advance``; ``rollcfg`` is
         the encoded RolloutConfig vector — dynamic, never a recompile);
      4. telemetry — the tick's decision rows (which double as the
         returned decisions) appended to the ring, oldest slots evicted.
    """
    post, rowcfg, flags, roll, tel = state

    # ---- 1. settle this tick's outcomes (exact discount recurrence).
    # ``a*d + zero`` pins round(a*d) (or is the identity fma), so the
    # update is bitwise the scalar two-step ``a *= d; a += x``.
    def step(p, o):
        r, x = o
        ri = jnp.maximum(r, 0)
        a, b = p[ri, 0], p[ri, 1]
        d = rowcfg[ri, 1]
        a2 = (a * d + zero) + x
        b2 = (b * d + zero) + (1.0 - x)
        new = jnp.where(r >= 0, jnp.stack([a2, b2]), jnp.stack([a, b]))
        return p.at[ri].set(new), None

    if out_row.shape[0]:          # static: the S=0 executable has no scan
        post, _ = jax.lax.scan(step, post, (out_row, out_x))

    # ---- 2. batched D4 decisions against the settled table
    valid = row >= 0
    ri = jnp.maximum(row, 0)
    g = post[ri]
    P_mean = g[:, 0] / (g[:, 0] + g[:, 1])
    if use_lower_bound:
        # padding lanes gather whatever sits in slot 0; pin them to an
        # inert Beta(1, 1) so they cannot change how many iterations the
        # vectorized betainc runs, which would move the real lanes' bits
        one = jnp.ones_like(P_mean)
        P_used = betaincinv(jnp.where(valid, g[:, 0], one),
                            jnp.where(valid, g[:, 1], one),
                            jnp.where(valid, rowcfg[ri, 0], 0.5 * one))
    else:
        P_used = P_mean
    if use_beam:
        EV, thr, flag, C_spec, L_value, w_eff, p_cum = beam_gate(
            P_used, bconf, bwidth, reqs[:, 0], reqs[:, 1], reqs[:, 2],
            reqs[:, 3], reqs[:, 4], reqs[:, 5], reqs[:, 6], zero)
        # the telemetry P_used column reports what the gate ran on — for
        # a beam that is the cumulative commit probability
        P_used = p_cum
        w_eff_f = w_eff.astype(post.dtype)
    else:
        EV, thr, flag, C_spec, L_value = d4_gate(
            P_used, reqs[:, 0], reqs[:, 1], reqs[:, 2], reqs[:, 3],
            reqs[:, 4], reqs[:, 5], reqs[:, 6], zero)
    enabled_req = flags[ri, 0] > 0
    if use_rollout:
        # serving gated by the PRE-tick lifecycle state: SHADOW rows are
        # decided + logged but answer WAIT; CANARY serves its period tick.
        # The lifecycle gate folds into the per-request enabled bit so
        # TickDecisions.speculate (and the frontend reading it) agrees
        # with the telemetry "speculate" column.
        enabled_req = enabled_req & rollout_allow(roll, rollcfg)[ri]
    served = flag & enabled_req

    # ---- 3. drift / kill-switch (trigger 2 semantics, per touched row)
    n_rows = post.shape[0]
    if check_drift or use_rollout:
        touched = jnp.zeros(n_rows, jnp.int32).at[ri].add(
            valid.astype(jnp.int32)) > 0
    if check_drift:
        run = flags[:, 1]
        breached = _breached(post[:, 0], post[:, 1], rowcfg[:, 0],
                             rowcfg[:, 2], touched)
        run = jnp.where(touched, jnp.where(breached, run + 1, 0), run)
        triggered = touched & (run >= consecutive_n)
        enabled = (flags[:, 0] > 0) & ~triggered
        run = jnp.where(triggered, 0, run)
        flags = jnp.stack([enabled.astype(jnp.int32), run], 1)
    else:
        triggered = jnp.zeros(n_rows, bool)

    # ---- 3b. rollout lifecycle advance over the post-drift state
    if use_rollout:
        if out_row.shape[0]:      # static: the S=0 executable skips it
            ovalid = (out_row >= 0).astype(jnp.int32)
            ori = jnp.maximum(out_row, 0)
            n_out = jnp.zeros(n_rows, jnp.int32).at[ori].add(ovalid)
            s_out = jnp.zeros(n_rows, jnp.int32).at[ori].add(
                ovalid * (out_x > 0.5).astype(jnp.int32))
        else:
            n_out = s_out = jnp.zeros(n_rows, jnp.int32)
        # per-row L_value sums: the latency value a demotion walks away
        # from this tick — the USD the transition event is billed
        row_L = jnp.zeros(n_rows, post.dtype).at[ri].add(
            jnp.where(valid, L_value, 0.0))
        roll, flags, transitions = rollout_advance(
            roll, flags, triggered, touched, n_out, s_out, rollcfg)
    else:
        transitions = jnp.zeros(0, jnp.int32)
        row_L = jnp.zeros(0, post.dtype)

    # ---- 4. telemetry: the decision rows ARE the ring rows.  The ring
    # holds the most recent R slots in order (append + evict is two
    # memcpys — far cheaper than a modulo scatter on CPU); sentinel rows
    # (row == -1) are dropped at drain time.
    dt = post.dtype
    served_f = served.astype(dt)
    launched_col = served_f * w_eff_f if use_beam else served_f
    rows_out = jnp.stack([
        logrow.astype(dt), served_f, P_used, P_mean,
        EV, thr, EV - thr, C_spec, L_value, launched_col,
    ], axis=1)
    Bp = rows_out.shape[0]
    R = tel.shape[0]
    if Bp >= R:
        tel = rows_out[Bp - R:]
    else:
        tel = jnp.concatenate([tel[Bp:], rows_out], 0)

    new_state = ServiceState(post=post, rowcfg=rowcfg, flags=flags,
                             roll=roll, tel=tel)
    bools = jnp.stack([flag, enabled_req], 1)
    return new_state, rows_out, bools, triggered, transitions, row_L


# Donation is opt-in (OnlineDecisionService(donate=True)): aliasing the
# state buffers caps memory at two table copies — the double-buffer story
# for HBM-resident million-row tables — but measurably slows CPU dispatch,
# so the default follows multi_tenant_replay(donate=False).
_TICK_STATICS = ("use_lower_bound", "check_drift", "use_rollout",
                 "use_beam")
_tick = functools.partial(jax.jit, static_argnames=_TICK_STATICS)(_tick_impl)
_tick_donated = functools.partial(
    jax.jit, static_argnames=_TICK_STATICS, donate_argnums=(0,))(_tick_impl)


def _fused_tick_impl(state, zero, row, logrow, reqs, out_row, out_x,
                     consecutive_n, use_lower_bound, check_drift, block_n,
                     interpret):
    """The tick's steps 1-3 as ONE Pallas launch over the SoA row axis
    (``repro.kernels.online_tick``: settle + D4 gate + drift fused),
    plus the same step-4 telemetry append as ``_tick_impl``.

    Contract: on the mean path (``use_lower_bound=False``) every output
    — settled posteriors, decisions, drift runs, telemetry rows — is
    bitwise-f64 equal to ``_tick_impl`` (the kernel preserves the traced
    runtime-zero FMA pin and the arrival-order settle recurrence); the
    lower-bound / drift quantile paths sit at the <= 1e-10 betaincinv
    tier because the kernel carries its own betainc evaluator.  Rollout
    and beam ticks are not fused — ``tick_packed`` falls back to
    ``_tick_impl`` for those.
    """
    # trace-time import: keeps repro.core free of any module-level
    # dependency on the kernels package (which imports back into core)
    from ..kernels.online_tick import online_tick_kernel_call

    post, rowcfg, flags, roll, tel = state
    (post, flags, P_used, P_mean, EV, thr, C_spec, L_value,
     flagv, enreqv, trig) = online_tick_kernel_call(
        post, rowcfg, flags, zero, row, reqs, out_row, out_x,
        consecutive_n, use_lower_bound=use_lower_bound,
        check_drift=check_drift, block_n=block_n, interpret=interpret)
    flag = flagv > 0
    enabled_req = enreqv > 0
    served = flag & enabled_req
    triggered = trig > 0

    # ---- step 4 verbatim from _tick_impl (non-beam: launched = served)
    dt = post.dtype
    served_f = served.astype(dt)
    rows_out = jnp.stack([
        logrow.astype(dt), served_f, P_used, P_mean,
        EV, thr, EV - thr, C_spec, L_value, served_f,
    ], axis=1)
    Bp = rows_out.shape[0]
    R = tel.shape[0]
    if Bp >= R:
        tel = rows_out[Bp - R:]
    else:
        tel = jnp.concatenate([tel[Bp:], rows_out], 0)

    new_state = ServiceState(post=post, rowcfg=rowcfg, flags=flags,
                             roll=roll, tel=tel)
    bools = jnp.stack([flag, enabled_req], 1)
    return (new_state, rows_out, bools, triggered,
            jnp.zeros(0, jnp.int32), jnp.zeros(0, dt))


_FUSED_TICK_STATICS = ("use_lower_bound", "check_drift", "block_n",
                       "interpret")
_fused_tick = functools.partial(
    jax.jit, static_argnames=_FUSED_TICK_STATICS)(_fused_tick_impl)
_fused_tick_donated = functools.partial(
    jax.jit, static_argnames=_FUSED_TICK_STATICS,
    donate_argnums=(0,))(_fused_tick_impl)


@jax.jit
def _append_tel(tel, rows):
    """Append pre-encoded rows to the slide-buffer ring (same append +
    evict semantics as the tick's step 4) — the out-of-tick path the
    front-end's resilience events take."""
    E, R = rows.shape[0], tel.shape[0]
    if E >= R:
        return rows[E - R:]
    return jnp.concatenate([tel[E:], rows], 0)


def _bucket(n: int, lo: int = 1) -> int:
    """Power-of-two shape bucket (compile-cache stability across ticks)."""
    if n <= 0:
        return 0
    return max(lo, 1 << (n - 1).bit_length())


class _RowsView:
    """Sequence view over the store registry exposing per-row
    :class:`repro.core.store._RowConfig` records (the pre-store
    ``service._rows`` list surface, preserved for callers)."""

    def __init__(self, store: PosteriorStore) -> None:
        self._store = store

    def __len__(self) -> int:
        return self._store.n_rows

    def __getitem__(self, i: int) -> _RowConfig:
        return self._store.row_config(i)


def _fetch(x) -> np.ndarray:
    """A tick output on the host: blocks until the device has it, then
    copies it."""
    with TraceAnnotation("service.fetch"):
        return np.asarray(x)


@dataclasses.dataclass
class TickDecisions:
    """One tick's batched answers.  The device outputs are pulled to host
    lazily and at most once — reading any field is the tick's single
    host sync (the decision block is the same (B, F) matrix the
    telemetry ring stores)."""

    batch: int
    _rows: Any                # (Bp, F) decision/telemetry block
    _bools: Any               # (Bp, 2) [raw D4 flag, enabled]
    _drift: Any               # (N,) bool, over *physical* slots
    # paged-store ticks: the tick's slot -> logical-id map (None in the
    # dense identity mode, where slot == logical row) and the logical
    # high-water mark, so drift_triggered reads in logical coordinates
    _slot_logical: Any = None
    _n_logical: int = 0
    # rollout ticks: packed per-slot transition codes and per-slot
    # L_value sums (None when the tick ran without the rollout static)
    _transitions: Any = None
    _row_L: Any = None
    _cache: dict = dataclasses.field(default_factory=dict)

    def _col(self, name: str) -> np.ndarray:
        if "rows" not in self._cache:
            self._cache["rows"] = _fetch(self._rows)[: self.batch]
        return self._cache["rows"][:, _COL[name]]

    def _bool(self, j: int) -> np.ndarray:
        if "bools" not in self._cache:
            self._cache["bools"] = _fetch(self._bools)[: self.batch]
        return self._cache["bools"][:, j]

    @property
    def speculate(self) -> np.ndarray:      # D4 flag AND kill-switch
        # identical to the telemetry "speculate" column, but served from
        # the small bool block (the common flags-only flush stays cheap)
        return self._bool(0) & self._bool(1)

    @property
    def flag(self) -> np.ndarray:           # raw D4 flag (parity-pinned)
        return self._bool(0)

    @property
    def enabled(self) -> np.ndarray:
        return self._bool(1)

    @property
    def EV_usd(self) -> np.ndarray:
        return self._col("EV_usd")

    @property
    def threshold_usd(self) -> np.ndarray:
        return self._col("threshold_usd")

    @property
    def margin_usd(self) -> np.ndarray:
        return self._col("margin_usd")

    @property
    def C_spec_usd(self) -> np.ndarray:
        return self._col("C_spec_usd")

    @property
    def L_value_usd(self) -> np.ndarray:
        return self._col("L_value_usd")

    @property
    def P_used(self) -> np.ndarray:
        return self._col("P_used")

    @property
    def P_mean(self) -> np.ndarray:
        return self._col("P_mean")

    @property
    def launched(self) -> np.ndarray:
        """Candidates launched per served decision: ``w_eff`` on beam
        ticks, 0/1 on single-candidate ticks — the per-candidate USD
        attribution column."""
        return self._col("launched")

    @property
    def drift_triggered(self) -> np.ndarray:
        if "drift" not in self._cache:
            mask = _fetch(self._drift)
            if self._slot_logical is not None:
                # paged store: compose the per-slot trip mask back into
                # logical row coordinates (unoccupied slots drop out)
                with TraceAnnotation("service.drift_mask"):
                    out = np.zeros(self._n_logical, bool)
                    sl = self._slot_logical
                    res = sl >= 0
                    out[sl[res]] = mask[: sl.shape[0]][res]
                mask = out
            self._cache["drift"] = mask
        return self._cache["drift"]

    def _logical_vec(self, arr, dtype) -> np.ndarray:
        """Compose a per-physical-slot vector into logical coordinates
        (identity mode truncates; paged mode maps through the tick's
        slot -> logical snapshot)."""
        out = np.zeros(self._n_logical, dtype)
        if arr is None:
            return out
        vec = np.asarray(arr)
        if self._slot_logical is None:
            n = min(vec.shape[0], self._n_logical)
            out[:n] = vec[:n]
            return out
        sl = self._slot_logical
        res = sl >= 0
        out[sl[res]] = vec[: sl.shape[0]][res]
        return out

    @property
    def rollout_transitions(self) -> np.ndarray:
        """(n_logical,) int32 packed lifecycle transition codes
        (``rollout.decode_transition``; 0 = no transition) — zeros when
        the tick ran without the rollout machine."""
        if "trans" not in self._cache:
            self._cache["trans"] = self._logical_vec(
                self._transitions, np.int32)
        return self._cache["trans"]

    @property
    def rollout_usd(self) -> np.ndarray:
        """(n_logical,) summed L_value USD over each row's requests this
        tick — the demotion-billing vector."""
        if "row_L" not in self._cache:
            self._cache["row_L"] = self._logical_vec(self._row_L, np.float64)
        return self._cache["row_L"]


@dataclasses.dataclass(frozen=True)
class TelemetryBatch:
    """Rows drained from the device telemetry ring, oldest first."""

    fields: dict[str, np.ndarray]
    dropped: int                     # rows overwritten before this drain
    # resilience event rows that shared the drained window (see
    # log_events): [{"kind", "row", "usd"}], oldest first
    events: list = dataclasses.field(default_factory=list)
    events_dropped: int = 0

    def __len__(self) -> int:
        return int(next(iter(self.fields.values())).shape[0]) if self.fields else 0

    def rows(self) -> list[dict]:
        n = len(self)
        return [
            {k: (int(v[i]) if k in ("row", "speculate") else float(v[i]))
             for k, v in self.fields.items()}
            for i in range(n)
        ]


class OnlineDecisionService:
    """Device-resident batched decision service over the shared
    :class:`repro.core.store.PosteriorStore` row registry.

    Registration is host-side and O(1) amortized; the first tick (or the
    first one after a registration / dtype change) materializes pending
    rows into the store's device table — one batched scatter, padded to a
    power-of-two row count so registries grow without retracing and
    without per-row host rebuilds.  Passing ``resident_rows=R`` selects
    the store's *paged* mode: the physical table shape is fixed forever
    (zero recompiles under unbounded registry growth) and cold rows spill
    LRU-first to the store's host shelf, faulting back in transparently
    when a tick touches them.  When a ``mesh`` with a ``fleet`` axis
    divides the padded row count, the table's row axis is sharded across
    it (``sharding.rules.fleet_axis_spec``); otherwise the established
    unsharded fallback applies.
    """

    def __init__(
        self,
        *,
        use_lower_bound: bool = False,
        credible_consecutive_n: int = 5,
        telemetry_capacity: int = 4096,
        mesh=None,
        axis_name: str = "fleet",
        min_rows: int = 16,
        donate: bool = False,
        resident_rows: Optional[int] = None,
        store: Optional[PosteriorStore] = None,
        use_fused_tick: bool = False,
        fused_block_n: int = 1024,
    ) -> None:
        if telemetry_capacity < 1:
            raise ValueError("telemetry_capacity must be >= 1")
        self.use_lower_bound = use_lower_bound
        # Pallas fused-tick dispatch (settle + gate + drift in one kernel
        # launch; repro.kernels.online_tick).  Off by default: rollout /
        # beam ticks always take the XLA path, and the mean path is the
        # only fully bitwise tier (see _fused_tick_impl).
        self.use_fused_tick = bool(use_fused_tick)
        self.fused_block_n = int(fused_block_n)
        self.credible_consecutive_n = int(credible_consecutive_n)
        self.telemetry_capacity = int(telemetry_capacity)
        self.mesh = mesh
        self.axis_name = axis_name
        self.min_rows = int(min_rows)
        self.donate = bool(donate)
        self.store = store if store is not None else PosteriorStore(
            resident_rows=resident_rows, min_rows=min_rows, mesh=mesh,
            axis_name=axis_name)
        self._tel = None
        self._state_dtype: Optional[str] = None
        self._pending: list[tuple[int, bool]] = []
        # telemetry totals, tracked host-side in unbounded Python ints
        self._slots_total = 0
        self._rows_total = 0
        self._drained_slots = 0
        self._drained_rows = 0
        self._events_total = 0
        self._drained_events = 0
        # idle ticks (B=0, S=0, no drift check) short-circuit host-side —
        # the deadline-driven batcher hits this path constantly, and even
        # an empty jit'd tick costs ~0.1 ms of dispatch
        self.idle_ticks_skipped = 0
        # all-padding settle buckets downgrade to the S=0 executable at
        # the trace key (the settle scan is a provable no-op on them);
        # counts how often the cheaper executable was substituted
        self.empty_settles_skipped = 0

    # ------------------------------------------------------------- registry
    def register_edge(
        self,
        edge: tuple[str, str],
        *,
        tenant: Optional[str] = None,
        dep_type: Optional[DependencyType] = None,
        k: Optional[int] = None,
        rare_event_p: Optional[float] = None,
        n0: float = DEFAULT_N0,
        posterior: Optional[BetaPosterior] = None,
        gamma: float = 0.1,
        discount: float = 1.0,
        floor_alpha: float = 0.5,
        floor_C_spec_usd: Optional[float] = None,
        floor_L_value_usd: Optional[float] = None,
        bucket: Optional[str] = None,
        pooled: bool = True,
    ) -> int:
        """Add one (tenant, edge) row; returns its stable logical id.

        Delegates to :meth:`PosteriorStore.register`: the prior is
        taxonomy-keyed (``prior_params(dep_type, k=...)``) — or, when the
        store has a fitted empirical-Bayes hyperprior for the row's
        taxonomy ``bucket`` and ``pooled`` is left on, the bucket's
        *learned* prior — unless an explicit ``posterior`` seeds the row
        (§12.1 data-seeded deployment).  ``floor_*`` pin the row's
        trigger-2 credible floor ``(1 - alpha) * C / (L_value + C)`` from
        its canonical decision context; rows without one never breach.
        Host-only and O(1) amortized — the row materializes on device in
        the next tick's batched pending scatter.
        """
        return self.store.register(
            edge, tenant=tenant, dep_type=dep_type, k=k,
            rare_event_p=rare_event_p, n0=n0, posterior=posterior,
            gamma=gamma, discount=discount, floor_alpha=floor_alpha,
            floor_C_spec_usd=floor_C_spec_usd,
            floor_L_value_usd=floor_L_value_usd,
            bucket=bucket, pooled=pooled)

    def evict_edge(self, edge: tuple[str, str],
                   tenant: Optional[str] = None) -> None:
        """Drop a (tenant, edge) row entirely (free-list recycling; any
        attached drift monitor's host state is dropped via the store's
        ``on_evict`` hook)."""
        self.store.evict(edge, tenant)

    def attach_drift_monitor(self, monitor) -> None:
        """Wire a ``DriftMonitor``'s host-side bookkeeping to the store's
        row lifecycle: eviction drops the monitor's per-row state and a
        spilled row faulting back in re-seeds its trigger-1 baseline
        (the device-resident flags stay authoritative for trigger 2)."""
        self.store.on_evict = monitor.evict_state
        self.store.on_fault_in = monitor.reseed_baseline

    def row_index(self, edge: tuple[str, str],
                  tenant: Optional[str] = None) -> int:
        return self.store.row_index(edge, tenant)

    def row_key(self, row: int) -> tuple[Optional[str], tuple[str, str]]:
        return self.store.row_key(row)

    def row_gamma(self, row: int) -> float:
        """The §7.5 gamma the row's lower-bound gate uses."""
        return self.store.row_config(row).gamma

    @property
    def n_rows(self) -> int:
        return self.store.n_rows

    @property
    def _rows(self) -> _RowsView:
        return _RowsView(self.store)

    def fit_hyperpriors(self, **kwargs) -> dict:
        """Run the store's jit'd empirical-Bayes bucket fit over the
        device-resident rows (see :meth:`PosteriorStore.fit_hyperpriors`);
        subsequent registrations in fitted buckets are born pooled."""
        self._ensure_ready()
        return self.store.fit_hyperpriors(**kwargs)

    # ------------------------------------------------------------ state mgmt
    def _ensure_ready(self) -> None:
        """Materialize the store's device tables for the working dtype
        (applying pending registrations in one batched scatter) and the
        service-owned telemetry ring."""
        # config read (~0.2us) instead of jnp.result_type (~5us): the
        # working float dtype only ever changes through jax_enable_x64
        dtype = "float64" if jax.config.jax_enable_x64 else "float32"
        if self.store.n_rows == 0:
            raise ValueError("no edges registered")
        self.store.device_tables(dtype)
        if self._tel is None or self._state_dtype != dtype:
            if self._tel is not None:
                # dtype switch: f64 round-trip is value-exact for the f32
                # case; the f64 -> f32 direction re-rounds, as any dtype
                # change must
                tel = np.asarray(self._tel, np.float64)
            else:
                tel = np.zeros((self.telemetry_capacity,
                                len(TELEMETRY_FIELDS)))
                tel[:, _COL["row"]] = -1.0    # empty slots drop at drain
            if self.store.row_sharding is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                rep = NamedSharding(self.mesh, PartitionSpec())
                self._tel = jax.device_put(_f(tel), rep)
            else:
                self._tel = _f(tel)
            self._state_dtype = dtype
            # per-tick constants, rebuilt only here (hot-path dispatch
            # stays free of dtype machinery)
            self._np_dtype = np.dtype(dtype)
            self._zero = self._np_dtype.type(0.0)
            self._cn = np.int32(self.credible_consecutive_n)
            self._empty_out = (np.full(0, -1, np.int32),
                               np.zeros(0, self._np_dtype))
            # placeholder rollout config operand for non-rollout ticks
            # (one fixed array — never churns the executable's operands)
            self._null_rollcfg = np.ones(9, np.int32)
            # placeholder beam operands for non-beam ticks (zero-size and
            # shape-stable: the use_beam=False executable never reads them)
            self._null_beam = (np.zeros((0, 1), self._np_dtype),
                               np.zeros(0, np.int32))

    def _ensure_state(self) -> ServiceState:
        self._ensure_ready()
        post, rowcfg, flags, roll = self.store.tables()
        return ServiceState(post=post, rowcfg=rowcfg, flags=flags,
                            roll=roll, tel=self._tel)

    @property
    def state(self) -> ServiceState:
        return self._ensure_state()

    # -------------------------------------------------------------- queries
    def posterior_snapshot(self) -> np.ndarray:
        """(n_rows, 2) alpha/beta view composed across the store's tiers
        (device-resident rows, spilled shelf rows, unborn priors)."""
        self._ensure_ready()
        return self.store.snapshot(self._np_dtype)

    def rows_snapshot(self, rows) -> np.ndarray:
        """(k, 2) f64 alpha/beta values for specific logical rows without
        touching residency — the front-end mirror-miss read path."""
        self._ensure_ready()
        return self.store.rows_snapshot(rows, np.float64)

    def posterior(self, row: int) -> BetaPosterior:
        self._ensure_ready()
        a, b = self.store.rows_snapshot([row], self._np_dtype)[0]
        return BetaPosterior.from_row(
            a, b, discount=self.store.row_config(row).discount)

    def set_posterior(self, row: int, alpha: float, beta: float) -> None:
        if alpha <= 0 or beta <= 0:
            raise ValueError("Beta parameters must be positive")
        self._ensure_ready()
        self.store.set_rows(np.asarray([row]),
                            np.asarray([[alpha, beta]], np.float64))

    def enabled_snapshot(self) -> np.ndarray:
        self._ensure_ready()
        return self.store.flags_snapshot()[:, 0] > 0

    def breach_runs(self) -> np.ndarray:
        self._ensure_ready()
        return self.store.flags_snapshot()[:, 1].copy()

    # ---------------------------------------------------------------- ticks
    def observe(self, row: int, success: bool) -> None:
        """Queue a settled outcome; applied (in order) on the next tick."""
        row = int(row)
        # same contract as tick(outcomes=...): a bad (or evicted) row must
        # raise here, not silently scatter onto padding at the next tick
        self.store.check_rows(np.asarray([row]), "outcome")
        self._pending.append((row, bool(success)))

    def tick(
        self,
        rows,
        *,
        alpha,
        lambda_usd_per_s,
        latency_s,
        input_tokens,
        output_tokens,
        input_price,
        output_price,
        outcomes: Optional[Sequence[tuple[int, bool]]] = None,
        use_lower_bound: Optional[bool] = None,
        check_drift: bool = False,
        use_rollout: bool = False,
        rollout_cfg: Optional[np.ndarray] = None,
        beam_confidences=None,
        beam_width=None,
    ) -> TickDecisions:
        """Answer B decision requests in one donated XLA call.

        ``rows`` indexes the table; every other request field broadcasts
        against it.  ``outcomes`` (plus anything queued via
        :meth:`observe`) settle *before* the decisions are answered —
        freshest-belief serving.  ``check_drift`` runs the in-graph
        trigger-2 breach step on every touched row.

        Request shapes bucket to powers of two (padding rows carry the -1
        sentinel), so variable batch sizes share executables.  Host
        arrays are handed to the jit'd call directly in the working dtype
        — per-tick overhead is dispatch-bound, not transfer-bound.

        ``beam_confidences`` (B, W) switches the tick to the top-k beam
        gate (repro.core.beam): each request row carries its candidate
        confidences (sorted non-increasing, summing to <= 1) and
        ``beam_width`` (scalar or per-request) caps launches; the
        telemetry "launched" column then attributes every launched
        candidate in USD-traceable form.
        """
        self._ensure_ready()
        fdtype = self._np_dtype
        rows = np.atleast_1d(np.asarray(rows, np.int32))
        B = int(rows.shape[0])
        self.store.check_rows(rows, "request")
        Bp = _bucket(B)
        req_row = np.full(Bp, -1, np.int32)
        req_row[:B] = rows
        reqs = np.zeros((Bp, 7), fdtype)
        for j, x in enumerate((alpha, lambda_usd_per_s, latency_s,
                               input_tokens, output_tokens, input_price,
                               output_price)):
            reqs[:B, j] = np.asarray(x, fdtype)

        bconf = bwidth = None
        if beam_confidences is not None:
            bc = np.asarray(beam_confidences, fdtype)
            if bc.ndim != 2 or bc.shape[0] != B:
                raise ValueError(
                    f"beam_confidences must be ({B}, W), got {bc.shape}")
            if (bc < 0).any() or (bc > 1).any():
                raise ValueError("candidate confidences must be in [0, 1]")
            if (bc[:, 1:] > bc[:, :-1]).any():
                raise ValueError(
                    "beam_confidences rows must be sorted non-increasing")
            if (bc.sum(1) > 1.0 + 1e-9).any():
                raise ValueError("beam_confidences rows must sum to <= 1")
            if beam_width is None:
                beam_width = bc.shape[1]
            # padding rows: one certain candidate, width 1 (inert — the
            # -1 row sentinel already drops their decisions)
            bconf = np.zeros((Bp, bc.shape[1]), fdtype)
            bconf[:, 0] = 1.0
            bconf[:B] = bc
            bwidth = np.ones(Bp, np.int32)
            bwidth[:B] = np.asarray(beam_width, np.int32)
            if (bwidth < 1).any():
                raise ValueError("beam_width must be >= 1")
        elif beam_width is not None:
            raise ValueError("beam_width requires beam_confidences")

        out_row = out_x = None
        if outcomes is not None:
            outs = [(int(r), bool(s)) for r, s in outcomes]
            if outs:
                self.store.check_rows(
                    np.fromiter((r for r, _ in outs), np.int64, len(outs)),
                    "outcome")
            Sp = _bucket(len(outs), lo=1) if outs else 0
            out_row = np.full(Sp, -1, np.int32)
            out_x = np.zeros(Sp, fdtype)
            for i, (r, s) in enumerate(outs):
                out_row[i], out_x[i] = r, float(s)
        return self.tick_packed(
            req_row, reqs, batch=B, out_row=out_row, out_x=out_x,
            use_lower_bound=use_lower_bound, check_drift=check_drift,
            use_rollout=use_rollout, rollout_cfg=rollout_cfg,
            bconf=bconf, bwidth=bwidth)

    def tick_packed(
        self,
        row: np.ndarray,
        reqs: np.ndarray,
        *,
        batch: Optional[int] = None,
        out_row: Optional[np.ndarray] = None,
        out_x: Optional[np.ndarray] = None,
        use_lower_bound: Optional[bool] = None,
        check_drift: bool = False,
        use_rollout: bool = False,
        rollout_cfg: Optional[np.ndarray] = None,
        bconf: Optional[np.ndarray] = None,
        bwidth: Optional[np.ndarray] = None,
    ) -> TickDecisions:
        """The zero-copy hot path: the caller hands the packed request
        block its batcher accumulated between ticks — ``row`` (Bp,) int32
        with -1 padding sentinels, ``reqs`` (Bp, 7) in the working float
        dtype with columns [alpha, lambda_usd_per_s, latency_s, in_tok,
        out_tok, in_price, out_price] — and the tick dispatches with no
        per-request conversion or validation (out-of-range rows clamp;
        :meth:`tick` is the validating wrapper).  ``out_row``/``out_x``
        are the equivalently packed settled outcomes.  ``bconf`` (Bp, W)
        / ``bwidth`` (Bp,) switch the tick to the beam gate (see
        :meth:`tick`); both pre-packed to the bucket shape."""
        with TraceAnnotation("service.tick"):
            self._ensure_ready()
            if (not check_drift and not self._pending and row.shape[0] == 0
                    and (out_row is None or out_row.shape[0] == 0)):
                # idle tick: nothing to settle, decide or drift-check.  The
                # jit'd tick would be a provable no-op (the S=0 executable
                # already skips its scan at trace time) yet still costs
                # ~0.1ms of dispatch — the deadline batcher fires these
                # constantly, so skip the XLA call entirely.  State and the
                # telemetry ring are bitwise what the dispatched no-op
                # leaves.
                self.idle_ticks_skipped += 1
                F = len(TELEMETRY_FIELDS)
                return TickDecisions(
                    batch=0 if batch is None else batch,
                    _rows=np.zeros((0, F), self._np_dtype),
                    _bools=np.zeros((0, 2), bool),
                    _drift=np.zeros(self.store.capacity, bool),
                    _slot_logical=self.store.logical_map(),
                    _n_logical=self.store.n_rows)
            if self._pending:
                # outcomes queued via observe() settle first (arrival
                # order), ahead of this call's packed outcomes
                pend, self._pending = self._pending, []
                extra_r = np.fromiter((r for r, _ in pend), np.int32,
                                      len(pend))
                extra_x = np.fromiter((float(s) for _, s in pend),
                                      self._np_dtype, len(pend))
                if out_row is None:
                    out_row, out_x = self._empty_out
                out_row = np.concatenate([extra_r, out_row])
                out_x = np.concatenate([extra_x, out_x])
                Sp = _bucket(out_row.shape[0], lo=1)
                if Sp != out_row.shape[0]:
                    pad_r = np.full(Sp, -1, np.int32)
                    pad_r[: out_row.shape[0]] = out_row
                    pad_x = np.zeros(Sp, self._np_dtype)
                    pad_x[: out_x.shape[0]] = out_x
                    out_row, out_x = pad_r, pad_x
            elif out_row is None:
                out_row, out_x = self._empty_out
            if out_row.shape[0] and not (out_row >= 0).any():
                # all-padding settle bucket: the S>0 executable's settle
                # scan would be a provable no-op (every lane masked), but S
                # is part of the trace key, so substituting the S=0 bucket
                # here skips both the scan trace and its per-tick dispatch
                # cost — bitwise the same state (mirrors the
                # idle_ticks_skipped fast path)
                out_row, out_x = self._empty_out
                self.empty_settles_skipped += 1
            if self.store.identity:
                srow, sout = row, out_row
            else:
                # paged store: fault every row this tick touches onto the
                # device (LRU-spilling victims), then run the jit'd tick in
                # slot coordinates — the executable never sees logical ids,
                # so unbounded registry growth never retraces it
                touched = np.concatenate(
                    [row[row >= 0].astype(np.int64),
                     out_row[out_row >= 0].astype(np.int64)])
                with TraceAnnotation("store.page_in"):
                    self.store.ensure_resident(touched)
                    srow = self.store.translate(row)
                    sout = self.store.translate(out_row)
            state = self._ensure_state()
            ulb = (self.use_lower_bound if use_lower_bound is None
                   else bool(use_lower_bound))
            rcfg = (self._null_rollcfg if rollout_cfg is None
                    else np.asarray(rollout_cfg, np.int32))
            use_beam = bconf is not None
            if use_beam:
                if bwidth is None:
                    raise ValueError("bconf requires bwidth")
                if (bconf.shape[0] != row.shape[0]
                        or bwidth.shape[0] != row.shape[0]):
                    raise ValueError(
                        "bconf/bwidth must match the packed batch")
            else:
                bconf, bwidth = self._null_beam
            # fused Pallas tick: only the settle+gate+drift core is fused,
            # so rollout / beam ticks always fall back to the XLA executable
            use_fused = (self.use_fused_tick and not use_rollout
                         and not use_beam)
            if use_fused:
                from ..kernels.ops import _interpret

                fn = _fused_tick_donated if self.donate else _fused_tick
                new_state, rows_out, bools, drift, transitions, row_L = fn(
                    state, self._zero, srow, row, reqs, sout, out_x,
                    self._cn, use_lower_bound=ulb, check_drift=check_drift,
                    block_n=self.fused_block_n, interpret=_interpret(),
                )
            else:
                fn = _tick_donated if self.donate else _tick
                new_state, rows_out, bools, drift, transitions, row_L = fn(
                    state, self._zero, srow, row, reqs, bconf, bwidth, sout,
                    out_x, self._cn, rcfg, use_lower_bound=ulb,
                    check_drift=check_drift, use_rollout=bool(use_rollout),
                    use_beam=use_beam,
                )
            self.store.adopt(new_state.post, new_state.rowcfg,
                             new_state.flags, new_state.roll)
            self._tel = new_state.tel
            n_real = int((row >= 0).sum())
            self._slots_total += int(row.shape[0])
            self._rows_total += n_real
            # sentinels are tail-only by the packing convention, so the
            # real batch defaults to the valid count — never report padding
            # slots as decisions
            return TickDecisions(
                batch=n_real if batch is None else batch,
                _rows=rows_out, _bools=bools, _drift=drift,
                _slot_logical=self.store.logical_map(),
                _n_logical=self.store.n_rows,
                _transitions=transitions if use_rollout else None,
                _row_L=row_L if use_rollout else None)

    def apply_outcomes(
        self, outcomes: Optional[Sequence[tuple[int, bool]]] = None
    ) -> None:
        """Settle outcomes without answering any requests (a B=0 tick)."""
        self.tick(
            np.zeros(0, np.int32), alpha=0.0, lambda_usd_per_s=0.0,
            latency_s=0.0, input_tokens=0, output_tokens=0,
            input_price=0.0, output_price=0.0, outcomes=outcomes,
        )

    def decide(
        self,
        edge: Optional[tuple[str, str]] = None,
        *,
        tenant: Optional[str] = None,
        row: Optional[int] = None,
        posterior: Optional[BetaPosterior] = None,
        alpha: float,
        lambda_usd_per_s: float,
        latency_s: float,
        input_tokens: int,
        output_tokens: float,
        input_price: float,
        output_price: float,
        use_lower_bound: Optional[bool] = None,
    ) -> DecisionResult:
        """Single-request convenience (the ``serving.spec_bridge`` route):
        a B=1 tick returning a scalar ``DecisionResult`` whose floats are
        bitwise-f64 equal to ``decision.evaluate``.  ``posterior=`` syncs
        the row's table params first (the bridge keeps the caller-held
        ``BetaPosterior`` authoritative; a disabled row answers WAIT)."""
        if row is None:
            if edge is None:
                raise ValueError("decide needs edge or row")
            row = self.row_index(edge, tenant)
        if posterior is not None:
            self.set_posterior(row, posterior.alpha, posterior.beta)
        d = self.tick(
            [row], alpha=alpha, lambda_usd_per_s=lambda_usd_per_s,
            latency_s=latency_s, input_tokens=input_tokens,
            output_tokens=output_tokens, input_price=input_price,
            output_price=output_price, use_lower_bound=use_lower_bound,
        )
        return DecisionResult(
            decision=Decision.SPECULATE if bool(d.speculate[0]) else Decision.WAIT,
            EV_usd=float(d.EV_usd[0]),
            threshold_usd=float(d.threshold_usd[0]),
            C_spec_usd=float(d.C_spec_usd[0]),
            L_value_usd=float(d.L_value_usd[0]),
            P_used=float(d.P_used[0]),
        )

    def decide_beam(
        self,
        edge: Optional[tuple[str, str]] = None,
        *,
        tenant: Optional[str] = None,
        row: Optional[int] = None,
        confidences,
        width: int,
        alpha: float,
        lambda_usd_per_s: float,
        latency_s: float,
        input_tokens: int,
        output_tokens: float,
        input_price: float,
        output_price: float,
        use_lower_bound: Optional[bool] = None,
    ):
        """Single-request top-k convenience: a B=1 beam tick returning a
        scalar ``repro.core.beam.BeamDecisionResult`` whose floats are
        bitwise-f64 equal to ``beam_evaluate`` on the row's posterior
        (same contraction-pinned lowering as :meth:`decide`)."""
        from .beam import BeamDecisionResult

        self._ensure_ready()
        if row is None:
            if edge is None:
                raise ValueError("decide_beam needs edge or row")
            row = self.row_index(edge, tenant)
        d = self.tick(
            [row], alpha=alpha, lambda_usd_per_s=lambda_usd_per_s,
            latency_s=latency_s, input_tokens=input_tokens,
            output_tokens=output_tokens, input_price=input_price,
            output_price=output_price, use_lower_bound=use_lower_bound,
            beam_confidences=np.asarray(confidences, self._np_dtype)[None, :],
            beam_width=int(width),
        )
        speculate = bool(d.speculate[0])
        launched = int(d.launched[0])
        return BeamDecisionResult(
            decision=Decision.SPECULATE if speculate else Decision.WAIT,
            EV_usd=float(d.EV_usd[0]),
            threshold_usd=float(d.threshold_usd[0]),
            C_spec_usd=float(d.C_spec_usd[0]),
            L_value_usd=float(d.L_value_usd[0]),
            P_used=float(d.P_used[0]),
            width=int(width),
            w_eff=launched if speculate else 0,
            launched=launched,
        )

    # ------------------------------------------------------------ telemetry
    def log_events(
        self, events: Sequence[tuple[Optional[int], str, float]]
    ) -> None:
        """Append resilience event rows — ``(row_or_None, kind, usd)``
        with ``kind`` from ``telemetry.RESILIENCE_KINDS`` — to the device
        telemetry ring (breaker trips, bulkhead sheds, fallback hops from
        the serving front-end ride the same D2 flush path as decisions).

        Event rows are encoded via the "row" column discriminator (see
        the module-level note) and surface as ``TelemetryBatch.events``
        at drain time; decision fields are unaffected.  The event batch
        shape buckets to a power of two so bursts share executables.
        """
        if not events:
            return
        self._ensure_ready()
        n = len(events)
        Ep = _bucket(n, lo=1)
        rows = np.zeros((Ep, len(TELEMETRY_FIELDS)), self._np_dtype)
        rows[:, _COL["row"]] = -1.0            # padding slots stay empty
        for i, (row, kind, usd) in enumerate(events):
            if row is not None and not (0 <= int(row) < self.n_rows):
                raise IndexError("event row out of range")
            rows[i, _COL["row"]] = _encode_event_row(row)
            rows[i, _COL["speculate"]] = float(_EVENT_CODE[kind])
            rows[i, _COL["C_spec_usd"]] = float(usd)
        self._tel = _append_tel(self._tel, rows)
        self._slots_total += Ep
        self._events_total += n

    def drain_telemetry(self) -> TelemetryBatch:
        """Pull the per-decision USD rows written since the last drain
        (one device sync total — the D2 flush path).  The ring holds the
        most recent ``telemetry_capacity`` *slots* (a ragged tick consumes
        its padded bucket; sentinel slots are filtered here); real rows
        evicted before this drain are counted as ``dropped`` — size the
        ring to the tick cadence.  Resilience event rows sharing the
        window (see :meth:`log_events`) are decoded into ``events``."""
        self._ensure_ready()
        tel = np.asarray(self._tel)
        slots, total_rows = self._slots_total, self._rows_total
        total_events = self._events_total
        R = tel.shape[0]
        new_slots = slots - self._drained_slots
        take = min(new_slots, R)
        window = tel[R - take:] if take else tel[:0]
        valid = window[:, _COL["row"]] >= 0
        new_rows = total_rows - self._drained_rows
        new_events = total_events - self._drained_events
        self._drained_slots = slots
        self._drained_rows = total_rows
        self._drained_events = total_events
        fields = {
            name: window[valid, j].copy()
            for j, name in enumerate(TELEMETRY_FIELDS)
        }
        ev_rows = window[window[:, _COL["row"]] <= -2.0]
        events = [
            {"kind": _EVENT_KIND[int(r[_COL["speculate"]])],
             "row": _decode_event_row(float(r[_COL["row"]])),
             "usd": float(r[_COL["C_spec_usd"]])}
            for r in ev_rows
        ]
        return TelemetryBatch(fields=fields,
                              dropped=new_rows - int(valid.sum()),
                              events=events,
                              events_dropped=new_events - len(events))

    # ----------------------------------------------------------- drift fold
    def drift_rows(self, decisions: TickDecisions) -> list[
            tuple[Optional[str], tuple[str, str]]]:
        """(tenant, edge) labels of rows the tick's drift check tripped."""
        mask = decisions.drift_triggered[: self.n_rows]
        return [self.row_key(i) for i in np.flatnonzero(mask)]


# ---------------------------------------------------------------------------
# §12.2–12.4 folded onto the posterior table: a calibration round as array
# ops over a snapshot instead of per-record Python.
# ---------------------------------------------------------------------------
def _posterior_rows(posteriors, n: int):
    """(a0, b0, discount, s0, f0) arrays from BetaPosterior objects or a
    raw (n, 2) snapshot."""
    if isinstance(posteriors, np.ndarray) or (
            posteriors and not isinstance(posteriors[0], BetaPosterior)):
        rows = np.asarray(posteriors, float).reshape(n, 2)
        return (rows[:, 0].copy(), rows[:, 1].copy(), np.ones(n),
                np.zeros(n, int), np.zeros(n, int))
    a = np.array([p.alpha for p in posteriors], float)
    b = np.array([p.beta for p in posteriors], float)
    d = np.array([p.discount for p in posteriors], float)
    s = np.array([p.successes for p in posteriors], int)
    f = np.array([p.failures for p in posteriors], int)
    return a, b, d, s, f


def shadow_mode_batch(
    edges: Sequence[tuple[str, str]],
    posteriors,
    trials: Sequence[Sequence[tuple[Any, Any]]],
    *,
    discounts=None,
    graded_subsets: Optional[Sequence[Sequence[tuple[Any, Any, bool]]]] = None,
    thresholds: Sequence[float] = (0.80, 0.85, 0.90, 0.95, 0.99),
    output_token_counts: Optional[Sequence[Sequence[float]]] = None,
    cancel_fractions: Optional[Sequence[Sequence[float]]] = None,
    n_shadow: int = 100,
    stability_window: int = 50,
    stability_tol: float = 0.05,
    tenants: Optional[Sequence[Optional[str]]] = None,
) -> list[ShadowReport]:
    """§12.2 shadow mode for a whole fleet of edges in one pass.

    ``posteriors`` is either a list of ``BetaPosterior`` (never mutated —
    the same zero-exposure contract as the scalar stage), a raw ``(R, 2)``
    snapshot of the online service's table (then ``discounts`` supplies
    the per-row forgetting factors), or a :class:`PosteriorStore` / an
    object holding one as ``.store`` — then each edge's alpha/beta and
    discount are read through the store snapshot API (``tenants`` keys
    multi-tenant rows), spilled rows included, without touching
    residency.  Tier checks call the same ``check_success`` per trial as
    the scalar stage; the posterior recurrence, convergence windows and
    token-EMA run as array ops across all R rows at once.  Per-row
    reports match scalar ``shadow_mode`` bitwise at f64 (posteriors,
    means, F1) and exactly (flags).
    """
    R = len(edges)
    if len(trials) != R:
        raise ValueError("trials must align with edges")
    store = getattr(posteriors, "store", posteriors)
    if isinstance(store, PosteriorStore):
        tens = tenants if tenants is not None else [None] * R
        ids = [store.row_index(e, t) for e, t in zip(edges, tens)]
        posteriors = store.rows_snapshot(np.asarray(ids, np.int64))
        discounts = np.array([store.row_config(i).discount for i in ids])
    a, b, d, s0, f0 = _posterior_rows(posteriors, R)
    if discounts is not None:
        d = np.broadcast_to(np.asarray(discounts, float), (R,)).copy()
    policy = TierPolicy()
    T = max((len(t) for t in trials), default=0)
    ok = np.zeros((R, max(T, 1)))
    mask = np.zeros((R, max(T, 1)), bool)
    for r, tr in enumerate(trials):
        for t, (i_actual, i_hat) in enumerate(tr):
            ok[r, t] = float(check_success(i_actual, i_hat, policy).success)
            mask[r, t] = True

    # vectorized discount recurrence (bitwise the scalar two-step update)
    means = np.zeros((R, max(T, 1)))
    for t in range(T):
        mt = mask[:, t]
        x = ok[:, t]
        a2 = a * d + x
        b2 = b * d + (1.0 - x)
        a = np.where(mt, a2, a)
        b = np.where(mt, b2, b)
        means[:, t] = a / (a + b)

    reports = []
    for r, edge in enumerate(edges):
        n_t = len(trials[r])
        row_means = list(means[r, :n_t])
        converged = n_t >= n_shadow and _stability_converged(
            row_means, stability_window, stability_tol)
        graded = graded_subsets[r] if graded_subsets else ()
        best_thr, best_f1 = _tier2_threshold_sweep(graded, thresholds)
        est = TokenEstimator()
        for tok in (output_token_counts[r] if output_token_counts else ()):
            est.observe(tok)
        cancels = cancel_fractions[r] if cancel_fractions else ()
        rho_mean = statistics.fmean(cancels) if cancels else 0.5
        s_new = int(ok[r, :n_t].sum())
        reports.append(ShadowReport(
            edge=tuple(edge),
            trials=n_t,
            posterior=BetaPosterior(
                alpha=float(a[r]), beta=float(b[r]),
                successes=int(s0[r]) + s_new,
                failures=int(f0[r]) + (n_t - s_new),
                discount=float(d[r]),
            ),
            converged=converged,
            best_tier2_threshold=best_thr,
            tier2_f1=max(best_f1, 0.0),
            token_estimator=est,
            rho_mean=rho_mean,
        ))
    return reports


def canary_batch(
    control_latency_s,
    control_cost_usd,
    sweeps: Sequence[dict[float, tuple[float, float]]],
    chosen_alphas,
    P,
    C_spec,
    L_upstream_s,
    lambda_declared,
    *,
    budget_guardrail_usd=None,
    consistency_band: float = 0.5,
) -> list[CanaryReport]:
    """§12.3 canary for R edges in one pass: the implied-lambda recovery
    and audit verdicts vectorize over the fleet (``P`` typically the
    posterior-snapshot means of the online table); the per-arm Pareto /
    promotion logic reuses the scalar code per row.  Reports match scalar
    ``canary`` bitwise at f64 (``lambda_implied``) and exactly (audit
    strings, promote flags, Pareto sets).
    """
    R = len(sweeps)

    def rvec(x):
        return np.broadcast_to(np.asarray(x, float), (R,))

    ctrl_lat, ctrl_cost = rvec(control_latency_s), rvec(control_cost_usd)
    ca, P = rvec(chosen_alphas), rvec(P)
    C, L, lam_dec = rvec(C_spec), rvec(L_upstream_s), rvec(lambda_declared)
    if np.any((P < 0.0) | (P > 1.0)):
        raise ValueError("P must be in [0, 1]")
    if np.any((ca < 0.0) | (ca > 1.0)):
        raise ValueError("alpha must be in [0, 1]")
    if np.any(P <= 0.0) or np.any(L <= 0.0):
        raise ValueError("implied lambda requires P > 0 and L > 0")
    # same expression order as decision.implied_lambda -> bitwise at f64
    lam_imp = ((1.0 - ca) * C + (1.0 - P) * C) / (P * L)
    # divide only where declared > 0 (the scalar guard, warning-free)
    ratio = np.divide(lam_imp, lam_dec, where=lam_dec > 0.0,
                      out=np.full(R, np.inf))
    audit = np.where(
        ratio > 1.0 + consistency_band, "refresh_lambda",
        np.where(ratio < 1.0 - consistency_band, "inspect_declared",
                 "consistent"))

    guard = None if budget_guardrail_usd is None else rvec(budget_guardrail_usd)
    reports = []
    for r in range(R):
        # per-arm logic is the scalar stage's own helper — only the
        # implied-lambda / audit math above is worth vectorizing
        arms, pareto, promote = _canary_sweep_eval(
            sweeps[r], float(ca[r]), float(ctrl_lat[r]), float(ctrl_cost[r]),
            None if guard is None else float(guard[r]))
        reports.append(CanaryReport(
            arms=arms,
            pareto_alphas=pareto,
            lambda_implied=float(lam_imp[r]),
            lambda_declared=float(lam_dec[r]),
            audit=str(audit[r]),
            promote=promote,
        ))
    return reports


def online_calibration_batch(
    n_rows: int,
    row_index,
    P_mean,
    has_outcome,
    success,
    *,
    committed=None,
    tier3_sampled=None,
    tier3_accept=None,
    tokens_generated=None,
    output_tokens_est=None,
    bucket_width: float = 0.1,
    tier2_tolerance: float = 0.05,
    cov_threshold: float = 0.5,
    quarters_since_lambda_refresh=0,
) -> list[OnlineReport]:
    """§12.4 continuous checks for R edges over one flat decision-row
    batch (the online service's telemetry layout: ``row_index`` maps each
    decision row onto the posterior table).  ``n_rows`` may be a
    :class:`PosteriorStore` (or a service holding one) — the row space is
    then the store's logical id range, so drained telemetry from a paged
    service feeds straight in.

    The per-record work — calibration bucketing, success-rate sums,
    tier-2 false-accept and token-CoV masks — runs as array ops over all
    M rows at once; per-(row, bucket) statistics then reduce via
    ``np.add.at``.  Reports match scalar ``online_calibration`` on the
    equivalent per-edge ``TelemetryLog`` bitwise (rates, CIs, CoV) and
    exactly (flags).
    """
    if not isinstance(n_rows, int):
        n_rows = getattr(n_rows, "store", n_rows).n_rows
    row_index = np.asarray(row_index, int)
    M = row_index.shape[0]
    if M and (row_index.min() < 0 or row_index.max() >= n_rows):
        # same contract as tick()/observe(): a bad row (including the
        # ring's -1 padding sentinels — filter a drained batch first)
        # must raise, not wrap into the last edge's stats
        raise IndexError("row_index out of range")

    def mvec(x, fill=0.0, dtype=float):
        if x is None:
            return np.full(M, fill, dtype)
        return np.broadcast_to(np.asarray(x, dtype), (M,))

    P_mean = mvec(P_mean)
    has_outcome = mvec(has_outcome, False, bool)
    success = mvec(success, False, bool)
    committed = mvec(committed, False, bool)
    tier3_sampled = mvec(tier3_sampled, False, bool)
    tier3_accept = mvec(tier3_accept, False, bool)
    toks = mvec(tokens_generated, np.nan)
    toks_est = mvec(output_tokens_est, 0.0)
    quarters = np.broadcast_to(np.asarray(quarters_since_lambda_refresh, int),
                               (n_rows,))

    # ---- calibration buckets: vectorized bucket ids, merged through the
    # same rounded-midpoint key as TelemetryLog.calibration_buckets
    n_ids = int(1.0 / bucket_width) + 2
    # (i + 0.5) * width is a robust representative P for integer id i
    keys = np.array([bucket_key((i + 0.5) * bucket_width, bucket_width)
                     for i in range(n_ids)])
    # ids computed exactly as the scalar int() truncation (P_mean >= 0)
    ids = np.minimum((P_mean / bucket_width + 1e-9).astype(int), n_ids - 1)
    uniq_keys = np.unique(keys)
    key_of_id = np.searchsorted(uniq_keys, keys)
    K = uniq_keys.shape[0]
    succ_mat = np.zeros((n_rows, K), np.int64)
    n_mat = np.zeros((n_rows, K), np.int64)
    sel = has_outcome
    np.add.at(n_mat, (row_index[sel], key_of_id[ids[sel]]), 1)
    np.add.at(succ_mat, (row_index[sel], key_of_id[ids[sel]]),
              success[sel].astype(np.int64))

    # ---- tier-2 false accepts / token CoV, masked per row
    far_sel = committed & tier3_sampled
    far_num = np.zeros(n_rows, np.int64)
    far_den = np.zeros(n_rows, np.int64)
    np.add.at(far_den, row_index[far_sel], 1)
    np.add.at(far_num, row_index[far_sel],
              (~tier3_accept[far_sel]).astype(np.int64))
    tok_sel = ~np.isnan(toks) & (toks_est > 0)
    # group token ratios per row once (stable sort preserves log order
    # within a row, keeping np.std bitwise vs the scalar twin) — the per
    # -row report loop then slices instead of re-scanning all M records
    tok_rows = row_index[tok_sel]
    tok_ratios = toks[tok_sel] / toks_est[tok_sel]
    tok_order = np.argsort(tok_rows, kind="stable")
    tok_rows = tok_rows[tok_order]
    tok_ratios = tok_ratios[tok_order]
    tok_start = np.searchsorted(tok_rows, np.arange(n_rows))
    tok_end = np.searchsorted(tok_rows, np.arange(n_rows), side="right")

    reports = []
    for r in range(n_rows):
        buckets = []
        overpredicted = []
        for kk in range(K):
            n = int(n_mat[r, kk])
            if n == 0:
                continue
            bucket, over = _calibration_bucket(
                float(uniq_keys[kk]), int(succ_mat[r, kk]) / n, n,
                bucket_width)
            buckets.append(bucket)
            overpredicted.append(over)
        monotonic_over = len(overpredicted) >= 2 and all(overpredicted)
        den = int(far_den[r])
        far = (int(far_num[r]) / den) if den else None
        row_ratios = tok_ratios[tok_start[r]:tok_end[r]]
        cov = float(np.std(row_ratios, ddof=1)) if row_ratios.shape[0] >= 2 else None
        reports.append(OnlineReport(
            buckets=buckets,
            monotonic_overprediction=monotonic_over,
            tier2_false_accept_rate=far,
            tier2_needs_tightening=far is not None and far > tier2_tolerance,
            token_cov=cov,
            uncertain_cost=cov is not None and cov > cov_threshold,
            lambda_refresh_due=int(quarters[r]) >= 1,
        ))
    return reports
