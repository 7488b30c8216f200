#!/usr/bin/env python3
"""Bring-up check: the decision service and the replay engine on a TPU.

    python chip_smoke.py              # one chip: serving, kernels, replay
    python chip_smoke.py --chips 4    # four chips: the sharded paths only

Every phase runs in this one process (a chip belongs to one process at a
time).  Earlier lines of standard output are one ``[phase] {json}`` record
each: device, sizes, compile seconds, decisions answered, faults, parity
numbers.  The last line is ``{"ok": true, "device": {...}}`` with the
device as JAX reports it.  The script exits non-zero, without that line,
when any phase fails or when JAX's default device is not a TPU.

One chip, at deployment size:

* serving — an ``OnlineDecisionService`` over a paged ``PosteriorStore``
  (1,000,000 registered (tenant, edge) rows across the eight §13
  archetypes, 262,144 resident) behind a ``ServingFrontend``; Zipf
  (theta 0.99) traffic with settles fed back, so the store faults rows in
  and spills them; one batch each on the §7.5 lower-bound tier and the
  rollout tier; a scalar f64 ``decision.evaluate`` parity sample; the
  same sample once more under ``jax.enable_x64``.  Every 7th row carries
  a trigger-2 floor, so the kill switch trips there; each of its steps
  is replayed on the host against the f64 Beta quantile.
* kernels — the fused Pallas tick against the XLA tick over the same
  tick sequence (mean and §7.5 tiers, settles, drift), and the Pallas
  Beta quantile and §12.1 grid kernels against their references.
* replay — ``episode_sharded_replay`` (8 segments) against
  ``fleet_replay`` on the AutoReply workflow at 1,000,000 episodes, at
  the benchmark's lambda and at one where the gate fires.

Four chips (``--chips 4``): episode- and tenant-sharded replay over the
1-D fleet mesh and the row-sharded service, each against the same call
unsharded on one chip.

``--rehearse-on-cpu`` runs the same phases on the CPU at one fixed small
size and never prints the ``ok`` line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SEED = 0
# the near-tie band inside which f32 and f64 flags may disagree
BAND_REL = 1e-5
BATCH = 256             # the frontend's max_batch
ZIPF_THETA = 0.99       # YCSB's zipfian request distribution
SAMPLE_EVERY = 4        # scalar-parity sample: every 4th counted tick
SEGMENTS = 8            # episode-sharded replay segments
TENANTS = 8             # multi-tenant replay tenants
SHARDED_CHIPS = 4
FLOOR_EVERY = 7         # every 7th registered row carries a floor


@dataclasses.dataclass(frozen=True)
class Sizes:
    rows: int           # registered (tenant, edge) rows
    resident_rows: int  # the paged store's device table
    decisions: int      # service decisions in the counted window
    parity_sample: int  # least scalar-parity sample
    episodes: int       # replay episodes
    table_rows: int     # dense tables: fused tick, row-sharded service
    grid_rows: int      # §12.1 grid kernel log rows


DEPLOYMENT = Sizes(rows=1_000_000, resident_rows=262_144, decisions=20_000,
                   parity_sample=1024, episodes=1_000_000,
                   table_rows=262_144, grid_rows=1_048_576)
CPU_REHEARSAL = Sizes(rows=20_000, resident_rows=4096, decisions=2560,
                      parity_sample=256, episodes=20_000, table_rows=4096,
                      grid_rows=65_536)


class SmokeFailure(Exception):
    pass


def emit(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, default=_jsonable), flush=True)


def _jsonable(x):
    import numpy as np

    if isinstance(x, np.generic):
        return x.item()
    if isinstance(x, np.ndarray):
        return x.tolist()
    return str(x)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileMeter:
    """Backend compile count and seconds, as JAX itself records them."""

    def __init__(self) -> None:
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def mark(self) -> tuple[int, float]:
        return self.count, self.seconds

    def since(self, mark: tuple[int, float]) -> dict:
        return {"compiles": self.count - mark[0],
                "compile_s": self.seconds - mark[1]}


def _tie(ev, thr):
    """Inside the near-tie band, where f32 and f64 may pick either side."""
    import numpy as np

    return np.abs(ev - thr) <= BAND_REL * np.maximum(np.abs(thr), 1e-12)


# --------------------------------------------------------------------------
# serving phase
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Traffic:
    """Seeded request stream: Zipf(theta) row ranks over the registry,
    mapped through a fixed permutation so hot rows scatter across
    tenants, with the store benchmark's request-parameter ranges."""

    n_rows: int
    theta: float
    seed: int

    def __post_init__(self) -> None:
        import numpy as np

        self.rng = np.random.default_rng(self.seed)
        w = 1.0 / np.arange(1, self.n_rows + 1, dtype=np.float64) ** self.theta
        self.cdf = np.cumsum(w) / w.sum()
        self.by_rank = self.rng.permutation(self.n_rows)

    def rows(self, n: int):
        import numpy as np

        ranks = np.searchsorted(self.cdf, self.rng.random(n), side="right")
        return self.by_rank[np.minimum(ranks, self.n_rows - 1)]

    def params(self, n: int) -> dict:
        r = self.rng
        return dict(
            alpha=r.uniform(0, 1, n),
            lambda_usd_per_s=r.uniform(1e-4, 0.5, n),
            latency_s=r.uniform(0.01, 5.0, n),
            input_tokens=r.integers(1, 2000, n).astype(float),
            output_tokens=r.uniform(1, 2000, n),
            input_price=r.uniform(1e-8, 1e-4, n),
            output_price=r.uniform(1e-8, 1e-4, n),
        )


class Fleet:
    """The registry layout: row i belongs to archetype ``i % 8`` and to
    tenant ``i // 8 % tenants_per_archetype`` of it."""

    def __init__(self, n_rows: int, tenants_per_archetype: int = 128):
        import numpy as np

        from repro.core.archetypes import ARCHETYPES

        self.n_rows = n_rows
        self.names = sorted(ARCHETYPES)
        self.tpa = tenants_per_archetype
        # the success rate of speculating the modal branch (§13 profile)
        self.p_true = [min(0.98, ARCHETYPES[n].profile().p_mode)
                       for n in self.names]
        self.has_floor = np.arange(n_rows) % FLOOR_EVERY == 0

    def tenant(self, i: int) -> str:
        return f"{self.names[i % 8]}/t{i // 8 % self.tpa}"

    def edge(self, i: int) -> tuple[str, str]:
        return ("op", f"e{i}")

    def register(self, svc) -> None:
        """The 1M-row store benchmark's registration
        (``benchmarks.store_scale.store_record``: the ``_dep_mix`` cycle
        of dependency types and router fan-outs) with the discount spread
        of ``store_scale._register_mixed``.  Every 7th row (7 is prime to
        the 8 archetypes, the 5 dependency types and the 3-row discount
        cycle) carries a trigger-2 floor of 0.5, the break-even of C = L
        at alpha 0: archetypes whose modal branch is rarer than even odds
        breach it for good, so their hot rows' kill switches trip.
        ``_register_mixed``'s own floor (0.083) trips no row under this
        traffic: hot rows clear it within a few settles.  Rows without
        a floor never breach."""
        from benchmarks.store_scale import _dep_mix

        mix = _dep_mix()
        for i in range(self.n_rows):
            dep, k = mix[i % len(mix)]
            floor = (dict(floor_alpha=0.0, floor_C_spec_usd=0.01,
                          floor_L_value_usd=0.01)
                     if self.has_floor[i] else {})
            svc.register_edge(self.edge(i), tenant=self.tenant(i),
                              dep_type=dep, k=k,
                              discount=(0.95 if i % 3 == 0 else 1.0),
                              **floor)


def _submit_batch(fe, fleet: Fleet, rows, params):
    from repro.serving.frontend import DecisionRequest

    tickets = []
    for j, r in enumerate(rows):
        r = int(r)
        tickets.append(fe.submit(DecisionRequest(
            row=r, tenant=fleet.tenant(r), edge=fleet.edge(r),
            **{k: float(v[j]) for k, v in params.items()})))
    return tickets


class KillSwitchAudit:
    """Replays the tick's trigger-2 step (``_tick_impl`` step 3) on the
    host for the floor rows, one tick at a time.

    After each tick, every floor row the tick touched is checked against
    the flags it had before the tick: with ``P_low`` its f64 Beta
    quantile (scipy) at its gamma, a breach (``P_low < floor``) extends
    the breach run, anything else resets it, and a run reaching
    ``credible_consecutive_n`` disables the row and restarts the run.
    Where the device's own f32 quantile of the same posterior lands on
    the other side of the floor, or within ``BAND_REL`` of it, the step
    is a tie and is counted, not checked.  Rollout ticks (whose re-entry
    rule also rewrites the flags) only refresh the flags."""

    def __init__(self, svc, has_floor) -> None:
        import jax

        from repro.core.betainc import betaincinv

        self.svc = svc
        self.has_floor = has_floor
        self.n = svc.credible_consecutive_n
        self.flags = svc.store.flags_snapshot()
        self._quantile = jax.jit(betaincinv)
        self.steps = self.breaches = self.disables = 0
        self.ties = self.mismatches = 0
        self.max_abs_dp = 0.0       # |device f32 P_low - f64 P_low|
        self.first_mismatch = None

    def after_tick(self, rows, results, *, audit: bool = True) -> None:
        import numpy as np

        flags = self.svc.store.flags_snapshot()
        served = np.asarray([int(r) for r, res in zip(rows, results)
                             if res.source == "service"], np.int64)
        touched = np.unique(served[self.has_floor[served]])
        if audit and touched.size:
            self._check(touched, flags)
        self.flags = flags

    def _check(self, touched, flags) -> None:
        import numpy as np
        from scipy import stats

        post = self.svc.rows_snapshot(touched)
        cfg = [self.svc.store.row_config(int(r)) for r in touched]
        gamma = np.asarray([c.gamma for c in cfg])
        dt = self.svc._np_dtype
        # the device compares against its working-dtype copy of the floor
        floor = np.asarray([c.floor for c in cfg]).astype(dt).astype(float)
        p64 = stats.beta.ppf(gamma, post[:, 0], post[:, 1])
        k = touched.size
        pad = np.ones((BATCH, 3), dt)
        pad[:, 2] = 0.5
        pad[:k, 0], pad[:k, 1], pad[:k, 2] = post[:, 0], post[:, 1], gamma
        pdev = np.asarray(self._quantile(pad[:, 0], pad[:, 1],
                                         pad[:, 2]))[:k].astype(float)
        self.max_abs_dp = max(self.max_abs_dp,
                              float(np.max(np.abs(pdev - p64))))
        tie = ((p64 < floor) != (pdev < floor)) | _tie(p64, floor) \
            | _tie(pdev, floor)
        breach = p64 < floor
        pre_en, pre_run = self.flags[touched, 0] > 0, self.flags[touched, 1]
        run = np.where(breach, pre_run + 1, 0)
        trig = run >= self.n
        run = np.where(trig, 0, run)
        enabled = pre_en & ~trig
        ok = (flags[touched, 1] == run) & ((flags[touched, 0] > 0) == enabled)
        self.steps += int((~tie).sum())
        self.ties += int(tie.sum())
        self.breaches += int((breach & ~tie).sum())
        self.disables += int((pre_en & ~enabled & ~tie).sum())
        bad = ~ok & ~tie
        self.mismatches += int(bad.sum())
        if bad.any() and self.first_mismatch is None:
            j = int(np.flatnonzero(bad)[0])
            self.first_mismatch = dict(
                row=int(touched[j]), post=post[j].tolist(),
                p_low_f64=float(p64[j]), p_low_device=float(pdev[j]),
                floor=float(floor[j]),
                flags_before=self.flags[touched[j]].tolist(),
                flags_after=flags[touched[j]].tolist())

    def report(self) -> dict:
        return dict(audited_steps=self.steps, breaches=self.breaches,
                    disables=self.disables, ties=self.ties,
                    mismatches=self.mismatches,
                    max_abs_p_low_diff=self.max_abs_dp,
                    first_mismatch=self.first_mismatch)


class Serving:
    """The frontends over one service, with every answer's source and
    the kill-switch audit kept across ticks."""

    def __init__(self, svc, fleet: Fleet, traffic: Traffic, outcome_rng):
        from repro.core.rollout import RolloutController
        from repro.serving.frontend import FrontendConfig, ServingFrontend

        # no tick timeout anywhere: a first call that compiles must not
        # read as a hung tick
        cfg = FrontendConfig(max_batch=BATCH, deadline_s=0.005,
                             check_drift=True, bulkhead_limit=BATCH,
                             tick_timeout_s=None)
        self.svc, self.fleet, self.traffic = svc, fleet, traffic
        self.outcome_rng = outcome_rng
        self.fe = ServingFrontend(svc, cfg, autostart=False)
        self.fe_roll = ServingFrontend(RolloutController(svc), cfg,
                                       autostart=False)
        self.audit = KillSwitchAudit(svc, fleet.has_floor)
        self.fallback_rows: list[int] = []
        self.other_sources: list[str] = []

    def tick(self, *, rollout: bool = False):
        """Submit one batch, pump it through the chain, feed every
        answer's outcome back (the upstream resolves for every request,
        so every decision yields an observation).  Returns (rows, params,
        results)."""
        fe = self.fe_roll if rollout else self.fe
        rows = self.traffic.rows(BATCH)
        params = self.traffic.params(BATCH)
        tickets = _submit_batch(fe, self.fleet, rows, params)
        fe.pump()
        results = [t.result(0) for t in tickets]
        for r, res in zip(rows, results):
            if res.source == "scalar":
                self.fallback_rows.append(int(r))
            elif res.source != "service":
                self.other_sources.append(res.source)
        self.audit.after_tick(rows, results, audit=not rollout)
        p = [self.fleet.p_true[int(r) % 8] for r in rows]
        for t, pr in zip(tickets, p):
            t.settle(bool(self.outcome_rng.random() < pr))
        return rows, params, results

    def trips(self) -> tuple[int, list[int]]:
        rows = [e.row for f in (self.fe, self.fe_roll)
                for e in f.resilience.events if e.kind == "drift_trip"]
        return self.fe.breaker.trips + self.fe_roll.breaker.trips, rows


def _scalar(snap_row, params, j, *, use_lower_bound=False, gamma=0.1):
    from repro.core.decision import DecisionInputs, evaluate
    from repro.core.posterior import BetaPosterior

    post = BetaPosterior(alpha=float(snap_row[0]), beta=float(snap_row[1]))
    return evaluate(DecisionInputs(
        P=post.mean,
        alpha=float(params["alpha"][j]),
        lambda_usd_per_s=float(params["lambda_usd_per_s"][j]),
        latency_seconds=float(params["latency_s"][j]),
        input_tokens=float(params["input_tokens"][j]),
        output_tokens=float(params["output_tokens"][j]),
        input_price=float(params["input_price"][j]),
        output_price=float(params["output_price"][j]),
        P_lower_bound=(post.lower_bound(gamma) if use_lower_bound else None),
    ), use_lower_bound=use_lower_bound)


class ParityTally:
    """Device answers against scalar f64 ``decision.evaluate``."""

    def __init__(self) -> None:
        self.n = 0
        self.bitwise = 0
        self.near_ties = 0
        self.flag_mismatch_in_band = 0
        self.flag_mismatch_outside_band = 0
        self.max_rel_ev = 0.0           # |dEV| / |EV_ref|
        self.max_rel_ev_terms = 0.0     # |dEV| / (P*L + (1-P)*C)
        self.max_abs_p = 0.0            # |dP_used|

    def add(self, ev, thr, speculate, p_used, ref) -> None:
        self.n += 1
        if ev == ref.EV_usd and thr == ref.threshold_usd:
            self.bitwise += 1
        d = abs(ev - ref.EV_usd)
        self.max_rel_ev = max(self.max_rel_ev,
                              d / max(abs(ref.EV_usd), 1e-300))
        P = ref.P_used
        terms = P * ref.L_value_usd + (1.0 - P) * ref.C_spec_usd
        self.max_rel_ev_terms = max(self.max_rel_ev_terms,
                                    d / max(terms, 1e-300))
        self.max_abs_p = max(self.max_abs_p, abs(p_used - P))
        tie = bool(_tie(ref.EV_usd, ref.threshold_usd))
        self.near_ties += tie
        if speculate != (ref.EV_usd >= ref.threshold_usd):
            if tie:
                self.flag_mismatch_in_band += 1
            else:
                self.flag_mismatch_outside_band += 1

    def report(self) -> dict:
        return dict(sampled=self.n, bitwise_equal=self.bitwise,
                    near_ties=self.near_ties,
                    flag_mismatch_in_band=self.flag_mismatch_in_band,
                    flag_mismatch_outside_band=self.flag_mismatch_outside_band,
                    max_rel_ev_diff=self.max_rel_ev,
                    max_ev_diff_rel_to_terms=self.max_rel_ev_terms,
                    max_abs_p_used_diff=self.max_abs_p)


def serving_phase(sizes: Sizes, meter: CompileMeter) -> None:
    import numpy as np

    from repro.core.online import OnlineDecisionService

    B = BATCH
    fleet = Fleet(sizes.rows)
    svc = OnlineDecisionService(resident_rows=sizes.resident_rows,
                                min_rows=256)
    t0 = time.perf_counter()
    fleet.register(svc)
    emit("serving", step="register", rows=sizes.rows,
         resident_rows=sizes.resident_rows, archetypes=fleet.names,
         tenants=8 * fleet.tpa, floor_rows=int(fleet.has_floor.sum()),
         wall_s=time.perf_counter() - t0)

    traffic = Traffic(sizes.rows, ZIPF_THETA, SEED)
    sv = Serving(svc, fleet, traffic, np.random.default_rng(SEED + 1))
    fe, fe_roll = sv.fe, sv.fe_roll

    # ---- warm-up: compile every executable the counted window runs
    store = svc.store
    mark = meter.mark()
    first = {}
    for name in ("tick_no_settles", "tick_settles_256"):
        t0 = time.perf_counter()
        sv.tick()
        first[name] = time.perf_counter() - t0
    svc.use_lower_bound = True
    t0 = time.perf_counter()
    sv.tick()
    first["tick_lower_bound"] = time.perf_counter() - t0
    svc.use_lower_bound = False
    for name in ("tick_rollout_no_settles", "tick_rollout_settles_256"):
        t0 = time.perf_counter()
        sv.tick(rollout=True)
        first[name] = time.perf_counter() - t0
    # fill the resident table with the coldest rows, so every fault-in
    # of the counted window spills a victim, then compile each
    # power-of-two fault-in / spill bucket a tick can need
    cold = traffic.by_rank[::-1]
    cold = cold[store._slot_of[cold] < 0]
    free = store.capacity - store.n_resident
    t0 = time.perf_counter()
    store.ensure_resident(cold[:free])
    pos, k = free, 1
    while k <= 2 * B:
        store.ensure_resident(cold[pos:pos + k])
        pos, k = pos + k, 2 * k
    first["store_fill_and_buckets"] = time.perf_counter() - t0
    emit("serving", step="warmup", first_call_s=first, **meter.since(mark))
    require(store.n_resident == store.capacity, "resident table not full")

    # ---- counted window: service answers until the decision count
    stats0 = dict(fe.stats)
    faults0, spills0 = store.stats["fault_ins"], store.stats["spills"]
    mark = meter.mark()
    tally = ParityTally()
    samples = []
    n_ticks = 0
    t0 = time.perf_counter()
    while fe.stats["service"] - stats0["service"] < sizes.decisions:
        rows, params, results = sv.tick()
        if n_ticks % SAMPLE_EVERY == 0:
            snap = svc.posterior_snapshot()
            for j, res in enumerate(results):
                r = int(rows[j])
                if fleet.has_floor[r] or res.source != "service":
                    continue    # the kill switch may hold these at WAIT
                tally.add(res.EV_usd, res.threshold_usd, res.speculate,
                          res.P_used, _scalar(snap[r], params, j))
            samples.append((rows, params))
        n_ticks += 1
    window_s = time.perf_counter() - t0
    answered = fe.stats["service"] - stats0["service"]
    # one batch on the §7.5 lower-bound tier, one on the rollout tier
    svc.use_lower_bound = True
    rows, params, lb_results = sv.tick()
    snap = svc.posterior_snapshot()
    lb = ParityTally()
    for j, res in enumerate(lb_results):
        r = int(rows[j])
        if not fleet.has_floor[r] and res.source == "service":
            lb.add(res.EV_usd, res.threshold_usd, res.speculate, res.P_used,
                   _scalar(snap[r], params, j, use_lower_bound=True))
    svc.use_lower_bound = False
    _, _, roll_results = sv.tick(rollout=True)
    in_window = meter.since(mark)

    delta = {k: fe.stats[k] - stats0[k] for k in fe.stats}
    trips, trip_rows = sv.trips()
    flags = store.flags_snapshot()
    plain = ~fleet.has_floor
    emit("serving", step="window", decisions=answered,
         lower_bound_decisions=sum(r.source == "service"
                                   for r in lb_results),
         rollout_decisions=sum(r.source == "service" for r in roll_results),
         ticks=n_ticks, wall_s=window_s,
         store_fault_ins=store.stats["fault_ins"] - faults0,
         store_spills=store.stats["spills"] - spills0,
         frontend=delta, rollout_frontend=fe_roll.stats,
         last_tick_error=fe.last_tick_error or fe_roll.last_tick_error,
         **in_window)
    emit("serving", step="kill_switch", floor_every=FLOOR_EVERY,
         breaker_trips=trips, drift_trip_events=len(trip_rows),
         disabled_rows=int((flags[:, 0] == 0).sum()),
         scalar_fallbacks=len(sv.fallback_rows),
         **sv.audit.report())
    emit("serving", step="parity_f32", band_rel=BAND_REL, **tally.report())
    emit("serving", step="parity_lower_bound_f32", **lb.report())

    require(answered >= sizes.decisions,
            f"answered {answered} < {sizes.decisions}")
    # warm-up included: a tick that fell back while compiling counts too
    for name, d in (("frontend", fe.stats),
                    ("rollout frontend", fe_roll.stats)):
        require(d["tick_faults"] == 0,
                f"{name}: {d['tick_faults']} tick faults "
                f"({fe.last_tick_error or fe_roll.last_tick_error})")
        for k in ("conservative", "shed"):
            require(d[k] == 0, f"{name}: {d[k]} answers from {k!r}")
    require(not sv.other_sources,
            f"non-service answers: {sv.other_sources[:5]}")
    # the kill switch: it trips on floor rows, as the host replay of each
    # step says, and nowhere else
    require(sv.audit.mismatches == 0,
            f"kill-switch step disagrees with the f64 replay: "
            f"{sv.audit.first_mismatch}")
    require(sv.audit.disables > 0 and trip_rows,
            "no floor row tripped its kill switch")
    require(trips == len(trip_rows), "a breaker tripped without a breach")
    require(all(fleet.has_floor[r] for r in trip_rows + sv.fallback_rows),
            "a row without a floor tripped or fell back")
    require(bool((flags[plain, 0] == 1).all() and
                 (flags[plain, 1] == 0).all()),
            "a row without a floor breached")
    require(store.stats["fault_ins"] > faults0
            and store.stats["spills"] > spills0,
            "the store neither faulted in nor spilled during the window")
    require(tally.n >= sizes.parity_sample,
            f"parity sample {tally.n} < {sizes.parity_sample}")
    require(tally.flag_mismatch_outside_band == 0
            and lb.flag_mismatch_outside_band == 0,
            "f32 flags disagree with scalar f64 outside the near-tie band")

    # ---- the same sample once more under x64 on the XLA tick
    from jax import enable_x64

    mark = meter.mark()
    x64 = ParityTally()
    with enable_x64():
        snap = svc.posterior_snapshot()
        for rows, params in samples:
            n = rows.shape[0]
            reqs = np.stack([params[k] for k in (
                "alpha", "lambda_usd_per_s", "latency_s", "input_tokens",
                "output_tokens", "input_price", "output_price")], 1)
            d = svc.tick_packed(rows.astype(np.int32), reqs, batch=n)
            ev, thr, spec = d.EV_usd, d.threshold_usd, d.speculate
            p_used = d.P_used
            for j in range(n):
                r = int(rows[j])
                if not fleet.has_floor[r]:
                    x64.add(float(ev[j]), float(thr[j]), bool(spec[j]),
                            float(p_used[j]), _scalar(snap[r], params, j))
        table_dtype = str(svc.state.post.dtype)
    rep = x64.report()
    emit("serving", step="parity_x64", table_dtype=table_dtype,
         all_bitwise=rep["bitwise_equal"] == rep["sampled"],
         **rep, **meter.since(mark))
    require(table_dtype == "float64", "x64 run did not use an f64 table")
    require(x64.flag_mismatch_outside_band == 0,
            "x64 flags disagree with scalar f64 outside the near-tie band")


# --------------------------------------------------------------------------
# kernels phase: each Pallas kernel natively, against its reference
# --------------------------------------------------------------------------
def kernels_phase(sizes: Sizes, meter: CompileMeter) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from scipy import stats

    from repro.core.betainc import betaincinv
    from repro.kernels import betaincinv_op, replay_grid_op
    from repro.kernels.ops import _interpret
    from repro.kernels.ref import reference_replay_grid

    interpret = _interpret()

    # -- the fused tick against the XLA tick, same tick sequence
    mark = meter.mark()
    xla = _service_ticks(sizes, None)
    fused = _service_ticks(sizes, None, fused=True)
    tie = _tie(xla["EV"], xla["EV"] - xla["margin"])
    flag_out = int((xla["speculate"] != fused["speculate"])[~tie].sum())
    same = {k: bool(np.array_equal(xla[k], fused[k]))
            for k in ("posterior", "drift", "enabled", "breach_runs")}
    ev_terms = np.abs(xla["EV"] - fused["EV"]) / np.maximum(
        np.abs(xla["EV"]) + np.abs(xla["EV"] - xla["margin"]), 1e-300)
    emit("kernels", step="fused_tick_vs_xla_tick", rows=sizes.table_rows,
         ticks=["mean", "lower_bound", "mean"], interpret=interpret,
         near_ties=int(tie.sum()), flag_mismatch_outside_band=flag_out,
         bitwise_equal=same, max_ev_diff_rel=float(ev_terms.max()),
         breaches=int((xla["breach_runs"] > 0).sum()),
         disabled=int((~xla["enabled"]).sum()), **meter.since(mark))
    require(flag_out == 0, "fused tick flags differ outside the band")
    require(all(same.values()), f"fused tick state differs: {same}")

    # -- the Beta quantile: Pallas and XLA against scipy f64
    rng = np.random.default_rng(SEED)
    n = sizes.grid_rows // 16
    a = rng.uniform(0.5, 200.0, n).astype(np.float32)
    b = rng.uniform(0.5, 200.0, n).astype(np.float32)
    q = np.full(n, 0.1, np.float32)
    ref = stats.beta.ppf(0.1, a.astype(float), b.astype(float))
    mark = meter.mark()
    pk = np.asarray(betaincinv_op(jnp.asarray(a), jnp.asarray(b),
                                  jnp.asarray(q))).astype(float)
    px = np.asarray(jax.jit(betaincinv)(a, b, q)).astype(float)
    emit("kernels", step="betaincinv_vs_scipy", rows=n, interpret=interpret,
         pallas_max_abs_err=float(np.max(np.abs(pk - ref))),
         xla_max_abs_err=float(np.max(np.abs(px - ref))),
         pallas_finite=bool(np.isfinite(pk).all()), **meter.since(mark))
    require(np.isfinite(pk).all() and np.isfinite(px).all(),
            "non-finite Beta quantile")

    # -- the §12.1 counterfactual grid: Pallas against the XLA oracle
    from benchmarks.workflow_sim import DEFAULT_ALPHAS

    n = sizes.grid_rows
    P = rng.uniform(0.1, 0.95, n).astype(np.float32)
    lat = rng.uniform(0.5, 3.0, n).astype(np.float32)
    cost = rng.uniform(0.005, 0.03, n).astype(np.float32)
    alphas = np.asarray(DEFAULT_ALPHAS, np.float32)
    lams = np.array([0.005, 0.01, 0.05, 0.08, 0.25], np.float32)
    mark = meter.mark()
    got = [np.asarray(x) for x in replay_grid_op(
        jnp.asarray(P), jnp.asarray(lat), jnp.asarray(cost),
        jnp.asarray(alphas), jnp.asarray(lams))]
    want = [np.asarray(x) for x in jax.jit(reference_replay_grid)(
        P, lat, cost, alphas, lams)]
    # a row within BAND_REL of its cell's threshold, relative to the
    # terms, may count on either side
    gain = (P * lat).astype(float)[None, None] * lams[None, :, None]
    lose = ((1.0 - P) * cost).astype(float)[None, None]
    thr = ((1.0 - alphas)[:, None, None] * cost[None, None]).astype(float)
    cell_ties = (np.abs(gain - lose - thr)
                 <= BAND_REL * (gain + lose + thr)).sum(-1)
    dcount = np.abs(got[0] - want[0])
    rel = [float(np.max(np.abs(g - w) / np.maximum(np.abs(w), 1e-30)))
           for g, w in zip(got[1:], want[1:])]
    emit("kernels", step="replay_grid_vs_reference", rows=n,
         cells=list(got[0].shape), interpret=interpret,
         counts_equal=bool(np.array_equal(got[0], want[0])),
         max_count_diff=float(dcount.max()),
         max_rel_latency_sum_diff=rel[0], max_rel_waste_sum_diff=rel[1],
         **meter.since(mark))
    require(bool((dcount <= cell_ties).all()),
            "grid counts differ beyond the cells' near ties")
    require(max(rel) <= 1e-4, f"grid sums differ by {max(rel)} relative")


# --------------------------------------------------------------------------
# replay phase
# --------------------------------------------------------------------------
def _compare_reports(a, b) -> dict:
    """Bitwise comparison of two FleetReports: final posteriors, decision
    counts per grid point, and every per-episode field."""
    import numpy as np

    final = (np.array_equal(a.post_alpha[-1], b.post_alpha[-1])
             and np.array_equal(a.post_beta[-1], b.post_beta[-1]))
    counts = {k: bool(np.array_equal(getattr(a, k).sum(0),
                                     getattr(b, k).sum(0)))
              for k in ("launched", "committed", "cancelled", "speculate")}
    fields, max_diff = {}, {}
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray):
            eq = bool(np.array_equal(x, y))
            fields[f.name] = eq
            if not eq and x.dtype.kind == "f":
                max_diff[f.name] = float(np.max(np.abs(x - y)))
    return dict(final_posteriors_equal=bool(final), counts_equal=counts,
                all_fields_equal=all(fields.values()),
                fields_differing=[k for k, v in fields.items() if not v],
                max_abs_diff=max_diff,
                final_posterior=[a.post_alpha[-1].tolist(),
                                 a.post_beta[-1].tolist()],
                launched_per_alpha=a.launched.sum(0).tolist())


def replay_phase(sizes: Sizes, meter: CompileMeter) -> None:
    import numpy as np

    from benchmarks.workflow_sim import (BEAM_LAMBDA_USD_PER_S,
                                         DEFAULT_ALPHAS, LAMBDA_USD_PER_S,
                                         _autoreply_fleet)
    from repro.core import episode_sharded_replay, fleet_replay

    t0 = time.perf_counter()
    lowered, success, _ = _autoreply_fleet(episodes=sizes.episodes,
                                           seed=SEED)
    alphas = np.asarray(DEFAULT_ALPHAS)
    emit("replay", step="log", episodes=sizes.episodes,
         grid_points=len(alphas), segments=SEGMENTS,
         wall_s=time.perf_counter() - t0)
    # the benchmark's lambda, and the beam record's, where the gate fires
    # and the posterior handed across segments moves
    for lam, must_move in ((LAMBDA_USD_PER_S, False),
                           (BEAM_LAMBDA_USD_PER_S, True)):
        mark = meter.mark()
        t0 = time.perf_counter()
        base = fleet_replay(lowered, success, alphas, lam)
        t_base = time.perf_counter() - t0
        t0 = time.perf_counter()
        seg, starts = episode_sharded_replay(
            lowered, success, alphas, lam, n_segments=SEGMENTS,
            return_boundaries=True)
        t_seg = time.perf_counter() - t0
        cmp = _compare_reports(base, seg)
        # segments whose own episodes moved the posterior they hand on
        moved = int(sum(not np.array_equal(starts[c], starts[c - 1])
                        for c in range(1, SEGMENTS)))
        emit("replay", step="episode_sharded_vs_fleet_replay",
             lambda_usd_per_s=lam, handoffs=SEGMENTS - 1,
             handoffs_moved=moved, fleet_replay_first_call_s=t_base,
             episode_sharded_first_call_s=t_seg, **cmp,
             **meter.since(mark))
        require(cmp["final_posteriors_equal"],
                f"final posteriors differ at lambda {lam}")
        require(all(cmp["counts_equal"].values()),
                f"decision counts differ at lambda {lam}")
        if must_move:
            require(moved == SEGMENTS - 1,
                    f"only {moved} of {SEGMENTS - 1} handoffs carried a "
                    f"moved posterior at lambda {lam}")


# --------------------------------------------------------------------------
# four chips: the sharded paths against the same calls on one chip
# --------------------------------------------------------------------------
def sharded_phase(sizes: Sizes, meter: CompileMeter) -> None:
    import jax
    import numpy as np

    from benchmarks.workflow_sim import (DEFAULT_ALPHAS, LAMBDA_USD_PER_S,
                                         _autoreply_fleet,
                                         _episode_sharded_shards, _mt_stack)
    from repro.core import episode_sharded_replay, multi_tenant_replay
    from repro.launch.mesh import make_fleet_mesh

    chips = SHARDED_CHIPS
    require(len(jax.devices()) == chips,
            f"{len(jax.devices())} devices, expected {chips}")
    mesh = make_fleet_mesh(chips)
    alphas = np.asarray(DEFAULT_ALPHAS)

    # -- episode-sharded replay: segments over the fleet axis
    lowered, success, _ = _autoreply_fleet(episodes=sizes.episodes,
                                           seed=SEED)
    mark = meter.mark()
    one = episode_sharded_replay(lowered, success, alphas, LAMBDA_USD_PER_S,
                                 n_segments=SEGMENTS)
    many = episode_sharded_replay(lowered, success, alphas, LAMBDA_USD_PER_S,
                                  n_segments=SEGMENTS, mesh=mesh)
    shards = _episode_sharded_shards(lowered, success, alphas, mesh,
                                     SEGMENTS)
    cmp = _compare_reports(one, many)
    emit("sharded", step="episode_sharded_replay", episodes=sizes.episodes,
         segments=SEGMENTS, shards=shards, **cmp, **meter.since(mark))
    require(shards == chips, f"episode-sharded shards {shards}")
    require(cmp["final_posteriors_equal"]
            and all(cmp["counts_equal"].values()),
            "episode-sharded replay differs from one chip")

    # -- multi-tenant replay: tenants over the fleet axis
    per_tenant = max(1, sizes.episodes // TENANTS)
    stack = _mt_stack(tenants=TENANTS, episodes=per_tenant, seed=SEED)
    mark = meter.mark()
    one = multi_tenant_replay(stack, alphas, LAMBDA_USD_PER_S)
    many = multi_tenant_replay(stack, alphas, LAMBDA_USD_PER_S, mesh=mesh)
    shards = len(many.post_final.sharding.device_set)
    diff = [f.name for f in dataclasses.fields(one)
            if isinstance(getattr(one, f.name), np.ndarray)
            and not np.array_equal(getattr(one, f.name),
                                   getattr(many, f.name))]
    post_eq = bool(np.array_equal(np.asarray(one.post_final),
                                  np.asarray(many.post_final)))
    emit("sharded", step="multi_tenant_replay", tenants=TENANTS,
         episodes_per_tenant=per_tenant, shards=shards,
         final_posteriors_equal=post_eq, fields_differing=diff,
         **meter.since(mark))
    require(shards == chips, f"multi-tenant shards {shards}")
    require(post_eq and not diff, "multi-tenant replay differs from one chip")

    # -- the row-sharded online service
    mark = meter.mark()
    base = _service_ticks(sizes, None)
    sharded = _service_ticks(sizes, mesh)
    shards = len(sharded["svc"].state.post.sharding.device_set)
    same, max_diff = {}, {}
    for k in base:
        if k == "svc":
            continue
        a, b = base[k], sharded[k]
        same[k] = bool(np.array_equal(a, b))
        if not same[k] and a.dtype.kind == "f":
            max_diff[k] = float(np.max(np.abs(a - b)))
    emit("sharded", step="row_sharded_service", rows=sizes.table_rows,
         shards=shards, equal=same, max_abs_diff=max_diff,
         **meter.since(mark))
    require(shards == chips, f"row-sharded service shards {shards}")
    require(same["speculate"] and same["drift"] and same["enabled"],
            "row-sharded service decisions differ from one chip")


def _service_ticks(sizes: Sizes, mesh, *, fused: bool = False) -> dict:
    """The multi-device test's tick sequence (decisions, a §7.5 tick,
    settles, drift) over a dense ``table_rows`` table, in the default
    dtype, on the XLA tick or the fused Pallas one."""
    import numpy as np

    from repro.core.online import OnlineDecisionService
    from repro.core.taxonomy import DependencyType

    n = sizes.table_rows
    svc = OnlineDecisionService(mesh=mesh, credible_consecutive_n=2,
                                use_fused_tick=fused)
    for i in range(n):
        svc.register_edge(
            ("u", f"v{i}"), dep_type=DependencyType.ROUTER_K_WAY,
            k=2 + i % 5, discount=(0.97 if i % 3 == 0 else 1.0),
            floor_alpha=0.5, floor_C_spec_usd=0.01,
            floor_L_value_usd=0.002 + 0.001 * (i % 16))
    rng = np.random.default_rng(SEED)
    out = {k: [] for k in ("EV", "margin", "speculate", "drift")}
    for t in range(3):
        B = BATCH
        d = svc.tick(
            rng.integers(0, n, B), alpha=rng.uniform(0, 1, B),
            lambda_usd_per_s=0.05, latency_s=rng.uniform(0.1, 2, B),
            input_tokens=20, output_tokens=rng.uniform(10, 200, B),
            input_price=1e-6, output_price=1e-5,
            outcomes=[(int(r), bool(s)) for r, s in zip(
                rng.integers(0, n, B), rng.integers(0, 2, B))],
            use_lower_bound=(t == 1), check_drift=True)
        out["EV"].append(d.EV_usd)
        out["margin"].append(d.margin_usd)
        out["speculate"].append(d.speculate)
        out["drift"].append(d.drift_triggered)
    res = {k: np.concatenate(v) for k, v in out.items()}
    res["posterior"] = svc.posterior_snapshot()
    res["enabled"] = svc.enabled_snapshot()
    res["breach_runs"] = svc.breach_runs()
    res["svc"] = svc
    return res


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    choices=(1, SHARDED_CHIPS))
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="run on the CPU at a small size; never prints "
                         "the ok line")
    args = ap.parse_args(argv)
    sizes = CPU_REHEARSAL if args.rehearse_on_cpu else DEPLOYMENT

    from repro.launch.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu" and not args.rehearse_on_cpu:
        print(f"no TPU: default device is {dev['platform']!r}",
              file=sys.stderr)
        return 2
    emit("device", **dev, jax=jax.__version__, compile_cache=cache_dir,
         x64=bool(jax.config.jax_enable_x64), sizes=dataclasses.asdict(sizes))

    meter = CompileMeter()
    phases = ([("sharded", sharded_phase)] if args.chips == SHARDED_CHIPS
              else [("serving", serving_phase), ("kernels", kernels_phase),
                    ("replay", replay_phase)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(sizes, meter)
        except Exception as e:          # report, then run the next phase
            failed.append(name)
            traceback.print_exc()
            emit(name, step="FAILED", error=f"{type(e).__name__}: {e}")
        emit(name, step="done", wall_s=time.perf_counter() - t0,
             ok=name not in failed)
    if failed:
        print(f"failed phases: {failed}", file=sys.stderr)
        return 1
    if dev["platform"] != "tpu":
        print("rehearsal on the CPU finished; not a chip run",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
