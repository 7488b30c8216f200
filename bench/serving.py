"""The serving cells' shared machinery: build the deployment, put a
recording proxy between the frontend and the service, warm every shape
the window uses, and decide ``correct`` against the plain reference.

The window drives ``ServingFrontend.submit``; the frontend's batcher
thread calls ``OnlineDecisionService.tick_packed`` through the proxy,
which records each tick (the rows, request columns and settles the
service was handed, and what it answered) and times it as a host span.
The benchmark stamps each ticket's answer time itself, as the ticket is
resolved (:func:`stamp_resolves`).
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from . import gen
from .common import log
from .reference import serving as ref

# a serving window is traced from this share of its length on
TRACE_FROM = 0.3
# how long after the window's close the answers still due are awaited
DRAIN_S = 30.0


def stamp_resolves() -> None:
    """Stamp ``bench_t_resolve`` (``time.monotonic``) on every frontend
    ticket as it is resolved, whatever path resolves it, so that the
    latency the benchmark reports ends at a time it took itself."""
    from repro.serving.frontend import FrontendTicket

    orig = FrontendTicket._resolve
    if getattr(orig, "bench_stamp", False):
        return

    def _resolve(self, result):
        orig(self, result)
        self.bench_t_resolve = time.monotonic()

    _resolve.bench_stamp = True
    FrontendTicket._resolve = _resolve


def resolved_at(ticket) -> float:
    """The benchmark's stamp of an answered ticket."""
    t = getattr(ticket, "bench_t_resolve", None)
    if t is None:
        raise RuntimeError("a ticket was answered without passing through "
                           "FrontendTicket._resolve; no answer time")
    return t


class ServiceProxy:
    """Stands between the frontend and the service: every attribute is
    the service's, and ``tick_packed`` is recorded."""

    def __init__(self, svc, spans) -> None:
        self._svc = svc
        self._spans = spans
        self.records: list[ref.TickRecord] = []
        # (start, end, real requests, settles, store fault-ins, settle
        # bucket)
        self.ticks: list[tuple[float, float, int, int, int, int]] = []
        self.req_dtype = None             # the type requests are packed in

    def __getattr__(self, name):
        return getattr(self._svc, name)

    def tick_packed(self, row, reqs, *, batch=None, out_row=None, out_x=None,
                    **kw):
        B = int((row >= 0).sum()) if batch is None else int(batch)
        outs = (np.zeros(0, np.int64) if out_row is None
                else out_row[out_row >= 0].astype(np.int64))
        xs = (np.zeros(0) if out_x is None
              else np.asarray(out_x, np.float64)[: outs.size])
        self.req_dtype = reqs.dtype
        rec = ref.TickRecord(rows=row[:B].astype(np.int64),
                             reqs=np.array(reqs[:B], np.float64),
                             out_rows=outs, out_x=xs)
        store = self._svc.store
        f0 = store.stats["fault_ins"]
        t0 = time.monotonic()
        try:
            with self._spans.annotation("bench.tick"):
                d = self._svc.tick_packed(row, reqs, batch=batch,
                                          out_row=out_row, out_x=out_x, **kw)
                # what the frontend reads next (cached on ``d``), read here
                # so the span holds the tick's whole device round trip
                rec.triggered = np.flatnonzero(d.drift_triggered)
                rec.EV = np.asarray(d.EV_usd, np.float64).copy()
                rec.thr = np.asarray(d.threshold_usd, np.float64).copy()
                rec.flag = d.flag.copy()
                rec.enabled = d.enabled.copy()
        except Exception:
            rec.ok = False
            raise
        finally:
            t1 = time.monotonic()
            self.records.append(rec)
            self.ticks.append((t0, t1, B, outs.size,
                               store.stats["fault_ins"] - f0,
                               0 if out_row is None else out_row.shape[0]))
        return d


@dataclasses.dataclass
class Deployment:
    svc: object
    proxy: ServiceProxy
    fe: object
    layout: gen.FleetLayout
    tables: ref.FleetTables
    zipf: gen.ZipfRows


def build(cfg: dict, seed: int, spans) -> Deployment:
    """Register the fleet, fill the resident table and put the frontend
    (batcher not yet started) over the recording proxy."""
    from repro.core.online import OnlineDecisionService
    from repro.serving.frontend import FrontendConfig, ServingFrontend

    stamp_resolves()
    layout = gen.FleetLayout(cfg)
    svc = OnlineDecisionService(
        resident_rows=cfg["resident_rows"], min_rows=256,
        credible_consecutive_n=cfg["credible_consecutive_n"])
    gen.register_fleet(svc, layout)
    proxy = ServiceProxy(svc, spans)
    f = cfg["frontend"]
    fe = ServingFrontend(proxy, FrontendConfig(
        max_batch=f["max_batch"], deadline_s=f["deadline_s"],
        max_queue=f["max_queue"], bulkhead_limit=f["bulkhead_limit"],
        tick_timeout_s=f["tick_timeout_s"], check_drift=cfg["drift_check"]),
        clock=time.monotonic, autostart=False)
    zipf = gen.ZipfRows(layout.n_rows, cfg["zipf_theta"], seed)
    return Deployment(svc, proxy, fe, layout, ref.FleetTables(cfg), zipf)


def fill_resident_table(dep: Deployment, max_batch: int) -> None:
    """A long-running service has a full resident table: fill it with the
    rows the traffic touches least, so every fault-in of the window
    spills a victim, then build each power-of-two fault-in and spill
    bucket a tick can need (``chip_smoke``'s warm-up)."""
    store = dep.svc.store
    cold = dep.zipf.by_rank[::-1]
    cold = cold[store._slot_of[cold] < 0]
    free = store.capacity - store.n_resident
    store.ensure_resident(cold[:free])
    pos, k = free, 1
    while k <= 2 * max_batch:
        store.ensure_resident(cold[pos:pos + k])
        pos, k = pos + k, 2 * k
    if store.n_resident != store.capacity:
        raise RuntimeError("resident table not full after the fill")


class Clients:
    """The requests of a run and the tickets they got, in submit order.
    A client settles each answer with the upstream's outcome."""

    def __init__(self, dep: Deployment, spans) -> None:
        from repro.serving.frontend import DecisionRequest

        self._Req = DecisionRequest
        self.dep, self.spans = dep, spans
        self.tickets: list = []
        self.outcome: list[bool] = []     # the upstream's outcome of each
        self.submit_t: list[float] = []
        self.ticks_before: list[int] = []  # ticks completed before submit
        self.bypass: list[bool] = []

    def submit(self, s: gen.RequestStream, i: int):
        lay = self.dep.layout
        r = int(s.rows[i])
        p = s.params[i]
        with self.spans.annotation("bench.submit"):
            self.ticks_before.append(len(self.dep.proxy.ticks))
            self.submit_t.append(time.monotonic())
            t = self.dep.fe.submit(self._Req(
                row=r, tenant=lay.tenant(r), edge=lay.edge(r),
                alpha=p[0], lambda_usd_per_s=p[1], latency_s=p[2],
                input_tokens=p[3], output_tokens=p[4], input_price=p[5],
                output_price=p[6]))
        self.tickets.append(t)
        self.outcome.append(bool(s.outcome[i]))
        # answered inside submit: shed, or the breaker sent it to the
        # scalar stage; everything else waits for a tick
        self.bypass.append(t.done())
        return t

    def settle(self, k: int) -> None:
        with self.spans.annotation("bench.settle"):
            self.tickets[k].settle(self.outcome[k])


def settle_buckets(cfg: dict) -> list[int]:
    """Every settle bucket a tick can take above ``max_batch``: the
    frontend pads the outcomes queued since the last tick to a power of
    two.  Outcomes only come from answered requests, and a client that
    falls behind stops submitting, so at most ``max_queue`` queued
    requests and one batch are answered before it settles again."""
    f = cfg["frontend"]
    top = f["max_queue"] + f["max_batch"]
    out, s = [], 2 * f["max_batch"]
    while s < 2 * top:
        out.append(s)
        s *= 2
    return out


def warm_requests(cfg: dict) -> int:
    """Requests the frontend's part of the warm-up submits: two batches."""
    return 2 * cfg["frontend"]["max_batch"]


def warm_up(dep: Deployment, clients: Clients, stream: gen.RequestStream,
            cfg: dict, seed: int) -> None:
    """Build every executable the window can reach before it opens.
    Through the frontend: a tick with no settles, and one whose settles
    fill one batch.  Then, straight through the proxy, one tick for each
    larger settle bucket (:func:`settle_buckets`), with no requests and
    that many outcomes on the coldest resident rows, so no row the
    traffic asks for moves; the reference replays these ticks too."""
    fe, B = dep.fe, cfg["frontend"]["max_batch"]
    nxt = iter(range(stream.n))
    unsettled: list[int] = []

    def batch() -> None:
        k0 = len(clients.tickets)
        for _ in range(B):
            clients.submit(stream, next(nxt))
        while fe.pump():
            pass
        unsettled.extend(range(k0, len(clients.tickets)))

    def settle_all() -> None:
        for k in unsettled:
            clients.tickets[k].result(0)
            clients.settle(k)
        unsettled.clear()

    batch()                              # no settles queued
    settle_all()
    batch()                              # a tick with max_batch settles
    settle_all()
    row = np.full(B, -1, np.int32)
    reqs = np.zeros((B, 7), dep.proxy.req_dtype)
    cold = dep.zipf.by_rank[::-1]
    cold = cold[dep.svc.store._slot_of[cold] >= 0]
    lay = dep.layout
    r = gen.rng(seed, 32)
    for s in settle_buckets(cfg):
        rows = cold[:s]
        out_row = np.full(s, -1, np.int32)
        out_row[:rows.size] = rows
        out_x = np.zeros(s, reqs.dtype)
        out_x[:rows.size] = r.random(rows.size) < lay.p_true[rows % lay.n_arch]
        dep.proxy.tick_packed(row, reqs, batch=0, out_row=out_row,
                              out_x=out_x, check_drift=cfg["drift_check"])
    # the out-of-tick telemetry append a frontend event takes
    dep.svc.log_events([(None, "breaker_close", 0.0)])


def tick_order(clients: Clients) -> list[int]:
    """Ticket -> index of the tick that answered it, or -1 for tickets the
    frontend answered itself at submit (shed, or the breaker open).  The
    batcher takes queued tickets first in, first out, in tick order."""
    out = [-1] * len(clients.tickets)
    sizes = [t[2] for t in clients.dep.proxy.ticks]
    t, used = 0, 0
    for k in range(len(clients.tickets)):
        if clients.bypass[k]:
            continue
        while t < len(sizes) and used >= sizes[t]:
            t, used = t + 1, 0
        if t >= len(sizes):
            break
        out[k] = t
        used += 1
    return out


def judge(dep: Deployment, clients: Clients, *, final, records=None,
          final_table=None) -> dict:
    """Compare the run with the reference; returns the compared numbers.
    ``final`` is (touched rows, their posteriors, their flags) as the
    service left them.  ``records`` and ``final_table`` replace the
    service's own ticks and table (the control)."""
    recs = dep.proxy.records if records is None else records
    touched, final_post, final_flags = final
    if final_table is not None:
        final_post, final_flags = final_table
    want = ref.replay(dep.tables, recs)
    cmp = ref.compare(dep.tables, recs, want, final_post=final_post,
                      final_flags=final_flags, touched=touched)
    cmp["answer_mismatch"] = (answer_mismatch(clients, recs, want)
                              if records is None else 0)
    # the exact comparisons, as one count with the limit 0
    cmp["mismatches"] = (cmp["flag_mismatch"] + cmp["kill_switch_mismatch"]
                         + cmp["answer_mismatch"])
    return cmp


def answer_mismatch(clients: Clients, recs, want: ref.Replay) -> int:
    """Tickets whose answer is not what their tick answered (served =
    D4 flag and enabled, and the EV) or whose request is not what the
    tick was handed (row and every column, in the type they were packed
    in), plus answers the frontend gave itself on rows whose kill switch
    had neither tripped nor met a tie in an earlier tick."""
    order = tick_order(clients)
    dtype = clients.dep.proxy.req_dtype
    pos = {}
    tripped_at = {}
    for t, trig in enumerate(want.triggered):
        if trig is not None:
            for r in trig.tolist():
                tripped_at.setdefault(r, t)
    bad = 0
    for k, tk in enumerate(clients.tickets):
        if not tk.done():
            continue
        res = tk.result(0)
        t = order[k]
        if t < 0:
            # the breaker opens on a kill-switch trip; a row whose kill
            # switch met a tie may have tripped at any later tick
            r, before = tk.request.row, clients.ticks_before[k]
            if (res.source == "scalar"
                    and tripped_at.get(r, len(recs)) >= before
                    and want.ambiguous_from[r] >= before):
                bad += 1
            continue
        j = pos.get(t, 0)
        pos[t] = j + 1
        rec = recs[t]
        if not rec.ok:
            continue
        served = bool(rec.flag[j] and rec.enabled[j])
        q = tk.request
        asked = np.array([q.alpha, q.lambda_usd_per_s, q.latency_s,
                          q.input_tokens, q.output_tokens, q.input_price,
                          q.output_price], dtype).astype(np.float64)
        if (res.source != "service" or res.speculate != served
                or res.EV_usd != rec.EV[j]
                or q.row != int(rec.rows[j])
                or not np.array_equal(asked, rec.reqs[j])):
            bad += 1
    return bad


def run_control(dep: Deployment, clients: Clients, dtype, *,
                final) -> dict:
    """The reference in ``dtype`` put in the service's place, judged like
    the service: the numbers it reads.  Its answers are its own, so the
    ticket check does not apply."""
    recs, ctl = ref.control_records(dep.tables, dep.proxy.records, dtype)
    touched = final[0]
    table = (ctl.post[touched],
             np.stack([ctl.enabled_final[touched].astype(np.int32),
                       ctl.run[touched].astype(np.int32)], 1))
    return judge(dep, clients, final=final, records=recs, final_table=table)


def drain(clients: Clients, pending, deadline: float) -> None:
    """Settle what is outstanding, waiting for each answer until
    ``deadline``."""
    from repro.serving.spec_bridge import SpeculationTimeout

    while pending:
        k = pending[0]
        t = clients.tickets[k]
        left = deadline - time.monotonic()
        if left <= 0:
            return
        try:
            t.result(left)
        except SpeculationTimeout:
            return
        pending.popleft()
        clients.settle(k)
    log(f"drained by {deadline - time.monotonic():.3f} s before the cap")


class ServeRun:
    """What a serving driver hands back to the harness."""

    def __init__(self, ctx, dep: Deployment, clients: Clients, *,
                 t0: float, t1: float, metrics: dict, attempted: int,
                 failed: int, data: dict, notes: dict, trace_path) -> None:
        self.ctx, self.dep, self.clients = ctx, dep, clients
        self.t0, self.t1 = t0, t1
        self.metrics, self.attempted, self.failed = metrics, attempted, failed
        self.data, self.notes, self.trace_path = data, notes, trace_path
        self.window_builds = notes["window_executables_built"]
        self._final = None

    def release(self) -> None:
        """Keep the service's final table for the touched rows, then drop
        the service and the frontend's device state."""
        recs = self.dep.proxy.records
        touched = np.unique(np.concatenate(
            [r.rows for r in recs] + [np.zeros(0, np.int64)]))
        self._final = (touched, self.dep.svc.rows_snapshot(touched),
                       self.dep.svc.store.flags_snapshot()[touched])
        self.dep.fe.service = None
        self.dep.svc = None
        self.dep.proxy._svc = None

    def judge(self) -> dict:
        return judge(self.dep, self.clients, final=self._final)

    def control(self, dtype) -> dict:
        return run_control(self.dep, self.clients, dtype, final=self._final)


def window_notes(dep: Deployment, t0: float, t1: float) -> dict:
    """The window's tick count and the largest settle bucket a tick took."""
    ticks = [t for t in dep.proxy.ticks if t0 <= t[0] <= t1]
    return dict(ticks=len(ticks),
                settle_bucket_max=max((t[5] for t in ticks), default=0))


def window_data(dep: Deployment, clients: Clients, k0: int, t0: float,
                t1: float, due, max_batch: int) -> dict:
    """What the per-layer readers of a serving cell read: the window's
    ticks (start, end, requests, settles, fault-ins, settle bucket), and
    per window request its due time, submit time and its tick's
    start."""
    ticks = [t for t in dep.proxy.ticks if t0 <= t[0] <= t1]
    order = tick_order(clients)
    starts = [t[0] for t in dep.proxy.ticks]
    n = len(clients.tickets) - k0
    tick_start = np.full(n, np.nan)
    for j in range(n):
        o = order[k0 + j]
        if o >= 0:
            tick_start[j] = starts[o]
    return dict(ticks=ticks, max_batch=max_batch,
                due=None if due is None else np.asarray(due),
                submit=np.asarray(clients.submit_t[k0:]),
                tick_start=tick_start)
