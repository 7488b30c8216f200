"""Open loop: independent agents' decision requests arrive on a Poisson
schedule at a rate fixed in the cell, whether or not earlier ones were
answered.  Each request is timed from when it was due, so a stall that
delays later submits shows in their latency.

End-to-end: ``decision_p99_ms``, the 99th percentile over every request
due in the window of its answer time minus its due time; a request that
was shed, answered by a tick-fault fallback or never answered counts as
longer than any latency.
"""
from __future__ import annotations

import collections
import math
import time

import numpy as np

from bench import gen, serving
from bench.common import log, quantile


def measure(ctx):
    cfg, tr = ctx.config, ctx.workload["traffic"]
    B = cfg["frontend"]["max_batch"]
    W = float(ctx.seconds)
    dep = serving.build(cfg, ctx.seed, ctx.spans)
    serving.fill_resident_table(dep, B)
    rate = float(tr["rate_per_s"])
    n = int(round(rate * W))
    stream = gen.RequestStream(dep.layout, dep.zipf, cfg["request_ranges"],
                               ctx.seed, 1, gen.base_uniforms(n, ctx.seed))
    warm = gen.RequestStream(
        dep.layout, dep.zipf, cfg["request_ranges"], ctx.seed, 2,
        gen.rng(ctx.seed, 30).random(serving.warm_requests(cfg)))
    clients = serving.Clients(dep, ctx.spans)
    serving.warm_up(dep, clients, warm, cfg, ctx.seed)
    gaps = gen.poisson_gaps(rate, n, ctx.seed)
    offsets = np.r_[0.0, np.cumsum(gaps)[:-1]] * (W / gaps.sum())

    dep.fe.start()
    tracer = ctx.tracer
    k0 = len(clients.tickets)
    ctx.gc.mark()
    mark = ctx.meter.mark()
    t0 = time.monotonic() + 0.01
    setup_s = t0 - ctx.t_start
    due = t0 + offsets
    t1 = t0 + W
    trace_on = t0 + serving.TRACE_FROM * W
    traced = tracer is None
    pending = collections.deque()
    tickets = clients.tickets

    def settle_done() -> None:
        while pending and tickets[pending[0]].done():
            clients.settle(pending.popleft())

    for i in range(n):
        d = due[i]
        while True:
            settle_done()
            now = time.monotonic()
            if now >= d:
                break
            time.sleep(min(d - now, 0.001))
        if not traced and now >= trace_on:
            tracer.begin(tr["trace_seconds"])
            traced = True
        clients.submit(stream, i)
        pending.append(len(tickets) - 1)
    while time.monotonic() < t1:
        settle_done()
        time.sleep(0.001)
    in_window = ctx.meter.since(mark)
    gc_window = ctx.gc.since_mark()
    serving.drain(clients, pending, t1 + serving.DRAIN_S)
    trace_path = tracer.join() if tracer is not None else None
    dep.fe.stop()

    lat, failed = [], 0
    src = collections.Counter()
    for j in range(n):
        k = k0 + j
        tk = tickets[k]
        if not tk.done():
            src["unresolved"] += 1
            failed += 1
            lat.append(math.inf)
            continue
        res = tk.result(0)
        src[res.source] += 1
        ok = res.source == "service" or (res.source == "scalar"
                                         and clients.bypass[k])
        if not ok:
            failed += 1
        lat.append((serving.resolved_at(tk) - due[j]) * 1e3 if ok
                   else math.inf)
    p99 = float(quantile(lat, 0.99))
    late = sum(1 for tk in tickets[k0:]
               if not tk.done() or serving.resolved_at(tk) > t1)
    notes = dict(window_executables_built=in_window, sources=dict(src),
                 offered_per_s=rate, requests=n, setup_s=setup_s,
                 in_flight_at_close=late, p50_ms=float(quantile(lat, 0.5)),
                 **serving.window_notes(dep, t0, t1), **gc_window)
    log(f"window: {notes}")
    data = serving.window_data(dep, clients, k0, t0, t1, due, B)
    return serving.ServeRun(
        ctx, dep, clients, t0=t0, t1=t1,
        metrics={"decision_p99_ms": p99, "setup_s": setup_s},
        attempted=n, failed=failed, data=data, notes=notes,
        trace_path=trace_path)
