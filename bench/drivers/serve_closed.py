"""Closed loop: a fixed number of agents, each of which submits its next
decision request only when its previous one was answered and settled
(the agent's step waits on the decision).  The service sees full
batches; what is judged is how many decisions it completes.

End-to-end: ``decisions_per_s``, the requests answered inside the window
(not shed, not a tick-fault fallback) over the window's length.
"""
from __future__ import annotations

import collections
import time

from bench import gen, serving
from bench.common import log


def measure(ctx):
    from repro.serving.spec_bridge import SpeculationTimeout

    cfg, tr = ctx.config, ctx.workload["traffic"]
    B = cfg["frontend"]["max_batch"]
    W = float(ctx.seconds)
    agents = int(tr["agents"])
    dep = serving.build(cfg, ctx.seed, ctx.spans)
    serving.fill_resident_table(dep, B)
    stream = gen.GrowingStream(dep.layout, dep.zipf, cfg["request_ranges"],
                               ctx.seed)
    warm = gen.RequestStream(
        dep.layout, dep.zipf, cfg["request_ranges"], ctx.seed, 2,
        gen.rng(ctx.seed, 30).random(serving.warm_requests(cfg)))
    clients = serving.Clients(dep, ctx.spans)
    serving.warm_up(dep, clients, warm, cfg, ctx.seed)

    dep.fe.start()
    tracer = ctx.tracer
    tickets = clients.tickets
    k0 = len(tickets)
    ctx.gc.mark()
    mark = ctx.meter.mark()
    t0 = time.monotonic()
    setup_s = t0 - ctx.t_start
    t1 = t0 + W
    trace_on = t0 + serving.TRACE_FROM * W
    traced = tracer is None
    pending = collections.deque()
    nxt = 0

    def submit_next() -> None:
        nonlocal nxt
        if nxt == stream.n:
            stream.grow()
        clients.submit(stream, nxt)
        pending.append(len(tickets) - 1)
        nxt += 1

    for _ in range(agents):
        submit_next()
    while pending:
        now = time.monotonic()
        if now >= t1:
            break
        if not traced and now >= trace_on:
            tracer.begin(tr["trace_seconds"])
            traced = True
        try:
            tickets[pending[0]].result(min(t1 - now, 0.05))
        except SpeculationTimeout:
            continue
        while pending and tickets[pending[0]].done():
            clients.settle(pending.popleft())
            submit_next()
    in_window = ctx.meter.since(mark)
    gc_window = ctx.gc.since_mark()
    serving.drain(clients, pending, t1 + serving.DRAIN_S)
    trace_path = tracer.join() if tracer is not None else None
    dep.fe.stop()

    answered, failed, attempted = 0, 0, 0
    src = collections.Counter()
    for k in range(k0, len(tickets)):
        tk = tickets[k]
        attempted += 1
        if not tk.done():
            src["unresolved"] += 1
            failed += 1
            continue
        res = tk.result(0)
        src[res.source] += 1
        ok = res.source == "service" or (res.source == "scalar"
                                         and clients.bypass[k])
        if not ok:
            failed += 1
        elif serving.resolved_at(tk) <= t1:
            answered += 1
    rate = answered / W
    notes = dict(window_executables_built=in_window, sources=dict(src),
                 agents=agents, answered_in_window=answered,
                 setup_s=setup_s, stream_chunks=stream.chunks,
                 **serving.window_notes(dep, t0, t1), **gc_window)
    log(f"window: {notes}")
    data = serving.window_data(dep, clients, k0, t0, t1, None, B)
    return serving.ServeRun(
        ctx, dep, clients, t0=t0, t1=t1,
        metrics={"decisions_per_s": rate, "setup_s": setup_s},
        attempted=attempted, failed=failed, data=data, notes=notes,
        trace_path=trace_path)
