"""The program's spans: a ``ServingFrontend`` over a small paged
``OnlineDecisionService`` (fewer resident rows than the rows the ticks
touch, so rows fault in) runs under ``jax.profiler.trace``; the spans are
read back from the ``.xplane.pb`` the profiler wrote."""
import glob

import jax
import pytest
from jax.profiler import ProfileData

from repro.core.online import OnlineDecisionService
from repro.core.posterior import BetaPosterior
from repro.serving.frontend import (
    DecisionRequest,
    FrontendConfig,
    ServingFrontend,
)

ROWS, RESIDENT, BATCH, FLUSHES = 64, 16, 8, 5

SPANS = ("frontend.wait_batch", "frontend.flush", "frontend.pack",
         "frontend.answer", "frontend.refresh_mirror", "service.tick",
         "service.fetch", "service.drift_mask", "store.page_in",
         "store.slot_map")


def _request(row):
    return DecisionRequest(
        row=row, tenant="t0", edge=(f"u{row}", f"v{row}"), alpha=0.5,
        lambda_usd_per_s=0.9, latency_s=3.0, input_tokens=500.0,
        output_tokens=300.0, input_price=3e-6, output_price=15e-6)


def _events(path):
    """name -> [(start_ns, end_ns, line index)] over the host plane's
    thread lines."""
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in SPANS:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, k))
    for evs in out.values():
        evs.sort()
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Warm the tick's executables, then trace ``FLUSHES`` pumped flushes
    and one flush of the batcher thread.  Returns the span events and,
    per flush in order, the rows the store faulted in."""
    svc = OnlineDecisionService(resident_rows=RESIDENT, min_rows=RESIDENT)
    for r in range(ROWS):
        svc.register_edge((f"u{r}", f"v{r}"), tenant="t0",
                          posterior=BetaPosterior(alpha=16.0, beta=2.0))
    fe = ServingFrontend(svc, FrontendConfig(
        max_batch=BATCH, deadline_s=10.0, bulkhead_limit=ROWS,
        snapshot_refresh_ticks=2), autostart=False)
    faults = svc.store.stats
    counts, nxt = [], 0

    def submit_batch():
        nonlocal nxt
        tickets = [fe.submit(_request((nxt + i) % ROWS))
                   for i in range(BATCH)]
        nxt += BATCH
        return tickets

    def settle(tickets):
        for t in tickets:
            t.result(0)
            t.settle(True)

    for _ in range(2):                   # compile with and without settles
        tickets = submit_batch()
        fe.pump()
        settle(tickets)
    d = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(d), profiler_options=opts):
        for _ in range(FLUSHES):
            f0 = faults["fault_ins"]
            tickets = submit_batch()
            fe.pump()
            counts.append(faults["fault_ins"] - f0)
            settle(tickets)
        f0 = faults["fault_ins"]
        fe.start()
        tickets = submit_batch()
        for t in tickets:
            t.result(30)
        fe.stop()
        counts.append(faults["fault_ins"] - f0)
    path = sorted(glob.glob(str(d / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    return _events(path), counts


@pytest.mark.parametrize("name", SPANS)
def test_every_span_is_in_the_trace(traced, name):
    events, _ = traced
    assert events.get(name)


def _inside(inner, outers):
    s, e, line = inner
    return any(os <= s and e <= oe and ol == line
               for os, oe, ol in outers)


@pytest.mark.parametrize("inner,outer", [
    ("store.page_in", "service.tick"),
    ("store.slot_map", "service.tick"),
    ("service.tick", "frontend.flush"),
    ("service.fetch", "frontend.flush"),
    ("frontend.pack", "frontend.flush"),
    ("frontend.answer", "frontend.flush"),
    ("frontend.refresh_mirror", "frontend.flush"),
    ("service.drift_mask", "frontend.answer"),
])
def test_spans_nest_on_one_thread_line(traced, inner, outer):
    events, _ = traced
    assert events[inner]
    for ev in events[inner]:
        assert _inside(ev, events[outer]), (inner, ev)


@pytest.mark.parametrize("name", [
    "frontend.flush", "frontend.pack", "frontend.answer", "service.tick",
    "store.page_in",
])
def test_one_span_per_flush(traced, name):
    events, counts = traced
    assert len(events[name]) == len(counts) == FLUSHES + 1


def test_rows_fault_in_every_flush(traced):
    _, counts = traced
    assert all(c > 0 for c in counts)
