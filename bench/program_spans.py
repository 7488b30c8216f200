"""The serving program's own spans, reduced to what the per-layer metrics
of the frontend batcher, the host tick path and store paging read.

The program marks its hot path with ``jax.profiler.TraceAnnotation``
spans named after their module (``frontend.*``, ``service.*``,
``store.*``).  They land on the host plane of the profiler's trace, one
line per thread, on the same clock as the device's operations, so each
span's time and each device idle gap can be put down to a phase of the
batcher thread.

The reduction reads the cell's own trace, the newest ``*.xplane.pb``
under ``bench/_out/trace/<cell>/`` (where ``bench.trace.Tracer`` writes
it), and only where its ``bench.traced`` stretch is the one the run's
``data["trace"]`` summary holds.  On it:

* the batcher thread is the host line that holds ``frontend.flush``;
* a span's self time is its length less that of the spans (program or
  ``bench.*``) directly inside it; every moment of a flush is the self
  time of exactly one span, so the phases below add up to the flush;
* per flush, of the flushes that lie wholly inside the stretch:
  ``frontend_host`` is the self time of ``frontend.flush``,
  ``frontend.pack`` and ``frontend.answer``; ``service_host`` that of
  every ``service.*`` span but ``service.fetch``; ``page_in`` that of
  ``store.*``; ``device_wait`` that of ``service.fetch`` (the host
  blocked on the device, plus the copy); ``refresh_mirror`` that of
  ``frontend.refresh_mirror``;
* ``device_idle_host`` is the share of the stretch in which the device
  is idle while the batcher thread's innermost program span is neither
  ``service.fetch`` nor ``frontend.wait_batch``: idle caused by host
  work on the tick's critical path (averaged over devices).

A program without these spans reads None throughout, never 0.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import pathlib
import statistics

from .common import OUT, ROOT, load_json
from .trace import _DEVICE_PLANE, _OPS_LINE, WINDOW_EVENT, _clip, _union

PROGRAM = ("frontend.", "service.", "store.")
FLUSH = "frontend.flush"
FETCH = "service.fetch"
WAIT = "frontend.wait_batch"

# flush phase -> does a span of this name count towards it
PHASES = {
    "frontend_host": lambda n: n in (FLUSH, "frontend.pack",
                                     "frontend.answer"),
    "refresh_mirror": lambda n: n == "frontend.refresh_mirror",
    "service_host": lambda n: n.startswith("service.") and n != FETCH,
    "page_in": lambda n: n.startswith("store."),
    "device_wait": lambda n: n == FETCH,
}


def _program(name: str) -> bool:
    return name.startswith(PROGRAM)


def _kept(name: str) -> bool:
    return _program(name) or name.startswith("bench.")


@dataclasses.dataclass
class Span:
    """One span on a thread line, its times in the trace's nanoseconds."""

    name: str
    start: float
    end: float
    children: list = dataclasses.field(default_factory=list)

    @property
    def self_time(self) -> float:
        """Length less that of the spans directly inside."""
        return (self.end - self.start
                - sum(c.end - c.start for c in self.children))

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


def nest(events) -> list[Span]:
    """The span trees of one thread line: ``(name, start, end)`` events,
    of which an event wholly inside another is its child."""
    roots, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        sp = Span(name, s, e)
        while stack and stack[-1].end < e:
            stack.pop()
        (stack[-1].children if stack else roots).append(sp)
        stack.append(sp)
    return roots


def innermost(roots, keep) -> list[tuple[str, float, float]]:
    """The thread's time as ``(owner, start, end)`` pieces, where the
    owner is the innermost span whose name ``keep`` accepts (a span it
    refuses is transparent: its own time goes to its owner)."""
    out = []

    def visit(sp: Span, owner):
        if keep(sp.name):
            owner = sp.name
        t = sp.start
        for c in sp.children:
            if c.start > t and owner is not None:
                out.append((owner, t, c.start))
            visit(c, owner)
            t = max(t, c.end)
        if sp.end > t and owner is not None:
            out.append((owner, t, sp.end))

    for r in roots:
        visit(r, None)
    return out


def _overlap(pieces, gaps) -> float:
    """How long a ``(start, end)`` of ``pieces`` and one of the sorted,
    disjoint ``gaps`` are both open, in their own unit."""
    ends = [e for _, e in gaps]
    tot = 0.0
    for s, e in pieces:
        i = bisect.bisect_right(ends, s)
        while i < len(gaps) and gaps[i][0] < e:
            tot += min(e, gaps[i][1]) - max(s, gaps[i][0])
            i += 1
    return tot


@dataclasses.dataclass
class Trace:
    """A trace's host lines and device idle gaps.  Times are kept in the
    trace's own nanoseconds, so that nesting is exact; what the methods
    return is in seconds.  ``window`` is the stretch in seconds, as
    ``bench.trace.Summary.window`` has it."""

    window: tuple[float, float]
    stretch: tuple[float, float]        # the same, in nanoseconds
    lines: dict[str, list[Span]]        # host line -> span trees
    idle: list[list[tuple[float, float]]]   # per device: idle gaps

    @property
    def window_s(self) -> float:
        return (self.stretch[1] - self.stretch[0]) * 1e-9

    def batcher(self) -> list[Span] | None:
        """The span trees of the line that holds the most flushes."""
        best, n = None, 0
        for roots in self.lines.values():
            k = sum(1 for r in roots for sp in r.walk() if sp.name == FLUSH)
            if k > n:
                best, n = roots, k
        return best

    def flushes(self) -> list[dict]:
        """Per flush wholly inside the stretch, seconds of each phase."""
        roots = self.batcher() or []
        lo, hi = self.stretch
        out = []
        for r in roots:
            for f in r.walk():
                if f.name != FLUSH or f.start < lo or f.end > hi:
                    continue
                ph = dict.fromkeys(PHASES, 0.0)
                for sp in f.walk():
                    for k, match in PHASES.items():
                        if match(sp.name):
                            ph[k] += sp.self_time * 1e-9
                out.append(ph)
        return out

    def idle_s(self, pieces) -> float:
        """Device idle seconds, mean over devices, inside ``pieces``."""
        pieces = _union(_clip(pieces, *self.stretch))
        ns = sum(_overlap(pieces, g) for g in self.idle) / len(self.idle)
        return ns * 1e-9

    def idle_host_share(self) -> float | None:
        """``device_idle_host``: see the module's docstring."""
        roots = self.batcher()
        if roots is None or self.window_s <= 0:
            return None
        host = [(s, e) for o, s, e in innermost(roots, _program)
                if o not in (FETCH, WAIT)]
        return self.idle_s(host) / self.window_s

    def idle_by_span(self) -> dict[str, dict[str, float]]:
        """Per host line holding kept spans: device idle seconds (mean
        over devices) by the line's innermost program or ``bench.*`` span
        at the time, ``-`` where it was in none."""
        total = self.idle_s([self.stretch])
        out = {}
        for line, roots in self.lines.items():
            by = {}
            for owner, s, e in innermost(roots, _kept):
                by.setdefault(owner, []).append((s, e))
            acc = {owner: self.idle_s(p) for owner, p in by.items()}
            acc["-"] = total - sum(acc.values())
            out[line] = acc
        return out

    def self_times(self) -> dict[str, list[float]]:
        """Self seconds of every kept span wholly inside the stretch, by
        name, over all lines."""
        lo, hi = self.stretch
        out = {}
        for roots in self.lines.values():
            for r in roots:
                for sp in r.walk():
                    if lo <= sp.start and sp.end <= hi:
                        out.setdefault(sp.name, []).append(sp.self_time * 1e-9)
        return out


def reduce_planes(planes) -> Trace | None:
    """Reduce profiler planes (``ProfileData.planes`` or plain objects of
    the same shape, as ``bench.trace.reduce_planes`` takes).  None where
    the trace has no program flush or no device plane."""
    window = stretch = None
    lines, devices = {}, []
    for plane in planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append([
                (ev.start_ns, ev.start_ns + ev.duration_ns)
                for line in plane.lines if line.name == _OPS_LINE
                for ev in line.events])
            continue
        if not plane.name.startswith("/host"):
            continue
        for k, line in enumerate(plane.lines):
            evs = []
            for ev in line.events:
                if not _kept(ev.name):
                    continue
                if ev.name == WINDOW_EVENT:
                    s = ev.start_ns * 1e-9
                    window = (s, s + ev.duration_ns * 1e-9)
                    stretch = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    continue
                evs.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
            if evs:
                lines[f"{k}:{line.name}"] = nest(evs)
    if window is None or not devices:
        return None
    if not any(sp.name == FLUSH for roots in lines.values()
               for r in roots for sp in r.walk()):
        return None
    lo, hi = stretch
    idle = []
    for ops in devices:
        busy = _union(_clip(ops, lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle.append([(s, e) for s, e in zip(edges[::2], edges[1::2])
                     if e > s])
    return Trace(window=window, stretch=stretch, lines=lines, idle=idle)


def reduce_file(path) -> Trace | None:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(path)).planes)


@functools.lru_cache(maxsize=1)
def _reduce_cached(path: str, mtime_ns: int) -> Trace | None:
    return reduce_file(path)


def cell_trace(cell: str, data: dict) -> Trace | None:
    """The reduced trace of the cell's run, once per file: None where the
    cell wrote no trace or its stretch is not the run's."""
    summary = data.get("trace")
    found = glob.glob(str(OUT / "trace" / cell / "**" / "*.xplane.pb"),
                      recursive=True)
    if summary is None or not found:
        return None
    path = max(found, key=os.path.getmtime)
    t = _reduce_cached(path, os.stat(path).st_mtime_ns)
    if t is None or t.window != tuple(summary.window):
        return None
    return t


def _median_ms(phase: str):
    def read(t: Trace):
        fl = t.flushes()
        return statistics.median(f[phase] for f in fl) * 1e3 if fl else None
    return read


QUANTITIES = {
    "frontend_host_ms": _median_ms("frontend_host"),
    "service_host_ms": _median_ms("service_host"),
    "page_in_ms": _median_ms("page_in"),
    "device_wait_ms": _median_ms("device_wait"),
    "device_idle_host": Trace.idle_host_share,
}


def reader(metric_file: str):
    """The ``read(data)`` of ``bench/metrics/<quantity>.<suffix>.py``: the
    quantity is the file name's first part, and the trace read is that
    of the metric's first cell in ``BENCHMARK.json``."""
    name = pathlib.Path(metric_file).name[: -len(".py")]
    quantity = QUANTITIES[name.split(".")[0]]

    def read(data):
        bench = load_json(ROOT / "BENCHMARK.json")
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        t = cell_trace(entry["workloads"][0], data)
        return None if t is None else quantity(t)
    return read
