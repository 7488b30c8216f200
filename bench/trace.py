"""Profiler traces: capture a short stretch of a run, and reduce the
trace to what the per-layer metrics read.

The reduction works on the ``.xplane.pb`` JAX's profiler writes, read
with ``jax.profiler.ProfileData``:

* device planes are those named ``/device:TPU:<n>``; their ``XLA Ops``
  line holds one event per operation run, their ``XLA Modules`` line one
  per executable run (named after the jitted function);
* busy time of a device is the union of its operation intervals inside
  the traced stretch, and idle is the rest of the stretch;
* the traced stretch is the host event ``bench.traced``, which the
  benchmark opens and closes around what it wants measured;
* the benchmark's own host spans (``bench.*`` annotations) say what the
  host was doing in each idle gap of the device.
"""
from __future__ import annotations

import dataclasses
import glob
import pathlib
import re
import shutil
import threading

WINDOW_EVENT = "bench.traced"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Summary:
    """A traced stretch, reduced.  Times are in seconds, on the trace's
    own clock (only differences are meaningful)."""

    window: tuple[float, float]
    busy_s: list[float]                    # per device
    # per device: the executables that ran wholly inside the stretch
    modules: list[list[tuple[str, float, float]]]
    op_seconds: dict[str, float]           # summed over devices
    idle_gaps: dict[str, float]            # host activity -> seconds
    # per device: (name, start) of every operation that started inside
    # the stretch, in the order it started
    op_starts: list[list[tuple[str, float]]] = dataclasses.field(
        default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def devices(self) -> int:
        return len(self.busy_s)

    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    def module_seconds(self, pattern: str) -> tuple[float, int]:
        """Summed device seconds and count, per device on average, of the
        executables whose name matches ``pattern`` and that ran wholly
        inside the stretch (one cut by its edge would count as a whole
        run with part of its time)."""
        rx = re.compile(pattern)
        tot, n = 0.0, 0
        for mods in self.modules:
            for name, s, e in mods:
                if rx.search(name):
                    tot += e - s
                    n += 1
        d = max(1, self.devices)
        return tot / d, n / d

    def loop_step_s(self) -> float | None:
        """Device seconds per iteration of the loop that fills the
        stretch, as the mean over devices, or None where the stretch's
        operations do not repeat as one loop body does (see
        :func:`loop_step`)."""
        steps = [loop_step(ops) for ops in self.op_starts]
        if not steps or any(s is None for s in steps):
            return None
        return sum(steps) / len(steps)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _union(intervals):
    """Merge ``(start, end)`` intervals; returns the merged list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def loop_step(ops, *, match: float = 0.99) -> float | None:
    """Seconds per iteration of a device loop, from the operations one
    device started, in order: ``(name, start)``.

    A loop body runs the same operations in the same order each time, so
    the sequence of names repeats with a period of ``P`` operations, and
    a name the body runs once recurs every ``P`` places (a name an inner
    loop runs twice recurs at shorter, uneven distances).  The longest
    constant recurrence of any name is a multiple of ``P``; ``P`` is its
    least divisor with which the sequence repeats (``match`` of its
    places).  Where the sequence does not repeat, this is no single loop
    and the answer is None.  The time per iteration is taken between the
    first and the last start of the name with the longest recurrence:
    whole iterations only, every gap inside them counted."""
    names = [n for n, _ in ops]
    where: dict[str, list[int]] = {}
    for i, n in enumerate(names):
        where.setdefault(n, []).append(i)
    period, anchor = 0, None
    for n, idx in where.items():
        if len(idx) < 3:
            continue
        gaps = {b - a for a, b in zip(idx, idx[1:])}
        if len(gaps) == 1:
            g = gaps.pop()
            if g > period or (g == period and len(idx) > len(where[anchor])):
                period, anchor = g, n
    if anchor is None or len(names) < 3 * period:
        return None

    def repeats(p: int) -> bool:
        same = sum(names[i] == names[i + p] for i in range(len(names) - p))
        return same >= match * (len(names) - p)

    p = next((d for d in range(1, period + 1)
              if period % d == 0 and repeats(d)), None)
    if p is None:
        return None
    idx = where[anchor]
    return (ops[idx[-1]][1] - ops[idx[0]][1]) / ((idx[-1] - idx[0]) / p)


def _host_activity(spans, lo, hi) -> str:
    """The bench span covering most of [lo, hi], or "no bench span"."""
    best, cover = "no bench span", 0.0
    acc = {}
    for name, s, e in spans:
        ov = min(e, hi) - max(s, lo)
        if ov > 0:
            acc[name] = acc.get(name, 0.0) + ov
    for name, v in acc.items():
        if v > cover:
            best, cover = name, v
    return best


def reduce_planes(planes):
    """Reduce profiler planes (``ProfileData.planes`` or the same shape in
    plain objects: ``name``, ``lines`` of ``name`` and ``events`` with
    ``name``, ``start_ns``, ``duration_ns``).  None where no TPU plane
    is in the trace."""
    host_spans, window = [], None
    devices = {}
    for plane in planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if ev.name == WINDOW_EVENT:
                        window = (s, e)
                    else:
                        host_spans.append((ev.name, s, e))
    if window is None:
        raise ValueError(f"no {WINDOW_EVENT!r} event in the trace")
    if not devices:
        return None                     # a run without a chip
    lo, hi = window
    busy, modules, op_s, gaps, starts = [], [], {}, {}, []
    for idx in sorted(devices):
        plane = devices[idx]
        ops, mods, seq = [], [], []
        for line in plane.lines:
            if line.name == _OPS_LINE:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    c = _clip([(s, e)], lo, hi)
                    if c:
                        ops.append(c[0])
                        if s >= lo:
                            seq.append((ev.name, s))
                        op_s[ev.name] = op_s.get(ev.name, 0.0) + c[0][1] - c[0][0]
            elif line.name == _MODULES_LINE:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if lo <= s and e <= hi:
                        mods.append((ev.name, s, e))
        merged = _union(ops)
        busy.append(sum(e - s for s, e in merged))
        modules.append(mods)
        seq.sort(key=lambda x: x[1])
        starts.append(seq)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                who = _host_activity(host_spans, gs, ge)
                gaps[who] = gaps.get(who, 0.0) + (ge - gs) / len(devices)
    return Summary(window=window, busy_s=busy, modules=modules,
                   op_seconds={k: v / len(devices) for k, v in op_s.items()},
                   idle_gaps=gaps, op_starts=starts)


def reduce_file(path) -> Summary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(path)).planes)


class Tracer:
    """Profiles one stretch of a run into one fixed directory.

    ``begin(seconds)`` returns once the profiler runs: a helper thread
    starts JAX's profiler, marks the stretch with the ``bench.traced``
    host event for ``seconds`` and stops the profiler, so the thread
    driving the load is not held while the profiler writes its file (a
    span opened before the profiler starts is not in the trace).
    ``join`` waits for the file.  Python-level tracing and HLO protos are off:
    the reduction reads neither."""

    def __init__(self, directory: pathlib.Path) -> None:
        self.dir = pathlib.Path(directory)
        self._thread = None
        self._error = None
        self._started = threading.Event()
        self.timings = {}

    def begin(self, seconds: float) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        self._thread = threading.Thread(target=self._stretch,
                                        args=(seconds,), name="bench-trace")
        self._thread.start()
        self._started.wait()

    def _stretch(self, seconds: float) -> None:
        import time

        import jax

        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            t = time.monotonic()
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.timings["start_s"] = time.monotonic() - t
            with jax.profiler.TraceAnnotation(WINDOW_EVENT):
                self._started.set()
                time.sleep(seconds)
            t = time.monotonic()
            jax.profiler.stop_trace()
            self.timings["stop_s"] = time.monotonic() - t
        except Exception as e:          # handed to the joining thread
            self._error = e
        finally:
            self._started.set()

    def join(self) -> pathlib.Path:
        if self._thread is None:
            raise RuntimeError("no stretch was traced")
        self._thread.join()
        if self._error is not None:
            raise self._error
        found = glob.glob(str(self.dir / "**" / "*.xplane.pb"), recursive=True)
        if not found:
            raise RuntimeError(f"no trace written under {self.dir}")
        return pathlib.Path(sorted(found)[-1])
