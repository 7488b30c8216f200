"""Pieces every driver shares: where the repo is, the compile cache, the
device check, the compile meter, host spans, and small statistics.

Nothing here imports the program; drivers import it after
:func:`prepare_jax` has placed the compile cache.
"""
from __future__ import annotations

import contextlib
import json
import math
import os
import pathlib
import statistics
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "_out"                    # traces and scratch (git-ignored)
CACHE_DIR = ROOT / ".jax_cache"         # the program's own fixed choice


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def add_program_to_path() -> None:
    """Make ``repro`` (under ``src/``) importable.  In a directory that
    holds only the benchmark, the import that follows fails."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def prepare_jax():
    """Place JAX's persistent compilation cache before the first
    computation and cache every executable, however fast it compiled.

    The placement rule is the program's (``launch.compile_cache``):
    ``JAX_COMPILATION_CACHE_DIR`` where it is set, else one fixed
    directory inside the checkout.  The TPU runtime's own logs go inside
    the checkout too, not to a fixed path under ``/tmp``.  Returns the
    ``jax`` module."""
    os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def peaks_for(kind: str) -> dict:
    """The published peaks of one chip, keyed by ``device_kind``."""
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise NoAccelerator(f"no peaks for device kind {kind!r}; "
                            f"known: {sorted(table)}")
    return table[kind]


def check_device(chips: int, *, require_tpu: bool = True) -> dict:
    """Name the device; refuse anything but a TPU with ``chips`` chips."""
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if require_tpu:
        if dev["platform"] != "tpu":
            raise NoAccelerator(f"default device is {dev['platform']!r}, "
                                "not a TPU")
        if len(devices) < chips:
            raise NoAccelerator(f"{len(devices)} chips, the cell asks for "
                                f"{chips}")
        peaks_for(dev["kind"])
    return dev


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the chips used, where the
    backend reports it."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileMeter:
    """Executables JAX lowered, compiled or took from the persistent
    cache, counted from JAX's own monitoring events."""

    _EVENTS = {
        "/jax/core/compile/backend_compile_duration": "compiles",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
    }

    def __init__(self) -> None:
        import jax

        self.counts = {"compiles": 0, "lowerings": 0}
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        key = self._EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1
            if key == "compiles":
                self.compile_s += duration

    def mark(self) -> dict:
        return dict(self.counts)

    def since(self, mark: dict) -> int:
        """Executables built (lowered, whether then compiled or read from
        the cache) since ``mark``."""
        return self.counts["lowerings"] - mark["lowerings"]


class GcPauses:
    """Seconds the interpreter spent collecting garbage, from ``mark`` on,
    and how many of those collections were full (generation 2) ones."""

    def __init__(self) -> None:
        import gc

        self.seconds, self.full, self._t = 0.0, 0, None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.seconds += time.monotonic() - self._t
            self.full += info["generation"] == 2
            self._t = None

    def mark(self) -> None:
        self.seconds, self.full = 0.0, 0

    def since_mark(self) -> dict:
        return {"gc_s": self.seconds, "gc_full": self.full}


class Spans:
    """Host spans of the benchmark's own calls into the program, kept in
    memory: ``(name, start, end)`` on ``time.monotonic``.  With
    ``annotate`` each span is also a ``jax.profiler.TraceAnnotation``,
    so it lands in the profiler's trace beside the device's events."""

    def __init__(self, annotate: bool = False) -> None:
        self.annotate = annotate
        self.records: list[tuple[str, float, float]] = []
        if annotate:
            import jax

            self._annotation = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.monotonic()
        if self.annotate:
            with self._annotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.monotonic()))

    def annotation(self, name: str):
        """A trace annotation alone (no record), or a null context."""
        if self.annotate:
            return self._annotation(name)
        return contextlib.nullcontext()


def quantile(values, q: float) -> float:
    """The q-quantile by the nearest-rank rule (a tail counts whole
    samples; no interpolation between two latencies)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    k = min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))
    return xs[k]


def median(values) -> float:
    return statistics.median(values)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
