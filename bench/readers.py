"""Reductions the per-layer metric readers share.  Each reader in
``bench/metrics/<metric>.py`` takes the run's data and returns one number,
or None where the run gave it nothing to read (it never stands in a 0).

Serving data: ``ticks`` (start, end, requests, settles, store fault-ins,
settle bucket) of the window's ticks on the host clock, ``due`` /
``submit`` / ``tick_start`` per window request, ``max_batch``, and ``trace`` (a
``bench.trace.Summary`` of the traced stretch).  Replay data:
``episodes`` per job, ``episodes_per_step`` of the engine's scan,
``job_seconds`` of the window's jobs, and ``trace`` over a stretch
inside one job's scan.
"""
from __future__ import annotations

import numpy as np

from .common import median, quantile

# the jitted service tick by the name its module carries in a trace: the
# chip's trace names ``core/online.py``'s ``_tick`` after the function it
# wraps, ``jit__tick_impl(<hash>)``
TICK_MODULE = r"_tick(_impl)?\("


def gen_lag_p99_ms(d):
    if d.get("due") is None or not len(d["submit"]):
        return None
    return quantile(((d["submit"] - d["due"]) * 1e3).tolist(), 0.99)


def batch_fill(d):
    if not d["ticks"]:
        return None
    return float(np.mean([t[2] for t in d["ticks"]])) / d["max_batch"]


def queue_wait_ms(d):
    if d.get("due") is None:
        return None
    w = (d["tick_start"] - d["due"])[~np.isnan(d["tick_start"])]
    return median((w * 1e3).tolist()) if w.size else None


def host_tick_ms(d):
    if not d["ticks"]:
        return None
    return median([(t[1] - t[0]) * 1e3 for t in d["ticks"]])


def store_faults_per_tick(d):
    if not d["ticks"]:
        return None
    return sum(t[4] for t in d["ticks"]) / len(d["ticks"])


def tick_device_ms(d):
    s = d.get("trace")
    if s is None:
        return None
    sec, n = s.module_seconds(TICK_MODULE)
    return sec / n * 1e3 if n else None


def device_idle(d):
    s = d.get("trace")
    if s is None or s.window_s <= 0:
        return None
    return 1.0 - s.mean_busy_s() / s.window_s


def replay_device_us_per_episode(d):
    """Device microseconds per episode: the time one iteration of the
    replay's episode scan takes on the device, read from the trace of a
    stretch inside the scan (``bench.trace.loop_step``), over the
    episodes one iteration replays."""
    s = d.get("trace")
    if s is None or not d.get("episodes_per_step"):
        return None
    step = s.loop_step_s()
    return None if step is None else step / d["episodes_per_step"] * 1e6
