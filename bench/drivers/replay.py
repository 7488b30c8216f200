"""Back-to-back §12.1 calibration replays: each job replays a fresh seeded
AutoReply log of E episodes over the G = alphas x lambdas grid, through
``fleet_replay`` (one chip) or ``episode_sharded_replay`` (segments over
the fleet mesh).  Jobs start only while the window is open; each one
started is waited for.

End-to-end: ``replay_cells_per_s``, the episodes x grid points of the
jobs completed over the time from the window's start to the end of the
last of them.

A traced run profiles a stretch inside the episode scan of one job: it
opens a set share into the job, measured by the job before it, so the
log's way to the device and the reports' way back lie outside it.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from bench import gen
from bench.common import log
from bench.reference import replay as ref

REPORT_FIELDS = ref.FIELDS_EG + ref.FIELDS_EGV
# the traced job: the window's second, timed by its first
TRACED_JOB = 2
# the traced stretch opens this share of the job before it into the job,
# and lasts at most this share of it, so it lies inside the scan
TRACE_OFFSET = 0.3
TRACE_SHARE = 0.3


class ReplayRun:
    def __init__(self, ctx, jobs, metrics, attempted, failed, data, notes,
                 trace_path) -> None:
        self.ctx, self.jobs = ctx, jobs
        self.metrics, self.attempted, self.failed = metrics, attempted, failed
        self.data, self.notes, self.trace_path = data, notes, trace_path
        self.wf = ref.Workflow(ctx.config)
        self.alphas, self.lambdas = gen.grid(ctx.config)
        self.window_builds = notes["window_executables_built"]

    def release(self) -> None:
        """The reports are host arrays already; free the device."""
        import jax

        jax.clear_caches()

    def _fold(self, per_job) -> dict:
        out = {}
        for cmp in per_job:
            for k, v in cmp.items():
                out[k] = max(out.get(k, v), v) if k.endswith("gap") \
                    else out.get(k, 0) + v
        return out

    def judge(self) -> dict:
        return self._fold(
            ref.check(self.wf, rep, succ, self.alphas, self.lambdas)
            for succ, rep in self.jobs)

    def control(self, dtype) -> dict:
        """The reference in ``dtype`` put in the engine's place on the
        same logs, judged like the engine."""
        return self._fold(
            ref.check(self.wf,
                      ref.simulate(self.wf, succ, self.alphas, self.lambdas,
                                   dtype),
                      succ, self.alphas, self.lambdas)
            for succ, _ in self.jobs)


def _engine(ctx, lowered, alphas, lambdas):
    tr = ctx.workload["traffic"]
    if tr["engine"] == "fleet_replay":
        from repro.core import fleet_replay

        return lambda success: fleet_replay(lowered, success, alphas, lambdas)
    if tr["engine"] == "episode_sharded_replay":
        from repro.core import episode_sharded_replay
        from repro.launch.mesh import make_fleet_mesh

        mesh = make_fleet_mesh(ctx.chips)
        return lambda success: episode_sharded_replay(
            lowered, success, alphas, lambdas, n_segments=tr["segments"],
            mesh=mesh, pipelined=tr["pipelined"])
    raise ValueError(f"unknown replay engine {tr['engine']!r}")


def measure(ctx):
    cfg, tr = ctx.config, ctx.workload["traffic"]
    E = int(tr.get("episodes", cfg["episodes"]))
    W = float(ctx.seconds)
    lowered = gen.autoreply_workflow(cfg)
    vi = lowered.names.index(cfg["ops"][1]["name"])
    alphas, lambdas = gen.grid(cfg)
    run_engine = _engine(ctx, lowered, alphas, lambdas)

    def job(j: int):
        s = gen.autoreply_success(cfg, E, ctx.seed, j)
        success = np.zeros((E, lowered.n_ops), bool)
        success[:, vi] = s
        with ctx.spans.span("bench.replay_job"):
            rep = run_engine(success)
        return s, {f: getattr(rep, f) for f in REPORT_FIELDS}

    job(0)                                # warm-up: builds every executable
    ctx.gc.mark()
    mark = ctx.meter.mark()
    tracer = ctx.tracer
    trace_path = None
    jobs, ends = [], []
    t0 = time.monotonic()
    setup_s = t0 - ctx.t_start
    t1 = t0 + W
    j = 1
    while time.monotonic() < t1:
        timer = None
        if tracer is not None and j == TRACED_JOB:
            last = ends[-1] - (ends[-2] if len(ends) > 1 else t0)
            timer = threading.Timer(
                TRACE_OFFSET * last, tracer.begin,
                (min(tr["trace_seconds"], TRACE_SHARE * last),))
            timer.start()
        jobs.append(job(j))
        ends.append(time.monotonic())
        if timer is not None:
            timer.join()
        j += 1
    in_window = ctx.meter.since(mark)
    gc_window = ctx.gc.since_mark()
    if tracer is not None:
        if j <= TRACED_JOB:
            raise RuntimeError("the window closed before the traced job")
        trace_path = tracer.join()
    G = alphas.shape[0]
    cells = E * G * len(jobs)
    rate = cells / (ends[-1] - t0)
    durations = [e - s for n, s, e in ctx.spans.records
                 if n == "bench.replay_job"]
    notes = dict(window_executables_built=in_window, jobs=len(jobs),
                 episodes=E, grid_points=G, setup_s=setup_s,
                 warm_job_s=durations[0], job_s=durations[1:], **gc_window)
    log(f"window: {notes}")
    data = dict(episodes=E, job_seconds=durations[1:],
                episodes_per_step=1 if tr["engine"] == "fleet_replay"
                else None)
    return ReplayRun(ctx, jobs,
                     {"replay_cells_per_s": rate, "setup_s": setup_s},
                     attempted=len(jobs), failed=0, data=data, notes=notes,
                     trace_path=trace_path)
