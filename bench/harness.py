"""Runs one cell once and prints its result line.

Everything about a cell is found by name: ``BENCHMARK.json`` lists the
cells and metrics; ``bench/workloads/<cell>.json`` names the cell's
configuration, driver and traffic; ``bench/configs/<config>.json`` holds
the deployment; ``bench/drivers/<driver>.py`` runs it; and each
per-layer metric is read by ``bench/metrics/<metric>.py``.  Adding a
cell, a configuration or a metric adds files and entries; it edits none.

A driver module has ``measure(ctx) -> run``; the run it returns has
``metrics`` (end-to-end name -> value), ``attempted``, ``failed``,
``window_builds`` (executables built inside the window), ``data`` (what
the metric readers read), ``judge() -> numbers`` (the comparison with
the plain reference, made after the window) and ``release()`` (drops the
program's state before the reference runs).

Besides the cell's own limits, every run is held to building no
executable inside its window (``window_builds``, limit 0).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
import time

from . import common
from .common import BENCH, OUT, ROOT, log


@dataclasses.dataclass
class Context:
    cell: str
    workload: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    spans: common.Spans
    meter: object
    gc: object
    t_start: float
    chips: int
    tracer: object = None


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, *, trace: bool) -> list[dict]:
    """The metrics a cell reports: end-to-end with ``--trace 0``,
    per-layer with ``--trace 1``.  A metric without ``workloads`` goes to
    every cell that reports the end-to-end metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def load_cell(cell: str) -> tuple[dict, dict, dict]:
    bench = common.load_json(ROOT / "BENCHMARK.json")
    if cell not in {w["name"] for w in bench["workloads"]}:
        raise SystemExit(f"unknown workload {cell!r}")
    workload = common.load_json(BENCH / "workloads" / f"{cell}.json")
    config = common.load_json(BENCH / "configs" / f"{workload['config']}.json")
    return bench, workload, config


def number(x):
    """JSON has no infinity: a metric that is unbounded is reported as
    the largest float, so it reads as worse than any measured value."""
    return sys.float_info.max if math.isinf(x) else x


def measure_cell(cell: str, seed: int, seconds: float, trace: bool, *,
                 t_start: float, require_tpu: bool = True, overrides=None):
    """Set up and run one cell's window; returns (run, device, summary,
    bench, workload).  ``overrides`` (a function of the config and
    workload dicts) shrinks a cell for a rehearsal on the CPU."""
    bench, workload, config = load_cell(cell)
    if overrides is not None:
        overrides(config, workload)
    chips = int(workload["chips"])
    common.prepare_jax()
    device = common.check_device(chips, require_tpu=require_tpu)
    common.add_program_to_path()
    driver = load_module(BENCH / "drivers" / f"{workload['driver']}.py",
                         f"bench_driver_{workload['driver']}")
    from .trace import Tracer

    ctx = Context(cell=cell, workload=workload, config=config, seed=seed,
                  seconds=seconds, trace=trace,
                  spans=common.Spans(annotate=trace),
                  meter=common.CompileMeter(), gc=common.GcPauses(),
                  t_start=t_start, chips=chips,
                  tracer=Tracer(OUT / "trace" / cell) if trace else None)
    run = driver.measure(ctx)
    device["memory_peak_bytes"] = common.memory_peak_bytes(chips)
    summary = None
    if trace and run.trace_path is not None:
        from .trace import reduce_file

        t = time.monotonic()
        summary = reduce_file(run.trace_path)
        run.notes["trace"] = dict(ctx.tracer.timings,
                                  reduce_s=time.monotonic() - t,
                                  bytes=run.trace_path.stat().st_size)
        if summary is not None:
            device["busy_s"] = summary.mean_busy_s()
            device["window_s"] = summary.window_s
    return run, device, summary, bench, workload


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             overrides=None) -> dict:
    """Run one cell once; returns the result object (not printed)."""
    run, device, summary, bench, workload = measure_cell(
        cell, seed, seconds, trace, t_start=t_start,
        require_tpu=require_tpu, overrides=overrides)
    run.release()
    numbers = dict(run.judge(), window_builds=run.window_builds)
    limits = dict(workload["limits"], window_builds=0)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = all(numbers[k] <= limits[k] for k in limits)

    metrics = {}
    wanted = cell_metrics(bench, cell, trace=trace)
    if not trace:
        for m in wanted:
            metrics[m["name"]] = {"value": number(run.metrics[m["name"]]),
                                  "unit": m["unit"]}
    else:
        run.data["trace"] = summary
        for m in wanted:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            v = reader.read(run.data)
            if v is not None:
                metrics[m["name"]] = {"value": number(v), "unit": m["unit"]}
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = summary.breakdown()
    out["notes"] = run.notes
    out["checks"] = checks
    return out


def main(argv=None, *, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except common.NoAccelerator as e:
        log(f"no accelerator: {e}")
        return 2
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0
