"""Run one cell at several values of one traffic or configuration key, in
one process, and print a line per value: the end-to-end metrics, the
run's notes and the host-clock per-layer numbers.  The knee sweep of an
open-loop cell is

    python3 bench/tools/sweep.py --cell fleet1m.zipf.steady \\
        --key rate_per_s --values 2000,4000,8000 --seconds 10

It prints no result line; the cell's file keeps the rate chosen.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import harness, readers  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--key", required=True)
    ap.add_argument("--values", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--judge", action="store_true")
    args = ap.parse_args(argv)
    for k, raw in enumerate(args.values.split(",")):
        value = json.loads(raw)

        def override(config, workload, value=value):
            target = workload["traffic"] if args.key in workload["traffic"] \
                else config
            target[args.key] = value

        t = time.monotonic()
        run, device, _, _, _ = harness.measure_cell(
            args.cell, args.seed + k, args.seconds, False, t_start=t,
            overrides=override)
        row = {"cell": args.cell, args.key: value, "metrics": run.metrics,
               "notes": run.notes, "device": device}
        d = run.data
        if "ticks" in d:
            row["layers"] = {
                "gen_lag_p99_ms": readers.gen_lag_p99_ms(d),
                "queue_wait_ms": readers.queue_wait_ms(d),
                "batch_fill": readers.batch_fill(d),
                "host_tick_ms": readers.host_tick_ms(d),
                "store_faults_per_tick": readers.store_faults_per_tick(d)}
        if args.judge:
            run.release()
            row["checks"] = run.judge()
        print("sweep " + json.dumps(row, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
