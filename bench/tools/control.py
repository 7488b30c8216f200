"""Read, for a cell on the chip, the numbers its comparison reads for
the program and for the low-precision control over many seeds, in one
process: each seed runs the cell's window at its own load, the program's
numbers are judged, and then the reference computed in the control's
type (bfloat16 for the float32 configurations) is put in the program's
place over the same inputs and judged the same way.

    python3 bench/tools/control.py --cell fleet1m.zipf.steady \\
        --seeds 1,2,3 --control-seeds 1,2,3 --seconds 10

One line per seed: ``{"seed", "program": {...}, "control": {...}}``.  The
limits in a cell's file are set between the largest program reading and
the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402

CONTROL_DTYPE = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import ml_dtypes

    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        run, device, _, _, workload = harness.measure_cell(
            args.cell, seed, args.seconds, False, t_start=t)
        run.release()
        row = {"seed": seed, "program": run.judge(), "metrics": run.metrics,
               "attempted": run.attempted, "failed": run.failed}
        if seed in ctl_seeds:
            dt = getattr(ml_dtypes,
                         CONTROL_DTYPE[run.ctx.config["dtype"]])
            row["control"] = run.control(dt)
        row["limits"] = workload["limits"]
        print("control " + json.dumps(row, default=float), flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
