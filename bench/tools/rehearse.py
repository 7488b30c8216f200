"""Rehearse every cell on the CPU at a tiny size, end to end through the
harness, the reference and the control; it never prints a result line.

    JAX_PLATFORMS=cpu python3 bench/tools/rehearse.py [cell ...]

Times printed here are the CPU's and say nothing of the chip.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402

TINY = {
    "fleet1m": dict(rows=20_000, resident_rows=4096),
    "autoreply": dict(episodes=2048),
}
TRAFFIC = {
    "serve_open": dict(rate_per_s=400),
    "serve_closed": dict(),
    "replay": dict(),
}


def shrink(config: dict, workload: dict) -> None:
    config.update(TINY[workload["config"]])
    workload["traffic"].update(TRAFFIC[workload["driver"]])


def main(argv) -> int:
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cells = argv or [w["name"] for w in bench["workloads"]]
    for cell in cells:
        for trace in (False,):
            t = time.monotonic()
            out = harness.run_cell(cell, 12345, 3.0, trace, t_start=t,
                                   require_tpu=False, overrides=shrink)
            print(f"rehearsal {cell}: {json.dumps(out)}", flush=True)
    print("rehearsal on the CPU finished; not a chip run", file=sys.stderr)
    return 3


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
