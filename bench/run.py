#!/usr/bin/env python3
"""Run one benchmark cell once:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object; the
numbers compared with the reference are also the last lines of standard
error.  Exits non-zero, with no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
import pathlib
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
