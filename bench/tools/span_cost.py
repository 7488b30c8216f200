"""Measure what one program span costs the thread that opens it: a
``jax.profiler.TraceAnnotation`` entered and left with no work inside,
with the profiler off and with it running (the profiler options the
benchmark's traced stretch uses), in the form the program uses: a bare
name.  Each cost is the best of several rounds, less the same loop
calling an empty function.

    python3 bench/tools/span_cost.py [--rounds 7] [--n 200000]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from jax.profiler import TraceAnnotation  # noqa: E402


def _null(i):
    pass


def _span(i):
    with TraceAnnotation("frontend.pack"):
        pass


def _best_us(fn, n: int, rounds: int) -> float:
    best = float("inf")
    for _ in range(rounds):
        t = time.perf_counter()
        for i in range(n):
            fn(i)
        best = min(best, time.perf_counter() - t)
    return best / n * 1e6


def measure(n: int, rounds: int) -> float:
    return _best_us(_span, n, rounds) - _best_us(_null, n, rounds)


def main(argv=None) -> int:
    import jax

    from bench.common import OUT

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rounds", type=int, default=7)
    ap.add_argument("--n", type=int, default=200_000)
    args = ap.parse_args(argv)
    out = {"off_us": measure(args.n, args.rounds)}
    d = OUT / "trace" / "span_cost"
    shutil.rmtree(d, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(d), profiler_options=opts)
    # fewer spans while the profiler records each one
    out["on_us"] = measure(max(1, args.n // 10), args.rounds)
    jax.profiler.stop_trace()
    shutil.rmtree(d, ignore_errors=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
