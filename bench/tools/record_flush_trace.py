"""Record the small chip trace that ``bench/tests/test_program_spans.py``
reads: a ``ServingFrontend`` batcher thread over a small paged
``OnlineDecisionService`` (4,096 rows, 256 resident, so every flush
faults rows in and spills others) answering ten full batches of 32, each
answer settled before the next batch, all in one ``bench.traced``
stretch.  The drift step is off, which keeps the trace small.

    python3 bench/tools/record_flush_trace.py bench/tests/data/flush.xplane.pb
"""
from __future__ import annotations

import pathlib
import shutil
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

ROWS, RESIDENT, BATCH = 4096, 256, 32
WARM, FLUSHES = 9, 10        # the warm-up fills the table, so spills warm


def main(dest: str) -> int:
    import jax

    from bench.common import OUT, add_program_to_path, check_device
    from bench.trace import WINDOW_EVENT

    check_device(1)
    add_program_to_path()
    from repro.core.online import OnlineDecisionService
    from repro.core.posterior import BetaPosterior
    from repro.serving.frontend import (DecisionRequest, FrontendConfig,
                                        ServingFrontend)

    svc = OnlineDecisionService(resident_rows=RESIDENT, min_rows=RESIDENT)
    for r in range(ROWS):
        svc.register_edge((f"u{r}", f"v{r}"), tenant=f"t{r % 8}",
                          posterior=BetaPosterior(alpha=16.0, beta=2.0))
    fe = ServingFrontend(svc, FrontendConfig(
        max_batch=BATCH, deadline_s=10.0, bulkhead_limit=ROWS,
        check_drift=False, snapshot_refresh_ticks=4))

    def batch(k: int) -> None:
        tickets = [fe.submit(DecisionRequest(
            row=r, tenant=f"t{r % 8}", edge=(f"u{r}", f"v{r}"), alpha=0.5,
            lambda_usd_per_s=0.9, latency_s=3.0, input_tokens=500.0,
            output_tokens=300.0, input_price=3e-6, output_price=15e-6))
            for r in range(k * BATCH, (k + 1) * BATCH)]
        for t in tickets:
            t.result(60)
            t.settle(True)

    for k in range(WARM):
        batch(k)
    out = OUT / "trace" / "record_flush"
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW_EVENT):
        for k in range(WARM, WARM + FLUSHES):
            batch(k)
    jax.profiler.stop_trace()
    fe.stop()
    shutil.copy(next(out.glob("plugins/profile/*/*.xplane.pb")), dest)
    print(f"{dest}: {pathlib.Path(dest).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
