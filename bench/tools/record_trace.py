"""Record the small chip trace that ``bench/tests/test_trace.py`` reads:
twenty runs of a jitted ``_tick`` on a TPU, each inside a ``bench.tick``
span and followed by a 2 ms ``bench.submit`` span of host work, all in
one ``bench.traced`` stretch.

    python3 bench/tools/record_trace.py bench/tests/data/tick.xplane.pb
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

TICKS = 20


def main(dest: str) -> int:
    import jax
    import jax.numpy as jnp

    from bench.common import OUT, check_device
    from bench.trace import WINDOW_EVENT

    check_device(1)

    @jax.jit
    def _tick(x):
        return jnp.sin(x) @ x

    x = jnp.ones((1024, 1024), jnp.float32)
    _tick(x).block_until_ready()
    out = OUT / "trace" / "record"
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW_EVENT):
        for _ in range(TICKS):
            with jax.profiler.TraceAnnotation("bench.tick"):
                _tick(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.submit"):
                t = time.monotonic()
                while time.monotonic() - t < 0.002:
                    pass
    jax.profiler.stop_trace()
    shutil.copy(next(out.glob("plugins/profile/*/*.xplane.pb")), dest)
    print(f"{dest}: {pathlib.Path(dest).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
