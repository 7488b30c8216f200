"""Running a cell on the CPU at a tiny size, past the harness's look for
a chip, and judging it with the cell's own limits."""
from __future__ import annotations

import time

from bench import harness
from bench.tools.rehearse import shrink


def judged(cell: str, seed: int, seconds: float = 2.0, *, control=None,
           extra=None) -> tuple[bool, dict]:
    """(correct, numbers) of one tiny run; ``control`` puts the reference
    in that type in the program's place."""

    def overrides(config, workload):
        shrink(config, workload)
        if extra is not None:
            extra(config, workload)

    run, _, _, _, workload = harness.measure_cell(
        cell, seed, seconds, False, t_start=time.monotonic(),
        require_tpu=False, overrides=overrides)
    run.release()
    numbers = run.judge() if control is None else run.control(control)
    limits = workload["limits"]
    return all(numbers[k] <= limits[k] for k in limits), numbers
