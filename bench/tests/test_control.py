"""The low-precision control of each cell, at a size a test run holds:
the plain reference computed in bfloat16 and put in the program's place
must come out not correct, while the float32 program over the same
inputs comes out correct.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q
"""
import ml_dtypes
import pytest

from bench.tests._tiny import judged


@pytest.mark.parametrize("cell", ["fleet1m.zipf.steady",
                                  "fleet1m.zipf.saturate",
                                  "autoreply.grid35"])
def test_program_correct_and_bf16_control_not(cell):
    ok, numbers = judged(cell, 7)
    assert ok, numbers
    ok, numbers = judged(cell, 7, control=ml_dtypes.bfloat16)
    assert not ok, numbers
