"""device_idle_host.steady: share of the traced stretch in which the device
is idle while the batcher thread is in host work (a program span other
than service.fetch and frontend.wait_batch); read from the program's
spans in the trace (bench/program_spans.py)."""
from bench.program_spans import reader

read = reader(__file__)
