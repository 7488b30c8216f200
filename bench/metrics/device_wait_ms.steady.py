"""device_wait_ms.steady: median over whole flushes of the summed
service.fetch spans (the host blocked on the device, plus the copy), ms;
read from the program's spans in the trace (bench/program_spans.py)."""
from bench.program_spans import reader

read = reader(__file__)
