"""frontend_host_ms.steady: median over whole flushes of the batcher's own
host time: the self time of frontend.flush, frontend.pack and
frontend.answer, ms; read from the program's spans in the trace
(bench/program_spans.py)."""
from bench.program_spans import reader

read = reader(__file__)
