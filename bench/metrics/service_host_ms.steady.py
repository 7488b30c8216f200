"""service_host_ms.steady: median over whole flushes of the host time in
service.* spans, service.fetch and the store.* spans inside them left
out, ms; read from the program's spans in the trace
(bench/program_spans.py)."""
from bench.program_spans import reader

read = reader(__file__)
