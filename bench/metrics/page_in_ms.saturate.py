"""page_in_ms.saturate: median over whole flushes of store.page_in plus
store.slot_map, ms; read from the program's spans in the trace
(bench/program_spans.py)."""
from bench.program_spans import reader

read = reader(__file__)
