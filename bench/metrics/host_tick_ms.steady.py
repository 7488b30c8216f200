"""host_tick_ms.steady: median host wall of the span around tick_packed, ms."""
from bench.readers import host_tick_ms as read  # noqa: F401
