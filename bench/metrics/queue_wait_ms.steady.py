"""queue_wait_ms.steady: median of tick start minus due time, ms (frontend batcher)."""
from bench.readers import queue_wait_ms as read  # noqa: F401
