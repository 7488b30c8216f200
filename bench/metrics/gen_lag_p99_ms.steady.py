"""gen_lag_p99_ms.steady: p99 of submit time minus due time, ms (load generator)."""
from bench.readers import gen_lag_p99_ms as read  # noqa: F401
