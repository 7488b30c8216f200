"""batch_fill.steady: real requests per tick over max_batch (frontend batcher)."""
from bench.readers import batch_fill as read  # noqa: F401
