"""store_faults_per_tick.saturate: store fault-ins per tick in the window (store paging)."""
from bench.readers import store_faults_per_tick as read  # noqa: F401
