"""tick_device_ms.saturate: device time of the tick executable per tick, ms (trace)."""
from bench.readers import tick_device_ms as read  # noqa: F401
