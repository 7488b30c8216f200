"""device_idle.steady: 1 - device busy over the traced stretch (trace)."""
from bench.readers import device_idle as read  # noqa: F401
