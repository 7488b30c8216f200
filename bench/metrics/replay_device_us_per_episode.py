"""replay_device_us_per_episode: device busy time of one replay job per episode, us (trace)."""
from bench.readers import replay_device_us_per_episode as read  # noqa: F401
