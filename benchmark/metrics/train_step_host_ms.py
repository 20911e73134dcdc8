"""trainer layer: wall time per step that the device did not cover."""
from benchmark.lib.readers import train_step_host_ms as read  # noqa: F401
