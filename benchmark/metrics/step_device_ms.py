"""step layer: device busy time per step and chip, from the trace."""
from benchmark.lib.readers import step_device_ms as read  # noqa: F401
