"""step layer: device busy time per step and chip over a period's 4-chip and
2-chip steps, from the trace."""
from benchmark.lib.readers import step_device_ms as read  # noqa: F401
