"""device: share of the traced window with no operation on the chip."""
from benchmark.lib.readers import device_idle_pct as read  # noqa: F401
