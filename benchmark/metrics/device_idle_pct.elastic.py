"""device: share of the traced window with no operation running, averaged over
the four chips the job holds: pauses, and the two chips that idle while the
job runs on two."""
from benchmark.lib.readers import device_idle_pct as read  # noqa: F401
