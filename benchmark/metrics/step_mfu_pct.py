"""kernels layer: model FLOP/s utilization of the busy device time."""
from benchmark.lib.readers import step_mfu_pct as read  # noqa: F401
