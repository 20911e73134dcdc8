"""kernels layer: model FLOP/s utilization of the busy device time, over the
chips that stepped."""
from benchmark.lib.readers import step_mfu_pct as read  # noqa: F401
