"""harness: compile events inside the measured window (a run fails above 0)."""
from benchmark.lib.readers import window_compiles as read  # noqa: F401
