"""harness: compile events inside the measured window and outside a resize
pause (a run fails above 0)."""
from benchmark.lib.readers import window_compiles as read  # noqa: F401
