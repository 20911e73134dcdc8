"""model parts: share of the step's device self time under a scope the program registered (edl_tpu/obs/devtime.py:SCOPES)."""
from benchmark.lib.scope_readers import coverage_pct as read  # noqa: F401
