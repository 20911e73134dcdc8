"""model step layer: the least time the chip could take to read what one
decode step MUST read — every weight once, and the cached keys and values
of the positions live in the traced window
(benchmark/program/<family>.py:decode_bytes) at the chip's peak HBM
bandwidth (benchmark/lib/peaks.py) — over the step's device time. The
step is bound by memory traffic, not by operations."""
from benchmark.lib.harness import load_module
from benchmark.lib.readers import executions


def read(view):
    count, seconds = executions(view, "step_impl")
    live = view["counters"].get("traced_live_positions")
    if count <= 0 or not live:
        return None
    step_s = seconds / count
    fam = load_module("program", view["config"]["family"])
    need = fam.decode_bytes(view["config"], view["traffic"], live)
    return 100.0 * need / view["peaks"]["hbm_bytes_s"] / step_s
