"""Arithmetic shared by per-layer metric readers (benchmark/metrics/): a
metric that exists under several names — one per end-to-end metric it
moves — keeps one definition here."""


def device_idle_pct(view):
    """1 - (union of device operation intervals / traced window), as a
    percentage, averaged over the chips the cell holds."""
    t = view["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def window_compiles(view):
    """JAX trace / lower / compile / cache-load events inside the measured
    window and outside a measured pause. The run fails above 0; the count
    is reported so that the ledger shows it was read."""
    return float(view["window_compiles"])


def executions(view, *parts):
    """(count, seconds) per chip of the executables whose name holds one
    of `parts`, from the trace's executable line."""
    hits = [m for name, m in view["trace"]["modules"].items()
            if any(p in name for p in parts)]
    return (sum(m["count"] for m in hits), sum(m["seconds"] for m in hits))


def _steps(view):
    """(the `steps` spans' reduction, traced steps, traced chip-steps),
    or None where the trace holds no step."""
    s = view["trace"]["spans"].get("steps")
    steps = view["counters"].get("traced_steps")
    chip_steps = view["counters"].get("traced_chip_steps")
    if not s or not steps or not chip_steps:
        return None
    return s, steps, chip_steps


def step_device_ms(view):
    """Device busy time per step and chip: the union of device operation
    intervals inside the harness's `steps` spans, over the chips that
    stepped."""
    got = _steps(view)
    if got is None:
        return None
    s, _, chip_steps = got
    return s["busy_chip_s"] / chip_steps * 1e3


def step_mfu_pct(view):
    """Model FLOP/s utilization of the busy device time: the operations
    forward and backward REQUIRE for the traced steps (counted from shapes
    by benchmark/program/<family>.py:train_flops, no recomputation) over
    device busy chip-seconds inside the `steps` spans, over the chip's
    bf16 peak (benchmark/lib/peaks.py)."""
    got = _steps(view)
    if got is None or got[0]["busy_chip_s"] <= 0:
        return None
    s, steps, _ = got
    flops = view["counters"]["step_flops"] * steps
    return 100.0 * flops / s["busy_chip_s"] / view["peaks"]["bf16_flops"]


def train_step_host_ms(view):
    """Wall time per step that the device did not cover: (seconds inside
    the harness's `steps` spans - device busy seconds inside them, per
    chip) / steps, over the traced periods."""
    got = _steps(view)
    if got is None:
        return None
    s, steps, chip_steps = got
    chips = chip_steps / float(steps)
    return (s["seconds"] - s["busy_chip_s"] / chips) / steps * 1e3
