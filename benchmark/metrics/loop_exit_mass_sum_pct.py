"""100 x the sum over the passes of a looped model's `loop_exit_mass` (the mean over the predicted tokens of the exit distribution p(u), summed over the steps) over the steps: reads 100, or the loss the program applied was no expectation over exits."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    counters = model_counters()
    mass, steps = counters.get("loop_exit_mass"), counters.get("steps")
    if not mass or not steps or not steps[0]:
        return None
    return 100.0 * sum(mass) / steps[0]
