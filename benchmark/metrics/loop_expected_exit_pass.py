"""The pass at which a looped model would stop, in the mean over tokens and steps: the sum over u of u x `loop_exit_mass`(u) over the steps, from 1 to the number of passes; what an early exit (none runs while training) would save."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    counters = model_counters()
    mass, steps = counters.get("loop_exit_mass"), counters.get("steps")
    if not mass or not steps or not steps[0]:
        return None
    return sum((u + 1) * m for u, m in enumerate(mass)) / steps[0]
