"""collectives layer: collective time with no compute running on the same
chip, per step and chip, from the trace."""


def read(view):
    chip_steps = view["counters"].get("traced_chip_steps")
    if not chip_steps or view["trace"]["collective_s"] <= 0:
        return None
    return view["trace"]["collective_exposed_s"] / chip_steps * 1e3
