"""Imbalance of the held experts: over the layers, the largest load a
held expert had in any step over that layer's mean load (the program's
routing counters, mirrored into its registry at `trainer.close()`)."""
from benchmark.lib.kernel_readers import load_max_over_mean, model_counters


def read(view):
    return load_max_over_mean(model_counters())
