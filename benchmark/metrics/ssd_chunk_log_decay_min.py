"""The most negative cumulative log decay (the sum of dt A over a chunk) that a chunk of the SSD scan reached, over the Mamba-2 layers and the steps (`ssd_chunk_log_decay_min`, a running minimum kept on the device): what a chunked form that takes exp(-gamma) would overflow on below about -88; the program takes exp of differences that are never positive."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    lows = model_counters().get("ssd_chunk_log_decay_min")
    return float(min(lows)) if lows else None
