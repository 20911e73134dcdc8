"""The most negative cumulative log decay (the sum of a_t over a chunk, a key channel and head) that a chunk of Kimi Delta Attention's rule reached, over the KDA layers and the steps (`kda_chunk_log_decay_min`, a running minimum kept on the device): what a chunked form that takes exp(-gamma) would overflow on below about -88; the program takes exp of differences that are never positive."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    lows = model_counters().get("kda_chunk_log_decay_min")
    return float(min(lows)) if lows else None
