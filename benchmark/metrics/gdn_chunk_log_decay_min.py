"""The most negative cumulative log decay a chunk of the gated delta rule reached, over the linear-attention layers and the steps (`gdn_chunk_log_decay_min`, a running minimum kept on the device): what a chunked form that takes exp(-gamma) would overflow on below about -88; the program takes exp of differences that are never positive."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    lows = model_counters().get("gdn_chunk_log_decay_min")
    return float(min(lows)) if lows else None
