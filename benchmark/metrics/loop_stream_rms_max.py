"""The largest root mean square the residual stream of a looped model reached at the end of a pass, BEFORE the final norm, over the passes and the steps (`loop_stream_rms_max`, a running maximum kept on the device): a stream that grows from pass to pass says the recurrence is not bounded."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    tops = model_counters().get("loop_stream_rms_max")
    return float(max(tops)) if tops else None
