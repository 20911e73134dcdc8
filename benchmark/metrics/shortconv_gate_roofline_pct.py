"""Share of its roofline reached by the scope `mixer.conv.gate` (a gated short convolution between its two products): the least time the chip needs for the scope's REQUIRED bytes at the memory peak (benchmark/program/<family>.py:kernel_costs under `shortconv_gate`: forward reads B, C, x and writes C * z, backward reads the cotangent, B, C, x and writes three cotangents, remat adds a forward; bfloat16; the same whatever implements the scope) over the time measured under it (shortconv_gate_device_ms). The share AS COMPUTED, whatever it reads: in the compiled step (v5e, PR 63) the scope's forward is two loop fusions that read the in-projection's own result and write the bfloat16 array the out-projection reads, the float32 B * x between them kept on the chip, so its traffic in HBM is the required 4 arrays and nothing of it lies under the neighbouring scopes. None where the trace holds no time under the scope."""
from benchmark.lib import harness
from benchmark.lib.kernel_readers import expert_rows_per_step, model_counters

_gate = harness.load_module("metrics", "shortconv_gate_device_ms")


def read(view):
    ms = _gate.read(view)
    family = harness.load_module("program", view["config"]["family"])
    if not ms or not hasattr(family, "kernel_costs"):
        return None
    costs = family.kernel_costs(
        view["config"], view["traffic"], view["traffic"]["batch_per_chip"],
        expert_rows_per_step(model_counters()))
    if "shortconv_gate" not in costs:
        return None
    # the scope's operations are the vector unit's, not the MXU's the
    # table's peak is of: the bytes alone bound it
    least_s = costs["shortconv_gate"][1] / view["peaks"]["hbm_bytes_s"]
    return 100.0 * least_s / (ms / 1e3)
