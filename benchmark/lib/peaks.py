"""The ONE table of published chip peaks every share is taken against.

Copied from `edl_tpu/parallel/costmodel.py:CHIP_PEAKS` (PR 24) so that no
later PR can move a utilization by editing the program. Source: Google
Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
819 GB/s per chip. A device kind that is not here is an error.
"""

CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197.0e12, "hbm_bytes_s": 819.0e9,
                    "hbm_bytes": 16.0e9},
}


def peaks(device_kind):
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peaks for device_kind %r; add it to "
                       "benchmark/lib/peaks.py with its source"
                       % (device_kind,)) from None
