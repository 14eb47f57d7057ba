"""Published peaks of the chips the benchmark may run on, keyed by the
exact ``device_kind`` JAX reports. A device that is not here is an error,
never a default. Copied (not imported) from the program's
``util/profiler.py`` so that no later PR can move the yardstick.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
16 GB HBM at 819 GB/s per chip.
"""

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a "
            "row with its source to perfbench/peaks.py") from None
