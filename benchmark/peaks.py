"""Peak rates of the chips the benchmark knows, keyed by `device_kind` as
jax reports it.  A device that is not in the table is an error, never a
default: a roofline share against a guessed peak means nothing.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip,
1,600 Gbit/s inter-chip interconnect)."""

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": {"bfloat16": 197e12, "int8": 393e12},
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
        "source": "cloud.google.com/tpu/docs/v5e (System architecture)",
    },
}
# fp32 matmuls at jax's default precision run as ONE bf16 pass on the MXU, so
# the bf16 peak is the bound the hardware sets for them too.
PEAKS["TPU v5 lite"]["flops_per_s"]["float32_default_precision"] = \
    PEAKS["TPU v5 lite"]["flops_per_s"]["bfloat16"]


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no peaks recorded for device kind %r; add a row to "
                       "benchmark/peaks.py with its source" % (device_kind,))
