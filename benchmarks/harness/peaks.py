"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

The benchmark's own table: the program's `telemetry.PEAK_FLOPS` can be
overridden by an environment variable, so a run could move its own MFU.
A device that is not listed here is an error, never a default, and no
device metric is computed off the chip.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s, 1,600 Gbit/s inter-chip interconnect per chip.
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            "no published peak for device_kind %r in benchmarks/harness/"
            "peaks.py; add its row with a source, do not guess"
            % (device_kind,)) from None
