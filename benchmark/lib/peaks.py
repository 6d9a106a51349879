"""Peak rates of one chip, keyed by the `device_kind` string JAX reports.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip. A device that is not in
the table is an error, not a default."""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind, what):
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak on record for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add it to benchmark/lib/peaks.py with its "
            "source")
    return PEAKS[device_kind][what]
