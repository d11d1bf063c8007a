"""Published peaks of the chips the benchmark runs on, keyed by
``jax.devices()[0].device_kind``. A kind that is not here is an error,
never a default.

Source for ``TPU v5 lite``: Google Cloud documentation, "TPU v5e" system
architecture page (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
819 GB/s, 1,600 Gbit/s inter-chip interconnect per chip).
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device_kind {device_kind!r} has no row in benchmark/lib/"
            "peaks.py; add its published peaks with their source")
    return PEAKS[device_kind]
