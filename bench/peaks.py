"""Published peaks per chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error: a missing peak is never
guessed.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s",
    },
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py with "
                       f"their source")
    return PEAKS[device_kind]
