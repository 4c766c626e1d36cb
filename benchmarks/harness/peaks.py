"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.
A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 16 GB HBM2e at
819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to harness/peaks.py "
                       f"with their source")
    return PEAKS[device_kind]
