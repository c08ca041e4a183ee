"""The one table of chip peaks, keyed by jax ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip does 197 TFLOP/s in bf16 and holds 16 GB of HBM at 819 GB/s. A device
that is not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r}; the table holds "
            f"{sorted(PEAKS)}") from None
