"""Published peaks, keyed by ``device_kind`` as JAX reports it.  A device
that is not in the table is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" system architecture — 16 GB
of HBM2e at 819 GB/s per chip (copied from bench.py's
HBM_PEAK_BYTES_PER_SEC, PR 22)."""

HBM_PEAK_BYTES_PER_SEC = {"TPU v5 lite": 819e9}


def hbm_bytes_per_s(device_kind: str) -> float:
    if device_kind not in HBM_PEAK_BYTES_PER_SEC:
        raise KeyError(
            f"no HBM peak on record for device_kind {device_kind!r} (known: "
            f"{sorted(HBM_PEAK_BYTES_PER_SEC)}); add it with its source")
    return HBM_PEAK_BYTES_PER_SEC[device_kind]
