"""Published peaks of the chips the benchmark may run on, and the least
bytes a kernel's work has to move.  An unknown device kind is an error."""

from __future__ import annotations

# source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12,
                    "bf16_flops_per_s": 197e12,
                    "source": "Google Cloud docs, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"has {sorted(PEAKS)}") from None


def ec_encode_min_bytes(k: int, m: int, encoded_bytes: int) -> float:
    """Least HBM traffic of encoding `encoded_bytes` of user data with a
    k+m code: every data byte read once, every parity byte written once.
    (The resident lane also writes the k+m planes it keeps; a lower bound
    stays a lower bound, so the share can only read low, never over 100.)"""
    return encoded_bytes * (k + m) / k


def roofline_share(min_bytes: float, kernel_seconds: float,
                   device_kind: str) -> float:
    """Percent of the HBM roofline: least time over measured time."""
    if kernel_seconds <= 0:
        raise ValueError("no kernel time")
    return 100.0 * (min_bytes / peaks(device_kind)["hbm_bytes_per_s"]) \
        / kernel_seconds
