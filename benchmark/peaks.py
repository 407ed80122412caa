"""Published peaks of each chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
The same numbers as ``bench.py``'s ``_DEVICE_PEAKS`` (which gave no
source).  A kind that is not here is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
    },
}


def peak(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {kind!r}; add them to "
            f"benchmark/peaks.py with their source"
        ) from None


def roofline(bytes_: float, int_ops: float, seconds: float,
             kind: str) -> tuple[float, str]:
    """``(share in %, bound)``: the least time the chip could take for
    this work, the larger of bytes over HBM bandwidth and integer ops
    over the int8 peak (the only published integer peak, so an upper
    bound on integer throughput), over the measured kernel time."""
    p = peak(kind)
    t_bytes = bytes_ / p["hbm_bytes_per_s"]
    t_ops = int_ops / p["int8_ops_per_s"]
    least, bound = (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
    return 100.0 * least / seconds, bound
