"""Published peaks of the chips this benchmark may run on, keyed by JAX's
``device_kind``. Source: Google Cloud documentation, "TPU v5e" system
architecture page (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
819 GB/s per chip). A kind that is not here is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            "with its source to benchmarks/lib/peaks.py"
        ) from None


def roofline_seconds(flops: float, nbytes: float, device_kind: str):
    """(least seconds the chip could take, which bound holds)."""
    p = peaks_for(device_kind)
    t_flops = flops / p["bf16_flops"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
