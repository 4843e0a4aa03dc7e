"""Published peaks per chip, keyed by JAX's ``device_kind``.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" page:
197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.

Which peak bounds the panel GEMM: the panel-update kernel feeds the MXU
float32 operands at JAX's default matmul precision, which on a TPU is one
bfloat16 pass (the first residual of the kernel backend, about 1.5e-4, is
what one bf16 pass gives).  So its compute roof is the bf16 peak.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks on record for device kind "
                       f"{device_kind!r}; add it to bench/lib/peaks.py "
                       f"with its source") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   kind: str) -> float:
    """Share in % of the roofline of work that took ``seconds`` of device
    time: the least time the chip could take, max(flops / bf16 peak,
    bytes / HBM bandwidth), over ``seconds``."""
    pk = peaks(kind)
    return 100.0 * max(flops / pk["bf16_flop_per_s"],
                       nbytes / pk["hbm_bytes_per_s"]) / seconds
