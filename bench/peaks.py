"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  Every
roofline and ``mfu`` share divides by the bf16 peak, the chip's highest
floating-point rate; the fit and the serving waves run f32 at ``HIGHEST``
(several bf16 passes per product), so their shares stay well under 100%.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip that is not listed is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    pk = peaks(device_kind)
    return max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
