"""Peaks and the bytes a kernel must move: the roofline arithmetic.

Peaks of one chip, keyed by ``jax.Device.device_kind``.  Source: Google
Cloud documentation, "TPU v5e": per chip 197 TFLOP/s in bf16, 394
TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s (200 GB/s) of
chip-to-chip interconnect.  A device that is not in the table has no
roofline: :func:`peak` raises rather than assume one.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    hbm_bytes_s: float      # HBM bandwidth, bytes/s
    bf16_flop_s: float      # dense bf16 matmul, FLOP/s
    ici_bytes_s: float      # chip-to-chip interconnect, bytes/s


PEAKS = {
    "TPU v5 lite": Peak(hbm_bytes_s=819e9, bf16_flop_s=197e12,
                        ici_bytes_s=200e9),
}


class UnknownDevice(LookupError):
    """An accelerator whose ``device_kind`` has no entry in ``PEAKS``."""


def peak(kind: str) -> Peak:
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {kind!r}: add it to "
            "benchmark/roofline.py PEAKS with its source") from None


def dia_apply_bytes(rows: int, diagonals: int, itemsize: int) -> int:
    """Bytes one DIA SpMV y = A x must move at the least: every stored
    diagonal value once, x once and y once."""
    return rows * (diagonals + 2) * itemsize


def dia_apply_flops(rows: int, diagonals: int) -> int:
    """One multiply and one add per stored diagonal value."""
    return 2 * rows * diagonals


def roofline_share(flops: float, nbytes: float, seconds: float,
                   p: Peak) -> "tuple[float, str]":
    """The least time the chip could take for the work, over the time it
    took, and which bound sets that least time (``"hbm"`` or
    ``"flops"``).  The DIA SpMV is f32 on the vector units, far below
    the bf16 matmul peak, so its bound is always the bytes."""
    t_bytes = nbytes / p.hbm_bytes_s
    t_flops = flops / p.bf16_flop_s
    bound = "hbm" if t_bytes >= t_flops else "flops"
    return max(t_bytes, t_flops) / seconds, bound
