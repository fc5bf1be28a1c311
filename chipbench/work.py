"""Operations and bytes of each kernel call, from its shapes, and the chip's
published peaks (``peaks.json``, keyed by ``device_kind``).

A kernel's roofline share is its least possible time over its measured
time, the least time being the larger of its operations over the peak rate
and its bytes over the peak bandwidth.  Only the algorithm's work counts:
padding and the extra passes that ``Precision.HIGHEST`` makes of an f32
product are not work.
"""
from __future__ import annotations

import json
from pathlib import Path

__all__ = ["bmm_quant_call", "least_time_s", "load_peaks"]

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def bmm_quant_call(B: int, C: int, D: int, value_bytes: float):
    """``_bmm_quant_kernel``: a (B, D) f32 query block against one (D, C)
    mirror tile of ``value_bytes`` per value -> (B, C) f32 distances.
    Returns (flops, bytes): 2*B*C*D multiply-adds; the tile read once, the
    queries read and the distances written in f32."""
    flops = 2.0 * B * C * D
    nbytes = C * D * value_bytes + 4.0 * B * D + 4.0 * B * C
    return flops, nbytes


def load_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"{PEAKS_FILE.name} (known: {sorted(table)})"
        )
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound) with bound "compute" or "memory"."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
