"""Roofline counts of a kernel's CONTRACT, and the table of peaks.

Operations and bytes are functions of the shapes alone: inputs read once,
outputs written once, the algorithm's operations. Nothing an
implementation chooses to materialise is counted (the B x N score matrix
of the scorer is in no count), so no later kernel can read over 100% by
doing less than it must.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak FLOP/s and HBM bytes/s of one chip. A device that is not in
    the table is an error, never a default."""
    with open(_PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in {_PEAKS_FILE}; add it "
            "with its source")
    return table[device_kind]


def scorer_counts(b: int, n: int, k: int, num: int) -> Tuple[float, float]:
    """(operations, bytes) of scoring B user rows of rank K against N
    items and keeping `num` results a row: the B x N x K multiply-adds;
    V and the user rows read once as f32, `num` (score f32, index i32)
    pairs a row written once."""
    ops = 2.0 * b * n * k
    nbytes = (n * k + b * k) * 4.0 + b * num * 8.0
    return ops, nbytes


def solve_counts(s: int, k: int) -> Tuple[float, float]:
    """(operations, bytes) of S symmetric positive-definite K x K solves
    with one right-hand side each: Cholesky K^3/3, two triangular solves
    2 K^2; A and b read once, x written once, f32."""
    ops = s * (k ** 3 / 3.0 + 2.0 * k * k)
    nbytes = s * (k * k + 2.0 * k) * 4.0
    return ops, nbytes


def least_time_s(ops: float, nbytes: float, device_kind: str
                 ) -> Tuple[float, str]:
    """The least time the chip could take, and which roof sets it."""
    p = peaks(device_kind)
    t_ops = ops / p["flops_per_s"]
    t_mem = nbytes / p["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def roofline_pct(ops: float, nbytes: float, kernel_s: float,
                 device_kind: str) -> Tuple[float, str]:
    """Share of the roofline reached: least time over measured time."""
    if kernel_s <= 0:
        raise ValueError("kernel time must be above 0")
    least, bound = least_time_s(ops, nbytes, device_kind)
    return 100.0 * least / kernel_s, bound
