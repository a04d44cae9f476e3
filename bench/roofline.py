"""Peak rates of the chips the benchmark runs on, and the operation and
byte count of the min-plus sweep kernel (``kernels/minplus/kernel.py``).

Peaks are keyed by JAX's ``device_kind``; a device missing from the table
is an error, never a default.  Source: Google Cloud documentation, "TPU
v5e" (197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s).  No
vector-unit (VPU) peak is published, so the sweep, which runs on the VPU,
is bounded here by its HBM side only.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}

LANE = 128


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def pad_lanes(n: int) -> int:
    return -(-n // LANE) * LANE


def sweep_cost(T: int, dc1: int, d1: int) -> Dict[str, float]:
    """One launch of the sweep over ``T`` slots with COST rows of ``dc1``
    taps and DP rows of ``d1`` columns (both as the kernel sees them).

    * ``useful_ops``: one add and one min per tap and column per slot,
      ``2 T dc1 d1``;
    * ``hbm_bytes``: float32 words the kernel reads and writes at its
      lane-padded block shapes: the first carry ``(1, d1p + dc1p)`` once,
      the rows ``(T, dc1p)``, and the cost and argmin tables
      ``(T, d1p)`` each.
    """
    dc1p, d1p = pad_lanes(dc1), pad_lanes(d1)
    words = (d1p + dc1p) + T * dc1p + 2 * T * d1p
    return {"useful_ops": 2.0 * T * dc1 * d1, "hbm_bytes": 4.0 * words}


def sweep_cost_from_shapes(rows_shape, out_shape) -> Dict[str, float]:
    """``sweep_cost`` from the kernel's padded operand shapes: rows
    ``(T, 1, dc1p)`` and one output ``(T, 1, d1p)``."""
    T, dc1p, d1p = rows_shape[0], rows_shape[-1], out_shape[-1]
    return sweep_cost(T, dc1p, d1p)
