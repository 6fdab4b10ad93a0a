"""Compulsory work of one MAP solve, and the table of peaks.

The least work a smoother of ``N`` intervals must do, whatever implements
it, counted per pass of the smoother (one pass for a linear model, one per
linearisation for the iterated one) and per interval, in float32:

bytes
    ``ts`` and ``y`` read once (``1 + ny`` floats); the forward pass's
    per-point result, the filter information ``S`` and ``v`` (``nx^2 +
    nx`` floats), written once and read once; ``x`` written once
    (``nx``); for an iterated model, ``x`` of the previous pass read once
    more (``nx``), the point it linearises about.  Padding to the chip's
    tiles is not counted: it is waste, not work.

operations
    A sequential Kalman filter and RTS smoother: the prediction ``G P
    G^T`` and ``G m`` (``4 nx^3 + 2 nx^2``), the gain ``P H^T``, ``H P
    H^T`` and its solve (``2 nx^2 ny + 2 nx ny^2 + ny^3``), the mean and
    covariance update (``4 nx ny + 2 nx^2 ny + 2 nx^3``), the smoother
    gain and step (``2 nx^3 + nx^3 + 2 nx^2``).

No float32 peak of the v5e's vector unit is published, so a smoother's
roofline share is bounded by bytes alone: the least time is the
compulsory bytes over the HBM bandwidth of ``peaks.json``.
"""
from __future__ import annotations

import json
from pathlib import Path

FLOAT_BYTES = 4


def compulsory_bytes(N: int, nx: int, ny: int, passes: int = 1,
                     iterated: bool = False) -> int:
    per_point = (1 + ny) + 2 * (nx * nx + nx) + nx + (nx if iterated else 0)
    return FLOAT_BYTES * per_point * N * passes


def compulsory_flops(N: int, nx: int, ny: int, passes: int = 1) -> int:
    predict = 4 * nx ** 3 + 2 * nx ** 2
    gain = 2 * nx ** 2 * ny + 2 * nx * ny ** 2 + ny ** 3
    update = 4 * nx * ny + 2 * nx ** 2 * ny + 2 * nx ** 3
    smooth = 3 * nx ** 3 + 2 * nx ** 2
    return (predict + gain + update + smooth) * N * passes


def peaks(device_kind: str) -> dict:
    """The peak rates of ``device_kind``; a device not in the table is an
    error, never a default."""
    table = json.loads(Path(__file__).with_name("peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]
