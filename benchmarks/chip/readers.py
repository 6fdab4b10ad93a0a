"""What the per-layer metric readers share.

Each reader (``metrics/<name>.py``) has one function, ``read(ctx)``,
which returns the metric's value from the traced run's context, or
``None`` where the run holds nothing to read; the harness then leaves the
metric out.  ``ctx`` carries ``trace`` (``tracereduce.reduce`` of the
window), ``driver`` (the traffic driver, with its sizes and its count of
solves), ``cfg``, ``traffic`` and ``peaks`` (the device's row of
``peaks.json``).
"""
from __future__ import annotations

import roofline


def idle_share(ctx):
    """Per cent of the window in which no operation ran on the device."""
    t = ctx.trace
    if not t or t["window_s"] <= 0 or t["devices"] == 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def smoother_roofline_share(ctx):
    """Per cent: the least time of one solve's compulsory bytes at the
    peak HBM bandwidth, over the device's busy time per solve."""
    t, d, cfg = ctx.trace, ctx.driver, ctx.cfg
    solves = getattr(d, "solves", 0)
    if not t or not solves or t["busy_s"] <= 0 or ctx.peaks is None:
        return None
    iterated = "iterations" in cfg
    passes = cfg.get("iterations", 1)
    least = roofline.compulsory_bytes(
        ctx.traffic["intervals"], cfg["nx"], cfg["ny"], passes,
        iterated) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (t["busy_s"] / solves)
