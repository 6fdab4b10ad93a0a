"""Reduction of a profiler trace to device busy time, op time and idle gaps.

A trace is read into two plain lists (``events``): the device operations,
``(device, name, start_ns, dur_ns)`` from the ``XLA Ops`` line of each
``/device:`` plane, named by their HLO instruction (``%fusion.732``),
and the host annotations, ``(name, start_ns, dur_ns)`` from the host
plane's threads (the benchmark's ``jax.profiler.TraceAnnotation`` spans,
``bench.*``).  ``reduce`` then works on those lists alone, so it is checked on
a small recorded trace without a chip.

The window is the host annotation ``bench.window``.  Busy time is the
union of the device-op intervals inside it, per device, averaged over the
devices that ran anything; the idle gaps are the holes in that union,
each named by the innermost host annotation open at its midpoint.

A trace whose device ops stop well before the window ends, or start well
after it begins, has lost events (the profiler caps what it keeps), and
its busy share would read low: ``reduce`` refuses it.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
HOST_PREFIXES = ("bench.",)
# Share of the window at either end that may pass with no device op.
EDGE_SHARE = 0.05


def events(trace_dir: str):
    """``(device_ops, host_spans)`` of the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    ops, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((plane.name, e.name.split(" = ")[0],
                                int(e.start_ns), int(e.duration_ns))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return ops, host


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def window(host) -> Tuple[int, int]:
    spans = [(s, s + d) for name, s, d in host if name == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(spans)}")
    return spans[0]


def reduce(ops, host, top: int = 10) -> Dict:
    """Busy and window seconds, op time, idle gaps of one traced window.

    Returns ``{"window_s", "busy_s", "devices", "op_s": {name: s},
    "device_ops": [[name, s], ...], "idle_gaps": [[name, s], ...]}``,
    the last two the ``top`` largest, ``busy_s`` averaged over devices.
    Raises ``ValueError`` where a device's ops leave more than
    ``EDGE_SHARE`` of the window bare at its start or its end."""
    w0, w1 = window(host)
    per_device: Dict[str, List[Tuple[int, int]]] = {}
    op_ns: Dict[str, int] = {}
    for dev, name, s, d in ops:
        a, b = max(s, w0), min(s + d, w1)
        if b <= a:
            continue
        per_device.setdefault(dev, []).append((a, b))
        op_ns[name] = op_ns.get(name, 0) + (b - a)
    unions = {dev: _union(iv) for dev, iv in per_device.items()}
    edge = EDGE_SHARE * (w1 - w0)
    for dev, u in unions.items():
        if u[0][0] - w0 > edge or w1 - u[-1][1] > edge:
            raise ValueError(
                f"{dev}: device ops cover only [{(u[0][0] - w0) * 1e-9:.3f}"
                f", {(u[-1][1] - w0) * 1e-9:.3f}] s of a "
                f"{(w1 - w0) * 1e-9:.3f} s window: events were lost")
    busy = [sum(b - a for a, b in u) for u in unions.values()]
    spans = sorted(((s, s + d, name) for name, s, d in host
                    if name != WINDOW), key=lambda t: t[0])
    gaps = []
    for u in unions.values():
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _doing(spans, (a + b) // 2)))
    gaps.sort(key=lambda g: -g[0])
    ranked = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": (sum(busy) / len(busy) if busy else 0.0) * 1e-9,
        "devices": len(unions),
        "op_s": {k: v * 1e-9 for k, v in op_ns.items()},
        "device_ops": [[k, v * 1e-9] for k, v in ranked[:top]],
        "idle_gaps": [[name, d * 1e-9] for d, name in gaps[:top]],
    }


def _doing(spans, t: int) -> str:
    """The innermost host annotation open at ``t`` (the latest-starting
    one that covers it), or ``"none"``."""
    best: Optional[str] = None
    for a, b, name in spans:
        if a > t:
            break
        if b >= t:
            best = name
    return best or "none"
