"""The idle share of the most idle chip: per device plane of the
traced window, 1 minus the union of its op intervals over the window,
and the largest of those. device_idle_share averages the planes, which
hides one idle chip. The reader loads the capture itself."""

from __future__ import annotations

import functools
import os
from typing import Optional

from benchmark import trace as tracelib


def idle_by_plane(pd, device_prefix: str = tracelib.DEVICE_PREFIX) -> dict:
    """{device plane name: idle share over the host's `window`
    annotation} of a `jax.profiler.ProfileData`; {} without a window."""
    window, planes = [], {}
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            planes[plane.name] = [
                (ev.start_ns, ev.start_ns + ev.duration_ns)
                for line in plane.lines if line.name == tracelib.OPS_LINE
                for ev in line.events
            ]
        elif plane.name.startswith("/host:"):
            window += [
                (ev.start_ns, ev.start_ns + ev.duration_ns)
                for line in plane.lines for ev in line.events
                if ev.name == tracelib.WINDOW
            ]
    if not window:
        return {}
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    if hi <= lo:
        return {}
    out = {}
    for name, ops in planes.items():
        busy = sum(e - s for s, e in tracelib._union(
            tracelib._clip(ops, lo, hi)))
        out[name] = 1.0 - busy / (hi - lo)
    return out


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int) -> dict:
    from jax.profiler import ProfileData

    return idle_by_plane(ProfileData.from_file(path))


def read(ctx) -> Optional[float]:
    if ctx.trace is None:
        return None
    trace_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".cache", "trace",
    )
    try:
        path = tracelib.find_xspace(trace_dir)
    except FileNotFoundError:
        return None
    idle = _load(path, os.stat(path).st_mtime_ns)
    return max(idle.values()) if idle else None
