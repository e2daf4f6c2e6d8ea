"""Reduction of a profiler trace to device busy/idle, kernel time and the
breakdown the driver copies into the ledger.

Reads the `.xplane.pb` that `jax.profiler` writes with nothing but
`jax.profiler.ProfileData`. Device time is the union of the intervals of
the operations on the "XLA Ops" line of each device plane; a kernel's
time is the sum of its events there. Idle gaps are the holes in that
union inside the traced window (the harness's own `window` annotation),
each named by the harness annotation that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, Optional

# Published peaks of one chip, keyed by jax's device_kind. Source:
# Google Cloud documentation, "TPU v5e" (system architecture table).
# The EC ladders are int32 VPU arithmetic, which none of these bound;
# the table is here so that a roofline share has one place to come
# from, and an unknown chip is an error rather than a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "window"
# a gap is named by the first of these that covers half of it, else by
# the one covering most of it: the pump thread's flush or tick first,
# then the intake thread's state
HOST_ANNOTATIONS = ("flush", "pump.tick", "generator", "ingest.feed")


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} has no row in the peak table "
            "(benchmark/trace.py PEAKS)"
        ) from None


def find_xspace(log_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
    ), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: float, hi: float):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def op_name(hlo: str) -> str:
    """A device op's short name: its HLO instruction name, with the
    custom-call target where there is one (the Pallas ladders are
    `tpu_custom_call`s)."""
    name = hlo.split(" = ", 1)[0]
    m = re.search(r'custom_call_target="([^"]+)"', hlo)
    return f"{name} {m.group(1)}" if m else name


def _overlap(gap, spans) -> float:
    s0, e0 = gap
    return sum(max(0.0, min(e, e0) - max(s, s0)) for s, e in spans)


def _name_gap(gap, host) -> str:
    cover = {n: _overlap(gap, host.get(n, ())) for n in HOST_ANNOTATIONS}
    for n in HOST_ANNOTATIONS:
        if cover[n] >= 0.5 * (gap[1] - gap[0]):
            return n
    best = max(HOST_ANNOTATIONS, key=cover.get)
    return best if cover[best] else "none"


class Reduced:
    """Everything the per-layer readers take from one trace.

    Times are seconds. `busy_s` is averaged over the device planes;
    `op_s` sums each op name over all of them."""

    def __init__(self, window_s, busy_s, n_devices, op_s, gaps):
        self.window_s = window_s
        self.busy_s = busy_s
        self.n_devices = n_devices
        self.op_s = op_s
        self.gaps = gaps

    def kernel_s(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(t for name, t in self.op_s.items() if rx.search(name))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in self.gaps[:top]],
        }


def reduce_profile(pd, device_prefix: str = DEVICE_PREFIX,
                   window: Optional[tuple[float, float]] = None) -> Reduced:
    """Reduce a `jax.profiler.ProfileData`. `window` (ns) defaults to
    the extent of the host's `window` annotation."""
    devices, host = [], {}
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                             op_name(ev.name)) for ev in line.events]
            devices.append(ops)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW or ev.name in HOST_ANNOTATIONS:
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns)
                        )
    if not devices:
        raise ValueError(f"trace has no {device_prefix}* plane")
    if window is None:
        if WINDOW not in host:
            raise ValueError("trace has no host 'window' annotation")
        window = (min(s for s, _ in host[WINDOW]),
                  max(e for _, e in host[WINDOW]))
    lo, hi = window
    op_s: dict[str, float] = {}
    busy = 0.0
    gaps: list[tuple[str, float]] = []
    for ops in devices:
        for s, e, name in ops:
            for cs, ce in _clip([(s, e)], lo, hi):
                op_s[name] = op_s.get(name, 0.0) + (ce - cs) * 1e-9
        merged = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_name_gap((s, e), host), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced((hi - lo) * 1e-9, busy / len(devices), len(devices),
                   op_s, gaps)


def reduce_dir(log_dir: str, device_prefix: str = DEVICE_PREFIX) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(
        ProfileData.from_file(find_xspace(log_dir)), device_prefix
    )
