"""The program's own profiler regions in a traced run's capture.

The notary marks its host work as profiler regions (corda_tpu
utils/tracing): one per flush phase (`notary.<phase>`), the pump's
batching hold and starvation (`notary.hold`, `notary.starved`), each
collector pause (`gc.collect`) and each ladder launch (`verify.launch`,
carrying its real `rows` and padded `batch`). The readers of those
regions load the capture the harness wrote (benchmark/.cache/trace)
and read it over the harness's `window` annotation, the window
trace.py reduces. A capture that holds no `notary.<phase>` region comes
from a program that marks none of these: `load` returns None there,
and so do the readers.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

from benchmark import trace as tracelib

FLUSH_PHASES = ("stage", "dispatch", "resolve_verify", "link_wait",
                "validate", "commit", "stream_commit", "sign_scatter")


class Regions:
    """Host regions of one capture by name: [(start_ns, end_ns, stats)],
    with the window (ns) they are read over."""

    def __init__(self, events: dict, window: tuple):
        self.events = events
        self.window = window
        self.window_s = (window[1] - window[0]) * 1e-9

    def seconds(self, name: str) -> float:
        """Seconds of `name` regions inside the window."""
        lo, hi = self.window
        return sum(max(0, min(e, hi) - max(s, lo))
                   for s, e, _ in self.events.get(name, ())) * 1e-9

    def stat_sum(self, name: str, key: str) -> int:
        """Sum of the `key` argument over `name` regions that start
        inside the window."""
        lo, hi = self.window
        return sum(st.get(key, 0) for s, _, st in self.events.get(name, ())
                   if lo <= s < hi)


# the program's region names start with one of these
PREFIXES = ("notary.", "gc.", "verify.", "ingest.")


def from_profile(pd) -> Optional[Regions]:
    """Regions of a `jax.profiler.ProfileData`; None when it has no
    `window` annotation or no flush-phase region."""
    events: dict = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != tracelib.WINDOW and not ev.name.startswith(
                    PREFIXES
                ):
                    continue
                events.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats))
                )
    window = events.get(tracelib.WINDOW)
    if not window or not any("notary." + p in events for p in FLUSH_PHASES):
        return None
    return Regions(events, (min(s for s, _, _ in window),
                            max(e for _, e, _ in window)))


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int) -> Optional[Regions]:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def load(ctx, reader_file: str) -> Optional[Regions]:
    """The regions of the run `ctx` describes, from the capture beside
    the reader at `reader_file` (benchmark/metrics/<name>.py); None for
    an untraced run or a program without the regions."""
    if ctx.trace is None:
        return None
    trace_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(reader_file))),
        ".cache", "trace",
    )
    try:
        path = tracelib.find_xspace(trace_dir)
    except FileNotFoundError:
        return None
    return _load(path, os.stat(path).st_mtime_ns)
