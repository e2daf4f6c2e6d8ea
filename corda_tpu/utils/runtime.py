"""Process runtime ledger: garbage-collector pauses, and their pacing.

A `gc.callbacks` watch counts every collection and its pause seconds
per generation, and marks each pause as a `gc.collect` profiler region
(utils/tracing.open_region) carrying its generation — so a capture
shows the collector's pauses on the same clock as the device trace.

Process-scoped like device_telemetry.get_device_accounting: the
collector is a process resource, and two embedded nodes must read one
ledger. `BatchingNotaryService` and `Node` each hold one reference
(`acquire` / `release`); the callback is installed while any is held.

A hold taken with `pace=True` (the batching notary's) also paces the
automatic full collections. A serving notary keeps each frame's object
graph alive for a second or two, long enough to be promoted to the
oldest generation, where refcounting frees it: CPython's rule (a full
pass every 10 gen-1 collections, once 25% of the survivors are new)
then starts full passes at the notarisation rate, each walking the
whole live heap and finding no garbage. At the end of each full pass
the watch sets `threshold2` so that the next pass comes no sooner than
`pass_s * (1 - FULL_PASS_SHARE) / FULL_PASS_SHARE` seconds later, at
the gen-1 rate it measured: full passes then take at most about 5% of
the wall. The 25% rule still applies and the spacing never falls
below the threshold in force before the first hold, so pacing only
ever removes full passes; `FULL_PASS_MAX_S` bounds how long a cycle
waits to be collected.
"""

from __future__ import annotations

import gc
import math
import time

from . import locks, tracing

#: the share of wall time paced full passes may take
FULL_PASS_SHARE = 0.05
#: the longest spacing the pacer sets between full passes, in seconds
FULL_PASS_MAX_S = 60.0


def full_pass_threshold(rate1: float, pass_s: float, floor: int = 10) -> int:
    """`threshold2` (gen-1 collections between full passes) that spaces
    full passes of `pass_s` seconds to `FULL_PASS_SHARE` of the wall at
    `rate1` gen-1 collections per second, between `floor` and
    `FULL_PASS_MAX_S` of gen-1 collections; `floor` wins."""
    want = math.ceil(
        rate1 * pass_s * (1 - FULL_PASS_SHARE) / FULL_PASS_SHARE
    )
    return max(floor, min(want, math.ceil(rate1 * FULL_PASS_MAX_S)))


class GcWatch:
    """Collections and pause seconds per generation, since the process
    started watching. Callbacks never overlap (one collection at a
    time under the GIL), so the counters need no lock; the lock guards
    the reference counts alone."""

    def __init__(self):
        self.collections: dict[int, int] = {0: 0, 1: 0, 2: 0}
        self.seconds: dict[int, float] = {0: 0.0, 1: 0.0, 2: 0.0}
        self._t0: float | None = None
        self._region = None
        self._refs = 0
        # pacing holds, the thresholds before the first, and the clock
        # and gen-1 count at the last full pass (or the first hold)
        self._paced = 0
        self._prior: tuple[int, int, int] = gc.get_threshold()
        self._full_mark = (0.0, 0)
        self._lock = locks.make_lock("GcWatch._lock")
        self._callback = self._on_gc

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._region = tracing.open_region(
                "gc.collect", generation=info["generation"]
            )
            self._t0 = time.perf_counter()
            return
        t0, self._t0 = self._t0, None
        if t0 is None:   # installed while a collection ran
            return
        gen = info["generation"]
        now = time.perf_counter()
        self.collections[gen] = self.collections.get(gen, 0) + 1
        self.seconds[gen] = self.seconds.get(gen, 0.0) + now - t0
        region, self._region = self._region, None
        if gen != 2:
            tracing.close_region(region, collected=info["collected"])
            return
        if self._paced:
            self._pace(now, now - t0)
        if region is not None:
            tracing.close_region(region, collected=info["collected"],
                                 threshold=gc.get_threshold()[2])

    def _pace(self, now: float, pass_s: float) -> None:
        """At the end of a full pass: space the next by this one's
        pause, at the gen-1 rate since the last."""
        (t_mark, n1_mark), n1 = self._full_mark, self.collections[1]
        self._full_mark = (now, n1)
        if now <= t_mark:
            return
        t0, t1, _ = gc.get_threshold()
        gc.set_threshold(t0, t1, full_pass_threshold(
            (n1 - n1_mark) / (now - t_mark), pass_s, self._prior[2]
        ))

    def acquire(self, pace: bool = False) -> None:
        """Hold the watch; with `pace`, also pace the full passes."""
        with self._lock:
            self._refs += 1
            if self._refs == 1:
                gc.callbacks.append(self._callback)
            if pace:
                self._paced += 1
                if self._paced == 1:
                    self._prior = gc.get_threshold()
                    self._full_mark = (time.perf_counter(),
                                       self.collections[1])

    def release(self, pace: bool = False) -> None:
        """Drop a hold taken with the same `pace`; the last pacing hold
        restores the thresholds that stood before the first."""
        with self._lock:
            if not self._refs or (pace and not self._paced):
                return
            self._refs -= 1
            if pace:
                self._paced -= 1
                if not self._paced:
                    gc.set_threshold(*self._prior)
            if not self._refs and self._callback in gc.callbacks:
                gc.callbacks.remove(self._callback)

    def snapshot(self) -> dict[int, tuple[int, float]]:
        """{generation: (collections, pause seconds)}."""
        return {g: (n, self.seconds.get(g, 0.0))
                for g, n in sorted(self.collections.items())}


_watch = GcWatch()


def get_gc_watch() -> GcWatch:
    return _watch


def register_gc_gauges(metrics) -> None:
    """`Runtime.GcCollections.gen<k>` and `Runtime.GcSeconds.gen<k>`
    over the process watch, and `Runtime.GcFullThreshold` (the
    `threshold2` in force: 10, CPython's default, when not paced), on
    one registry."""
    w = _watch
    for gen in sorted(w.collections):
        metrics.gauge(f"Runtime.GcCollections.gen{gen}",
                      lambda g=gen: w.collections.get(g, 0))
        metrics.gauge(f"Runtime.GcSeconds.gen{gen}",
                      lambda g=gen: w.seconds.get(g, 0.0))
    metrics.gauge("Runtime.GcFullThreshold", lambda: gc.get_threshold()[2])
