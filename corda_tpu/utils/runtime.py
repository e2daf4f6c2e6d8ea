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
automatic collections, full and young alike. A serving notary keeps
each frame's object graph alive for a second or two, long enough to be
promoted to the oldest generation, where refcounting frees it:
CPython's rule (a full pass every 10 gen-1 collections, once 25% of
the survivors are new) then starts full passes at the notarisation
rate, each walking the whole live heap and finding no garbage. At the
end of each full pass the watch sets `threshold2` so that the next
pass comes no sooner than
`pass_s * (1 - FULL_PASS_SHARE) / FULL_PASS_SHARE` seconds later, at
the gen-1 rate it measured: full passes then take at most about 5% of
the wall. The 25% rule still applies and the spacing never falls
below the threshold in force before the first hold, so pacing only
ever removes full passes; `FULL_PASS_MAX_S` bounds how long a cycle
waits to be collected.

Young passes (generations 0 and 1) fire every 700 net allocations by
default, every few milliseconds at a notary's rate: each walks the
in-flight frames, promotes them, and frees almost nothing, since
refcounting frees a frame right after its flush. At the end of each
young pass the watch sets `threshold0` (the net allocations that start
the next automatic pass) so that the next comes no sooner than
`pass_s * (1 - YOUNG_PASS_SHARE) / YOUNG_PASS_SHARE` seconds later, at
the net allocation rate since the last pass (`gc.get_count()[0]` as the
pass starts, over the time since the last pass ended). The spacing
never falls below the `threshold0` in force before the first hold, and
never exceeds `YOUNG_PASS_MAX_S` of allocations at that rate nor
`YOUNG_PASS_MAX_N` allocations, which bounds what one pass walks and
what a burst's rate can set. `threshold1` shrinks as `threshold0`
grows, so that a gen-1 pass answers no more allocations than at the
prior thresholds (every second young pass is a gen-1 pass once
`threshold0` exceeds their product): a gen-1 pass then walks about two
spacings' survivors, not ten, and comes often enough for the full-pass
pacer's spacing to stay about `FULL_PASS_MAX_S`. Like CPython's own
thresholds these bounds count net allocations: a cycle born young waits
at most `YOUNG_PASS_MAX_N` of them (`YOUNG_PASS_MAX_S` at a steady
rate), one promoted to generation 1 two such spacings, and one promoted
to the oldest generation the full-pass spacing, at least ten gen-1
passes.
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
#: the share of wall time paced young passes may take
YOUNG_PASS_SHARE = 0.05
#: the longest spacing the pacer sets between young passes, in seconds
YOUNG_PASS_MAX_S = 5.0
#: the most net allocations the pacer lets one young pass answer
YOUNG_PASS_MAX_N = 1 << 18


def full_pass_threshold(rate1: float, pass_s: float, floor: int = 10) -> int:
    """`threshold2` (gen-1 collections between full passes) that spaces
    full passes of `pass_s` seconds to `FULL_PASS_SHARE` of the wall at
    `rate1` gen-1 collections per second, between `floor` and
    `FULL_PASS_MAX_S` of gen-1 collections; `floor` wins."""
    want = math.ceil(
        rate1 * pass_s * (1 - FULL_PASS_SHARE) / FULL_PASS_SHARE
    )
    return max(floor, min(want, math.ceil(rate1 * FULL_PASS_MAX_S)))


def young_pass_threshold(rate0: float, pass_s: float,
                         floor: int = 700) -> int:
    """`threshold0` (net allocations between automatic passes) that
    spaces young passes of `pass_s` seconds to `YOUNG_PASS_SHARE` of
    the wall at `rate0` net allocations per second, between `floor` and
    the lesser of `YOUNG_PASS_MAX_S` of allocations and
    `YOUNG_PASS_MAX_N`; `floor` wins."""
    want = math.ceil(
        rate0 * pass_s * (1 - YOUNG_PASS_SHARE) / YOUNG_PASS_SHARE
    )
    cap = min(math.ceil(rate0 * YOUNG_PASS_MAX_S), YOUNG_PASS_MAX_N)
    return max(floor, min(want, cap))


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
        # pacing holds, the thresholds before the first, the clock and
        # gen-1 count at the last full pass (or the first hold), the
        # clock at the end of the last pass (any generation resets the
        # allocation count) and the count as the running pass started
        self._paced = 0
        self._prior: tuple[int, int, int] = gc.get_threshold()
        self._full_mark = (0.0, 0)
        self._young_mark = 0.0
        self._count0 = 0
        self._lock = locks.make_lock("GcWatch._lock")
        self._callback = self._on_gc

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._region = tracing.open_region(
                "gc.collect", generation=info["generation"]
            )
            self._count0 = gc.get_count()[0]
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
        if self._paced:
            if gen == 2:
                self._pace(now, now - t0)
            else:
                self._pace_young(t0, now)
            self._young_mark = now
        if region is not None:
            tracing.close_region(
                region, collected=info["collected"],
                threshold=gc.get_threshold()[2 if gen == 2 else 0],
            )

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

    def _pace_young(self, t0: float, now: float) -> None:
        """At the end of a young pass that started at `t0`: space the
        next by this one's pause, at the net allocation rate since the
        last pass ended, with `threshold1` shrunk to match. A
        `threshold0` of 0 (automatic collection off before the first
        hold) stays off."""
        floor, prior1, _ = self._prior
        if t0 <= self._young_mark or not floor:
            return
        t0_next = young_pass_threshold(
            self._count0 / (t0 - self._young_mark), now - t0, floor
        )
        gc.set_threshold(t0_next, floor * prior1 // t0_next,
                         gc.get_threshold()[2])

    def acquire(self, pace: bool = False) -> None:
        """Hold the watch; with `pace`, also pace the automatic passes."""
        with self._lock:
            self._refs += 1
            if self._refs == 1:
                gc.callbacks.append(self._callback)
            if pace:
                self._paced += 1
                if self._paced == 1:
                    self._prior = gc.get_threshold()
                    now = time.perf_counter()
                    self._full_mark = (now, self.collections[1])
                    self._young_mark = now

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
    over the process watch, `Runtime.GcFullThreshold` (the `threshold2`
    in force: 10, CPython's default, when not paced) and
    `Runtime.GcYoungThreshold` (the `threshold0` in force: 700 when not
    paced), on one registry."""
    w = _watch
    for gen in sorted(w.collections):
        metrics.gauge(f"Runtime.GcCollections.gen{gen}",
                      lambda g=gen: w.collections.get(g, 0))
        metrics.gauge(f"Runtime.GcSeconds.gen{gen}",
                      lambda g=gen: w.seconds.get(g, 0.0))
    metrics.gauge("Runtime.GcFullThreshold", lambda: gc.get_threshold()[2])
    metrics.gauge("Runtime.GcYoungThreshold", lambda: gc.get_threshold()[0])
