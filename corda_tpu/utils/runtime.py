"""Process runtime ledger: garbage-collector pauses.

A `gc.callbacks` watch counts every collection and its pause seconds
per generation, and marks each pause as a `gc.collect` profiler region
(utils/tracing.open_region) carrying its generation — so a capture
shows the collector's pauses on the same clock as the device trace.

Process-scoped like device_telemetry.get_device_accounting: the
collector is a process resource, and two embedded nodes must read one
ledger. `BatchingNotaryService` and `Node` each hold one reference
(`acquire` / `release`); the callback is installed while any is held.
"""

from __future__ import annotations

import gc
import time

from . import locks, tracing


class GcWatch:
    """Collections and pause seconds per generation, since the process
    started watching. Callbacks never overlap (one collection at a
    time under the GIL), so the counters need no lock; the lock guards
    the reference count alone."""

    def __init__(self):
        self.collections: dict[int, int] = {0: 0, 1: 0, 2: 0}
        self.seconds: dict[int, float] = {0: 0.0, 1: 0.0, 2: 0.0}
        self._t0: float | None = None
        self._region = None
        self._refs = 0
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
        self.collections[gen] = self.collections.get(gen, 0) + 1
        self.seconds[gen] = (
            self.seconds.get(gen, 0.0) + time.perf_counter() - t0
        )
        region, self._region = self._region, None
        tracing.close_region(region, collected=info["collected"])

    def acquire(self) -> None:
        with self._lock:
            self._refs += 1
            if self._refs == 1:
                gc.callbacks.append(self._callback)

    def release(self) -> None:
        with self._lock:
            if not self._refs:
                return
            self._refs -= 1
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
    over the process watch, on one registry."""
    w = _watch
    for gen in sorted(w.collections):
        metrics.gauge(f"Runtime.GcCollections.gen{gen}",
                      lambda g=gen: w.collections.get(g, 0))
        metrics.gauge(f"Runtime.GcSeconds.gen{gen}",
                      lambda g=gen: w.seconds.get(g, 0.0))
