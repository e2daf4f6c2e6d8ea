"""The readers of the program's own profiler regions: on a synthetic
capture in the profiler's format, on a capture from a program that
marks none (they read nothing and do not raise), and on a traced run of
a cell on the CPU."""

from __future__ import annotations

import os

import pytest

import benchmark_harness_util  # noqa: F401  (puts the repo on sys.path)
from benchmark import regions as regionlib
from benchmark_harness_util import run_cell

# the harness's window over 10 us; a flush phase, a hold episode that
# starts before the window, a collector pause, and two ladder launches
# of which the second starts after the window closes
XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: -2000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 5000000 duration_ps: 1000000
      stats { metadata_id: 6 int64_value: 5 }
      stats { metadata_id: 7 int64_value: 32 } }
    events { metadata_id: 5 offset_ps: 11000000 duration_ps: 1000000
      stats { metadata_id: 6 int64_value: 32 }
      stats { metadata_id: 7 int64_value: 32 } }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "notary.stage" } }
  event_metadata { key: 3 value { id: 3 name: "notary.hold" } }
  event_metadata { key: 4 value { id: 4 name: "gc.collect" } }
  event_metadata { key: 5 value { id: 5 name: "verify.launch" } }
  stat_metadata { key: 6 value { id: 6 name: "rows" } }
  stat_metadata { key: 7 value { id: 7 name: "batch" } }
}
"""


def _profile(text):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)
    )


def test_regions_are_read_inside_the_window():
    r = regionlib.from_profile(_profile(XSPACE))
    assert r.window_s == pytest.approx(10e-6)
    assert r.seconds("notary.stage") == pytest.approx(2e-6)
    assert r.seconds("notary.hold") == pytest.approx(2e-6)   # clipped
    assert r.seconds("gc.collect") == pytest.approx(0.5e-6)
    assert r.seconds("notary.starved") == 0
    assert r.stat_sum("verify.launch", "rows") == 5
    assert r.stat_sum("verify.launch", "batch") == 32


def test_a_program_without_the_regions_reads_nothing():
    """A capture of a program that marks no flush phase (the parent of
    the regions) gives no Regions, and every reader returns None."""
    old = XSPACE.replace('"notary.stage"', '"flush"')
    assert regionlib.from_profile(_profile(old)) is None

    class Ctx:
        trace = None

    for name in ("ladder_fill_share", "pump_hold_share",
                 "pump_starved_share", "gc_pause_share"):
        mod = __import__(f"benchmark.metrics.{name}", fromlist=["read"])
        assert mod.read(Ctx()) is None


def test_traced_cell_reads_the_regions(bench_root, monkeypatch):
    """A traced run on the CPU reads the four region metrics as shares,
    and its capture's flush-phase regions add up, phase by phase, to
    what the FlushPhase timers counted over the traced window."""
    from jax.profiler import ProfileData

    from benchmark import harness
    from benchmark import trace as tracelib
    from corda_tpu.utils.perf import flush_phase_seconds

    snapshots = []
    real = harness.registry_snapshot

    def snapshot(svc):
        snapshots.append(flush_phase_seconds(svc.metrics))
        return real(svc)

    monkeypatch.setattr(harness, "registry_snapshot", snapshot)
    out = run_cell(bench_root, "p256_cash.backlog", trace=1)
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in ("ladder_fill_share", "pump_hold_share",
                 "pump_starved_share", "gc_pause_share"):
        assert 0 <= m[f"{name}.backlog"] <= 1, name
    assert m["ladder_fill_share.backlog"] > 0

    before, after = snapshots     # the traced window's two ends
    r = regionlib.from_profile(ProfileData.from_file(tracelib.find_xspace(
        os.path.join(bench_root, "benchmark", ".cache", "trace"))))
    marked = 0
    for phase, row in after.items():
        n = row["count"] - before.get(phase, {}).get("count", 0)
        if not n:
            continue
        marked += 1
        delta = row["total_s"] - before.get(phase, {}).get("total_s", 0.0)
        assert r.seconds("notary." + phase) == pytest.approx(
            delta, rel=0.05, abs=30e-6 * n), phase
    assert marked >= 4
