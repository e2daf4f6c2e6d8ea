"""The four-shard wave flush: what the benchmark's sharded cell cannot
show by itself.

- Durability per shard: every signed reply follows an fsync of the
  commit-log segment of its OWN transaction's shard (the harness counts
  any segment's fsync, so it cannot tell one shard from another).
- The cross-shard path: DvP frames (two inputs from two issuances) on
  four shards go through the two-phase reserve→commit, re-spends that
  span shards among them, and agree with the plain reference.
- A due wave takes every shard with work, so the shards' batches stay
  in step.
- The wave's profiler region `notary.wave` and the `shard` argument of
  a sharded flush's phase regions; the one-shard flush marks neither,
  and nothing is built off a capture.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import shutil
import sys
import time

import pytest

from corda_tpu.crypto.batch_verifier import CpuBatchVerifier
from corda_tpu.flows.api import FlowFuture
from corda_tpu.node.notary import (
    UniquenessConflict,
    _PendingNotarisation,
    shard_of_tx,
)
from corda_tpu.node.statestore import ShardedCommitLogUniquenessProvider
from corda_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SEED = 2**31 + 25
CONFIG = os.path.join(REPO, "benchmark", "configs",
                      "notary_p256_cash_sharded4.json")


class _StreamingCpu(CpuBatchVerifier):
    """CPU results through a streamed PendingVerification, so the
    flush takes the stream_commit path the chip takes."""

    def verify_batch_async(self, requests):
        import numpy as np

        from corda_tpu.crypto.batch_verifier import PendingVerification

        res = super().verify_batch(requests)
        pending = [(np.asarray(res[o:o + 4], dtype=bool),
                    list(range(o, min(o + 4, len(res)))),
                    min(4, len(res) - o))
                   for o in range(0, len(res), 4)]
        return PendingVerification([None] * len(res), pending, streamed=True)


class _Plane:
    """A batching notary over `shards` commit-log partitions, built
    from the sharded cell's configuration, and `n` of its Cash-move
    frames (1/16 tampered, 1/16 re-spends of states committed first)."""

    def __init__(self, tmp_path, shards=4, verifier=None, fsync=True,
                 n=128):
        from benchmark import fixture, harness
        from benchmark import store as storelib
        from corda_tpu.core import serialization as ser
        from corda_tpu.core.identity import Party

        with open(CONFIG) as fh:
            cfg = json.load(fh)
        cfg["notary"] = dict(cfg["notary"], shards=shards, max_wait_micros=0)
        self.store = ShardedCommitLogUniquenessProvider(
            str(tmp_path / "store"), shards, fsync=fsync
        )
        self.services, self.svc = harness.make_services(
            cfg, SEED, self.store, verifier or CpuBatchVerifier(), None
        )
        shape = fixture.load_shape(cfg["tx_shape"])
        shape.register()
        kinds = fixture.schedule(n, SEED)
        fanout = cfg["shape"]["issue_fanout"]
        self.kinds, self.stxs, spent = [], [], []
        for off in range(0, n, fanout):
            chunk = fixture.make_chunk(
                fixture.BENCH_DIR, cfg["tx_shape"], cfg["shape"], SEED, off,
                kinds[off:off + fanout],
            )
            self.services.record_transactions(
                ser.decode(b) for b in chunk.issues
            )
            self.stxs += [ser.decode(b) for b in chunk.blobs]
            self.kinds += kinds[off:off + fanout]
            spent += [ref for k, ins in zip(kinds[off:off + fanout],
                                            chunk.inputs)
                      if k == fixture.CONFLICT for ref in ins]
        storelib.commit_spent(self.store, spent)
        self.requester = Party(
            "O=Client,L=London,C=GB",
            fixture.keypair(SEED, "client", 4).public,
        )

    def submit(self, stxs, on_answer=None):
        futs = []
        for stx in stxs:
            fut = FlowFuture()
            if on_answer is not None:
                fut.add_done_callback(on_answer(stx))
            futs.append(fut)
            self.svc.enqueue_pending(
                _PendingNotarisation(stx, self.requester, fut)
            )
        return futs

    def close(self):
        self.svc.stop()
        self.store.close()


# -- durability per shard -----------------------------------------------------


@pytest.mark.parametrize("path,fsync", [
    ("join", True), ("stream", True), ("no_fsync", False),
])
def test_every_signed_reply_follows_its_own_shards_fsync(
        tmp_path, monkeypatch, path, fsync):
    """Per shard: the segment fsyncs of partition k that returned
    between a transaction's write into k and its signed reply. Every
    signed reply needs one; a store that never fsyncs fails them all,
    which shows the count can see a missing fsync."""
    from benchmark import fixture

    verifier = _StreamingCpu() if path == "stream" else CpuBatchVerifier()
    plane = _Plane(tmp_path, verifier=verifier, fsync=fsync)
    synced = [0] * 4
    real_fsync = os.fsync

    def counting_fsync(fd):
        real_fsync(fd)
        m = re.search(r"shard-(\d+)/segment-",
                      os.readlink(f"/proc/self/fd/{fd}"))
        if m:
            synced[int(m.group(1))] += 1

    monkeypatch.setattr(os, "fsync", counting_fsync)
    written: dict = {}
    for k, part in enumerate(plane.store._stores):
        def commit_rows(rows, _k=k, _real=part.commit_rows):
            for _ref, consumer, _who in rows:
                written.setdefault(
                    getattr(consumer, "bytes_", consumer), (_k, synced[_k])
                )
            return _real(rows)

        part.commit_rows = commit_rows
    at_reply: dict = {}

    def on_answer(stx):
        return lambda fut: at_reply.__setitem__(stx.id.bytes_, list(synced))

    try:
        futs = []
        for lo in (0, 64):
            futs += plane.submit(plane.stxs[lo:lo + 64], on_answer)
            plane.svc.flush()
    finally:
        plane.close()
    signed = unsynced = 0
    flushed_shards = set()
    for stx, kind, fut in zip(plane.stxs, plane.kinds, futs):
        got = "signed" if hasattr(fut.result(), "by") else "refused"
        assert (got == "signed") == (kind == fixture.VALID)
        if got != "signed":
            continue
        signed += 1
        shard, mark = written[stx.id.bytes_]
        assert shard == shard_of_tx(stx, 4)
        flushed_shards.add(shard)
        if at_reply[stx.id.bytes_][shard] <= mark:
            unsynced += 1
    assert signed and len(flushed_shards) == 4
    assert unsynced == (0 if fsync else signed)


# -- the cross-shard path -----------------------------------------------------


@pytest.fixture
def bench_root(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), root / "benchmark",
        ignore=shutil.ignore_patterns(".cache", "__pycache__"),
    )
    return str(root)


def test_cross_shard_dvp_frames_agree_with_the_reference(bench_root):
    """DvP frames on four shards at a CPU size: most spend two states
    that live on two partitions, so they commit through reserve→commit,
    and their re-spends conflict across shards. Every answer is held to
    construction and to the plain reference, and every commit is read
    back from the store reopened from disk (the harness's check)."""
    from benchmark import harness

    seen = collections.Counter()

    def watch_reserve(svc, services, store):
        real = store.reserve

        def reserve(states, tx_id, requester):
            seen["reserve"] += 1
            try:
                return real(states, tx_id, requester)
            except UniquenessConflict as e:
                if len({store.shard_of(r) for r in e.conflict}) > 1:
                    seen["conflict_across_shards"] += 1
                raise

        store.reserve = reserve

    args = harness.parse_args([
        "--workload", "dvp_ed25519.backlog", "--seed", str(SEED),
        "--seconds", "1", "--trace", "0",
    ])
    out = harness.run(
        args, time.monotonic(), root=bench_root, allow_cpu=True,
        overrides={
            # the pool outlasts what the notary can take in before its
            # first CPU wave (several seconds) blocks intake: the ring
            # batches drained inside the 50-ms hold, the ring and the
            # batch in flight, about 2,000 frames with the native codec
            "config": {"committed_states": 2048, "pool_per_s": 3000},
            "shape": {"issue_fanout": 8},
            "notary": {"verifier_batch_sizes": [32], "max_batch": 256,
                       "shards": 4},
            "store": {"n_shards": 4},
            "traffic": {"warmup_frames": 128},
            "workers": 1,
            "build_native": False,
        },
        fault=watch_reserve, verbose=lambda msg: None,
    )
    assert out["correct"], out["checks"]
    assert out["answers"]["conflict"] > 0
    assert seen["reserve"] > 0
    assert seen["conflict_across_shards"] > 0


# -- profiler regions ---------------------------------------------------------


# -- the wave's membership -----------------------------------------------------


def test_a_due_wave_takes_every_shard_with_work(tmp_path):
    """While every shard is inside its batching window the pump holds;
    once one shard's batch is due, the wave also takes the shards whose
    batches are not, so no shard falls due inside the wave and takes
    the next one alone."""
    plane = _Plane(tmp_path, n=48)
    svc = plane.svc
    svc.max_wait_micros = 50_000
    by_shard = collections.defaultdict(list)
    for stx in plane.stxs:
        by_shard[shard_of_tx(stx, 4)].append(stx)
    assert len(by_shard) >= 2
    waves = []
    real = svc._flush_wave

    def flush_wave(shards):
        waves.append([s.id for s in shards])
        return real(shards)

    svc._flush_wave = flush_wave
    first = min(by_shard)
    try:
        futs = plane.submit(plane.stxs)
        assert svc.tick() == 0
        assert waves == []
        svc._shards[first].oldest_arrival -= 60_000
        assert svc.tick() == 48
    finally:
        plane.close()
    assert waves == [sorted(by_shard)]
    assert all(f.done for f in futs)


def _capture(tmp_path, body):
    """{name: [(start_ns, end_ns, stats)]} of the host regions recorded
    while `body()` runs under a profiler capture."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "trace", "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("notary."):
                        out.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             dict(ev.stats))
                        )
    return out


PHASES = ("stage", "dispatch", "resolve_verify", "link_wait", "validate",
          "commit", "stream_commit", "sign_scatter")


def test_wave_region_carries_its_shards_and_frames(tmp_path):
    """One `notary.wave` per wave, with the shards it flushed, the
    plane's shard count, the transactions it carried and the deepest
    shard's count; every phase
    region inside it carries its shard, and a shard's consume starts
    after every shard's dispatch (the wave's own order)."""
    plane = _Plane(tmp_path, n=48)
    depth = collections.Counter(shard_of_tx(s, 4) for s in plane.stxs)

    def body():
        plane.submit(plane.stxs)
        plane.svc.flush()

    try:
        regions = _capture(tmp_path, body)
    finally:
        plane.close()
    (w0, w1, wave), = regions["notary.wave"]
    assert wave == {"shards": len(depth), "n_shards": 4, "frames": 48,
                    "max_frames": max(depth.values())}
    phase_regions = [(name[len("notary."):], s, e, st)
                     for name, evs in regions.items()
                     if name[len("notary."):] in PHASES
                     for s, e, st in evs]
    assert {st["shard"] for _, _, _, st in phase_regions} == set(depth)
    assert all(w0 <= s and e <= w1 for _, s, e, _ in phase_regions)
    dispatched = max(e for p, _, e, _ in phase_regions if p == "dispatch")
    assert all(s >= dispatched for p, s, _, _ in phase_regions
               if p == "resolve_verify")
    for shard in depth:
        assert {p for p, _, _, st in phase_regions
                if st["shard"] == shard} >= {"stage", "dispatch",
                                             "resolve_verify"}


def test_one_shard_flush_marks_no_wave_and_no_shard(tmp_path):
    """A one-shard flush is a wave of one: its `notary.wave` region
    counts one shard of one, and no region names a shard."""
    plane = _Plane(tmp_path, shards=1, n=16)

    def body():
        plane.submit(plane.stxs)
        plane.svc.flush()

    try:
        regions = _capture(tmp_path, body)
    finally:
        plane.close()
    (_w0, _w1, wave), = regions.pop("notary.wave")
    assert wave == {"shards": 1, "n_shards": 1, "frames": 16,
                    "max_frames": 16}
    assert regions["notary.stage"]
    assert all(st == {} for name, evs in regions.items()
               for _, _, st in evs)


def test_a_wave_builds_no_region_off_a_capture(tmp_path, monkeypatch):
    class Refused:
        @staticmethod
        def is_enabled():
            return False

        def __init__(self, *a, **kw):
            raise AssertionError("a region was built off a capture")

    opened = []
    real_open = tracing.open_region

    def open_region(name, **metadata):
        region = real_open(name, **metadata)
        opened.append((name, region))
        return region

    monkeypatch.setattr(tracing, "_traceme", Refused)
    monkeypatch.setattr(tracing, "open_region", open_region)
    plane = _Plane(tmp_path, n=16)
    try:
        futs = plane.submit(plane.stxs)
        plane.svc.flush()
    finally:
        plane.close()
    assert all(f.done for f in futs)
    assert ("notary.wave", None) in opened
    assert all(region is None for _, region in opened)
