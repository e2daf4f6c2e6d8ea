"""Performance-attribution plane: profiler, kernel accounting, skew,
history + baseline diff, /perf + /profile, the ?ts=1 echo.

The acceptance arc (ISSUE 7): GET /perf on a booted node attributes a
notarisation workload across host stages and device kernels
(compile-vs-execute split per (scheme, shape)); the retrace counter
holds ZERO after warmup and a deliberately shape-varying dispatch
drives it nonzero and fires the alert; per-shard skew gauges populate
under a skewed-prefix load with the skew alert firing (hot-shard trace
evidence) and resolving; and the in-process baseline diff flags a
synthetic 12% throughput regression against a fixture BENCH record.
The profiler's <=2% overhead bound is gated by `bench.py --quick perf`
(subprocess smoke at the bottom).

Simulated time (TestClock) everywhere the plane allows it; the
profiler tests are real time — sampling wall stacks has no simulated
analogue.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from corda_tpu.client.webserver import NodeWebServer
from corda_tpu.core import serialization as ser
from corda_tpu.core.contracts import Amount, Issued, StateRef
from corda_tpu.core.identity import PartyAndReference
from corda_tpu.core.transactions import TransactionBuilder
from corda_tpu.crypto import schemes
from corda_tpu.crypto.batch_verifier import (
    CpuBatchVerifier,
    TpuBatchVerifier,
    VerificationRequest,
)
from corda_tpu.finance.cash import (
    CASH_CONTRACT,
    CashIssue,
    CashMove,
    CashState,
)
from corda_tpu.flows.api import FlowFuture
from corda_tpu.node.notary import (
    BatchingNotaryService,
    ShardedUniquenessProvider,
    _PendingNotarisation,
)
from corda_tpu.node.services import TestClock
from corda_tpu.testing.mock_network import MockNetwork
from corda_tpu.utils import health as hlib
from corda_tpu.utils import perf as plib
from corda_tpu.utils.metrics import MetricRegistry
from corda_tpu.utils.tracing import Tracer


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers["Content-Type"], resp.read()


def _get_json(url, timeout=10):
    status, _, body = _get(url, timeout)
    return status, json.loads(body)


# ---------------------------------------------------------------------------
# sampling profiler


def test_profiler_folded_stacks_and_prefix_filter():
    stop = threading.Event()

    def busy():
        while not stop.is_set():
            sum(range(200))

    t = threading.Thread(target=busy, name="flush-worker-0", daemon=True)
    t.start()
    try:
        prof = plib.SamplingProfiler(hz=100).watch("flush-worker")
        for _ in range(20):
            prof.sample_once()          # deterministic: no sampler thread
        folded = prof.collapsed()
        assert folded, "watched busy thread produced no stacks"
        for line in folded.splitlines():
            stack, count = line.rsplit(" ", 1)
            assert count.isdigit() and int(count) >= 1
            assert stack.startswith("flush-worker-0;")
            assert ";" in stack          # thread;file:func;...
        # the filter held: nothing from MainThread (this test's frame)
        assert "MainThread" not in folded
        assert prof.samples == 20 and prof.frames_seen >= 1
    finally:
        stop.set()


def test_profiler_measures_own_overhead_and_bounds_table():
    prof = plib.SamplingProfiler(hz=200, max_stacks=4)
    stop = threading.Event()
    threads = [
        threading.Thread(
            target=lambda: stop.wait(5), name=f"parked-{i}", daemon=True
        )
        for i in range(8)
    ]
    for t in threads:
        t.start()
    try:
        prof.watch("parked-")
        prof.start()
        time.sleep(0.25)
        prof.stop()
        assert prof.samples > 0
        snap = prof.snapshot()
        # the overhead is MEASURED (sample wall / elapsed wall), tiny
        assert 0.0 <= snap["overhead_fraction"] < 0.5
        assert snap["distinct_stacks"] <= 4          # bounded table
        # 8 parked threads, 4 table slots: the bound dropped some
        assert prof.truncated > 0
    finally:
        stop.set()


# ---------------------------------------------------------------------------
# kernel accounting: compile-vs-execute split + retraces


def _p256_requests(n: int):
    kp = schemes.generate_keypair(
        schemes.ECDSA_SECP256R1_SHA256, seed=11
    )
    msg = b"perf-attribution"
    sig = kp.private.sign(msg)
    return [VerificationRequest(kp.public, sig, msg)] * n


def _stub_kernels(monkeypatch):
    """Replace the EC ladder with an accept-all stub so the dispatch
    seam (staging, shape bucketing, the accounting hooks) runs for
    real without minutes of XLA compile."""
    monkeypatch.setattr(
        TpuBatchVerifier,
        "_kernel",
        lambda self, scheme_id, batch: (
            lambda **staged: np.ones(batch, dtype=bool)
        ),
    )


def test_verifier_dispatch_records_compile_execute_split(monkeypatch):
    _stub_kernels(monkeypatch)
    acct = plib.KernelAccounting()
    v = TpuBatchVerifier(batch_sizes=(4, 8), perf=acct)
    assert all(v.verify_batch(_p256_requests(3)))     # shape 4: compile
    assert all(v.verify_batch(_p256_requests(3)))     # shape 4: execute
    snap = acct.snapshot()
    row = snap["keys"][f"scheme{schemes.ECDSA_SECP256R1_SHA256}/batch4"]
    assert row["compiles"] == 1 and row["executes"] == 1
    assert row["compile_seconds"] > 0 and row["execute_seconds"] > 0
    assert row["transfer_bytes"] > 0                  # staged operands
    # warmup compiles are NOT retraces
    assert acct.retraces == 0 and acct.compiles == 1
    # a standalone transfer (the pinned-device device_put path) must
    # touch ONLY the transfer fields — a phantom zero-second execute
    # would halve the execute mean the split exists for
    sid = schemes.ECDSA_SECP256R1_SHA256
    acct.record_transfer(sid, 4, 4096, 0.001)
    row = acct.snapshot()["keys"][f"scheme{sid}/batch4"]
    assert row["executes"] == 1 and row["compiles"] == 1
    assert row["transfer_seconds"] > 0


def test_retrace_zero_after_warmup_then_shape_varying_drives_it(
    monkeypatch,
):
    _stub_kernels(monkeypatch)
    acct = plib.KernelAccounting()
    v = TpuBatchVerifier(batch_sizes=(4, 8), perf=acct)
    v.verify_batch(_p256_requests(3))                 # warm shape 4
    acct.mark_warm()
    for _ in range(3):                                # stable at zero
        v.verify_batch(_p256_requests(4))
    assert acct.retraces == 0
    v.verify_batch(_p256_requests(6))                 # NEW shape: 8
    assert acct.retraces == 1
    assert acct.is_cold(schemes.ECDSA_SECP256R1_SHA256, 4) is False


def test_retrace_alert_fires_on_shape_varying_load_and_resolves(
    monkeypatch,
):
    _stub_kernels(monkeypatch)
    clock = TestClock()
    plane = plib.PerfPlane(
        clock=clock,
        policy=plib.PerfPolicy(
            sample_gap_micros=0,
            retrace_warmup_micros=1_000,
            skew_window_micros=5_000_000,
        ),
        install_default_kernels=False,
    )
    monitor = hlib.HealthMonitor(
        clock=clock,
        policy=hlib.HealthPolicy(
            alert_for_micros=0, alert_clear_for_micros=0
        ),
    )
    monitor.watch_perf(plane)
    v = TpuBatchVerifier(batch_sizes=(4, 8), perf=plane.kernels)
    v.verify_batch(_p256_requests(3))           # warmup compile
    clock.advance(2_000)                        # past the grace: armed
    monitor.tick()
    alerts = monitor.snapshot()["alerts"]
    assert alerts["perf.jit_retrace"]["state"] == hlib.ALERT_INACTIVE

    v.verify_batch(_p256_requests(6))           # shape-varying: retrace
    clock.advance(1_000)
    monitor.tick()
    alert = monitor.snapshot()["alerts"]["perf.jit_retrace"]
    assert alert["state"] == hlib.ALERT_FIRING
    assert alert["detail"]["retraces"] == 1
    assert alert["detail"]["retraces_in_window"] >= 1

    # shapes stop varying: the window slides past the burst, resolves
    for _ in range(8):
        clock.advance(1_000_000)
        v.verify_batch(_p256_requests(4))       # warm shape only
        monitor.tick()
    assert (
        monitor.snapshot()["alerts"]["perf.jit_retrace"]["state"]
        == hlib.ALERT_RESOLVED
    )
    assert plane.kernels.retraces == 1          # stable since


# ---------------------------------------------------------------------------
# shard skew: gauges, alert fire with hot-shard evidence, resolve


def _sharded_rig(n_spends: int, shards: int = 4, seed: int = 31):
    net = MockNetwork(seed=seed, batch_verifier=CpuBatchVerifier())
    notary = net.create_notary("Notary", batching=True)
    bank = net.create_node("Bank")
    alice = net.create_node("Alice")
    token = Issued(PartyAndReference(bank.party, b"\x01"), "USD")
    spends = []
    for i in range(n_spends):
        ib = TransactionBuilder(notary.party)
        ib.add_output_state(
            CashState(Amount(100 + i, token), alice.party.owning_key),
            CASH_CONTRACT,
        )
        ib.add_command(CashIssue(i + 1), bank.party.owning_key)
        issue = bank.services.sign_initial_transaction(ib)
        notary.services.record_transactions([issue])
        alice.services.record_transactions([issue])
        sb = TransactionBuilder(notary.party)
        sb.add_input_state(alice.vault.state_and_ref(StateRef(issue.id, 0)))
        sb.add_output_state(
            CashState(Amount(100 + i, token), bank.party.owning_key),
            CASH_CONTRACT, notary.party,
        )
        sb.add_command(CashMove(), alice.party.owning_key)
        spends.append(alice.services.sign_initial_transaction(sb))
    svc = BatchingNotaryService(
        notary.services,
        ShardedUniquenessProvider(shards),
        max_batch=256,
        shards=shards,
    )
    return net, svc, alice.party, spends


def test_skewed_prefix_load_fires_skew_alert_with_evidence_then_resolves():
    net, svc, requester, spends = _sharded_rig(56)
    tracer = Tracer(enabled=True)
    plane = plib.PerfPlane(
        clock=net.clock,
        policy=plib.PerfPolicy(
            sample_gap_micros=0,
            skew_window_micros=10_000_000,
            skew_min_requests=8,
            skew_threshold=2.0,
        ),
        install_default_kernels=False,
    )
    svc.attach_perf(plane)
    monitor = hlib.HealthMonitor(
        clock=net.clock, tracer=tracer,
        policy=hlib.HealthPolicy(
            alert_for_micros=0, alert_clear_for_micros=0
        ),
    )
    monitor.watch_perf(plane)

    by_shard: dict[int, list] = {}
    for stx in spends:
        by_shard.setdefault(svc.shard_of(stx), []).append(stx)
    hot = max(by_shard, key=lambda k: len(by_shard[k]))
    assert len(by_shard[hot]) >= 8, "fixture too small to skew"

    def notarise(stxs) -> None:
        futs = []
        for stx in stxs:
            span = tracer.start_trace("notarise.frame", tx_id=str(stx.id))
            fut = FlowFuture()
            futs.append(fut)
            svc.enqueue_pending(
                _PendingNotarisation(stx, requester, fut, span=span)
            )
        svc.flush()
        for fut in futs:
            assert hasattr(fut.result(), "by")

    # skewed-prefix load: every request lands on ONE shard
    notarise(by_shard[hot])
    net.clock.advance(1_000)
    monitor.tick()

    # gauges populated: the ratio gauge reads the full N-on-one skew
    ratio = plane.metrics.get("Perf.SkewRatio").value()
    assert ratio == pytest.approx(4.0)
    share = plane.metrics.get(f"Perf.Shard{hot}.LoadShare").value()
    assert share == pytest.approx(1.0)
    snap = plane.skew.snapshot()
    assert snap["hot_shard"] == hot
    assert snap["per_shard"][hot]["flushes_in_window"] >= 1
    assert snap["per_shard"][hot]["mean_flush_wall_s"] > 0

    alert = monitor.snapshot()["alerts"]["perf.shard_skew"]
    assert alert["state"] == hlib.ALERT_FIRING
    assert alert["detail"]["hot_shard"] == hot
    assert alert["detail"]["skew_ratio"] == pytest.approx(4.0)
    # evidence: the slowest traces that actually TOUCHED the hot shard
    evidence = alert["evidence"]["traces"]
    assert evidence, "skew alert fired without trace evidence"
    ids = {t["trace_id"] for t in evidence}
    hot_traces = {
        f"{t.trace_id:#x}"
        for t in tracer.recorder.slowest()
        if t.matches(f"shard{hot}")
    }
    assert ids <= hot_traces

    # balanced load after the window slides: the alert resolves
    balanced = [s for k, v in by_shard.items() if k != hot for s in v]
    net.clock.advance(11_000_000)            # old anchors age out
    for stx in balanced:
        notarise([stx])
        net.clock.advance(200_000)
    monitor.tick()
    assert (
        monitor.snapshot()["alerts"]["perf.shard_skew"]["state"]
        == hlib.ALERT_RESOLVED
    )
    assert plane.skew.skew()[0] < 2.0


def test_skew_alert_resolves_when_traffic_stops():
    """The skew window must keep sliding on an IDLE plane: once the
    hot burst ages past the window (plane.tick anchors it), the alert
    resolves — it must not stay firing forever on a quiet node."""
    clock = TestClock()
    plane = plib.PerfPlane(
        clock=clock,
        policy=plib.PerfPolicy(
            sample_gap_micros=0,
            skew_window_micros=5_000_000,
            skew_min_requests=8,
        ),
        install_default_kernels=False,
    )
    plane.attach_shards(4, [lambda: 0] * 4)
    monitor = hlib.HealthMonitor(
        clock=clock,
        policy=hlib.HealthPolicy(
            alert_for_micros=0, alert_clear_for_micros=0
        ),
    )
    monitor.watch_perf(plane)
    for _ in range(4):                         # hot burst, shard 2 only
        plane.skew.observe_flush(2, 8, 0.001)
        clock.advance(1_000)
    monitor.tick()
    assert (
        monitor.snapshot()["alerts"]["perf.shard_skew"]["state"]
        == hlib.ALERT_FIRING
    )
    for _ in range(8):                         # idle: ticks only
        clock.advance(1_000_000)
        plane.tick()
        monitor.tick()
    assert (
        monitor.snapshot()["alerts"]["perf.shard_skew"]["state"]
        == hlib.ALERT_RESOLVED
    )
    assert plane.skew.skew()[0] == 1.0         # window fully decayed


def test_second_verifier_instance_compiles_are_not_hidden(monkeypatch):
    """first-call-per-shape is judged per VERIFIER: jit caches live on
    the instance, so a second verifier's first dispatch of a shape
    pays its own trace+lower and must record as a compile on the
    shared ledger — not masquerade as a multi-second execute."""
    _stub_kernels(monkeypatch)
    acct = plib.KernelAccounting()
    v1 = TpuBatchVerifier(batch_sizes=(4,), perf=acct)
    v2 = TpuBatchVerifier(batch_sizes=(4,), perf=acct)
    v1.verify_batch(_p256_requests(3))
    v2.verify_batch(_p256_requests(3))         # ITS first call: compile
    key = f"scheme{schemes.ECDSA_SECP256R1_SHA256}/batch4"
    row = acct.snapshot()["keys"][key]
    assert row["compiles"] == 2 and row["executes"] == 0


def test_wave_overlap_efficiency_from_marks():
    wave = plib.WaveOverlap()
    # two shards, 10ms wave; shard 1 spent 4ms blocked on the link
    wave.observe([
        (0, 8, [("stage", 0.000, 0.002), ("dispatch", 0.002, 0.004),
                ("commit", 0.006, 0.010)]),
        (1, 8, [("stage", 0.001, 0.003), ("link_wait", 0.004, 0.008)]),
    ])
    snap = wave.snapshot()
    assert snap["waves"] == 1
    assert snap["overlap_efficiency"] == pytest.approx(0.6)
    # a fully-streamed wave (no link_wait) is perfect overlap
    wave2 = plib.WaveOverlap()
    wave2.observe([(0, 4, [("stage", 0.0, 0.001), ("commit", 0.001, 0.002)])])
    assert wave2.snapshot()["overlap_efficiency"] == pytest.approx(1.0)


def test_sharded_flush_feeds_wave_overlap():
    net, svc, requester, spends = _sharded_rig(24, seed=37)
    plane = plib.PerfPlane(
        clock=net.clock,
        policy=plib.PerfPolicy(sample_gap_micros=0),
        install_default_kernels=False,
    )
    svc.attach_perf(plane)
    futs = [svc.submit(stx, requester) for stx in spends]
    svc.flush()
    for fut in futs:
        assert hasattr(fut.result(), "by")
    snap = plane.wave.snapshot()
    assert snap["waves"] >= 1
    assert snap["overlap_efficiency"] is not None
    assert 0.0 <= snap["overlap_efficiency"] <= 1.0


# ---------------------------------------------------------------------------
# history ring + baseline diff


def _bench_fixture_record(tmp_path, value: float):
    doc = {
        "n": 6,
        "cmd": "python bench.py",
        "rc": 0,
        "tail": "\n".join([
            "WARNING: All log messages before absl::InitializeLog() is called are written to STDERR",
            json.dumps({
                "metric": "batching_notary_notarisations_per_sec",
                "value": value,
                "unit": "notarisations/s",
                "vs_baseline": round(value / 50_000.0, 3),
            }),
            json.dumps({
                "metric": "wire_ingest_pipelined_per_sec",
                "value": 20_000.0,
                "unit": "tx/s",
            }),
        ]),
    }
    path = tmp_path / "BENCH_r06.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_history_ring_is_bounded_and_sustained_is_lower_median():
    hist = plib.PerfHistory(capacity=16)
    for i in range(100):
        hist.record("k", i, float(i))
    assert len(hist.series("k")) == 16            # bounded
    assert hist.latest("k") == 99.0
    assert hist.sustained("k", window=4) == 97.0  # lower median of last 4


def test_baseline_diff_flags_synthetic_12pct_regression(tmp_path):
    clock = TestClock()
    plane = plib.PerfPlane(
        clock=clock,
        policy=plib.PerfPolicy(sample_gap_micros=0),
        install_default_kernels=False,
        baseline_path=_bench_fixture_record(tmp_path, 50_000.0),
    )
    served = {"n": 0}
    ingested = {"n": 0}
    plane.watch_rate(
        "batching_notary_notarisations_per_sec", lambda: served["n"]
    )
    plane.watch_rate(
        "wire_ingest_pipelined_per_sec", lambda: ingested["n"]
    )
    for _ in range(10):
        served["n"] += 44_000            # 12% under the 50k baseline
        ingested["n"] += 21_000          # healthy: above ITS baseline
        clock.advance(1_000_000)
        plane.tick()
    diff = plane.baseline_diff()
    assert diff["baseline"] == "BENCH_r06.json"
    rows = {r["metric"]: r for r in diff["rows"]}
    bad = rows["batching_notary_notarisations_per_sec"]
    assert bad["regressed"] is True
    assert bad["delta_pct"] == pytest.approx(-12.0)
    assert rows["wire_ingest_pipelined_per_sec"]["regressed"] is False
    assert diff["regressions"] == [
        "batching_notary_notarisations_per_sec regressed 12.0% "
        "vs BENCH_r06.json"
    ]
    # the /perf payload carries the same verdict
    assert plane.snapshot()["baseline"]["regressions"]


def test_missing_baseline_degrades_not_500(tmp_path):
    """A configured-but-absent baseline file must degrade ONLY the
    baseline section of /perf (with the error named), never take the
    whole attribution snapshot down."""
    plane = plib.PerfPlane(
        clock=TestClock(),
        baseline_path=str(tmp_path / "no-such-BENCH_r99.json"),
        install_default_kernels=False,
    )
    snap = plane.snapshot()                        # must not raise
    assert snap["baseline"]["rows"] == []
    assert "FileNotFoundError" in snap["baseline"]["error"]
    assert "profiler" in snap and "kernels" in snap


def test_notary_attach_perf_feeds_the_history_key():
    net, svc, requester, spends = _sharded_rig(8, shards=1, seed=41)
    plane = plib.PerfPlane(
        clock=net.clock,
        policy=plib.PerfPolicy(sample_gap_micros=0),
        install_default_kernels=False,
    )
    svc.attach_perf(plane)
    plane.tick()                                   # rate anchor
    futs = [svc.submit(stx, requester) for stx in spends]
    svc.flush()
    for fut in futs:
        assert hasattr(fut.result(), "by")
    net.clock.advance(1_000_000)
    plane.tick()
    assert plane.history.latest(
        "batching_notary_notarisations_per_sec"
    ) == pytest.approx(8.0)                        # 8 served in 1s


# ---------------------------------------------------------------------------
# ingest pipeline hook


def test_ingest_pipeline_reports_frames_and_stage_seconds():
    from corda_tpu.node.ingest import IngestPipeline

    net, _svc, _requester, spends = _sharded_rig(4, shards=1, seed=43)
    blobs = [ser.encode(stx) for stx in spends]
    plane = plib.PerfPlane(
        clock=net.clock,
        policy=plib.PerfPolicy(sample_gap_micros=0),
        install_default_kernels=False,
    )
    pipe = IngestPipeline(perf=plane, frame_cache_size=0)
    entries = pipe.ingest(blobs)
    pipe.close()
    assert all(e.error is None for e in entries)
    assert plane.ingest_frames == len(blobs)
    stages = plane.snapshot()["host_stages"]
    assert stages["ingest.decode"]["total_s"] > 0
    assert stages["ingest.decode"]["count"] == len(blobs)


# ---------------------------------------------------------------------------
# the booted node: /perf, /profile, ?ts=1


def test_node_boots_perf_plane_and_serves_attribution(tmp_path, monkeypatch):
    from corda_tpu.node.config import NodeConfig, RpcUserConfig
    from corda_tpu.node.node import Node

    _stub_kernels(monkeypatch)
    node = Node(
        NodeConfig(
            name="PerfNode", base_dir=str(tmp_path / "n"),
            notary="batching", notary_shards=4, use_tls=False,
            verifier_backend="cpu", web_port=0,
            perf_profile_hz=97.0,
            rpc_users=(RpcUserConfig("ops", "pw", ("ALL",)),),
        )
    ).start()
    try:
        assert node.perf is not None
        assert node.perf.profiler.running
        base = f"http://127.0.0.1:{node.web.port}"

        # drive the canary through a few real flushes so the notary
        # phase timers populate (the node is SHARDED: the canary
        # routes to its shard's queue through enqueue_pending)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            node.pump()
            if node.health.canary.completed >= 1:
                break
            time.sleep(0.01)
        assert node.health.canary.completed >= 1

        # a TpuBatchVerifier with NO explicit accounting records into
        # the shared process accounting the node plane adopted — the
        # production seam. Deltas, not absolutes: the ledger is
        # process-scoped (like the jit caches), so other suites may
        # already hold rows.
        key = f"scheme{schemes.ECDSA_SECP256R1_SHA256}/batch4"
        before = node.perf.kernels.snapshot()
        row0 = before["keys"].get(
            key, {"compiles": 0, "executes": 0}
        )
        v = TpuBatchVerifier(batch_sizes=(4,))
        assert all(v.verify_batch(_p256_requests(3)))
        assert all(v.verify_batch(_p256_requests(3)))

        status, body = _get_json(base + "/perf")
        assert status == 200
        # host stages attributed (the canary flushes populated them)
        assert body["host_stages"], "no host stage attribution"
        assert "stage" in body["host_stages"]
        assert "sign_scatter" in body["host_stages"]
        assert body["shards"]["n_shards"] == 4
        assert body["shards"]["requests_in_window"] >= 1   # the canary
        # device kernels: the compile-vs-execute split per (scheme,
        # shape) — one compile (first call this process for the
        # shape), the rest executes, and NO retraces from the warm
        # repeat
        row = body["kernels"]["keys"][key]
        new_calls = (
            row["compiles"] + row["executes"]
            - row0["compiles"] - row0["executes"]
        )
        assert new_calls == 2
        assert row["compiles"] >= 1 and row["executes"] >= 1
        assert body["kernels"]["retraces"] == before["retraces"]
        assert body["profiler"]["running"] is True

        # the profiler saw the node's threads: /profile serves folded
        # stacks (flamegraph.pl format)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            node.pump()
            if node.perf.profiler.samples >= 3:
                break
            time.sleep(0.01)
        status, ctype, payload = _get(base + "/profile")
        assert status == 200 and ctype.startswith("text/plain")
        lines = [
            ln for ln in payload.decode().splitlines()
            if ln and not ln.startswith("#")
        ]
        assert lines, "no folded stacks after sampling"
        assert all(ln.rsplit(" ", 1)[1].isdigit() for ln in lines)

        # Perf.* gauges land on the node's scrape surface
        _, _, metrics_text = _get(base + "/metrics")
        assert b"Perf_ProfilerOverhead" in metrics_text
        assert b"Perf_KernelRetraces" in metrics_text

        # the shared ?ts=1 echo: one monotonic stamp per payload, on
        # JSON endpoints AND the /metrics text form
        status, perf_body = _get_json(base + "/perf?ts=1")
        status2, health_body = _get_json(base + "/health?ts=1")
        assert isinstance(perf_body["ts_micros"], int)
        assert isinstance(health_body["ts_micros"], int)
        assert abs(health_body["ts_micros"] - perf_body["ts_micros"]) < (
            60_000_000
        )
        _, _, stamped = _get(base + "/metrics?ts=1")
        assert b"# ts_micros " in stamped
        # without the query nothing changes
        _, plain_body = _get_json(base + "/perf")
        assert "ts_micros" not in plain_body
    finally:
        node.stop()
        assert not node.perf.profiler.running       # stopped with the node


def test_webserver_perf_404_when_not_wired():
    web = NodeWebServer(
        client=object(), pump=lambda: None, metrics=MetricRegistry()
    ).start()
    try:
        base = f"http://127.0.0.1:{web.port}"
        for path in ("/perf", "/profile"):
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(base + path, timeout=10)
            assert exc.value.code == 404
            assert "error" in json.loads(exc.value.read())
        # the index lists both, disabled
        status, index = _get_json(base + "/")
        paths = {e["path"]: e for e in index["endpoints"]}
        assert paths["/perf"]["enabled"] is False
        assert paths["/profile"]["enabled"] is False
    finally:
        web.stop()


# ---------------------------------------------------------------------------
# CI smoke: the bench plumbing itself (profiler overhead bound)


def test_bench_quick_perf_bounds_overhead_and_counts_retrace():
    """`bench.py --quick perf` must run under JAX_PLATFORMS=cpu and
    gate the profiler's measured overhead at <=2% of the notary flush
    wall, with the forced-retrace proof in the same record."""
    bench = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    out = subprocess.run(
        [sys.executable, os.path.abspath(bench), "--quick", "perf"],
        # default batch/iters: the quick mode's 32x3 interleaved A/B
        # is the tuned noise floor (the health smoke's discipline)
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "perf_plane_overhead"
    assert rec["quick"] is True
    assert rec["value"] <= 0.02
    assert rec["profiler_samples"] >= 1
    assert rec["retrace_stable_after_warmup"] is True
    assert rec["retrace_counted"] is True
