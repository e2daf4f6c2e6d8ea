"""End-to-end hot-path tracing (utils/tracing.py): span propagation,
flight-recorder retention, Chrome export, the connected-trace
acceptance path, and the registry-backed observability satellites.

The contract under test: ONE notarisation driven through
MessagingService -> IngestRing -> IngestPipeline ->
BatchingNotaryService -> BatchSignatureVerifier yields ONE connected
trace (every span shares the trace_id, every parent link resolves)
with the stage spans a regression hunt needs — retrievable from both
the flight recorder and GET /traces — while a tracing-DISABLED run
creates no spans at all.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from corda_tpu.core import serialization as ser
from corda_tpu.core.contracts import Amount, Issued, StateRef
from corda_tpu.core.identity import PartyAndReference
from corda_tpu.core.transactions import TransactionBuilder
from corda_tpu.crypto.batch_verifier import CpuBatchVerifier
from corda_tpu.finance.cash import (
    CASH_CONTRACT,
    CashIssue,
    CashMove,
    CashState,
)
from corda_tpu.flows.api import FlowFuture
from corda_tpu.node.ingest import IngestPipeline, IngestRing
from corda_tpu.node.messaging import InMemoryMessagingNetwork
from corda_tpu.node.notary import _PendingNotarisation
from corda_tpu.testing.mock_network import MockNetwork
from corda_tpu.utils.metrics import MetricRegistry
from corda_tpu.utils.tracing import (
    NOOP_SPAN,
    FlightRecorder,
    SpanContext,
    Tracer,
    chrome_trace,
    stage_summary,
)


def _cash_spends(n: int, seed: int = 51):
    """(net, notary node, requester party, [SignedTransaction])."""
    net = MockNetwork(seed=seed, batch_verifier=CpuBatchVerifier())
    notary = net.create_notary("Notary", batching=True)
    bank = net.create_node("Bank")
    alice = net.create_node("Alice")
    token = Issued(PartyAndReference(bank.party, b"\x01"), "USD")
    spends = []
    for i in range(n):
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
            CASH_CONTRACT,
            notary.party,
        )
        sb.add_command(CashMove(), alice.party.owning_key)
        spends.append(alice.services.sign_initial_transaction(sb))
    return net, notary, alice.party, spends


# ---------------------------------------------------------------------------
# span mechanics + propagation


def test_span_parenting_survives_fabric_hop():
    """The sender's SpanContext rides the optional Message.trace header
    and the receiver's start_trace(parent=...) JOINS the same trace —
    parent links intact across the hop."""
    tracer = Tracer(enabled=True)
    imn = InMemoryMessagingNetwork()
    rx = imn.endpoint("rx")
    tx = imn.endpoint("tx")
    received = []
    rx.add_handler("traced.topic", received.append)

    client = tracer.start_trace("client.submit", peer="rx")
    tx.send("traced.topic", b"payload", "rx", trace=tuple(client.context))
    imn.run()
    assert len(received) == 1
    header = received[0].trace
    assert header == tuple(client.context)

    server = tracer.start_trace("server.handle", parent=header)
    assert server.trace_id == client.trace_id
    assert server.parent_id == client.span_id
    server.end()
    client.end()

    traces = tracer.recorder.traces()
    assert len(traces) == 1
    spans = traces[0].spans
    assert {s.name for s in spans} == {"client.submit", "server.handle"}
    assert all(s.trace_id == client.trace_id for s in spans)
    # a header mangled in transit degrades to a fresh trace, never a crash
    assert SpanContext.from_header("garbage") is None
    assert SpanContext.from_header(None) is None


def test_flight_recorder_keeps_slowest_under_churn():
    """Churn evicts from the recent ring only: the N slowest completed
    traces survive 200 faster newcomers."""
    rec = FlightRecorder(keep_recent=4, keep_slowest=3)
    tracer = Tracer(enabled=True, recorder=rec)
    # three slow outliers early...
    for ms in (300, 200, 100):
        s = tracer.start_trace(f"slow-{ms}")
        s.start = 0.0
        s.end(ms / 1000.0)
    # ...then a churn of fast traces
    for i in range(200):
        s = tracer.start_trace(f"fast-{i}")
        s.start = 0.0
        s.end(0.001)
    slow = rec.slowest()
    assert [t.name for t in slow] == ["slow-300", "slow-200", "slow-100"]
    recent = rec.recent()
    assert len(recent) == 4
    assert [t.name for t in recent] == [f"fast-{i}" for i in range(196, 200)]
    # the export union carries both sets, deduplicated
    union = rec.traces()
    assert len(union) == 7
    assert rec.recorded == 203


def test_span_at_joins_an_open_trace_or_completes_alone():
    """A pre-timed span joins a trace still open on this tracer (the
    trace records when its last span ends), and under a propagated
    header whose trace is not open here it records at once as a
    one-span trace, leaving the open table empty."""
    tracer = Tracer(enabled=True)
    root = tracer.start_trace("notarise.frame")
    stage = tracer.span_at("stage", root, 1.0, 2.5, batch=3)
    assert (stage.start, stage.end_time) == (1.0, 2.5)
    assert stage.parent_id == root.span_id
    assert stage.attributes == {"batch": 3}
    assert tracer.recorder.traces() == []
    root.end()
    (joined,) = tracer.recorder.traces()
    assert {s.name for s in joined.spans} == {"notarise.frame", "stage"}

    remote = tracer.span_at("raft.apply", (77 << 20, 5, 123), 4.0, 4.25)
    assert (remote.trace_id, remote.parent_id) == (77 << 20, 5)
    assert tracer._open == {}
    alone = [t for t in tracer.recorder.traces() if t.trace_id == 77 << 20]
    assert len(alone) == 1 and alone[0].spans == [remote]
    assert alone[0].duration_s == 0.25
    assert tracer.span_at("x", "garbage", 0.0, 1.0) is NOOP_SPAN


def test_chrome_export_roundtrips_json():
    tracer = Tracer(enabled=True)
    root = tracer.start_trace("notarise.frame", wire_bytes=123)
    child = tracer.start_span("ingest.decode", root, batch=8)
    child.add_event("cache_probe", hit=False)
    child.end()
    root.end()
    out = json.loads(json.dumps(tracer.export()))
    events = out["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"notarise.frame", "ingest.decode"}
    for e in complete:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["pid"] == 1
    instants = [e for e in events if e["ph"] == "i"]
    assert [e["name"] for e in instants] == ["cache_probe"]
    decode = next(e for e in complete if e["name"] == "ingest.decode")
    assert decode["args"]["batch"] == 8
    assert decode["args"]["parent_span_id"] == root.span_id
    assert out["stageSummary"]["ingest.decode"]["count"] == 1
    # bare helpers round-trip too (what other exporters build on)
    assert json.loads(json.dumps(chrome_trace(tracer.recorder.traces())))
    assert json.loads(json.dumps(stage_summary(tracer.recorder.traces())))


def test_disabled_tracer_is_span_free_and_cheap():
    """Tracing off: every factory returns the ONE noop singleton, the
    recorder stays empty, the ingest pipeline attaches no spans, and
    the per-call cost is a near-zero constant."""
    tracer = Tracer(enabled=False)
    assert tracer.start_trace("x") is NOOP_SPAN
    assert tracer.start_span("y", NOOP_SPAN) is NOOP_SPAN
    assert tracer.span_at("z", NOOP_SPAN, 0.0, 1.0) is NOOP_SPAN
    assert not NOOP_SPAN   # falsy: `if span:` gates downstream work

    _, _, _, spends = _cash_spends(2)
    pipe = IngestPipeline(tracer=tracer)
    entries = pipe.ingest([ser.encode(s) for s in spends])
    assert all(e.span is None for e in entries)
    assert all(e.error is None for e in entries)
    pipe.close()
    assert tracer.recorder.recorded == 0

    t0 = time.perf_counter()
    for _ in range(100_000):
        tracer.start_trace("hot")
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"100k disabled start_trace calls took {dt:.3f}s"


# ---------------------------------------------------------------------------
# the acceptance path: one connected trace, wire frame -> commit


def _drive_traced_notarisation(tracer, n: int = 1):
    """Drive `n` notarisations through MessagingService -> IngestRing ->
    IngestPipeline -> BatchingNotaryService flush; returns the client
    root spans (ended) so callers can interrogate the recorder."""
    net, notary, requester, spends = _cash_spends(n)
    svc = notary.services.notary_service
    imn = InMemoryMessagingNetwork()
    rx = imn.endpoint("notaryhost")
    tx = imn.endpoint("client")
    ring = IngestRing(depth=8)
    rx.add_ring("notary.requests", ring)

    client_spans = []
    for s in spends:
        span = tracer.start_trace("client.submit", tx_id=str(s.id))
        client_spans.append(span)
        tx.send(
            "notary.requests", ser.encode(s), "notaryhost",
            trace=tuple(span.context),
        )
    imn.run()
    msgs = ring.drain()
    assert len(msgs) == n

    pipe = IngestPipeline(tracer=tracer)
    entries = pipe.ingest(
        [m.payload for m in msgs],
        trace_parents=[m.trace for m in msgs],
        end_spans=False,   # the notary flush owns + ends the frame spans
    )
    futs = []
    for e in entries:
        assert e.error is None
        fut = FlowFuture()
        futs.append(fut)
        svc.enqueue_pending(
            _PendingNotarisation(e.stx, requester, fut, span=e.span)
        )
    svc.flush()
    for fut in futs:
        sig = fut.result()
        assert hasattr(sig, "by"), f"notarisation failed: {sig}"
    for span in client_spans:
        span.end()
    pipe.close()
    return client_spans


def test_single_notarisation_yields_one_connected_trace():
    """The PR's acceptance criterion: >= 6 stage spans, one trace_id,
    every parent link resolving inside the trace, retrievable from the
    flight recorder."""
    tracer = Tracer(enabled=True)
    (client_span,) = _drive_traced_notarisation(tracer, n=1)

    matching = [
        t for t in tracer.recorder.traces()
        if t.trace_id == client_span.trace_id
    ]
    assert len(matching) == 1, "one notarisation must be ONE trace"
    spans = matching[0].spans
    assert all(s.trace_id == client_span.trace_id for s in spans)
    ids = {s.span_id for s in spans}
    for s in spans:
        if s.parent_id is not None:
            assert s.parent_id in ids, f"dangling parent on {s.name}"
    names = [s.name for s in spans]
    stage_names = {
        n for n in names if n not in ("client.submit", "notarise.frame")
    }
    assert len(stage_names) >= 6, names
    # the load-bearing stages are all present and attributed
    for expected in (
        "ingest.decode", "ingest.merkle_id", "ingest.stage",
        "notary.stage", "notary.dispatch", "notary.commit",
        "notary.sign_scatter",
    ):
        assert expected in stage_names, names
    # spans nest under the frame root which nests under the client span
    frame = next(s for s in spans if s.name == "notarise.frame")
    assert frame.parent_id == client_span.span_id
    decode = next(s for s in spans if s.name == "ingest.decode")
    assert decode.parent_id == frame.span_id


def test_traces_endpoint_serves_chrome_json_and_stage_summary():
    """GET /traces next to /metrics: chrome://tracing-loadable JSON
    plus the per-stage latency summary, straight from the recorder."""
    from corda_tpu.client.webserver import NodeWebServer

    tracer = Tracer(enabled=True)
    (client_span,) = _drive_traced_notarisation(tracer, n=1)

    web = NodeWebServer(
        client=object(), pump=lambda: None, tracer=tracer
    ).start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{web.port}/traces", timeout=10
        ) as resp:
            assert resp.status == 200
            body = json.loads(resp.read())
    finally:
        web.stop()
    want = f"{client_span.trace_id:#x}"
    events = [
        e for e in body["traceEvents"]
        if e["ph"] == "X" and e["args"].get("trace_id") == want
    ]
    stage_events = [
        e for e in events
        if e["name"] not in ("client.submit", "notarise.frame")
    ]
    assert len(stage_events) >= 6, [e["name"] for e in events]
    assert body["stageSummary"]["notary.dispatch"]["count"] >= 1
    assert body["tracesRetained"] >= 1
    # a gateway without a tracer answers 404, not a stack trace
    bare = NodeWebServer(client=object(), pump=lambda: None).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"http://127.0.0.1:{bare.port}/traces", timeout=10
            )
        assert exc.value.code == 404
    finally:
        bare.stop()


def test_flow_driven_notarisation_traces_via_default_tracer():
    """CORDA_TPU_TRACE=1 on a real node must produce notary phase
    spans for FLOW-driven requests too (no wire ingest involved):
    process() opens the root span on the process-default tracer."""
    from corda_tpu.utils import tracing as trmod

    tracer = Tracer(enabled=True)
    trmod.set_tracer(tracer)
    try:
        net, notary, requester, spends = _cash_spends(1)
        svc = notary.services.notary_service

        def drive():
            result = yield from svc.process(spends[0], requester)
            return result

        gen = drive()
        wait_req = next(gen)   # suspends on the _WaitFuture request
        svc.flush()
        assert wait_req.future.done
        with pytest.raises(StopIteration) as stop:
            gen.send(wait_req.future.result())
        assert hasattr(stop.value.value, "by"), stop.value.value
    finally:
        trmod.set_tracer(None)
    traces = [
        t for t in tracer.recorder.traces() if t.name == "notarise.request"
    ]
    assert len(traces) == 1
    names = {s.name for s in traces[0].spans}
    assert "notary.dispatch" in names and "notary.commit" in names


def test_verifier_worker_ingest_joins_sender_trace():
    """The worker's ring drain must hand each frame's propagated trace
    header to the pipeline — the pool side of the connected trace."""
    from corda_tpu.node import messaging as msglib
    from corda_tpu.node.verifier import (
        OutOfProcessTransactionVerifierService,
        VerifierWorker,
        request_ingest_pipeline,
    )

    tracer = Tracer(enabled=True)
    net, _, _, spends = _cash_spends(1)
    alice = next(n for n in net.nodes if n.name == "Alice")
    ltx = spends[0].to_ledger_transaction(alice.services)
    imn = InMemoryMessagingNetwork()
    node_ep = imn.endpoint("nodeA")
    worker_ep = imn.endpoint("w1")
    svc = OutOfProcessTransactionVerifierService(node_ep)
    worker = VerifierWorker(
        worker_ep,
        "nodeA",
        batch_verifier=CpuBatchVerifier(),
        batch_window=10**9,
        ingest=request_ingest_pipeline(shards=1, tracer=tracer),
    )
    imn.run()                   # WorkerReady handshake
    client = tracer.start_trace("client.verify")
    # the service API doesn't thread trace headers yet; send the
    # request frame directly with one, as a fabric-level client would
    from corda_tpu.core import serialization as cser
    from corda_tpu.node.verifier import TxVerificationRequest

    req = TxVerificationRequest(1, ltx, "nodeA", spends[0])
    node_ep.send(
        msglib.TOPIC_VERIFIER_REQ, cser.encode(req), "w1",
        trace=tuple(client.context),
    )
    imn.run()
    assert worker.drain() == 1
    client.end()
    match = [
        t for t in tracer.recorder.traces()
        if t.trace_id == client.trace_id
    ]
    assert len(match) == 1
    names = {s.name for s in match[0].spans}
    assert {"client.verify", "notarise.frame", "ingest.decode"} <= names


def test_async_commit_defers_root_span_end_until_answered():
    """A distributed (non-batch_synchronous) provider resolves commits
    on consensus AFTER the flush returns: the frame's root span must
    stay open until the future is answered, so the consensus latency
    is inside the trace."""
    from corda_tpu.node.notary import UniquenessProvider

    class ManualAsyncProvider(UniquenessProvider):
        batch_synchronous = False

        def __init__(self):
            self.futs = []

        def commit_async(self, states, tx_id, requester, trace=None):
            # trace= is the SPI contract (UniquenessProvider): the
            # flush threads the frame's root span through it so
            # distributed providers can stamp consensus/xshard spans
            fut = FlowFuture()
            self.futs.append(fut)
            return fut

    tracer = Tracer(enabled=True)
    net, notary, requester, spends = _cash_spends(1)
    svc = notary.services.notary_service
    provider = ManualAsyncProvider()
    svc.uniqueness = provider
    root = tracer.start_trace("notarise.frame", tx_id=str(spends[0].id))
    fut = FlowFuture()
    svc.enqueue_pending(
        _PendingNotarisation(spends[0], requester, fut, span=root)
    )
    svc.flush()
    assert not root.ended, "span must stay open until consensus answers"
    assert not fut.done
    provider.futs[0].set_result(None)   # consensus resolves
    assert fut.done and hasattr(fut.result(), "by")
    assert root.ended
    assert len(tracer.recorder.traces()) == 1


def test_traces_endpoint_survives_unserializable_attribute():
    """A non-JSON span attribute must yield the handler's defensive
    500 JSON error, not a dropped response."""
    from corda_tpu.client.webserver import NodeWebServer

    tracer = Tracer(enabled=True)
    s = tracer.start_trace("bad", blob=b"\x00raw-bytes")
    s.end()
    web = NodeWebServer(
        client=object(), pump=lambda: None, tracer=tracer
    ).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"http://127.0.0.1:{web.port}/traces", timeout=10
            )
        assert exc.value.code == 500
        assert "trace export failed" in json.loads(exc.value.read())["error"]
    finally:
        web.stop()


# ---------------------------------------------------------------------------
# registry-backed observability satellites


def test_notary_batching_counters_and_ratio_are_scrapeable():
    net, notary, requester, spends = _cash_spends(3)
    svc = notary.services.notary_service
    reg = svc.metrics
    assert svc.batches_dispatched == 0
    futs = []
    for s in spends:
        fut = FlowFuture()
        futs.append(fut)
        svc.enqueue_pending(_PendingNotarisation(s, requester, fut))
    svc.flush()
    for fut in futs:
        assert hasattr(fut.result(), "by")
    assert svc.batches_dispatched == 1       # back-compat view...
    assert svc.requests_batched == 3
    text = reg.to_prometheus()               # ...over scrapeable metrics
    assert "Notary_BatchesDispatched 1" in text
    assert "Notary_RequestsBatched 3" in text
    assert "Notary_BatchingRatio 3.0" in text
    # the always-on flush-phase timers carry the stage breakdown
    assert "Notary_FlushPhase_dispatch_total 1" in text
    assert "Notary_FlushPhase_commit_seconds_sum" in text


def test_ring_depth_highwater_and_parked_gauges():
    imn = InMemoryMessagingNetwork()
    rx = imn.endpoint("rx")
    tx = imn.endpoint("tx")
    ring = IngestRing(depth=2)
    reg = MetricRegistry()
    rx.add_ring("ingest.topic", ring, metrics=reg)
    for i in range(5):
        tx.send("ingest.topic", b"frame-%d" % i, "rx")
    imn.run()
    # 2 in the ring (high water 2), 3 parked for retry
    text = reg.to_prometheus()
    assert "Ingest_ingest_topic_RingDepth 2" in text
    assert "Ingest_ingest_topic_RingHighWater 2" in text
    assert "Ingest_ingest_topic_Parked 3" in text
    ring.drain()
    assert rx.retry_parked("ingest.topic") == 2
    text = reg.to_prometheus()
    assert "Ingest_ingest_topic_RingDepth 2" in text
    assert "Ingest_ingest_topic_Parked 1" in text
    ring.drain()
    rx.retry_parked("ingest.topic")
    text = reg.to_prometheus()
    assert "Ingest_ingest_topic_RingDepth 1" in text
    assert "Ingest_ingest_topic_Parked 0" in text
    # the high-water mark REMEMBERS the worst depth
    assert "Ingest_ingest_topic_RingHighWater 2" in text


def test_notary_ingest_ring_gauges_via_attach():
    net, notary, requester, spends = _cash_spends(1)
    svc = notary.services.notary_service
    pipe = IngestPipeline()
    svc.attach_ingest(pipe.ring)
    assert pipe.ring.put(
        [_PendingNotarisation(spends[0], requester, FlowFuture())], timeout=1
    )
    text = svc.metrics.to_prometheus()
    assert "Ingest_notary_RingDepth 1" in text
    assert "Ingest_notary_RingHighWater 1" in text
    pipe.close()


# ---------------------------------------------------------------------------
# CI smoke: the traced-bench plumbing


def test_bench_quick_trace_emits_breakdown_and_bounds_overhead():
    """`bench.py --quick trace` must run under JAX_PLATFORMS=cpu, emit
    the decode/merkle/stage/dispatch/kernel/commit breakdown, assert
    the stages sum to ~the traced wall, and bound tracing overhead —
    the tier-1 guard on the stage-attributed perf record."""
    bench = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    out = subprocess.run(
        [sys.executable, os.path.abspath(bench), "--quick", "trace"],
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "BENCH_BATCH": "48",
            "BENCH_TRACE_REPS": "2",
            # the gate's DEFAULT is 5% (the bench-run contract); under
            # a fully loaded tier-1 box the A/B minima carry ~±10%
            # scheduler noise, so the smoke widens the ceiling — the
            # gate-fires path is pinned deterministically below
            "BENCH_TRACE_OVERHEAD_MAX": "0.5",
        },
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, out.stdout
    rec = json.loads(lines[0])
    assert rec["metric"] == "hot_path_stage_breakdown"
    assert rec["quick"] is True
    stages = rec["stages_seconds"]
    assert set(stages) == {
        "decode", "merkle", "stage", "dispatch", "kernel", "commit"
    }
    # the breakdown sums to ~the traced wall (the quick mode itself
    # enforces the band and exits non-zero outside it)
    assert 0.6 <= rec["value"] <= 1.4
    assert stages["decode"] > 0 and stages["dispatch"] > 0
    assert rec["wall_seconds"] > 0 and rec["untraced_wall_seconds"] > 0
    assert rec["tracing_overhead"] < 0.5


def test_bench_quick_trace_overhead_gate_fires():
    """The overhead gate must actually FAIL the run when tripped: an
    impossible threshold (any measured overhead exceeds -1) forces the
    non-zero exit deterministically, independent of box load."""
    bench = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    out = subprocess.run(
        [sys.executable, os.path.abspath(bench), "--quick", "trace"],
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "BENCH_BATCH": "16",
            "BENCH_TRACE_REPS": "2",
            "BENCH_TRACE_OVERHEAD_MAX": "-1",
        },
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert out.returncode != 0
    assert "tracing overhead" in out.stderr
