"""QoS plane: deadline shedding, lanes, admission, adaptive batching.

The acceptance arc (ISSUE 4): under ~2x sustained offered load on the
CPU fixture the notary sheds-not-crashes, holds the admitted
(interactive) p99 at or under the configured target, commits nothing
that was already expired, keeps goodput >= 90% of the no-overload
capacity, counts every shed in Qos.Shed.* and serves the control-plane
state at GET /qos — with accept/reject semantics for every admitted
transaction bit-exact vs the serial reference path (the CrossCashTest
reconciliation discipline, applied to overload).

Time is the node TestClock throughout, so queue ages, deadlines and
latency percentiles are DETERMINISTIC — no wall-clock flakes.
"""

import json
import urllib.request

import pytest

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
from corda_tpu.node import qos as qoslib
from corda_tpu.node.messaging import InMemoryMessagingNetwork, Message
from corda_tpu.node.notary import (
    InMemoryUniquenessProvider,
    NotaryError,
    UniquenessConflict,
    _PendingNotarisation,
)
from corda_tpu.testing.mock_network import MockNetwork


# ---------------------------------------------------------------------------
# fixture: a batching notary + signed cash spends on the CPU verifier


def _rig(n_spends: int, qos: qoslib.NotaryQos = None, seed: int = 21):
    """(net, svc, alice.party, spends): `n_spends` distinct signed
    single-input cash spends whose issue backchain is recorded at a
    CPU-verifier batching notary."""
    net = MockNetwork(seed=seed, batch_verifier=CpuBatchVerifier())
    notary = net.create_notary("Notary", batching=True)
    bank = net.create_node("Bank")
    alice = net.create_node("Alice")
    svc = notary.services.notary_service
    if qos is not None:
        svc.qos = qos
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
    return net, svc, alice.party, spends


def _conflicting_spend(net, svc, requester, spend):
    """A DIFFERENT transaction consuming `spend`'s input — the serial
    reference must answer conflict for whichever commits second."""
    wtx = spend.wtx
    sb = TransactionBuilder(wtx.notary)
    # same input ref, different output amount -> different tx id
    for ref in wtx.inputs:
        sb.add_input_state(
            [n for n in net.nodes if n.party == requester][0]
            .vault.state_and_ref(ref)
        )
    out = wtx.outputs[0]
    sb.add_output_state(
        CashState(
            Amount(out.data.amount.quantity - 1, out.data.amount.token),
            out.data.owner,
        ),
        CASH_CONTRACT, wtx.notary,
    )
    sb.add_command(CashMove(), requester.owning_key)
    node = [n for n in net.nodes if n.party == requester][0]
    return node.services.sign_initial_transaction(sb)


# ---------------------------------------------------------------------------
# unit: headers, gate, lanes, controller, brownout


def test_deadline_header_rides_in_memory_fabric():
    net = InMemoryMessagingNetwork()
    a, b = net.endpoint("A"), net.endpoint("B")
    got = []
    b.add_handler("t", got.append)
    a.send("t", b"x", "B", deadline=987_654)
    a.send("t", b"y", "B")
    net.run()
    assert [(m.payload, m.deadline) for m in got] == [
        (b"x", 987_654), (b"y", None),
    ]


def test_token_bucket_admits_burst_then_refills():
    bucket = qoslib.TokenBucket(rate_per_sec=10.0, burst=3)
    t0 = 1_000_000
    assert [bucket.admit("c", t0) for _ in range(4)] == [
        True, True, True, False,
    ]
    # 10 tokens/sec -> one token back after 100 ms; another client is
    # an independent bucket
    assert bucket.admit("c", t0 + 100_000)
    assert not bucket.admit("c", t0 + 100_000)
    assert bucket.admit("other", t0)
    # rate 0 disables the gate entirely
    assert all(
        qoslib.TokenBucket(0, 1).admit("c", t0) for _ in range(100)
    )


def test_lane_router_weighted_fair_never_starves_interactive():
    """A bulk (resolution) flood ahead of interactive arrivals: the
    weighted-fair drain interleaves 4:1, so interactive frames come out
    ahead of most of the flood instead of queuing behind ALL of it."""
    qos = qoslib.NotaryQos(
        qoslib.QosPolicy(interactive_weight=4, bulk_weight=1)
    )
    for i in range(40):   # the flood arrives FIRST
        assert qos.lanes.offer(
            Message("tx.resolution", b"", "bulk-peer", i)
        )
    for i in range(8):
        assert qos.lanes.offer(
            Message("platform.notarise", b"", "alice", 100 + i)
        )
    order = [m.topic for m in qos.lanes.drain()]
    assert len(order) == 48
    # every interactive frame is out within the first 2.5 fair rounds,
    # despite 40 bulk frames queued ahead of them
    last_interactive = max(
        i for i, t in enumerate(order) if t == "platform.notarise"
    )
    assert last_interactive < 12, order[:16]
    # within each lane, FIFO order held
    assert [
        m for m in order if m == "tx.resolution"
    ] == ["tx.resolution"] * 40


def test_lane_router_sheds_expired_and_gated_frames_pre_decode():
    qos = qoslib.NotaryQos(
        qoslib.QosPolicy(admission_rate_per_sec=1, admission_burst=1)
    )
    now = qos.now_micros()
    # expired at offer: consumed (True — must NOT park for redelivery)
    assert qos.lanes.offer(Message("t", b"", "a", 1, None, now - 1))
    # admission: burst 1 -> second frame from the same client sheds
    assert qos.lanes.offer(Message("t", b"", "a", 2))
    assert qos.lanes.offer(Message("t", b"", "a", 3))
    assert qos.lanes.drain() != []
    shed = qos.snapshot()["shed"]
    assert shed[qoslib.SHED_EXPIRED_INGRESS] == 1
    assert shed[qoslib.SHED_ADMISSION] == 1


def test_ingest_pipeline_sheds_expired_before_decode():
    """Pre-decode means PRE-decode: the decoder must never see an
    expired frame's bytes."""
    from corda_tpu.node.ingest import IngestPipeline

    decoded = []

    def counting_decode(blob):
        decoded.append(blob)
        raise ValueError("not a real frame")   # per-slot isolation

    pipe = IngestPipeline(decode=counting_decode, frame_cache_size=0)
    blobs = [b"dead", b"live-a", b"live-b"]
    entries = pipe.ingest(
        blobs, deadlines=[100, None, 10**18], now_micros=200
    )
    assert isinstance(entries[0].error, qoslib.DeadlineExpired)
    assert entries[0].deadline == 100
    assert b"dead" not in decoded and len(decoded) == 2
    assert entries[2].deadline == 10**18
    pipe.close()


def test_adaptive_controller_aimd():
    from corda_tpu.utils.metrics import Histogram

    pol = qoslib.QosPolicy(
        target_p99_micros=10_000, min_wait_micros=0,
        max_wait_micros=16_000, min_batch=4, max_batch=64,
        wait_step_micros=1_000,
    )
    hist = Histogram()
    ctrl = qoslib.AdaptiveBatchController(pol, hist)
    w0, b0 = ctrl.wait_micros, ctrl.batch
    # latency breach: multiplicative collapse of window AND depth
    for _ in range(64):
        hist.update(50_000)
    ctrl.observe_flush(batch_size=64, backlog=10)
    assert ctrl.wait_micros == w0 // 2 and ctrl.batch == b0 // 2
    for _ in range(20):
        ctrl.observe_flush(batch_size=8, backlog=10)
    assert ctrl.wait_micros == pol.min_wait_micros
    assert ctrl.batch == pol.min_batch
    # healthy latency + full batches: additive window growth back up,
    # depth re-opens, both clamped at the policy ceiling
    hist2 = Histogram()
    ctrl2 = qoslib.AdaptiveBatchController(pol, hist2)
    hist2.update(1_000)
    for _ in range(40):
        ctrl2.observe_flush(batch_size=ctrl2.batch, backlog=0)
    assert ctrl2.wait_micros == pol.max_wait_micros
    assert ctrl2.batch == pol.max_batch


def test_brownout_walks_levels_on_backlog_trend():
    qos = qoslib.NotaryQos(qoslib.QosPolicy(brownout_after_flushes=3))
    assert qos.brownout_level == 0
    for _ in range(3):
        qos.observe_flush(batch_size=8, backlog=100)
    assert qos.brownout_level == 1
    # level 1: bulk lane shed at admission
    assert qos.lanes.offer(Message("tx.resolution", b"", "p", 1))
    assert qos.snapshot()["shed"][qoslib.SHED_BROWNOUT_BULK] == 1
    for _ in range(3):
        qos.observe_flush(batch_size=8, backlog=100)
    assert qos.brownout_level == 2
    # level 2: deadline-less interactive sheds too; deadline-carrying
    # interactive still admitted
    assert qos.lanes.offer(Message("platform.notarise", b"", "p", 2))
    assert (
        qos.snapshot()["shed"][qoslib.SHED_BROWNOUT_NO_DEADLINE] == 1
    )
    now = qos.now_micros()
    assert qos.lanes.offer(
        Message("platform.notarise", b"", "p", 3, None, now + 10**9)
    )
    assert len(qos.lanes.lanes[qoslib.LANE_INTERACTIVE]) == 1
    # recovery: shrinking backlog steps the level back down
    for _ in range(6):
        qos.observe_flush(batch_size=8, backlog=0)
    assert qos.brownout_level == 0


# ---------------------------------------------------------------------------
# the notary flush under QoS


def test_flush_sheds_expired_pre_stage_with_typed_error():
    qos = qoslib.NotaryQos(
        qoslib.QosPolicy(min_batch=2, max_batch=64), clock=None
    )
    net, svc, requester, spends = _rig(3, qos=qos)
    qos._clock = net.clock
    now = net.clock.now_micros()
    futs = [FlowFuture() for _ in spends]
    deadlines = [now - 1, now + 10**9, None]
    for stx, fut, dl in zip(spends, futs, deadlines):
        svc.enqueue_pending(
            _PendingNotarisation(
                stx, requester, fut, deadline=dl, arrival_micros=now
            )
        )
    svc.flush()
    assert all(f.done for f in futs)
    shed = futs[0].result()
    assert isinstance(shed, NotaryError) and shed.kind == qoslib.SHED_KIND
    assert hasattr(futs[1].result(), "by")
    assert hasattr(futs[2].result(), "by")
    assert qos.snapshot()["shed"][qoslib.SHED_EXPIRED_FLUSH] == 1
    # the shed tx's input was NEVER committed — no burned verify/commit
    assert all(
        ref not in svc.uniqueness.committed
        for ref in spends[0].wtx.inputs
    )


def test_shed_becomes_span_event_on_traced_frames():
    """ISSUE: shed events become span events — a traced frame that is
    shed carries qos.shed on its root span."""
    from corda_tpu.utils.tracing import Tracer

    tracer = Tracer(enabled=True)
    qos = qoslib.NotaryQos(qoslib.QosPolicy(min_batch=2, max_batch=64))
    net, svc, requester, spends = _rig(1, qos=qos)
    qos._clock = net.clock
    now = net.clock.now_micros()
    span = tracer.start_trace("notarise.frame")
    fut = FlowFuture()
    svc.enqueue_pending(
        _PendingNotarisation(
            spends[0], requester, fut,
            span=span, deadline=now - 1, arrival_micros=now,
        )
    )
    svc.flush()
    assert fut.result().kind == qoslib.SHED_KIND
    assert span.ended
    assert span.attributes.get("shed") == qoslib.SHED_EXPIRED_FLUSH
    assert any(name == "qos.shed" for _, name, _ in span.events)


def test_process_rejects_dead_on_arrival_without_queuing():
    qos = qoslib.NotaryQos(qoslib.QosPolicy())
    net, svc, requester, spends = _rig(1, qos=qos)
    qos._clock = net.clock
    gen = svc.process(
        spends[0], requester, deadline=net.clock.now_micros() - 1
    )
    # a shed at entry returns the error without ever yielding
    try:
        next(gen)
        resolved = None
    except StopIteration as stop:
        resolved = stop.value
    assert resolved is not None and resolved.kind == qoslib.SHED_KIND
    assert svc.backlog() == 0
    assert qos.snapshot()["shed"][qoslib.SHED_EXPIRED_INGRESS] == 1


def test_notary_flow_carries_deadline_end_to_end():
    """The PRODUCTION deadline source: NotaryFlow(deadline_micros=)
    ships a NotarisationRequest envelope; the service flow sheds an
    expired request before any service work (typed `shed` back to the
    requester), and a live deadline notarises normally."""
    from corda_tpu.flows.core_flows import NotaryFlow
    from corda_tpu.node.notary import NotaryException

    qos = qoslib.NotaryQos(qoslib.QosPolicy())
    net, svc, _, spends = _rig(2, qos=qos, seed=44)
    qos._clock = net.clock
    alice = next(n for n in net.nodes if n.name == "Alice")

    live = alice.start_flow(
        NotaryFlow(spends[0], deadline_micros=net.clock.now_micros() + 10**9)
    )
    net.run()
    # the adaptive controller opens with a non-zero batching window:
    # age the queue past it (simulated time) so the held flush fires
    net.clock.advance(qos.controller.wait_micros + 1)
    net.run()
    sigs = live.result_or_throw()
    assert sigs and all(hasattr(s, "by") for s in sigs)

    dead = alice.start_flow(
        NotaryFlow(spends[1], deadline_micros=net.clock.now_micros() - 1)
    )
    net.run()
    with pytest.raises(NotaryException) as exc:
        dead.result_or_throw()
    assert exc.value.error.kind == qoslib.SHED_KIND
    assert qos.snapshot()["shed"][qoslib.SHED_EXPIRED_INGRESS] == 1
    # the shed spend was never committed
    assert all(
        ref not in svc.uniqueness.committed
        for ref in spends[1].wtx.inputs
    )


def test_process_admission_gate_rate_shapes_flooding_client():
    """qos_admission_rate_per_sec engages on the real request path:
    one flooding requester is shed at process() entry once its token
    bucket drains — before any queue slot or verify work."""
    qos = qoslib.NotaryQos(
        qoslib.QosPolicy(admission_rate_per_sec=1, admission_burst=2)
    )
    net, svc, requester, spends = _rig(3, qos=qos, seed=55)
    qos._clock = net.clock

    outcomes = []
    for stx in spends:
        gen = svc.process(stx, requester)
        try:
            step = next(gen)
            outcomes.append(("queued", gen, step))
        except StopIteration as stop:
            outcomes.append(("answered", stop.value, None))
    kinds = [o[0] for o in outcomes]
    assert kinds == ["queued", "queued", "answered"]   # burst 2, then shed
    shed = outcomes[2][1]
    assert shed.kind == qoslib.SHED_KIND and requester.name in shed.message
    assert qos.snapshot()["shed"][qoslib.SHED_ADMISSION] == 1
    assert svc.backlog() == 2


def test_verifier_worker_sheds_expired_request_pre_decode():
    """The deadline header crosses the fabric into the verifier pool:
    an expired request is dropped at the worker's ingest seam (metered
    Verifier.Shed, never decoded into verify work); live requests in
    the same round are unaffected."""
    from corda_tpu.core import serialization as ser
    from corda_tpu.node import messaging as msglib
    from corda_tpu.node.verifier import (
        OutOfProcessTransactionVerifierService,
        TxVerificationRequest,
        VerifierWorker,
        request_ingest_pipeline,
    )

    net, _, _, spends = _rig(2, seed=33)
    alice = next(n for n in net.nodes if n.name == "Alice")
    ltxs = [s.to_ledger_transaction(alice.services) for s in spends]
    imn = InMemoryMessagingNetwork()
    node_ep, worker_ep = imn.endpoint("nodeA"), imn.endpoint("w1")
    oop = OutOfProcessTransactionVerifierService(node_ep)
    worker = VerifierWorker(
        worker_ep, "nodeA",
        batch_verifier=CpuBatchVerifier(),
        batch_window=10**9,          # drain only when we say so
        ingest=request_ingest_pipeline(shards=1),
        clock=net.clock,             # expiry judged on the clock that
        #                              MINTS the deadlines (TestClock)
    )
    imn.run()                        # WorkerReady handshake
    fut_live = oop.verify(ltxs[0], spends[0])
    # a live TestClock deadline must NOT shed (wall clock is years
    # past every TestClock value — the injected clock is load-bearing;
    # the unknown-nonce reply is dropped node-side, which is fine: the
    # assertion is that the WORKER processed it)
    node_ep.send(
        msglib.TOPIC_VERIFIER_REQ,
        ser.encode(TxVerificationRequest(998, ltxs[1], "nodeA", spends[1])),
        "w1",
        deadline=net.clock.now_micros() + 10**9,
    )
    # the expired one: same envelope, deadline long past on ANY clock
    node_ep.send(
        msglib.TOPIC_VERIFIER_REQ,
        ser.encode(TxVerificationRequest(999, ltxs[1], "nodeA", spends[1])),
        "w1",
        deadline=1,
    )
    imn.run()                        # all land in the worker's ring
    assert worker.drain() == 2       # live + live-deadline processed
    assert worker.metrics.get("Verifier.Shed").count == 1
    assert worker.metrics.get("Verifier.Failed").count == 0
    imn.run()                        # response pumps back
    assert fut_live.done
    fut_live.result()


# ---------------------------------------------------------------------------
# node config + wiring


def test_config_qos_knobs_validate_and_roundtrip(tmp_path):
    from corda_tpu.node.config import (
        ConfigError,
        NodeConfig,
        config_from_dict,
        write_config,
    )

    cfg = NodeConfig(
        name="N", base_dir=str(tmp_path), notary="batching",
        qos_enabled=True, qos_target_p99_micros=75_000,
        qos_admission_rate_per_sec=100, qos_admission_burst=32,
    )
    path = str(tmp_path / "node.toml")
    write_config(cfg, path)
    text = open(path).read()
    for line in (
        "qos_enabled = true", "qos_target_p99_micros = 75000",
        "qos_admission_rate_per_sec = 100", "qos_admission_burst = 32",
    ):
        assert line in text, text
    # the dict binding (what TOML loading feeds) accepts the knobs
    cfg2 = config_from_dict(
        {"node": {
            "name": "N", "base_dir": str(tmp_path), "notary": "batching",
            "qos_enabled": True, "qos_target_p99_micros": 75_000,
            "qos_admission_rate_per_sec": 100, "qos_admission_burst": 32,
        }}
    )
    assert cfg2.qos_enabled
    assert cfg2.qos_target_p99_micros == 75_000
    assert cfg2.qos_admission_rate_per_sec == 100
    assert cfg2.qos_admission_burst == 32
    # the QoS plane steers the batching flush: other notaries reject it
    with pytest.raises(ConfigError):
        NodeConfig(
            name="N", base_dir=str(tmp_path), notary="simple",
            qos_enabled=True,
        )
    with pytest.raises(ConfigError):
        NodeConfig(
            name="N", base_dir=str(tmp_path), notary="batching",
            qos_enabled=True, qos_target_p99_micros=0,
        )


def test_node_boots_qos_plane_and_serves_get_qos(tmp_path):
    """qos_enabled in the TOML wires the whole plane: the batching
    notary holds a NotaryQos, Qos.* gauges land on the node registry,
    and the embedded web gateway serves GET /qos."""
    from corda_tpu.node.config import NodeConfig, RpcUserConfig
    from corda_tpu.node.node import Node

    node = Node(
        NodeConfig(
            name="QosNode", base_dir=str(tmp_path / "n"),
            notary="batching", qos_enabled=True,
            qos_target_p99_micros=80_000,
            use_tls=False, verifier_backend="cpu", web_port=0,
            rpc_users=(RpcUserConfig("ops", "pw", ("ALL",)),),
        )
    ).start()
    try:
        svc = node.services.notary_service
        assert svc.qos is node.qos and node.qos is not None
        assert node.qos.policy.target_p99_micros == 80_000
        assert "Qos.BrownoutLevel" in node.metrics.names()
        with urllib.request.urlopen(
            f"http://127.0.0.1:{node.web.port}/qos", timeout=10
        ) as resp:
            body = json.loads(resp.read())
        assert body["enabled"] is True
        assert body["controller"]["target_p99_micros"] == 80_000
    finally:
        node.stop()


# ---------------------------------------------------------------------------
# GET /qos


def test_qos_endpoint_serves_control_plane_state():
    from corda_tpu.client.webserver import NodeWebServer

    qos = qoslib.NotaryQos(qoslib.QosPolicy(target_p99_micros=42_000))
    qos.count_shed(qoslib.SHED_EXPIRED_FLUSH)
    qos.record_admitted(1_234)
    web = NodeWebServer(client=object(), pump=lambda: None, qos=qos).start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{web.port}/qos", timeout=10
        ) as resp:
            assert resp.status == 200
            body = json.loads(resp.read())
    finally:
        web.stop()
    assert body["enabled"] is True
    assert body["controller"]["target_p99_micros"] == 42_000
    assert body["shed"][qoslib.SHED_EXPIRED_FLUSH] == 1
    assert body["answered"] == 1
    assert set(body["lanes"]) == {"interactive", "bulk"}
    # a gateway without qos answers 404, not a stack trace
    bare = NodeWebServer(client=object(), pump=lambda: None).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"http://127.0.0.1:{bare.port}/qos", timeout=10
            )
        assert exc.value.code == 404
    finally:
        bare.stop()


def test_bench_quick_qos_emits_wellformed_overload_record():
    """`bench.py --quick qos` must run under JAX_PLATFORMS=cpu, shed
    under 2x offered load, count the sheds, and emit one well-formed
    qos_overload_serving record — the tier-1 guard on the QoS bench
    plumbing (wired next to --quick ingest / --quick trace)."""
    import os
    import subprocess
    import sys

    bench = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    out = subprocess.run(
        [sys.executable, os.path.abspath(bench), "--quick", "qos"],
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "BENCH_BATCH": "8",
            "BENCH_ITERS": "1",
        },
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, out.stdout
    rec = json.loads(lines[0])
    assert rec["metric"] == "qos_overload_serving"
    assert rec["quick"] is True
    assert rec["controller_on"]["shed_fraction"] > 0
    assert rec["shed_counters"].get(qoslib.SHED_EXPIRED_FLUSH, 0) > 0
    assert rec["capacity_per_sec"] > 0
    assert rec["value"] >= 0.5
    for side in ("controller_on", "controller_off"):
        assert set(rec[side]) >= {"goodput_per_sec", "p99_ms",
                                  "shed_fraction", "answered"}


# ---------------------------------------------------------------------------
# the acceptance soak: ~2x capacity, simulated time, CPU fixture


def test_overload_soak_sheds_holds_p99_and_reconciles():
    """12 rounds of 2x offered load against a capacity-capped batching
    notary on the CPU verifier, in SIMULATED time (TestClock):

      - shed-not-crash: every future resolves, each with a signature,
        a conflict, or a typed shed — nothing strands, nothing raises
      - admitted (interactive) p99 <= the configured target
      - zero admitted-then-expired commits: every signed answer landed
        at or before its deadline
      - goodput >= 90% of the no-overload capacity over the offer
        window
      - accept/reject for every ADMITTED transaction is bit-exact vs
        the serial reference path replayed in answer order (CrossCash
        reconciliation: value neither lost nor duplicated)
      - sheds counted in Qos.Shed.* and visible at GET /qos
    """
    ROUND_MICROS = 10_000
    CAP = 8                    # controller ceiling == capacity/flush
    ROUNDS = 12
    OFFER = 2 * CAP            # 2x sustained
    TARGET = 30_000            # p99 SLO, micros (3 rounds)
    DEADLINE = 25_000          # per-request budget (2.5 rounds)

    qos = qoslib.NotaryQos(
        qoslib.QosPolicy(
            target_p99_micros=TARGET, min_batch=CAP, max_batch=CAP,
            max_wait_micros=0,
        )
    )
    n = ROUNDS * OFFER
    net, svc, requester, spends = _rig(n, qos=qos)
    qos._clock = net.clock
    # two double-spend attempts ride along: DIFFERENT transactions
    # claiming inputs of spends[0]/spends[1] — the reference path must
    # call conflict on whichever lands second, and so must we
    rivals = [
        _conflicting_spend(net, svc, requester, spends[i]) for i in (0, 1)
    ]

    answers = []               # (tag, stx, outcome) in ANSWER order
    meta = {}                  # id(fut) -> (tag, stx, deadline, arrival)

    def submit(tag, stx, deadline):
        fut = FlowFuture()
        arrival = net.clock.now_micros()
        meta[id(fut)] = (tag, stx, deadline, arrival)
        fut.add_done_callback(
            lambda f: answers.append(
                (meta[id(f)], f.result(), net.clock.now_micros())
            )
        )
        svc.enqueue_pending(
            _PendingNotarisation(
                stx, requester, fut,
                deadline=deadline, arrival_micros=arrival,
            )
        )
        return fut

    futs = []
    it = iter(spends)
    for rnd in range(ROUNDS):
        now = net.clock.now_micros()
        for _ in range(OFFER):
            futs.append(submit("interactive", next(it), now + DEADLINE))
        if rnd == 2:
            for rival in rivals:
                futs.append(submit("rival", rival, now + DEADLINE))
        svc.tick()
        net.clock.advance(ROUND_MICROS)
    for _ in range(8):         # drain: backlog either serves or expires
        svc.tick()
        net.clock.advance(ROUND_MICROS)

    # -- shed-not-crash ----------------------------------------------------
    assert all(f.done for f in futs)
    signed = [a for a in answers if hasattr(a[1], "by")]
    sheds = [
        a for a in answers
        if isinstance(a[1], NotaryError) and a[1].kind == qoslib.SHED_KIND
    ]
    conflicts = [
        a for a in answers
        if isinstance(a[1], NotaryError) and a[1].kind == "conflict"
    ]
    assert len(signed) + len(sheds) + len(conflicts) == len(futs)
    assert sheds, "2x overload must shed"
    assert qos.shed_total >= len(sheds)
    snapshot = qos.snapshot()
    assert snapshot["shed"].get(qoslib.SHED_EXPIRED_FLUSH, 0) >= len(sheds)

    # -- goodput >= 90% of no-overload capacity ----------------------------
    capacity = CAP * ROUNDS
    assert len(signed) >= 0.9 * capacity, (len(signed), capacity)

    # -- admitted p99 at or under target, zero admitted-then-expired -------
    latencies = sorted(
        done_at - arrival
        for (tag, stx, dl, arrival), outcome, done_at in answers
        if hasattr(outcome, "by")
    )
    p99 = latencies[min(len(latencies) - 1, int(0.99 * len(latencies)))]
    assert p99 <= TARGET, (p99, TARGET)
    for (tag, stx, dl, arrival), outcome, done_at in answers:
        if hasattr(outcome, "by"):
            assert done_at <= dl, f"admitted-then-expired commit of {stx.id}"
    # the controller's own histogram agrees (the /qos readout)
    assert qos.admitted_latency.quantile(0.99) <= TARGET

    # -- bit-exact accept/reject vs the serial reference path --------------
    reference = InMemoryUniquenessProvider()
    for (tag, stx, dl, arrival), outcome, done_at in answers:
        if isinstance(outcome, NotaryError) and outcome.kind == (
            qoslib.SHED_KIND
        ):
            continue           # shed before any consensus decision
        try:
            reference.commit(list(stx.wtx.inputs), stx.id, requester)
            serial_ok = True
        except UniquenessConflict:
            serial_ok = False
        assert serial_ok == hasattr(outcome, "by"), (
            f"QoS path and serial reference disagree on {stx.id}"
        )
    # ledger reconciliation: the committed map IS the signed set
    committed_ids = set(svc.uniqueness.committed.values())
    assert committed_ids == {
        stx.id for (tag, stx, dl, arrival), outcome, _ in answers
        if hasattr(outcome, "by")
    }
    # every committed input consumed exactly once (no lost/dup value)
    assert len(svc.uniqueness.committed) == len(signed)

    # -- visible at GET /qos -----------------------------------------------
    from corda_tpu.client.webserver import NodeWebServer

    web = NodeWebServer(client=object(), pump=lambda: None, qos=qos).start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{web.port}/qos", timeout=10
        ) as resp:
            body = json.loads(resp.read())
    finally:
        web.stop()
    assert body["shed_total"] == qos.shed_total
    assert body["controller"]["admitted_p99_micros"] <= TARGET
