"""Health plane: watchdogs, burn-rate alerts, canary, cluster rollup.

The acceptance arc (ISSUE 5): killing or wedging any registered
hot-path loop (flush tick, ingest pool, verifier drain) flips
GET /healthz to 503 and raises a firing alert with trace-id evidence
within one watchdog deadline in SIMULATED time, then auto-resolves on
recovery — alongside burn-rate alerting with hysteresis (no flapping),
the canary riding the real flush without touching the uniqueness
namespace, its deadman alert, and a two-node GET /cluster rollup where
an unreachable peer is marked stale, never fatal.

Time is the TestClock throughout the watchdog/alert tests; the only
real threads are the ones being wedged on purpose.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from corda_tpu.client.webserver import NodeWebServer
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
from corda_tpu.node.notary import _PendingNotarisation
from corda_tpu.node.services import TestClock
from corda_tpu.testing.mock_network import MockNetwork
from corda_tpu.utils import health as hlib
from corda_tpu.utils.metrics import MetricRegistry
from corda_tpu.utils.tracing import Tracer


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get_status(url, timeout=10):
    try:
        return _get(url, timeout)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _rig(n_spends: int, seed: int = 73):
    """(net, svc, requester, spends): a CPU-verifier batching notary
    plus signed single-input cash spends (the test_qos fixture shape)."""
    net = MockNetwork(seed=seed, batch_verifier=CpuBatchVerifier())
    notary = net.create_notary("Notary", batching=True)
    bank = net.create_node("Bank")
    alice = net.create_node("Alice")
    svc = notary.services.notary_service
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
    return net, notary, svc, alice.party, spends


def _wait_for(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return False


# ---------------------------------------------------------------------------
# the acceptance soak: wedged flush tick -> 503 + firing alert -> recovery


def test_wedged_flush_tick_flips_healthz_and_auto_resolves():
    """Kill the notary flush loop mid-work: /healthz goes 503 and the
    watchdog.notary.flush alert fires — with trace-id evidence from the
    flight recorder — within ONE watchdog deadline of simulated time,
    then auto-resolves the tick after the loop recovers."""
    DEADLINE = 1_000_000
    net, notary, svc, requester, spends = _rig(3)
    tracer = Tracer(enabled=True)
    monitor = hlib.HealthMonitor(
        clock=net.clock, tracer=tracer,
        policy=hlib.HealthPolicy(heartbeat_deadline_micros=DEADLINE),
    )
    svc.attach_health(monitor)

    # a real traced notarisation first, so the recorder holds the
    # notary.* spans a firing alert will cite as evidence
    span = tracer.start_trace("notarise.frame", tx_id=str(spends[0].id))
    fut = FlowFuture()
    svc.enqueue_pending(
        _PendingNotarisation(spends[0], requester, fut, span=span)
    )
    assert svc.tick() == 1 and hasattr(fut.result(), "by")
    assert tracer.recorder.recorded >= 1
    monitor.tick()
    assert monitor.healthz()[0]

    web = NodeWebServer(
        client=object(), pump=lambda: None, health=monitor
    ).start()
    try:
        status, body = _get_status(f"http://127.0.0.1:{web.port}/healthz")
        assert status == 200 and body["status"] == "ok"

        # the wedge: work queues, the tick loop never runs again
        fut2 = FlowFuture()
        svc.enqueue_pending(
            _PendingNotarisation(spends[1], requester, fut2)
        )
        net.clock.advance(DEADLINE + 1)
        monitor.tick()

        status, body = _get_status(f"http://127.0.0.1:{web.port}/healthz")
        assert status == 503
        assert body["unhealthy"] == {"notary.flush": "stalled"}

        alerts = monitor.snapshot()["alerts"]
        alert = alerts["watchdog.notary.flush"]
        assert alert["state"] == hlib.ALERT_FIRING
        assert alert["severity"] == hlib.SEV_CRITICAL
        # trace-id evidence: the recorder's slowest matching traces
        evidence = alert["evidence"]
        assert evidence["traces"], "firing alert carries no trace ids"
        assert all(
            t["trace_id"].startswith("0x") for t in evidence["traces"]
        )
        assert "Health.CanaryLatencyMicros" in evidence["metrics"]

        # GET /health carries the full picture + the event-log line
        status, body = _get_status(f"http://127.0.0.1:{web.port}/health")
        assert status == 200 and body["status"] == "unhealthy"
        assert body["heartbeats"]["notary.flush"]["state"] == "stalled"
        assert any(
            e["event"] == "firing"
            and e["alert"] == "watchdog.notary.flush"
            for e in body["events"]
        )

        # recovery: the loop ticks again (flushing the queued work)
        assert svc.tick() == 1 and fut2.done
        monitor.tick()
        status, body = _get_status(f"http://127.0.0.1:{web.port}/healthz")
        assert status == 200
        alerts = monitor.snapshot()["alerts"]
        assert alerts["watchdog.notary.flush"]["state"] == (
            hlib.ALERT_RESOLVED
        )
        assert any(
            e["event"] == "resolved"
            and e["alert"] == "watchdog.notary.flush"
            for e in monitor.events.tail()
        )
    finally:
        web.stop()


def test_wedged_verifier_drain_thread_soak():
    """The satellite soak: a REAL verifier-worker drain thread wedged
    on a blocking event. Beats stop, the stall alert fires within the
    watchdog deadline in TestClock time, and resolves after the thread
    resumes."""
    from corda_tpu.node.messaging import InMemoryMessagingNetwork
    from corda_tpu.node.verifier import VerifierWorker

    DEADLINE = 500_000
    clock = TestClock()
    monitor = hlib.HealthMonitor(
        clock=clock,
        policy=hlib.HealthPolicy(heartbeat_deadline_micros=DEADLINE),
    )
    imn = InMemoryMessagingNetwork()
    worker = VerifierWorker(
        imn.endpoint("w1"), "nodeA",
        batch_verifier=CpuBatchVerifier(),
        health=monitor, clock=clock,
    )
    gate = threading.Event()
    gate.set()
    stop = threading.Event()
    hb = monitor.watchdog.heartbeats()[0]
    assert hb.name == "verifier.drain"

    def drain_loop():
        while not stop.is_set():
            gate.wait()
            worker.drain()
            time.sleep(0.002)

    t = threading.Thread(target=drain_loop, daemon=True)
    t.start()
    try:
        assert _wait_for(lambda: hb.beats >= 2)
        monitor.tick()
        assert monitor.healthz()[0]

        gate.clear()                      # the wedge
        settled = hb.beats

        def beats_static():
            nonlocal settled
            b = hb.beats
            if b != settled:
                settled = b
                return False
            return True

        assert _wait_for(beats_static)    # the in-flight drain finished
        time.sleep(0.02)
        clock.advance(DEADLINE + 1)
        monitor.tick()
        ok, detail = monitor.healthz()
        assert not ok and detail["unhealthy"] == {
            "verifier.drain": "stalled"
        }
        assert (
            monitor.snapshot()["alerts"]["watchdog.verifier.drain"]["state"]
            == hlib.ALERT_FIRING
        )

        gate.set()                        # recovery
        before = hb.beats
        assert _wait_for(lambda: hb.beats > before)
        monitor.tick()
        ok, _ = monitor.healthz()
        assert ok
        assert (
            monitor.snapshot()["alerts"]["watchdog.verifier.drain"]["state"]
            == hlib.ALERT_RESOLVED
        )
    finally:
        stop.set()
        gate.set()
        t.join(timeout=5)


def test_wedged_ingest_feed_loop_trips_watchdog():
    """The ingest pool's feed loop parked forever on a full ring nobody
    drains: beats stop, the watchdog flags the stall; draining the ring
    un-parks the loop and the plane recovers."""
    from corda_tpu.node.ingest import IngestPipeline

    DEADLINE = 500_000
    clock = TestClock()
    monitor = hlib.HealthMonitor(
        clock=clock,
        policy=hlib.HealthPolicy(heartbeat_deadline_micros=DEADLINE),
    )
    hb = monitor.heartbeat("ingest.feed")
    pipe = IngestPipeline(ring_depth=1, frame_cache_size=0)
    try:
        # junk frames are fine: per-slot error isolation still produces
        # entries, and the feed loop still beats per batch
        t = pipe.feed(iter([[b"junk"]] * 3), heartbeat=hb)
        assert _wait_for(lambda: hb.beats >= 1)
        # depth-1 ring, no consumer: the second put parks the thread
        time.sleep(0.05)
        beats_parked = hb.beats
        clock.advance(DEADLINE + 1)
        monitor.tick()
        ok, detail = monitor.healthz()
        assert not ok and "ingest.feed" in detail["unhealthy"]

        pipe.ring.drain()                 # consumer shows up
        assert _wait_for(lambda: hb.beats > beats_parked)
        monitor.tick()
        assert monitor.healthz()[0]
        t.join(timeout=5)
    finally:
        pipe.close()


def test_livelock_detected_when_beating_without_progress():
    """Beating is not health: queue depth > 0 with zero progress across
    the livelock window flags LIVELOCK — the wedge a stall detector
    cannot see."""
    clock = TestClock()
    monitor = hlib.HealthMonitor(
        clock=clock,
        policy=hlib.HealthPolicy(
            heartbeat_deadline_micros=10_000_000,
            livelock_deadline_micros=1_000_000,
        ),
    )
    depth = {"n": 4}
    hb = monitor.heartbeat("spin.loop", queue_depth=lambda: depth["n"])
    for _ in range(5):
        hb.beat()                        # alive, but progress-free
        clock.advance(300_000)
        monitor.tick()
    ok, detail = monitor.healthz()
    assert not ok and detail["unhealthy"] == {"spin.loop": "livelock"}
    # progress (or an empty queue) clears it
    hb.beat(progress=4)
    depth["n"] = 0
    monitor.tick()
    assert monitor.healthz()[0]


# ---------------------------------------------------------------------------
# alert hysteresis + burn rate


def test_alert_hysteresis_never_flaps_on_oscillating_metric():
    """A metric crossing its threshold every tick must never walk
    pending -> firing: the for-duration hold IS the flap damper. A
    sustained breach fires exactly once, and oscillation while firing
    doesn't churn resolved/refired events either."""
    clock = TestClock()
    monitor = hlib.HealthMonitor(
        clock=clock,
        policy=hlib.HealthPolicy(
            alert_for_micros=350_000, alert_clear_for_micros=350_000
        ),
    )
    box = {"v": 0}
    monitor.add_rule(
        hlib.AlertRule(
            "flap.metric",
            check=lambda now: (box["v"] > 10, {"value": box["v"]}),
        )
    )

    def alert():
        return monitor.snapshot()["alerts"]["flap.metric"]

    for i in range(40):                  # oscillate every 100ms tick
        box["v"] = 100 if i % 2 == 0 else 0
        monitor.tick()
        clock.advance(100_000)
    assert alert()["fire_count"] == 0
    assert alert()["state"] in (hlib.ALERT_INACTIVE, hlib.ALERT_PENDING)
    assert monitor.events.tail() == []   # zero firing/resolved churn

    box["v"] = 100                       # sustained breach: fires once
    for _ in range(6):
        monitor.tick()
        clock.advance(100_000)
    assert alert()["state"] == hlib.ALERT_FIRING
    assert alert()["fire_count"] == 1

    for i in range(10):                  # oscillation while firing
        box["v"] = 100 if i % 2 == 0 else 0
        monitor.tick()
        clock.advance(100_000)
    assert alert()["state"] == hlib.ALERT_FIRING
    assert alert()["fire_count"] == 1
    assert sum(1 for e in monitor.events.tail() if e["event"] == "firing") == 1

    box["v"] = 0                         # sustained clear: resolves once
    for _ in range(6):
        monitor.tick()
        clock.advance(100_000)
    assert alert()["state"] == hlib.ALERT_RESOLVED
    events = [e["event"] for e in monitor.events.tail()]
    assert events == ["firing", "resolved"]


def test_slo_burn_rate_fires_on_sustained_breach_only():
    """watch_qos installs the multi-window burn-rate rule over
    Qos.AdmittedLatencyMicros p99 vs the configured target: a brief
    breach never fires (the long window filters it), a sustained one
    walks pending -> firing with the burn rates in the detail."""
    from corda_tpu.node import qos as qoslib

    clock = TestClock()
    policy = hlib.HealthPolicy(
        burn_short_window_micros=5_000_000,
        burn_long_window_micros=30_000_000,
        # a 10% budget: a 2-tick blip in a 30-tick long window (6.7%)
        # stays inside it — the long window's whole job
        slo_budget_fraction=0.1,
        alert_for_micros=2_000_000,
    )
    # brief breach: the short window burns, the long window filters it
    # (unit-level: a controllable p99 feed into the same rule class)
    box = {"p99": 1_000.0}
    brief_rule = hlib.BurnRateRule(lambda: box["p99"], 10_000, policy)
    monitor = hlib.HealthMonitor(clock=clock, policy=policy)
    monitor.add_rule(brief_rule)
    # a full healthy long window first, then the 2-tick blip: 2/30
    # breached (6.7%) stays inside the 10% budget on the long window
    for i in range(60):
        box["p99"] = 50_000.0 if i in (40, 41) else 1_000.0
        monitor.tick()
        clock.advance(1_000_000)
    brief = monitor.snapshot()["alerts"]["slo.burn_rate"]
    assert brief["fire_count"] == 0

    # sustained breach: every sample over target -> both windows burn
    qos2 = qoslib.NotaryQos(
        qoslib.QosPolicy(target_p99_micros=10_000), clock=clock
    )
    monitor2 = hlib.HealthMonitor(clock=clock, policy=policy)
    monitor2.watch_qos(qos2)
    for _ in range(64):
        qos2.admitted_latency.update(50_000)
    for _ in range(5):
        monitor2.tick()
        clock.advance(1_000_000)
    alert = monitor2.snapshot()["alerts"]["slo.burn_rate"]
    assert alert["state"] == hlib.ALERT_FIRING
    assert alert["severity"] == hlib.SEV_CRITICAL
    assert alert["detail"]["burn_short"] >= 1.0
    assert alert["detail"]["burn_long"] >= 1.0
    assert alert["detail"]["p99_micros"] >= 50_000
    assert "metrics" in alert["evidence"]


def test_shed_ratio_rule_fires_under_sustained_shedding():
    from corda_tpu.node import qos as qoslib

    clock = TestClock()
    qos = qoslib.NotaryQos(qoslib.QosPolicy(), clock=clock)
    monitor = hlib.HealthMonitor(
        clock=clock,
        policy=hlib.HealthPolicy(
            shed_ratio_threshold=0.5, alert_for_micros=1_000_000
        ),
    )
    monitor.watch_qos(qos)
    for _ in range(5):
        for _ in range(10):
            qos.count_shed(qoslib.SHED_EXPIRED_FLUSH)
        qos.answered.inc(2)              # 10 shed : 2 answered
        monitor.tick()
        clock.advance(500_000)
    alert = monitor.snapshot()["alerts"]["qos.shed_ratio"]
    assert alert["state"] == hlib.ALERT_FIRING
    assert alert["detail"]["shed_ratio"] > 0.5


def test_ring_rule_fires_on_saturation_and_parked_growth():
    """The ingest-ring rule: depth at >= 90% of the bound fires, and so
    does parked-frame growth (frames parking faster than retry_parked
    re-admits them) — both precede a stalled pump."""
    clock = TestClock()
    monitor = hlib.HealthMonitor(
        clock=clock,
        policy=hlib.HealthPolicy(
            alert_for_micros=0, alert_clear_for_micros=0,
            ring_saturation_threshold=0.9,
            shed_window_micros=10_000_000,
        ),
    )
    depth = {"n": 0}
    parked = {"n": 0}
    monitor.watch_ring(
        "verifier.requests",
        lambda: depth["n"],
        capacity=10,
        parked_fn=lambda: parked["n"],
    )

    def alert():
        return monitor.snapshot()["alerts"]["ring.verifier.requests"]

    monitor.tick()
    assert alert()["state"] == hlib.ALERT_INACTIVE

    depth["n"] = 9                       # 90% of the bound
    monitor.tick()
    assert alert()["state"] == hlib.ALERT_FIRING
    assert alert()["detail"]["saturation"] == 0.9
    depth["n"] = 1
    clock.advance(1_000_000)
    monitor.tick()
    assert alert()["state"] == hlib.ALERT_RESOLVED

    parked["n"] = 5                      # frames parking, none re-admitted
    clock.advance(1_000_000)
    monitor.tick()
    assert alert()["state"] == hlib.ALERT_FIRING
    assert alert()["detail"]["parked_growth"] == 5
    clock.advance(11_000_000)            # growth window drains
    monitor.tick()
    assert alert()["state"] == hlib.ALERT_RESOLVED


def test_event_log_appends_json_lines_to_file(tmp_path):
    path = str(tmp_path / "health_events.jsonl")
    clock = TestClock()
    monitor = hlib.HealthMonitor(
        clock=clock,
        policy=hlib.HealthPolicy(alert_for_micros=0),
        event_log_path=path,
    )
    monitor.add_rule(
        hlib.AlertRule("always.on", check=lambda now: (True, {"v": 1}))
    )
    monitor.tick()
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 1
    assert lines[0]["event"] == "firing" and lines[0]["alert"] == "always.on"
    assert lines[0]["at_micros"] == clock.now_micros()


# ---------------------------------------------------------------------------
# the canary


def test_canary_rides_real_flush_without_touching_uniqueness():
    """The canary notarisation goes through the REAL hot path — staged,
    batch-dispatched, validated, committed (vacuously) and signed by an
    ordinary flush — feeds Health.CanaryLatencyMicros, and leaves the
    uniqueness store's real namespace untouched."""
    net, notary, svc, requester, spends = _rig(1)
    monitor = hlib.HealthMonitor(
        clock=net.clock,
        policy=hlib.HealthPolicy(canary_interval_micros=1_000),
    )
    svc.attach_health(monitor)
    probe = monitor.attach_canary(
        hlib.notary_canary_fn(notary.services, notary.party)
    )
    monitor.tick()                       # launches: enqueues one canary
    assert probe.launched == 1 and svc.backlog() == 1
    net.clock.advance(2_500)
    assert svc.tick() == 1               # a REAL flush serves it
    assert probe.completed == 1
    assert probe.last_latency_micros == 2_500
    assert monitor.canary_latency.count == 1
    # nothing committed: the canary has no inputs to consume
    assert svc.uniqueness.committed == {}
    # ordinary traffic flushes alongside later canaries untouched
    fut = FlowFuture()
    svc.enqueue_pending(_PendingNotarisation(spends[0], requester, fut))
    net.clock.advance(2_000)
    monitor.tick()                       # second canary joins the batch
    assert svc.tick() == 2
    assert hasattr(fut.result(), "by") and probe.completed == 2
    assert len(svc.uniqueness.committed) == 1   # the spend's input only


def test_canary_deadman_fires_when_probes_stop_and_resolves():
    net, notary, svc, requester, _ = _rig(0)
    monitor = hlib.HealthMonitor(
        clock=net.clock,
        policy=hlib.HealthPolicy(
            canary_interval_micros=1_000,
            canary_deadman_micros=10_000,
        ),
    )
    svc.attach_health(monitor)
    real_fn = hlib.notary_canary_fn(notary.services, notary.party)
    probe = monitor.attach_canary(real_fn)
    monitor.tick()
    svc.tick()
    assert probe.completed == 1

    probe._fn = lambda complete: None    # probes launch, never complete
    for _ in range(12):
        net.clock.advance(1_500)
        monitor.tick()
    alert = monitor.snapshot()["alerts"]["canary.deadman"]
    assert alert["state"] == hlib.ALERT_FIRING
    assert alert["severity"] == hlib.SEV_CRITICAL
    assert monitor.snapshot()["canary"]["overdue"]

    probe._fn = real_fn                  # the path heals
    net.clock.advance(1_500)
    monitor.tick()                       # relaunch
    svc.tick()                           # the flush answers it
    monitor.tick()
    assert (
        monitor.snapshot()["alerts"]["canary.deadman"]["state"]
        == hlib.ALERT_RESOLVED
    )


# ---------------------------------------------------------------------------
# endpoints: /healthz, /health, the index, JSON 404s, /cluster


def test_webserver_index_content_types_and_json_404():
    monitor = hlib.HealthMonitor(clock=TestClock())
    web = NodeWebServer(
        client=object(), pump=lambda: None,
        metrics=MetricRegistry(), health=monitor,
    ).start()
    try:
        base = f"http://127.0.0.1:{web.port}"
        with urllib.request.urlopen(base + "/", timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == "application/json"
            index = json.loads(resp.read())
        paths = {e["path"]: e for e in index["endpoints"]}
        assert {"/", "/metrics", "/traces", "/qos", "/healthz",
                "/health", "/cluster"} <= set(paths)
        assert paths["/healthz"]["enabled"] is True
        assert paths["/cluster"]["enabled"] is False   # not wired here
        assert "/api/status" in index["api"]

        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")

        status, body = _get_status(base + "/no/such/endpoint")
        assert status == 404 and "no such endpoint" in body["error"]

        # non-GET/POST methods get a JSON error too, never the
        # http.server default stub
        req = urllib.request.Request(base + "/healthz", method="PUT")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 405
        assert json.loads(exc.value.read())["error"].startswith("method PUT")

        # /cluster without a rollup wired: JSON 404
        status, body = _get_status(base + "/cluster")
        assert status == 404 and "error" in body
    finally:
        web.stop()


def test_health_summary_query_serves_condensed_form():
    monitor = hlib.HealthMonitor(clock=TestClock())
    monitor.heartbeat("loop.a")
    monitor.tick()
    web = NodeWebServer(
        client=object(), pump=lambda: None, health=monitor
    ).start()
    try:
        status, full = _get(f"http://127.0.0.1:{web.port}/health")
        assert status == 200 and "heartbeats" in full and "events" in full
        status, summary = _get(
            f"http://127.0.0.1:{web.port}/health?summary=1"
        )
        assert status == 200
        assert summary["healthy"] is True
        assert "heartbeats" not in summary
    finally:
        web.stop()


def test_cluster_rollup_two_nodes_with_stale_peer():
    """Two live gateways + one unreachable peer: GET /cluster on node A
    rolls up B's summary, counts B's firing alert, carries the fleet
    worst-state, and marks the unreachable C stale — not fatal."""
    clock = TestClock()
    monitor_a = hlib.HealthMonitor(clock=clock)
    monitor_b = hlib.HealthMonitor(
        clock=clock, policy=hlib.HealthPolicy(alert_for_micros=0)
    )
    monitor_b.add_rule(
        hlib.AlertRule(
            "b.trouble", check=lambda now: (True, {"v": 9}),
            severity=hlib.SEV_WARNING,
        )
    )
    monitor_b.tick()
    web_b = NodeWebServer(
        client=object(), pump=lambda: None, health=monitor_b
    ).start()
    cluster = hlib.ClusterHealth(
        "A",
        lambda: monitor_a.snapshot(summary=True),
        lambda: {
            "B": f"http://127.0.0.1:{web_b.port}/health?summary=1",
            # nothing listens here: connection refused, fast
            "C": "http://127.0.0.1:9/health?summary=1",
        },
        clock_fn=clock.now_micros,
        timeout=1.0,
    )
    web_a = NodeWebServer(
        client=object(), pump=lambda: None,
        health=monitor_a, cluster=cluster,
    ).start()
    try:
        status, body = _get(f"http://127.0.0.1:{web_a.port}/cluster")
        assert status == 200
        assert body["self"] == "A"
        assert set(body["nodes"]) == {"A", "B", "C"}
        assert body["nodes"]["A"]["status"] == "ok"
        assert body["nodes"]["B"]["status"] == "degraded"
        assert body["nodes"]["B"]["summary"]["alerts_firing"] == 1
        assert body["nodes"]["C"]["stale"] is True
        assert body["nodes"]["C"]["error"]
        assert body["stale_peers"] == ["C"]
        assert body["worst"] == "degraded"
        assert body["alerts_firing"] == {"A": 0, "B": 1, "C": 0}
        assert body["alerts_firing_total"] == 1
    finally:
        web_a.stop()
        web_b.stop()


def test_cluster_keeps_last_summary_when_peer_goes_dark():
    clock = TestClock()
    calls = {"n": 0}

    def fetch(url):
        calls["n"] += 1
        if calls["n"] > 1:
            raise OSError("connection refused")
        return {"healthy": True, "status": "ok", "alerts_firing": 0}

    cluster = hlib.ClusterHealth(
        "A",
        lambda: {"healthy": True, "status": "ok", "alerts_firing": 0},
        lambda: {"B": "http://b/health"},
        fetch=fetch,
        clock_fn=clock.now_micros,
        cache_ttl_micros=1_000,
    )
    first = cluster.snapshot()
    assert first["nodes"]["B"]["stale"] is False
    clock.advance(2_000)                 # cache expires -> refetch fails
    second = cluster.snapshot()
    assert second["nodes"]["B"]["stale"] is True
    # the last-known summary survives the outage
    assert second["nodes"]["B"]["summary"]["status"] == "ok"
    assert second["worst"] == "ok"       # stale is not fatal


# ---------------------------------------------------------------------------
# the real node: boot, heartbeats, endpoints, advertised web_port


def test_node_boots_health_plane_and_serves_endpoints(tmp_path):
    from corda_tpu.node.config import NodeConfig, RpcUserConfig
    from corda_tpu.node.node import Node

    node = Node(
        NodeConfig(
            name="HealthNode", base_dir=str(tmp_path / "n"),
            notary="batching", use_tls=False,
            verifier_backend="cpu", web_port=0,
            rpc_users=(RpcUserConfig("ops", "pw", ("ALL",)),),
        )
    ).start()
    try:
        node.pump()
        base = f"http://127.0.0.1:{node.web.port}"
        status, body = _get_status(base + "/healthz")
        assert status == 200 and body["status"] == "ok"

        status, body = _get(base + "/health")
        assert {"messaging.pump", "notary.flush"} <= set(
            body["heartbeats"]
        )
        assert body["canary"] is not None

        # the canary launched at boot rides the next flush
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            node.pump()
            if node.health.canary.completed >= 1:
                break
            time.sleep(0.01)
        assert node.health.canary.completed >= 1
        # ...without touching the uniqueness namespace
        rows = node.db.query("SELECT COUNT(*) FROM notary_commits")
        assert rows[0][0] == 0

        # /cluster answers (a fleet of one) and the map advertises the
        # gateway port peers would pull /health from
        status, body = _get(base + "/cluster")
        assert status == 200 and body["worst"] == "ok"
        assert set(body["nodes"]) == {"HealthNode"}
        assert node.info.web_port == node.web.port
        cached = node.services.network_map_cache.node_by_name("HealthNode")
        assert cached is not None and cached.web_port == node.web.port

        # Health.* metrics land on the node's scrape surface
        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
        assert "Health_CanaryLatencyMicros" in text
        assert "Health_Healthy 1" in text
    finally:
        node.stop()


def test_node_health_peer_urls_follow_the_network_map(tmp_path):
    from corda_tpu.node.config import NodeConfig, RpcUserConfig
    from corda_tpu.node.node import Node
    from corda_tpu.node.services import NodeInfo
    from corda_tpu.core.identity import Party
    from corda_tpu.crypto import schemes

    node = Node(
        NodeConfig(
            name="MapNode", base_dir=str(tmp_path / "m"),
            notary="", use_tls=False, verifier_backend="cpu",
            web_port=0,
            rpc_users=(RpcUserConfig("ops", "pw", ("ALL",)),),
        )
    ).start()
    try:
        kp = schemes.generate_keypair(seed=9)
        node.services.network_map_cache.add_node(
            NodeInfo(
                "PeerWithWeb", Party("PeerWithWeb", kp.public),
                host="10.0.0.7", port=10002, web_port=8443,
            )
        )
        node.services.network_map_cache.add_node(
            NodeInfo(
                "PeerNoWeb", Party("PeerNoWeb", kp.public),
                host="10.0.0.8", port=10002,
            )
        )
        urls = node._health_peer_urls()
        assert urls == {
            "PeerWithWeb": "http://10.0.0.7:8443/health?summary=1"
        }
    finally:
        node.stop()


# ---------------------------------------------------------------------------
# bench smoke: --quick health


def test_bench_quick_health_emits_wellformed_record():
    """`bench.py --quick health` must run under JAX_PLATFORMS=cpu,
    prove a canary round trip through the real flush, and hold the
    health plane's overhead under the 2% line — the tier-1 guard on
    the health bench plumbing (next to --quick ingest/trace/qos)."""
    import os
    import subprocess
    import sys

    bench = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    out = subprocess.run(
        [sys.executable, os.path.abspath(bench), "--quick", "health"],
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            # the plane costs ~8us/tick; at tiny batches the A/B is
            # dominated by timer noise on ~100ms walls, so keep the
            # flush deep enough (and reps >= 3 for the min-of-reps)
            # that 2% is signal, not jitter
            "BENCH_BATCH": "32",
            "BENCH_ITERS": "3",
        },
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, out.stdout
    rec = json.loads(lines[0])
    assert rec["metric"] == "health_plane_overhead"
    assert rec["quick"] is True
    assert rec["value"] <= 0.02
    assert rec["canary_completed"] >= 1
    assert rec["healthy"] is True
    assert rec["alerts_firing"] == 0
