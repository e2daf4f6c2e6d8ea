"""Transaction provenance plane (ISSUE 13): the lifecycle ledger,
stage histograms + slowest leaderboard, the stage-SLO rule, the sqlite
spill, cluster-wide GET /tx/<id>, the parallel peer fan-out, the fleet
lifecycle-ledger reconciliation under chaos, and the bench smoke.

The acceptance arcs:
  - a booted node (batching, shards>=2, verifier pool, intent WAL)
    serves GET /tx/<id> with a complete admission->commit timeline
    (>=6 lifecycle events incl. per-attempt verify history),
    /tx/slowest populated, Tx.Stage.* on /metrics;
  - a fleet chaos scenario (verifier kill + notary kill-restart)
    passes the lifecycle-ledger reconciliation: every admitted tx
    reaches EXACTLY ONE terminal event, shed/unavailable attributed
    by reason;
  - a real two-process TCP rig: tx admitted on A, verified by a
    worker attached to B, committed via consensus — one merged
    timeline with events from both processes.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from corda_tpu.node import qos as qoslib
from corda_tpu.node.persistence import NodeDatabase, TxStoryIndex
from corda_tpu.node.services import TestClock
from corda_tpu.testing import fleet as fl
from corda_tpu.utils import tracing
from corda_tpu.utils.health import HealthMonitor, HealthPolicy
from corda_tpu.utils.metrics import MetricRegistry
from corda_tpu.utils.txstory import (
    ClusterTxStory,
    TERMINALS,
    TxStory,
)


def _get_json(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


# ---------------------------------------------------------------------------
# the ledger core


def test_story_closes_exactly_once_and_reanswers_dedupe():
    s = TxStory(metrics=MetricRegistry())
    s.admit("T1", trace_id="0xabc", deadline=5, requester="alice")
    s.journal("T1", 3)
    s.flush_membership(["T1"], shard=1)
    s.record("T1", "notary.verified")
    s.close("T1", "committed")
    st = s.story("T1")
    assert st["terminal"] == "committed" and not st["open"]
    assert st["trace_id"] == "0xabc"
    assert [e["name"] for e in st["events"]] == [
        "notary.admit", "wal.journal", "notary.flush",
        "notary.verified", "tx.committed",
    ]
    # the flush event carries its batch id + shard
    flush = st["events"][2]
    assert flush["batch_id"] == 1 and flush["shard"] == 1
    # a second answer (the WAL-replay window) records tx.reanswer,
    # never a second terminal
    s.close("T1", "committed")
    st = s.story("T1")
    terms = [
        e["name"] for e in st["events"]
        if e["name"] in set(TERMINALS.values())
    ]
    assert terms == ["tx.committed"]
    assert st["events"][-1]["name"] == "tx.reanswer"
    assert s.reanswers == 1 and s.closed == 1


def test_terminal_mapping_covers_every_notary_answer_kind():
    from corda_tpu.node.notary import NotaryError

    s = TxStory()
    cases = [
        (object(), "committed", None),                     # signature
        (NotaryError("conflict", "x"), "rejected", "conflict"),
        (NotaryError("invalid-transaction", "x"), "rejected",
         "invalid-transaction"),
        (NotaryError("shed", "brownout: nope"), "shed", "brownout"),
        (NotaryError("shed", "admission rate exceeded"), "shed",
         "admission"),
        (NotaryError("shed", "deadline 5 expired while queued"),
         "shed", "expired"),
        (NotaryError("poison-quarantined", "x"), "quarantined",
         "poison-quarantined"),
        (NotaryError("verification-unavailable", "x"), "unavailable",
         "verification-unavailable"),
        (NotaryError("shard-unavailable", "x"), "unavailable",
         "shard-unavailable"),
    ]
    for i, (outcome, kind, reason) in enumerate(cases):
        tid = f"T{i}"
        s.admit(tid)
        s.terminal_from(tid, outcome)
        st = s.story(tid)
        assert st["terminal"] == kind, (tid, st)
        assert st["reason"] == reason, (tid, st)


def test_open_table_bounded_and_eviction_counted():
    s = TxStory(max_open=16)
    for i in range(64):
        s.admit(f"T{i}")
    assert s.snapshot()["open"] <= 16
    assert s.evicted == 48
    # the newest stories survived, the oldest fell off
    assert s.story("T63") is not None
    assert s.story("T0") is None


def test_per_tx_event_cap_drops_not_grows_but_never_the_terminal():
    db = NodeDatabase(":memory:")
    index = TxStoryIndex(db)
    s = TxStory(max_events_per_tx=8, index=index)
    s.admit("T1")
    for i in range(32):
        s.record("T1", "verify.redispatch", attempt=i)
    st = s.story("T1")
    assert st["event_count"] == 8
    assert s.dropped_events == 25
    # the close is EXEMPT from the cap: a retry storm must not leave
    # the story (or its sqlite spill) reading open-forever
    s.close("T1", "committed")
    s.tick()
    st = s.story("T1")
    assert st["events"][-1]["name"] == "tx.committed"
    assert any(
        e["name"] == "tx.committed" for e in index.events_for("T1")
    )
    db.close()


def test_stage_histograms_and_slowest_leaderboard():
    m = MetricRegistry()
    s = TxStory(metrics=m, keep_slowest=2)
    for tid, dwell in (("FAST", 0.0), ("SLOW", 0.02), ("MID", 0.005)):
        s.admit(tid)
        s.flush_membership([tid])
        time.sleep(dwell)
        s.record(tid, "notary.verified")
        s.close(tid, "committed")
    # histograms populated per closed tx
    assert m.get("Tx.Stage.TotalMicros").count == 3
    assert m.get("Tx.Stage.VerifyMicros").count == 3
    text = m.to_prometheus()
    assert "Tx_Stage_TotalMicros" in text
    # bounded leaderboard keeps the two SLOWEST, slowest first
    rows = s.slowest()
    assert [r["tx_id"] for r in rows] == ["SLOW", "MID"]
    assert rows[0]["total_micros"] >= rows[1]["total_micros"]
    assert "stages_micros" in rows[0]


def test_stage_slo_rule_fires_with_offending_tx_ids():
    clock = TestClock()
    m = MetricRegistry()
    s = TxStory(metrics=m, clock=clock)
    monitor = HealthMonitor(
        clock=clock,
        policy=HealthPolicy(
            alert_for_micros=0, alert_clear_for_micros=0,
        ),
    )
    monitor.watch_txstory(
        s, {"verify": 1}, window_micros=1_000_000
    )
    # one genuinely slow transaction (real dwell between flush and
    # verified: the stage deltas ride the monotonic clock)
    s.admit("SLOW-TX")
    s.flush_membership(["SLOW-TX"])
    time.sleep(0.003)
    s.record("SLOW-TX", "notary.verified")
    s.close("SLOW-TX", "committed")
    clock.advance(1)
    monitor.tick()
    alert = monitor.snapshot()["alerts"]["txstory.stage_slo"]
    assert alert["state"] == "firing"
    breach = alert["detail"]["stages"]["verify"]
    assert "SLOW-TX" in breach["tx_ids"]
    assert breach["p99_micros"] > breach["target_micros"]
    # the window drains -> the rule resolves (no frozen breach)
    clock.advance(2_000_000)
    monitor.tick()
    alert = monitor.snapshot()["alerts"]["txstory.stage_slo"]
    assert alert["state"] != "firing"


def test_install_rules_rejects_unknown_stage():
    s = TxStory()
    monitor = HealthMonitor(clock=TestClock())
    with pytest.raises(ValueError):
        s.install_rules(monitor, {"not-a-stage": 5})


# ---------------------------------------------------------------------------
# the sqlite spill (persistence.TxStoryIndex)


def test_index_spill_serves_ring_evicted_stories():
    db = NodeDatabase(":memory:")
    index = TxStoryIndex(db)
    s = TxStory(max_open=16, keep_done=16, index=index)
    for i in range(64):
        tid = f"T{i:02d}"
        s.admit(tid)
        s.close(tid, "committed")
    s.tick()   # group-commit the buffer (the pump-tick discipline)
    assert index.appended == 128
    # T00 fell off BOTH in-memory rings; the index still answers
    assert s.snapshot()["completed_retained"] == 16
    st = s.story("T00")
    assert st is not None and st["from_index"]
    assert st["terminal"] == "committed"
    assert [e["name"] for e in st["events"]] == [
        "notary.admit", "tx.committed",
    ]
    # unknown tx stays a miss
    assert s.story("NOPE") is None
    db.close()


def test_index_rows_bounded_by_retention():
    db = NodeDatabase(":memory:")
    index = TxStoryIndex(db, max_rows=1_000)
    for i in range(1_500):
        index.append(f"T{i}", "notary.admit", i, i, None)
    index.flush()
    assert index.row_count <= 1_000
    db.close()


# ---------------------------------------------------------------------------
# QoS attribution hooks


def test_qos_shed_tx_attributes_reason_and_closes_pre_queue_sheds():
    s = TxStory()
    qos = qoslib.NotaryQos(clock=TestClock())
    qos.txstory = s
    qos.admit_tx("T-OK")
    qos.shed_tx(qoslib.SHED_BROWNOUT_NO_DEADLINE, "T-BROWN", terminal=True)
    qos.shed_tx(qoslib.SHED_EXPIRED_FLUSH, "T-FLUSH")   # future owns it
    assert s.story("T-OK")["events"][0]["name"] == "qos.admit"
    brown = s.story("T-BROWN")
    assert brown["terminal"] == "shed" and brown["reason"] == "brownout"
    assert brown["events"][0]["reason"] == qoslib.SHED_BROWNOUT_NO_DEADLINE
    flush = s.story("T-FLUSH")
    assert flush["open"] and flush["events"][0]["name"] == "qos.shed"
    # counters moved alongside (the attribution never replaced them)
    assert qos.shed_total == 2 and qos.admitted.count == 1


# ---------------------------------------------------------------------------
# the batching notary end to end (mock fabric)


def _notary_with_story(**kw):
    """MockNetwork batching notary + an UNATTACHED TxStory: the spend
    fixture's issue flows notarise through the service too, so tests
    attach the ledger AFTER issuing to keep the timeline they assert
    to the submissions they make."""
    from corda_tpu.testing.mock_network import MockNetwork

    net = MockNetwork(seed=3)
    notary = net.create_notary("StoryNotary", batching=True, **kw)
    svc = notary.services.notary_service
    story = TxStory(metrics=svc.metrics, clock=net.clock)
    return net, notary, svc, story


def _spend_fixture(net, notary, n=4):
    from corda_tpu.finance.cash import CashIssueFlow

    alice = net.create_node("Alice")
    stxs = []
    for i in range(n):
        # distinct quantities -> distinct tx ids (identical issues
        # would merge into ONE story and double its events)
        stxs.append(
            alice.run_flow(
                CashIssueFlow(100 + i, "USD", alice.party, notary.party)
            )
        )
    return alice, stxs


def test_batching_notary_emits_complete_stories():
    net, notary, svc, story = _notary_with_story()
    alice, stxs = _spend_fixture(net, notary, n=3)
    svc.attach_txstory(story)
    futs = [svc.submit(stx, alice.party) for stx in stxs]
    svc.flush()
    for stx, fut in zip(stxs, futs):
        assert hasattr(fut.result(), "by")
        st = story.story(str(stx.id))
        assert [e["name"] for e in st["events"]] == [
            "notary.admit", "notary.flush", "notary.verified",
            "tx.committed",
        ], st
        assert st["stages_micros"].get("total") is not None
    # all three txs shared ONE flush batch id
    bids = {
        e["batch_id"]
        for stx in stxs
        for e in story.story(str(stx.id))["events"]
        if e["name"] == "notary.flush"
    }
    assert len(bids) == 1
    assert story.snapshot()["closed"] == 3


def test_wal_journal_and_replay_events_reconcile_across_kill():
    from corda_tpu.node.persistence import NotaryIntentJournal

    net, notary, svc, story = _notary_with_story()
    journal = NotaryIntentJournal(NodeDatabase(":memory:"))
    alice, stxs = _spend_fixture(net, notary, n=2)
    svc.attach_intent_journal(journal)
    svc.attach_txstory(story)
    futs = [svc.submit(stx, alice.party) for stx in stxs]
    del futs
    tids = [str(stx.id) for stx in stxs]
    for tid in tids:
        assert [e["name"] for e in story.story(tid)["events"]] == [
            "notary.admit", "wal.journal",
        ]
    # kill: pending vanishes with the heap, futures never resolve
    for shard in svc._shards:
        shard.pending.clear()
    # restart: a fresh service over the same WAL + the SAME ledger
    from corda_tpu.node.notary import BatchingNotaryService

    svc2 = BatchingNotaryService(
        notary.services, svc.uniqueness, intent_journal=journal,
    )
    svc2.attach_txstory(story)
    replayed = svc2.replay_intents()
    assert len(replayed) == 2
    svc2.flush()
    svc2.tick()
    for tid in tids:
        st = story.story(tid)
        names = [e["name"] for e in st["events"]]
        assert "wal.replay" in names, names
        assert st["terminal"] == "committed"
        terms = [
            n for n in names if n in set(TERMINALS.values())
        ]
        assert terms == ["tx.committed"], names
    assert journal.unresolved_count == 0


def test_degraded_flush_attributes_outcome_per_tx():
    from corda_tpu.crypto.batch_verifier import DispatchFaultInjector

    net, notary, svc, story = _notary_with_story()
    alice, stxs = _spend_fixture(net, notary, n=2)
    svc.attach_txstory(story)
    injector = DispatchFaultInjector(notary.services.batch_verifier)
    notary.services._batch_verifier = injector
    injector.arm(2)   # first attempt + the one retry both fail
    futs = [svc.submit(stx, alice.party) for stx in stxs]
    svc.flush()
    for stx, fut in zip(stxs, futs):
        assert hasattr(fut.result(), "by")   # CPU fallback signed it
        st = story.story(str(stx.id))
        names = [e["name"] for e in st["events"]]
        assert "notary.degraded" in names, names
        assert st["terminal"] == "committed"
    assert svc.degraded


# ---------------------------------------------------------------------------
# parallel peer fan-out (the ClusterTraces satellite)


def test_fan_out_overlaps_slow_peers_and_degrades_errors():
    def slow():
        time.sleep(0.25)
        return "ok"

    def boom():
        raise ConnectionError("unreachable")

    jobs = {f"peer{i}": slow for i in range(8)}
    jobs["dead"] = boom
    t0 = time.perf_counter()
    results, errors = tracing.fan_out(jobs, workers=8)
    wall = time.perf_counter() - t0
    assert set(results) == {f"peer{i}" for i in range(8)}
    assert errors == {"dead": "ConnectionError: unreachable"}
    # 8 x 0.25s sequential = 2s; the fan-out pays ~one sleep
    assert wall < 1.0, wall


def test_cluster_traces_pulls_peers_in_parallel():
    tracer = tracing.Tracer(enabled=True)
    span = tracer.start_trace("alpha.request")
    span.end()
    calls = []

    def fetch(url):
        calls.append((url, time.perf_counter()))
        time.sleep(0.2)
        return {"traceEvents": [], "clockSync": {}}

    ct = tracing.ClusterTraces(
        "A", tracer,
        peers_fn=lambda: {f"B{i}": f"http://b{i}" for i in range(6)},
        fetch=fetch,
    )
    t0 = time.perf_counter()
    out = ct.assemble(span.trace_id)
    wall = time.perf_counter() - t0
    assert len(calls) == 6
    assert wall < 0.8, wall        # sequential would be >= 1.2s
    assert out["found"]            # the local span alone


def test_cluster_tx_story_merges_members_with_clock_shift():
    clock = TestClock()
    a, b = TxStory(clock=clock), TxStory(clock=clock)
    a.admit("TX9", trace_id="0x9")
    a.record("TX9", "notary.verified")
    a.close("TX9", "committed")
    b.record("TX9", "consensus.commit", index=4, member="B")

    ct = ClusterTxStory(
        "A", a,
        peers_fn=lambda: {"B": "http://b", "A": "ignored"},
        fetch=lambda url: b.local_payload("TX9"),
    )
    out = ct.assemble("TX9")
    assert out["found"] and out["members"] == ["A", "B"]
    assert out["terminal"] == "committed"
    assert out["trace_id"] == "0x9"
    names = {(e["node"], e["name"]) for e in out["events"]}
    assert ("B", "consensus.commit") in names
    assert ("A", "tx.committed") in names
    # every merged event landed on ONE shifted axis and stays sorted
    ts = [e["ts_us"] for e in out["events"]]
    assert ts == sorted(ts)
    # an unreachable peer degrades, never fails the assembly
    ct_bad = ClusterTxStory(
        "A", a,
        peers_fn=lambda: {"DEAD": "http://dead"},
        fetch=lambda url: (_ for _ in ()).throw(OSError("down")),
    )
    out = ct_bad.assemble("TX9")
    assert out["found"] and "DEAD" in out["errors"]


# ---------------------------------------------------------------------------
# the booted node (acceptance): GET /tx/<id>, /tx/slowest, Tx.Stage.*


def test_node_boots_provenance_plane_and_serves_tx_timeline(tmp_path):
    from corda_tpu.crypto import schemes
    from corda_tpu.crypto.batch_verifier import CpuBatchVerifier
    from corda_tpu.node.config import NodeConfig, RpcUserConfig
    from corda_tpu.node.fabric import FabricEndpoint, PeerAddress
    from corda_tpu.node.node import Node
    from corda_tpu.node.verifier import VerifierWorker
    from corda_tpu.utils.health import canary_transaction

    node = Node(
        NodeConfig(
            name="TxNode", base_dir=str(tmp_path / "n"),
            notary="batching", notary_shards=2,
            notary_intent_wal=True, txstory_index=True,
            verifier_type="out_of_process",
            verifier_backend="cpu", use_tls=False, web_port=0,
            rpc_users=(RpcUserConfig("ops", "pw", ("ALL",)),),
        )
    ).start()
    wep = None
    try:
        assert node.txstory is not None
        node_port = node.messaging.listen_port
        # a real out-of-process worker attaches over TCP: the pool's
        # dispatch/answer events land in the SAME tx stories
        wep = FabricEndpoint(
            "tx-worker",
            schemes.generate_keypair(seed=77),
            NodeDatabase(str(tmp_path / "w.db")),
            resolve=lambda peer: (
                PeerAddress("127.0.0.1", node_port, None)
                if peer == "TxNode" else None
            ),
        )
        wep.start()
        worker = VerifierWorker(
            wep, "TxNode", batch_verifier=CpuBatchVerifier(),
        )

        def drive(until, timeout=20.0):
            t_end = time.monotonic() + timeout
            while time.monotonic() < t_end:
                node.pump(timeout=0.02)
                wep.pump(block=False)
                worker.drain()
                if until():
                    return True
            return False

        svc = node.services.notary_service
        pool = node.verifier_service
        assert drive(lambda: pool.worker_count == 1), "worker never attached"

        # six synthetic spends through the REAL intake + flush
        stxs = [
            canary_transaction(
                node.services, svc.identity, node.party.owning_key, i
            )
            for i in range(1, 7)
        ]
        futs = [svc.submit(stx, node.party) for stx in stxs]
        assert drive(lambda: all(f.done for f in futs)), "flush stalled"
        for f in futs:
            assert hasattr(f.result(), "by")
        # one of them additionally round-trips the verifier pool (the
        # per-attempt verify history in the timeline)
        target = stxs[0]
        ltx = node.services.resolve_transaction(target.wtx)
        vfut = pool.verify(ltx, target)
        assert drive(lambda: vfut.done), "pool verify stalled"
        vfut.result()

        base = f"http://127.0.0.1:{node.web.port}"
        tid = str(target.id)
        status, body = _get_json(f"{base}/tx/{tid}")
        assert status == 200 and body["found"]
        names = [e["name"] for e in body["events"]]
        assert len(names) >= 6, names
        for expected in (
            "notary.admit", "wal.journal", "notary.flush",
            "notary.verified", "verify.dispatch", "verify.done",
            "tx.committed",
        ):
            assert expected in names, (expected, names)
        assert body["terminal"] == "committed"
        # ?local=1 — the peer-pull form — carries the same story
        status, local = _get_json(f"{base}/tx/{tid}?local=1")
        assert status == 200 and local["found"]
        assert local["story"]["terminal"] == "committed"

        status, slowest = _get_json(f"{base}/tx/slowest")
        assert status == 200 and slowest["slowest"], slowest
        assert slowest["slowest"][0]["total_micros"] >= 0

        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "Tx_Stage_TotalMicros" in text
        assert "Tx_Stage_VerifyMicros" in text

        # unknown tx -> 404, never a 500
        try:
            urllib.request.urlopen(f"{base}/tx/DEADBEEF", timeout=10)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        node.stop()
        if wep is not None:
            wep.stop()


def test_tx_endpoints_404_when_unwired():
    import urllib.error

    from corda_tpu.client.webserver import NodeWebServer

    web = NodeWebServer(None, pump=lambda: None).start()
    try:
        for path in ("/tx/ABC", "/tx/slowest"):
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{web.port}{path}", timeout=10
                )
                assert False, "expected 404"
            except urllib.error.HTTPError as e:
                assert e.code == 404
    finally:
        web.stop()


# ---------------------------------------------------------------------------
# config plumbing


def test_config_roundtrip_and_validation(tmp_path):
    from corda_tpu.node.config import (
        ConfigError,
        NodeConfig,
        load_config,
        write_config,
    )

    cfg = NodeConfig(
        name="N", base_dir=str(tmp_path), notary="batching",
        txstory_index=True, txstory_stage_slo_micros=250_000,
        use_tls=False,
    )
    path = str(tmp_path / "node.toml")
    write_config(cfg, path)
    back = load_config(path)
    assert back.txstory_enabled
    assert back.txstory_index
    assert back.txstory_stage_slo_micros == 250_000
    with pytest.raises(ConfigError):
        NodeConfig(
            name="N", base_dir=str(tmp_path), txstory_enabled=False,
            txstory_index=True, use_tls=False,
        ).validate()
    with pytest.raises(ConfigError):
        NodeConfig(
            name="N", base_dir=str(tmp_path),
            txstory_stage_slo_micros=-1, use_tls=False,
        ).validate()


# ---------------------------------------------------------------------------
# the fleet chaos acceptance: lifecycle-ledger reconciliation


@pytest.fixture(scope="module")
def chaos_report():
    scn = fl.FleetScenario(
        clients=64, seed=7,
        phases=(fl.Phase("ramp", 6, 24), fl.Phase("steady", 14, 24)),
        mix=fl.TrafficMix(conflict_fraction=0.05),
    )
    sim = fl.FleetSim(
        scn, "batching",
        chaos=(
            fl.kill_verifier(0, at=0.2, revive_at=0.4),
            fl.kill_notary_mid_flush(at=0.5, restart_at=0.8),
        ),
        verifier_pool=2, intent_wal=True, txstory=True,
    )
    return sim.run()


def test_fleet_chaos_passes_lifecycle_reconciliation(chaos_report):
    """THE acceptance arc: verifier kill + notary kill-restart, and
    every admitted transaction still reaches exactly one terminal
    event — replays dedupe as tx.reanswer, sheds carry their reason,
    the checker replays the ledger against the model."""
    verdict = fl.InvariantChecker(chaos_report).check_all(
        expect_conflicts=True
    )
    assert verdict["reconciled"]
    led = verdict["lifecycle_ledger"]
    assert led["closed"] > 0 and led["evicted"] == 0
    # the kill-restart really exercised the replay window
    stories = chaos_report.txstory.stories()
    replayed = [
        s for s in stories
        if any(e["name"] == "wal.replay" for e in s["events"])
    ]
    assert replayed, "kill/restart produced no replayed stories"
    # the verifier kill really exercised redispatch attribution
    redispatched = [
        s for s in stories
        if any(e["name"] == "verify.redispatch" for e in s["events"])
    ]
    assert redispatched, "worker kill produced no redispatch events"
    # answered-but-undeleted intents re-answered as reanswer, never a
    # second terminal (the exactly-once discipline under replay)
    assert led["reanswers"] >= 0
    for s in stories:
        terms = [
            e["name"] for e in s["events"]
            if e["name"] in set(TERMINALS.values())
        ]
        assert len(terms) <= 1, (s["tx_id"], terms)


def test_lifecycle_checker_rejects_doctored_ledger(chaos_report):
    """The reconciliation has teeth: flipping one story's terminal
    against the model fails the check."""
    checker = fl.InvariantChecker(chaos_report)
    signed = next(
        r for r in chaos_report.records if r.outcome == fl.OUT_SIGNED
    )
    story = chaos_report.txstory._done[str(signed.tx_id)]
    original = story.terminal
    story.terminal = "shed"
    try:
        with pytest.raises(AssertionError, match="story closed"):
            checker.check_lifecycle_ledger()
    finally:
        story.terminal = original
    checker.check_lifecycle_ledger()   # restored: green again


def test_lifecycle_checker_requires_stories_for_submissions():
    """A missing story (a seam that stopped emitting) fails the
    reconciliation — the checker demands per-tx coverage, not
    counters."""
    scn = fl.FleetScenario(
        clients=8, seed=3, phases=(fl.Phase("steady", 4, 4),),
    )
    sim = fl.FleetSim(scn, "batching", txstory=True)
    rep = sim.run()
    checker = fl.InvariantChecker(rep)
    checker.check_lifecycle_ledger()
    # surgically drop one story
    tid = str(rep.records[0].tx_id)
    rep.txstory._done.pop(tid, None)
    rep.txstory._open.pop(tid, None)
    with pytest.raises(AssertionError, match="no lifecycle story"):
        checker.check_lifecycle_ledger()


# ---------------------------------------------------------------------------
# two-process TCP: cross-member GET /tx/<id>


def test_two_process_tx_timeline_assembles_across_members(tmp_path):
    """Admitted on A (this process), verified by a worker attached to
    B (a real child OS process over TCP), committed via consensus
    (2-member raft, both members apply): one merged timeline served by
    a real HTTP GET /tx/<id> against A's gateway, with events from
    BOTH processes."""
    from corda_tpu.client.webserver import NodeWebServer
    from corda_tpu.core import serialization as ser
    from corda_tpu.crypto import schemes
    from corda_tpu.node.fabric import FabricEndpoint, PeerAddress
    from corda_tpu.node.raft import LEADER, RaftConfig, RaftNode
    from corda_tpu.node.services import Clock
    from corda_tpu.testing.mock_network import MockNetwork
    from corda_tpu.finance.cash import CashIssueFlow

    # the transaction under test: a real cash issue, shipped to the
    # child as a wire blob so both processes hold the SAME tx
    net = MockNetwork(seed=11)
    mock_notary = net.create_notary()
    alice = net.create_node("Alice")
    stx = alice.run_flow(
        CashIssueFlow(1000, "USD", alice.party, mock_notary.party)
    )
    tid = str(stx.id)
    blob_path = tmp_path / "stx.bin"
    blob_path.write_bytes(ser.encode(stx))

    child_src = """
import sys, time
from corda_tpu.client.webserver import NodeWebServer
from corda_tpu.core import serialization as ser
from corda_tpu.crypto import schemes
from corda_tpu.crypto.batch_verifier import CpuBatchVerifier
from corda_tpu.node.fabric import FabricEndpoint, PeerAddress
from corda_tpu.node.persistence import NodeDatabase
from corda_tpu.node.raft import RaftConfig, RaftNode
from corda_tpu.node.services import Clock
from corda_tpu.node.verifier import (
    OutOfProcessTransactionVerifierService, VerifierWorker,
)
from corda_tpu.testing.mock_network import MockNetwork
from corda_tpu.utils.txstory import TxStory
import corda_tpu.finance.cash  # noqa: F401 - registers the cash codec tags

parent_port, db_path, blob_path = (
    int(sys.argv[1]), sys.argv[2], sys.argv[3]
)
story = TxStory()
ep = FabricEndpoint(
    "B",
    schemes.generate_keypair(seed=99),
    NodeDatabase(db_path),
    resolve=lambda peer: (
        PeerAddress("127.0.0.1", parent_port, None)
        if peer == "A" else None
    ),
)
ep.start()
raft = RaftNode(
    "B", ["A", "B"], ep, lambda cmd: "ok", Clock(), txstory=story,
    # B must never win the election: A is the scripted leader
    config=RaftConfig(
        election_min_micros=30_000_000, election_max_micros=60_000_000,
    ),
)
# the worker attached to B: B's pool service + an in-child worker on
# B's own mock fabric verify THE transaction, stamping per-attempt
# verify history into B's ledger
stx = ser.decode(open(blob_path, "rb").read())
net = MockNetwork(seed=11)
bob = net.create_node("Bob")
bob.services.record_transactions([stx])
ltx = bob.services.resolve_transaction(stx.wtx)
pool = OutOfProcessTransactionVerifierService(bob.messaging)
pool.txstory = story
wep = net.fabric.endpoint("b-worker")
worker = VerifierWorker(wep, "Bob", batch_verifier=CpuBatchVerifier())
net.fabric.run()
fut = pool.verify(ltx, stx)
net.fabric.run()
assert fut.done, "child pool verify never resolved"
web = NodeWebServer(None, pump=lambda: None, txstory=story).start()
print(f"PORTS {ep.listen_port} {web.port}", flush=True)
deadline = time.monotonic() + 60
while time.monotonic() < deadline:
    ep.pump(block=True, timeout=0.05)
    raft.tick()
"""
    db_a = NodeDatabase(str(tmp_path / "a.db"))
    child_ports = {}
    ep_a = FabricEndpoint(
        "A",
        schemes.generate_keypair(seed=98),
        db_a,
        resolve=lambda peer: (
            PeerAddress("127.0.0.1", child_ports["fabric"], None)
            if peer == "B" and "fabric" in child_ports else None
        ),
    )
    ep_a.start()
    story_a = TxStory()
    raft_a = RaftNode(
        "A", ["A", "B"], ep_a, lambda cmd: "ok", Clock(),
        txstory=story_a,
        config=RaftConfig(
            election_min_micros=200_000, election_max_micros=400_000,
        ),
    )
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    child = subprocess.Popen(
        [sys.executable, "-c", child_src,
         str(ep_a.listen_port), str(tmp_path / "b.db"), str(blob_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )
    web_a = None
    try:
        line = child.stdout.readline().strip()
        if not line.startswith("PORTS "):
            err = child.stderr.read()
            raise AssertionError(f"child failed: {line!r} {err}")
        _tag, fabric_port, web_port = line.split()
        child_ports["fabric"] = int(fabric_port)
        child_ports["web"] = int(web_port)

        def drive(until, timeout=30.0):
            t_end = time.monotonic() + timeout
            while time.monotonic() < t_end:
                ep_a.pump(block=True, timeout=0.05)
                raft_a.tick()
                if until():
                    return True
            return False

        assert drive(lambda: raft_a.role == LEADER), "no leader elected"
        # admitted on A: the real watch_future intake seam — the
        # consensus command carries the tx id, so BOTH appliers stamp
        # consensus.commit into their ledgers
        story_a.admit(tid, requester="Alice")
        fut = raft_a.submit(["commit", stx.id.bytes_, []])
        story_a.watch_future(tid, fut)
        assert drive(lambda: fut.done), "command never committed"
        assert fut.result() == "ok"

        ct = ClusterTxStory(
            "A", story_a,
            peers_fn=lambda: {
                "B": f"http://127.0.0.1:{child_ports['web']}"
            },
        )
        web_a = NodeWebServer(
            None, pump=lambda: None, txstory=story_a, cluster_tx=ct,
        ).start()

        def fetch_tree():
            # keep heartbeats flowing so B learns the commit index
            # and applies (stamping ITS consensus.commit)
            drive(lambda: True, timeout=0.2)
            status, body = _get_json(
                f"http://127.0.0.1:{web_a.port}/tx/{tid}", timeout=5
            )
            return body

        tree = None
        for _ in range(60):
            try:
                tree = fetch_tree()
            except Exception:
                continue
            b_events = [
                e for e in tree["events"] if e["node"] == "B"
            ]
            if any(e["name"] == "consensus.commit" for e in b_events):
                break
        assert tree is not None and tree["found"]
        by_node = {}
        for e in tree["events"]:
            by_node.setdefault(e["node"], []).append(e["name"])
        assert set(by_node) == {"A", "B"}, by_node
        # A: admitted + committed; both: consensus.commit; B: the
        # per-attempt verify history from its attached worker
        assert "notary.admit" in by_node["A"]
        assert "tx.committed" in by_node["A"]
        assert "consensus.commit" in by_node["A"]
        assert "consensus.commit" in by_node["B"]
        assert "verify.dispatch" in by_node["B"]
        assert "verify.done" in by_node["B"]
        assert tree["terminal"] == "committed"
        # one merged axis, ordered
        ts = [e["ts_us"] for e in tree["events"] if "ts_us" in e]
        assert ts == sorted(ts)
    finally:
        child.terminate()
        child.wait(timeout=10)
        if web_a is not None:
            web_a.stop()
        raft_a.stop()
        ep_a.stop()
        db_a.close()


# ---------------------------------------------------------------------------
# bench plumbing


def test_bench_quick_txstory_smoke():
    """`bench.py --quick txstory` emits one record: overhead <= 2% of
    the flush wall (required-true `txstory_overhead_ok` riding the
    bench_history gate) and complete stories proven."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_BATCH="48",
               BENCH_ITERS="2")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"),
         "--quick", "txstory"],
        capture_output=True, text=True, timeout=600, cwd=repo, env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "txstory_plane_overhead"
    assert rec["txstory_overhead_ok"] is True
    assert rec["gate_required_true"] == ["txstory_overhead_ok"]
    assert rec["lower_is_better"] is True
    assert rec["events_per_tx"] >= 4
