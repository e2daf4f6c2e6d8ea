"""Cluster-wide tracing: consensus-phase spans, cross-node assembly,
incident forensics bundles (PR 11).

Pins the tentpole arc end to end: (1) a trace context submitted with a
command threads through every Raft/BFT protocol message and every
member stamps per-member phase spans into the SAME trace — with
always-on Raft.Phase.*/Bft.Phase.* timers and quorum-lag gauges on the
registry, and a span-free consensus path when tracing is off; (2)
`ClusterTraces` assembles one causally-linked cross-node tree from
every peer's filtered /traces pull, clock-offset-adjusted; (3) a
firing alert (or failed fleet invariant) snapshots a durable incident
bundle carrying the assembled remote halves, and the fleet's slow-peer
chaos scenario is debuggable from the bundle alone. Plus the
satellites: /traces server-side filtering, health-event log rotation,
the real two-process TCP continuity test, and the bench consensus
smoke.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from corda_tpu.core.contracts import StateRef
from corda_tpu.crypto import schemes
from corda_tpu.crypto.hashes import SecureHash
from corda_tpu.node.services import TestClock
from corda_tpu.testing.mock_network import MockNetwork
from corda_tpu.utils import tracing
from corda_tpu.utils.health import (
    AlertRule,
    HealthEventLog,
    HealthMonitor,
    HealthPolicy,
    IncidentRecorder,
)
from corda_tpu.utils.metrics import MetricRegistry

RAFT_SCHEME = schemes.ECDSA_SECP256R1_SHA256


# ---------------------------------------------------------------------------
# helpers


def make_traced_raft_cluster(n=3, seed=5):
    """(net, members, tracers, registries) with per-member observability."""
    tracers, registries = {}, {}

    def tracer_for(name):
        if name not in tracers:
            tracers[name] = tracing.Tracer(enabled=True)
        return tracers[name]

    net = MockNetwork(seed=seed)
    _party, members = net.create_raft_notary_cluster(
        n,
        scheme_id=RAFT_SCHEME,
        tracer_factory=tracer_for,
        metrics_factory=lambda name: registries.setdefault(
            name, MetricRegistry()
        ),
    )
    net.elect(members)
    return net, members, tracers, registries


def commit_traced(net, member, tracers, tag, trace=None):
    """One distributed commit through `member`'s provider; returns the
    resolved future. `trace` defaults to a fresh root span context on
    the member's tracer."""
    root = None
    if trace is None and tracers:
        root = tracers[member.name].start_trace(
            "notarise.client", tag=tag
        )
        trace = tuple(root.context)
    fut = member.services.notary_service.uniqueness.commit_async(
        [StateRef(SecureHash.sha256(b"coin:%s" % tag.encode()), 0)],
        SecureHash.sha256(b"tx:%s" % tag.encode()),
        member.party,
        trace=trace,
    )
    for _ in range(100):
        net.clock.advance(60_000)
        net.run()
        if fut.done:
            break
    assert fut.done, "distributed commit never resolved"
    # two extra heartbeats: followers learn the commit index and stamp
    # their commit/apply phases
    for _ in range(3):
        net.clock.advance(60_000)
        net.run()
    if root is not None:
        root.end()
    return fut, root


def consensus_spans(tracers, trace_id, prefix="raft."):
    """[(member tracer name, span name, member attr)] for one trace."""
    out = []
    for name, t in tracers.items():
        for e in t.export(trace_id=trace_id)["traceEvents"]:
            if e["ph"] == "X" and e["name"].startswith(prefix):
                out.append((name, e["name"], e["args"].get("member")))
    return out


# ---------------------------------------------------------------------------
# tentpole 1: consensus-phase spans + timers/gauges


def test_raft_phase_spans_join_client_trace_across_members():
    """A traced command submitted on a FOLLOWER stamps per-member phase
    spans into the client's trace on >= 2 members: propose on the
    origin, quorum/commit/apply on the leader, append/commit/apply on
    followers — every span carrying member= and at= attributes."""
    from corda_tpu.node.raft import LEADER

    net, members, tracers, registries = make_traced_raft_cluster()
    leader = next(m for m in members if m.raft.role == LEADER)
    origin = next(m for m in members if m is not leader)
    fut, root = commit_traced(net, origin, tracers, "follower-submit")
    assert fut.result() is None

    spans = consensus_spans(tracers, root.trace_id)
    phases = {name for _, name, _ in spans}
    assert {"raft.propose", "raft.append", "raft.quorum",
            "raft.commit", "raft.apply"} <= phases
    # spans live on the member that did the work, stamped member=self
    assert all(owner == member for owner, _, member in spans)
    assert len({member for _, _, member in spans}) >= 2
    # propose on the origin, quorum only on the leader
    assert (origin.name, "raft.propose", origin.name) in spans
    assert all(
        member == leader.name
        for _, name, member in spans if name == "raft.quorum"
    )
    # at= rides every phase span (the simulated-time ordering key)
    for name, t in tracers.items():
        for e in t.export(trace_id=root.trace_id)["traceEvents"]:
            if e["ph"] == "X" and e["name"].startswith("raft."):
                assert isinstance(e["args"]["at"], int)


def test_raft_append_frames_carry_entry_contexts_and_one_send_stamp():
    """A traced entry ships in AppendEntries as a bare (trace_id,
    span_id) pair; the frame's own header is the first traced entry's
    context plus the sender's send stamp (the clock-offset evidence).
    A malformed header binds nothing."""
    from corda_tpu.core import serialization as ser
    from corda_tpu.node.raft import LEADER, AppendEntries

    net, members, tracers, _ = make_traced_raft_cluster(seed=7)
    leader = next(m for m in members if m.raft.role == LEADER)
    sent = []
    send = leader.raft._send

    def spy(peer, message, trace=None):
        sent.append((message, trace))
        send(peer, message, trace=trace)

    leader.raft._send = spy
    fut, root = commit_traced(net, leader, tracers, "frame-shape")
    assert fut.result() is None
    ctx = (root.trace_id, root.span_id)
    traced = [
        (m, hdr) for m, hdr in sent
        if isinstance(m, AppendEntries) and any(len(e) > 2 for e in m.entries)
    ]
    assert traced, "no AppendEntries frame carried the traced entry"
    for m, hdr in traced:
        heads = [tuple(e[2]) for e in m.entries if len(e) > 2]
        assert heads == [ctx]
        assert hdr[:2] == ctx and len(hdr) == 3 and isinstance(hdr[2], int)
        # the pair survives the codec as the follower decodes it
        entry = ser.decode(ser.encode(m)).entries[-1]
        assert tuple(entry[2]) == ctx
    leader.raft._bind_trace(10_000, "garbage")
    leader.raft._bind_trace(10_001, None)
    assert 10_000 not in leader.raft._entry_trace
    assert 10_001 not in leader.raft._entry_trace


def test_raft_phase_timers_and_lag_gauges_always_on():
    """Raft.Phase.* timers count phases with tracing OFF too, and the
    quorum-lag gauges render on the exposition."""
    net, members, tracers, registries = make_traced_raft_cluster(seed=9)
    for t in tracers.values():
        t.enabled = False
    fut, _ = commit_traced(net, members[0], {}, "untraced")
    assert fut.result() is None
    counted = 0
    for name, reg in registries.items():
        timer = reg.get("Raft.Phase.Apply")
        assert timer is not None
        counted += timer.count
        text = reg.to_prometheus()
        assert "Raft_QuorumLagEntries" in text
        assert "Raft_ApplyLagEntries" in text
    # every member applied the entry (plus election noops)
    assert counted >= len(members)


def test_raft_tracing_disabled_keeps_consensus_span_free():
    net, members, tracers, registries = make_traced_raft_cluster(seed=13)
    # disable AFTER the (traced) election; from here the consensus
    # path must record nothing, even for a command carrying a context
    for t in tracers.values():
        t.enabled = False
    baseline = {n: t.recorder.recorded for n, t in tracers.items()}
    root = tracing.Tracer(enabled=True).start_trace("notarise.client")
    fut, _ = commit_traced(
        net, members[0], {}, "disabled", trace=tuple(root.context)
    )
    assert fut.result() is None
    for n, t in tracers.items():
        assert t.recorder.recorded == baseline[n]


def test_bft_phase_spans_join_client_trace_across_replicas():
    tracers = {}

    def tracer_for(name):
        if name not in tracers:
            tracers[name] = tracing.Tracer(enabled=True)
        return tracers[name]

    registries = {}
    net = MockNetwork(seed=31)
    _party, members = net.create_bft_notary_cluster(
        4,
        scheme_id=RAFT_SCHEME,
        tracer_factory=tracer_for,
        metrics_factory=lambda name: registries.setdefault(
            name, MetricRegistry()
        ),
    )
    origin = members[1]
    root = tracer_for(origin.name).start_trace("notarise.client")
    fut = origin.bft.submit(
        ["notarise", b"not-a-real-tearoff"], trace=tuple(root.context)
    )
    for _ in range(60):
        net.clock.advance(60_000)
        net.run()
        if fut.done:
            break
    assert fut.done
    root.end()
    spans = consensus_spans(tracers, root.trace_id, prefix="bft.")
    phases = {name for _, name, _ in spans}
    assert {"bft.pre_prepare", "bft.prepare", "bft.commit",
            "bft.reply"} <= phases
    assert len({member for _, _, member in spans}) >= 2
    for reg in registries.values():
        assert reg.get("Bft.Phase.PrePrepare") is not None
        assert "Bft_View" in reg.to_prometheus()


def test_notary_flow_client_trace_threads_through_consensus():
    """The production path end to end in-process: NotaryFlow opens the
    client root span, the session messages carry its context to the
    cluster member's service flow, and the Raft commit stamps
    consensus phase spans into the SAME trace — one connected tree
    from flow start to replicated apply."""
    from corda_tpu.finance.cash import CashIssueFlow, CashPaymentFlow

    shared = tracing.Tracer(
        enabled=True,
        recorder=tracing.FlightRecorder(keep_recent=512, keep_slowest=16),
    )
    tracing.set_tracer(shared)
    try:
        net = MockNetwork(seed=41)
        notary_party, members = net.create_raft_notary_cluster(
            3, tracer_factory=lambda name: shared,
        )
        alice = net.create_node("Alice")
        bob = net.create_node("Bob")
        net.elect(members)

        def settle(fn, rounds=400):
            for _ in range(rounds):
                net.run()
                if fn():
                    return
                net.clock.advance(20_000)
            raise AssertionError("condition not reached")

        issue = alice.start_flow(
            CashIssueFlow(500, "EUR", alice.party, notary_party)
        )
        settle(lambda: issue.done)
        issue.result_or_throw()
        pay = alice.start_flow(CashPaymentFlow(200, "EUR", bob.party))
        settle(lambda: pay.done)
        pay.result_or_throw()

        by_id: dict = {}
        for t in shared.recorder.traces():
            by_id.setdefault(t.trace_id, set()).update(
                s.name for s in t.spans
            )
        connected = [
            names for names in by_id.values()
            if "notarise.client" in names
            and any(n.startswith("raft.") for n in names)
        ]
        assert connected, sorted(by_id.values(), key=len)[-3:]
        # the tree reaches from the client span through the replicated
        # commit's full phase ladder
        assert any(
            {"raft.propose", "raft.quorum", "raft.commit",
             "raft.apply"} <= names
            for names in connected
        )
    finally:
        tracing.set_tracer(None)


# ---------------------------------------------------------------------------
# satellite: /traces server-side filtering + clock sync


def test_traces_export_filters_server_side():
    t = tracing.Tracer(enabled=True)
    ids = []
    for k in range(6):
        span = t.start_trace(f"alpha.{'slow' if k % 2 else 'fast'}")
        child = t.start_span("alpha.child", span)
        child.end()
        span.end()
        ids.append(span.trace_id)
    full = t.export()
    assert full["tracesReturned"] == 6
    one = t.export(trace_id=ids[2])
    assert one["tracesReturned"] == 1
    assert all(
        e["args"]["trace_id"] == f"{ids[2]:#x}"
        for e in one["traceEvents"] if e["ph"] == "X"
    )
    named = t.export(name="alpha.slow")
    assert named["tracesReturned"] == 3
    assert t.export(name="nope")["tracesReturned"] == 0
    assert t.export(limit=2)["tracesReturned"] == 2
    assert "clockSync" in full
    # parse_trace_id round-trips both printed forms
    assert tracing.parse_trace_id(f"{ids[0]:#x}") == ids[0]
    assert tracing.parse_trace_id(str(ids[0])) == ids[0]
    assert tracing.parse_trace_id("garbage") is None


def test_clock_sync_offsets_pair_into_honest_midpoints():
    sync = tracing.ClockSync()
    # frames from peer P observed locally: skew = offset + delay
    sync.observe("P", sent_us=1000, recv_us=1250)   # delay 50, off 200
    sync.observe("P", sent_us=2000, recv_us=2400)   # slower frame
    assert sync.min_skew("P") == 250
    assert sync.export()["P"]["count"] == 2
    # header form: only 3-element headers observe
    sync.observe_header("Q", (1, 2))
    assert sync.min_skew("Q") is None
    sync.observe_header("Q", (1, 2, 500))
    assert sync.min_skew("Q") is not None

    # paired midpoint: local ClockSync fwd + the peer's exported bwd
    local = tracing.Tracer(enabled=True)
    local.clock_sync.observe("B", sent_us=0, recv_us=250)    # fwd 250
    ct = tracing.ClusterTraces(
        "A", local, peers_fn=lambda: {}, fetch=lambda url: {}
    )
    payload = {"clockSync": {"A": {"min_skew_us": -150, "count": 3}}}
    off, quality = ct._offset_for("B", payload)
    assert (off, quality) == ((250 - (-150)) // 2, "paired")
    off1, q1 = ct._offset_for("B", {})
    assert (off1, q1) == (250, "one_way")
    off2, q2 = ct._offset_for("C", {})
    assert (off2, q2) == (0, "none")


# ---------------------------------------------------------------------------
# tentpole 2: cross-node assembly


def test_cluster_traces_assembles_cross_member_tree():
    net, members, tracers, _regs = make_traced_raft_cluster(seed=17)
    origin = members[1]
    fut, root = commit_traced(net, origin, tracers, "assemble-me")
    assert fut.result() is None

    home = members[0].name
    ct = tracing.ClusterTraces(
        home,
        tracers[home],
        peers_fn=lambda: {m.name: f"sim://{m.name}" for m in members},
        fetch=lambda url: tracers[
            url.split("//")[1].split("/")[0]
        ].export(
            trace_id=tracing.parse_trace_id(
                url.split("trace_id=")[1].split("&")[0]
            )
        ),
    )
    tree = ct.assemble(root.trace_id)
    assert tree["found"]
    assert len(tree["members"]) >= 2
    cons = [s for s in tree["spans"] if s["name"].startswith("raft.")]
    assert len(cons) >= 4
    # merged spans sort by (offset-adjusted) timestamp and carry
    # parent links back to the client root
    ts = [s["ts_us"] for s in tree["spans"]]
    assert ts == sorted(ts)
    have = {s["span_id"] for s in tree["spans"]}
    root_spans = [
        s for s in tree["spans"] if s["parent_span_id"] not in have
    ]
    assert any(s["name"] == "notarise.client" for s in root_spans)
    # per-member phase summary: every consensus member has a row with
    # phase totals and a node-clock completion stamp
    for member in tree["members"]:
        if any(s["node"] == member for s in cons):
            row = tree["phase_summary"][member]
            assert row["busy_us"] > 0
            assert row["last_at_micros"] is not None

    # an unreachable peer degrades to an errors entry, never a failure
    def flaky_fetch(url):
        if members[2].name in url:
            raise ConnectionError("down")
        return tracers[url.split("//")[1].split("/")[0]].export(
            trace_id=root.trace_id
        )

    ct2 = tracing.ClusterTraces(
        home, tracers[home],
        peers_fn=lambda: {m.name: f"sim://{m.name}" for m in members},
        fetch=flaky_fetch,
    )
    partial = ct2.assemble(root.trace_id)
    assert partial["found"]
    assert members[2].name in partial["errors"]


# ---------------------------------------------------------------------------
# tentpole 3: incident bundles


def test_incident_recorder_bundles_and_bounded_retention(tmp_path):
    clock = TestClock()
    rec = IncidentRecorder(
        str(tmp_path / "incidents"), clock_fn=clock.now_micros, keep=3
    )
    ids = []
    for k in range(5):
        clock.advance(1_000)
        ids.append(rec.record(
            "alert", f"rule.{k}", detail={"k": k}, severity="warning",
        ))
    listed = rec.list()
    assert len(listed) == 3                      # retention pruned to keep
    assert listed[0]["id"] == ids[-1]            # newest first
    bundle = rec.load(ids[-1])
    assert bundle["alert"]["name"] == "rule.4"
    assert rec.load(ids[0]) is None              # pruned
    assert rec.load("../../etc/passwd") is None  # traversal refused


def test_firing_alert_snapshots_bundle_with_assembled_trace(tmp_path):
    """The full tentpole-3 arc in miniature: an alert whose evidence
    cites a traced distributed commit fires, and the bundle on disk
    carries the ASSEMBLED cross-node trace — remote halves included —
    plus the metrics snapshot and event tail."""
    net, members, tracers, _regs = make_traced_raft_cluster(seed=23)
    fut, root = commit_traced(net, members[1], tracers, "evidence")
    assert fut.result() is None
    home = members[0].name
    ct = tracing.ClusterTraces(
        home, tracers[home],
        peers_fn=lambda: {m.name: f"sim://{m.name}" for m in members},
        fetch=lambda url: tracers[
            url.split("//")[1].split("/")[0]
        ].export(
            trace_id=tracing.parse_trace_id(
                url.split("trace_id=")[1].split("&")[0]
            )
        ),
    )
    clock = TestClock()
    mon = HealthMonitor(
        clock=clock, tracer=tracers[members[1].name],
        policy=HealthPolicy(alert_for_micros=0),
    )
    rec = IncidentRecorder(
        str(tmp_path / "incidents"), clock_fn=clock.now_micros,
        assemble=ct.assemble,
    )
    mon.attach_incidents(rec, node=home)
    mon.add_rule(AlertRule(
        "consensus.lag", lambda now: (True, {"lag": 9}),
        trace_filter="raft",
    ))
    mon.tick()
    alerts = mon.snapshot()["alerts"]
    assert alerts["consensus.lag"]["state"] == "firing"
    iid = alerts["consensus.lag"]["evidence"]["incident_id"]
    bundle = rec.load(iid)
    assert bundle is not None and bundle["node"] == home
    assembled = [t for t in bundle["traces"] if t.get("assembled")]
    assert assembled, "bundle carries no assembled cross-node trace"
    cons = [
        s for s in assembled[0]["spans"]
        if s["name"].startswith("raft.")
    ]
    assert len(cons) >= 4
    assert len({s["attributes"]["member"] for s in cons}) >= 2
    assert "metrics" in bundle["evidence"]
    assert isinstance(bundle["events"], list)


def test_health_event_log_rotates_on_disk(tmp_path):
    path = str(tmp_path / "health_events.jsonl")
    log = HealthEventLog(capacity=16, path=path, max_bytes=4096)
    for k in range(400):
        log.append({"event": "tick", "k": k, "pad": "x" * 40})
    assert log.rotations >= 1
    assert os.path.getsize(path) <= 4096 + 200   # current file bounded
    assert os.path.exists(path + ".1")           # one rotation kept
    assert os.path.getsize(path + ".1") <= 4096 + 200
    # tail + lifetime counter unaffected by rotation
    assert log.appended == 400
    assert log.tail(4)[-1]["k"] == 399


# ---------------------------------------------------------------------------
# the acceptance scenario: slow raft peer -> debuggable bundle


@pytest.fixture(scope="module")
def slow_peer_report(tmp_path_factory):
    from corda_tpu.node.raft import LEADER
    from corda_tpu.testing.fleet import (
        ChaosPlane, FleetScenario, FleetSim, Phase, TrafficMix, slow_peer,
    )

    tmp = tmp_path_factory.mktemp("incidents")
    scenario = FleetScenario(
        clients=64, seed=7,
        phases=(Phase("steady", 24, 12),),
        mix=TrafficMix(deadline_micros=10_000_000, conflict_fraction=0.1),
        drain_rounds=120,
    )
    sim = FleetSim(
        scenario, flavour="raft",
        lag_alert_threshold=6,
        tracing=True, incident_dir=str(tmp),
    )
    # the straggler is a FOLLOWER (the canonical slow-replica incident;
    # a slow LEADER stalls everything and is its own, louder page)
    leader_idx = next(
        i for i, m in enumerate(sim.members) if m.raft.role == LEADER
    )
    victim_idx = (leader_idx + 1) % len(sim.members)
    sim.chaos = ChaosPlane(
        (slow_peer(victim_idx, 0.3, 0.7, delay_micros=200_000),)
    )
    report = sim.run()
    report.victim = sim.members[victim_idx].name
    return report


def test_slow_raft_peer_produces_forensic_incident_bundle(slow_peer_report):
    """THE acceptance criterion: a fleet chaos scenario (slow Raft peer
    mid-load) produces a firing alert whose incident bundle contains a
    fully assembled cross-node trace with >= 4 consensus phase spans
    from >= 2 members — and the slow member is identifiable from the
    phase timings in the bundle alone."""
    report = slow_peer_report
    victim = report.victim
    rows = report.incidents.list()
    lag = [r for r in rows if r["alert"] == "consensus.lag"]
    assert lag, f"no consensus.lag bundle among {rows}"
    assert any(r["node"] == victim for r in lag)   # fired on the victim
    bundle = report.incidents.load(
        next(r for r in lag if r["node"] == victim)["id"]
    )
    assembled = [t for t in bundle["traces"] if t.get("assembled")]
    assert assembled, "bundle has no assembled cross-node trace"
    best = max(assembled, key=lambda t: len(t["members"]))
    cons = [s for s in best["spans"] if s["name"].startswith("raft.")]
    members = {s["attributes"]["member"] for s in cons}
    assert len(cons) >= 4
    assert len(members) >= 2
    # slow-member identification from the bundle alone: among the
    # FOLLOWER rows (no raft.quorum — that marks the leader), the
    # straggler is the one whose node-clock completion stamp lags
    # (its commits land a slow-link delay late); with only one
    # follower row visible, the victim is the dominant busy row
    nominated = set()
    for tree in assembled:
        rows_ = tree["phase_summary"]
        followers = {
            m: r for m, r in rows_.items()
            if "raft.quorum" not in r["phases"]
            and r["last_at_micros"] is not None
        }
        if len(followers) >= 2:
            nominated.add(
                max(followers, key=lambda m: followers[m]["last_at_micros"])
            )
        elif rows_:
            nominated.add(
                max(rows_, key=lambda m: rows_[m]["busy_us"])
            )
    assert victim in nominated, (nominated, victim)
    # the bundle carries the injected-reality log next to the story
    assert any(e.get("kind") == "slow" for e in bundle["chaos"])


def test_slow_peer_scenario_reconciles_and_traces_stay_neutral(
    slow_peer_report,
):
    from corda_tpu.testing.fleet import InvariantChecker

    verdict = InvariantChecker(slow_peer_report).check_all()
    assert verdict["reconciled"]
    # every traced request recorded its root trace id
    traced = [r for r in slow_peer_report.records if r.trace_id]
    assert len(traced) == len(slow_peer_report.records)


def test_reconciliation_failure_cites_incident_id(slow_peer_report):
    """A failed invariant mints a reconciliation bundle and the raised
    AssertionError cites its id — forensics at the moment of failure."""
    from corda_tpu.testing.fleet import InvariantChecker, OUT_LOST

    report = slow_peer_report
    # doctor >5% of the records into silent losses (the bound the
    # checker holds non-WAL runs to)
    n = max(1, len(report.records) // 10)
    saved = [(r, r.outcome) for r in report.records[:n]]
    try:
        for r, _ in saved:
            r.outcome = OUT_LOST
        before = report.incidents.recorded
        with pytest.raises(AssertionError, match=r"\[incident inc-"):
            InvariantChecker(report).check_all()
        assert report.incidents.recorded == before + 1
        rows = report.incidents.list()
        assert any(r["alert"] == "fleet.invariant_failed" for r in rows)
    finally:
        for r, outcome in saved:   # restore the module-scoped report
            r.outcome = outcome


# ---------------------------------------------------------------------------
# satellite: real two-process TCP continuity via GET /cluster/trace/<id>


def test_two_process_trace_assembles_remote_consensus_spans(tmp_path):
    """A trace born on the client node comes back ASSEMBLED: member A
    (this process) and member B (a real child OS process over the TCP
    fabric) form a 2-member Raft cluster; a traced command committed
    through A gathers B's consensus phase spans via a real HTTP
    GET /cluster/trace/<id> against A's gateway, which pulls B's
    filtered /traces over HTTP."""
    import urllib.request

    from corda_tpu.client.webserver import NodeWebServer
    from corda_tpu.node.fabric import FabricEndpoint, PeerAddress
    from corda_tpu.node.persistence import NodeDatabase
    from corda_tpu.node.raft import LEADER, RaftConfig, RaftNode
    from corda_tpu.node.services import Clock

    child_src = """
import sys, time
from corda_tpu.client.webserver import NodeWebServer
from corda_tpu.crypto import schemes
from corda_tpu.node.fabric import FabricEndpoint, PeerAddress
from corda_tpu.node.persistence import NodeDatabase
from corda_tpu.node.raft import RaftConfig, RaftNode
from corda_tpu.node.services import Clock
from corda_tpu.utils import tracing

parent_port, db_path = int(sys.argv[1]), sys.argv[2]
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
tracer = tracing.Tracer(enabled=True)
raft = RaftNode(
    "B", ["A", "B"], ep, lambda cmd: "ok", Clock(), tracer=tracer,
    # B must never win the election: A is the scripted leader
    config=RaftConfig(
        election_min_micros=30_000_000, election_max_micros=60_000_000,
    ),
)
web = NodeWebServer(None, pump=lambda: None, tracer=tracer).start()
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
    tracer_a = tracing.Tracer(enabled=True)
    raft_a = RaftNode(
        "A", ["A", "B"], ep_a, lambda cmd: "ok", Clock(),
        tracer=tracer_a,
        config=RaftConfig(
            election_min_micros=200_000, election_max_micros=400_000,
        ),
    )
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    child = subprocess.Popen(
        [sys.executable, "-c", child_src,
         str(ep_a.listen_port), str(tmp_path / "b.db")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )
    web_a = None
    try:
        line = child.stdout.readline().strip()
        assert line.startswith("PORTS "), line
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

        # A wins the 2-member election over real TCP (B grants)
        assert drive(lambda: raft_a.role == LEADER), "no leader elected"
        # the trace is born on the client (this process) and threads
        # through the replicated commit
        root = tracer_a.start_trace("notarise.client")
        fut = raft_a.submit(["commit-me"], trace=tuple(root.context))
        assert drive(lambda: fut.done), "command never committed"
        assert fut.result() == "ok"
        root.end()

        # assembly over REAL HTTP: A's gateway serves the merged tree,
        # pulling B's filtered /traces across processes
        ct = tracing.ClusterTraces(
            "A", tracer_a,
            peers_fn=lambda: {
                "B": f"http://127.0.0.1:{child_ports['web']}"
            },
        )
        web_a = NodeWebServer(
            None, pump=lambda: None, tracer=tracer_a, cluster_traces=ct,
        ).start()

        def fetch_tree():
            # keep heartbeats flowing so B learns the commit index and
            # stamps its commit/apply phases
            drive(lambda: True, timeout=0.2)
            with urllib.request.urlopen(
                f"http://127.0.0.1:{web_a.port}/cluster/trace/"
                f"{root.trace_id:#x}",
                timeout=5,
            ) as resp:
                return json.loads(resp.read())

        tree = None
        for _ in range(60):
            try:
                tree = fetch_tree()
            except Exception:
                continue
            b_spans = [
                s for s in tree["spans"]
                if s["node"] == "B" and s["name"].startswith("raft.")
            ]
            if len(b_spans) >= 2:
                break
        assert tree is not None and tree["found"]
        cons = [
            s for s in tree["spans"] if s["name"].startswith("raft.")
        ]
        members = {s["attributes"]["member"] for s in cons}
        assert len(cons) >= 4, [s["name"] for s in tree["spans"]]
        assert members == {"A", "B"}, members
        # the remote member's spans were offset-adjusted with real
        # clock evidence (both directions observed over the fabric)
        assert tree["offsets_micros"]["B"]["quality"] in (
            "paired", "one_way"
        )
        assert any(s["name"] == "notarise.client" for s in tree["spans"])
    finally:
        child.terminate()
        child.wait(timeout=10)
        if web_a is not None:
            web_a.stop()
        raft_a.stop()
        ep_a.stop()
        db_a.close()


def test_incidents_endpoints_over_http(tmp_path):
    """GET /incidents lists bundles and /incidents/<id> serves one in
    full; unwired gateways 404 cleanly."""
    import urllib.request
    from urllib.error import HTTPError

    from corda_tpu.client.webserver import NodeWebServer

    clock = TestClock()
    rec = IncidentRecorder(
        str(tmp_path / "incidents"), clock_fn=clock.now_micros
    )
    iid = rec.record("alert", "doc.rule", detail={"k": 1})
    web = NodeWebServer(None, pump=lambda: None, incidents=rec).start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{web.port}/incidents", timeout=5
        ) as resp:
            listing = json.loads(resp.read())
        assert listing["recorded"] == 1
        assert listing["incidents"][0]["id"] == iid
        with urllib.request.urlopen(
            f"http://127.0.0.1:{web.port}/incidents/{iid}", timeout=5
        ) as resp:
            bundle = json.loads(resp.read())
        assert bundle["alert"]["name"] == "doc.rule"
        with pytest.raises(HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{web.port}/incidents/nope", timeout=5
            )
        assert err.value.code == 404
    finally:
        web.stop()
    bare = NodeWebServer(None, pump=lambda: None).start()
    try:
        with pytest.raises(HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{bare.port}/incidents", timeout=5
            )
        assert err.value.code == 404
    finally:
        bare.stop()


# ---------------------------------------------------------------------------
# satellite: bench consensus smoke


def test_bench_quick_consensus_smoke():
    """`python bench.py --quick consensus` emits a well-formed record:
    all five raft phases stamped, >= 2 members represented, measured
    tracing overhead under the gate."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", BENCH_BATCH="16", BENCH_ITERS="2",
        # the gate's DEFAULT is 5% (the bench-run contract); a loaded
        # tier-1 box adds one-sided scheduler noise to the A/B minima,
        # so the smoke widens the ceiling (the quick-trace precedent)
        BENCH_CONSENSUS_OVERHEAD_MAX="0.5",
    )
    out = subprocess.run(
        [sys.executable, os.path.join(repo_root, "bench.py"),
         "--quick", "consensus"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "consensus"
    assert rec["value"] > 0
    assert all(n > 0 for n in rec["phase_span_counts"].values())
    assert len(rec["members_with_spans"]) >= 2
    assert rec["overhead_ok"] is True
    assert rec["gate_required_true"] == ["overhead_ok"]
    assert rec["tracing_overhead"] <= 0.5
    assert set(rec["phases_seconds"]) == {
        "propose", "append", "quorum", "commit", "apply",
    }
