"""Sharded commit plane (round 6): correctness gates.

The tentpole claim is that partitioning the uniqueness namespace by
state-ref prefix into per-shard flush pipelines changes THROUGHPUT and
nothing else: accept/reject decisions — including cross-shard
double-spends taking the two-phase reserve→commit — must stay
bit-exact against a serial single-shard reference replaying the same
decisions in answer order. These tests pin that, plus the routing
determinism the partitioned namespace rests on, reservation release on
abort, the boot-time partition migrations, the per-shard QoS lanes and
the per-shard health heartbeats flipping /healthz when one shard
wedges while its siblings keep serving.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from corda_tpu.core.contracts import Amount, Issued, StateRef
from corda_tpu.core.identity import PartyAndReference
from corda_tpu.core.transactions import TransactionBuilder
from corda_tpu.crypto.batch_verifier import CpuBatchVerifier
from corda_tpu.crypto.hashes import SecureHash
from corda_tpu.finance.cash import (
    CASH_CONTRACT,
    CashIssue,
    CashMove,
    CashState,
)
from corda_tpu.flows.api import FlowFuture
from corda_tpu.node.notary import (
    BatchingNotaryService,
    InMemoryUniquenessProvider,
    ShardedUniquenessProvider,
    UniquenessConflict,
    _PendingNotarisation,
    shard_of_ref,
    shard_of_tx,
)
from corda_tpu.testing.mock_network import MockNetwork
from corda_tpu.utils import health as hlib


def _party():
    from corda_tpu.core.identity import Party
    from corda_tpu.crypto import schemes

    kp = schemes.generate_keypair(seed=11)
    return Party("Requester", kp.public)


def _refs(n, salt=b""):
    return [
        StateRef(SecureHash.sha256(salt + bytes([i, i >> 8])), 0)
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# routing determinism


def test_shard_routing_is_deterministic_and_restart_stable():
    """shard_of_ref is a pure function of the ref bytes: recomputing in
    a fresh interpreter (a 'restart') must route identically, and
    sibling outputs of one transaction share a shard. Pinned values
    guard against anyone 'improving' the hash and silently
    re-partitioning a live namespace."""
    refs = _refs(64)
    first = [shard_of_ref(r, 8) for r in refs]
    again = [shard_of_ref(r, 8) for r in refs]
    assert first == again
    # all indices of one producing tx land together (prefix routing)
    h = SecureHash.sha256(b"tx")
    assert len({shard_of_ref(StateRef(h, i), 8) for i in range(16)}) == 1
    # every shard is reachable (the prefix really spreads)
    assert len(set(first)) == 8
    # cross-process stability: the same computation in a fresh python
    out = subprocess.run(
        [sys.executable, "-c", (
            "from corda_tpu.node.notary import shard_of_ref\n"
            "from corda_tpu.core.contracts import StateRef\n"
            "from corda_tpu.crypto.hashes import SecureHash\n"
            "refs=[StateRef(SecureHash.sha256(bytes([i,i>>8])),0)"
            " for i in range(64)]\n"
            "print([shard_of_ref(r,8) for r in refs])"
        )],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-1000:]
    assert json.loads(out.stdout.strip().replace("'", '"')) == first


# ---------------------------------------------------------------------------
# provider semantics: two-phase reserve→commit


def test_reserve_commit_abort_releases_reservations():
    party = _party()
    p = ShardedUniquenessProvider(4)
    refs = _refs(8, b"res")
    tx1 = SecureHash.sha256(b"tx1")
    tx2 = SecureHash.sha256(b"tx2")
    res = p.reserve(refs[:4], tx1, party)
    assert len(res.shards) >= 1
    res.abort()
    # released: a different transaction may now take every ref
    p.commit(refs[:4], tx2, party)
    # and the aborted transaction now conflicts (first-wins held)
    with pytest.raises(UniquenessConflict):
        p.commit(refs[:4], tx1, party)
    # commit path: reserve -> commit flips reservations to rows
    res2 = p.reserve(refs[4:], tx1, party)
    res2.commit()
    with pytest.raises(UniquenessConflict) as e:
        p.commit(refs[4:6], tx2, party)
    assert set(e.value.conflict) == set(refs[4:6])
    # resolve is exactly-once: a second abort on a committed
    # reservation must not release the committed rows
    res2.abort()
    with pytest.raises(UniquenessConflict):
        p.commit(refs[4:6], tx2, party)


def test_reserve_releases_partial_reservations_on_backend_error():
    """A storage-backend error mid-reserve (the persistent subclass's
    _prior_consumer can raise, e.g. sqlite 'database is locked') must
    release the partitions already reserved — a leaked reservation is
    waited on FOREVER by every later committer of those refs."""
    party = _party()

    class _Flaky(ShardedUniquenessProvider):
        def __init__(self):
            super().__init__(4)
            self.boom = False

        def _prior_consumer(self, shard, ref):
            if self.boom and shard == self.shard_of(ref) and shard >= 2:
                raise RuntimeError("database is locked")
            return super()._prior_consumer(shard, ref)

    p = _Flaky()
    refs = _refs(64, b"leak")
    by_shard = {}
    for r in refs:
        by_shard.setdefault(p.shard_of(r), []).append(r)
    spread = [r for k in sorted(by_shard) for r in by_shard[k][:3]]
    assert {p.shard_of(r) for r in spread} & {0, 1}
    assert {p.shard_of(r) for r in spread} & {2, 3}
    tx1 = SecureHash.sha256(b"t1")
    tx2 = SecureHash.sha256(b"t2")
    p.boom = True
    with pytest.raises(RuntimeError):
        p.reserve(spread, tx1, party)
    for part in p._parts:
        assert not part.reserved, "partial reservation leaked"
    # and the refs are immediately committable by someone else (no
    # parked waiter, no stale rows)
    p.boom = False
    p.commit(spread, tx2, party)


def test_commit_many_parks_on_foreign_reservation_first_wins():
    """A commit_many batch whose entry spends a ref held by ANOTHER
    transaction's in-flight reservation must wait for that reservation
    to resolve — and lose to it if it commits — rather than deciding
    against un-resolved state. (The batched run may not release its
    partition mid-run, so such an entry truncates the run and takes
    the per-entry two-phase path.)"""
    party = _party()
    p = ShardedUniquenessProvider(2)
    refs = _refs(16, b"park")
    same = [r for r in refs if p.shard_of(r) == 0]
    assert len(same) >= 4
    tx_res = SecureHash.sha256(b"holder")
    tx_a = SecureHash.sha256(b"a")
    tx_b = SecureHash.sha256(b"b")
    res = p.reserve(same[:1], tx_res, party)   # foreign reservation

    out_box = {}

    def run():
        out_box["out"] = p.commit_many([
            ([same[1]], tx_b, party),          # free: commits in-run
            ([same[0]], tx_a, party),          # parked behind res
        ])

    t = threading.Thread(target=run, daemon=True)
    t.start()
    time.sleep(0.2)
    assert t.is_alive(), "commit_many decided against an unresolved reservation"
    res.commit()                               # holder wins same[0]
    t.join(timeout=10)
    assert not t.is_alive()
    out = out_box["out"]
    assert out[0] is None
    assert isinstance(out[1], UniquenessConflict)
    assert out[1].conflict == {same[0]: tx_res}


def test_cross_shard_conflict_reports_full_set_and_writes_nothing():
    """A cross-shard reservation that conflicts on ANY shard aborts
    atomically: no partition keeps a row or a reservation."""
    party = _party()
    p = ShardedUniquenessProvider(4)
    refs = _refs(32, b"x")
    tx1 = SecureHash.sha256(b"a")
    tx2 = SecureHash.sha256(b"b")
    # tx1 takes a few refs spread over shards
    taken = refs[:6]
    p.commit(taken, tx1, party)
    # tx2 wants a superset: some fresh refs + two committed ones
    want = refs[6:12] + [taken[0], taken[3]]
    with pytest.raises(UniquenessConflict) as e:
        p.commit(want, tx2, party)
    assert set(e.value.conflict) == {taken[0], taken[3]}
    assert all(e.value.conflict[r] == tx1 for r in e.value.conflict)
    # nothing from the failed attempt stuck anywhere
    committed = p.committed
    for r in refs[6:12]:
        assert r not in committed
    for part in p._parts:
        assert not part.reserved


# ---------------------------------------------------------------------------
# the bit-exact gate: sharded decisions == serial single-shard replay


def _cash_rig(n, seed=21):
    net = MockNetwork(seed=seed, batch_verifier=CpuBatchVerifier())
    notary = net.create_notary("Notary", batching=True)
    bank = net.create_node("Bank")
    alice = net.create_node("Alice")
    token = Issued(PartyAndReference(bank.party, b"\x01"), "USD")

    issued = []
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
        issued.append(issue)

    def spend(inputs, dest):
        # value-conserving (CashMove checks it); rivals differ by DEST,
        # which changes the tx id without breaking the contract
        sb = TransactionBuilder(notary.party)
        for issue in inputs:
            sb.add_input_state(
                alice.vault.state_and_ref(StateRef(issue.id, 0))
            )
        sb.add_output_state(
            CashState(
                Amount(sum(100 + issued.index(i) for i in inputs), token),
                dest.owning_key,
            ),
            CASH_CONTRACT, notary.party,
        )
        sb.add_command(CashMove(), alice.party.owning_key)
        return alice.services.sign_initial_transaction(sb)

    return net, notary, alice, bank, issued, spend


def _conflict_workload(n_shards=4):
    """Spends + rivals with single- AND cross-shard double-spend
    attempts: for each pair of issues, one honest 2-input spend and a
    rival claiming one of its inputs (the rival is single-shard, the
    honest spend usually cross-shard)."""
    net, notary, alice, bank, issued, spend = _cash_rig(24)
    stxs = []
    for a, b in zip(issued[0::2], issued[1::2]):
        honest = spend([a, b], bank.party)
        rival = spend([b], notary.party)
        stxs.append(honest)
        stxs.append(rival)
    # make sure the workload really exercises cross-shard routing
    multi = [
        s for s in stxs
        if len({shard_of_ref(r, n_shards) for r in s.wtx.inputs}) > 1
    ]
    assert multi, "fixture produced no cross-shard transaction"
    return net, notary, alice, stxs


def _replay_serial(decisions, inputs_of):
    """Replay the provider's decision log through a single-map serial
    reference; returns the accept/reject sequence it produces."""
    ref_provider = InMemoryUniquenessProvider()
    party = _party()
    out = []
    for tx_id, _conflict in decisions:
        try:
            ref_provider.commit(inputs_of[tx_id], tx_id, party)
            out.append((tx_id, None))
        except UniquenessConflict as e:
            out.append((tx_id, dict(e.conflict)))
    return out


@pytest.mark.parametrize("shards", [1, 4])
def test_cross_shard_double_spend_bit_exact_vs_serial_replay(shards):
    """The acceptance gate: run a conflict-heavy workload (single- and
    cross-shard rivals on four shards) through the one-shard plane and
    the four-shard plane, then replay the provider's decision log —
    answer order — through a serial single-shard
    InMemoryUniquenessProvider. Accept/reject AND the conflicting
    consumer must match decision for decision."""
    net, notary, alice, stxs = _conflict_workload(4)
    uniq = ShardedUniquenessProvider(shards, record_decisions=True)
    svc = BatchingNotaryService(
        notary.services, uniq, shards=shards, max_batch=4096,
    )
    try:
        futs = [(stx, svc.submit(stx, alice.party)) for stx in stxs]
        svc.flush()
        assert all(f.done for _, f in futs)
        answers = {stx.id: f.result() for stx, f in futs}
    finally:
        svc.stop()

    inputs_of = {stx.id: list(stx.wtx.inputs) for stx in stxs}
    replayed = _replay_serial(uniq.decisions, inputs_of)
    assert len(replayed) == len(uniq.decisions) == len(stxs)
    for (tx_id, got), (tx_id2, want) in zip(uniq.decisions, replayed):
        assert tx_id == tx_id2
        if want is None:
            assert got is None, f"{tx_id}: sharded rejected, serial accepts"
        else:
            assert got is not None, f"{tx_id}: sharded accepted, serial rejects"
            assert dict(got) == want, f"{tx_id}: conflict sets differ"
    # the futures agree with the log: every accepted tx got a
    # signature, every rejected one a conflict error naming its winner
    for tx_id, conflict in uniq.decisions:
        if conflict is None:
            assert hasattr(answers[tx_id], "by")
        else:
            err = answers[tx_id]
            assert getattr(err, "kind", None) == "conflict"
    # sanity: the rivals really produced rejections
    assert sum(1 for _, c in uniq.decisions if c is not None) >= 1


def test_exactly_one_winner_per_contested_ref():
    """Double-spend exactness, stated as the ledger invariant: across
    every contested ref (honest cross-shard spend vs its rival),
    EXACTLY one consumer commits — never zero (lost value), never two
    (duplicated value) — whatever order the shards decided in."""
    for n_shards in (1, 2, 4, 8):
        net, notary, alice, stxs = _conflict_workload(4)
        uniq = (
            ShardedUniquenessProvider(n_shards)
            if n_shards > 1 else InMemoryUniquenessProvider()
        )
        svc = BatchingNotaryService(
            notary.services, uniq, shards=n_shards, max_batch=4096,
        )
        try:
            futs = [(stx, svc.submit(stx, alice.party)) for stx in stxs]
            svc.flush()
            consumers: dict = {}
            for stx, f in futs:
                if hasattr(f.result(), "by"):
                    for ref in stx.wtx.inputs:
                        assert ref not in consumers, (
                            f"{n_shards} shards: ref double-committed"
                        )
                        consumers[ref] = stx.id
            assert consumers == dict(uniq.committed)
            # each (honest, rival) pair contests one ref: EXACTLY one
            # of the two signs, whichever order the shards decided in
            # (the loser's other input staying unconsumed is correct —
            # it remains spendable, value is not lost)
            for honest, rival in zip(futs[0::2], futs[1::2]):
                signed = [
                    hasattr(f.result(), "by") for _, f in (honest, rival)
                ]
                assert signed.count(True) == 1, (
                    f"{n_shards} shards: contested pair signed {signed}"
                )
        finally:
            svc.stop()


# ---------------------------------------------------------------------------
# persistent partitions: migration + restart-stable routing


def test_persistent_sharded_migration_and_restart(tmp_path):
    from corda_tpu.node.persistence import (
        NodeDatabase,
        PersistentUniquenessProvider,
        ShardedPersistentUniquenessProvider,
    )

    party = _party()
    path = str(tmp_path / "n.db")
    refs = _refs(12, b"db")
    tx = [SecureHash.sha256(b"db%d" % i) for i in range(6)]

    db = NodeDatabase(path)
    legacy = PersistentUniquenessProvider(db)
    legacy.commit(refs[:4], tx[0], party)
    # first sharded boot migrates the legacy rows into partitions
    p = ShardedPersistentUniquenessProvider(db, 4)
    with pytest.raises(UniquenessConflict):
        p.commit([refs[1], refs[6]], tx[1], party)
    p.commit(refs[4:8], tx[2], party)
    assert p.committed_count == 8
    assert sum(p.partition_depth(k) for k in range(4)) == 8
    db.close()

    # restart with a DIFFERENT shard count: rows re-route, nothing lost
    db2 = NodeDatabase(path)
    p2 = ShardedPersistentUniquenessProvider(db2, 2)
    with pytest.raises(UniquenessConflict):
        p2.commit([refs[5]], tx[3], party)
    # same-tx re-commit stays idempotent across the migration (the
    # client-retry invariant the streamed tail rides on)
    p2.commit(refs[4:8], tx[2], party)
    assert p2.committed_count == 8
    # routing matches shard_of_ref exactly after the re-partition
    for r in refs[:8]:
        k = shard_of_ref(r, 2)
        assert r in {
            rr for rr in p2.committed if shard_of_ref(rr, 2) == k
        }
    db2.close()


def test_node_boot_sharded_plane_and_sticky_layout(tmp_path):
    """A real Node with notary_shards=2 boots the sharded plane; a
    restart with the knob reverted to 0 must STILL read the partition
    tables (sticky layout) — reverting to the legacy table would miss
    partitioned commits."""
    from corda_tpu.node.config import NodeConfig
    from corda_tpu.node.node import Node
    from corda_tpu.node.persistence import (
        ShardedPersistentUniquenessProvider,
    )

    cfg = NodeConfig(
        name="ShardNode", base_dir=str(tmp_path / "node"),
        notary="batching", notary_shards=2, verifier_backend="cpu",
        use_tls=False,
    )
    node = Node(cfg)
    svc = node.services.notary_service
    assert isinstance(svc, BatchingNotaryService)
    assert svc.n_shards == 2
    assert isinstance(svc.uniqueness, ShardedPersistentUniquenessProvider)
    node.stop()

    cfg2 = NodeConfig(
        name="ShardNode", base_dir=str(tmp_path / "node"),
        notary="batching", verifier_backend="cpu", use_tls=False,
    )
    node2 = Node(cfg2)
    svc2 = node2.services.notary_service
    assert isinstance(
        svc2.uniqueness, ShardedPersistentUniquenessProvider
    )
    node2.stop()


def test_config_validates_shard_knobs(tmp_path):
    from corda_tpu.node.config import (
        ConfigError,
        NodeConfig,
        load_config,
        write_config,
    )

    with pytest.raises(ConfigError):
        NodeConfig(name="X", base_dir=".", notary="simple", notary_shards=4)
    cfg = NodeConfig(
        name="X", base_dir=".", notary="batching", notary_shards=4,
    )
    out = str(tmp_path / "node.toml")
    write_config(cfg, out)
    text = open(out).read()
    assert "notary_shards = 4" in text
    assert load_config(out).notary_shards == 4


# ---------------------------------------------------------------------------
# per-shard QoS lanes


def test_per_shard_qos_lane_retunes_hot_shard_only():
    from corda_tpu.node import qos as qoslib

    pol = qoslib.QosPolicy(target_p99_micros=10_000, max_batch=256)
    qos = qoslib.NotaryQos(pol)
    qos.ensure_shards(3)
    assert len(qos.shard_controllers) == 3
    # shard 0 runs hot: admitted latency far over target
    for _ in range(64):
        qos.record_admitted(50_000, shard=0)
        qos.record_admitted(1_000, shard=1)
    for _ in range(4):
        qos.observe_shard_flush(0, 256, 512)
        qos.observe_shard_flush(1, 256, 0)
    hot, cool = qos.controller_for(0), qos.controller_for(1)
    assert hot.batch < pol.max_batch, "hot shard did not collapse"
    assert cool.batch == pol.max_batch, "cool shard was collapsed too"
    # one hot shard must NOT walk the node into brownout by itself:
    # brownout only steps on the aggregate backlog observation
    assert qos.brownout_level == 0
    snap = qos.snapshot()
    assert len(snap["shards"]) == 3
    assert snap["shards"][0]["batch"] == hot.batch
    # unknown shard ids fall back to the global lane
    assert qos.controller_for(None) is qos.controller
    assert qos.controller_for(99) is qos.controller


def test_sharded_notary_wires_qos_lanes():
    from corda_tpu.node import qos as qoslib

    net, notary, alice, stxs = _conflict_workload(4)
    qos = qoslib.NotaryQos(
        qoslib.QosPolicy(max_batch=512), clock=net.clock
    )
    svc = BatchingNotaryService(
        notary.services, ShardedUniquenessProvider(4),
        shards=4, qos=qos, max_batch=512,
    )
    try:
        assert len(qos.shard_controllers) == 4
        for stx in stxs:
            svc.submit(
                stx, alice.party,
                arrival_micros=net.clock.now_micros(),
            )
        # flush() drains regardless of the controllers' initial
        # batching window (tick would hold a fresh lane's 5 ms window)
        svc.flush()
        assert all(
            c.flushes >= 1
            for c in qos.shard_controllers
            if c.latency.count
        )
        # the per-shard latency histograms collected the answers
        assert sum(h.count for h in qos._shard_latency) > 0
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# per-shard health: one wedged shard flips /healthz, siblings keep going


def test_shard_heartbeats_carry_own_progress_and_name_a_stalled_shard():
    """Inline waves, 2 shards: each `notary.shard<k>.flush` heartbeat
    carries its own shard's answered requests as progress, and a shard
    left with pending work while the pump stops ticking goes stalled
    past the watchdog deadline, named with its queue depth; the next
    tick serves it and /healthz recovers."""
    DEADLINE = 1_000_000
    net, notary, alice, bank, issued, spend = _cash_rig(8)
    svc = BatchingNotaryService(
        notary.services, ShardedUniquenessProvider(2),
        shards=2, max_batch=64,
    )
    monitor = hlib.HealthMonitor(
        clock=net.clock,
        policy=hlib.HealthPolicy(heartbeat_deadline_micros=DEADLINE),
    )
    svc.attach_health(monitor)
    try:
        spends = [spend([i], bank.party) for i in issued]
        to_zero = [s for s in spends if shard_of_tx(s, 2) == 0]
        to_one = [s for s in spends if shard_of_tx(s, 2) == 1]
        assert len(to_zero) >= 2 and len(to_one) >= 2, "fixture missed a shard"

        futs = [svc.submit(s, alice.party) for s in to_one]
        futs.append(svc.submit(to_zero[0], alice.party))
        assert svc.tick() == len(to_one) + 1   # one wave, both shards
        assert all(hasattr(f.result(), "by") for f in futs)
        monitor.tick()
        ok, detail = monitor.healthz()
        assert ok
        beats = monitor.watchdog.check(net.clock.now_micros())
        assert beats["notary.shard0.flush"]["progress"] == 1
        assert beats["notary.shard1.flush"]["progress"] == len(to_one)
        assert beats["notary.flush"]["progress"] == len(to_one) + 1

        # shard 0 holds work and the pump stops ticking
        late = svc.submit(to_zero[1], alice.party)
        net.clock.advance(DEADLINE + 1)
        monitor.tick()
        ok, detail = monitor.healthz()
        assert not ok
        stalled = detail["unhealthy"]
        assert "notary.shard0.flush" in stalled
        beats = monitor.watchdog.check(net.clock.now_micros())
        assert beats["notary.shard0.flush"]["state"] == hlib.HB_STALLED
        assert beats["notary.shard0.flush"]["queue_depth"] == 1
        assert beats["notary.shard1.flush"]["queue_depth"] == 0

        # the pump comes back: the due shard flushes, progress moves
        assert svc.tick() == 1
        assert hasattr(late.result(), "by")
        net.clock.advance(10)
        monitor.tick()
        assert monitor.healthz()[0]
        beats = monitor.watchdog.check(net.clock.now_micros())
        assert beats["notary.shard0.flush"]["progress"] == 2
        assert beats["notary.shard1.flush"]["progress"] == len(to_one)
    finally:
        svc.stop()


class _PerfFeed:
    """A perf plane's notary seam, recording what each flush feeds."""

    def __init__(self):
        self.shards = None
        self.flushes = []
        self.waves = []

    def attach_shards(self, n, depth_fns):
        self.shards = n

    def observe_flush(self, shard, n, marks):
        self.flushes.append((shard, n))

    def observe_wave(self, feeds):
        self.waves.append([(sid, n) for sid, n, _ in feeds])

    def watch_rate(self, key, fn):
        pass


@pytest.mark.parametrize("shards", [1, 4])
def test_one_shard_plane_names_no_shard(shards):
    """A one-shard batching notary looks from outside as the
    single-queue notary always did: no Notary.Shard<k>.* metric and no
    Notary.Shards gauge, no notary.shard<k>.flush heartbeat, no `shard`
    attribute on its spans, no per-shard QoS lane, and one
    observe_flush(0, ...) per flush with no wave sample. A plane of
    several shards names each of them."""
    from corda_tpu.node import qos as qoslib
    from corda_tpu.utils.tracing import Tracer

    net, notary, alice, stxs = _conflict_workload(4)
    qos = qoslib.NotaryQos(qoslib.QosPolicy(max_batch=512), clock=net.clock)
    svc = BatchingNotaryService(
        notary.services, ShardedUniquenessProvider(shards),
        shards=shards, qos=qos, max_batch=512,
    )
    monitor = hlib.HealthMonitor(clock=net.clock)
    svc.attach_health(monitor)
    perf = _PerfFeed()
    svc.attach_perf(perf)
    tracer = Tracer(enabled=True)
    try:
        futs = []
        for stx in stxs:
            fut = FlowFuture()
            futs.append(fut)
            svc.enqueue_pending(_PendingNotarisation(
                stx, alice.party, fut,
                span=tracer.start_trace("notarise.frame"),
                arrival_micros=net.clock.now_micros(),
            ))
        svc.flush()
        assert all(f.done for f in futs)
    finally:
        svc.stop()
    names = svc.metrics.names()
    shard_metrics = [n for n in names if n.startswith("Notary.Shard")]
    beats = set(monitor.watchdog.check(net.clock.now_micros()))
    spans = [s for t in tracer.recorder.traces() for s in t.spans]
    assert {s.name for s in spans} >= {"notarise.frame", "notary.stage"}
    tagged = [s for s in spans if "shard" in s.attributes]
    if shards == 1:
        assert shard_metrics == []
        assert beats == {"notary.flush"}
        assert tagged == []
        assert qos.shard_controllers == [] and qos.snapshot()["shards"] == []
        assert qos.controller.flushes == 1
        assert perf.shards == 1
        assert perf.flushes == [(0, len(stxs))] and perf.waves == []
    else:
        assert "Notary.Shards" in names
        assert "Notary.Shard3.Depth" in names
        assert beats == {"notary.flush"} | {
            f"notary.shard{k}.flush" for k in range(shards)
        }
        assert len(tagged) == len(spans)
        assert len(qos.shard_controllers) == shards
        assert qos.controller.flushes == 0
        assert perf.shards == shards and len(perf.waves) == 1


@pytest.mark.parametrize("shards", [1, 4])
def test_tick_flushes_through_the_flush_attribute(shards):
    """The pump's tick flushes through `svc.flush`, on every plane, so
    what an embedding rig wraps around it (a profiler annotation, an
    fsync hook) runs on every pump flush. One flush is one wave, and
    without QoS nothing caps it: shards past their batch flush whole.
    The tick still counts what it answered when the wrapper returns
    nothing."""
    net, notary, alice, stxs = _conflict_workload(4)
    svc = BatchingNotaryService(
        notary.services, ShardedUniquenessProvider(shards),
        shards=shards, max_batch=4,
    )
    calls = []
    inner = svc.flush

    def wrapped():
        calls.append(svc.backlog())
        inner()

    svc.flush = wrapped
    try:
        futs = [svc.submit(stx, alice.party) for stx in stxs]
        assert svc.tick() == len(stxs)
        assert calls == [len(stxs)]
        assert all(f.done for f in futs)
        assert svc.batches_dispatched == min(
            shards, len({svc.shard_of(s) for s in stxs})
        )
        assert svc.tick() == 0 and calls == [len(stxs)]
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# bench plumbing: the quick smoke emits a well-formed sweep record


@pytest.mark.slow
def test_bench_quick_shards_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py"),
         "--quick", "shards"],
        capture_output=True, text=True, timeout=540,
        env=dict(
            os.environ, JAX_PLATFORMS="cpu", BENCH_BATCH="24",
            BENCH_ITERS="1",
        ),
        cwd=root,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    rec = json.loads(lines[-1])
    assert rec["metric"] == "notary_commit_plane_sharded_per_sec"
    assert rec["quick"] is True
    assert set(rec["shard_sweep"]) == {"1", "2", "4"}
    assert rec["per_shard_depth"] > 0
    assert rec["verify_stubbed"] is True
