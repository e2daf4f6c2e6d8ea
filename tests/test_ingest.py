"""Pipelined wire ingest (node/ingest.py): parity + backpressure.

The pipeline is an OPTIMISATION seam over consensus-critical work
(transaction ids, signature staging), so its contract is bit-identity
with the serial path: same ids, same accept/reject verdicts, same
per-slot error behaviour for malformed frames — including when the
digest/frame caches are warm. The ring's bounded-put backpressure and
the notary/verifier drains are behavioural seams pinned here too, plus
the round-5 advisor's notary recovery invariant: the uniqueness
provider's same-tx re-commit MUST succeed after a simulated
`_stream_tail` mid-stream failure, because committed-but-unsigned
transactions recover their signature only through an idempotent client
retry (docs/serving-notary.md).
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from corda_tpu.core import serialization as ser
from corda_tpu.core.contracts import Amount, Issued, StateRef
from corda_tpu.core.identity import PartyAndReference
from corda_tpu.core.transactions import SignedTransaction, TransactionBuilder
from corda_tpu.crypto.batch_verifier import (
    CpuBatchVerifier,
    PendingVerification,
)
from corda_tpu.crypto.hashes import SecureHash
from corda_tpu.finance.cash import (
    CASH_CONTRACT,
    CashIssue,
    CashMove,
    CashState,
)
from corda_tpu.node.ingest import (
    DigestCache,
    IngestPipeline,
    IngestRing,
    install_tx_ids,
)
from corda_tpu.node.notary import (
    InMemoryUniquenessProvider,
    NotaryError,
    UniquenessConflict,
    _PendingNotarisation,
)
from corda_tpu.testing.mock_network import MockNetwork


def _cash_spends(n: int, seed: int = 21):
    """(net, notary, requester_party, [SignedTransaction]) — n signed
    single-input cash spends, the canonical ingest fixture."""
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


@pytest.fixture(scope="module")
def cash_fixture():
    return _cash_spends(4)


# ---------------------------------------------------------------------------
# parity with the serial path


def test_pipelined_matches_serial_ids_and_verdicts(cash_fixture):
    """Bit-identical tx ids and accept/reject verdicts vs the serial
    decode path on the canonical signed-cash fixture, including a
    mid-batch malformed blob and a tampered signature — and again on a
    second, cache-warm pass."""
    _, _, _, spends = cash_fixture
    good = [ser.encode(s) for s in spends]
    # a tampered signature: decodes fine, must REJECT identically
    s0 = spends[0]
    bad_sig = s0.sigs[0].__class__(
        signature=bytes([s0.sigs[0].signature[0] ^ 1])
        + s0.sigs[0].signature[1:],
        by=s0.sigs[0].by,
        metadata=s0.sigs[0].metadata,
        partial_merkle=s0.sigs[0].partial_merkle,
    )
    tampered = ser.encode(SignedTransaction(s0.wtx, (bad_sig,)))
    malformed = good[1][:-5]            # truncated mid-batch frame
    blobs = [good[0], good[1], malformed, tampered, good[2], good[3],
             good[0]]                   # repeat: intra-run re-seen frame

    # serial reference: fresh decode, cold id, staged requests, CPU
    # verdicts — per slot
    serial = []
    for b in blobs:
        try:
            stx = ser.decode(b)
        except ser.SerializationError as e:
            serial.append(("error", type(e)))
            continue
        reqs = stx.signature_requests()
        serial.append(
            ("ok", stx.wtx.id, CpuBatchVerifier().verify_batch(reqs))
        )

    pipe = IngestPipeline(shards=2)
    for attempt in ("cold", "cache-warm"):
        entries = pipe.ingest(blobs)
        assert len(entries) == len(blobs)
        for slot, (entry, ref) in enumerate(zip(entries, serial)):
            if ref[0] == "error":
                assert entry.error is not None, (attempt, slot)
                assert isinstance(entry.error, ser.SerializationError)
                assert entry.stx is None
                continue
            assert entry.error is None, (attempt, slot, entry.error)
            assert entry.tx_id == ref[1], (attempt, slot)
            got = CpuBatchVerifier().verify_batch(entry.requests)
            assert got == ref[2], (attempt, slot)
    # the repeated + second-pass frames hit the hot-frame cache
    assert pipe.frame_hits > 0
    pipe.close()


def test_install_tx_ids_matches_property_walk(cash_fixture):
    """The batched Merkle-id stage is bit-identical to wtx.id, with
    and without caches."""
    _, _, _, spends = cash_fixture
    blobs = [ser.encode(s) for s in spends]
    want = [ser.decode(b).wtx.id for b in blobs]
    # no caches
    wtxs = [ser.decode(b).wtx for b in blobs]
    install_tx_ids(wtxs, None, None)
    assert [w.id for w in wtxs] == want
    # shared caches, two passes (second is all hits)
    leaf, root = DigestCache(1024), DigestCache(1024)
    for _ in range(2):
        wtxs = [ser.decode(b).wtx for b in blobs]
        install_tx_ids(wtxs, leaf, root)
        assert [w.id for w in wtxs] == want


def test_staging_is_memoised_not_restaged(cash_fixture):
    """The notary flush / worker drain must reuse the ingest-staged
    list — signature_requests() returns the SAME object the pipeline
    staged."""
    _, _, _, spends = cash_fixture
    pipe = IngestPipeline()
    entry = pipe.ingest([ser.encode(spends[0])])[0]
    assert entry.requests
    assert entry.stx.signature_requests() is entry.requests


def test_digest_cache_bounded():
    cache = DigestCache(capacity=16)
    for i in range(100):
        cache.put(bytes([i]) * 4, b"v")
    assert len(cache) <= 16


# ---------------------------------------------------------------------------
# ring backpressure + messaging seam


def test_ring_put_blocks_until_consumer_drains():
    ring = IngestRing(depth=1)
    assert ring.put(["batch-0"], timeout=1)
    state = {"second_put_done": False}

    def producer():
        ring.put(["batch-1"], timeout=5)
        state["second_put_done"] = True

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    time.sleep(0.05)
    assert not state["second_put_done"], "full ring must block the producer"
    assert ring.take(timeout=1) == ["batch-0"]
    t.join(5)
    assert state["second_put_done"], "drain must release the producer"
    assert ring.take(timeout=1) == ["batch-1"]


def test_messaging_ring_seam_parks_on_full_and_retries():
    from corda_tpu.node.messaging import InMemoryMessagingNetwork

    imn = InMemoryMessagingNetwork()
    rx = imn.endpoint("rx")
    tx = imn.endpoint("tx")
    ring = IngestRing(depth=2)
    rx.add_ring("ingest.topic", ring)
    for i in range(5):
        tx.send("ingest.topic", b"frame-%d" % i, "rx")
    imn.run()
    # 2 in the ring, 3 parked (backpressure, pump never blocked)
    assert len(ring) == 2
    drained = ring.drain()
    assert [m.payload for m in drained] == [b"frame-0", b"frame-1"]
    moved = rx.retry_parked("ingest.topic")
    assert moved == 2
    assert [m.payload for m in ring.drain()] == [b"frame-2", b"frame-3"]
    rx.retry_parked("ingest.topic")
    assert [m.payload for m in ring.drain()] == [b"frame-4"]


def test_ring_seam_redelivery_of_parked_frame_stays_exactly_once():
    """At-least-once upstream: a frame parked while the ring was full
    may be REDELIVERED before retry_parked runs. The redelivery enters
    the ring (room now) and marks the frame seen; the parked copy must
    then be dropped, not offered — exactly-once holds on the ring path
    just like the handler path."""
    from corda_tpu.node.messaging import InMemoryMessagingNetwork, Message

    imn = InMemoryMessagingNetwork()
    rx = imn.endpoint("rx")
    ring = IngestRing(depth=1)
    rx.add_ring("ingest.topic", ring)
    m0 = Message("ingest.topic", b"frame-0", "tx", 1)
    m1 = Message("ingest.topic", b"frame-1", "tx", 2)
    rx._deliver(m0)                 # fills the ring
    rx._deliver(m1)                 # full -> parked, NOT marked seen
    assert ring.drain()[0].payload == b"frame-0"
    rx._deliver(m1)                 # at-least-once redelivery: room now
    assert [m.payload for m in ring.drain()] == [b"frame-1"]
    assert rx.retry_parked("ingest.topic") == 0   # parked dup dropped
    assert ring.drain() == []
    rx._deliver(m1)                 # further redeliveries: already seen
    assert ring.drain() == []


# ---------------------------------------------------------------------------
# notary + verifier drains


def test_notary_flush_drains_ingest_ring(cash_fixture):
    from corda_tpu.flows.api import FlowFuture

    net, notary, requester, spends = cash_fixture
    svc = notary.services.notary_service
    svc.uniqueness = InMemoryUniquenessProvider()   # fresh per test
    pipe = IngestPipeline()
    svc.attach_ingest(pipe.ring)
    futs = []

    def wrap(entries):
        out = []
        for e in entries:
            assert e.error is None
            fut = FlowFuture()
            futs.append(fut)
            out.append(_PendingNotarisation(e.stx, requester, fut))
        return out

    blobs = [ser.encode(s) for s in spends]
    feeder = pipe.feed([blobs[:2], blobs[2:]], wrap=wrap)
    feeder.join(10)
    svc.flush()
    assert len(futs) == len(spends)
    for fut in futs:
        sig = fut.result()
        assert hasattr(sig, "by"), f"notarisation failed: {sig}"
    pipe.close()


def test_verifier_worker_drains_ring_with_prestaged_requests(cash_fixture):
    from corda_tpu.node import messaging as msglib
    from corda_tpu.node.messaging import InMemoryMessagingNetwork
    from corda_tpu.node.verifier import (
        OutOfProcessTransactionVerifierService,
        VerifierWorker,
        request_ingest_pipeline,
    )

    net, _, _, spends = cash_fixture
    alice = next(n for n in net.nodes if n.name == "Alice")
    ltxs = [s.to_ledger_transaction(alice.services) for s in spends]
    imn = InMemoryMessagingNetwork()
    node_ep = imn.endpoint("nodeA")
    worker_ep = imn.endpoint("w1")
    svc = OutOfProcessTransactionVerifierService(node_ep)
    worker = VerifierWorker(
        worker_ep,
        "nodeA",
        batch_verifier=CpuBatchVerifier(),
        batch_window=10**9,         # drain only when we say so
        ingest=request_ingest_pipeline(shards=1),
    )
    imn.run()                       # WorkerReady handshake
    futs = [svc.verify(ltx, stx) for ltx, stx in zip(ltxs, spends)]
    # a contract-only request (stx=None — the reference seam's shape)
    # must ride the same ingest ring and still be answered
    futs.append(svc.verify(ltxs[0]))
    imn.run()                       # requests land in the worker's ring
    assert worker.drain() == len(spends) + 1
    imn.run()                       # responses pump back
    for fut in futs:
        assert fut.done
        fut.result()                # raises on verification failure
    # a malformed frame is dropped in its slot, rest of round survives
    node_ep.send(msglib.TOPIC_VERIFIER_REQ, b"\x07garbage", "w1")
    imn.run()
    assert worker.drain() == 0


# ---------------------------------------------------------------------------
# notary recovery: same-tx re-commit after a mid-stream failure


class MidStreamFailVerifier(CpuBatchVerifier):
    """A streamed PendingVerification whose chunk iterator dies after
    the first chunk — the simulated `_stream_tail` mid-stream
    chunk-fetch failure (earlier drain groups have already committed
    their input states when it fires)."""

    def __init__(self, chunk: int = 2):
        self.chunk = chunk

    def verify_batch_async(self, requests):
        import numpy as np

        res = CpuBatchVerifier().verify_batch(requests)
        pending = [
            (
                np.asarray(res[off : off + self.chunk], dtype=bool),
                list(range(off, min(off + self.chunk, len(res)))),
                min(self.chunk, len(res) - off),
            )
            for off in range(0, len(res), self.chunk)
        ]
        handle = PendingVerification([None] * len(res), pending, streamed=True)
        real_chunks = handle.chunks

        def chunks_then_fail():
            it = real_chunks()
            yield next(it)
            raise RuntimeError("simulated mid-stream chunk fetch failure")

        handle.chunks = chunks_then_fail
        return handle


def test_same_tx_recommit_recovers_after_stream_tail_failure():
    """ADVICE r5: `_stream_tail` diverges from the join path's
    all-or-nothing flush — a mid-stream failure leaves
    committed-but-unsigned transactions whose ONLY recovery is the
    client re-submitting the identical transaction and the uniqueness
    provider accepting the same-tx re-commit. Pin exactly that for the
    round-9 fallback turned OFF; with the degraded fallback ON (the
    default, pinned at the end) the same mid-stream failure now
    completes IN PLACE on the CPU reference — no client retry needed."""
    from corda_tpu.flows.api import FlowFuture

    net, notary, requester, spends = _cash_spends(4, seed=33)
    svc = notary.services.notary_service
    svc.uniqueness = InMemoryUniquenessProvider()
    svc.degraded_fallback = False   # the old contract first
    # first attempt: streamed verify dies after chunk 1 (2 of 4 txs)
    notary.services._batch_verifier = MidStreamFailVerifier(chunk=2)
    futs = []
    for stx in spends:
        fut = FlowFuture()
        futs.append(fut)
        svc.enqueue_pending(_PendingNotarisation(stx, requester, fut))
    svc.flush()
    outcomes = [f.result() for f in futs]
    assert all(isinstance(o, NotaryError) for o in outcomes), outcomes
    # ...but the first chunk's inputs ARE committed (the divergence)
    committed = svc.uniqueness.committed
    assert set(spends[0].wtx.inputs) | set(spends[1].wtx.inputs) <= set(
        committed
    )
    assert committed[spends[0].wtx.inputs[0]] == spends[0].id
    # provider-level invariant: re-committing the SAME tx succeeds,
    # a DIFFERENT tx for the same input still conflicts
    svc.uniqueness.commit(
        list(spends[0].wtx.inputs), spends[0].id, requester
    )
    with pytest.raises(UniquenessConflict):
        svc.uniqueness.commit(
            list(spends[0].wtx.inputs), spends[1].id, requester
        )
    # client retry: identical transactions, healthy verifier -> every
    # tx (including the committed-but-unsigned ones) gets its signature
    notary.services._batch_verifier = CpuBatchVerifier()
    retry_futs = []
    for stx in spends:
        fut = FlowFuture()
        retry_futs.append(fut)
        svc.enqueue_pending(_PendingNotarisation(stx, requester, fut))
    svc.flush()
    for stx, fut in zip(spends, retry_futs):
        sig = fut.result()
        assert hasattr(sig, "by"), f"retry not recovered: {sig}"

    # round 9: with the degraded fallback ON (the shipped default) the
    # SAME mid-stream failure no longer needs the client retry — the
    # CPU reference fills the unresolved rows bit-exact and the flush
    # completes in place, signing everything (already-committed chunk-1
    # rows keep their first commit; the pointer never revisits them)
    svc2 = type(svc)(notary.services, InMemoryUniquenessProvider())
    notary.services._batch_verifier = MidStreamFailVerifier(chunk=2)
    futs2 = []
    for stx in spends:
        fut = FlowFuture()
        futs2.append(fut)
        svc2.enqueue_pending(_PendingNotarisation(stx, requester, fut))
    svc2.flush()
    for stx, fut in zip(spends, futs2):
        sig = fut.result()
        assert hasattr(sig, "by"), f"degraded flush did not sign: {sig}"
    assert svc2.degraded
    assert svc2.metrics.counter("Notary.DegradedFlushes").count == 1


# ---------------------------------------------------------------------------
# CI smoke: the bench plumbing itself


def test_bench_quick_ingest_emits_wellformed_metric_lines():
    """`bench.py --quick ingest` must run under JAX_PLATFORMS=cpu and
    emit one well-formed serial and one pipelined metric line — the
    tier-1 guard that keeps the ingest perf plumbing from rotting."""
    bench = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    out = subprocess.run(
        [sys.executable, os.path.abspath(bench), "--quick", "ingest"],
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "BENCH_BATCH": "64",
            "BENCH_ITERS": "1",
        },
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert len(lines) == 2, out.stdout
    serial = json.loads(lines[0])
    pipelined = json.loads(lines[1])
    assert serial["metric"] == "wire_ingest_decode_id_stage_per_sec"
    assert pipelined["metric"] == "wire_ingest_pipelined_per_sec"
    for rec in (serial, pipelined):
        assert rec["unit"] == "tx/s"
        assert rec["value"] > 0
        assert rec["quick"] is True
    assert pipelined["serial_per_sec"] > 0
    assert pipelined["vs_serial"] > 0
