"""BatchingNotaryService: cross-transaction signature batching.

The serving path of SURVEY §7 Phase 4: concurrent notarisation
requests accumulate while messages pump; at the quiescent tick the
notary drains EVERY pending transaction's signatures through ONE
BatchSignatureVerifier dispatch, commits inputs in arrival order and
scatters replies. Reference seams: NotaryFlow.kt:107-130 (per-request
service this batches), OutOfProcessTransactionVerifierService.kt:19-73
(the offload pattern the SPI generalises).
"""

import pytest

from corda_tpu.core.transactions import TransactionBuilder
from corda_tpu.crypto.batch_verifier import CpuBatchVerifier
from corda_tpu.finance import CashIssueFlow, CashPaymentFlow
from corda_tpu.finance.cash import CASH_CONTRACT, CashMove, CashState
from corda_tpu.flows.core_flows import FinalityFlow
from corda_tpu.node.notary import BatchingNotaryService, NotaryException
from corda_tpu.testing.mock_network import MockNetwork


class SpyVerifier(CpuBatchVerifier):
    """Records the size of every SPI dispatch."""

    def __init__(self):
        self.dispatch_sizes: list[int] = []

    def verify_batch(self, requests):
        self.dispatch_sizes.append(len(requests))
        return super().verify_batch(requests)


class StreamingStubVerifier(CpuBatchVerifier):
    """CPU results delivered through a REAL streamed
    PendingVerification in small chunks — CI coverage for the notary's
    _stream_tail (CpuBatchVerifier alone has no verify_batch_async, so
    the streaming consensus path would otherwise only execute on TPU
    hardware)."""

    def __init__(self, chunk: int = 2):
        self.chunk = chunk
        self.handles: list = []

    def verify_batch_async(self, requests):
        import numpy as np

        from corda_tpu.crypto.batch_verifier import PendingVerification

        res = super().verify_batch(requests)
        pending = [
            (
                np.asarray(res[off : off + self.chunk], dtype=bool),
                list(range(off, min(off + self.chunk, len(res)))),
                min(self.chunk, len(res) - off),
            )
            for off in range(0, len(res), self.chunk)
        ]
        h = PendingVerification([None] * len(res), pending, streamed=True)
        self.handles.append(h)
        return h


def test_streaming_tail_matches_join_path_outcomes():
    """The round-5 streaming tail (per-chunk validate+commit while
    later chunks 'compute') must decide identically to the join path:
    the same mixed flush — valid spends, an intra-flush double spend,
    a tampered signature — through both, with first-wins preserved."""
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.notary import _PendingNotarisation

    outcomes = {}
    for mode, verifier in (
        ("stream", StreamingStubVerifier(chunk=2)),
        ("join", CpuBatchVerifier()),
    ):
        net = MockNetwork(seed=44, batch_verifier=verifier)
        notary = net.create_notary("Notary", batching=True)
        bank = net.create_node("Bank")
        alice = net.create_node("Alice")
        for amt in (500, 300, 200):
            bank.run_flow(CashIssueFlow(amt, "USD", alice.party, notary.party))
        notary.services.record_transactions(
            alice.services.validated_transactions.all()
        )
        coins = sorted(
            alice.vault.unconsumed_states(CashState),
            key=lambda s: s.state.data.amount.quantity,
        )

        def spend(coin, dest_key):
            b = TransactionBuilder(notary.party)
            b.add_input_state(coin)
            b.add_output_state(
                coin.state.data.with_owner(dest_key), CASH_CONTRACT,
                notary.party,
            )
            b.add_command(CashMove(), alice.party.owning_key)
            return alice.services.sign_initial_transaction(b)

        stx_ok = spend(coins[0], bank.party.owning_key)
        stx_first = spend(coins[1], bank.party.owning_key)
        stx_second = spend(coins[1], notary.party.owning_key)  # double
        stx_bad = spend(coins[2], bank.party.owning_key)
        sig = stx_bad.sigs[0]
        tampered = type(sig)(
            by=sig.by,
            signature=sig.signature[:-1] + bytes([sig.signature[-1] ^ 1]),
            metadata=sig.metadata,
        )
        stx_bad = type(stx_bad)(stx_bad.wtx, (tampered,))

        svc = notary.services.notary_service
        futs = {}
        for name, stx in (
            ("ok", stx_ok), ("first", stx_first),
            ("second", stx_second), ("bad", stx_bad),
        ):
            fut = FlowFuture()
            svc.enqueue_pending(
                _PendingNotarisation(stx, alice.party, fut)
            )
            futs[name] = fut
        svc.flush()
        got = {}
        for name, fut in futs.items():
            v = fut.result()
            got[name] = "signed" if hasattr(v, "by") else ("err", v.kind)
        outcomes[mode] = got
        if mode == "stream":
            h = verifier.handles[-1]
            # the streaming tail consumed chunks; result() never ran
            assert not h._done, "join fallback ran instead of streaming"
    assert outcomes["stream"] == outcomes["join"]
    assert outcomes["join"]["ok"] == "signed"
    assert outcomes["join"]["first"] == "signed"      # arrival order wins
    assert outcomes["join"]["second"] == ("err", "conflict")
    assert outcomes["join"]["bad"] == ("err", "invalid-transaction")


def make_net(n_clients: int = 4):
    spy = SpyVerifier()
    net = MockNetwork(seed=33, batch_verifier=spy)
    notary = net.create_notary("Notary", batching=True)
    assert isinstance(notary.services.notary_service, BatchingNotaryService)
    bank = net.create_node("Bank")
    clients = [net.create_node(f"Client{i}") for i in range(n_clients)]
    return net, spy, notary, bank, clients


def test_concurrent_requests_share_one_dispatch():
    net, spy, notary, bank, clients = make_net(4)
    svc = notary.services.notary_service

    # seed every client with cash (sequential warm-up traffic)
    for c in clients:
        bank.run_flow(CashIssueFlow(1000, "USD", c.party, notary.party))
    base_batches = svc.batches_dispatched

    # start all payments BEFORE pumping: they notarise concurrently
    fsms = [
        c.start_flow(CashPaymentFlow(100, "USD", bank.party))
        for c in clients
    ]
    spy.dispatch_sizes.clear()
    net.run()
    for f in fsms:
        f.result_or_throw()

    assert svc.requests_batched >= len(clients)
    # all 4 concurrent requests answered by ONE batch dispatch
    assert svc.batches_dispatched == base_batches + 1
    # ...and that dispatch carried multiple transactions' signatures:
    # each payment tx has >= 1 signature, so the notary's single call
    # must be at least as large as the per-tx signature count times 4
    assert max(spy.dispatch_sizes) >= 4


def test_double_spend_within_one_batch():
    """Two txs spending the same StateRef queued into the SAME flush:
    arrival order wins, the second gets a conflict error."""
    net, spy, notary, bank, clients = make_net(1)
    alice = clients[0]
    bank.run_flow(CashIssueFlow(500, "USD", alice.party, notary.party))
    st = alice.vault.unconsumed_states(CashState)[0]

    def spend_to(dest):
        b = TransactionBuilder(notary.party)
        b.add_input_state(st)
        b.add_output_state(
            st.state.data.with_owner(dest.party.owning_key),
            CASH_CONTRACT,
            notary.party,
        )
        b.add_command(CashMove(), alice.party.owning_key)
        return alice.services.sign_initial_transaction(b)

    fsm_a = alice.start_flow(FinalityFlow(spend_to(bank)))
    fsm_b = alice.start_flow(FinalityFlow(spend_to(notary)))
    net.run()
    fsm_a.result_or_throw()   # first arrival commits
    with pytest.raises(NotaryException) as exc:
        fsm_b.result_or_throw()
    assert exc.value.error.kind == "conflict"


def test_invalid_signature_scattered_to_its_requester():
    """A tampered tx inside a batch fails alone; its neighbours
    notarise fine from the same dispatch."""
    net, spy, notary, bank, clients = make_net(2)
    good, bad = clients
    for c in clients:
        bank.run_flow(CashIssueFlow(300, "USD", c.party, notary.party))

    st = bad.vault.unconsumed_states(CashState)[0]
    b = TransactionBuilder(notary.party)
    b.add_input_state(st)
    b.add_output_state(
        st.state.data.with_owner(bank.party.owning_key),
        CASH_CONTRACT,
        notary.party,
    )
    b.add_command(CashMove(), bad.party.owning_key)
    stx = bad.services.sign_initial_transaction(b)
    # corrupt the signature bytes
    sig = stx.sigs[0]
    tampered = type(sig)(
        by=sig.by,
        signature=sig.signature[:-1]
        + bytes([sig.signature[-1] ^ 1]),
        metadata=sig.metadata,
    )
    stx_bad = type(stx)(stx.wtx, (tampered,))

    fsm_good = good.start_flow(CashPaymentFlow(100, "USD", bank.party))
    fsm_bad = bad.start_flow(FinalityFlow(stx_bad))
    net.run()
    fsm_good.result_or_throw()
    with pytest.raises(Exception) as exc:
        fsm_bad.result_or_throw()
    assert "invalid" in str(exc.value).lower()


def test_batching_notary_rejects_wrong_notary_immediately():
    net, spy, notary, bank, clients = make_net(1)
    svc = notary.services.notary_service
    # a tx naming the CLIENT as notary must bounce without batching
    alice = clients[0]
    bank.run_flow(CashIssueFlow(100, "USD", alice.party, notary.party))
    st = alice.vault.unconsumed_states(CashState)[0]
    gen = svc.process(
        alice.services.sign_initial_transaction(
            TransactionBuilder(notary.party)
            .add_input_state(st)
            .add_output_state(
                st.state.data.with_owner(bank.party.owning_key),
                CASH_CONTRACT,
                notary.party,
            )
            .add_command(CashMove(), alice.party.owning_key)
        ),
        alice.party,
    )
    # swap the service identity so the check fires
    svc.service_identity = alice.party
    try:
        next(gen)
        raise AssertionError("expected immediate return")
    except StopIteration as stop:
        assert stop.value.kind == "wrong-notary"


def test_dispatch_failure_answers_every_requester():
    """A failed SPI dispatch (device down, unsupported scheme) must
    resolve every queued future, not strand the flows or crash the
    pump tick. With the round-9 degraded fallback OFF, every future
    answers `verification-unavailable`; with it ON (the default), the
    flush falls back to the CPU reference and answers for REAL —
    either way, nothing strands."""
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.notary import NotaryError, _PendingNotarisation

    net, spy, notary, bank, clients = make_net(1)
    svc = notary.services.notary_service
    alice = clients[0]
    issue = bank.run_flow(
        CashIssueFlow(100, "USD", alice.party, notary.party)
    )
    # the degraded flush below validates for real: the (validating)
    # notary needs the spend's backchain in its tx storage
    notary.services.record_transactions([issue])
    st = alice.vault.unconsumed_states(CashState)[0]
    b = TransactionBuilder(notary.party)
    b.add_input_state(st)
    b.add_output_state(
        st.state.data.with_owner(bank.party.owning_key),
        CASH_CONTRACT,
        notary.party,
    )
    b.add_command(CashMove(), alice.party.owning_key)
    stx = alice.services.sign_initial_transaction(b)

    class BoomVerifier(CpuBatchVerifier):
        def verify_batch(self, requests):
            raise RuntimeError("device unavailable")

    svc.degraded_fallback = False   # the fallback path has its own test
    futs = [FlowFuture(), FlowFuture()]
    for f in futs:
        svc.enqueue_pending(_PendingNotarisation(stx, alice.party, f))
    svc.services._batch_verifier = BoomVerifier()
    svc.flush()   # must not raise
    for f in futs:
        err = f.result()
        assert isinstance(err, NotaryError)
        assert err.kind == "verification-unavailable"

    # fallback ON (default): the same dead device degrades the flush
    # instead of failing it — the CPU reference answers for real and
    # the degraded flag arms the recovery probe
    svc.degraded_fallback = True
    fut = FlowFuture()
    svc.enqueue_pending(_PendingNotarisation(stx, alice.party, fut))
    svc.flush()
    assert hasattr(fut.result(), "by"), "degraded flush must sign"
    assert svc.degraded
    assert svc.metrics.counter("Notary.DegradedFlushes").count == 1


def test_max_batch_triggers_inline_flush():
    net, spy, notary, bank, clients = make_net(1)
    svc = notary.services.notary_service
    svc.max_batch = 1   # every enqueue flushes immediately
    alice = clients[0]
    bank.run_flow(CashIssueFlow(100, "USD", alice.party, notary.party))
    before = svc.batches_dispatched
    alice.run_flow(CashPaymentFlow(40, "USD", bank.party))
    assert svc.batches_dispatched > before


def test_malformed_tx_in_batch_fails_alone():
    """One transaction whose signature staging raises must answer ITS
    future with an error while the rest of the batch proceeds —
    aborting flush after the queue swap would strand every requester
    (round-3 advisor finding)."""
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.notary import NotaryError, _PendingNotarisation

    net, spy, notary, bank, clients = make_net(1)
    svc = notary.services.notary_service
    alice = clients[0]
    bank.run_flow(CashIssueFlow(100, "USD", alice.party, notary.party))
    st = alice.vault.unconsumed_states(CashState)[0]
    b = TransactionBuilder(notary.party)
    b.add_input_state(st)
    b.add_output_state(
        st.state.data.with_owner(bank.party.owning_key),
        CASH_CONTRACT,
        notary.party,
    )
    b.add_command(CashMove(), alice.party.owning_key)
    good_stx = alice.services.sign_initial_transaction(b)
    # flush() is driven directly (no notary-client flow), so hand the
    # notary the backchain it would otherwise have resolved in-session
    issue_stx = alice.services.validated_transactions.get(st.ref.txhash)
    notary.services.record_transactions([issue_stx])

    class MalformedStx:
        def signature_requests(self):
            raise ValueError("unsupported signature scheme")

    bad_fut, good_fut = FlowFuture(), FlowFuture()
    svc.enqueue_pending(
        _PendingNotarisation(MalformedStx(), alice.party, bad_fut)
    )
    svc.enqueue_pending(_PendingNotarisation(good_stx, alice.party, good_fut))
    svc.flush()   # must not raise
    err = bad_fut.result()
    assert isinstance(err, NotaryError)
    assert err.kind == "invalid-transaction"
    # the good transaction still got a notary signature from the batch
    sig = good_fut.result()
    assert not isinstance(sig, NotaryError)


def test_batch_deadline_holds_then_flushes():
    """max_wait_micros (SURVEY §7 hard part 4 — batching latency vs
    throughput): ticks HOLD pending requests until the oldest has aged
    past the deadline, then one flush answers all of them in a single
    dispatch; max_batch still forces an immediate flush."""
    spy = SpyVerifier()
    net = MockNetwork(seed=44, batch_verifier=spy)
    notary = net.create_notary("Notary", batching=True)
    svc = notary.services.notary_service
    svc.max_wait_micros = 1_000_000          # 1s deadline
    bank = net.create_node("Bank")
    clients = [net.create_node(f"C{i}") for i in range(3)]
    for c in clients:
        bank.run_flow(CashIssueFlow(500, "USD", c.party, notary.party))

    fsms = [
        c.start_flow(CashPaymentFlow(100, "USD", bank.party))
        for c in clients
    ]
    base = svc.batches_dispatched
    net.run()
    # held: requests arrived but the deadline has not aged out
    assert svc.batches_dispatched == base
    assert svc.backlog() == len(clients)
    assert all(not f.done for f in fsms)

    net.clock.advance(2_000_000)             # age past the deadline
    spy.dispatch_sizes.clear()
    net.run()
    for f in fsms:
        f.result_or_throw()
    # one flush; its dispatch (the first after the hold) covers every
    # held request's signature in one SPI call — later dispatches are
    # the peers re-verifying the notarised transactions on receipt
    assert svc.batches_dispatched == base + 1
    assert spy.dispatch_sizes[0] == len(clients)

    # max_batch overrides the deadline: filling the batch flushes NOW
    svc.max_batch = 2
    fsms = [
        c.start_flow(CashPaymentFlow(50, "USD", bank.party))
        for c in clients[:2]
    ]
    net.run()
    for f in fsms:
        f.result_or_throw()


def test_attachment_code_gated_on_valid_signatures():
    """Peer-supplied (attachment-carried, sandboxed) contract code must
    not execute during the speculative overlap phase: a transaction
    with forged signatures is rejected WITHOUT its attachment code ever
    loading; the honestly-signed transaction loads and runs it."""
    from corda_tpu.core import sandbox
    from corda_tpu.core.transactions import SignedTransaction
    from corda_tpu.core.contracts import StateRef
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.notary import _PendingNotarisation, NotaryError

    source = '''
from corda_tpu.core.contracts import ContractViolation

class GateContract:
    def verify(self, ltx):
        if not ltx.outputs:
            raise ContractViolation("no outputs")
'''
    att = sandbox.make_contract_attachment(
        "test.gated.Contract", "GateContract", source
    )

    net, spy, notary, bank, clients = make_net(1)
    alice = clients[0]
    svc = notary.services.notary_service
    bank.run_flow(CashIssueFlow(500, "USD", alice.party, notary.party))
    st = alice.vault.unconsumed_states(CashState)[0]
    issue_stx = alice.services.validated_transactions.get(st.ref.txhash)
    notary.services.record_transactions([issue_stx])
    notary.services.attachments.import_attachment(att.data)
    alice.services.attachments.import_attachment(att.data)

    b = TransactionBuilder(notary.party)
    b.add_input_state(st)
    b.add_output_state(
        st.state.data.with_owner(bank.party.owning_key),
        "test.gated.Contract",
        notary.party,
    )
    b.add_command(CashMove(), alice.party.owning_key)
    b.add_attachment(att.id)
    good_stx = alice.services.sign_initial_transaction(b)

    # forge: signature over a DIFFERENT tx id
    other = bank.run_flow(CashIssueFlow(5, "EUR", alice.party, notary.party))
    wrong_sig = alice.services.key_management.sign(
        other.id, alice.party.owning_key
    )
    forged = SignedTransaction(good_stx.wtx, (wrong_sig,))

    sandbox._loaded_cache.clear()
    fut = FlowFuture()
    svc.enqueue_pending(_PendingNotarisation(forged, alice.party, fut))
    svc.flush()
    err = fut.result()
    assert isinstance(err, NotaryError) and err.kind == "invalid-transaction"
    assert "signature" in err.message.lower()
    # the forged tx's attachment code never loaded, let alone ran
    assert att.id.bytes_ not in sandbox._loaded_cache

    fut = FlowFuture()
    svc.enqueue_pending(_PendingNotarisation(good_stx, alice.party, fut))
    svc.flush()
    sig = fut.result()
    assert not isinstance(sig, NotaryError)
    # now it did: the honest transaction ran the attachment contract
    assert att.id.bytes_ in sandbox._loaded_cache


def test_flush_with_async_verifier_verifies_in_process():
    """A batching notary configured with an ASYNC (out-of-process
    style) verifier service must not block on futures that resolve via
    the pump it is running on — it verifies in-process instead, for
    both registered and (signature-gated) attachment contracts."""
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.notary import NotaryError, _PendingNotarisation
    from corda_tpu.node.services import TransactionVerifierService

    class NeverResolves(TransactionVerifierService):
        synchronous = False

        def verify(self, ltx):
            from corda_tpu.node.services import _Future

            return _Future()   # pending forever (pump-resolved IRL)

    net, spy, notary, bank, clients = make_net(1)
    alice = clients[0]
    svc = notary.services.notary_service
    bank.run_flow(CashIssueFlow(500, "USD", alice.party, notary.party))
    st = alice.vault.unconsumed_states(CashState)[0]
    issue_stx = alice.services.validated_transactions.get(st.ref.txhash)
    notary.services.record_transactions([issue_stx])
    b = TransactionBuilder(notary.party)
    b.add_input_state(st)
    b.add_output_state(
        st.state.data.with_owner(bank.party.owning_key),
        CASH_CONTRACT,
        notary.party,
    )
    b.add_command(CashMove(), alice.party.owning_key)
    stx = alice.services.sign_initial_transaction(b)

    notary.services.transaction_verifier = NeverResolves()
    fut = FlowFuture()
    svc.enqueue_pending(_PendingNotarisation(stx, alice.party, fut))
    svc.flush()
    sig = fut.result()
    assert not isinstance(sig, NotaryError), f"rejected: {sig}"


def test_upgrade_attachment_code_also_gated_on_signatures():
    """A contract-UPGRADE transaction's conversion can ship as an
    attachment too; a forged-signature upgrade must be rejected without
    that peer-supplied code ever loading (the gate defers ALL
    replacement transactions)."""
    from corda_tpu.core import sandbox
    from corda_tpu.core.replacement import ContractUpgradeCommand
    from corda_tpu.core.transactions import SignedTransaction
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.notary import _PendingNotarisation, NotaryError

    upgrade_src = '''
from corda_tpu.finance.cash import CashState

class GatedUpgrade:
    def verify(self, ltx):
        return

def convert(old_state):
    return CashState(old_state.amount, old_state.owner)
'''
    att = sandbox.make_contract_attachment(
        "test.gated.Upgrade", "GatedUpgrade", upgrade_src,
        upgrades_from=CASH_CONTRACT,
    )

    net, spy, notary, bank, clients = make_net(1)
    alice = clients[0]
    svc = notary.services.notary_service
    bank.run_flow(CashIssueFlow(500, "USD", alice.party, notary.party))
    st = alice.vault.unconsumed_states(CashState)[0]
    issue_stx = alice.services.validated_transactions.get(st.ref.txhash)
    notary.services.record_transactions([issue_stx])
    notary.services.attachments.import_attachment(att.data)
    alice.services.attachments.import_attachment(att.data)

    b = TransactionBuilder(notary.party)
    b.add_input_state(st)
    b.add_output_state(st.state.data, "test.gated.Upgrade", notary.party)
    b.add_command(
        ContractUpgradeCommand(CASH_CONTRACT, "test.gated.Upgrade"),
        st.state.data.owner,
    )
    b.add_attachment(att.id)
    good_stx = alice.services.sign_initial_transaction(b)

    other = bank.run_flow(CashIssueFlow(5, "EUR", alice.party, notary.party))
    wrong_sig = alice.services.key_management.sign(
        other.id, alice.party.owning_key
    )
    forged = SignedTransaction(good_stx.wtx, (wrong_sig,))

    sandbox._upgrade_cache.clear()
    fut = FlowFuture()
    svc.enqueue_pending(_PendingNotarisation(forged, alice.party, fut))
    svc.flush()
    err = fut.result()
    assert isinstance(err, NotaryError) and err.kind == "invalid-transaction"
    assert "signature" in err.message.lower()
    # the forged upgrade's conversion code never loaded
    assert att.id.bytes_ not in sandbox._upgrade_cache

    fut = FlowFuture()
    svc.enqueue_pending(_PendingNotarisation(good_stx, alice.party, fut))
    svc.flush()
    sig = fut.result()
    assert not isinstance(sig, NotaryError), f"rejected: {sig}"
    assert att.id.bytes_ in sandbox._upgrade_cache
