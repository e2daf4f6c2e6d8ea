"""Delivery versus payment: CommercialPaper against Cash.

Corda samples/trader-demo (TwoPartyTradeFlow, corda_tpu/finance/
trade_flows.py BuyerFlow): the buyer's Cash moves to the seller and the
seller's paper moves to the buyer in ONE transaction — 2 inputs owned by
two parties, 2 outputs, 2 contracts, 2 commands, 2 signatures — with a
time window the notary checks. Each chunk of frames consumes the
outputs of one Cash issuance (the buyers' coins) and one paper issuance
(the sellers' papers, one maturity each)."""

from __future__ import annotations

_PARTIES: dict = {}


def register() -> None:
    """Import the CorDapps whose states and commands the frames carry,
    so the decoder knows their tags."""
    import corda_tpu.finance.cash  # noqa: F401
    import corda_tpu.finance.commercial_paper  # noqa: F401


def parties(seed: int, params: dict):
    key = (seed, params["traders"], params["scheme_id"])
    if key not in _PARTIES:
        from corda_tpu.core.identity import Party

        from benchmark import fixture

        sid = params["scheme_id"]

        def party(role, name):
            kp = fixture.keypair(seed, role, sid)
            return Party(name, kp.public), kp

        _PARTIES[key] = (
            fixture.notary_party(seed, params["notary_scheme_id"])[0],
            party("bank", "O=BankOfCorda,L=London,C=GB"),
            party("paper_issuer", "O=PaperIssuer,L=New York,C=US"),
            [party(f"buyer{k}", f"O=BankB{k},L=Frankfurt,C=DE")
             for k in range(params["traders"])],
            [party(f"seller{k}", f"O=BankA{k},L=Madrid,C=ES")
             for k in range(params["traders"])],
        )
    return _PARTIES[key]


def build(params: dict, seed: int, start: int, n: int):
    """(trades, [cash issuance, paper issuance]) for frames
    start .. start+n."""
    from corda_tpu.core.contracts import (
        Amount, Issued, StateAndRef, StateRef, TimeWindow,
    )
    from corda_tpu.core.identity import PartyAndReference
    from corda_tpu.core.transactions import TransactionBuilder
    from corda_tpu.finance.cash import (
        CASH_CONTRACT, CashIssue, CashMove, CashState,
    )
    from corda_tpu.finance.commercial_paper import (
        CP_CONTRACT, CommercialPaperState, CPIssue, CPMove,
    )

    notary, (bank, bank_kp), (pi, pi_kp), buyers, sellers = parties(
        seed, params
    )
    m = len(buyers)
    token = Issued(PartyAndReference(bank, b"\x01"), params["currency"])
    issuance = PartyAndReference(pi, b"\x01")
    face = params["face"]
    t0 = params["epoch_micros"]

    def price(k):
        return face - 1000 - k % 7919

    cb = TransactionBuilder(notary)
    pb = TransactionBuilder(notary)
    pb.set_time_window(TimeWindow(until_time=t0))
    for j in range(n):
        k = start + j
        cb.add_output_state(
            CashState(Amount(price(k), token), buyers[k % m][0].owning_key),
            CASH_CONTRACT,
        )
        # one maturity per paper: each paper is its own issue group
        pb.add_output_state(
            CommercialPaperState(
                issuance, sellers[k % m][0].owning_key,
                Amount(face, token), t0 + params["tenor_micros"] + k,
            ),
            CP_CONTRACT,
        )
    cb.add_command(CashIssue(start), bank.owning_key)
    pb.add_command(CPIssue(start), pi.owning_key)
    cash = cb.sign_initial_transaction(bank_kp.private)
    paper = pb.sign_initial_transaction(pi_kp.private)
    window = TimeWindow(from_time=t0)
    trades = []
    for j in range(n):
        k = start + j
        (buyer, buyer_kp), (seller, seller_kp) = buyers[k % m], sellers[k % m]
        coin, cp = cash.wtx.outputs[j], paper.wtx.outputs[j]
        b = TransactionBuilder(notary)
        b.add_input_state(StateAndRef(coin, StateRef(cash.id, j)))
        b.add_input_state(StateAndRef(cp, StateRef(paper.id, j)))
        b.add_output_state(
            CashState(coin.data.amount, seller.owning_key), CASH_CONTRACT
        )
        b.add_output_state(cp.data.with_owner(buyer.owning_key), CP_CONTRACT)
        b.add_command(CashMove(), buyer.owning_key)
        b.add_command(CPMove(), seller.owning_key)
        b.set_time_window(window)
        trades.append(
            b.sign_initial_transaction(buyer_kp.private, seller_kp.private)
        )
    return trades, [cash, paper]
