"""Single-input, single-output Cash moves, one signer each.

Corda tools/loadtest NotaryTest.kt: Cash issued by a bank, then moved
between parties through the notary. Each chunk of frames consumes the
outputs of ONE issuing transaction (the bank issues `len` coins in one
go); the frames themselves are the moves."""

from __future__ import annotations

_PARTIES: dict = {}


def register() -> None:
    """Import the CorDapps whose states and commands the frames carry,
    so the decoder knows their tags."""
    import corda_tpu.finance.cash  # noqa: F401


def parties(seed: int, params: dict):
    key = (seed, params["owners"], params["scheme_id"])
    if key not in _PARTIES:
        from corda_tpu.core.identity import Party

        from benchmark import fixture

        sid = params["scheme_id"]
        bank = fixture.keypair(seed, "bank", sid)
        owners = [
            fixture.keypair(seed, f"owner{k}", sid)
            for k in range(params["owners"])
        ]
        _PARTIES[key] = (
            fixture.notary_party(seed, params["notary_scheme_id"])[0],
            (Party("O=Bank,L=London,C=GB", bank.public), bank),
            [(Party(f"O=Owner{k},L=Paris,C=FR", kp.public), kp)
             for k, kp in enumerate(owners)],
        )
    return _PARTIES[key]


def build(params: dict, seed: int, start: int, n: int):
    """(moves, [issuing transaction]) for frames start .. start+n."""
    from corda_tpu.core.contracts import Amount, Issued, StateAndRef, StateRef
    from corda_tpu.core.identity import PartyAndReference
    from corda_tpu.core.transactions import TransactionBuilder
    from corda_tpu.finance.cash import (
        CASH_CONTRACT, CashIssue, CashMove, CashState,
    )

    notary, (bank, bank_kp), owners = parties(seed, params)
    token = Issued(PartyAndReference(bank, b"\x01"), params["currency"])
    m = len(owners)
    ib = TransactionBuilder(notary)
    for j in range(n):
        k = start + j
        ib.add_output_state(
            CashState(Amount(100 + k % 9973, token),
                      owners[k % m][0].owning_key),
            CASH_CONTRACT,
        )
    ib.add_command(CashIssue(start), bank.owning_key)
    issue = ib.sign_initial_transaction(bank_kp.private)
    moves = []
    for j, out in enumerate(issue.wtx.outputs):
        k = start + j
        owner, owner_kp = owners[k % m]
        sb = TransactionBuilder(notary)
        sb.add_input_state(StateAndRef(out, StateRef(issue.id, j)))
        sb.add_output_state(
            CashState(out.data.amount, owners[(k + 1) % m][0].owning_key),
            CASH_CONTRACT,
        )
        sb.add_command(CashMove(), owner.owning_key)
        moves.append(sb.sign_initial_transaction(owner_kp.private))
    return moves, [issue]
