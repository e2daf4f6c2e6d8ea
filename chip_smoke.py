#!/usr/bin/env python3
"""Chip smoke: the batching notary end to end on the TPU.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # sharded notary + mesh verifier

One chip drives the deployment BASELINE names — a single batching
notary verifying p256-signed Cash spends — through the node's own
entry points: a MockNetwork notary whose services carry
TpuBatchVerifier(batch_sizes=(4096,)), fed by BatchingNotaryService
.submit and drained by .flush at production depth (16,384 spends per
flush, 4,096-signature chunks, uniqueness on the commit-log store
preloaded with 1,000,000 committed states). 1/16 of the spends carry a
tampered signature and 1/16 re-spend a committed state; every answer
must equal the outcome known from construction, and 1,024 signature
rows spread over every chunk and tile residue are re-checked on the
CPU reference. A few CashIssueFlow/CashPaymentFlow round trips reach
the chip through the flow entry, and tpu_selfcheck puts all three
schemes through the Pallas path bit-exact against the CPU.

`--chips 4` runs only the multi-chip path: the same flush over four
commit-plane shards with one device-pinned verifier per chip, and one
4096x4 chunk through the mesh verifier.

Nothing here falls back: the notary runs with degraded_fallback=False,
a first device that is not a TPU fails the run, and the native codec
is rebuilt from committed source before any phase. Earlier lines are
for the builder (flush walls are information, not metrics); the last
line is one JSON object. The phases are functions so tier-1 runs them
on the CPU at a tiny size (tests/test_chip_smoke.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import re
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

TAMPER, CONFLICT, VALID = "invalid-signature", "conflict", "signed"
TILE = 128   # the Pallas ladder's lane block
SEED = 21
WORKDIR = os.path.join(REPO, ".smoke")   # parent of the store's tmp dir


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the run drives. The defaults are the deployment's; only
    tier-1 builds a smaller one (tests/test_chip_smoke.py)."""

    spends: int = 16384          # flush depth (spends per flush)
    chunk: int = 4096            # signature chunk (verifier batch size)
    states: int = 1_000_000      # committed states preloaded
    selfcheck_n: int = 256


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:   # explicit raise: must fire under python -O too
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# -- set-up ------------------------------------------------------------------


def build_native() -> str:
    """Rebuild the CTS/SHA-256 extension from committed source and load
    it: a binary carried over from another machine is never trusted."""
    from corda_tpu import native
    from corda_tpu.native import build

    path = build.build(verbose=False)
    native.reset_cache()
    check(native.get() is not None, f"native codec {path} did not load")
    return path


class CacheWatch:
    """Counts jax persistent-compile-cache hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def build_network(verifier, seed: int):
    """Notary, issuer and spender; the parties sign with p256, the
    scheme of the deployment (the node default is ed25519)."""
    from corda_tpu.crypto import schemes
    from corda_tpu.testing.mock_network import MockNetwork

    net = MockNetwork(seed=seed, batch_verifier=verifier)
    notary = net.create_notary("Notary", batching=True)
    p256 = schemes.ECDSA_SECP256R1_SHA256
    bank = net.create_node("Bank", scheme_id=p256)
    alice = net.create_node("Alice", scheme_id=p256)
    return net, notary, bank, alice


class _Requester:
    name = "O=Preload"


def preload_store(path: str, shards: int, states: int, seed: int,
                  spent_refs=()):
    """A commit-log uniqueness store holding `states` committed states
    (random refs) plus `spent_refs`, folded into the mmap index."""
    from corda_tpu.core.contracts import StateRef
    from corda_tpu.crypto.hashes import SecureHash
    from corda_tpu.node.statestore import ShardedCommitLogUniquenessProvider

    rng = random.Random(seed)
    store = ShardedCommitLogUniquenessProvider(path, shards)
    who = _Requester()
    step = 32 * 2048    # 2,048 transactions of 32 inputs per commit
    for off in range(0, states, step):
        n = min(step, states - off)
        refs = [StateRef(SecureHash(rng.randbytes(32)), 0) for _ in range(n)]
        entries = [
            (refs[i:i + 32], SecureHash(rng.randbytes(32)), who)
            for i in range(0, n, 32)
        ]
        check(not any(store.commit_many(entries)), "preload conflicted")
    spender = SecureHash(rng.randbytes(32))
    check(
        not any(store.commit_many([([r], spender, who) for r in spent_refs])),
        "preload of spent refs conflicted",
    )
    store.compact_all()
    return store


def schedule(n: int, seed: int) -> list[str]:
    """The construction-known outcome per spend: exactly n//16 tampered
    signatures and n//16 re-spends, at seeded positions."""
    kinds = [TAMPER] * (n // 16) + [CONFLICT] * (n // 16)
    kinds += [VALID] * (n - len(kinds))
    random.Random(seed).shuffle(kinds)
    return kinds


def make_spends(notary, bank, alice, kinds):
    """One pre-signed single-input Cash spend per entry of `kinds`
    (bench.py's notary fixture shape). Returns (spends, refs the
    CONFLICT spends consume — to be committed before the flush)."""
    from corda_tpu.core.contracts import Amount, Issued, StateRef
    from corda_tpu.core.identity import PartyAndReference
    from corda_tpu.core.transactions import SignedTransaction
    from corda_tpu.core.transactions import TransactionBuilder
    from corda_tpu.finance.cash import (
        CASH_CONTRACT, CashIssue, CashMove, CashState,
    )

    token = Issued(PartyAndReference(bank.party, b"\x01"), "USD")
    spends, spent = [], []
    for i, kind in enumerate(kinds):
        ib = TransactionBuilder(notary.party)
        ib.add_output_state(
            CashState(Amount(100, token), alice.party.owning_key),
            CASH_CONTRACT,
        )
        ib.add_command(CashIssue(i), bank.party.owning_key)
        issue = bank.services.sign_initial_transaction(ib)
        notary.services.record_transactions([issue])
        alice.services.record_transactions([issue])
        ref = StateRef(issue.id, 0)
        sb = TransactionBuilder(notary.party)
        sb.add_input_state(alice.vault.state_and_ref(ref))
        sb.add_output_state(
            CashState(Amount(100, token), bank.party.owning_key),
            CASH_CONTRACT,
            notary.party,
        )
        sb.add_command(CashMove(), alice.party.owning_key)
        stx = alice.services.sign_initial_transaction(sb)
        if kind == TAMPER:
            sig = stx.sigs[0]
            raw = bytearray(sig.signature)
            raw[-3] ^= 0x01      # inside s: DER stays well-formed
            stx = SignedTransaction(
                stx.wtx, (dataclasses.replace(sig, signature=bytes(raw)),)
            )
        elif kind == CONFLICT:
            spent.append(ref)
        spends.append(stx)
    return spends, spent


def install_notary(notary, store, depth: int, shards: int = 1,
                   shard_verifiers=None):
    """Serve the notary node from `store` at flush depth `depth`, with
    no degraded CPU fallback: a device fault fails the flush."""
    from corda_tpu.node.notary import BatchingNotaryService

    svc = BatchingNotaryService(
        notary.services,
        store,
        max_batch=depth,
        shards=shards,
        shard_verifiers=shard_verifiers,
        shard_queue_depth=depth,
        degraded_fallback=False,
    )
    notary.services.notary_service = svc
    notary.ticks[:] = [svc.tick]   # the pump tick is the batch deadline
    return svc


def outcome(answer) -> str:
    if hasattr(answer, "by"):
        return VALID
    kind = getattr(answer, "kind", type(answer).__name__)
    if kind == "invalid-transaction" and "invalid signature" in str(
        getattr(answer, "message", "")
    ):
        return TAMPER
    return kind


# -- phases ------------------------------------------------------------------


def phase_flush(svc, spends, kinds, requester) -> dict:
    """Submit every spend, drain them with ONE flush, and hold every
    answer to its construction-known outcome."""
    futs = [svc.submit(stx, requester) for stx in spends]
    t0 = time.perf_counter()
    svc.flush()
    wall = time.perf_counter() - t0
    got = [outcome(f.result()) for f in futs]
    wrong = [i for i, (g, k) in enumerate(zip(got, kinds)) if g != k]
    check(
        not wrong,
        f"{len(wrong)} answers differ from construction, first "
        f"{[(i, got[i], kinds[i]) for i in wrong[:5]]}",
    )
    degraded = svc.metrics.counter("Notary.DegradedFlushes").count
    check(degraded == 0, f"Notary.DegradedFlushes = {degraded}")
    return {
        "answers": len(got),
        "accepts": got.count(VALID),
        "rejects": got.count(TAMPER),
        "conflicts": got.count(CONFLICT),
        "degraded_flushes": degraded,
        "flush_wall_s": wall,
    }


def crosscheck_rows(n_rows: int, chunk: int, want: int) -> list[int]:
    """At least min(want, n_rows) distinct row indices covering every
    chunk and, within each, every tile residue equally."""
    rng = random.Random(3)
    chunks = -(-n_rows // chunk)
    per_residue = -(-want // (chunks * min(TILE, chunk)))
    rows = []
    for c in range(chunks):
        lo, hi = c * chunk, min((c + 1) * chunk, n_rows)
        for residue in range(min(TILE, hi - lo)):
            cands = range(lo + residue, hi, TILE)
            rows += rng.sample(cands, min(per_residue, len(cands)))
    return sorted(rows)


def phase_crosscheck(verifier, spends, kinds, chunk: int,
                     want: int = 1024) -> dict:
    """Verify every spend's signature row on the device again (in
    `chunk`-row tiles of the dispatch), hold it to construction, and
    re-check a sample spread over every chunk and tile residue on the
    CPU reference."""
    from corda_tpu.crypto.batch_verifier import CpuBatchVerifier

    reqs = [r for stx in spends for r in stx.signature_requests()]
    check(len(reqs) == len(spends), "expected one signature per spend")
    dev = verifier.verify_batch(reqs)
    check(
        dev == [k != TAMPER for k in kinds],
        "device verify disagrees with construction",
    )
    rows = crosscheck_rows(len(reqs), chunk, want)
    check(len(rows) >= min(want, len(reqs)), "cross-check sample short")
    cpu = CpuBatchVerifier().verify_batch([reqs[i] for i in rows])
    bad = [i for i, ok in zip(rows, cpu) if dev[i] != ok]
    check(not bad, f"device != CPU at rows {bad[:10]}")
    return {"rows": len(rows), "chunks": -(-len(reqs) // chunk)}


def phase_flows(net, notary, bank, rounds: int = 3) -> dict:
    """CashIssueFlow / CashPaymentFlow round trips through FinalityFlow
    on the same network: the flow entry reaches the same verifier. A
    fresh holder, so no coin the flush already spent is picked."""
    from corda_tpu.finance.cash import CashIssueFlow, CashPaymentFlow

    holder = net.create_node("Carol", scheme_id=bank.scheme_id)
    notarised = 0
    for r in range(rounds):
        # distinct amounts: an identical issuance would be the same
        # transaction, recorded once
        for node, flow in (
            (bank, CashIssueFlow(1000 + r, "USD", holder.party,
                                 notary.party)),
            (holder, CashPaymentFlow(1000 + r, "USD", bank.party)),
        ):
            stx = node.run_flow(flow)
            # an issuance consumes nothing, so only spends need the
            # notary's signature
            check(
                not stx.wtx.inputs
                or any(s.by == notary.party.owning_key for s in stx.sigs),
                f"{type(flow).__name__} came back without the notary "
                "signature",
            )
            notarised += bool(stx.wtx.inputs)
    check(notarised == rounds, f"{notarised} of {rounds} payments notarised")
    return {"flows": 2 * rounds, "notarised": notarised}


def phase_selfcheck(n: int, batch: int, allow_cpu: bool = False) -> dict:
    """p256, k1 and ed25519, each on its default ladder, bit-exact vs
    the CPU reference; `batch` = the flush's chunk, so p256 reuses the
    program the flush compiled."""
    from corda_tpu.testing import tpu_selfcheck

    return tpu_selfcheck.run(n=n, batch_size=batch, allow_cpu=allow_cpu)


def phase_mesh(devices, spends, kinds, chunk: int) -> dict:
    """One chunk of `chunk` rows per device through the mesh verifier
    (shard_map'd ladder), held to construction and the CPU."""
    from corda_tpu.crypto.batch_verifier import TpuBatchVerifier
    from corda_tpu.parallel import mesh as meshlib

    n = chunk * len(devices)
    check(len(spends) >= n, f"mesh phase needs {n} spends")
    v = TpuBatchVerifier(batch_sizes=(n,), mesh=meshlib.make_mesh(devices))
    return phase_crosscheck(v, spends[:n], kinds[:n], chunk)


# -- reporting ---------------------------------------------------------------


def compile_report(verifiers) -> list[str]:
    from corda_tpu.crypto import schemes
    from corda_tpu.utils import perf as perflib

    names = {
        schemes.ECDSA_SECP256R1_SHA256: "p256",
        schemes.ECDSA_SECP256K1_SHA256: "k1",
        schemes.EDDSA_ED25519_SHA512: "ed25519",
    }
    lines = []
    for key, row in perflib.get_kernel_accounting().snapshot()["keys"].items():
        sid, batch = map(int, re.fullmatch(r"scheme(\d+)/batch(\d+)",
                                           key).groups())
        if row["compiles"]:
            lines.append(
                f"compile {names.get(sid, sid)}@{batch}: "
                f"{row['compile_seconds']} s over {row['compiles']} "
                "first call(s)"
            )
    for v in verifiers:
        for (sid, batch), origin in sorted(v.kernel_origins().items()):
            lines.append(f"program {names.get(sid, sid)}@{batch}: {origin}")
    return lines


# -- entry -------------------------------------------------------------------


def run_one_chip(sizes: Sizes, workdir: str, verbose=log,
                 allow_cpu: bool = False) -> None:
    from corda_tpu.crypto.batch_verifier import TpuBatchVerifier

    verifier = TpuBatchVerifier(batch_sizes=(sizes.chunk,))
    net, notary, bank, alice = build_network(verifier, SEED)
    kinds = schedule(sizes.spends, SEED)
    t0 = time.perf_counter()
    spends, spent = make_spends(notary, bank, alice, kinds)
    verbose(f"fixture: {len(spends)} signed spends in "
            f"{time.perf_counter() - t0:.1f} s")
    with tempfile.TemporaryDirectory(dir=workdir) as tmp:
        t0 = time.perf_counter()
        store = preload_store(tmp, 1, sizes.states, SEED, spent)
        verbose(f"store: commitlog, {store.committed_count} committed "
                f"states ({sizes.states} preloaded + {len(spent)} to "
                f"re-spend) in {time.perf_counter() - t0:.1f} s")
        try:
            svc = install_notary(notary, store, sizes.spends)
            res = phase_flush(svc, spends, kinds, alice.party)
            verbose(
                f"flush: {res['answers']} answers == construction "
                f"(accepts {res['accepts']}, invalid signature "
                f"{res['rejects']}, conflicts {res['conflicts']}); "
                f"Notary.DegradedFlushes = {res['degraded_flushes']}; "
                f"depth {sizes.spends}, chunk {sizes.chunk}; flush wall "
                f"{res['flush_wall_s']:.3f} s (information only)"
            )
            res = phase_crosscheck(verifier, spends, kinds, sizes.chunk)
            verbose(f"cpu cross-check: {res['rows']} rows over "
                    f"{res['chunks']} chunks and every tile residue ok")
            res = phase_flows(net, notary, bank)
            verbose(f"flows: {res['flows']} Cash issue/payment flows, "
                    f"{res['notarised']} payments notarised through "
                    "FinalityFlow")
            degraded = svc.metrics.counter("Notary.DegradedFlushes").count
            check(degraded == 0, f"Notary.DegradedFlushes = {degraded}")
        finally:
            store.close()
    res = phase_selfcheck(sizes.selfcheck_n, sizes.chunk, allow_cpu)
    verbose(f"tpu_selfcheck: n={res['n']} p256/k1/ed25519 bit-exact vs "
            f"CPU (accepts {res['accepts']})")
    for line in compile_report([verifier]):
        verbose(line)


def run_four_chips(sizes: Sizes, workdir: str, devices,
                   verbose=log) -> None:
    from corda_tpu.crypto.batch_verifier import (
        TpuBatchVerifier, per_shard_verifiers,
    )
    from corda_tpu.utils import device_telemetry as devlib

    shared = TpuBatchVerifier(batch_sizes=(sizes.chunk,))
    net, notary, bank, alice = build_network(shared, SEED)
    kinds = schedule(sizes.spends, SEED)
    spends, spent = make_spends(notary, bank, alice, kinds)
    shard_vs = per_shard_verifiers(
        len(devices), batch_sizes=(sizes.chunk,), devices=devices
    )
    with tempfile.TemporaryDirectory(dir=workdir) as tmp:
        store = preload_store(tmp, len(devices), sizes.states, SEED,
                              spent)
        verbose(f"store: commitlog over {len(devices)} shards, "
                f"{store.committed_count} committed states")
        svc = install_notary(notary, store, sizes.spends,
                             shards=len(devices), shard_verifiers=shard_vs)
        try:
            res = phase_flush(svc, spends, kinds, alice.party)
        finally:
            svc.stop()
            store.close()
    verbose(
        f"sharded flush: {res['answers']} answers == construction over "
        f"{len(devices)} shards (accepts {res['accepts']}, invalid "
        f"signature {res['rejects']}, conflicts {res['conflicts']}); "
        f"Notary.DegradedFlushes = {res['degraded_flushes']}; flush wall "
        f"{res['flush_wall_s']:.3f} s (information only)"
    )
    per_dev = devlib.get_device_accounting().snapshot()["devices"]
    for d in devices:
        row = per_dev.get(d.id, {})
        verbose(f"device {d.id}: {row.get('dispatches', 0)} dispatches, "
                f"{row.get('requests', 0)} rows")
        check(row.get("dispatches", 0) > 0, f"device {d.id} got no work")
    res = phase_mesh(devices, spends, kinds, sizes.chunk)
    verbose(f"mesh verify: {sizes.chunk * len(devices)} rows over "
            f"{len(devices)} devices == construction, {res['rows']} rows "
            "vs CPU ok")
    for line in compile_report(shard_vs):
        verbose(line)


def parse_args(argv):
    p = argparse.ArgumentParser(prog="chip_smoke.py")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    sys.path.insert(0, REPO)
    try:
        import corda_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the corda_tpu package is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    import jax

    from corda_tpu.crypto import pallas_ec
    from corda_tpu.utils import jaxenv

    devices = jax.devices()
    d0 = devices[0]
    log(f"jax {jax.__version__}; devices: platform {d0.platform}, kind "
        f"{d0.device_kind}, count {len(devices)}")
    if d0.platform != "tpu":
        print(f"chip_smoke: first device is {d0.platform!r}, not a TPU",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    os.makedirs(WORKDIR, exist_ok=True)
    try:
        path = build_native()
        log(f"native codec: built from cts_hash.cpp and loaded ({path})")
        jaxenv.enable_compile_cache()
        cache = CacheWatch()
        from corda_tpu.crypto import aot_store

        log(f"compile cache: {jaxenv.compile_cache_dir()}; AOT store: "
            f"{aot_store.store_dir()}")
        check(pallas_ec.use_pallas_ladder(), "Pallas ladder not selected")
        log("use_pallas_ladder(): True")
        if args.chips == 1:
            run_one_chip(Sizes(), WORKDIR)
        else:
            run_four_chips(Sizes(), WORKDIR, devices)
        log(f"compile cache: {cache.hits} hits, {cache.misses} misses")
    except Exception as e:   # noqa: BLE001 - any failed phase fails the run
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": d0.platform,
            "kind": d0.device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
