"""Headline benchmark: ECDSA secp256r1 verifies/sec through the SPI.

North star (BASELINE.md): >= 50,000 ECDSA-p256 verifies/sec on one TPU
v5e chip through the BatchSignatureVerifier SPI, bit-exact
accept/reject vs the CPU reference semantics.

Prints one JSON line per metric: {"metric", "value", "unit",
"vs_baseline"}. The DEFAULT run (no BENCH_METRIC) measures the whole
BASELINE.md table — mixed, merkle, notary, ingest, plus a reduced-n
kernel parity refresh — inside ONE wall-clock budget (BENCH_TIME_BUDGET
seconds, default 900), trimming then skipping secondaries as the
budget tightens, and ALWAYS prints the headline p256 line LAST, so a
driver that parses the final line records the headline while the full
table lands in the same capture. The p256 line carries `spread`
(min/max over the timed reps).

Every measured run needs a TPU: a metric process whose first jax
device is not a TPU exits non-zero before measuring anything, and the
default run's parent never imports jax (each metric child owns the
chip while it runs). Only the `--quick` smokes run on the CPU.

BENCH_METRIC restricts to one measurement:
  p256            — the headline ECDSA-p256 batch
  mixed           — even thirds ed25519 / secp256k1 / p256 in one call
  merkle          — FilteredTransaction shape: partial Merkle proof
                    (native host SHA-256) + p256 signature per item
  notary          — BatchingNotaryService serving rate
  ingest          — wire-ingest rate: CTS decode + cold Merkle id +
                    signature staging per received transaction (host
                    only; the flush metrics never see this cost)
  ingest_pipelined — the same work through node/ingest.py (sharded
                    decode pool, batched Merkle-id pass, content-keyed
                    digest + hot-frame caches); records vs_serial
                    measured on the same fixture in the same process

  trace           — stage-attributed hot path: wire frames through
                    IngestPipeline + a BatchingNotaryService flush with
                    tracing on, recording the decode / merkle / stage /
                    dispatch / kernel / commit seconds breakdown plus
                    the measured tracing overhead vs an untraced run on
                    the same fixture
  qos             — overload serving through the QoS plane
                    (node/qos.py): goodput and admitted p99 at 2x the
                    measured no-overload capacity, adaptive controller
                    on vs off, shed fraction — CPU fixture, real time
  health          — health-plane steady-state overhead on the notary
                    CPU rig (utils/health.py: heartbeats + watchdog +
                    alert rules ticked every flush, A/B vs the bare
                    flush) plus a canary round trip proven through the
                    real hot path (timed separately — the probe's
                    build+sign cost amortises at the production
                    cadence, not per flush) — CPU fixture, real time
  perf            — perf-attribution plane (utils/perf.py): sampling-
                    profiler overhead A/B on the notary CPU flush
                    (acceptance <= 2%) plus the jit-retrace counter
                    proven stable-at-zero on warm shapes and counting
                    a forced fresh-shape retrace — CPU fixture
  device          — device-telemetry plane (utils/device_telemetry.py):
                    plane-tick overhead A/B on the notary CPU flush
                    (acceptance <= 2%, REQUIRED-TRUE
                    device_plane_overhead_ok) plus the capacity
                    model's binding-constraint proof — on the CPU rig
                    it must name host_pump — CPU fixture
  wire            — wire & gateway telemetry plane (utils/
                    wire_telemetry.py): fabric->ingest frames/s over a
                    real localhost TCP FabricEndpoint pair with the
                    plane attached (the headline), interleaved A/B
                    plane overhead (acceptance <= 2%, REQUIRED-TRUE
                    wire_plane_overhead_ok) plus gateway requests/s
                    against a live NodeWebServer under concurrent
                    notarisation load with the per-endpoint accounting
                    proven to have counted every request
                    (gateway_accounted_ok) — CPU fixture, real sockets

`python bench.py --quick ingest` runs tiny serial + pipelined ingest
records in one CPU-safe process (tier-1 smoke of the perf plumbing);
`--quick trace` smokes the traced hot path, asserting the stage
breakdown sums to ~the batch wall and tracing overhead stays under 5%.
  statestore      — billion-state uniqueness store (node/
                    statestore.py): sustained commit_many rate of the
                    commit-log + mmap-index backend vs the sqlite
                    backend at a pre-populated committed set
                    (BENCH_STATESTORE_STATES, CI-scaled; =10000000 for
                    the 10^7 acceptance record), probe p99 proven flat
                    as the set grows 10x, and accept/reject bit-exact
                    vs sqlite — three REQUIRED-TRUE verdicts ride
                    bench_history --gate
  montmul         — device-resident A/B of the MXU (batched int8
                    Toeplitz matmul) vs VPU (shifted accumulate)
                    Montgomery-multiply formulations (experiment rig,
                    not part of the default table)
  parity          — reduced-n windowed+plain kernel parity refresh;
                    rewrites KERNEL_PARITY.json (TPU backend only)
  all  (default)  — everything, p256 last, under BENCH_TIME_BUDGET
"""

import json
import os
import random
import sys
import time

BASELINE = 50_000.0  # verifies/sec target per BASELINE.json
MERKLE_TARGET = 45_000.0  # FilteredTransaction metric's own target


def _timed_rates(run_once, batch: int, iters: int) -> list[float]:
    """Per-iteration rates, one independent timing each."""
    rates = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        run_once()
        rates.append(batch / (time.perf_counter() - t0))
    return rates


def _median(rates: list[float]) -> float:
    """Lower median — ONE convention for every metric."""
    ordered = sorted(rates)
    return ordered[(len(ordered) - 1) // 2]


def _median_rate(run_once, batch: int, iters: int) -> float:
    """batch/median(iteration wall): one slow iteration inside a
    pooled-time loop would drag the whole record, while the median of
    independent iterations reports the sustained rate. Shared by the
    per-item
    verification metrics (spi, merkle); the notary metric deliberately
    pools time (a serving rate is sustained throughput) and the
    montmul A/B reports best-of-reps."""
    return _median(_timed_rates(run_once, batch, iters))


def _merkle_metric(batch: int, iters: int) -> dict:
    """FilteredTransaction-shape verification (BASELINE.md row:
    'FilteredTransaction Merkle + multi-sig batch verify'): each item is
    a 6-of-64-leaf partial Merkle proof (native SHA-256 kernels on the
    host) plus one notary signature over the root drained through the
    TPU SPI."""
    import random as _r

    from corda_tpu.crypto import schemes
    from corda_tpu.crypto.batch_verifier import (
        TpuBatchVerifier,
        VerificationRequest,
    )
    from corda_tpu.crypto.hashes import SecureHash
    from corda_tpu.crypto.merkle import (
        PartialMerkleTree,
        merkle_root,
        verify_proofs,
    )

    rng = _r.Random(7)
    keys = [
        schemes.generate_keypair(
            schemes.ECDSA_SECP256R1_SHA256, seed=rng.getrandbits(64)
        )
        for _ in range(8)
    ]
    # fixture tiling, as in _requests: per-item signing dominates the
    # fixture build and none of it is measured work — proof kernels and
    # the SPI treat repeated rows identically to unique ones
    tile = max(1, int(os.environ.get("BENCH_TILE", "8")))
    unique = -(-batch // tile)
    items = []
    for i in range(unique):
        leaves = [SecureHash.sha256(rng.randbytes(64)) for _ in range(64)]
        included = [leaves[j] for j in sorted(rng.sample(range(64), 6))]
        pmt = PartialMerkleTree.build(leaves, included)
        root = merkle_root(leaves)
        kp = keys[i % 8]
        sig = kp.private.sign(root.bytes_)
        items.append((pmt, root, included, kp.public, sig))
    items = (items * tile)[:batch]

    chunk = min(int(os.environ.get("BENCH_CHUNK", "4096")), batch)
    verifier = TpuBatchVerifier(batch_sizes=(chunk,))
    # request/proof lists build ONCE (matching _spi_metric): object
    # construction is fixture work, not the measured verification
    reqs = [
        VerificationRequest(pub, sig, root.bytes_)
        for _, root, _, pub, sig in items
    ]
    proofs = [(pmt, root, incl) for pmt, root, incl, _, _ in items]

    def run_once() -> None:
        # explicit raises, not asserts: the proof verification IS the
        # measured work and must survive python -O. Signatures dispatch
        # to the device FIRST (async), then the native bulk proof kernel
        # (ONE C call, SHA-NI) runs on host while the device computes;
        # one collect at the end.
        handle = verifier.verify_batch_async(reqs)
        if not all(verify_proofs(proofs)):
            raise SystemExit("merkle proof failed — bench aborted")
        if not all(handle.result()):
            raise SystemExit("signature verify failed — bench aborted")

    run_once()                       # warm-up: compile + correctness
    value = round(_median_rate(run_once, batch, iters), 1)
    return {
        "metric": "filtered_tx_merkle_plus_sig_verifies_per_sec",
        "value": value,
        "unit": "verifies/s",
        "vs_baseline": round(value / BASELINE, 3),
        # this metric's OWN target (BASELINE.md north-star table,
        # round-5): the merkle+sig composite is not the raw-sig
        # headline and is judged against its own line
        "target": MERKLE_TARGET,
        "vs_target": round(value / MERKLE_TARGET, 3),
    }


def _notary_fixture(batch: int, batch_verifier=None):
    """`batch` pre-signed single-input Cash spends against a batching
    notary MockNode — the shared fixture for the notary serving metric
    and its shard-scaling sweep (one build, every configuration)."""
    from corda_tpu.core.transactions import TransactionBuilder
    from corda_tpu.finance.cash import (
        CASH_CONTRACT,
        CashIssue,
        CashMove,
        CashState,
    )
    from corda_tpu.testing.mock_network import MockNetwork
    from corda_tpu.core.contracts import Amount, Issued, StateRef
    from corda_tpu.core.identity import PartyAndReference

    net = MockNetwork(seed=5, batch_verifier=batch_verifier)
    notary = net.create_notary("Notary", batching=True)
    bank = net.create_node("Bank")
    alice = net.create_node("Alice")

    token = Issued(PartyAndReference(bank.party, b"\x01"), "USD")
    spends = []
    for i in range(batch):
        ib = TransactionBuilder(notary.party)
        ib.add_output_state(
            CashState(Amount(100, token), alice.party.owning_key),
            CASH_CONTRACT,
        )
        ib.add_command(CashIssue(i), bank.party.owning_key)
        issue_stx = bank.services.sign_initial_transaction(ib)
        # the notary resolves spend inputs from its tx storage
        notary.services.record_transactions([issue_stx])
        alice.services.record_transactions([issue_stx])
        sb = TransactionBuilder(notary.party)
        sb.add_input_state(
            alice.vault.state_and_ref(StateRef(issue_stx.id, 0))
        )
        sb.add_output_state(
            CashState(Amount(100, token), bank.party.owning_key),
            CASH_CONTRACT,
            notary.party,
        )
        sb.add_command(CashMove(), alice.party.owning_key)
        spends.append(alice.services.sign_initial_transaction(sb))
    return net, notary, alice, spends


def _notary_rate(
    notary, alice, spends, batch: int, iters: int,
    shards: int, chunk: int,
    verifier=None, report_phases: bool = False,
) -> float:
    """Measured notarisations/s for ONE commit-plane configuration:
    every spend queued (routed to its owning shard when sharded), then
    drained by one flush — a dispatch-all-then-consume wave over the
    shards — through the real service code."""
    from corda_tpu.node.notary import (
        BatchingNotaryService,
        InMemoryUniquenessProvider,
        ShardedUniquenessProvider,
    )
    from corda_tpu.utils.perf import flush_phase_seconds

    shard_verifiers = None
    if shards > 1 and verifier is not None:
        # per-device dispatch only pays when there is more than one
        # device: N unpinned copies on one chip would just multiply jit
        # caches while queueing on the same device as the shared SPI
        import jax

        from corda_tpu.crypto.batch_verifier import per_shard_verifiers

        devices = jax.devices()
        if len(devices) > 1:
            shard_verifiers = per_shard_verifiers(
                shards, batch_sizes=(chunk,), devices=devices
            )

    def fresh_uniqueness():
        return (
            ShardedUniquenessProvider(shards) if shards > 1
            else InMemoryUniquenessProvider()
        )

    svc = BatchingNotaryService(
        notary.services,
        fresh_uniqueness(),
        max_batch=batch,               # one deep flush per pass
        shards=shards,
        shard_verifiers=shard_verifiers,
        shard_queue_depth=batch,       # the bench fills the whole plane
    )

    def run_once() -> None:
        # fresh uniqueness per pass so re-notarising is conflict-free
        svc.uniqueness = fresh_uniqueness()
        futs = [svc.submit(stx, alice.party) for stx in spends]
        svc.flush()
        for fut in futs:
            sig = fut.result()   # raises if a NotaryError leaked
            if not hasattr(sig, "by"):
                raise SystemExit(f"notarisation failed: {sig}")

    try:
        run_once()                    # warm-up: compile + correctness
        # the timed reps' phases: the FlushPhase timers past warm-up
        warm_phases = flush_phase_seconds(svc.metrics)
        # the staged fixture (pre-signed spends + their backchain) is a
        # large STATIC heap; freeze it out of the collector's
        # generations so the flush-time allocations don't drag it
        # through gen-2 sweeps
        import gc

        gc.collect()
        gc.freeze()
        try:
            t0 = time.perf_counter()
            for _ in range(iters):
                run_once()
            dt = time.perf_counter() - t0
        finally:
            # even on a failed rep: frozen fixture objects are immortal
            # to the collector, and the default run's later metrics
            # must not pay the leaked memory
            gc.unfreeze()
        phases = {
            k: row["total_s"] - warm_phases.get(k, {}).get("total_s", 0.0)
            for k, row in flush_phase_seconds(svc.metrics).items()
        }
        total = sum(phases.values())
        if report_phases and total > 0:
            # per-phase share of the timed reps' flush wall
            print(
                "notary flush phases "
                + " ".join(
                    f"{k}={v * 1e6 / (batch * iters):.1f}us/tx"
                    f"({100 * v / total:.0f}%)"
                    for k, v in sorted(phases.items(), key=lambda kv: -kv[1])
                ),
                file=sys.stderr,
            )
        return batch * iters / dt
    finally:
        svc.stop()                    # drops its GC pacing hold


def _notary_metric(batch: int, iters: int) -> dict:
    """Batching-notary serving rate (SURVEY §7 Phase 4) over the
    SHARDED commit plane (round 6): `batch` pre-signed single-input
    Cash spends routed onto BENCH_SHARDS per-shard flush pipelines
    (default 4; 1 = the classic single-queue plane) and drained by one
    flush — per-shard SPI dispatches (per-device when the process sees
    several chips), per-tx contract verification, partitioned
    uniqueness commit and notary signing, scattering signed replies.
    BENCH_SHARD_SWEEP (comma list, default "1,<shards>") measures the
    same fixture at each shard count so the record carries scaling
    rather than a single point. The flush depth is EXACTLY BENCH_BATCH:
    the former hard 16384 clamp is gone now that depth spreads across
    shards — `depth_saturation` stays in the record (false unless a
    per-shard queue bound ever clamps again)."""
    from corda_tpu.crypto.batch_verifier import TpuBatchVerifier

    chunk = min(int(os.environ.get("BENCH_CHUNK", "4096")), batch)
    shards, sweep = _shard_sweep_config()
    # chunk < batch => the SPI pipelines each shard's flush across
    # chunks: the host stages chunk k+1 while the device verifies k
    verifier = TpuBatchVerifier(batch_sizes=(chunk,))
    net, notary, alice, spends = _notary_fixture(
        batch, batch_verifier=verifier
    )
    rates: dict[str, float] = {}
    for n in sweep:
        rates[str(n)] = round(
            _notary_rate(
                notary, alice, spends, batch, iters,
                shards=n, chunk=chunk,
                verifier=verifier, report_phases=(n == shards),
            ),
            1,
        )
    # the headline value is the best swept configuration — the sweep
    # stays in the record, so the winning shard count is attributable
    # (and a host where threading loses never records a regression the
    # operator would not deploy)
    best = max(rates, key=lambda k: rates[k])
    rate = rates[best]
    out = {
        "metric": "batching_notary_notarisations_per_sec",
        "value": rate,
        "unit": "notarisations/s",
        "vs_baseline": round(rate / BASELINE, 3),
        "flush_depth": batch,   # actual queued depth this run measured
        "shards": int(best),
        "shards_requested": shards,
        "per_shard_depth": -(-batch // int(best)),
        # the 16384 clamp is lifted: the measured flush IS the
        # requested depth, so saturation only ever reads true again if
        # a future bound clamps it (kept for bench_history continuity)
        "depth_saturation": False,
    }
    if len(rates) > 1:
        out["shard_sweep"] = rates
        base = rates.get("1")
        if base:
            out["scaling_vs_1shard"] = round(rate / base, 3)
    return out


def _shard_sweep_config() -> tuple[int, list[int]]:
    """ONE parse of the shard-bench env knobs, shared by the notary
    and commit-plane metrics so their records cannot drift:
    (BENCH_SHARDS, sorted sweep counts — the BENCH_SHARD_SWEEP list
    unioned with {1, shards})."""
    shards = max(1, int(os.environ.get("BENCH_SHARDS", "4")))
    sweep_env = os.environ.get("BENCH_SHARD_SWEEP", "")
    sweep = sorted(
        {
            max(1, int(s))
            for s in (sweep_env.split(",") if sweep_env else [])
            if s.strip()
        }
        | {1, shards}
    )
    return shards, sweep


class _AcceptAllVerifier:
    """Constant-true SPI stand-in for the commit-plane metric: staging,
    routing, contract verification, partitioned uniqueness commit and
    reply signing all run for real — only the signature math is
    elided, so the record isolates the HOST commit plane the round-6
    sharding parallelises (on hardware the verify overlaps on-device;
    on this CPU-only instrument it would swamp the plane)."""

    def verify_batch(self, requests):
        return [True] * len(requests)


def _commit_plane_metric(batch: int, iters: int) -> dict:
    """Sharded commit-plane throughput (host side only): the notary
    flush pipeline with verification stubbed to accept — what remains
    is exactly the per-request host work (stage, resolve+contract,
    partitioned commit, sign, scatter) whose single-thread ceiling
    capped BENCH_r05's notary line at 27.5k/s. Swept over shard counts
    so the record shows whether the commit plane itself scales (or at
    minimum does not regress) as shards are added; runnable honestly
    on a CPU-only container, where the real-verify notary metric is
    link/device-bound and meaningless."""
    net, notary, alice, spends = _notary_fixture(batch)
    shards, sweep = _shard_sweep_config()
    # the stub replaces the hub verifier for every configuration
    notary.services._batch_verifier = _AcceptAllVerifier()
    rates: dict[str, float] = {}
    for n in sweep:
        rates[str(n)] = round(
            _notary_rate(
                notary, alice, spends, batch, iters,
                shards=n, chunk=batch,
                verifier=None,
            ),
            1,
        )
    rate = rates[str(shards)]
    out = {
        "metric": "notary_commit_plane_sharded_per_sec",
        "value": rate,
        "unit": "notarisations/s",
        "vs_baseline": round(rate / BASELINE, 3),
        "flush_depth": batch,
        "shards": shards,
        "per_shard_depth": -(-batch // shards),
        "verify_stubbed": True,
        "shard_sweep": rates,
    }
    base = rates.get("1")
    if base:
        out["scaling_vs_1shard"] = round(rate / base, 3)
    return out


def _ingest_fixture(unique: int = 1) -> list:
    """`unique` distinct canonical signed cash spends' CTS bytes — the
    wire frames a notary ingests. One fixture builder for the serial
    and pipelined ingest metrics so they measure identical work."""
    from corda_tpu.core import serialization as ser
    from corda_tpu.core.contracts import Amount, Issued, StateRef
    from corda_tpu.core.identity import PartyAndReference
    from corda_tpu.core.transactions import TransactionBuilder
    from corda_tpu.finance.cash import (
        CASH_CONTRACT,
        CashIssue,
        CashMove,
        CashState,
    )
    from corda_tpu.testing.mock_network import MockNetwork

    net = MockNetwork(seed=9)
    notary = net.create_notary("Notary")
    bank = net.create_node("Bank")
    alice = net.create_node("Alice")
    token = Issued(PartyAndReference(bank.party, b"\x01"), "USD")
    blobs = []
    for i in range(max(unique, 1)):
        ib = TransactionBuilder(notary.party)
        ib.add_output_state(
            CashState(Amount(100 + i, token), alice.party.owning_key),
            CASH_CONTRACT,
        )
        ib.add_command(CashIssue(i + 1), bank.party.owning_key)
        issue = bank.services.sign_initial_transaction(ib)
        alice.services.record_transactions([issue])
        sb = TransactionBuilder(notary.party)
        sb.add_input_state(alice.vault.state_and_ref(StateRef(issue.id, 0)))
        sb.add_output_state(
            CashState(Amount(100 + i, token), bank.party.owning_key),
            CASH_CONTRACT, notary.party,
        )
        sb.add_command(CashMove(), alice.party.owning_key)
        blobs.append(ser.encode(alice.services.sign_initial_transaction(sb)))
    return blobs


def _ingest_metric(batch: int, iters: int) -> dict:
    """Wire-ingest rate (round-5): decode a canonical signed cash
    spend's CTS bytes, compute its Merkle id COLD, and stage its
    signature requests — the per-transaction host cost a notary pays
    on arrival, BEFORE any flush (the flush metrics' fixtures carry
    warm objects and never see it). Pure host work, no device; the
    native CTS codec is what lifted this from ~2.5k/s
    (BASELINE.md round-5 second pass)."""
    from corda_tpu.core import serialization as ser

    blob = _ingest_fixture(1)[0]

    def run_once() -> None:
        for _ in range(batch):
            stx = ser.decode(blob)
            stx.wtx.id                  # cold Merkle id, every time
            if not stx.signature_requests():
                raise SystemExit("ingest staging produced nothing")

    run_once()                          # warm-up
    rate = _median_rate(run_once, batch, iters)
    from corda_tpu.native import get as _native

    return {
        "metric": "wire_ingest_decode_id_stage_per_sec",
        "value": round(rate, 1),
        "unit": "tx/s",
        "vs_baseline": round(rate / BASELINE, 3),
        "wire_bytes": len(blob),
        "native_codec": _native() is not None,
    }


def _ingest_pipelined_metric(batch: int, iters: int) -> dict:
    """Pipelined wire-ingest rate: the SAME decode + Merkle-id +
    signature-staging work as the serial metric, through the
    node/ingest.py pipeline — sharded decode pool double-buffered so
    decode of batch N+1 overlaps consumption of batch N, ONE batched
    SHA-256 pass per chunk for every component leaf, content-keyed
    leaf/subtree digest caches, and the hot-frame cache in front of
    decode. The fixture tiles BENCH_TILE unique frames across the
    batch (the SPI fixture-tiling convention), so the record shows the
    re-seen-frame serving shape a loaded notary actually ingests;
    `frame_cache_hits` makes the cache's share attributable, and
    `serial_per_sec` is the serial path measured on the SAME fixture
    in the SAME process, so the win is a ratio inside one record, not
    an inference across runs. Bit-identity of ids and staged requests
    vs the serial path is gated here and fuzzed in
    tests/test_ingest.py."""
    from corda_tpu.core import serialization as ser
    from corda_tpu.node.ingest import IngestPipeline

    tile = max(1, int(os.environ.get("BENCH_TILE", "8")))
    uniq = _ingest_fixture(min(tile, batch))
    blobs = (uniq * (batch // len(uniq) + 1))[:batch]
    chunk = min(512, batch)
    pipe = IngestPipeline()

    def run_once() -> None:
        n = 0
        for entries in pipe.pipeline_blobs(blobs, chunk=chunk):
            for e in entries:
                if e.error is not None or not e.requests:
                    raise SystemExit(f"pipelined ingest failed: {e.error}")
            n += len(entries)
        if n != batch:
            raise SystemExit("pipelined ingest lost transactions")

    run_once()                          # warm-up + correctness
    # parity gate (explicit raise, survives python -O): pipelined ids
    # and staged-request counts must match a cold serial decode
    for b in uniq:
        cold = ser.decode(b)
        ent = pipe.ingest([b])[0]
        if ent.tx_id != cold.wtx.id or len(ent.requests) != len(
            cold.signature_requests()
        ):
            raise SystemExit("pipelined/serial ingest parity failure")
    rate = _median_rate(run_once, batch, iters)

    def serial_once() -> None:
        for b in blobs:
            stx = ser.decode(b)
            stx.wtx.id                  # cold Merkle id, every time
            if not stx.signature_requests():
                raise SystemExit("ingest staging produced nothing")

    serial_once()                       # warm-up
    serial_rate = _median_rate(serial_once, batch, iters)
    from corda_tpu.native import get as _native

    return {
        "metric": "wire_ingest_pipelined_per_sec",
        "value": round(rate, 1),
        "unit": "tx/s",
        "vs_baseline": round(rate / BASELINE, 3),
        "serial_per_sec": round(serial_rate, 1),
        "vs_serial": round(rate / serial_rate, 3),
        "unique_frames": len(uniq),
        "frame_cache_hits": pipe.frame_hits,
        "wire_bytes": len(uniq[0]),
        "native_codec": _native() is not None,
    }


# bench-stage names <- span names (utils/tracing.py): the BENCH
# breakdown speaks decode/merkle/stage/dispatch/kernel/commit so the
# perf trajectory pins a regression to a stage without knowing the
# span vocabulary; "kernel" is the device wait (link_wait) — zero on
# CPU-synchronous verifiers, whose compute lands inside "dispatch"
_TRACE_STAGE_MAP = {
    "ingest.decode": "decode",
    "ingest.merkle_id": "merkle",
    "ingest.stage": "stage",
    "notary.stage": "stage",
    "notary.dispatch": "dispatch",
    "notary.resolve_verify": "dispatch",
    "notary.link_wait": "kernel",
    "notary.validate": "commit",
    "notary.commit": "commit",
    "notary.stream_commit": "commit",
    "notary.sign_scatter": "commit",
}


def _trace_fixture(unique: int, batch: int, cpu: bool):
    """(notary service, requester party, wire blobs): `unique` distinct
    signed cash spends tiled to `batch`, their issue backchain recorded
    at the notary — the full-path fixture the stage-breakdown metric
    drives from wire bytes to uniqueness commit."""
    from corda_tpu.core import serialization as ser
    from corda_tpu.core.contracts import Amount, Issued, StateRef
    from corda_tpu.core.identity import PartyAndReference
    from corda_tpu.core.transactions import TransactionBuilder
    from corda_tpu.crypto.batch_verifier import (
        CpuBatchVerifier,
        TpuBatchVerifier,
    )
    from corda_tpu.finance.cash import (
        CASH_CONTRACT,
        CashIssue,
        CashMove,
        CashState,
    )
    from corda_tpu.testing.mock_network import MockNetwork

    if cpu:
        verifier = CpuBatchVerifier()
    else:
        chunk = min(int(os.environ.get("BENCH_CHUNK", "4096")), batch)
        verifier = TpuBatchVerifier(batch_sizes=(chunk,))
    net = MockNetwork(seed=13, batch_verifier=verifier)
    notary = net.create_notary("Notary", batching=True)
    bank = net.create_node("Bank")
    alice = net.create_node("Alice")
    token = Issued(PartyAndReference(bank.party, b"\x01"), "USD")
    blobs = []
    for i in range(max(unique, 1)):
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
        blobs.append(ser.encode(alice.services.sign_initial_transaction(sb)))
    blobs = (blobs * (batch // len(blobs) + 1))[:batch]
    return notary.services.notary_service, alice.party, blobs


def _trace_metric(batch: int, iters: int, cpu: bool = False) -> dict:
    """Stage-attributed hot path (the tracing tentpole's bench leg):
    drive `batch` wire frames through IngestPipeline -> one
    BatchingNotaryService flush, alternating UNTRACED / TRACED reps,
    and fold the tracer's per-stage summary into the record as the
    decode / merkle / stage / dispatch / kernel / commit seconds
    breakdown. `value` is the coverage fraction — how much of the
    traced wall the stages attribute; `tracing_overhead` is
    min(traced)/min(untraced)-1 on the SAME fixture in the SAME
    process, so the cost of always-on tracing stays a measured ratio
    inside one record."""
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.ingest import IngestPipeline
    from corda_tpu.node.notary import (
        InMemoryUniquenessProvider,
        _PendingNotarisation,
    )
    from corda_tpu.utils import tracing

    cpu = cpu or os.environ.get("BENCH_TRACE_CPU", "") not in ("", "0")
    tile = max(1, int(os.environ.get("BENCH_TILE", "8")))
    svc, requester, blobs = _trace_fixture(min(tile, batch), batch, cpu)
    reps = max(2, iters)

    def run_once(tracer) -> float:
        # fresh uniqueness per pass (conflict-free re-notarise) and a
        # fresh pipeline with the frame cache OFF so every rep decodes
        # the same work — the traced/untraced ratio is then tracing,
        # not cache luck
        svc.uniqueness = InMemoryUniquenessProvider()
        pipe = IngestPipeline(tracer=tracer, frame_cache_size=0)
        futs = []
        t0 = time.perf_counter()
        entries = pipe.ingest(blobs, end_spans=False)
        for e in entries:
            if e.error is not None:
                raise SystemExit(f"trace metric ingest failed: {e.error}")
            fut = FlowFuture()
            futs.append(fut)
            svc.enqueue_pending(
                _PendingNotarisation(e.stx, requester, fut, span=e.span)
            )
        svc.flush()
        wall = time.perf_counter() - t0
        pipe.close()
        for fut in futs:
            sig = fut.result()
            if not hasattr(sig, "by"):
                raise SystemExit(f"trace metric notarisation failed: {sig}")
        return wall

    import gc

    off = tracing.Tracer(enabled=False)
    on = tracing.Tracer(
        enabled=True,
        recorder=tracing.FlightRecorder(
            keep_recent=batch * reps, keep_slowest=16
        ),
    )
    # warm-up BOTH modes (compile + correctness + first-run bytecode on
    # the span paths), then drop the warm-up traces so the stage
    # summary covers timed reps only
    run_once(off)
    run_once(on)
    on.recorder.clear()
    walls_off, walls_on = [], []
    for _ in range(reps):               # interleaved A/B: drift cancels
        gc.collect()                    # equalise collector debt per rep
        walls_off.append(run_once(off))
        gc.collect()
        walls_on.append(run_once(on))
    # min-of-reps on both sides: timing noise is one-sided positive, so
    # the minima are the comparable "clean lap" walls
    overhead = min(walls_on) / min(walls_off) - 1.0

    # per-flush stage seconds: each stage interval is SHARED across the
    # batch (one decode pass, one dispatch), so the per-frame mean IS
    # the per-flush interval, averaged over the traced reps
    summary = on.stage_summary()
    stages = {
        k: 0.0 for k in
        ("decode", "merkle", "stage", "dispatch", "kernel", "commit")
    }
    for span_name, row in summary.items():
        bucket = _TRACE_STAGE_MAP.get(span_name)
        if bucket is not None:
            stages[bucket] += row["mean_s"]
    attributed = sum(stages.values())
    wall = _median(walls_on)
    coverage = attributed / wall if wall > 0 else 0.0
    return {
        "metric": "hot_path_stage_breakdown",
        "value": round(coverage, 3),
        "unit": "fraction of traced wall attributed to stages",
        "vs_baseline": round(coverage, 3),
        "stages_seconds": {k: round(v, 6) for k, v in stages.items()},
        # first-class per-stage gate keys: tools/bench_history.py
        # explodes every dict named here into
        # hot_path_stage_breakdown.stages_seconds.<stage> rows diffed
        # in the LOWER-is-better direction, so a stage-level
        # regression (commit 2x slower under an unchanged headline)
        # fails `--gate` on its own line
        "gate_lower_is_better": ["stages_seconds"],
        "wall_seconds": round(wall, 6),
        "untraced_wall_seconds": round(_median(walls_off), 6),
        "tracing_overhead": round(overhead, 4),
        "batch": batch,
        "reps": reps,
        "verifier": "cpu" if cpu else "tpu",
    }


def _consensus_metric(batch: int, iters: int) -> dict:
    """Consensus-phase attribution (the cluster-tracing tentpole's
    bench leg): drive `batch` distributed commits through a REAL
    3-member Raft cluster on the in-memory fabric, alternating
    UNTRACED / TRACED reps, and fold every member's `raft.<phase>`
    span summary into a per-commit phase breakdown (propose / append /
    quorum / commit / apply seconds). `value` is untraced distributed
    commits/sec on this rig; `tracing_overhead` is
    min(traced)/min(untraced)-1 on the SAME cluster in the SAME
    process — the cost of consensus tracing stays a measured ratio
    inside one record, gated <= 5% like the PR 2 hot-path trace
    metric."""
    import gc

    from corda_tpu.crypto import schemes as _schemes
    from corda_tpu.flows.api import _WaitFuture
    from corda_tpu.testing.fleet import FleetClient, TearOffSource
    from corda_tpu.testing.mock_network import MockNetwork
    from corda_tpu.utils import tracing
    from corda_tpu.utils.metrics import MetricRegistry
    from corda_tpu.core.identity import Party

    batch = max(8, batch)
    reps = max(2, iters)
    tracers: dict = {}
    registries: dict = {}

    def tracer_for(name):
        t = tracers.get(name)
        if t is None:
            t = tracers[name] = tracing.Tracer(
                enabled=False,
                recorder=tracing.FlightRecorder(
                    # every phase span completes as its own recorder
                    # entry: size to the traced reps so the summary
                    # covers the whole run, not the tail
                    keep_recent=12 * batch * reps + 64,
                    keep_slowest=16,
                ),
            )
        return t

    net = MockNetwork(seed=11)
    service_party, members = net.create_raft_notary_cluster(
        3,
        scheme_id=_schemes.ECDSA_SECP256R1_SHA256,
        tracer_factory=tracer_for,
        metrics_factory=lambda name: registries.setdefault(
            name, MetricRegistry()
        ),
    )
    net.elect(members)
    # the REAL serving path, fleet-style: tear-off notarisations via
    # SimpleNotaryService.process (ftx verify + replicated commit +
    # sign), so the A/B measures tracing against production per-commit
    # work — not against a bare dict update
    kp = _schemes.generate_keypair(_schemes.ECDSA_SECP256R1_SHA256, seed=7)
    client = FleetClient("bench-consensus", Party("bench-consensus", kp.public))
    source = TearOffSource(service_party, seed=13)

    def fresh_payloads(n):
        out = []
        for _ in range(n):
            client.submitted += 1   # fresh coin per spend (no conflicts)
            out.append(source.spend(client))
        return out

    def run_once(traced: bool) -> float:
        for t in tracers.values():
            t.enabled = traced
        payloads = fresh_payloads(batch)   # fixture build OUTSIDE timing
        live = []
        t0 = time.perf_counter()
        for i, (ftx, _inputs, tx_id) in enumerate(payloads):
            member = members[i % len(members)]   # every member gateways
            root = (
                tracer_for(member.name).start_trace(
                    "notarise.bench", tx_id=str(tx_id)
                )
                if traced else None
            )
            gen = member.services.notary_service.process(
                ftx, client.party,
                trace=root.context if root is not None else None,
            )
            live.append([gen, None, root])
            net.run()
        # heartbeat rounds: commit-index propagation resolves forwarded
        # futures and lands follower commit/apply phases
        for _ in range(200):
            still = []
            for entry in live:
                gen, wait, root = entry
                try:
                    if wait is None:
                        step = gen.send(None)
                    elif wait.future.done:
                        step = gen.send(wait.future.result())
                    else:
                        still.append(entry)
                        continue
                    if isinstance(step, _WaitFuture):
                        entry[1] = step
                        still.append(entry)
                    else:
                        raise SystemExit(
                            f"unexpected notary yield {step!r}"
                        )
                except StopIteration as stop:
                    if not hasattr(stop.value, "by"):
                        raise SystemExit(
                            f"consensus notarisation failed: {stop.value}"
                        )
                    if root is not None:
                        root.end()
            live = still
            if not live:
                break
            net.clock.advance(60_000)
            net.run()
        if live:
            raise SystemExit(
                f"{len(live)} consensus notarisations never resolved"
            )
        wall = time.perf_counter() - t0
        # two extra heartbeats so every member's apply span completes
        # before the stage summary reads the recorders
        for _ in range(2):
            net.clock.advance(60_000)
            net.run()
        return wall

    run_once(False)   # warm both paths (jit-free, but first-run
    run_once(True)    # bytecode + fabric caches)
    for t in tracers.values():
        t.recorder.clear()
    walls_off, walls_on = [], []
    traced_commits = 0
    for _ in range(reps):             # interleaved A/B: drift cancels
        gc.collect()
        walls_off.append(run_once(False))
        gc.collect()
        walls_on.append(run_once(True))
        traced_commits += batch
    overhead = min(walls_on) / min(walls_off) - 1.0

    phases = {
        p: 0.0 for p in ("propose", "append", "quorum", "commit", "apply")
    }
    span_counts = dict.fromkeys(phases, 0)
    members_represented = set()
    for name, t in tracers.items():
        for span_name, row in t.stage_summary().items():
            if not span_name.startswith("raft."):
                continue
            phase = span_name[len("raft."):]
            if phase in phases:
                phases[phase] += row["total_s"]
                span_counts[phase] += row["count"]
                members_represented.add(name)
    per_commit = {
        k: round(v / max(traced_commits, 1), 9) for k, v in phases.items()
    }
    value = batch / min(walls_off)
    return {
        "metric": "consensus",
        "value": round(value, 3),
        "unit": "distributed raft notarisations/sec (3 members, untraced)",
        "vs_baseline": 1.0,
        # per-commit phase seconds, summed across members: the gate
        # catches a single phase regressing under a steady headline
        "phases_seconds": per_commit,
        "gate_lower_is_better": ["phases_seconds"],
        "phase_span_counts": span_counts,
        "members_with_spans": sorted(members_represented),
        "tracing_overhead": round(overhead, 4),
        "overhead_ok": overhead <= float(
            os.environ.get("BENCH_CONSENSUS_OVERHEAD_MAX", "0.05")
        ),
        "gate_required_true": ["overhead_ok"],
        "wall_seconds": round(_median(walls_on), 6),
        "untraced_wall_seconds": round(_median(walls_off), 6),
        "batch": batch,
        "reps": reps,
    }


def _qos_metric(batch: int, iters: int) -> dict:
    """QoS overload serving (the admission-control tentpole's bench
    leg): drive ~2x the measured no-overload capacity of a CPU-fixture
    batching notary, controller ON (node/qos.py NotaryQos — deadline
    shedding + adaptive batching against a p99 target) vs OFF (the
    plain unbounded flush), and record goodput, admitted p99, and the
    shed fraction. `value` is goodput under overload as a fraction of
    the no-overload capacity — the acceptance line is >= 0.9 (overload
    must cost latency-budget sheds, not throughput). The OFF pass shows
    WHY the controller exists: same goodput, but p99 grows with the
    unbounded backlog instead of holding the target."""
    import time as _time

    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node import qos as qoslib
    from corda_tpu.node.notary import (
        InMemoryUniquenessProvider,
        _PendingNotarisation,
    )
    from corda_tpu.node.services import Clock

    rounds = max(4, iters * 2)
    base = max(8, min(batch, 128))         # no-overload flush depth
    svc, requester, blobs = _trace_fixture(
        rounds * 2 * base + base, rounds * 2 * base + base, cpu=True
    )
    from corda_tpu.core import serialization as ser

    spends = [ser.decode(b) for b in blobs]
    # real wall-clock throughout: flush depth COSTS latency here (the
    # CPU verifier does real per-signature work), which is the trade
    # the adaptive controller manages
    clock = Clock()
    svc.services.clock = clock
    svc.time_window_checker.clock = clock

    def submit(stx, deadline, log):
        fut = FlowFuture()
        arrival = clock.now_micros()
        fut.add_done_callback(
            lambda f: log.append(
                (arrival, clock.now_micros(), deadline, f.result())
            )
        )
        svc.enqueue_pending(
            _PendingNotarisation(
                stx, requester, fut,
                deadline=deadline, arrival_micros=arrival,
            )
        )

    # -- no-overload capacity: one warmed flush of `base` ------------------
    def timed_flush(n_spends, offset=0):
        svc.uniqueness = InMemoryUniquenessProvider()
        log: list = []
        for stx in spends[offset : offset + n_spends]:
            submit(stx, None, log)
        t0 = _time.perf_counter()
        svc.flush()
        return _time.perf_counter() - t0, log

    svc.qos = None
    timed_flush(base)                       # warm-up (bytecode, caches)
    flush_wall, _ = timed_flush(base)
    capacity_per_sec = base / flush_wall
    target_micros = int(2 * flush_wall * 1e6)

    def overload_run(qos) -> dict:
        """`rounds` rounds of 2x per-flush offered load; answered-
        request latencies tracked in real micros."""
        svc.qos = qos
        svc.uniqueness = InMemoryUniquenessProvider()
        # a capped ON run can leave requeued backlog behind its drain
        # ticks; drop it so the OFF pass measures ONLY its own offered
        # load (apples-to-apples A/B)
        for shard in svc._shards:
            shard.pending.clear()
        log: list = []
        it = iter(spends[base:])
        t0 = _time.perf_counter()
        for _ in range(rounds):
            now = clock.now_micros()
            for _ in range(2 * base):
                submit(next(it), now + target_micros, log)
            svc.tick()
        for _ in range(4):                  # drain: serve or expire
            svc.tick()
        wall = _time.perf_counter() - t0
        signed = [r for r in log if hasattr(r[3], "by")]
        sheds = [
            r for r in log
            if getattr(r[3], "kind", None) == qoslib.SHED_KIND
        ]
        # steady-state p99: the controller needs a few flushes to find
        # the depth the target affords, so rank over the last half
        tail = sorted(
            done - arr for arr, done, _, out in signed[len(signed) // 2 :]
        )
        p99 = tail[min(len(tail) - 1, int(0.99 * len(tail)))] if tail else 0
        return {
            "goodput_per_sec": round(len(signed) / wall, 1),
            "p99_ms": round(p99 / 1e3, 3),
            "shed_fraction": round(len(sheds) / max(1, len(log)), 3),
            "answered": len(log),
        }

    # max_batch == the no-overload depth: per-flush capacity is the
    # measured base, so 2x offered load genuinely backlogs and the
    # deadline/shed machinery engages (an unbounded flush would just
    # absorb the whole round and nothing would ever queue)
    qos = qoslib.NotaryQos(
        qoslib.QosPolicy(
            target_p99_micros=target_micros,
            min_batch=max(8, base // 2), max_batch=base,
            max_wait_micros=0,
        ),
        clock=clock,
    )
    on = overload_run(qos)
    off = overload_run(None)
    svc.qos = None
    goodput_ratio = on["goodput_per_sec"] / capacity_per_sec
    return {
        "metric": "qos_overload_serving",
        "value": round(goodput_ratio, 3),
        "unit": "goodput fraction of no-overload capacity at 2x load",
        "vs_baseline": round(goodput_ratio, 3),
        "capacity_per_sec": round(capacity_per_sec, 1),
        "target_p99_ms": round(target_micros / 1e3, 3),
        "controller_on": on,
        "controller_off": off,
        "controller_state": qos.controller.snapshot(),
        "shed_counters": {
            k: v for k, v in qos.snapshot()["shed"].items()
        },
        "rounds": rounds,
        "offered_per_round": 2 * base,
    }


def _health_metric(batch: int, iters: int) -> dict:
    """Health-plane cost + canary proof (the self-monitoring
    tentpole's bench leg): the notary CPU rig serves `batch` spends
    per flush with the health plane OFF (bare tick) vs ON (flush
    heartbeat beaten, watchdog checked, alert rules walked every
    tick), interleaved min-of-reps A/B on the same fixture. `value`
    is the fractional wall overhead the plane adds to a flush — the
    acceptance line is <= 2% (BENCH_HEALTH_OVERHEAD_MAX). The canary
    round trip is proven (and its latency recorded) OUTSIDE the timed
    A/B: one probe through stage -> dispatch -> commit -> sign on a
    real flush, never touching the uniqueness namespace — in
    production its build+sign cost amortises at the probe cadence
    (every canary_interval, default 2 s), not per flush, so folding a
    per-flush launch into the steady-state number would measure a
    configuration no node runs."""
    import gc
    import time as _time

    from corda_tpu.core import serialization as ser
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.notary import (
        InMemoryUniquenessProvider,
        _PendingNotarisation,
    )
    from corda_tpu.utils.health import (
        HealthMonitor,
        HealthPolicy,
        notary_canary_fn,
    )

    tile = max(1, int(os.environ.get("BENCH_TILE", "8")))
    svc, requester, blobs = _trace_fixture(min(tile, batch), batch, cpu=True)
    spends = [ser.decode(b) for b in blobs]
    reps = max(2, iters)

    def run_once(monitor) -> float:
        svc.attach_health(monitor)   # None detaches (the OFF side)
        svc.uniqueness = InMemoryUniquenessProvider()
        futs = []
        t0 = _time.perf_counter()
        for stx in spends:
            fut = FlowFuture()
            futs.append(fut)
            svc.enqueue_pending(
                _PendingNotarisation(stx, requester, fut)
            )
        svc.tick()                   # flush + heartbeat
        if monitor is not None:
            monitor.tick()           # watchdog + rules + canary launch
        wall = _time.perf_counter() - t0
        if monitor is not None and svc.backlog():
            # serve the just-launched canary OUTSIDE the timed window:
            # left pending, the NEXT (baseline) rep would flush it
            # inside ITS timing and understate the measured overhead
            svc.tick()
        for fut in futs:
            sig = fut.result()
            if not hasattr(sig, "by"):
                raise SystemExit(f"health metric notarisation failed: {sig}")
        return wall

    monitor = HealthMonitor(
        policy=HealthPolicy(
            # one canary launch total: the round-trip proof below; the
            # timed reps then measure the per-tick plane only
            canary_interval_micros=3_600_000_000,
            # a slow CPU flush between ticks is not a stall: the bench
            # measures overhead, the watchdog soak lives in
            # tests/test_health.py on a TestClock
            heartbeat_deadline_micros=600_000_000,
            canary_deadman_micros=3_600_000_000,
        )
    )
    # the canary is the NOTARY's own synthetic traffic: its command
    # signer must be a key the serving hub holds (svc.identity), not
    # the remote requester's
    monitor.attach_canary(notary_canary_fn(svc.services, svc.identity))
    # canary round-trip proof, untimed: launch + one real flush
    svc.attach_health(monitor)
    monitor.tick()
    svc.tick()
    if monitor.canary.completed < 1:
        raise SystemExit(
            "health metric: no canary round trip completed through the "
            "real flush path"
        )
    run_once(None)                   # warm-up both sides
    run_once(monitor)
    walls_off, walls_on = [], []
    for _ in range(reps):            # interleaved A/B: drift cancels
        gc.collect()                 # equalise collector debt per rep
        walls_off.append(run_once(None))
        gc.collect()
        walls_on.append(run_once(monitor))
    svc.attach_health(None)
    overhead = min(walls_on) / min(walls_off) - 1.0
    canary = monitor.canary
    # the canary never touches the real uniqueness namespace: zero
    # inputs -> vacuous commit, so the final pass's provider holds
    # exactly the measured spends' (tiled fixture: unique) input refs
    # and nothing else
    expected_refs = len(
        {ref for stx in spends for ref in stx.wtx.inputs}
    )
    if len(svc.uniqueness.committed) != expected_refs:
        raise SystemExit(
            f"uniqueness map holds {len(svc.uniqueness.committed)} refs, "
            f"expected {expected_refs} — the canary (or something else) "
            "leaked in"
        )
    ok, _detail = monitor.healthz()
    return {
        "metric": "health_plane_overhead",
        "value": round(max(overhead, 0.0), 4),
        "unit": "fractional flush-wall overhead of the health plane",
        # direction marker (see perf_plane_overhead): overhead gates
        # when it grows, not when it improves
        "lower_is_better": True,
        "vs_baseline": round(max(overhead, 0.0), 4),
        "overhead_raw": round(overhead, 4),
        "batch": batch,
        "reps": reps,
        "canary_completed": canary.completed,
        "canary_latency_ms": round(
            (canary.last_latency_micros or 0) / 1e3, 3
        ),
        "healthy": ok,
        "alerts_firing": monitor.alerts_firing(),
    }


def _perf_metric(batch: int, iters: int) -> dict:
    """Perf-attribution plane cost + retrace proof (the round-7
    tentpole's bench leg): the notary CPU rig serves `batch` spends
    per flush with the sampling profiler OFF vs ON (utils/perf.py
    SamplingProfiler at BENCH_PERF_HZ, default 19 Hz, watching every
    thread), interleaved min-of-reps A/B on the same fixture. `value`
    is the fractional wall overhead continuous profiling adds to a
    flush — the acceptance line is <= 2% (BENCH_PERF_OVERHEAD_MAX) —
    cross-checked against the profiler's own measured self-overhead
    gauge. The jit-retrace counter is proven on a real jitted
    function: two warm-up shapes compile, `mark_warm()` arms the
    counter, a repeat call stays at zero retraces and a deliberately
    NEW shape increments it — the same KernelAccounting.timed_call
    bookkeeping the TpuBatchVerifier dispatch path records through,
    so the proof and production cannot fork."""
    import gc
    import time as _time

    import jax
    import jax.numpy as jnp

    from corda_tpu.core import serialization as ser
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.notary import (
        InMemoryUniquenessProvider,
        _PendingNotarisation,
    )
    from corda_tpu.utils.perf import KernelAccounting, SamplingProfiler

    # -- retrace proof (tiny jit, real trace-per-shape) --------------------
    acct = KernelAccounting()
    fn = jax.jit(lambda x: (x * 2 + 1).sum())
    for shape in (8, 16):                       # warmup: two shapes
        acct.timed_call(0, shape, fn, jnp.zeros(shape, jnp.float32))
    acct.mark_warm()
    acct.timed_call(0, 8, fn, jnp.zeros(8, jnp.float32))    # warm hit
    stable_after_warm = acct.retraces == 0
    acct.timed_call(0, 32, fn, jnp.zeros(32, jnp.float32))  # forced miss
    retrace_counted = acct.retraces == 1

    # -- profiler overhead A/B on the notary CPU flush rig -----------------
    tile = max(1, int(os.environ.get("BENCH_TILE", "8")))
    svc, requester, blobs = _trace_fixture(min(tile, batch), batch, cpu=True)
    spends = [ser.decode(b) for b in blobs]
    reps = max(2, iters)
    hz = float(os.environ.get("BENCH_PERF_HZ", "19"))
    prof = SamplingProfiler(hz=hz)

    def run_once() -> float:
        svc.uniqueness = InMemoryUniquenessProvider()
        futs = []
        t0 = _time.perf_counter()
        for stx in spends:
            fut = FlowFuture()
            futs.append(fut)
            svc.enqueue_pending(_PendingNotarisation(stx, requester, fut))
        svc.flush()
        wall = _time.perf_counter() - t0
        for fut in futs:
            sig = fut.result()
            if not hasattr(sig, "by"):
                raise SystemExit(f"perf metric notarisation failed: {sig}")
        return wall

    run_once()                       # warm-up (bytecode, caches)
    walls_off, walls_on = [], []
    for _ in range(reps):            # interleaved A/B: drift cancels
        gc.collect()                 # equalise collector debt per rep
        walls_off.append(run_once())
        gc.collect()
        prof.start()
        try:
            walls_on.append(run_once())
        finally:
            prof.stop()
    overhead = min(walls_on) / min(walls_off) - 1.0
    collapsed_lines = len(prof.collapsed().splitlines())
    return {
        "metric": "perf_plane_overhead",
        "value": round(max(overhead, 0.0), 4),
        "unit": "fractional flush-wall overhead of continuous profiling",
        # direction marker for tools/bench_history.py: an overhead
        # headline gates when it GROWS — higher-is-better gating would
        # fail the trajectory on an improvement
        "lower_is_better": True,
        "vs_baseline": round(max(overhead, 0.0), 4),
        "overhead_raw": round(overhead, 4),
        "profiler_hz": hz,
        "profiler_samples": prof.samples,
        "profiler_self_overhead": round(prof.overhead(), 5),
        "collapsed_stacks": collapsed_lines,
        "retrace_stable_after_warmup": stable_after_warm,
        "retrace_counted": retrace_counted,
        "batch": batch,
        "reps": reps,
    }


def _device_metric(batch: int, iters: int) -> dict:
    """Device-telemetry plane cost + capacity proof (the round-15
    tentpole's bench leg): the notary CPU rig serves `batch` spends
    per flush with the device plane DETACHED vs ATTACHED-and-ticked
    (utils/device_telemetry.DevicePlane — HBM/live-buffer sampling,
    per-device dispatch windows, the backlog window; one tick per
    flush, the pump cadence, with sample_gap 0 so EVERY tick pays the
    full sample — the honest worst case), interleaved min-of-reps A/B
    on the same fixture. `value` is the fractional flush-wall
    overhead; the acceptance line is <= 2% (BENCH_DEVICE_OVERHEAD_MAX)
    and `device_plane_overhead_ok` rides the bench_history --gate as a
    required-true verdict. The capacity model then resolves on the
    measured phase timers and must name `host_pump` on this CPU rig —
    the BENCH_r06 41.5k/s host wall, stated by the instrument itself
    (`capacity_names_host_pump`, also required-true)."""
    import gc
    import time as _time

    from corda_tpu.core import serialization as ser
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.notary import (
        InMemoryUniquenessProvider,
        _PendingNotarisation,
    )
    from corda_tpu.utils.device_telemetry import DevicePlane, DevicePolicy

    tile = max(1, int(os.environ.get("BENCH_TILE", "8")))
    svc, requester, blobs = _trace_fixture(min(tile, batch), batch, cpu=True)
    spends = [ser.decode(b) for b in blobs]
    reps = max(2, iters)

    def run_once(plane) -> float:
        svc.uniqueness = InMemoryUniquenessProvider()
        futs = []
        t0 = _time.perf_counter()
        for stx in spends:
            fut = FlowFuture()
            futs.append(fut)
            svc.enqueue_pending(_PendingNotarisation(stx, requester, fut))
        svc.flush()
        if plane is not None:
            plane.tick()
        wall = _time.perf_counter() - t0
        for fut in futs:
            sig = fut.result()
            if not hasattr(sig, "by"):
                raise SystemExit(f"device metric notarisation failed: {sig}")
        return wall

    plane = DevicePlane(
        metrics=svc.metrics,
        policy=DevicePolicy(sample_gap_micros=0),
        install_default_accounting=False,
    )
    svc.attach_device(plane)
    run_once(None)                   # warm-up (bytecode, caches)
    walls_off, walls_on = [], []
    for _ in range(reps):            # interleaved A/B: drift cancels
        gc.collect()                 # equalise collector debt per rep
        walls_off.append(run_once(None))
        gc.collect()
        walls_on.append(run_once(plane))
    overhead = min(walls_on) / min(walls_off) - 1.0
    max_overhead = float(
        os.environ.get("BENCH_DEVICE_OVERHEAD_MAX", "0.02")
    )
    cap = plane.capacity()
    snap = plane.snapshot()
    return {
        "metric": "device_plane_overhead",
        "value": round(max(overhead, 0.0), 4),
        "unit": "fractional flush-wall overhead of device telemetry",
        "lower_is_better": True,
        "vs_baseline": round(max(overhead, 0.0), 4),
        "overhead_raw": round(overhead, 4),
        "overhead_max": max_overhead,
        # required-true verdicts riding tools/bench_history.py --gate:
        # a plane that got expensive OR a capacity model that stopped
        # naming the measured CPU-rig wall fails CI regardless of the
        # headline
        "gate_required_true": [
            "device_plane_overhead_ok", "capacity_names_host_pump",
        ],
        "device_plane_overhead_ok": max(overhead, 0.0) <= max_overhead,
        "capacity_names_host_pump": (
            cap["binding_constraint"] == "host_pump"
        ),
        "binding_constraint": cap["binding_constraint"],
        "predicted_ceiling_per_sec": cap["predicted_ceiling_per_sec"],
        "headroom_fractions": {
            name: row["headroom_fraction"]
            for name, row in cap["resources"].items()
        },
        "devices_seen": len(snap["devices"]),
        "batch": batch,
        "reps": reps,
    }


def _wire_metric(batch: int, iters: int) -> dict:
    """Wire & gateway telemetry plane (the round-17 tentpole's bench
    leg), three measurements in one record:

    FABRIC HEADLINE: a localhost TCP FabricEndpoint pair (journal ->
    framed socket -> durable ingest -> pump) drains `batch` frames per
    rep with the wire plane attached and ticked (the production
    configuration); `value` is the min-of-reps frames/s, and the
    plane's journal/codec/per-link accounting is proven nonempty from
    the same run. This wall rides real asyncio socket scheduling whose
    run-to-run jitter (measured ~20% on a quiet box) dwarfs the
    plane's microsecond-level seam cost, so it is NOT the A/B gate.

    A/B OVERHEAD (gated): the served-transaction wall — each rep
    pushes `batch` request blobs through an in-memory fabric pair into
    the notary CPU rig, flushes, and returns the responses, with the
    wire plane DETACHED vs ATTACHED-and-ticked (sample_gap 0 so every
    tick pays the full depth pull), interleaved min-of-reps on the
    same fixture. This is the deterministic wall the sibling plane
    metrics gate against and the question an operator asks: does
    enabling wire telemetry slow the notary line? Acceptance <= 2%
    (BENCH_WIRE_OVERHEAD_MAX), riding the bench_history --gate as
    REQUIRED-TRUE `wire_plane_overhead_ok` (measured ~0.4%: the
    per-frame seams cost low single-digit microseconds).

    GATEWAY: a live NodeWebServer wired to the TCP plane serves GET
    /wire over real HTTP while the notary rig flushes concurrently on
    another thread (handler wall is stolen pump time — the contention
    being priced); requests/s plus the proof the dispatch wrapper
    counted EVERY request (`gateway_accounted_ok`, also
    required-true)."""
    import gc
    import shutil
    import tempfile
    import threading
    import time as _time
    import urllib.request

    from corda_tpu.core import serialization as ser
    from corda_tpu.crypto import schemes
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.fabric import FabricEndpoint, PeerAddress
    from corda_tpu.node.messaging import InMemoryMessagingNetwork
    from corda_tpu.node.notary import (
        InMemoryUniquenessProvider,
        _PendingNotarisation,
    )
    from corda_tpu.node.persistence import NodeDatabase
    from corda_tpu.utils.wire_telemetry import WirePlane, WirePolicy

    reps = max(2, iters)
    tmp = tempfile.mkdtemp(prefix="bench-wire-")
    addresses: dict[str, PeerAddress] = {}
    payload = b"\x5a" * 256
    got = [0]
    a = b = web = None
    try:
        def endpoint(name: str, seed: int) -> FabricEndpoint:
            ep = FabricEndpoint(
                name,
                schemes.generate_keypair(seed=seed),
                NodeDatabase(os.path.join(tmp, f"{name}.db")),
                resolve=lambda peer: addresses.get(peer),
            )
            ep.start()
            addresses[name] = PeerAddress("127.0.0.1", ep.listen_port, None)
            return ep

        a = endpoint("bench-a", 9101)
        b = endpoint("bench-b", 9102)
        b.add_handler("bench.wire", lambda m: got.__setitem__(0, got[0] + 1))
        plane = WirePlane(policy=WirePolicy(sample_gap_micros=0))
        plane.attach_fabric(b)   # depth pulls read the receiver

        def run_fabric_once() -> float:
            target = got[0] + batch
            t0 = _time.perf_counter()
            for _ in range(batch):
                a.send("bench.wire", payload, "bench-b")
            while got[0] < target:
                # block on the pump wake (the production loop's shape)
                # — a busy spin would starve the fabric's asyncio
                # threads of the GIL and measure scheduling noise
                b.pump(block=True, timeout=0.02)
                if _time.perf_counter() - t0 > 120:
                    raise SystemExit(
                        f"wire metric: fabric drain stuck at "
                        f"{got[0]}/{target}"
                    )
            plane.tick()         # the pump-cadence depth pull, in-wall
            return _time.perf_counter() - t0

        a.telemetry = plane.fabric
        b.telemetry = plane.fabric
        run_fabric_once()                # warm-up (sockets, bytecode)
        walls = [run_fabric_once() for _ in range(reps)]
        frames_per_sec = batch / min(walls)
        snap = plane.snapshot()

        # -- A/B: the served-transaction wall (gated) ------------------
        tile = max(1, int(os.environ.get("BENCH_TILE", "8")))
        svc, requester, blobs = _trace_fixture(
            min(tile, batch), min(batch, 64), cpu=True
        )
        spends = [ser.decode(blob) for blob in blobs]
        payloads = list(blobs)[: len(spends)]
        net = InMemoryMessagingNetwork()
        cli = net.endpoint("bench-client")
        srv = net.endpoint("bench-notary")
        plane_ab = WirePlane(policy=WirePolicy(sample_gap_micros=0))
        plane_ab.attach_fabric(srv)
        inbox: list = []
        srv.add_handler("wire.req", inbox.append)
        cli.add_handler("wire.resp", lambda m: None)

        def run_served_once(attach: bool) -> float:
            tel = plane_ab.fabric if attach else None
            cli.telemetry = tel
            srv.telemetry = tel
            svc.uniqueness = InMemoryUniquenessProvider()
            inbox.clear()
            t0 = _time.perf_counter()
            for blob in payloads:
                cli.send("wire.req", blob, "bench-notary")
            net.run()
            futs = []
            for i, _ in enumerate(inbox):
                fut = FlowFuture()
                futs.append(fut)
                svc.enqueue_pending(
                    _PendingNotarisation(spends[i], requester, fut)
                )
            svc.flush()
            for fut in futs:
                sig = fut.result()
                if not hasattr(sig, "by"):
                    raise SystemExit(
                        f"wire metric notarisation failed: {sig}"
                    )
                srv.send("wire.resp", b"signed", "bench-client")
            net.run()
            if attach:
                plane_ab.tick()
            return _time.perf_counter() - t0

        run_served_once(True)            # warm-up (jit, caches)
        walls_off, walls_on = [], []
        for _ in range(reps):            # interleaved A/B: drift cancels
            gc.collect()                 # equalise collector debt per rep
            walls_off.append(run_served_once(False))
            gc.collect()
            walls_on.append(run_served_once(True))
        overhead = min(walls_on) / min(walls_off) - 1.0
        max_overhead = float(
            os.environ.get("BENCH_WIRE_OVERHEAD_MAX", "0.02")
        )

        # -- gateway under concurrent notarisation load ----------------
        stop = threading.Event()
        flushes = [0]

        def pound():
            while not stop.is_set():
                svc.uniqueness = InMemoryUniquenessProvider()
                futs = []
                for stx in spends:
                    fut = FlowFuture()
                    futs.append(fut)
                    svc.enqueue_pending(
                        _PendingNotarisation(stx, requester, fut)
                    )
                svc.flush()
                for fut in futs:
                    sig = fut.result()
                    if not hasattr(sig, "by"):
                        raise SystemExit(
                            f"wire metric notarisation failed: {sig}"
                        )
                flushes[0] += 1

        from corda_tpu.client.webserver import NodeWebServer

        web = NodeWebServer(
            client=object(), pump=lambda: None,
            metrics=svc.metrics, wire=plane,
        ).start()
        n_req = max(30, min(200, batch))
        load = threading.Thread(target=pound, daemon=True)
        load.start()
        try:
            t0 = _time.perf_counter()
            for _ in range(n_req):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{web.port}/wire", timeout=30
                ) as resp:
                    resp.read()
            gw_wall = _time.perf_counter() - t0
        finally:
            stop.set()
            load.join(timeout=60)
        gw_totals = plane.gateway.totals()
        gw_snap = plane.snapshot()["gateway"]
        gateway_ok = (
            gw_totals["requests"] >= n_req
            and "/wire" in gw_snap["endpoints"]
        )
        return {
            "metric": "wire_fabric_ingest",
            "value": round(frames_per_sec, 1),
            "unit": "fabric->ingest frames/s over real TCP, plane attached",
            "lower_is_better": False,
            "wire_plane_overhead": round(max(overhead, 0.0), 4),
            "overhead_raw": round(overhead, 4),
            "overhead_max": max_overhead,
            # required-true verdicts riding tools/bench_history.py
            # --gate: a plane that got expensive OR a gateway wrapper
            # that stopped counting requests fails CI regardless of
            # the headline
            "gate_required_true": [
                "wire_plane_overhead_ok", "gateway_accounted_ok",
            ],
            "wire_plane_overhead_ok": max(overhead, 0.0) <= max_overhead,
            "gateway_accounted_ok": gateway_ok,
            "gateway_requests_per_sec": round(n_req / gw_wall, 1),
            "gateway_requests": n_req,
            "gateway_slow_requests": gw_totals["slow_requests"],
            "flushes_concurrent": flushes[0],
            "links_seen": len(snap["fabric"]["links"]),
            "codec_topics": sorted(snap["fabric"]["codec"]),
            "journal_appends": snap["fabric"]["journal"]["appends"],
            "batch": batch,
            "reps": reps,
        }
    finally:
        if web is not None:
            web.stop()
        for ep in (a, b):
            if ep is not None:
                ep.stop()
                ep._db.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _txstory_metric(batch: int, iters: int) -> dict:
    """Transaction-provenance plane cost + population proof (the
    round-13 tentpole's bench leg): the notary CPU rig serves `batch`
    spends per flush with the lifecycle ledger DETACHED vs ATTACHED
    (utils/txstory.TxStory — admit / flush-membership / verified /
    terminal events per transaction, stage histograms and the slowest
    leaderboard derived at close), interleaved min-of-reps A/B on the
    same fixture through the REAL intake (submit -> enqueue_pending,
    the path that emits). `value` is the fractional flush-wall
    overhead; the acceptance line is <= 2%
    (BENCH_TXSTORY_OVERHEAD_MAX), and `txstory_overhead_ok` rides the
    bench_history --gate as a required-true verdict. The ON side uses
    a FRESH ledger per rep — every rep pays full story creation, the
    honest worst case."""
    import gc
    import time as _time

    from corda_tpu.core import serialization as ser
    from corda_tpu.node.notary import InMemoryUniquenessProvider
    from corda_tpu.utils.txstory import TxStory

    tile = max(1, int(os.environ.get("BENCH_TILE", "8")))
    svc, requester, blobs = _trace_fixture(min(tile, batch), batch, cpu=True)
    spends = [ser.decode(b) for b in blobs]
    reps = max(2, iters)

    def run_once(story) -> float:
        svc.attach_txstory(story)   # None detaches (the OFF side)
        svc.uniqueness = InMemoryUniquenessProvider()
        futs = []
        t0 = _time.perf_counter()
        for stx in spends:
            # the REAL intake path (enqueue_pending): admit + terminal
            # hooks are exactly what production requests pay
            futs.append(svc.submit(stx, requester))
        svc.flush()
        wall = _time.perf_counter() - t0
        for fut in futs:
            sig = fut.result()
            if not hasattr(sig, "by"):
                raise SystemExit(
                    f"txstory metric notarisation failed: {sig}"
                )
        return wall

    # population proof, untimed: one pass with a ledger attached must
    # yield a complete admission->commit story per transaction
    proof = TxStory()
    run_once(proof)
    sample = proof.story(str(spends[0].id))
    if sample is None or sample["terminal"] != "committed":
        raise SystemExit(
            f"txstory metric: no committed story for the first spend "
            f"({sample})"
        )
    if sample["event_count"] < 4 or "total" not in sample["stages_micros"]:
        raise SystemExit(
            f"txstory metric: incomplete story {sample}"
        )
    if not proof.slowest(1):
        raise SystemExit("txstory metric: empty slowest leaderboard")

    run_once(None)                   # warm-up both sides
    walls_off, walls_on = [], []
    for _ in range(reps):            # interleaved A/B: drift cancels
        gc.collect()                 # equalise collector debt per rep
        walls_off.append(run_once(None))
        gc.collect()
        walls_on.append(run_once(TxStory()))
    svc.attach_txstory(None)
    overhead = min(walls_on) / min(walls_off) - 1.0
    max_overhead = float(
        os.environ.get("BENCH_TXSTORY_OVERHEAD_MAX", "0.02")
    )
    return {
        "metric": "txstory_plane_overhead",
        "value": round(max(overhead, 0.0), 4),
        "unit": "fractional flush-wall overhead of the lifecycle ledger",
        # direction marker (see perf_plane_overhead): overhead gates
        # when it grows, not when it improves
        "lower_is_better": True,
        "vs_baseline": round(max(overhead, 0.0), 4),
        "overhead_raw": round(overhead, 4),
        "overhead_max": max_overhead,
        "txstory_overhead_ok": overhead <= max_overhead,
        "gate_required_true": ["txstory_overhead_ok"],
        "events_per_tx": round(
            proof.recorded / max(1, len(spends)), 2
        ),
        "sample_stages_micros": sample["stages_micros"],
        "batch": batch,
        "reps": reps,
    }


def _sanitizer_metric(batch: int, iters: int) -> dict:
    """Disarmed-lock-factory overhead (the round-14 tentpole's bench
    leg): every `threading.*` constructor site now routes through
    `utils/locks.make_*`, which hands back the RAW primitive while no
    sanitizer monitor is installed — so the only conceivable hot-path
    cost is the factory call at lock CONSTRUCTION time (one FlowFuture
    lock per submitted request). A/B on the notary CPU flush wall
    through the REAL intake: the committed disarmed factory vs the
    factory bypassed to bare `threading` constructors, interleaved
    min-of-reps on the same fixture. `value` is the fractional
    flush-wall overhead of the committed factory; the acceptance line
    is <= 1% (BENCH_SANITIZER_OVERHEAD_MAX) and `sanitizer_overhead_ok`
    rides bench_history --gate as a required-true verdict — if a later
    change makes the disarmed path return wrappers, this trips. The
    ARMED cost (full lockdep recording) is reported as
    `armed_overhead` for context, ungated: arming is a test-rig act,
    never a production state."""
    import gc
    import threading
    import time as _time

    from corda_tpu.core import serialization as ser
    from corda_tpu.node.notary import InMemoryUniquenessProvider
    from corda_tpu.testing.sanitizer import ConcurrencySanitizer
    from corda_tpu.utils import locks as lockslib

    tile = max(1, int(os.environ.get("BENCH_TILE", "8")))
    svc, requester, blobs = _trace_fixture(min(tile, batch), batch, cpu=True)
    spends = [ser.decode(b) for b in blobs]
    reps = max(2, iters)

    # passthrough proof: disarmed, the factory returns the raw
    # primitives — no wrapper object exists to pay for
    if type(lockslib.make_lock("bench.probe")) is not type(
        threading.Lock()
    ):
        raise SystemExit(
            "disarmed make_lock returned a wrapper — the passthrough "
            "contract is broken"
        )

    def run_once() -> float:
        svc.uniqueness = InMemoryUniquenessProvider()
        futs = []
        t0 = _time.perf_counter()
        for stx in spends:
            futs.append(svc.submit(stx, requester))
        svc.flush()
        wall = _time.perf_counter() - t0
        for fut in futs:
            sig = fut.result()
            if not hasattr(sig, "by"):
                raise SystemExit(
                    f"sanitizer metric notarisation failed: {sig}"
                )
        return wall

    committed = (
        lockslib.make_lock, lockslib.make_rlock, lockslib.make_condition
    )

    def bypass() -> None:
        lockslib.make_lock = lambda name: threading.Lock()
        lockslib.make_rlock = lambda name: threading.RLock()
        lockslib.make_condition = (
            lambda name, lock=None: threading.Condition(lock)
        )

    def restore() -> None:
        (
            lockslib.make_lock,
            lockslib.make_rlock,
            lockslib.make_condition,
        ) = committed

    run_once()                      # warm-up
    walls_off, walls_on = [], []
    try:
        for _ in range(reps):       # interleaved A/B: drift cancels
            gc.collect()
            bypass()
            walls_off.append(run_once())
            restore()
            gc.collect()
            walls_on.append(run_once())
    finally:
        restore()
    overhead = min(walls_on) / min(walls_off) - 1.0

    # armed cost, informational: full held-stack/edge/hold recording
    gc.collect()
    san = ConcurrencySanitizer()
    with san:
        wall_armed = run_once()
    armed_overhead = wall_armed / min(walls_off) - 1.0

    max_overhead = float(
        os.environ.get("BENCH_SANITIZER_OVERHEAD_MAX", "0.01")
    )
    return {
        "metric": "sanitizer_factory_overhead",
        "value": round(max(overhead, 0.0), 4),
        "unit": "fractional flush-wall overhead of the disarmed factory",
        "lower_is_better": True,
        "vs_baseline": round(max(overhead, 0.0), 4),
        "overhead_raw": round(overhead, 4),
        "overhead_max": max_overhead,
        "sanitizer_overhead_ok": overhead <= max_overhead,
        "gate_required_true": ["sanitizer_overhead_ok"],
        "armed_overhead": round(max(armed_overhead, 0.0), 4),
        "armed_locks_observed": len(san.lock_stats()),
        "batch": batch,
        "reps": reps,
    }


def _statestore_metric(batch: int, iters: int) -> dict:
    """Billion-state uniqueness store (round 19, node/statestore.py):
    sustained `commit_many` rate of the commit-log + mmap-index
    backend vs the sqlite backend over a pre-populated committed-state
    set, batched-probe p99 flatness as the set grows 10x, and a
    bit-exact accept/reject replay vs sqlite — the scale story the
    registry was built for, CI-scaled.

    The set size is BENCH_STATESTORE_STATES (default 50k: CI-safe in
    seconds); the 10^7-state acceptance run is the same command with
    BENCH_STATESTORE_STATES=10000000 — nothing in the layout changes
    with n (probes touch O(1) mmap slots, commits append), which is
    exactly what `statestore_p99_flat` pins: probe p99 at 10xS must
    stay within BENCH_STATESTORE_P99_FACTOR (default 3.0, generous
    for CI noise — the deterministic gate is tests/test_statestore.py)
    of p99 at S. Durability parity for the rate A/B: the sqlite
    backend runs file-backed with its production pragmas (WAL,
    synchronous=NORMAL — no per-commit fsync), so the commit-log side
    runs fsync=False (group-commit, same WAL discipline). Verdicts
    `statestore_commit_rate_ok` (commit-log >= sqlite x
    BENCH_STATESTORE_RATE_MARGIN), `statestore_p99_flat` and
    `statestore_bitexact_vs_sqlite` ride bench_history --gate as
    REQUIRED-TRUE."""
    import shutil
    import tempfile
    import time as _time

    from corda_tpu.core.contracts import StateRef
    from corda_tpu.crypto.hashes import SecureHash
    from corda_tpu.node.notary import UniquenessConflict
    from corda_tpu.node.persistence import (
        NodeDatabase, ShardedPersistentUniquenessProvider,
    )
    from corda_tpu.node.statestore import (
        CommitLogStateStore, ShardedCommitLogUniquenessProvider,
    )

    rng = random.Random(19)
    states = max(
        int(os.environ.get("BENCH_STATESTORE_STATES", "50000")), 1000
    )
    rate_margin = float(
        os.environ.get("BENCH_STATESTORE_RATE_MARGIN", "0.9")
    )
    p99_factor = float(
        os.environ.get("BENCH_STATESTORE_P99_FACTOR", "3.0")
    )
    reps = max(2, iters)

    class _P:
        name = "O=Bench"

    party = _P()

    def mkrefs(n: int) -> list:
        return [StateRef(SecureHash(rng.randbytes(32)), 0)
                for _ in range(n)]

    def entries_of(refs: list) -> list:
        # multi-input transactions, 32 inputs each: the flush shape
        return [(refs[i:i + 32], SecureHash(rng.randbytes(32)), party)
                for i in range(0, len(refs), 32)]

    root = tempfile.mkdtemp(prefix="bench_statestore_")
    try:
        # -- commit-rate A/B at depth --------------------------------
        sq = ShardedPersistentUniquenessProvider(
            NodeDatabase(os.path.join(root, "sq.db")), 2
        )
        cl = ShardedCommitLogUniquenessProvider(
            os.path.join(root, "cl"), 2,
            segment_max_records=1 << 20,
            compact_min_segments=1 << 30, fsync=False,
        )
        for i in range(0, states, 4096):
            chunk = entries_of(mkrefs(min(4096, states - i)))
            sq.commit_many(chunk)
            cl.commit_many(chunk)
        cl.compact_all()   # probes below hit the mmap snapshot path

        walls_sq, walls_cl = [], []
        for _ in range(reps):   # interleaved A/B: drift cancels
            fresh = entries_of(mkrefs(batch))
            t0 = _time.perf_counter()
            out_sq = sq.commit_many(fresh)
            walls_sq.append(_time.perf_counter() - t0)
            t0 = _time.perf_counter()
            out_cl = cl.commit_many(fresh)
            walls_cl.append(_time.perf_counter() - t0)
            if any(r is not None for r in out_sq + out_cl):
                raise SystemExit(
                    "fresh-ref commit conflicted — the rate fixture "
                    "is broken"
                )
        rate_sq = batch / min(walls_sq)
        rate_cl = batch / min(walls_cl)
        ratio = rate_cl / rate_sq
        depth = cl.committed_count
        cl.close()

        # -- probe p99 flatness: grow ONE store S -> 10S -------------
        store = CommitLogStateStore(
            os.path.join(root, "p99"),
            segment_max_records=1 << 20,
            compact_min_segments=1 << 30, fsync=False,
        )
        kept: list = []   # every 16th ref: the probe sample pool
        tx = SecureHash(rng.randbytes(32))

        def grow(n: int) -> None:
            for i in range(0, n, 8192):
                refs = mkrefs(min(8192, n - i))
                kept.extend(refs[::16])
                store.commit_rows([(r, tx, "O=Bench") for r in refs])
            store.compact(force=True)   # probes read the mmap index

        def probe_p99_us() -> float:
            probe = min(256, len(kept))
            calls = 200
            walls = []
            for _ in range(calls):
                sample = rng.sample(kept, probe)
                t0 = _time.perf_counter()
                got = store.prior_consumers_many(sample)
                walls.append(_time.perf_counter() - t0)
                if len(got) != probe:
                    raise SystemExit(
                        "a committed ref probed silent — the index "
                        "is lying"
                    )
            walls.sort()
            return walls[int(0.99 * (len(walls) - 1))] / probe * 1e6

        grow(states)
        p99_small = probe_p99_us()
        grow(9 * states)
        p99_big = probe_p99_us()
        big_states = store.committed_count
        store.close()
        p99_ratio = p99_big / p99_small

        # -- bit-exact accept/reject replay vs sqlite ----------------
        pool = [StateRef(SecureHash(rng.randbytes(32)), rng.randrange(4))
                for _ in range(240)]
        workload = [
            (rng.sample(pool, rng.randint(1, 4)),
             SecureHash(rng.randbytes(32)), party)
            for _ in range(160)
        ]
        sq2 = ShardedPersistentUniquenessProvider(
            NodeDatabase(":memory:"), 4
        )
        cl2 = ShardedCommitLogUniquenessProvider(
            os.path.join(root, "bitexact"), 4,
            segment_max_records=32, compact_min_segments=2,
            fsync=False,
        )
        got_sq = sq2.commit_many(workload)
        got_cl = cl2.commit_many(workload)
        bitexact = len(got_sq) == len(got_cl) and all(
            (a is None and b is None)
            or (isinstance(a, UniquenessConflict)
                and isinstance(b, UniquenessConflict)
                and a.conflict == b.conflict)
            for a, b in zip(got_sq, got_cl)
        ) and cl2.committed == sq2.committed
        conflicts = sum(1 for r in got_sq if r is not None)
        cl2.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    return {
        "metric": "statestore_commit_rate",
        "value": round(rate_cl, 1),
        "unit": "states/s through commit_many at a pre-populated set "
                "(commit-log backend)",
        "lower_is_better": False,
        "vs_baseline": round(ratio, 3),
        "sqlite_rate": round(rate_sq, 1),
        "commit_rate_vs_sqlite": round(ratio, 3),
        "rate_margin": rate_margin,
        "statestore_commit_rate_ok": ratio >= rate_margin,
        "prepopulated_states": states,
        "grown_states": big_states,
        "commit_depth": depth,
        "probe_p99_us_per_ref_at_s": round(p99_small, 3),
        "probe_p99_us_per_ref_at_10s": round(p99_big, 3),
        "probe_p99_ratio": round(p99_ratio, 3),
        "p99_factor_max": p99_factor,
        "statestore_p99_flat": p99_ratio <= p99_factor,
        "bitexact_conflicts": conflicts,
        "statestore_bitexact_vs_sqlite": bitexact,
        "gate_required_true": [
            "statestore_commit_rate_ok", "statestore_p99_flat",
            "statestore_bitexact_vs_sqlite",
        ],
        "extrapolation": "probes touch O(1) mmap slots and commits "
                         "append; rerun with "
                         "BENCH_STATESTORE_STATES=10000000 for the "
                         "10^7-state acceptance record",
        "batch": batch,
        "reps": reps,
    }


def _montmul_metric(batch: int, iters: int) -> dict:
    """Interleaved device-resident A/B of the two variable x variable
    Montgomery-multiply formulations (round-3 MXU experiment, VERDICT
    r2 #5): `vpu` = the production shifted-accumulate schoolbook
    (`modmath._diag_mul`), `mxu` = batched int8 Toeplitz dot_general
    (`modmath._diag_mul_mxu`). Each side runs a 64-deep scan chain of
    full mont_muls (so the measurement is device-resident, not
    dispatch-bound), alternating A/B per rep; the reported value is
    best-of-reps mxu/vpu rate ratio (>1 means the MXU form wins)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from corda_tpu.crypto import modmath as mm
    from corda_tpu.crypto.curves import SECP256R1
    from corda_tpu.crypto.limbs import int_to_limbs

    ctx = mm.MontCtx.make(SECP256R1.p)
    rng = np.random.default_rng(11)

    def rand_batch():
        vals = [
            int.from_bytes(rng.bytes(32), "big") % SECP256R1.p
            for _ in range(batch)
        ]
        return jnp.asarray(
            np.stack([int_to_limbs(v) for v in vals], axis=1).astype(np.int32)
        )

    a, b = rand_batch(), rand_batch()
    chain = 64

    def make(form):
        def body(x, _):
            return mm._mont_reduce(ctx, form(x, b)), None

        return jax.jit(lambda x: lax.scan(body, x, None, length=chain)[0])

    f_vpu, f_mxu = make(mm._diag_mul), make(mm._diag_mul_mxu)
    # warm-up compiles + exactness: both formulations produce identical
    # raw column sums, so the chained outputs must be bit-identical
    ra = np.asarray(jax.block_until_ready(f_vpu(a)))
    rb = np.asarray(jax.block_until_ready(f_mxu(a)))
    if not np.array_equal(ra, rb):
        raise SystemExit("MXU/VPU montmul mismatch — bench aborted")

    best = {"vpu": 0.0, "mxu": 0.0}
    for _ in range(max(iters, 3)):
        for name, f in (("vpu", f_vpu), ("mxu", f_mxu)):  # interleaved
            t0 = time.perf_counter()
            jax.block_until_ready(f(a))
            dt = time.perf_counter() - t0
            best[name] = max(best[name], batch * chain / dt)
    ratio = best["mxu"] / best["vpu"]
    return {
        "metric": "mxu_montmul_ab_ratio",
        "value": round(ratio, 3),
        "unit": "mxu/vpu rate ratio",
        "vs_baseline": round(ratio, 3),
        "vpu_muls_per_sec": round(best["vpu"], 1),
        "mxu_muls_per_sec": round(best["mxu"], 1),
    }


def _requests(batch: int, metric: str):
    from corda_tpu.crypto import schemes
    from corda_tpu.crypto.batch_verifier import VerificationRequest

    if metric == "mixed":
        scheme_ids = (
            schemes.EDDSA_ED25519_SHA512,
            schemes.ECDSA_SECP256K1_SHA256,
            schemes.ECDSA_SECP256R1_SHA256,
        )
    else:
        scheme_ids = (schemes.ECDSA_SECP256R1_SHA256,)

    # fixture tiling: signing is pure-Python host math (~8 ms/sig), so
    # a 32k fully-unique fixture costs minutes of child wall-clock —
    # which is what timed the round-3 driver record out, and none of
    # which is measured work. Build batch/BENCH_TILE unique rows and
    # repeat the block: the SPI has no dedup/memo of any kind (every
    # row packs, ships and verifies identically), so the measured rate
    # is unchanged while the fixture builds 8x faster. BENCH_TILE=1
    # restores a fully unique fixture.
    tile = max(1, int(os.environ.get("BENCH_TILE", "8")))
    unique = -(-batch // tile)   # ceil
    rng = random.Random(2026)
    keys = {
        sid: [
            schemes.generate_keypair(sid, seed=rng.getrandbits(128))
            for _ in range(8)
        ]
        for sid in scheme_ids
    }
    reqs = []
    for i in range(unique):
        sid = scheme_ids[i % len(scheme_ids)]
        kp = keys[sid][i % 8]
        msg = rng.randbytes(64)
        sig = kp.private.sign(msg)
        if i % 7 == 3:  # mix in rejects so accept/reject is exercised
            msg = msg + b"x"
        reqs.append(VerificationRequest(kp.public, sig, msg))
    return (reqs * tile)[:batch]


def _spi_metric(metric: str, batch: int, iters: int) -> dict:
    from corda_tpu.crypto.batch_verifier import (
        CpuBatchVerifier,
        TpuBatchVerifier,
    )

    reqs = _requests(batch, metric)
    # per-scheme buckets pad to the bucket size; with mixed thirds the
    # relevant jit shape is ceil(batch/3) rounded up — give the verifier
    # both sizes so caches stay warm. BENCH_CHUNK < batch splits the
    # batch into pipelined chunks: host staging of chunk k+1 overlaps
    # device compute of chunk k (dispatch is async).
    chunk = int(os.environ.get("BENCH_CHUNK", "4096"))
    chunk = min(chunk, batch)
    # one size for both metrics: per-scheme buckets chunk at `chunk`
    # (smaller mixed buckets pad up to it — padding is cheaper than
    # losing the host/device overlap)
    verifier = TpuBatchVerifier(batch_sizes=(chunk,))

    got = verifier.verify_batch(reqs)  # warm-up: compile + correctness
    spot = random.Random(1).sample(range(batch), 32)
    cpu = CpuBatchVerifier().verify_batch([reqs[i] for i in spot])
    if [got[i] for i in spot] != cpu:   # must survive python -O
        raise SystemExit("TPU/CPU mismatch — bench aborted")

    rates = sorted(
        _timed_rates(lambda: verifier.verify_batch(reqs), batch, iters)
    )
    value = round(_median(rates), 1)
    name = (
        "ecdsa_p256_verifies_per_sec_via_spi"
        if metric == "p256"
        else "mixed_scheme_verifies_per_sec_via_spi"
    )
    return {
        "metric": name,
        "value": value,
        "unit": "verifies/s",
        "vs_baseline": round(value / BASELINE, 3),
        "spread": {
            "min": round(rates[0], 1),
            "max": round(rates[-1], 1),
            "reps": len(rates),
        },
    }


def _fleet_metric(batch: int, iters: int) -> dict:
    """Fleet soak (round 8): the simulated-time fleet simulator
    (corda_tpu/testing/fleet.py) drives a QoS batching notary through a
    ramp -> steady -> 3x spike -> recovery arc with a wedged-pump
    freeze mid-steady and injected double-spends, then reconciles the
    ledger against the model. `value` is simulated-time goodput
    (signed notarisations per simulated second under churn); the
    record's `reconciled` and `slo_held` verdicts are REQUIRED-TRUE
    gate keys for tools/bench_history.py — a soak that stops
    reconciling fails the gate no matter what the headline says."""
    from corda_tpu.node import qos as qoslib
    from corda_tpu.testing import fleet as fl

    R = 20_000
    cap = max(4, min(batch, 16))
    clients = int(os.environ.get("BENCH_FLEET_CLIENTS", "256"))
    steady = max(8, 4 * iters)
    slo_micros = 5 * R
    mix = fl.TrafficMix(deadline_micros=6 * R, conflict_fraction=0.06)
    scenario = fl.FleetScenario(
        clients=clients,
        phases=(
            fl.Phase("ramp", 2, max(1, cap // 2), mix),
            fl.Phase("steady", steady, cap, mix),
            fl.Phase("spike", 4, 3 * cap, fl.TrafficMix(
                deadline_micros=6 * R, bulk_fraction=0.34,
                conflict_fraction=0.06,
            )),
            fl.Phase("steady2", 6, max(1, cap - 1), mix),
        ),
        round_micros=R, drain_rounds=60, seed=17,
    )
    sim = fl.FleetSim(
        scenario, "batching",
        chaos=(fl.freeze(0, at=0.15, until=0.30),),
        qos_policy=qoslib.QosPolicy(
            target_p99_micros=slo_micros,
            min_batch=cap, max_batch=cap, max_wait_micros=0,
            brownout_after_flushes=3,
        ),
    )
    report = sim.run()
    checker = fl.InvariantChecker(report)
    reconcile_error = slo_error = None
    try:
        checker.check_replica_agreement()
        checker.check_ledger_vs_answers()
        checker.check_exactly_one_winner()
        checker.check_no_admitted_then_expired()
        checker.check_lost_bounded()
        checker.check_brownout_classes()
        checker.check_health_story()
        reconciled = True
    except AssertionError as e:
        reconciled, reconcile_error = False, str(e)
    try:
        checker.check_slo(slo_micros)
        slo_held = True
    except AssertionError as e:
        slo_held, slo_error = False, str(e)
    outcomes = report.outcomes()
    goodput = outcomes.get(fl.OUT_SIGNED, 0) / max(
        report.sim_seconds, 1e-9
    )
    return {
        "metric": "fleet_soak_goodput",
        "value": round(goodput, 3),
        "unit": "signed notarisations per SIMULATED second under churn",
        "vs_baseline": None,
        # bench_history --gate: these keys must be true in the newest
        # record — throughput without reconciliation is just a number
        "gate_required_true": ["reconciled", "slo_held"],
        "reconciled": reconciled,
        "slo_held": slo_held,
        "reconcile_error": reconcile_error,
        "slo_error": slo_error,
        "clients": clients,
        "distinct_clients": report.distinct_clients,
        "requests": len(report.records),
        "outcomes": outcomes,
        "shed_counters": dict(report.qos.snapshot()["shed"]),
        "bulk_offered": report.bulk_offered,
        "bulk_shed_brownout": report.bulk_shed_brownout,
        "faults_injected": len(report.chaos_log),
        "faults": [e["name"] for e in report.chaos_log],
        "sim_seconds": round(report.sim_seconds, 6),
        "slo_target_ms": round(slo_micros / 1e3, 3),
    }


def _distributed_metric(batch: int, iters: int) -> dict:
    """Distributed sharded uniqueness (round 12): the fleet simulator
    drives a 3-member notary cluster whose state-ref space is
    partitioned ACROSS the members (corda_tpu/node/
    distributed_uniqueness.py) — half the spends cross members and
    take the fabric two-phase reserve→commit — through a kill/restart
    of the coordinator-heavy home member mid-stream, with injected
    cross-shard double-spends. `value` is the cluster's simulated-time
    goodput; `vs_single_owner` compares the SAME offered load against
    a single-member cluster (every commit local — what the distributed
    plane's message round trips cost); `recovery_micros_after_kill` is
    how much simulated time the restarted member needed to finish
    everything still open after its WAL recovery. The record's
    `xshard_zero_orphans` and `xshard_exactly_once` verdicts are
    REQUIRED-TRUE gate keys for tools/bench_history.py — throughput
    with a leaked reservation or a double-signed double-spend fails
    the gate no matter what the headline says."""
    from corda_tpu.testing import fleet as fl

    R = 20_000
    cap = max(4, min(batch, 8))
    clients = int(os.environ.get("BENCH_DIST_CLIENTS", "192"))
    steady = max(10, 5 * iters)
    mix = fl.TrafficMix(
        deadline_micros=200 * R, conflict_fraction=0.08,
        cross_shard_fraction=0.5,
    )
    scenario = fl.FleetScenario(
        clients=clients,
        phases=(fl.Phase("steady", steady, cap, mix),),
        round_micros=R, drain_rounds=100, seed=23,
    )

    def run(cluster_size: int, chaos=()):
        sim = fl.FleetSim(
            scenario, "distributed", cluster_size=cluster_size,
            chaos=chaos, intent_wal=True,
        )
        report = sim.run()
        out = report.outcomes()
        goodput = out.get(fl.OUT_SIGNED, 0) / max(report.sim_seconds, 1e-9)
        lat = [
            r.answered_at - r.submitted_at
            for r in report.records
            if r.outcome == fl.OUT_SIGNED and r.answered_at is not None
        ]
        mean_lat = sum(lat) / max(len(lat), 1)
        return report, out, goodput, mean_lat

    chaos = (fl.kill_restart(0, at=0.45, restart_at=0.6),)
    report, outcomes, goodput, mean_lat = run(3, chaos)
    _base_report, _base_out, base_goodput, base_lat = run(1)
    checker = fl.InvariantChecker(report)
    exactly_once = True
    reconcile_error = None
    try:
        checker.check_all()
    except AssertionError as e:
        exactly_once, reconcile_error = False, str(e)
    zero_orphans = (
        all(v == 0 for v in report.reservations_live.values())
        and all(v == 0 for v in report.xshard_orphans.values())
        and report.intent_unresolved == 0
    )
    kill = next(
        (e for e in report.chaos_log if e["kind"] == "kill"), None
    )
    recovery_micros = None
    if kill is not None and kill.get("reverted_at_micros"):
        restart_at = kill["reverted_at_micros"]
        tail = [
            r.answered_at for r in report.records
            if r.answered_at is not None and r.answered_at >= restart_at
        ]
        recovery_micros = (max(tail) - restart_at) if tail else 0
    return {
        "metric": "distributed_commit",
        "value": round(goodput, 3),
        "unit": "signed notarisations per SIMULATED second, 3-member "
                "cluster under kill/restart, 50% cross-shard",
        "vs_baseline": None,
        "vs_single_owner": round(goodput / max(base_goodput, 1e-9), 3),
        "single_owner_goodput": round(base_goodput, 3),
        # where the cross-member protocol's cost actually shows in
        # simulated time: answer latency vs the all-local baseline
        # (goodput is offered-load-bound in both configurations)
        "answer_latency_micros_mean": round(mean_lat, 1),
        "single_owner_latency_micros_mean": round(base_lat, 1),
        "latency_vs_single_owner": round(
            mean_lat / max(base_lat, 1e-9), 3
        ),
        "recovery_micros_after_kill": recovery_micros,
        # bench_history --gate: REQUIRED TRUE in the newest record
        "gate_required_true": ["xshard_zero_orphans", "xshard_exactly_once"],
        "xshard_zero_orphans": zero_orphans,
        "xshard_exactly_once": exactly_once,
        "reconcile_error": reconcile_error,
        "cluster_shards": report.cluster_shards,
        "members": len(report.members),
        "clients": clients,
        "requests": len(report.records),
        "outcomes": outcomes,
        "decisions": len(report.xshard_decisions),
        "intent_replayed": report.intent_replayed,
        "faults": [e["name"] for e in report.chaos_log],
        "sim_seconds": round(report.sim_seconds, 6),
    }


def _faults_metric(batch: int, iters: int) -> dict:
    """Fault-tolerance plane (round 9): what the self-healing costs
    when nothing is broken, and whether it actually recovers when
    something is. Three interleaved A/B measurements on the CPU rig:

      - WAL append overhead: notarisations/s with the intent journal
        on a real (fsynced, WAL-mode) file vs without — the `value`
        headline is the WAL-on rate, `wal_overhead_fraction` the cost.
      - degraded-flush CPU-fallback throughput: flush wall with the
        dispatch-seam injector forcing retry->CPU-reference fallback
        vs the clean path, same spends.
      - redispatch latency penalty: wall time for a pool of verify
        round trips to ALL resolve with one of two workers killed
        mid-stream (lease expiry -> redispatch) vs unkilled.

    The record's recovery verdicts are REQUIRED-TRUE gate keys for
    tools/bench_history.py: a build whose degraded flush stops
    committing, whose WAL replay loses a request, or whose redispatch
    strands a future fails the gate no matter what the rates say."""
    import tempfile

    from corda_tpu.crypto.batch_verifier import (
        CpuBatchVerifier,
        DispatchFaultInjector,
    )
    from corda_tpu.node.notary import (
        BatchingNotaryService,
        InMemoryUniquenessProvider,
    )
    from corda_tpu.node.persistence import NodeDatabase, NotaryIntentJournal

    # hard cap: every flush here runs PURE-PYTHON reference crypto
    # (that is the point — the degraded path), so depth is latency
    batch = max(16, min(batch, 128))
    net, notary, alice, spends = _notary_fixture(
        batch, batch_verifier=CpuBatchVerifier()
    )
    requester = alice.party
    tmp = tempfile.mkdtemp(prefix="bench_faults_")
    dbs: list = []

    def flush_wall(intent_wal: bool, inject: bool) -> tuple[float, dict]:
        """One full submit-all + flush through a fresh service;
        returns (wall seconds, outcome summary)."""
        injector = DispatchFaultInjector(CpuBatchVerifier())
        notary.services._batch_verifier = injector
        journal = None
        if intent_wal:
            db = NodeDatabase(
                os.path.join(tmp, f"wal{len(dbs)}.db")
            )
            dbs.append(db)
            journal = NotaryIntentJournal(db)
        svc = BatchingNotaryService(
            notary.services, InMemoryUniquenessProvider(),
            intent_journal=journal,
        )
        if inject:
            injector.arm(2)    # dispatch + retry fail -> CPU fallback
        t0 = time.perf_counter()
        futs = [svc.submit(stx, requester) for stx in spends]
        svc.flush()
        svc.tick()             # group-commit the WAL deletes
        wall = time.perf_counter() - t0
        signed = sum(
            1 for f in futs if f.done and hasattr(f.result(), "by")
        )
        return wall, {
            "signed": signed,
            "answered": sum(1 for f in futs if f.done),
            "degraded": svc.degraded,
            "degraded_flushes": svc.metrics.counter(
                "Notary.DegradedFlushes"
            ).count,
            "wal_unresolved": (
                journal.unresolved_count if journal is not None else 0
            ),
        }

    # interleaved A/B, min-of-reps: wal-off / wal-on / degraded
    reps = max(2, iters)
    wal_off = wal_on = degraded = float("inf")
    wal_on_info = degraded_info = {}
    for _ in range(reps):
        w, _info = flush_wall(intent_wal=False, inject=False)
        wal_off = min(wal_off, w)
        w, info = flush_wall(intent_wal=True, inject=False)
        if w < wal_on:
            wal_on, wal_on_info = w, info
        w, info = flush_wall(intent_wal=False, inject=True)
        if w < degraded:
            degraded, degraded_info = w, info
    degraded_recovered = (
        degraded_info["answered"] == batch
        and degraded_info["signed"] == batch
        and degraded_info["degraded_flushes"] >= 1
    )
    wal_ok = (
        wal_on_info["signed"] == batch
        and wal_on_info["wal_unresolved"] == 0
    )

    # WAL kill/replay: admit without flushing, "crash", reopen, replay
    path = os.path.join(tmp, "replay.db")
    db = NodeDatabase(path)
    journal = NotaryIntentJournal(db)
    notary.services._batch_verifier = CpuBatchVerifier()
    uniq = InMemoryUniquenessProvider()
    svc = BatchingNotaryService(
        notary.services, uniq, intent_journal=journal
    )
    n_replay = min(64, batch)
    for stx in spends[:n_replay]:
        svc.submit(stx, requester)    # admitted, never flushed
    db.close()                        # process death
    db2 = NodeDatabase(path)
    journal2 = NotaryIntentJournal(db2)
    svc2 = BatchingNotaryService(
        notary.services, uniq, intent_journal=journal2
    )
    replayed = svc2.replay_intents()
    svc2.flush()
    svc2.tick()
    wal_zero_loss = (
        len(replayed) == n_replay
        and all(f.done for _s, _t, f in replayed)
        and journal2.unresolved_count == 0
        and wal_ok
    )
    for db_ in dbs:
        db_.close()
    db2.close()

    # redispatch penalty: real-time two-worker pool, one killed
    # mid-stream vs none (node/verifier.py lease/redispatch walk)
    from corda_tpu.node.messaging import FabricFaults
    from corda_tpu.node.verifier import (
        OutOfProcessTransactionVerifierService,
        RedispatchPolicy,
        VerifierWorker,
    )
    from corda_tpu.testing.mock_network import MockNetwork

    def pool_wall(kill: bool) -> tuple[float, bool]:
        faults = FabricFaults()
        pnet = MockNetwork(
            seed=7, faults=faults, batch_verifier=CpuBatchVerifier()
        )
        pnotary = pnet.create_notary()
        node = pnet.create_node("PoolNode")
        from corda_tpu.finance import CashIssueFlow

        stx = node.run_flow(
            CashIssueFlow(9, "USD", node.party, pnotary.party)
        )
        ltx = node.services.resolve_transaction(stx.wtx)
        pool = OutOfProcessTransactionVerifierService(
            node.messaging,
            policy=RedispatchPolicy(
                lease_micros=60_000,
                backoff_base_micros=10_000,
                backoff_cap_micros=40_000,
                request_timeout_micros=20_000_000,
            ),
        )
        workers = [
            VerifierWorker(
                pnet.fabric.endpoint(f"pw{k}"), "PoolNode",
                batch_verifier=CpuBatchVerifier(),
                heartbeat_micros=20_000,
            )
            for k in range(2)
        ]
        pnet.fabric.run()
        t0 = time.perf_counter()
        futs = [pool.verify(ltx, stx) for _ in range(16)]
        if kill:
            faults.kill("pw0")
            pnet.fabric.endpoint("pw0").running = False
        deadline = t0 + 30.0
        while (
            not all(f.done for f in futs)
            and time.perf_counter() < deadline
        ):
            pnet.fabric.run()
            for k, w in enumerate(workers):
                if not (kill and k == 0):
                    w.drain()
            pool.tick()
            time.sleep(0.002)
        return time.perf_counter() - t0, all(f.done for f in futs)

    pool_wall(kill=False)   # warmup: imports + first-rig costs out
    base_wall, base_ok = pool_wall(kill=False)
    kill_wall, kill_ok = pool_wall(kill=True)
    redispatch_recovered = base_ok and kill_ok

    return {
        "metric": "fault_tolerance_plane",
        "value": round(batch / wal_on, 3),
        "unit": "notarisations/s through a WAL-journaled CPU flush",
        "vs_baseline": None,
        "gate_required_true": [
            "redispatch_recovered", "degraded_recovered", "wal_zero_loss",
        ],
        "redispatch_recovered": redispatch_recovered,
        "degraded_recovered": degraded_recovered,
        "wal_zero_loss": wal_zero_loss,
        "batch": batch,
        "wal_off_per_sec": round(batch / wal_off, 3),
        "wal_overhead_fraction": round(max(0.0, wal_on / wal_off - 1), 4),
        "degraded_fallback_per_sec": round(batch / degraded, 3),
        "degraded_throughput_ratio": round(wal_off / degraded, 4),
        "redispatch_base_ms": round(base_wall * 1e3, 3),
        "redispatch_kill_ms": round(kill_wall * 1e3, 3),
        "redispatch_penalty_ms": round(
            max(0.0, kill_wall - base_wall) * 1e3, 3
        ),
        "replayed": len(replayed),
    }


def _parity_metric(batch: int, iters: int) -> dict:
    """Reduced-n refresh of the windowed+plain kernel-parity artifact
    (VERDICT r3 #8): regenerates KERNEL_PARITY.json from the default
    bench run so the evidence cannot rot. n is small (BENCH_PARITY_N,
    default 256 adversarial vectors) — the full 2048-vector record
    remains available via `tpu_selfcheck --full`."""
    from corda_tpu.testing.tpu_selfcheck import run_full

    n = int(os.environ.get("BENCH_PARITY_N", "256"))
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "KERNEL_PARITY.json")
    # allow_cpu stays False: overwriting the committed artifact with an
    # XLA-only (no-Pallas) record on a CPU box would downgrade the
    # evidence — off-TPU this raises and the orchestrator reports it
    rec = run_full(
        n=n,
        allow_cpu=False,
        out_path=out,
        generated_by=f"bench.py parity metric (BENCH_PARITY_N={n})",
    )
    return {
        "metric": "kernel_parity_bit_exact",
        "value": 1.0,     # run_full raises on any device/CPU mismatch
        "unit": "bool",
        "vs_baseline": 1.0,
        "n": rec["n"],
        "backend": rec["backend"],
        "runs": rec["runs"],
    }


def _environment() -> dict:
    """The rig this record was measured on, stamped into every metric
    line (and so into every BENCH_r*.json capture): jax version,
    backend platform, device kind + count, host cpu count. The
    trajectory tool (tools/bench_history.py) compares the newest two
    records' environments and DOWNGRADES its regression gate to
    warn-and-annotate when they differ — the CPU-container r06 vs the
    coming device round must not trade false gate failures."""
    env: dict = {
        "python": sys.version.split()[0],
        "cpu_count": os.cpu_count(),
    }
    try:
        import jax

        env["jax"] = jax.__version__
        devices = jax.devices()
        env["backend"] = devices[0].platform if devices else "none"
        env["device_kind"] = (
            devices[0].device_kind if devices else "none"
        )
        env["device_count"] = len(devices)
    except Exception as e:   # noqa: BLE001 - the record still stamps
        env["backend"] = f"unavailable ({type(e).__name__})"
    return env


NO_TPU_EXIT = 3   # a metric child's exit code when it finds no TPU


def _run_metric(metric: str, batch: int, iters: int) -> dict:
    from corda_tpu.utils import jaxenv

    try:
        jaxenv.require_tpu(f"bench metric {metric!r}")
    except RuntimeError as e:
        print(f"bench: {e}", file=sys.stderr)
        raise SystemExit(NO_TPU_EXIT)
    jaxenv.enable_compile_cache()
    out = _run_metric_inner(metric, batch, iters)
    out.setdefault("environment", _environment())
    return out


def _run_metric_inner(metric: str, batch: int, iters: int) -> dict:
    if metric == "merkle":
        return _merkle_metric(min(batch, 32768), iters)
    if metric == "notary":
        # round 6: the hard 16384 flush-depth clamp is LIFTED — depth
        # is per-shard now (BENCH_BATCH spreads across BENCH_SHARDS
        # pipelines), so a 32768 request measures a true 32768-deep
        # plane and depth_saturation reads false in the record
        return _notary_metric(batch, iters)
    if metric == "notary_commit_plane":
        return _commit_plane_metric(batch, iters)
    if metric == "montmul":
        return _montmul_metric(min(batch, 8192), iters)
    if metric == "ingest":
        out = _ingest_metric(min(batch, 16384), iters)
        out["batch"] = min(batch, 16384)   # cap visible in the record
        if batch > 16384:
            out["batch_requested"] = batch
        return out
    if metric == "ingest_pipelined":
        out = _ingest_pipelined_metric(min(batch, 16384), iters)
        out["batch"] = min(batch, 16384)   # cap visible in the record
        if batch > 16384:
            out["batch_requested"] = batch
        return out
    if metric == "trace":
        out = _trace_metric(min(batch, 4096), iters)
        if batch > 4096:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "consensus":
        out = _consensus_metric(min(batch, 512), iters)
        if batch > 512:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "qos":
        out = _qos_metric(min(batch, 256), iters)
        if batch > 256:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "health":
        out = _health_metric(min(batch, 512), iters)
        if batch > 512:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "perf":
        out = _perf_metric(min(batch, 512), iters)
        if batch > 512:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "txstory":
        out = _txstory_metric(min(batch, 512), iters)
        if batch > 512:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "device":
        out = _device_metric(min(batch, 512), iters)
        if batch > 512:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "wire":
        out = _wire_metric(min(batch, 256), iters)
        if batch > 256:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "sanitizer":
        out = _sanitizer_metric(min(batch, 512), iters)
        if batch > 512:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "statestore":
        out = _statestore_metric(min(batch, 8192), iters)
        if batch > 8192:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "fleet":
        out = _fleet_metric(min(batch, 16), iters)
        if batch > 16:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "faults":
        out = _faults_metric(min(batch, 128), iters)
        if batch > 128:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "distributed_commit":
        out = _distributed_metric(min(batch, 8), iters)
        if batch > 8:
            out["batch_requested"] = batch   # cap visible in the record
        return out
    if metric == "parity":
        return _parity_metric(batch, iters)
    return _spi_metric(metric, batch, iters)


def _run_child(m: str, env: dict, timeout: float) -> bool:
    """One metric in its own interpreter; prints its metric line on
    success. Returns False on any failure (reported to stderr)."""
    import subprocess

    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=timeout,
        )
        # pass the child's diagnostics through (the profile lines
        # docs/serving-notary.md documents arrive on stderr)
        if out.stderr:
            sys.stderr.write(out.stderr)
        if out.returncode == NO_TPU_EXIT:
            # no chip: every later child would fail the same way
            raise SystemExit(f"bench metric {m!r}: no TPU — run aborted")
        line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
        json.loads(line)          # a metric line, not stray output
        print(line, flush=True)
        return True
    except Exception as e:   # noqa: BLE001 - keep the run alive
        # a timed-out child still captured diagnostics worth keeping
        child_err = getattr(e, "stderr", None)
        if child_err:
            sys.stderr.write(
                child_err if isinstance(child_err, str)
                else child_err.decode(errors="replace")
            )
        print(f"bench metric {m!r} failed: {e}", file=sys.stderr)
        return False


def _retry_gate(out, rerun, value_key, ok, label, max_overhead):
    """Re-measure a flush-wall overhead gate up to BENCH_GATE_RETRIES
    times (default 2) before letting it fail: one co-scheduled process
    landing on the ON reps inflates min-of-reps A/B on a shared CI box,
    and mid-suite on a single-vCPU runner one retry is demonstrably not
    enough. Keeps the best attempt and stops as soon as the gate
    passes; the first attempt's value rides along in the record."""
    tries = int(os.environ.get("BENCH_GATE_RETRIES", "2"))
    for i in range(tries):
        if ok(out):
            break
        print(
            f"bench: {label} {out[value_key]:.4f} over the "
            f"{max_overhead:.0%} gate — noisy box? retry {i + 1}/{tries}",
            file=sys.stderr,
        )
        retry = rerun()
        if retry[value_key] < out[value_key]:
            retry["first_attempt_overhead"] = out.get(
                "first_attempt_overhead", out[value_key]
            )
            out = retry
    return out


def _quick(metric: str) -> None:
    """`python bench.py --quick ingest|trace|qos|health|fleet`: tiny,
    CPU-safe smoke runs so tier-1 (JAX_PLATFORMS=cpu, no device) can
    assert the perf plumbing emits well-formed records without paying
    a real measurement. Values from this mode are NOT comparable to
    the default run's.

      ingest — serial + pipelined ingest metric lines (PR 1).
      trace  — the full hot path with tracing ON: asserts the stage
               breakdown sums to ~the traced wall and that tracing
               overhead stays under BENCH_TRACE_OVERHEAD_MAX (default
               5%) vs the untraced run on the same fixture.
      qos    — the QoS overload record at 2x offered load, controller
               on vs off: asserts the plane engaged (sheds happened
               and were counted) and goodput held a healthy fraction
               of the no-overload capacity.
      health — the health-plane A/B on the notary CPU rig: asserts
               steady-state overhead <= BENCH_HEALTH_OVERHEAD_MAX
               (default 2%), that a canary round trip completed
               through the real flush, and that the plane reads
               healthy at the end.
      shards — the sharded commit plane (round 6) at a tiny depth with
               verification stubbed: asserts every request answers
               with a signature across 1/2/4-shard configurations
               (each flushed as one wave) and that the sweep
               record is well-formed — the deterministic correctness
               gate is tests/test_sharded_notary.py.
      fleet  — the simulated-time fleet soak (round 8): a small
               chaos-and-reconcile arc on the CPU rig; asserts the
               soak reconciled bit-exact vs the model, held the SLO
               through steady state, shed during the spike, and that
               the chaos plane injected (and recovered from) its
               fault — the full-shape deterministic gate is
               tests/test_fleet.py.
      perf   — the perf-attribution plane (round 7): asserts the
               sampling profiler's measured overhead stays <=
               BENCH_PERF_OVERHEAD_MAX (default 2%) of the notary CPU
               flush wall (interleaved A/B, the health-smoke
               discipline), that the profiler actually sampled, that
               the retrace counter held ZERO on a warm repeat shape,
               and that a forced jit retrace (a deliberately new
               shape after mark_warm) was counted.
      device — the device-telemetry plane (round 15): asserts the
               plane's per-flush tick overhead stays <=
               BENCH_DEVICE_OVERHEAD_MAX (default 2%) of the notary
               CPU flush wall (interleaved A/B) and that the capacity
               model resolves on the measured phase timers and names
               host_pump — the honest answer on a CPU-only rig.
      wire   — the wire & gateway telemetry plane (round 17): asserts
               the fabric A/B overhead stays <= BENCH_WIRE_OVERHEAD_MAX
               (default 2%) of the TCP drain wall, that frames flowed
               end to end, that the gateway dispatch wrapper counted
               every HTTP request it served under concurrent
               notarisation load, and that per-link + journal
               accounting is nonempty.
      statestore — the billion-state uniqueness store (round 19): a
               tiny pre-populated set, asserting the commit-log
               backend's accept/reject stayed bit-exact vs sqlite on
               a conflict-heavy workload, probe p99 held flat across
               a 10x set growth, and the sustained commit_many rate
               held the vs-sqlite margin — the deterministic gate is
               tests/test_statestore.py.
    """
    if metric == "shards":
        # force the smoke's sweep shape: the assertions below pin
        # {1,2,4}, so an inherited BENCH_SHARDS/BENCH_SHARD_SWEEP must
        # not widen it into a spurious CI failure
        os.environ["BENCH_SHARDS"] = "4"
        os.environ["BENCH_SHARD_SWEEP"] = "1,2,4"
        batch = int(os.environ.get("BENCH_BATCH", "48"))
        iters = int(os.environ.get("BENCH_ITERS", "1"))
        out = _commit_plane_metric(batch, iters)
        out["quick"] = True
        print(json.dumps(out), flush=True)
        if set(out["shard_sweep"]) != {"1", "2", "4"}:
            raise SystemExit(
                f"shard sweep incomplete: {sorted(out['shard_sweep'])}"
            )
        if out.get("per_shard_depth", 0) <= 0:
            raise SystemExit("per_shard_depth missing from the record")
        if any(v <= 0 for v in out["shard_sweep"].values()):
            raise SystemExit("a swept configuration measured zero rate")
        return
    if metric == "health":
        batch = int(os.environ.get("BENCH_BATCH", "32"))
        iters = int(os.environ.get("BENCH_ITERS", "3"))
        out = _health_metric(batch, iters)
        out["quick"] = True
        print(json.dumps(out), flush=True)
        max_overhead = float(
            os.environ.get("BENCH_HEALTH_OVERHEAD_MAX", "0.02")
        )
        if out["value"] > max_overhead:
            raise SystemExit(
                f"health plane overhead {out['value']:.4f} exceeds "
                f"{max_overhead:.0%} of the flush wall"
            )
        if out["canary_completed"] < 1:
            raise SystemExit("no canary round trip completed")
        if not out["healthy"]:
            raise SystemExit(
                "health plane reads unhealthy on a healthy rig"
            )
        return
    if metric == "perf":
        batch = int(os.environ.get("BENCH_BATCH", "32"))
        iters = int(os.environ.get("BENCH_ITERS", "3"))
        out = _perf_metric(batch, iters)
        max_overhead = float(
            os.environ.get("BENCH_PERF_OVERHEAD_MAX", "0.02")
        )
        out = _retry_gate(
            out, lambda: _perf_metric(batch, iters), "value",
            lambda o: o["value"] <= max_overhead,
            "perf overhead", max_overhead,
        )
        out["quick"] = True
        print(json.dumps(out), flush=True)
        if out["value"] > max_overhead:
            raise SystemExit(
                f"profiler overhead {out['value']:.4f} exceeds "
                f"{max_overhead:.0%} of the flush wall"
            )
        if out["profiler_samples"] < 1 or out["collapsed_stacks"] < 1:
            raise SystemExit(
                "profiler took no samples during the timed flushes"
            )
        if not out["retrace_stable_after_warmup"]:
            raise SystemExit(
                "retrace counter moved on a WARM shape — a repeat "
                "dispatch must not read as a jit cache miss"
            )
        if not out["retrace_counted"]:
            raise SystemExit(
                "forced jit retrace (fresh shape after warmup) was "
                "not counted"
            )
        return
    if metric == "txstory":
        batch = int(os.environ.get("BENCH_BATCH", "64"))
        iters = int(os.environ.get("BENCH_ITERS", "3"))
        out = _txstory_metric(batch, iters)
        max_overhead = out["overhead_max"]
        out = _retry_gate(
            out, lambda: _txstory_metric(batch, iters), "value",
            lambda o: o["txstory_overhead_ok"],
            "txstory overhead", max_overhead,
        )
        out["quick"] = True
        print(json.dumps(out), flush=True)
        if not out["txstory_overhead_ok"]:
            raise SystemExit(
                f"lifecycle-ledger overhead {out['value']:.4f} exceeds "
                f"{max_overhead:.0%} of the flush wall"
            )
        if out["events_per_tx"] < 4:
            raise SystemExit(
                f"incomplete lifecycle stories: {out['events_per_tx']} "
                f"events/tx (admit + flush + verified + terminal = 4)"
            )
        return
    if metric == "device":
        batch = int(os.environ.get("BENCH_BATCH", "32"))
        iters = int(os.environ.get("BENCH_ITERS", "3"))
        out = _device_metric(batch, iters)
        max_overhead = out["overhead_max"]
        out = _retry_gate(
            out, lambda: _device_metric(batch, iters), "value",
            lambda o: o["device_plane_overhead_ok"],
            "device overhead", max_overhead,
        )
        out["quick"] = True
        print(json.dumps(out), flush=True)
        if not out["device_plane_overhead_ok"]:
            raise SystemExit(
                f"device plane overhead {out['value']:.4f} exceeds "
                f"{max_overhead:.0%} of the flush wall"
            )
        if not out["capacity_names_host_pump"]:
            raise SystemExit(
                f"capacity model named "
                f"{out['binding_constraint']!r} on the CPU rig — the "
                f"host pump is the measured wall here and the model "
                f"must say so"
            )
        return
    if metric == "wire":
        batch = int(os.environ.get("BENCH_BATCH", "48"))
        iters = int(os.environ.get("BENCH_ITERS", "3"))
        out = _wire_metric(batch, iters)
        max_overhead = out["overhead_max"]
        out = _retry_gate(
            out, lambda: _wire_metric(batch, iters),
            "wire_plane_overhead",
            lambda o: o["wire_plane_overhead_ok"],
            "wire overhead", max_overhead,
        )
        out["quick"] = True
        print(json.dumps(out), flush=True)
        if not out["wire_plane_overhead_ok"]:
            raise SystemExit(
                f"wire plane overhead {out['wire_plane_overhead']:.4f} "
                f"exceeds {max_overhead:.0%} of the fabric drain wall"
            )
        if out["value"] <= 0:
            raise SystemExit("zero fabric->ingest throughput")
        if not out["gateway_accounted_ok"]:
            raise SystemExit(
                "the gateway dispatch wrapper did not account every "
                "HTTP request it served"
            )
        if out["links_seen"] < 2 or out["journal_appends"] < 1:
            raise SystemExit(
                "wire accounting incomplete: expected both in/out link "
                "rows and a nonzero journal histogram"
            )
        return
    if metric == "sanitizer":
        batch = int(os.environ.get("BENCH_BATCH", "64"))
        iters = int(os.environ.get("BENCH_ITERS", "3"))
        out = _sanitizer_metric(batch, iters)
        max_overhead = out["overhead_max"]
        out = _retry_gate(
            out, lambda: _sanitizer_metric(batch, iters), "value",
            lambda o: o["sanitizer_overhead_ok"],
            "sanitizer factory overhead", max_overhead,
        )
        out["quick"] = True
        print(json.dumps(out), flush=True)
        if not out["sanitizer_overhead_ok"]:
            raise SystemExit(
                f"disarmed lock-factory overhead {out['value']:.4f} "
                f"exceeds {max_overhead:.0%} of the flush wall"
            )
        if out["armed_locks_observed"] < 1:
            raise SystemExit(
                "the armed rep observed no locks — the factory is not "
                "routing constructions through the monitor"
            )
        return
    if metric == "statestore":
        # tiny set: tier-1 smokes the record shape and the three
        # REQUIRED-TRUE verdicts; the at-scale numbers come from the
        # default run (and BENCH_STATESTORE_STATES=10000000 for the
        # 10^7 acceptance record)
        os.environ.setdefault("BENCH_STATESTORE_STATES", "4000")
        batch = int(os.environ.get("BENCH_BATCH", "2048"))
        iters = int(os.environ.get("BENCH_ITERS", "2"))
        out = _statestore_metric(batch, iters)
        out["quick"] = True
        print(json.dumps(out), flush=True)
        if not out["statestore_bitexact_vs_sqlite"]:
            raise SystemExit(
                "commit-log accept/reject diverged from the sqlite "
                "backend on the same workload — the one thing the "
                "store must never do"
            )
        if out["bitexact_conflicts"] < 1:
            raise SystemExit(
                "the bit-exact workload produced no conflicts — the "
                "replay proved nothing"
            )
        if not out["statestore_p99_flat"]:
            raise SystemExit(
                f"probe p99 grew {out['probe_p99_ratio']:.2f}x when "
                "the committed set grew 10x — the O(1) index story "
                "is broken"
            )
        if not out["statestore_commit_rate_ok"]:
            raise SystemExit(
                f"commit-log sustained rate fell to "
                f"{out['commit_rate_vs_sqlite']:.2f} of sqlite's "
                f"(gate {out['rate_margin']:.2f}) at depth "
                f"{out['commit_depth']}"
            )
        if out["value"] <= 0:
            raise SystemExit("zero sustained commit rate")
        return
    if metric == "fleet":
        batch = int(os.environ.get("BENCH_BATCH", "8"))
        iters = int(os.environ.get("BENCH_ITERS", "1"))
        out = _fleet_metric(batch, iters)
        out["quick"] = True
        print(json.dumps(out), flush=True)
        if not out["reconciled"]:
            raise SystemExit(
                f"fleet soak failed reconciliation: "
                f"{out['reconcile_error']}"
            )
        if not out["slo_held"]:
            raise SystemExit(
                f"fleet soak breached the steady-state SLO: "
                f"{out['slo_error']}"
            )
        if out["outcomes"].get("shed", 0) <= 0:
            raise SystemExit("the 3x spike shed nothing")
        if out["faults_injected"] < 1:
            raise SystemExit("the chaos plane injected no fault")
        if out["value"] <= 0:
            raise SystemExit("zero goodput through the soak")
        return
    if metric == "distributed":
        batch = int(os.environ.get("BENCH_BATCH", "6"))
        iters = int(os.environ.get("BENCH_ITERS", "1"))
        os.environ.setdefault("BENCH_DIST_CLIENTS", "64")
        out = _distributed_metric(batch, iters)
        out["quick"] = True
        print(json.dumps(out), flush=True)
        if not out["xshard_exactly_once"]:
            raise SystemExit(
                f"distributed cluster failed reconciliation: "
                f"{out['reconcile_error']}"
            )
        if not out["xshard_zero_orphans"]:
            raise SystemExit(
                "orphaned reservations (or unresolved WAL intents) "
                "survived the drain — presumed-abort recovery leaked"
            )
        if out["value"] <= 0:
            raise SystemExit("zero cross-shard goodput")
        if not out["faults"]:
            raise SystemExit("the kill/restart chaos never fired")
        return
    if metric == "faults":
        batch = int(os.environ.get("BENCH_BATCH", "32"))
        iters = int(os.environ.get("BENCH_ITERS", "1"))
        out = _faults_metric(batch, iters)
        out["quick"] = True
        print(json.dumps(out), flush=True)
        if not out["redispatch_recovered"]:
            raise SystemExit(
                "a killed worker's in-flight verifications never all "
                "resolved — redispatch is stranding futures"
            )
        if not out["degraded_recovered"]:
            raise SystemExit(
                "the degraded CPU-fallback flush did not sign every "
                "request (device-fault recovery broken)"
            )
        if not out["wal_zero_loss"]:
            raise SystemExit(
                "intent-WAL replay lost an admitted request "
                "(kill-with-pending must recover ALL of them)"
            )
        if out["value"] <= 0:
            raise SystemExit("zero throughput through the WAL flush")
        return
    if metric == "qos":
        batch = int(os.environ.get("BENCH_BATCH", "24"))
        out = _qos_metric(batch, int(os.environ.get("BENCH_ITERS", "2")))
        out["quick"] = True
        print(json.dumps(out), flush=True)
        if out["controller_on"]["shed_fraction"] <= 0:
            raise SystemExit(
                "2x offered load shed nothing — the QoS plane is not "
                "engaging (deadline shedding broken?)"
            )
        if not out["shed_counters"]:
            raise SystemExit("sheds happened but Qos.Shed.* stayed empty")
        # generous CI floor — the deterministic acceptance gate is
        # tests/test_qos.py's simulated-time soak; this smokes the
        # real-time plumbing end to end on a possibly noisy box
        if out["value"] < 0.5:
            raise SystemExit(
                f"goodput under overload fell to {out['value']:.2f} of "
                "the no-overload capacity (expected ~1.0; >=0.9 is the "
                "acceptance line on a quiet machine)"
            )
        return
    if metric == "consensus":
        batch = int(os.environ.get("BENCH_BATCH", "48"))
        reps = int(os.environ.get("BENCH_ITERS", "3"))
        out = _consensus_metric(batch, reps)
        out["quick"] = True
        print(json.dumps(out), flush=True)
        missing = [
            p for p, n in out["phase_span_counts"].items() if n <= 0
        ]
        if missing:
            raise SystemExit(
                f"consensus phases {missing} stamped no spans — the "
                "distributed commit trace is incomplete"
            )
        if len(out["members_with_spans"]) < 2:
            raise SystemExit(
                "consensus phase spans came from "
                f"{out['members_with_spans']} — a distributed-commit "
                "trace must carry spans from >= 2 members"
            )
        if not out["overhead_ok"]:
            raise SystemExit(
                f"consensus tracing overhead {out['tracing_overhead']:.3f}"
                " exceeds BENCH_CONSENSUS_OVERHEAD_MAX (default 5%) vs "
                "the untraced run"
            )
        if out["value"] <= 0:
            raise SystemExit("zero distributed-commit throughput")
        return
    if metric == "trace":
        batch = int(os.environ.get("BENCH_BATCH", "192"))
        reps = int(os.environ.get("BENCH_TRACE_REPS", "3"))
        out = _trace_metric(batch, reps, cpu=True)
        out["quick"] = True
        print(json.dumps(out), flush=True)
        coverage = out["value"]
        if not 0.6 <= coverage <= 1.4:
            raise SystemExit(
                f"stage breakdown covers {coverage:.2f} of the traced "
                "wall — expected ~1.0 (stages must sum to ~batch wall "
                "time)"
            )
        max_overhead = float(
            os.environ.get("BENCH_TRACE_OVERHEAD_MAX", "0.05")
        )
        if out["tracing_overhead"] > max_overhead:
            raise SystemExit(
                f"tracing overhead {out['tracing_overhead']:.3f} exceeds "
                f"{max_overhead:.0%} vs the untraced run"
            )
        return
    if metric != "ingest":
        raise SystemExit(
            f"--quick supports 'ingest', 'trace', 'consensus', 'qos', "
            f"'health', 'perf', 'txstory', 'device', 'wire', "
            f"'sanitizer', 'statestore', 'fleet', 'faults', "
            f"'distributed' or 'shards', not {metric!r}"
        )
    batch = int(os.environ.get("BENCH_BATCH", "256"))
    iters = int(os.environ.get("BENCH_ITERS", "1"))
    out = _ingest_metric(batch, iters)
    out["quick"] = True
    print(json.dumps(out), flush=True)
    out = _ingest_pipelined_metric(batch, iters)
    out["quick"] = True
    print(json.dumps(out), flush=True)


def main() -> None:
    argv = sys.argv[1:]
    if argv[:1] == ["--quick"]:
        _quick(argv[1] if len(argv) > 1 else "ingest")
        return
    if argv:
        raise SystemExit(
            f"unknown arguments {argv!r} "
            "(try --quick ingest|trace|consensus|qos|health|perf|"
            "txstory|device|wire|sanitizer|statestore|fleet|faults|"
            "distributed|shards)"
        )
    t_start = time.perf_counter()
    batch = int(os.environ.get("BENCH_BATCH", "32768"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    metric = os.environ.get("BENCH_METRIC", "all")
    known = (
        "all", "p256", "mixed", "merkle", "notary", "notary_commit_plane",
        "ingest", "ingest_pipelined", "trace", "consensus", "qos", "health",
        "perf", "txstory", "device", "wire", "sanitizer", "statestore",
        "fleet", "faults", "distributed_commit", "montmul", "parity",
    )
    if metric not in known:
        # a typo must not record a p256-only rate under another name
        raise SystemExit(
            f"unknown BENCH_METRIC {metric!r}: " + " | ".join(known)
        )
    if metric != "all":
        print(json.dumps(_run_metric(metric, batch, iters)))
        return
    # Full table: each metric in its OWN subprocess, one at a time —
    # this parent never imports jax, so each child owns the chip while
    # it runs (a parent holding it would hang the child). Co-resident
    # metrics tax each other — a measured default run read p256 48.3k
    # after mixed/merkle/notary had run in-process vs 75.7k in a fresh
    # interpreter (earlier metrics' live jit programs, device buffers
    # and heap survive into later ones) — and the persistent compile
    # cache keeps subprocesses warm, so isolation costs only startup.
    #
    # The whole default run now lives under ONE wall-clock budget
    # (BENCH_TIME_BUDGET seconds): round 3's record was lost to an
    # unbounded four-child run timing out under the driver
    # (BENCH_r03.json rc=124). Secondary metrics spend only what the
    # budget allows — trimmed (fewer iters, smaller batch) when it is
    # tight, skipped (reported on stderr) when it is tighter — and the
    # headline p256 ALWAYS runs before the budget expires, LAST so
    # tail-line parsers record it.
    budget = float(os.environ.get("BENCH_TIME_BUDGET", "900"))
    # wall-clock held back for the headline child. With a warm AOT
    # store (crypto/aot_store) the p256 child runs in ~60-90 s; the
    # reserve covers the fresh-container worst case where the child
    # must trace+lower the ladder once (~430 s measured) and save the
    # artifact for every later run.
    reserve = float(os.environ.get("BENCH_HEADLINE_RESERVE", "480"))

    def left() -> float:
        return budget - (time.perf_counter() - t_start)

    # parity runs LAST of the optional work (cheapest to drop), but
    # before the headline so the headline stays the final stdout line
    for m in ("mixed", "merkle", "notary", "ingest", "ingest_pipelined",
              "trace", "consensus", "qos", "health", "perf", "txstory",
              "device", "wire", "sanitizer", "statestore", "fleet",
              "faults", "distributed_commit", "parity"):
        avail = left() - reserve
        if avail < 60:
            print(
                f"bench: skipped {m} — {avail:.0f}s of secondary budget"
                " left (BENCH_TIME_BUDGET)",
                file=sys.stderr,
            )
            continue
        env = dict(os.environ, BENCH_METRIC=m)
        if avail < 300 and m in (
            "mixed", "merkle", "notary", "ingest", "ingest_pipelined",
            "trace", "consensus", "qos", "health", "perf", "txstory",
            "device", "wire", "sanitizer", "statestore", "fleet",
            "faults", "distributed_commit",
        ):
            # trim before dropping: one timed rep at a shallower batch
            # still yields a usable point for the table
            env["BENCH_ITERS"] = "1"
            env["BENCH_BATCH"] = str(min(batch, 8192))
            print(
                f"bench: trimmed {m} to iters=1 batch<=8192 "
                f"({avail:.0f}s of secondary budget)",
                file=sys.stderr,
            )
        _run_child(m, env, timeout=max(avail, 60))
    # headline last, in its own child like the rest; a run without
    # the headline line fails
    headline_env = dict(os.environ, BENCH_METRIC="p256")
    if not _run_child("p256", headline_env, timeout=max(left(), 120)):
        raise SystemExit("bench: headline p256 metric failed")


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    main()
