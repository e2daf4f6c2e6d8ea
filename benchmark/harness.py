"""One run of one cell: set-up, a measured window, the check, one line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The window drives the node's wire-intake seam: canonical CTS frames of
signed transactions go into `IngestPipeline.feed`, whose ring is
attached to a `BatchingNotaryService` (`attach_ingest`), and the pump
loop here calls `svc.tick()` as `Node._tick_services` does. A request's
answer is its future resolving to the notary's `TransactionSignature`
or a typed `NotaryError`; its latency runs from the frame's due time.

Everything a cell is made of is found by name: the configuration
(BENCHMARK.json `configs[].file`), its transaction shape
(benchmark/shapes/), its reference (benchmark/references/), the traffic
mix (benchmark/traffic/<traffic>.json) and every metric
(benchmark/metrics/<name>.py, or the file of the name without its
last dotted part, a `read(ctx)` that returns a number or None).
"""

from __future__ import annotations

import argparse
import collections
import gc
import hashlib
import importlib.util
import json
import math
import os
import random
import shutil
import sys
import threading
import time
from typing import Callable, Optional

from benchmark import fixture
from benchmark import store as storelib

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
DRAIN_S = 60.0          # an answer may come this long after the close
TRACE_S = 2.0           # a traced run traces the window's last seconds
# every Notary.FlushPhase timer the flush marks (node/notary.py _mark)
PHASES = ("stage", "dispatch", "resolve_verify", "link_wait", "validate",
          "commit", "stream_commit", "sign_scatter")
SAMPLE = 256            # frames re-verified by the reference's own EC


class RunFailure(Exception):
    """The run cannot report: no chip, a pool that ran dry, bad input."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- what a cell is made of ---------------------------------------------------


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _module(path: str, name: str):
    if not os.path.exists(path):
        raise RunFailure(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    def __init__(self, root: str, name: str):
        self.root = root
        self.manifest = _json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise RunFailure(f"no workload {name!r} in BENCHMARK.json")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config = _json(
            os.path.join(root, configs[self.entry["config"]]["file"])
        )
        self.traffic = _json(os.path.join(
            root, "benchmark", "traffic", f"{self.entry['traffic']}.json"
        ))

    def metrics(self, trace: bool) -> list[dict]:
        group = self.manifest["per_layer" if trace else "end_to_end"]
        return [
            m for m in group
            if "workloads" not in m or self.name in m["workloads"]
        ]

    def reader(self, metric: str) -> Callable:
        """metrics/<name>.py, else the reader of the quantity the name
        splits per cell group: `flush_depth_mean.paced` falls back to
        metrics/flush_depth_mean.py."""
        d = os.path.join(self.root, "benchmark", "metrics")
        path = os.path.join(d, f"{metric}.py")
        if not os.path.exists(path) and "." in metric:
            path = os.path.join(d, f"{metric.rsplit('.', 1)[0]}.py")
        return _module(path, "_bench_metric_" + metric.replace(".", "_")).read

    def reference(self):
        name = self.config["reference"]
        path = os.path.join(self.root, "benchmark", "references", f"{name}.py")
        return _module(path, f"_bench_reference_{name}")


# -- set-up -------------------------------------------------------------------


def build_native(cache: str) -> None:
    """The CTS/SHA-256 extension, built from the committed source once
    per checkout (a binary from elsewhere is never trusted)."""
    from corda_tpu import native
    from corda_tpu.native import build

    src = os.path.join(os.path.dirname(build.__file__), "cts_hash.cpp")
    with open(src, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()
    stamp = os.path.join(cache, "native.stamp")
    built = None
    if os.path.exists(stamp):
        with open(stamp) as fh:
            built = fh.read().split()
    native.reset_cache()
    if built and built[0] == digest and os.path.exists(built[1]):
        if native.get() is not None:
            return
    path = build.build(verbose=False)
    with open(stamp, "w") as fh:
        fh.write(f"{digest} {path}\n")
    native.reset_cache()
    if native.get() is None:
        raise RunFailure(f"native codec {path} did not load")


def due_times(traffic: dict, seconds: float, seed: int) -> Optional[list]:
    """Seconds after the window opens at which each frame is due; None
    for a backlog (every frame due at once, intake paced by the ring).

    Poisson arrivals at `rate_per_s`: the gaps are drawn once from the
    mix's own `gap_seed`, scaled so the last one lands on the close, and
    the run's seed only shuffles them — every seed offers the same load
    in another order."""
    if traffic["arrivals"] == "backlog":
        return None
    if traffic["arrivals"] != "poisson":
        raise RunFailure(f"unknown arrivals {traffic['arrivals']!r}")
    rate = float(traffic["rate_per_s"])
    rng = random.Random(traffic["gap_seed"])
    gaps = [rng.expovariate(rate) for _ in range(max(1, int(rate * seconds)))]
    random.Random(seed).shuffle(gaps)
    scale = seconds / sum(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g * scale
        out.append(t)
    return out


def honour(cfg: dict, chips: int) -> None:
    """Refuse a configuration whose deployment this harness cannot
    serve as stated, rather than serve another one in its place."""
    g = cfg["guarantees"]
    if not g["validating"]:
        raise RunFailure("the harness serves BatchingNotaryService, a "
                         "validating notary; a non-validating config needs "
                         "a harness that serves one")
    if not g["exactly_once_commits"]:
        raise RunFailure("the check holds every commit to exactly one "
                         "consumer; a config without that guarantee needs "
                         "another check")
    if cfg["store"]["kind"] != "ShardedCommitLogUniquenessProvider":
        raise RunFailure(f"unknown store kind {cfg['store']['kind']!r}")
    shards = cfg["notary"]["shards"]
    if cfg["store"]["n_shards"] != shards:
        raise RunFailure("the notary's shards and the store's partitions "
                         "differ")
    if chips > 1 and shards < chips:
        raise RunFailure(f"a cell on {chips} chips needs a config with at "
                         f"least {chips} shards, one device-pinned verifier "
                         f"each; it has {shards}")


def make_verifiers(cfg: dict, devices: list):
    """The hub's verifier and, for a sharded notary, one verifier per
    shard pinned to the cell's chips in turn (chip_smoke.py's four-chip
    notary)."""
    from corda_tpu.crypto.batch_verifier import (
        TpuBatchVerifier,
        per_shard_verifiers,
    )

    sizes = tuple(cfg["notary"]["verifier_batch_sizes"])
    shards = cfg["notary"]["shards"]
    per_shard = (per_shard_verifiers(shards, batch_sizes=sizes,
                                     devices=devices)
                 if shards > 1 else None)
    return TpuBatchVerifier(batch_sizes=sizes), per_shard


def make_services(cfg: dict, seed: int, store, verifier, shard_verifiers):
    from corda_tpu.node.cordapp import install_cordapp_services
    from corda_tpu.node.notary import BatchingNotaryService
    from corda_tpu.node.services import (
        SERVICE_NOTARY_VALIDATING,
        Clock,
        IdentityService,
        KeyManagementService,
        NetworkMapCache,
        NodeInfo,
        ServiceHub,
    )

    party, kp = fixture.notary_party(seed, cfg["shape"]["notary_scheme_id"])
    services = ServiceHub(
        my_info=NodeInfo(party.name, party, (SERVICE_NOTARY_VALIDATING,)),
        key_management=KeyManagementService(
            kp, rng=random.Random(f"{seed}/kms")
        ),
        identity=IdentityService(party),
        network_map_cache=NetworkMapCache(),
        clock=Clock(),
        batch_verifier=verifier,
    )
    install_cordapp_services(services)
    n = cfg["notary"]
    svc = BatchingNotaryService(
        services,
        store,
        max_batch=n["max_batch"],
        max_wait_micros=n["max_wait_micros"],
        shards=n["shards"],
        shard_verifiers=shard_verifiers,
        degraded_fallback=cfg["guarantees"]["degraded_fallback"],
    )
    services.notary_service = svc
    return services, svc


class IngestProbe:
    """The ingest pipeline's `perf` seam: per-batch decode / Merkle-id /
    staging seconds (the intervals its ingest.* spans carry), summed
    while `on`."""

    def __init__(self):
        self.on = False
        self.frames = 0
        self.seconds = {"decode": 0.0, "merkle_id": 0.0, "stage": 0.0}

    def observe_ingest(self, n, decode_s, id_s, stage_s) -> None:
        if self.on:
            self.frames += n
            self.seconds["decode"] += decode_s
            self.seconds["merkle_id"] += id_s
            self.seconds["stage"] += stage_s


class Durability:
    """Watches the commit log's durability from outside the program:
    counts each `os.fsync` that returns on a segment file of the store
    (`n`), and records for every transaction the store writes how many
    had returned when its rows went in (`written`, keyed by its id). A
    reply that signs a transaction is durable when a segment fsync
    returned between that write and the reply (`check`)."""

    def __init__(self):
        self.n = 0
        self.written: dict = {}
        self._fsync = None

    def install(self, store) -> None:
        real = self._fsync = os.fsync

        def fsync(fd):
            real(fd)
            if os.path.basename(
                os.readlink(f"/proc/self/fd/{fd}")
            ).startswith("segment-"):
                self.n += 1

        os.fsync = fsync
        written = self.written
        for part in store._stores:
            def commit_rows(rows, _commit=part.commit_rows):
                mark = self.n
                for _ref, consumer, _who in rows:
                    written.setdefault(
                        getattr(consumer, "bytes_", consumer), mark
                    )
                return _commit(rows)

            part.commit_rows = commit_rows

    def uninstall(self) -> None:
        if self._fsync is not None:
            os.fsync = self._fsync
            self._fsync = None


def annotate(name: str):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


# -- the served window ------------------------------------------------------


class Served:
    """Per-frame record of one serving pass over a slice of the pool."""

    def __init__(self, lo: int, n: int, durability: Durability):
        self.lo = lo
        self.n = n
        self.durability = durability
        self.sent = [0.0] * n
        self.answered = [0.0] * n
        self.fsyncs = [0] * n
        self.answers: list = [None] * n
        self.decoded: list = [None] * n
        self.fed = 0
        self.n_answered = 0
        self.exhausted = False
        self._lock = threading.Lock()

    def answer(self, k: int, value) -> None:
        self.fsyncs[k] = self.durability.n
        self.answered[k] = time.perf_counter()
        self.answers[k] = value
        with self._lock:
            self.n_answered += 1

    def done(self) -> bool:
        return self.n_answered >= self.fed


def serve(svc, blobs: list, rec: Served, requester, *, t0: float,
          until: float, due: Optional[list], batch: int, ring_depth: int,
          traced: bool = False, probe=None, ring_hook=None):
    """Start feeding pool frames rec.lo .. rec.lo+rec.n through a fresh
    ingest pipeline attached to `svc`: on schedule (`due`, seconds after
    t0) or, with `due` None, as fast as the ring takes them until
    `until`. The caller pumps. Returns (pipeline, feeder, stop)."""
    from corda_tpu.flows.api import FlowFuture
    from corda_tpu.node.ingest import IngestPipeline
    from corda_tpu.node.notary import _PendingNotarisation

    pipe = IngestPipeline(ring_depth=ring_depth, perf=probe)
    if ring_hook is not None:
        ring_hook(pipe.ring)
    svc.attach_ingest(pipe.ring)
    stop = threading.Event()
    ranges: collections.deque = collections.deque()
    clock = time.perf_counter
    lo, n = rec.lo, rec.n

    def on_answer(k):
        return lambda fut: rec.answer(k, fut.result())

    def wrap(entries):
        a, b = ranges.popleft()
        out = []
        for k, e in zip(range(a, b), entries):
            if e.error is not None:
                rec.answer(k, e.error)
                continue
            rec.decoded[k] = e.stx.id.bytes_
            fut = FlowFuture()
            fut.add_done_callback(on_answer(k))
            out.append(
                _PendingNotarisation(e.stx, requester, fut, span=e.span)
            )
        return out

    def batches():
        feed_span = None
        k = 0
        while k < n and not stop.is_set():
            gen_span = annotate("generator") if traced else None
            if gen_span is not None:
                gen_span.__enter__()
            now = clock()
            if due is None:
                if now >= until:
                    if gen_span is not None:
                        gen_span.__exit__(None, None, None)
                    break
                j = min(n, k + batch)
            else:
                j = k
                while j < n and j - k < batch and t0 + due[j] <= now:
                    j += 1
                if j == k:
                    if gen_span is not None:
                        gen_span.__exit__(None, None, None)
                    time.sleep(min(t0 + due[k] - now, 0.001))
                    continue
            for i in range(k, j):
                rec.sent[i] = now
            ranges.append((k, j))
            rec.fed = j
            out = blobs[lo + k:lo + j]
            k = j
            if gen_span is not None:
                gen_span.__exit__(None, None, None)
            if feed_span is not None:
                feed_span.__exit__(None, None, None)
            if traced:
                feed_span = annotate("ingest.feed")
                feed_span.__enter__()
            yield out
        if feed_span is not None:
            feed_span.__exit__(None, None, None)
        if k >= n and due is None and clock() < until:
            rec.exhausted = True

    feeder = pipe.feed(batches(), wrap=wrap)
    return pipe, feeder, stop


def pump(svc, until: float, traced: bool = False,
         done: Optional[Callable[[], bool]] = None) -> None:
    """The node's pump (Node._tick_services): tick the notary (its
    batching deadline decides whether a flush runs), then walk the
    store's compaction, until `until` or `done()`."""
    clock = time.perf_counter
    tick = svc.tick
    maintain = svc.uniqueness.maintain
    while clock() < until:
        if done is not None and done():
            return
        if traced:
            with annotate("pump.tick"):
                n = tick()
                maintain()
        else:
            n = tick()
            maintain()
        if not n:
            time.sleep(0.0005)


def finish(svc, pipe, feeder, stop, rec: Served, deadline: float,
           paced: bool) -> None:
    """After the close: a backlog stops sending, a paced mix sends what
    was due in the window; then pump until every frame sent has its
    answer, or until the deadline."""
    if not paced:
        stop.set()
    pump(svc, deadline,
         done=lambda: not feeder.is_alive() and rec.done())
    stop.set()
    feeder.join(timeout=5.0)
    svc.flush()
    pipe.close()


# -- the check ----------------------------------------------------------------


def classify(answer) -> str:
    """The kind of a notary answer (chip_smoke.outcome)."""
    if hasattr(answer, "by"):
        return fixture.VALID
    kind = getattr(answer, "kind", type(answer).__name__)
    if kind == "invalid-transaction" and "invalid signature" in str(
        getattr(answer, "message", "")
    ):
        return fixture.TAMPER
    return kind


def reply_fields(sig) -> tuple:
    pm = sig.partial_merkle
    proof = None
    if pm is not None:
        proof = (getattr(pm, "index", None), getattr(pm, "tree_size", None),
                 getattr(pm, "path", None))
    return (sig.signature, sig.by.data, sig.by.scheme_id,
            sig.metadata.platform_version, sig.metadata.scheme_id, proof)


def check(ref, frames, recs: list, store_path: str, n_shards: int,
          notary_pub: bytes, seed: int, degraded: int,
          durable: bool) -> dict:
    """Every answer of every pass held to construction and to the plain
    reference; every signed reply to a commit-log fsync that returned
    after its transaction was written and before the reply (`durable`,
    the configuration's fsync-per-flush guarantee); every commit read
    back from the store reopened from disk. Returns {number: (value,
    limit)}."""
    from corda_tpu.core.contracts import StateRef
    from corda_tpu.crypto.hashes import SecureHash

    wrong = unanswered = ids = bad_sigs = unsynced = 0
    sig_cache: dict = {}
    fed: list[int] = []
    for rec in recs:
        for k in range(rec.fed):
            i = rec.lo + k
            fed.append(i)
            if rec.decoded[k] is not None and rec.decoded[k] != frames.ids[i]:
                ids += 1
            if not rec.answered[k]:
                unanswered += 1
                continue
            got = classify(rec.answers[k])
            if got != frames.kinds[i]:
                wrong += 1
            if got == fixture.VALID:
                mark = rec.durability.written.get(frames.ids[i])
                if mark is None or rec.fsyncs[k] <= mark:
                    unsynced += 1
                if not ref.notary_signature_valid(
                    frames.ids[i], reply_fields(rec.answers[k]),
                    notary_pub, sig_cache,
                ):
                    bad_sigs += 1
    rng = random.Random(f"{seed}/sample")
    sample = sorted(rng.sample(fed, min(SAMPLE, len(fed))))
    disagree = sum(
        ref.frame_signatures_valid(frames.ids[i], frames.sigs[i])
        != (frames.kinds[i] != fixture.TAMPER)
        for i in sample
    )
    # durability: reopen the store from disk and read every commit back
    spender = hashlib.sha256(b"earlier spender").digest()
    reopened = storelib.open_store(store_path, n_shards, fsync=False)  # reads
    lost = 0
    try:
        for i in fed:
            want = {
                fixture.VALID: frames.ids[i],
                fixture.CONFLICT: spender,
                fixture.TAMPER: None,
            }[frames.kinds[i]]
            for h, idx in frames.inputs[i]:
                ref_ = StateRef(SecureHash(h), idx)
                got = reopened.prior_consumer(reopened.shard_of(ref_), ref_)
                if (got.bytes_ if got is not None else None) != want:
                    lost += 1
                    break
    finally:
        reopened.close()
    out = {
        "wrong_answers": (wrong, 0),
        "unanswered": (unanswered, 0),
        "decoded_id_mismatches": (ids, 0),
        "bad_notary_signatures": (bad_sigs, 0),
        "reference_disagrees": (disagree, 0),
        "signed_before_fsync": (unsynced, 0),
        "commits_not_read_back": (lost, 0),
        "degraded_flushes": (degraded, 0),
    }
    if not durable:
        del out["signed_before_fsync"]
    return out


# -- metrics ------------------------------------------------------------------


def registry_snapshot(svc) -> dict:
    """Counter counts and FlushPhase timer (sum, count) pairs."""
    from corda_tpu.utils import device_telemetry as devlib

    out = {
        "Notary.RequestsBatched": svc.requests_batched,
        "Notary.BatchesDispatched": svc.batches_dispatched,
        "sig_rows": devlib.get_device_accounting().snapshot()["totals"][
            "requests"
        ],
    }
    for phase in PHASES:
        h = svc.metrics.timer("Notary.FlushPhase." + phase).histogram
        out["phase." + phase] = (h.sum, h.count)
    return out


def registry_delta(before: dict, after: dict) -> dict:
    out = {}
    for k, v in after.items():
        if isinstance(v, tuple):
            out[k] = (v[0] - before[k][0], v[1] - before[k][1])
        else:
            out[k] = v - before[k]
    return out


class Context:
    """What the metric readers (benchmark/metrics/*.py) read.

    window_s: the measured window's length; setup_s: process start to
    the window's first request; answered_in_window: answers that
    resolved inside the window; latencies_s / lateness_s: per request
    due in the window, reply minus due time and send minus due time
    (paced mixes only); registry: counter and FlushPhase timer deltas
    over the window (a traced run's: over its traced part); ingest:
    IngestProbe (traced runs); trace: the reduced profiler trace
    (traced runs)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


# -- the run ------------------------------------------------------------------


def parse_args(argv):
    p = argparse.ArgumentParser(prog="benchmark/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def run(args, t_start: float, *, root: str = ROOT, allow_cpu: bool = False,
        overrides: Optional[dict] = None, fault: Optional[Callable] = None,
        verbose: Callable = log) -> dict:
    """One run of one cell. `allow_cpu`, `overrides` (smaller sizes:
    "config", "shape", "notary", "store" and "traffic" entries merged over the
    cell's files, "workers", "build_native", "drain_s") and `fault`
    (breaks the timed path) exist for the tests and the control; the
    command line reaches none of them."""
    ov = overrides or {}
    cell = Cell(root, args.workload)
    cfg = dict(cell.config, **ov.get("config", {}))
    for group in ("shape", "notary", "store"):
        cfg[group] = dict(cell.config[group], **ov.get(group, {}))
    traffic = dict(cell.traffic, **ov.get("traffic", {}))
    ncfg = cfg["notary"]
    chips = cell.entry["chips"]
    honour(cfg, chips)
    durable = cfg["guarantees"]["fsync_per_flush"]
    traced = bool(args.trace)
    seconds = float(args.seconds)
    seed = args.seed
    cache = os.path.join(root, "benchmark", ".cache")
    os.makedirs(cache, exist_ok=True)
    if ov.get("build_native", True):
        build_native(cache)
    due = due_times(traffic, seconds, seed)
    n_window = (
        len(due) if due is not None
        else math.ceil(cfg["pool_per_s"] * seconds)
    )
    warm = traffic["warmup_frames"]
    workers = ov.get("workers", max(1, (os.cpu_count() or 2) - 2))
    t = time.perf_counter()
    pool = fixture.FramePool(root, cfg["tx_shape"], cfg["shape"], seed,
                             warm + n_window, workers,
                             cfg["shape"]["issue_fanout"])
    try:
        import jax

        devices = jax.devices()
        d0 = devices[0]
        if d0.platform != "tpu" and not allow_cpu:
            raise RunFailure(f"first device is {d0.platform!r}, not a TPU")
        if len(devices) < chips:
            raise RunFailure(f"the cell needs {chips} chips, "
                             f"jax sees {len(devices)}")
        devices = devices[:chips]
        if d0.platform == "tpu":
            from benchmark import trace as tracelib

            try:
                tracelib.peaks(d0.device_kind)
            except KeyError as e:
                raise RunFailure(str(e)) from None
        from corda_tpu.utils import jaxenv

        jaxenv.enable_compile_cache()
        states = cfg["committed_states"]
        n_shards = cfg["store"]["n_shards"]
        t_store = time.perf_counter()
        base, built = storelib.base(cache, states, n_shards)
        run_dir = os.path.join(cache, "run-store")
        storelib.copy(base, run_dir)
        store = storelib.open_store(run_dir, n_shards, durable)
        verbose(f"store: {states} states ({'built' if built else 'cached'}"
                f" base) in {time.perf_counter() - t_store:.1f} s")
        verifier, shard_verifiers = make_verifiers(cfg, devices)
        services, svc = make_services(cfg, seed, store, verifier,
                                      shard_verifiers)
        from corda_tpu.core import serialization as ser

        fixture.load_shape(
            cfg["tx_shape"], os.path.join(root, "benchmark")
        ).register()
        frames = fixture.Frames.join(
            pool.kinds, pool.chunks(),
            lambda blobs: services.record_transactions(
                ser.decode(b) for b in blobs
            ),
        )
    finally:
        pool.close()
    verbose(f"pool: {len(frames.blobs)} frames ({warm} warm-up) in "
            f"{time.perf_counter() - t:.1f} s")
    from corda_tpu.core.identity import Party

    storelib.commit_spent(store, frames.conflict_refs())
    requester = Party("O=Client,L=London,C=GB",
                      fixture.keypair(seed, "client", 4).public)
    durability = Durability()
    durability.install(store)
    ring_hook = fault(svc, services, store) if fault is not None else None
    batch, ring_depth = traffic["batch"], ncfg["ring_depth"]

    # warm-up: the window's own path and shapes on frames of its own
    recs = []
    if warm:
        rec = Served(0, warm, durability)
        pipe, feeder, stop = serve(
            svc, frames.blobs, rec, requester, t0=time.perf_counter(),
            until=math.inf, due=None, batch=batch, ring_depth=ring_depth,
        )
        deadline = time.perf_counter() + 600
        pump(svc, deadline, done=lambda: rec.fed == warm and rec.done())
        finish(svc, pipe, feeder, stop, rec, deadline, paced=False)
        recs.append(rec)
        verbose(f"warm-up: {warm} frames, set-up so far "
                f"{time.monotonic() - t_start:.1f} s")

    probe = IngestProbe() if traced else None
    trace_dir = os.path.join(cache, "trace")
    if traced:
        orig_flush = svc.flush

        def flush_annotated():
            with annotate("flush"):
                orig_flush()

        svc.flush = flush_annotated
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = Served(warm, n_window, durability)
    # set-up objects (the frame pool, the issuing transactions the
    # notary resolves against, the store) are frozen out of the
    # collector, so its cost in the window does not grow with the
    # pool's size, which is oversized by design
    gc.freeze()
    t0 = time.perf_counter()
    setup_s = time.monotonic() - t_start
    until = t0 + seconds
    if not traced:
        before = registry_snapshot(svc)
    pipe, feeder, stop = serve(
        svc, frames.blobs, rec, requester, t0=t0, until=until, due=due,
        batch=batch, ring_depth=ring_depth, traced=traced, probe=probe,
        ring_hook=ring_hook,
    )
    if traced:
        # the trace covers the window's last TRACE_S: a whole window of
        # the ladders' per-op events is hundreds of MB
        pump(svc, until - min(TRACE_S, seconds), traced)
        before = registry_snapshot(svc)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_span = annotate("window")
        window_span.__enter__()
        probe.on = True
    pump(svc, until, traced)
    t1 = time.perf_counter()
    after = registry_snapshot(svc)
    reduced = stopper = None
    if traced:
        probe.on = False
        window_span.__exit__(None, None, None)
        # writing the trace takes a while: the notary keeps serving
        stopper = threading.Thread(target=jax.profiler.stop_trace)
        stopper.start()
    finish(svc, pipe, feeder, stop, rec, t1 + ov.get("drain_s", DRAIN_S),
           paced=due is not None)
    if stopper is not None:
        stopper.join()
        verbose(f"trace written {time.perf_counter() - t1:.1f} s after "
                "the close")
    gc.unfreeze()
    recs.append(rec)
    if traced and not rec.exhausted:
        from benchmark import trace as tracelib

        t_red = time.perf_counter()
        reduced = tracelib.reduce_dir(
            trace_dir,
            tracelib.DEVICE_PREFIX if d0.platform == "tpu" else "/host:CPU",
        )
        verbose(f"trace reduced in {time.perf_counter() - t_red:.1f} s")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    degraded = svc.metrics.counter("Notary.DegradedFlushes").count
    svc.stop()
    store.close()
    durability.uninstall()
    del svc, services, verifier, shard_verifiers
    if rec.exhausted:
        raise RunFailure(
            f"the frame pool ran dry: {rec.fed} frames fed before the "
            "window closed"
        )

    t_check = time.perf_counter()
    checks = check(cell.reference(), frames, recs, run_dir, n_shards,
                   fixture.notary_party(
                       seed, cfg["shape"]["notary_scheme_id"])[1].public.data,
                   seed, degraded, durable)
    verbose(f"check: {time.perf_counter() - t_check:.1f} s")
    shutil.rmtree(run_dir, ignore_errors=True)

    in_window = sum(1 for k in range(rec.fed)
                    if rec.answered[k] and t0 <= rec.answered[k] <= t1)
    latencies, lateness = [], []
    if due is not None:
        for k in range(rec.fed):
            if rec.answered[k]:
                latencies.append(rec.answered[k] - (t0 + due[k]))
            lateness.append(rec.sent[k] - (t0 + due[k]))
    ctx = Context(
        window_s=t1 - t0, setup_s=setup_s, answered_in_window=in_window,
        latencies_s=latencies, lateness_s=lateness,
        registry=registry_delta(before, after), ingest=probe, trace=reduced,
    )
    metrics = {}
    for m in cell.metrics(traced):
        value = cell.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = checks["wrong_answers"][0] + checks["unanswered"][0]
    device = {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": chips,
        "memory_peak_bytes": peak,
    }
    out = {
        "correct": all(v <= lim for v, lim in checks.values()) and rec.fed > 0,
        "attempted": rec.fed,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    out["answers"] = dict(collections.Counter(
        classify(rec.answers[k]) for k in range(rec.fed) if rec.answered[k]
    ))
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None, t_start: Optional[float] = None, **kw) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    args = parse_args(sys.argv[1:] if argv is None else argv)
    try:
        out = run(args, t_start, **kw)
    except RunFailure as e:
        log(f"benchmark: FAILED: {e}")
        return 1
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0
