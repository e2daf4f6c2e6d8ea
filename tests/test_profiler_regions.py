"""The notary's host work as profiler regions, and the counts behind them.

Regions (utils/tracing) are recorded by the profiler itself, on the
clock of the device trace: the flush phases `notary.<phase>`, the
pump's `notary.hold` / `notary.starved` episodes, collector pauses
`gc.collect`, intake `ingest.*` and each ladder launch `verify.launch`.
Off a capture none of them builds an object."""

from __future__ import annotations

import gc
import glob
import math
import os
import time

import pytest

from corda_tpu.core.transactions import TransactionBuilder
from corda_tpu.crypto.batch_verifier import CpuBatchVerifier
from corda_tpu.finance import CashIssueFlow
from corda_tpu.finance.cash import CASH_CONTRACT, CashMove, CashState
from corda_tpu.flows.api import FlowFuture
from corda_tpu.node.notary import _PendingNotarisation
from corda_tpu.testing.mock_network import MockNetwork
from corda_tpu.utils import runtime, tracing
from corda_tpu.utils.perf import flush_phase_seconds


def _capture(tmp_path, body):
    """Run `body()` under a profiler capture; {name: [(start_ns,
    end_ns, stats)]} of the host regions it recorded."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats))
                    )
    return out


def _seconds(regions, name):
    return sum(e - s for s, e, _ in regions.get(name, ())) * 1e-9


def test_no_capture_builds_nothing():
    a = tracing.annotate("notary.stage")
    assert a is tracing.annotate("ingest.decode", frames=3)
    with a:
        pass
    assert tracing.open_region("notary.hold") is None
    tracing.close_region(None, collected=1)   # a no-op


def test_regions_record_under_a_capture(tmp_path):
    def body():
        assert tracing.annotate("x") is not tracing.annotate("x")
        with tracing.annotate("verify.launch", rows=5, batch=32):
            time.sleep(0.002)
        r = tracing.open_region("notary.hold")
        time.sleep(0.002)
        tracing.close_region(r, episodes=1)

    regions = _capture(tmp_path, body)
    assert regions["verify.launch"][0][2] == {"rows": 5, "batch": 32}
    (start, end, stats), = regions["notary.hold"]
    assert stats == {"episodes": 1} and end - start >= 2_000_000


# -- the pump's episodes ------------------------------------------------------


def _notary(shards=1, verifier=None, wait=0):
    net = MockNetwork(seed=23, batch_verifier=verifier or CpuBatchVerifier())
    notary = net.create_notary("Notary", batching=True, shards=shards)
    svc = notary.services.notary_service
    svc.max_wait_micros = wait
    return net, notary, svc


class _Unstageable:
    """A pending request whose staging fails: its flush answers it with
    an error, which is all a pump-state test needs of a flush."""

    id = "unstageable"

    def signature_requests(self):
        raise ValueError("not a transaction")


def _pending(svc):
    fut = FlowFuture()
    p = _PendingNotarisation(_Unstageable(), None, fut)
    p.intent_seq = -1       # no journal, no story
    svc._pending.append(p)
    svc._oldest_arrival = svc.services.clock.now_micros()
    return fut


def test_tick_sequence_charges_starved_then_hold_then_flush():
    net, notary, svc = _notary(wait=50_000)
    hold = svc.metrics.timer("Notary.PumpHold").histogram
    starved = svc.metrics.timer("Notary.PumpStarved").histogram
    t_first = time.perf_counter()
    assert svc.tick() == 0                      # starved: nothing at all
    t_after_first = time.perf_counter()
    time.sleep(0.01)
    assert starved.count == 0                   # the episode is open
    fut = _pending(svc)
    assert svc.tick() == 0                      # held: inside the deadline
    assert (starved.count, hold.count) == (1, 0)
    time.sleep(0.01)
    net.clock.advance(60_000)                   # the deadline passes
    t_before_last = time.perf_counter()
    assert svc.tick() == 1                      # flushed
    t_last = time.perf_counter()
    assert fut.result().kind == "invalid-transaction"
    assert (starved.count, hold.count) == (1, 1)
    assert starved.sum >= 0.01 and hold.sum >= 0.01
    # one clock read per tick: the episodes tile the wall between the
    # first tick's read and the last tick's
    assert t_before_last - t_after_first <= starved.sum + hold.sum
    assert starved.sum + hold.sum <= t_last - t_first
    assert svc._pump_state is None


def test_pump_episodes_are_regions(tmp_path):
    net, notary, svc = _notary(wait=50_000)

    def body():
        svc.tick()
        time.sleep(0.005)
        _pending(svc)
        svc.tick()
        time.sleep(0.005)
        net.clock.advance(60_000)
        svc.tick()

    regions = _capture(tmp_path, body)
    for name, timer in (("notary.starved", "Notary.PumpStarved"),
                        ("notary.hold", "Notary.PumpHold")):
        h = svc.metrics.timer(timer).histogram
        assert len(regions[name]) == h.count == 1
        assert _seconds(regions, name) == pytest.approx(h.sum, rel=0.05)


def test_sharded_tick_holds_and_starves_too():
    net, notary, svc = _notary(shards=2, wait=50_000)
    svc.tick()
    assert svc._pump_state == "starved"
    fut = FlowFuture()
    p = _PendingNotarisation(_Unstageable(), None, fut)
    p.intent_seq = -1
    svc._shards[0].pending.append(p)
    svc._shards[0].oldest_arrival = net.clock.now_micros()
    svc.tick()
    assert svc._pump_state == "hold"
    net.clock.advance(60_000)
    svc.tick()
    assert svc._pump_state is None and fut.done
    assert svc.metrics.timer("Notary.PumpHold").histogram.count == 1


# -- flush phases -------------------------------------------------------------


class _StreamingCpu(CpuBatchVerifier):
    """CPU results through a streamed PendingVerification, so the
    flush takes its stream_commit path."""

    def verify_batch_async(self, requests):
        import numpy as np

        from corda_tpu.crypto.batch_verifier import PendingVerification

        res = super().verify_batch(requests)
        pending = [(np.asarray(res[o:o + 2], dtype=bool),
                    list(range(o, min(o + 2, len(res)))),
                    min(2, len(res) - o))
                   for o in range(0, len(res), 2)]
        return PendingVerification([None] * len(res), pending, streamed=True)


def _spends(net, notary, n):
    bank = net.create_node("Bank")
    alice = net.create_node("Alice")
    for amt in range(100, 100 + n):
        bank.run_flow(CashIssueFlow(amt, "USD", alice.party, notary.party))
    notary.services.record_transactions(
        alice.services.validated_transactions.all()
    )
    out = []
    for coin in alice.vault.unconsumed_states(CashState):
        b = TransactionBuilder(notary.party)
        b.add_input_state(coin)
        b.add_output_state(coin.state.data.with_owner(bank.party.owning_key),
                           CASH_CONTRACT, notary.party)
        b.add_command(CashMove(), alice.party.owning_key)
        out.append(alice.services.sign_initial_transaction(b))
    return alice, out


@pytest.mark.parametrize("path,shards,verifier", [
    ("join", 1, CpuBatchVerifier),
    ("stream", 1, _StreamingCpu),
    ("wave", 2, CpuBatchVerifier),
])
def test_flush_phase_regions_match_the_phase_timers(tmp_path, path, shards,
                                                    verifier):
    net, notary, svc = _notary(shards=shards, verifier=verifier())
    alice, stxs = _spends(net, notary, 24)
    before = flush_phase_seconds(svc.metrics)
    futs = []

    def body():
        for half in (stxs[:12], stxs[12:]):
            for stx in half:
                fut = FlowFuture()
                futs.append(fut)
                svc.enqueue_pending(
                    _PendingNotarisation(stx, alice.party, fut)
                )
            svc.flush()

    regions = _capture(tmp_path, body)
    assert all(hasattr(f.result(), "by") for f in futs)
    after = flush_phase_seconds(svc.metrics)
    phases = {p for p in after
              if after[p]["count"] > before.get(p, {}).get("count", 0)}
    want = {"join": {"stage", "dispatch", "resolve_verify", "link_wait",
                     "validate", "commit", "sign_scatter"},
            "stream": {"stage", "dispatch", "resolve_verify",
                       "stream_commit", "sign_scatter"}}
    assert phases == want["stream" if path == "stream" else "join"]
    for phase in phases:
        delta_n = after[phase]["count"] - before.get(phase, {}).get("count", 0)
        delta_s = (after[phase]["total_s"]
                   - before.get(phase, {}).get("total_s", 0.0))
        got = regions.get("notary." + phase, [])
        assert len(got) == delta_n, phase
        # a region opens and closes a few (at most tens of)
        # microseconds off the clock read the timer takes at the same
        # boundary
        assert _seconds(regions, "notary." + phase) == pytest.approx(
            delta_s, rel=0.05, abs=30e-6 * delta_n), phase
    assert "corda_tpu.notary.batch_verify_dispatch" not in regions


# -- the collector ------------------------------------------------------------


def test_gc_watch_counts_a_gen2_pause_and_uninstalls():
    watch = runtime.GcWatch()
    callbacks = list(gc.callbacks)
    enabled = gc.isenabled()
    gc.disable()
    try:
        watch.acquire()
        watch.acquire()                 # refcounted: installed once
        assert gc.callbacks == callbacks + [watch._callback]
        gc.collect(2)
        assert watch.collections == {0: 0, 1: 0, 2: 1}
        assert watch.seconds[2] > 0
        watch.release()
        assert watch._callback in gc.callbacks
        watch.release()
        watch.release()                 # one too many: a no-op
    finally:
        if enabled:
            gc.enable()
    assert gc.callbacks == callbacks


def test_gc_pauses_are_regions_and_gauges(tmp_path):
    from corda_tpu.utils.metrics import MetricRegistry

    watch = runtime.get_gc_watch()
    metrics = MetricRegistry()
    runtime.register_gc_gauges(metrics)
    watch.acquire()
    try:
        n2 = metrics.get("Runtime.GcCollections.gen2").value()
        regions = _capture(tmp_path, lambda: (gc.collect(0), gc.collect(2)))
        assert metrics.get("Runtime.GcCollections.gen2").value() >= n2 + 1
        assert metrics.get("Runtime.GcSeconds.gen2").value() > 0
        assert metrics.get("Runtime.GcYoungThreshold").value() == (
            gc.get_threshold()[0])
    finally:
        watch.release()
    for gen in (0, 2):
        got = [st for _, _, st in regions["gc.collect"]
               if st["generation"] == gen]
        assert got and all(st["threshold"] >= 1 for st in got), gen


def test_notary_holds_the_watch():
    watch = runtime.get_gc_watch()
    net, notary, svc = _notary()
    assert watch._callback in gc.callbacks
    assert "Runtime.GcSeconds.gen2" in svc.metrics.names()
    svc.stop()
    svc.stop()                          # idempotent: one release


# -- pacing the full and young passes ----------------------------------------


@pytest.mark.parametrize("rate1,pass_s,floor,want", [
    (8.0, 0.002, 10, 10),               # ms-scale passes: CPython's 10
    (8.0, 0.002, 25, 25),               # never below the prior threshold
    (0.5, 5.0, 10, math.ceil(0.5 * runtime.FULL_PASS_MAX_S)),  # T_MAX
    (7.3, 0.41, 10, math.ceil(7.3 * 0.41 * 19)),               # between
])
def test_full_pass_threshold_rule(rate1, pass_s, floor, want):
    assert runtime.FULL_PASS_SHARE == 0.05
    assert runtime.full_pass_threshold(rate1, pass_s, floor) == want


def test_pacing_hold_is_refcounted_and_restores_thresholds():
    watch = runtime.GcWatch()
    callbacks = list(gc.callbacks)
    prior = gc.get_threshold()
    enabled = gc.isenabled()
    gc.disable()
    try:
        watch.acquire(pace=True)
        watch.acquire(pace=True)        # two holders, one install
        assert gc.callbacks == callbacks + [watch._callback]
        watch.release(pace=True)
        assert watch._callback in gc.callbacks
        watch.release(pace=True)
        assert gc.callbacks == callbacks
        watch.acquire()
        watch.release(pace=True)        # a plain hold is not a pace hold
        assert watch._refs == 1
        watch.release()
        watch.acquire(pace=True)
        watch.acquire(pace=True)
        gc.set_threshold(prior[0], prior[1], 77)    # as the pacer would
        watch.release(pace=True)
        assert gc.get_threshold()[2] == 77 and watch._paced == 1
        watch.release(pace=True)        # the last: the prior, exactly
        assert gc.get_threshold() == prior
        watch.release(pace=True)        # one too many: a no-op
        watch.release()
        assert gc.get_threshold() == prior and gc.callbacks == callbacks
    finally:
        gc.set_threshold(*prior)
        if enabled:
            gc.enable()


def test_cycles_are_still_collected_while_paced():
    watch = runtime.GcWatch()
    prior = gc.get_threshold()
    enabled = gc.isenabled()
    # the test process's heap out of the way: the 25% rule then weighs
    # the objects below alone
    gc.freeze()
    gc.enable()
    watch.acquire(pace=True)
    try:
        gc.collect(2)                   # gen-1 count from zero
        n1 = watch.collections[1]
        # a 1-s pass at 2 gen-1 collections per second: 38 between passes
        watch._full_mark = (time.perf_counter() - 10.0, n1 - 20)
        watch._pace(time.perf_counter(), 1.0)
        assert gc.get_threshold()[2] == math.ceil(2 * 1.0 * 19) == 38
        cycles = [[] for _ in range(10_000)]
        for c in cycles:
            c.append(c)
        gc.collect(1)                   # promoted: only a full pass frees them
        del cycles, c
        collected = gc.get_stats()[2]["collected"]
        n2 = watch.collections[2]
        young = watch.collections[0] + watch.collections[1]
        widest = 0
        keep: list = []
        for k in range(60_000_000):
            # survivors that live until the next young pass, as a
            # serving notary's in-flight frames do: the counts advance
            keep.append([k])
            if watch.collections[0] + watch.collections[1] > young:
                young = watch.collections[0] + watch.collections[1]
                widest = max(widest, len(keep))
                keep = []
            if watch.collections[2] > n2:
                break
        # both pacers ran: young passes spaced above the floor, within
        # the cap, and the full pass after the full pacer's 38 gen-1
        # passes (every second young pass once threshold0 is wide)
        assert prior[0] < widest <= runtime.YOUNG_PASS_MAX_N + 1_000
        assert watch.collections[2] == n2 + 1
        assert watch.collections[1] - n1 >= 38
        assert gc.get_stats()[2]["collected"] >= collected + 10_000
    finally:
        watch.release(pace=True)
        gc.unfreeze()
        if not enabled:
            gc.disable()
    assert gc.get_threshold() == prior


@pytest.mark.parametrize("rate0,pass_s,floor,want", [
    (20_000.0, 0.0005, 700, 700),       # short passes: CPython's 700
    (20_000.0, 0.0005, 2_000, 2_000),   # never below the prior threshold
    (30_000.0, 1.0, 700,                # YOUNG_PASS_MAX_S of allocations
     math.ceil(30_000 * runtime.YOUNG_PASS_MAX_S)),
    (250_000.0, 0.004, 700, math.ceil(250_000 * 0.004 * 19)),  # between
    (250_000.0, 1.0, 700, runtime.YOUNG_PASS_MAX_N),  # a long pass: N
    (2e7, 0.001, 700, runtime.YOUNG_PASS_MAX_N),      # a burst's rate: N
])
def test_young_pass_threshold_rule(rate0, pass_s, floor, want):
    assert runtime.YOUNG_PASS_SHARE == 0.05
    assert runtime.young_pass_threshold(rate0, pass_s, floor) == want


def test_paced_young_pass_raises_threshold0_until_the_last_release():
    watch = runtime.GcWatch()
    callbacks = list(gc.callbacks)
    prior = gc.get_threshold()
    enabled = gc.isenabled()
    gc.disable()
    try:
        watch.acquire(pace=True)
        watch.acquire(pace=True)
        # 200,000 net allocations over 0.1 s, then a pass over them: a
        # pass of more than ~18 us spaces the next beyond 700
        keep = [[k] for k in range(200_000)]
        watch._young_mark = time.perf_counter() - 0.1
        gc.collect(0)
        del keep
        t0, t1, t2 = gc.get_threshold()
        assert watch.collections[0] == 1
        assert prior[0] < t0 <= runtime.YOUNG_PASS_MAX_N
        # a gen-1 pass answers no more allocations than before
        assert t1 == prior[0] * prior[1] // t0 < prior[1]
        assert t2 == prior[2]
        gc.set_threshold(t0, t1, 77)    # as the full-pass pacer would
        watch.release(pace=True)
        assert gc.get_threshold() == (t0, t1, 77)
        watch.release(pace=True)        # the last: all three, exactly
        assert gc.get_threshold() == prior
        assert gc.callbacks == callbacks
    finally:
        gc.set_threshold(*prior)
        if enabled:
            gc.enable()


def test_young_cycles_are_still_collected_while_paced():
    watch = runtime.GcWatch()
    prior = gc.get_threshold()
    enabled = gc.isenabled()
    gc.freeze()
    gc.enable()
    watch.acquire(pace=True)
    try:
        gc.collect(2)                   # counts from zero
        # a 1-s young pass at 20,000 net allocations per second wants
        # 380,000 between passes; the cap holds it to YOUNG_PASS_MAX_S
        t = time.perf_counter()
        watch._young_mark, watch._count0 = t - 1.0, 20_000
        watch._pace_young(t, t + 1.0)
        cap = math.ceil(20_000 * runtime.YOUNG_PASS_MAX_S)
        assert cap < 380_000
        assert gc.get_threshold()[0] == cap
        cycles = [[] for _ in range(10_000)]
        for c in cycles:
            c.append(c)
        del cycles, c
        stats = gc.get_stats()
        collected = stats[0]["collected"] + stats[1]["collected"]
        n = watch.collections[0] + watch.collections[1]
        keep: list = []
        for k in range(2 * cap):
            keep.append([k])            # survivors: the net count grows
            if watch.collections[0] + watch.collections[1] > n:
                break
        # the next young pass came within the cap's allocations and
        # freed the cycles born young
        assert watch.collections[0] + watch.collections[1] == n + 1
        assert len(keep) <= cap
        stats = gc.get_stats()
        assert (stats[0]["collected"] + stats[1]["collected"]
                >= collected + 10_000)
    finally:
        watch.release(pace=True)
        gc.unfreeze()
        if not enabled:
            gc.disable()
    assert gc.get_threshold() == prior


def test_notary_engages_the_pacer_until_stop():
    watch = runtime.get_gc_watch()
    paced = watch._paced
    net, notary, svc = _notary()
    assert watch._paced == paced + 1
    assert svc.metrics.get("Runtime.GcFullThreshold").value() == (
        gc.get_threshold()[2])
    assert svc.metrics.get("Runtime.GcYoungThreshold").value() == (
        gc.get_threshold()[0])
    svc.stop()
    svc.stop()                          # idempotent: one release
    assert watch._paced == paced
    for validating in (False, True):    # simple and validating: never
        MockNetwork(seed=24).create_notary("Plain", validating=validating)
        assert watch._paced == paced


# -- intake -------------------------------------------------------------------


def test_full_ring_wait_is_counted_and_exported(tmp_path):
    import threading

    from corda_tpu.node.ingest import IngestRing
    from corda_tpu.node.messaging import register_ring_gauges
    from corda_tpu.utils.metrics import MetricRegistry

    ring = IngestRing(depth=1)
    metrics = MetricRegistry()
    register_ring_gauges(metrics, "notary", ring)
    ring.put(["a"])
    assert ring.full_wait_s == 0.0

    def body():
        t = threading.Timer(0.02, ring.drain)
        t.start()
        assert ring.put(["b"])          # blocks until the drain
        t.join()

    regions = _capture(tmp_path, body)
    assert ring.full_wait_s >= 0.015
    assert metrics.get("Ingest.notary.RingFullWaitSeconds").value() == (
        ring.full_wait_s)
    assert _seconds(regions, "ingest.ring_full") == pytest.approx(
        ring.full_wait_s, rel=0.05)


def test_ingest_batch_regions(tmp_path):
    from corda_tpu.core import serialization as ser
    from corda_tpu.node.ingest import IngestPipeline

    net, notary, svc = _notary()
    _, stxs = _spends(net, notary, 3)
    pipe = IngestPipeline(frame_cache_size=0)
    try:
        regions = _capture(
            tmp_path, lambda: pipe.ingest([ser.encode(s) for s in stxs])
        )
    finally:
        pipe.close()
    for name in ("ingest.decode", "ingest.decode_wait", "ingest.merkle_id",
                 "ingest.stage"):
        assert regions.get(name), name
    assert sum(st["frames"] for _, _, st in regions["ingest.decode"]) == 3
