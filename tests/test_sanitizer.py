"""Runtime concurrency sanitizer + crash-schedule explorer (round 14).

The acceptance arcs pinned here:

  * the instrumented factory: raw-primitive passthrough while
    disarmed, full lockdep while armed — a SEEDED lock-order inversion
    and a SEEDED pump-hot hold-time hazard are caught at runtime, a
    self-deadlock fails fast instead of hanging, contention and hold
    profiles are measured, Condition.wait releases the held stack;
  * the static<->dynamic diff: the committed tree's standard soak
    observes only statically-proven edges (gate-clean vs
    SANITIZER_BASELINE.json, by-design hold rows justified), a
    dynamically-dispatched edge the static graph lacks IS flagged, and
    the `--report split` output names the pump-hot locks with measured
    hold times;
  * the crash-schedule explorer: >= 100 distinct kill/reorder
    schedules over the cross-member 2PC + WAL protocols with ZERO
    invariant violations on the committed tree, and the deliberately
    broken WAL ordering (first ShardCommit before the commit mark) is
    detected — the negative pin that proves the instrument can fail;
  * the bench leg: `bench.py --quick sanitizer` emits the
    disarmed-overhead record with its required-true verdict.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from corda_tpu.testing import sanitizer as szr  # noqa: E402
from corda_tpu.utils import locks  # noqa: E402


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    locks.install_monitor(None)


@pytest.fixture(scope="module")
def view():
    """One fact-core extraction for the whole module (pure static,
    ~1.5s — no reason to pay it per test)."""
    return szr.static_lock_view(REPO)


@pytest.fixture(scope="module")
def soaked(view):
    """One armed standard soak shared by every committed-tree
    assertion (the soak itself is deterministic; the assertions read
    different views of the same run)."""
    san = szr.ConcurrencySanitizer(
        hot_locks=view.hot_locks, hold_budget_micros=2_000
    )
    with san:
        out = szr.standard_soak()
    return san, out


# ---------------------------------------------------------------------------
# the instrumented factory


def test_disarmed_factory_is_raw_passthrough():
    """No monitor installed -> the factory IS threading.Lock/RLock/
    Condition. Nothing wraps, nothing records, nothing to pay for."""
    assert type(locks.make_lock("X.a")) is type(threading.Lock())
    assert type(locks.make_rlock("X.b")) is type(threading.RLock())
    assert isinstance(locks.make_condition("X.c"), threading.Condition)
    assert locks.active_monitor() is None


def test_seeded_lock_order_inversion_caught_at_runtime():
    san = szr.ConcurrencySanitizer()
    with san:
        a = locks.make_lock("Seed.a")
        b = locks.make_lock("Seed.b")

        def forward():
            with a:
                with b:
                    pass

        def backward():
            with b:
                with a:
                    pass

        t = threading.Thread(target=forward)
        t.start()
        t.join()
        t = threading.Thread(target=backward)
        t.start()
        t.join()
    cycles = [
        f for f in san.findings() if f.rule == "sanitizer-lock-cycle"
    ]
    assert len(cycles) == 1
    assert cycles[0].severity == "P0"
    assert cycles[0].detail == "Seed.a<->Seed.b"
    assert cycles[0].evidence
    # both directed edges were observed, with call-site evidence
    g = san.graph()
    assert ("Seed.a", "Seed.b") in g and ("Seed.b", "Seed.a") in g
    assert "test_sanitizer.py" in g[("Seed.a", "Seed.b")][0]


def test_seeded_hold_time_hazard_caught_at_runtime():
    san = szr.ConcurrencySanitizer(
        hot_locks={"Seed.hot"}, hold_budget_micros=500
    )
    with san:
        hot = locks.make_lock("Seed.hot")
        cold = locks.make_lock("Seed.cold")
        with hot:
            time.sleep(0.003)
        with cold:                     # not pump-hot: never a hazard
            time.sleep(0.003)
    hazards = [
        f for f in san.findings() if f.rule == "sanitizer-hold-hazard"
    ]
    assert len(hazards) == 1
    assert "Seed.hot" in hazards[0].detail
    assert hazards[0].severity == "P1"
    st = san.lock_stats()["Seed.hot"]
    assert st["hold_us_max"] >= 2000


def test_self_deadlock_fails_fast_instead_of_hanging():
    san = szr.ConcurrencySanitizer()
    with san:
        lk = locks.make_lock("Seed.self")
        with lk:
            with pytest.raises(locks.SanitizerDeadlockError):
                lk.acquire()
            # the wrapper did NOT acquire: the outer exit releases once
        # reentrant locks keep their contract — no finding, no raise
        r = locks.make_rlock("Seed.re")
        with r:
            with r:
                pass
    rules = [f.rule for f in san.findings()]
    assert rules == ["sanitizer-self-deadlock"]


def test_contention_counted_and_wait_timed():
    san = szr.ConcurrencySanitizer()
    with san:
        lk = locks.make_lock("Seed.cont")
        entered = threading.Event()
        release = threading.Event()

        def holder():
            with lk:
                entered.set()
                release.wait(5)

        t = threading.Thread(target=holder)
        t.start()
        assert entered.wait(5)
        waited = threading.Thread(target=lambda: lk.acquire())
        waited.start()
        time.sleep(0.01)
        release.set()
        waited.join(5)
        lk.release()
        t.join(5)
    st = san.lock_stats()["Seed.cont"]
    assert st["acquisitions"] == 2
    assert st["contended"] == 1
    assert st["wait_us_total"] > 0
    assert st["contention_ratio"] == 0.5


def test_condition_wait_releases_held_stack():
    """A thread parked on cond.wait() does NOT hold the condition: no
    hold-hazard for the park, and the notifier's acquisition creates
    no phantom ordering edge against the parked thread."""
    san = szr.ConcurrencySanitizer(
        hot_locks={"Seed.cond"}, hold_budget_micros=1_000
    )
    with san:
        cond = locks.make_condition("Seed.cond")
        ready = threading.Event()
        state = {"go": False}

        def waiter():
            with cond:
                ready.set()
                cond.wait_for(lambda: state["go"], timeout=5)

        t = threading.Thread(target=waiter)
        t.start()
        assert ready.wait(5)
        time.sleep(0.01)       # parked well past the hold budget
        with cond:
            state["go"] = True
            cond.notify_all()
        t.join(5)
    hazards = [
        f for f in san.findings() if f.rule == "sanitizer-hold-hazard"
    ]
    assert hazards == [], [f.message for f in hazards]


def test_condition_reentrant_acquisition_is_legal_when_armed():
    """A default Condition wraps an RLock: nested acquisition by the
    holding thread runs fine with raw primitives, so the armed wrapper
    must not flag it as a self-deadlock (reentrancy follows the
    underlying primitive). A Condition built over a plain Lock keeps
    the trap."""
    san = szr.ConcurrencySanitizer()
    with san:
        cond = locks.make_condition("Seed.recond")
        with cond:
            with cond:             # legal: RLock underneath
                pass
        plain = locks.make_condition(
            "Seed.plaincond", threading.Lock()
        )
        with plain:
            with pytest.raises(locks.SanitizerDeadlockError):
                plain.acquire()
    rules = [f.rule for f in san.findings()]
    assert rules == ["sanitizer-self-deadlock"]
    assert san.findings()[0].detail == "Seed.plaincond"


def test_condition_over_held_sanitized_lock_is_same_primitive():
    """A condition built OVER a SanitizedLock is a second wrapper
    around the same physical lock: acquiring it while the lock is held
    must trip the fail-fast, not hang (the trap compares primitives,
    not wrapper identity)."""
    san = szr.ConcurrencySanitizer()
    with san:
        lk = locks.make_lock("Seed.shared")
        cond = locks.make_condition("Seed.sharedcond", lk)
        assert cond.primitive() is lk.primitive()
        with lk:
            with pytest.raises(locks.SanitizerDeadlockError):
                cond.acquire()


def test_nested_condition_wait_releases_every_level():
    """cond.wait() inside re-entrant acquisition releases EVERY level
    (Condition._release_save on the RLock): the park must not read as
    a hold, and the re-entry depth must restore at wake so the
    unwinding releases balance."""
    san = szr.ConcurrencySanitizer(
        hot_locks={"Seed.deep"}, hold_budget_micros=1_000
    )
    with san:
        cond = locks.make_condition("Seed.deep")
        state = {"go": False}
        ready = threading.Event()

        def waiter():
            with cond:
                with cond:                 # legal RLock re-entry
                    ready.set()
                    cond.wait_for(lambda: state["go"], timeout=5)

        t = threading.Thread(target=waiter)
        t.start()
        assert ready.wait(5)
        time.sleep(0.01)                   # parked past the budget
        with cond:
            state["go"] = True
            cond.notify_all()
        t.join(5)
        assert not t.is_alive()
    hazards = [
        f for f in san.findings() if f.rule == "sanitizer-hold-hazard"
    ]
    assert hazards == [], [f.message for f in hazards]


def test_export_is_json_safe():
    san = szr.ConcurrencySanitizer()
    with san:
        a = locks.make_lock("Seed.x")
        b = locks.make_lock("Seed.y")
        with a:
            with b:
                pass
    doc = json.loads(json.dumps(san.export()))
    assert doc["edges"][0]["from"] == "Seed.x"
    assert "Seed.x" in doc["locks"]
    assert doc["findings"] == []


# ---------------------------------------------------------------------------
# static <-> dynamic


def test_static_lock_view_extracts_the_fact_core(view):
    # the adopted factory names resolve to real static identities
    assert "NodeDatabase._lock" in view.locks
    assert "FlowFuture._lock" in view.locks
    assert view.kinds["NodeDatabase._lock"] == "RLock"
    assert view.hot_locks, "the pump-hot partition must be non-empty"
    # a known statically-proven ordering
    assert ("NotaryQos._lock", "MetricRegistry._lock") in view.edges


def test_diff_flags_edge_the_static_graph_lacks():
    """Dynamic dispatch the AST walk cannot resolve: the runtime edge
    must surface as a sanitizer-edge-unseen finding with a stable
    fingerprint, and a justified baseline row must suppress it."""
    view = szr.StaticLockView(
        edges=set(), locks={"Dyn.a", "Dyn.b"}, hot_locks=set(),
        groups={}, kinds={},
    )
    san = szr.ConcurrencySanitizer()
    with san:
        a = locks.make_lock("Dyn.a")
        b = locks.make_lock("Dyn.b")
        table = {"cb": lambda: b.acquire() or b.release()}
        with a:
            table["cb"]()          # the indirection statics can't see
    diff = san.diff_static(view)
    assert [f.detail for f in diff.findings()] == ["Dyn.a->Dyn.b"]
    f = diff.findings()[0]
    assert f.rule == "sanitizer-edge-unseen"
    # gate mechanics: new without a row, suppressed with justification
    new, stale, unjust = szr.gate([f], [])
    assert new == [f]
    row = {
        "fingerprint": f.fingerprint,
        "justification": "callback table exercised only in tests",
    }
    new, stale, unjust = szr.gate([f], [row])
    assert new == [] and stale == [] and unjust == []
    # an empty justification does NOT suppress
    new, _, unjust = szr.gate([f], [{**row, "justification": ""}])
    assert new == [f] and len(unjust) == 1


def test_committed_tree_soak_diff_clean_vs_baseline(view, soaked):
    """THE CI gate for the dynamic half: the standard soak over the
    committed tree observes only statically-proven lock orderings, no
    runtime inversions/self-deadlocks, and every hold-time hazard at a
    tight probe budget is a justified by-design baseline row."""
    san, out = soaked
    assert out["signed"] >= 1 and out["rejected"] >= 1
    diff = san.diff_static(view)
    findings = san.findings(szr.GATED_RULES) + diff.findings()
    baseline = szr.load_baseline(
        os.path.join(REPO, "SANITIZER_BASELINE.json")
    )
    new, stale, unjustified = szr.gate(findings, baseline)
    # deterministic rules gate hard; hold hazards are timing-dependent
    # and ride the baseline's by-design rows instead
    hard_new = [f for f in new if f.rule != "sanitizer-hold-hazard"]
    assert hard_new == [], [f.render() for f in hard_new]
    assert unjustified == []
    # every statically-unknown runtime lock name would be drift
    assert diff.unknown_locks == []
    # the soak really drove the plane cross-thread: a shard queue's
    # lock was held by a thread other than the pump
    stats = san.lock_stats()
    pump = threading.current_thread().name
    assert any(
        t != pump for t in stats["_NotaryShard.cond"]["threads"]
    ), "no lock was ever held by a thread other than the pump"


def test_split_report_names_pump_hot_locks_with_hold_times(view, soaked):
    san, _ = soaked
    report = san.split_report(view)
    assert report["pump_hot"], "no pump-hot lock was observed"
    for row in report["pump_hot"]:
        assert row["lock"] in view.hot_locks
        assert row["acquisitions"] > 0
        assert row["hold_us_max"] >= row["hold_us_mean"] >= 0
    # the split question: state shared across thread groups, measured
    shared = {r["lock"] for r in report["shared_locks"]}
    assert "_NotaryShard.cond" in shared
    text = szr.render_split_report(report)
    assert "pump-hot locks" in text and "hold mean=" in text
    # the CLI serves the same report (one line of proof, not a rerun:
    # the subprocess pays the whole soak)
    assert "process-split feasibility" in text


def test_write_baseline_roundtrip_preserves_justifications(tmp_path):
    f = szr.Finding(
        "sanitizer-edge-unseen", szr.P1, "x.py", 1, "", "A->B", "msg"
    )
    path = str(tmp_path / "SB.json")
    szr.write_baseline(path, [f])
    doc = json.load(open(path))
    assert doc["baselined"][0]["justification"] == ""
    doc["baselined"][0]["justification"] = "because"
    json.dump(doc, open(path, "w"))
    drift = szr.write_baseline(path, [f])   # re-seed merges, never erases
    assert drift == []
    doc = json.load(open(path))
    assert doc["baselined"][0]["justification"] == "because"
    # severity drift under a justified row is reported (the lint
    # --write-baseline contract)
    doc["baselined"][0]["severity"] = "P2"
    json.dump(doc, open(path, "w"))
    drift = szr.write_baseline(path, [f])
    assert len(drift) == 1 and f.fingerprint in drift[0]


# ---------------------------------------------------------------------------
# crash-schedule explorer


def test_explorer_trace_enumerates_every_journal_boundary():
    ex = szr.CrashScheduleExplorer()
    trace = ex.trace_boundaries()
    ops = {op for _, op in trace}
    # all three WAL surfaces appear in one clean run
    assert {"coord.begin", "coord.decide_commit", "coord.finish"} <= ops
    assert {"res.reserve", "res.release"} <= ops
    assert {
        "intent.append", "intent.mark_resolved", "intent.flush_resolved"
    } <= ops
    assert len(trace) >= 30


def test_explorer_hundred_plus_schedules_zero_violations():
    """THE tentpole acceptance: systematic kill points at EVERY
    coordinator-WAL / reservation-journal / intent-WAL boundary (pre
    and post) plus seeded delivery-permutation schedules — >= 100
    distinct schedules, every invariant holding after each one."""
    ex = szr.CrashScheduleExplorer()
    report = ex.explore(reorder_seeds=30)
    assert report.schedules >= 100, report.summary()
    assert report.violations == [], report.violations[:5]
    kinds = {r.schedule.kind for r in report.results}
    assert kinds == {"kill", "reorder"}
    # kill schedules really killed members at the armed boundary
    killed = [r for r in report.results if r.killed_at is not None]
    assert len(killed) == len(
        [r for r in report.results if r.schedule.kind == "kill"]
    )
    # exactly-one-winner on the contested ref, whichever order the
    # crash let the race resolve in: tx1 and the rival (tx5) contend
    # one ref; tx2/tx3/tx4 are uncontended and always commit
    for r in report.results:
        outcomes = list(r.outcomes.values())
        assert all(
            kind == "accept" for kind, _ in outcomes[1:4]
        ), outcomes
        contenders = [outcomes[0][0], outcomes[4][0]]
        assert sorted(contenders) == ["accept", "reject"], outcomes


def test_explorer_detects_broken_wal_ordering():
    """The negative pin: a coordinator that ships the first
    ShardCommit BEFORE the durable commit mark. A kill inside that
    window leaves a participant holding a commit the restarted
    coordinator presumes aborted — the serial-replay invariant must
    catch the decision-order break."""
    ex = szr.CrashScheduleExplorer(
        provider_cls=szr.make_broken_provider_cls()
    )
    report = ex.explore(
        reorder_seeds=0,
        boundary_filter=lambda op: op == "coord.decide_commit",
    )
    assert report.violations, (
        "the deliberately broken WAL ordering was not detected"
    )
    label, violation = report.violations[0]
    assert "kill" in label and "decide_commit" in label
    assert "serial replay" in violation


def test_explorer_schedules_are_deterministic():
    """Same schedule, same world -> same outcome fingerprint (seeded
    permutations, seeded backoff jitter, TestClock time)."""
    ex = szr.CrashScheduleExplorer()
    sched = szr.Schedule("reorder", seed=7, label="re7")
    r1 = ex.run_schedule(sched)
    r2 = ex.run_schedule(sched)
    assert r1.fingerprint == r2.fingerprint
    assert r1.outcomes == r2.outcomes
    assert r1.violations == [] and r2.violations == []


# ---------------------------------------------------------------------------
# bench leg


def test_bench_quick_sanitizer_smoke():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--quick", "sanitizer"],
        # the smoke batch is tiny (CI-speed), so its flush wall is a
        # few ms and scheduler noise alone exceeds 1% — the smoke
        # proves the record shape and the passthrough, at a
        # noise-floor gate; the default-table run keeps the honest 1%
        # (6% not 3%: on a single-vCPU CI box the mid-suite scheduler
        # jitter alone reaches ~4% of a few-ms wall)
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_BATCH": "48",
             "BENCH_ITERS": "3",
             "BENCH_SANITIZER_OVERHEAD_MAX": "0.06"},
        capture_output=True, text=True, timeout=420,
    )
    assert out.returncode == 0, out.stderr + out.stdout
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "sanitizer_factory_overhead"
    assert rec["sanitizer_overhead_ok"] is True
    assert rec["gate_required_true"] == ["sanitizer_overhead_ok"]
    assert rec["lower_is_better"] is True
    assert rec["value"] <= rec["overhead_max"]
    assert rec["armed_locks_observed"] >= 1


def test_lint_cli_report_split_subprocess():
    """`python -m tools.lint --report split` — the CLI face of the
    feasibility report (the mode that imports corda_tpu)."""
    out = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--report", "split"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "process-split feasibility" in out.stdout
    assert "pump-hot locks" in out.stdout
    assert "static<->dynamic:" in out.stdout
