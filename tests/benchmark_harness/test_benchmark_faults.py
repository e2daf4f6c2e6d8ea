"""The control and each fault the cell can have turn `correct` false.

Each fault is planted in the timed path (benchmark/faults.py) of a tiny
CPU run: signatures no longer checked (the control), a store that keeps
nothing, a notary signature altered where it is produced, a commit log
never fsynced or fsynced only after the replies, and half of each batch
left out."""

from __future__ import annotations

import pytest

from benchmark_harness_util import run_cell

CASES = {
    "accept_all_signatures": ("wrong_answers", "bad_notary_signatures"),
    "skip_uniqueness": ("wrong_answers", "commits_not_read_back"),
    "alter_answer": ("bad_notary_signatures",),
    "no_fsync": ("signed_before_fsync",),
    "fsync_after_reply": ("signed_before_fsync",),
    "drop_half": ("unanswered",),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_fault_makes_run_incorrect(bench_root, name):
    from benchmark import faults

    # with signatures unchecked the CPU serves far faster: a deeper pool
    out = run_cell(bench_root, "p256_cash.backlog", fault=faults.FAULTS[name],
                   seconds=1.0,
                   overrides={"drain_s": 3.0, "workers": 2,
                              "config": {"pool_per_s": 40000}})
    assert out["correct"] is False
    checks = out["checks"]
    assert any(checks[k]["value"] > checks[k]["limit"] for k in CASES[name])
