"""Every cell of BENCHMARK.json end to end on the CPU at a tiny size.

The window drives wire frames through IngestPipeline into the attached
BatchingNotaryService with a commit-log store; the check holds every
answer to construction and reads every commit back from the reopened
store. A tampered frame and a re-spend get their typed answers."""

from __future__ import annotations

import json
import os

import pytest

from benchmark_harness_util import REPO, run_cell

with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
    MANIFEST = json.load(fh)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_cpu(bench_root, cell):
    out = run_cell(bench_root, cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {
        m["name"] for m in MANIFEST["end_to_end"]
        if cell in m.get("workloads", [cell])
    }
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    # the typed answers: tampered frames refused for their signature,
    # re-spends refused as conflicts, the rest signed
    assert set(out["answers"]) == {"signed", "invalid-signature", "conflict"}
    assert out["device"]["platform"] == "cpu"


def test_paced_mix_runs_correct_on_cpu(bench_root):
    """The paced mix waits under PERF.md's Open questions with its files
    kept: added back by manifest entries alone, it serves open-loop
    arrivals and times each answer from its frame's due time."""
    man_p = os.path.join(bench_root, "BENCHMARK.json")
    with open(man_p) as fh:
        man = json.load(fh)
    man["workloads"].append({
        "name": "p256_cash.paced", "config": "notary_p256_cash",
        "traffic": "paced", "chips": 1, "why": "test"})
    man["end_to_end"] += [
        {"name": name, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["p256_cash.paced"]}
        for name in ("latency_p99_ms", "latency_p50_ms")
    ]
    with open(man_p, "w") as fh:
        json.dump(man, fh)
    out = run_cell(bench_root, "p256_cash.paced")
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"latency_p99_ms", "latency_p50_ms", "setup_s"}
    assert m["latency_p99_ms"] >= m["latency_p50_ms"] > 0
