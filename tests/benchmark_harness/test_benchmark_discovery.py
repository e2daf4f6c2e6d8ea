"""A later PR adds a cell by adding a configuration file, a traffic file
and manifest entries, and a metric by adding a reader file and a
manifest entry — no file that is already there changes."""

from __future__ import annotations

import hashlib
import json
import os

import pytest

from benchmark_harness_util import run_cell


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_are_found_by_name(bench_root):
    bench = os.path.join(bench_root, "benchmark")
    before = _digests(bench_root)
    with open(os.path.join(bench, "configs", "notary_p256_cash.json")) as fh:
        cfg = json.load(fh)
    cfg["name"] = "notary_p256_cash_small_owners"
    cfg["shape"]["owners"] = 8
    with open(os.path.join(bench, "configs", "extra.json"), "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(bench, "traffic", "burst.json"), "w") as fh:
        json.dump({"arrivals": "backlog", "batch": 64, "pool_per_s": 1500,
                   "warmup_frames": 32}, fh)
    with open(os.path.join(bench, "metrics", "answered_total.py"),
              "w") as fh:
        fh.write("def read(ctx):\n    return ctx.answered_in_window\n")
    man_p = os.path.join(bench_root, "BENCHMARK.json")
    with open(man_p) as fh:
        man = json.load(fh)
    man["configs"].append({
        "name": cfg["name"], "source": "https://example.org/x",
        "file": "benchmark/configs/extra.json", "reduced": [],
        "why": "test"})
    man["workloads"].append({
        "name": "extra.burst", "config": cfg["name"], "traffic": "burst",
        "chips": 1, "why": "test"})
    man["end_to_end"].append({
        "name": "answered_total", "unit": "tx", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["extra.burst"]})
    with open(man_p, "w") as fh:
        json.dump(man, fh)

    out = run_cell(bench_root, "extra.burst")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"answered_total", "setup_s"}
    assert out["metrics"]["answered_total"]["value"] > 0
    after = _digests(bench_root)
    assert all(after[p] == d for p, d in before.items())


def _add_cell(root, name, chips, **changes):
    """A configuration changed by `changes` (top-level keys; dict values
    merge into their group) and a backlog cell over it, as files and
    manifest entries only."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "notary_p256_cash.json")) as fh:
        cfg = json.load(fh)
    cfg["name"] = name
    for k, v in changes.items():
        cfg[k] = dict(cfg[k], **v) if isinstance(v, dict) else v
    with open(os.path.join(bench, "configs", f"{name}.json"), "w") as fh:
        json.dump(cfg, fh)
    man_p = os.path.join(root, "BENCHMARK.json")
    with open(man_p) as fh:
        man = json.load(fh)
    man["configs"].append({
        "name": name, "source": "https://example.org/x",
        "file": f"benchmark/configs/{name}.json", "reduced": [],
        "why": "test"})
    man["workloads"].append({
        "name": f"{name}.backlog", "config": name, "traffic": "backlog",
        "chips": chips, "why": "test"})
    with open(man_p, "w") as fh:
        json.dump(man, fh)
    return f"{name}.backlog"


@pytest.mark.parametrize("chips", [1, 4])
def test_sharded_config_is_served_as_stated(bench_root, chips):
    """A config with more commit-plane shards is served with that many
    store partitions and, on more than one chip, one verifier pinned
    to each chip (the CPU's virtual devices stand in)."""
    cell = _add_cell(bench_root, f"sharded_{chips}", chips,
                     notary={"shards": 4}, store={"n_shards": 4})
    seen = {}

    def observe(svc, services, store):
        seen["shards"] = svc.n_shards
        seen["partitions"] = store.n_shards
        seen["devices"] = {str(s.verifier.device) for s in svc._shards}

    out = run_cell(bench_root, cell, fault=observe)
    assert out["correct"], out["checks"]
    assert seen["shards"] == seen["partitions"] == 4
    assert len(seen["devices"]) == (4 if chips == 4 else 1)
    assert out["device"]["count"] == chips


@pytest.mark.parametrize("changes,why", [
    ({"guarantees": {"validating": False}}, "validating"),
    ({"store": {"n_shards": 2}}, "shards"),
    ({"store": {"kind": "InMemoryUniquenessProvider"}}, "store kind"),
])
def test_config_the_harness_cannot_honour_is_refused(bench_root, changes,
                                                     why):
    from benchmark import harness

    cell = _add_cell(bench_root, "unservable", 1, **changes)
    with pytest.raises(harness.RunFailure, match=why):
        run_cell(bench_root, cell)


def test_four_chip_cell_needs_a_shard_per_chip(bench_root):
    from benchmark import harness

    cell = _add_cell(bench_root, "one_shard_four_chips", 4)
    with pytest.raises(harness.RunFailure, match="shards"):
        run_cell(bench_root, cell)
