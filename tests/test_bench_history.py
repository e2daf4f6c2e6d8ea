"""tools/bench_history.py: the bench-trajectory diff + regression gate.

BENCH_r05 shipped two headline metrics at 0.55x/0.34x of baseline with
nothing in-repo flagging it; the CLI under test is that flag. Fixture
records mirror the real driver capture shape: a JSON document whose
`tail` text interleaves per-metric JSON lines with warning chatter.
"""

import json
import os
import sys

import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from tools import bench_history as bh  # noqa: E402


def _write_record(directory, filename, n, metric_lines):
    doc = {
        "n": n,
        "cmd": "python bench.py",
        "rc": 0,
        "tail": "\n".join(
            ["WARNING: All log messages before absl::InitializeLog() is called are written to STDERR"]
            + metric_lines
            + ["bench: skipped parity — 12s of secondary budget left"]
        ),
    }
    path = os.path.join(directory, filename)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def _metric(name, value, vs=None, **extra):
    rec = {"metric": name, "value": value, "unit": "x/s"}
    if vs is not None:
        rec["vs_baseline"] = vs
    rec.update(extra)
    return json.dumps(rec)


@pytest.fixture
def rounds(tmp_path):
    _write_record(
        tmp_path, "BENCH_r01.json", 1,
        [
            _metric("ecdsa_p256_verifies_per_sec_via_spi", 80_000.0, 1.6),
            _metric("batching_notary_notarisations_per_sec", 40_000.0, 0.8),
            _metric("wire_ingest_decode_id_stage_per_sec", 50_000.0, 1.0),
        ],
    )
    _write_record(
        tmp_path, "BENCH_r02.json", 2,
        [
            _metric("ecdsa_p256_verifies_per_sec_via_spi", 85_000.0, 1.7),
            # the regression the gate exists for: -31%
            _metric("batching_notary_notarisations_per_sec", 27_500.0, 0.55),
            # a metric the newest round skipped (budget) stays non-fatal
        ],
    )
    return str(tmp_path)


def test_discovery_orders_by_round_number(rounds):
    # a 2-digit round sorts after a 9 lexically only if ordered by the
    # numeric key, not the string
    _write_record(
        rounds, "BENCH_r10.json", 10,
        [_metric("ecdsa_p256_verifies_per_sec_via_spi", 90_000.0)],
    )
    names = [os.path.basename(p) for p in bh.discover(rounds)]
    assert names == ["BENCH_r01.json", "BENCH_r02.json", "BENCH_r10.json"]


def test_parse_record_skips_noise_and_keeps_last_line_per_metric(tmp_path):
    path = _write_record(
        tmp_path, "BENCH_r03.json", 3,
        [
            "not json at all",
            _metric("m", 1.0),
            '{"no_metric_key": true}',
            _metric("m", 2.0),   # a retry reprinted the line: last wins
        ],
    )
    parsed = bh.parse_record(path)
    assert parsed == {
        "m": {"metric": "m", "value": 2.0, "unit": "x/s"}
    }


def test_diff_reports_deltas_and_missing_metrics(rounds):
    old, new = [bh.parse_record(p) for p in bh.discover(rounds)]
    rows = {r["metric"]: r for r in bh.diff(old, new)}
    assert rows["ecdsa_p256_verifies_per_sec_via_spi"]["delta_pct"] == 6.25
    assert rows["batching_notary_notarisations_per_sec"]["delta_pct"] == (
        -31.25
    )
    assert rows["batching_notary_notarisations_per_sec"]["vs_baseline"] == (
        0.55
    )
    missing = rows["wire_ingest_decode_id_stage_per_sec"]
    assert missing["new"] is None and missing["delta_pct"] is None


def test_main_prints_diff_and_gate_verdicts(rounds, capsys):
    assert bh.main(["--dir", rounds]) == 0
    out = capsys.readouterr().out
    assert "BENCH_r01.json -> BENCH_r02.json" in out
    assert "batching_notary_notarisations_per_sec" in out
    assert "-31.25%" in out

    # gate wide enough: the -31% notary drop passes a 40% gate
    assert bh.main(["--dir", rounds, "--gate", "40"]) == 0
    # gate at 10%: the regression trips it, the missing metric doesn't
    assert bh.main(["--dir", rounds, "--gate", "10"]) == 1
    err = capsys.readouterr().err
    assert "GATE batching_notary_notarisations_per_sec" in err
    assert "wire_ingest" not in err


def test_main_needs_two_records(tmp_path, capsys):
    assert bh.main(["--dir", str(tmp_path)]) == 2
    _write_record(tmp_path, "BENCH_r01.json", 1, [_metric("m", 1.0)])
    assert bh.main(["--dir", str(tmp_path)]) == 2


def test_all_walks_the_whole_trajectory(rounds, capsys):
    _write_record(
        rounds, "BENCH_r03.json", 3,
        [_metric("ecdsa_p256_verifies_per_sec_via_spi", 88_000.0, 1.76)],
    )
    assert bh.main(["--dir", rounds, "--all"]) == 0
    out = capsys.readouterr().out
    assert "BENCH_r01.json -> BENCH_r02.json" in out
    assert "BENCH_r02.json -> BENCH_r03.json" in out


def test_nested_stage_keys_diff_and_gate_lower_is_better(tmp_path, capsys):
    """The trace metric's stages_seconds breakdown diffs per-stage
    (hot_path_stage_breakdown.stages_seconds.<k> rows, LOWER is
    better) and a stage-level regression fails the gate even when the
    headline value HELD — the exact blind spot the satellite closes:
    a 2x slower commit hidden behind a faster dispatch."""
    old_stages = {
        "decode": 0.010, "dispatch": 0.040, "commit": 0.020,
    }
    new_stages = {
        "decode": 0.010, "dispatch": 0.020, "commit": 0.044,  # +120%
    }
    _write_record(
        tmp_path, "BENCH_r01.json", 1,
        [_metric(
            "hot_path_stage_breakdown", 0.98, 0.98,
            stages_seconds=old_stages,
            gate_lower_is_better=["stages_seconds"],
        )],
    )
    _write_record(
        tmp_path, "BENCH_r02.json", 2,
        [_metric(
            "hot_path_stage_breakdown", 0.98, 0.98,   # headline holds
            stages_seconds=new_stages,
            gate_lower_is_better=["stages_seconds"],
        )],
    )
    old, new = [bh.parse_record(p) for p in bh.discover(str(tmp_path))]
    rows = {r["metric"]: r for r in bh.diff(old, new)}
    commit = rows["hot_path_stage_breakdown.stages_seconds.commit"]
    assert commit["better"] == "lower"
    assert commit["delta_pct"] == 120.0
    # dispatch IMPROVED (smaller seconds): never a gate failure
    dispatch = rows["hot_path_stage_breakdown.stages_seconds.dispatch"]
    assert dispatch["delta_pct"] == -50.0
    bad = bh.gate_failures(list(rows.values()), 10.0)
    assert [r["metric"] for r in bad] == [
        "hot_path_stage_breakdown.stages_seconds.commit"
    ]
    # end to end through main(): the headline held, the stage gates
    assert bh.main(["--dir", str(tmp_path), "--gate", "10"]) == 1
    err = capsys.readouterr().err
    assert "stages_seconds.commit" in err


def test_lower_is_better_headline_gates_on_growth_not_improvement(tmp_path):
    """Overhead-shaped headlines (perf/health plane cost) declare
    `lower_is_better`: an improvement must pass the gate, growth must
    fail it — the opposite of throughput rows."""
    _write_record(
        tmp_path, "BENCH_r01.json", 1,
        [_metric("perf_plane_overhead", 0.010, lower_is_better=True),
         _metric("health_plane_overhead", 0.004, lower_is_better=True)],
    )
    _write_record(
        tmp_path, "BENCH_r02.json", 2,
        [_metric("perf_plane_overhead", 0.005, lower_is_better=True),
         _metric("health_plane_overhead", 0.016, lower_is_better=True)],
    )
    old, new = [bh.parse_record(p) for p in bh.discover(str(tmp_path))]
    rows = bh.diff(old, new)
    bad = bh.gate_failures(rows, 10.0)
    # the 50% improvement passes; the 4x growth fails
    assert [r["metric"] for r in bad] == ["health_plane_overhead"]


def test_lower_is_better_growth_from_zero_still_gates(tmp_path):
    """The overhead metrics clamp at 0.0 on a quiet box; a later
    regression from that 0.0 has an undefined delta percent and used
    to slip the gate silently. Growth past the absolute floor gates;
    micro-noise above literal zero does not."""
    _write_record(
        tmp_path, "BENCH_r01.json", 1,
        [_metric("perf_plane_overhead", 0.0, lower_is_better=True),
         _metric("health_plane_overhead", 0.0, lower_is_better=True)],
    )
    _write_record(
        tmp_path, "BENCH_r02.json", 2,
        [_metric("perf_plane_overhead", 0.05, lower_is_better=True),
         _metric("health_plane_overhead", 0.0005, lower_is_better=True)],
    )
    old, new = [bh.parse_record(p) for p in bh.discover(str(tmp_path))]
    bad = bh.gate_failures(bh.diff(old, new), 10.0)
    assert [r["metric"] for r in bad] == ["perf_plane_overhead"]


def test_required_true_verdict_keys_gate(tmp_path, capsys):
    """PR 8: the fleet soak's `gate_required_true` keys. A newest
    record whose `reconciled` (or `slo_held`) verdict is false fails
    the gate regardless of the goodput headline; truthy verdicts pass;
    a missing fleet metric (budget-trimmed round) never gates."""
    _write_record(
        tmp_path, "BENCH_r01.json", 1,
        [_metric(
            "fleet_soak_goodput", 200.0,
            gate_required_true=["reconciled", "slo_held"],
            reconciled=True, slo_held=True,
        )],
    )
    _write_record(
        tmp_path, "BENCH_r02.json", 2,
        [_metric(
            # headline IMPROVED — and the soak stopped reconciling
            "fleet_soak_goodput", 250.0,
            gate_required_true=["reconciled", "slo_held"],
            reconciled=False, slo_held=True,
        )],
    )
    old, new = [bh.parse_record(p) for p in bh.discover(str(tmp_path))]
    rows = {r["metric"]: r for r in bh.diff(old, new)}
    assert rows["fleet_soak_goodput.reconciled"]["better"] == "required"
    bad = bh.gate_failures(list(rows.values()), 10.0)
    assert [r["metric"] for r in bad] == ["fleet_soak_goodput.reconciled"]
    assert bh.main(["--dir", str(tmp_path), "--gate", "10"]) == 1
    err = capsys.readouterr().err
    assert "fleet_soak_goodput.reconciled" in err

    # both verdicts true: the gate passes
    _write_record(
        tmp_path, "BENCH_r03.json", 3,
        [_metric(
            "fleet_soak_goodput", 190.0,
            gate_required_true=["reconciled", "slo_held"],
            reconciled=True, slo_held=True,
        )],
    )
    assert bh.main(["--dir", str(tmp_path), "--gate", "50"]) == 0
    # and a round that dropped the fleet metric entirely doesn't gate
    _write_record(
        tmp_path, "BENCH_r04.json", 4,
        [_metric("ecdsa_p256_verifies_per_sec_via_spi", 80_000.0)],
    )
    assert bh.main(["--dir", str(tmp_path), "--gate", "50"]) == 0


def test_nested_keys_explode_without_marker_for_old_records(tmp_path):
    """Records written before the marker existed still explode their
    stages_seconds via the built-in default, so the committed
    trajectory gains stage rows as soon as both sides carry them."""
    _write_record(
        tmp_path, "BENCH_r01.json", 1,
        [_metric("hot_path_stage_breakdown", 1.0,
                 stages_seconds={"commit": 0.02})],
    )
    parsed = bh.parse_record(bh.discover(str(tmp_path))[0])
    rows = {r["metric"]: r for r in bh.diff(parsed, parsed)}
    assert (
        rows["hot_path_stage_breakdown.stages_seconds.commit"]["delta_pct"]
        == 0.0
    )


def test_json_output_emits_machine_readable_diff(rounds, capsys):
    """`--json` (round-13 satellite): ONE JSON document on stdout —
    the newest pair's rows, gate failures alongside, the text table
    suppressed — so CI can archive the diff as an artifact without
    scraping the human format. Exit-code contract unchanged."""
    assert bh.main(["--dir", rounds, "--json"]) == 0
    out = capsys.readouterr().out
    doc = json.loads(out)
    assert doc["old"] == "BENCH_r01.json"
    assert doc["new"] == "BENCH_r02.json"
    by_name = {r["metric"]: r for r in doc["rows"]}
    notary = by_name["batching_notary_notarisations_per_sec"]
    assert notary["delta_pct"] == -31.25
    assert notary["better"] == "higher"
    # the skipped metric diffs as missing-in-new, never a failure
    assert by_name["wire_ingest_decode_id_stage_per_sec"]["new"] is None
    assert doc["gate_pct"] is None and doc["gate_failures"] == []
    assert "BENCH_r01.json ->" not in out   # no text table mixed in

    # with --gate, failures land IN the document and the exit code
    # still trips
    assert bh.main(["--dir", rounds, "--json", "--gate", "10"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["gate_pct"] == 10.0
    failed = {r["metric"] for r in doc["gate_failures"]}
    assert failed == {"batching_notary_notarisations_per_sec"}


def test_json_all_carries_every_pair(rounds, capsys):
    _write_record(
        rounds, "BENCH_r03.json", 3,
        [_metric("ecdsa_p256_verifies_per_sec_via_spi", 86_000.0, 1.7)],
    )
    assert bh.main(["--dir", rounds, "--json", "--all"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert [p["old"] for p in doc["pairs"]] == [
        "BENCH_r01.json", "BENCH_r02.json",
    ]
    # the top-level rows are the NEWEST pair's
    assert doc["new"] == "BENCH_r03.json"


def test_markdown_format_renders_github_table(rounds, capsys):
    """`--format md` (round-17 satellite): the same per-metric diff as
    the text table, rendered as a GitHub markdown table for PR
    descriptions and CI job summaries. Direction markers get their own
    column; missing values render as `-`."""
    assert bh.main(["--dir", rounds, "--format", "md"]) == 0
    out = capsys.readouterr().out
    assert "### bench diff: `BENCH_r01.json` -> `BENCH_r02.json`" in out
    assert "| metric | old | new | delta | vs_baseline | direction |" in out
    lines = {
        l.split("|")[1].strip(): l for l in out.splitlines()
        if l.startswith("| `")
    }
    notary = lines["`batching_notary_notarisations_per_sec`"]
    assert "-31.25%" in notary and "higher is better" in notary
    # the metric the newest round skipped renders with `-` cells
    missing = lines["`wire_ingest_decode_id_stage_per_sec`"]
    assert missing.count(" - ") >= 2
    # the text-table header never appears in md mode
    assert "bench diff: BENCH_r01.json ->" not in out

    # direction column distinguishes required-true and lower-is-better
    rows = [
        {"metric": "soak.reconciled", "old": 1.0, "new": 1.0,
         "delta_pct": 0.0, "vs_baseline": None, "better": "required"},
        {"metric": "plane_overhead", "old": 0.01, "new": 0.02,
         "delta_pct": 100.0, "vs_baseline": None, "better": "lower"},
    ]
    md = bh.format_rows_md(rows, "a.json", "b.json")
    assert "required true" in md and "lower is better" in md

    # --format md composes with --gate: same exit-code contract
    assert bh.main(
        ["--dir", rounds, "--format", "md", "--gate", "10"]
    ) == 1
    captured = capsys.readouterr()
    assert "| metric |" in captured.out
    assert "GATE batching_notary_notarisations_per_sec" in captured.err


def test_environment_change_waives_delta_gate_but_not_verdicts(
    tmp_path, capsys
):
    """Round 15: bench stamps an `environment` block into every metric
    line; when the newest two records' environments DIFFER (the CPU
    container vs the coming device round), a throughput delta measures
    the rig, not the code — the gate WARNS and annotates instead of
    failing. Required-true verdict rows still gate: a soak that
    stopped reconciling is broken on any backend."""
    cpu_env = {"jax": "0.4.37", "backend": "cpu", "device_kind": "cpu",
               "device_count": 1, "cpu_count": 8}
    tpu_env = dict(cpu_env, backend="tpu", device_kind="TPU v5e",
                   device_count=4)
    _write_record(
        tmp_path, "BENCH_r01.json", 1,
        [
            _metric("batching_notary_notarisations_per_sec", 41_500.0,
                    0.83, environment=cpu_env),
            _metric("fleet_soak_goodput_per_sec", 9_000.0, 1.0,
                    environment=cpu_env, reconciled=True,
                    gate_required_true=["reconciled"]),
        ],
    )
    _write_record(
        tmp_path, "BENCH_r02.json", 2,
        [
            # a 40% "regression" — but on a different backend
            _metric("batching_notary_notarisations_per_sec", 25_000.0,
                    0.5, environment=tpu_env),
            _metric("fleet_soak_goodput_per_sec", 9_500.0, 1.0,
                    environment=tpu_env, reconciled=True,
                    gate_required_true=["reconciled"]),
        ],
    )
    # the delta regression is WAIVED (warn + annotate), exit 0
    assert bh.main(["--dir", str(tmp_path), "--gate", "10"]) == 0
    err = capsys.readouterr().err
    assert "WARN" in err and "environment changed" in err
    assert "backend: cpu -> tpu" in err

    # the same delta with IDENTICAL environments still gates
    _write_record(
        tmp_path, "BENCH_r02.json", 2,
        [_metric("batching_notary_notarisations_per_sec", 25_000.0,
                 0.5, environment=cpu_env)],
    )
    assert bh.main(["--dir", str(tmp_path), "--gate", "10"]) == 1
    capsys.readouterr()

    # a stamped round following an UNSTAMPED one (the committed
    # r01-r06 trajectory predates the stamp) cannot claim same-rig
    # either: the first cross-rig round after this PR must not
    # hard-gate — the exact false failure the feature prevents
    _write_record(
        tmp_path, "BENCH_r01.json", 1,
        [_metric("batching_notary_notarisations_per_sec", 41_500.0,
                 0.83)],                       # no environment block
    )
    _write_record(
        tmp_path, "BENCH_r02.json", 2,
        [_metric("batching_notary_notarisations_per_sec", 25_000.0,
                 0.5, environment=tpu_env)],
    )
    assert bh.main(["--dir", str(tmp_path), "--gate", "10"]) == 0
    assert "WARN" in capsys.readouterr().err

    # two unstamped records keep the plain gate (no rig evidence)
    _write_record(
        tmp_path, "BENCH_r02.json", 2,
        [_metric("batching_notary_notarisations_per_sec", 25_000.0,
                 0.5)],
    )
    assert bh.main(["--dir", str(tmp_path), "--gate", "10"]) == 1
    capsys.readouterr()

    # a falsy required-true verdict gates THROUGH an environment change
    _write_record(
        tmp_path, "BENCH_r02.json", 2,
        [
            _metric("fleet_soak_goodput_per_sec", 9_500.0, 1.0,
                    environment=tpu_env, reconciled=False,
                    gate_required_true=["reconciled"]),
        ],
    )
    assert bh.main(["--dir", str(tmp_path), "--gate", "10"]) == 1


def test_environment_annotation_lands_in_json_output(tmp_path, capsys):
    env_a = {"backend": "cpu", "device_count": 1}
    env_b = {"backend": "tpu", "device_count": 4}
    _write_record(
        tmp_path, "BENCH_r01.json", 1,
        [_metric("m", 100.0, environment=env_a)],
    )
    _write_record(
        tmp_path, "BENCH_r02.json", 2,
        [_metric("m", 50.0, environment=env_b)],
    )
    assert bh.main(
        ["--dir", str(tmp_path), "--gate", "10", "--json"]
    ) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["environment_changed"] == {
        "backend": "cpu -> tpu", "device_count": "1 -> 4",
    }
    assert doc["gate_failures"] == []
    waived = doc["gate_waived_environment_change"]
    assert len(waived) == 1 and waived[0]["metric"] == "m"
    assert waived[0]["waived_environment_change"]["backend"] == (
        "cpu -> tpu"
    )
