"""What a run refuses: no TPU, a frame pool that runs dry, and a
directory that holds only the benchmark. Each fails and prints no
result."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from benchmark_harness_util import REPO, run_cell


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "p256_cash.backlog", "--seed", "7", "--seconds", "0.01",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _seed_native_stamp(root):
    """Mark the suite's own build of the native codec as current, so
    the run does not rebuild it under other workers' feet."""
    import hashlib
    import sysconfig

    native = os.path.join(REPO, "corda_tpu", "native")
    so = os.path.join(
        native, "_cts_hash" + (sysconfig.get_config_var("EXT_SUFFIX") or ".so")
    )
    if not os.path.exists(so):
        return
    with open(os.path.join(native, "cts_hash.cpp"), "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()
    cache = os.path.join(root, "benchmark", ".cache")
    os.makedirs(cache, exist_ok=True)
    with open(os.path.join(cache, "native.stamp"), "w") as fh:
        fh.write(f"{digest} {so}\n")


def test_no_tpu_fails_without_a_result(bench_root):
    # the checkout is the test's copy, with the system under test beside
    os.symlink(os.path.join(REPO, "corda_tpu"),
               os.path.join(bench_root, "corda_tpu"))
    _seed_native_stamp(bench_root)
    p = _run(bench_root)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_benchmark_alone_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_pool_that_runs_dry_fails(bench_root, capsys):
    from benchmark import harness

    with pytest.raises(harness.RunFailure, match="ran dry"):
        run_cell(bench_root, "p256_cash.backlog",
                 overrides={"config": {"pool_per_s": 10}})
