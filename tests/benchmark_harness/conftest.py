"""Shared set-up for the benchmark harness tests: a private copy of the
benchmark (manifest + benchmark/ without its cache) per test, so runs
on parallel workers never share a store or a trace directory, and the
tiny sizes a CPU run can hold."""

from __future__ import annotations

import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture
def bench_root(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), root / "benchmark",
        ignore=shutil.ignore_patterns(".cache", "__pycache__"),
    )
    return str(root)
