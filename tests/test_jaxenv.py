"""Process JAX environment: where the compile cache and the AOT store
live, and the guard that keeps a TPU-claiming path off other backends.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from corda_tpu.crypto import aot_store
from corda_tpu.utils import jaxenv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    prior = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prior)


def test_outer_cache_dir_is_the_only_one(tmp_path, monkeypatch,
                                         restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("CORDA_TPU_AOT_DIR", raising=False)
    jaxenv.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jaxenv.compile_cache_dir() == str(tmp_path)
    assert os.path.dirname(aot_store.store_dir()) == str(tmp_path)


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("CORDA_TPU_AOT_DIR", raising=False)
    cache = jaxenv.compile_cache_dir()
    assert cache == os.path.join(REPO, ".jax_cache")
    assert cache == jaxenv.compile_cache_dir()   # no pid, time or temp name
    aot = aot_store.store_dir()
    assert aot == os.path.join(cache, "corda_tpu_aot")
    out = subprocess.run(
        ["git", "check-ignore", "-q", os.path.join(cache, "x")],
        cwd=REPO, capture_output=True,
    )
    if out.returncode not in (0, 1):
        pytest.skip("not a git checkout")
    assert out.returncode == 0, ".jax_cache is not git-ignored"


def test_require_tpu_refuses_an_unpinned_cpu(monkeypatch):
    assert jax.devices()[0].platform == "cpu"
    # conftest pins the CPU on purpose, so the pinned form passes
    jaxenv.require_tpu("test", allow_pinned_cpu=True)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        jaxenv.require_tpu("test")
    monkeypatch.setattr(jaxenv, "cpu_pinned", lambda: False)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        jaxenv.require_tpu("test", allow_pinned_cpu=True)


def test_tpu_node_refuses_to_boot_off_tpu(tmp_path, monkeypatch):
    from corda_tpu.node.config import NodeConfig
    from corda_tpu.node.node import Node

    # pinned (as the suite is): a "tpu" node boots on the CPU on purpose
    node = Node(NodeConfig(name="Pinned", base_dir=str(tmp_path / "p")))
    node.stop()
    monkeypatch.setattr(jaxenv, "cpu_pinned", lambda: False)
    with pytest.raises(RuntimeError, match="verifier_backend='tpu'"):
        Node(NodeConfig(name="Unpinned", base_dir=str(tmp_path / "u")))
    # a cpu node never asks
    Node(NodeConfig(name="Cpu", base_dir=str(tmp_path / "c"),
                    verifier_backend="cpu")).stop()


def _bench(code: str, **env):
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=300,
    )


def test_bench_metric_fails_without_a_tpu():
    out = _bench(
        "import sys; sys.argv = ['bench.py']; import bench; bench.main()",
        BENCH_METRIC="p256",
    )
    assert out.returncode == 3, out.stderr[-2000:]
    assert "needs a TPU" in out.stderr
    assert '"metric"' not in out.stdout


def test_bench_parent_stays_off_jax():
    """The default run's parent runs metric children one at a time and
    never imports jax, so each child owns the chip."""
    out = _bench(
        "import sys; sys.argv = ['bench.py']; import bench\n"
        "ran = []\n"
        "bench._run_child = lambda m, env, timeout: ran.append(m) or True\n"
        "bench.main()\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
        "assert ran[-1] == 'p256' and len(ran) > 1, ran\n"
        "print('ok')",
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("ok")
