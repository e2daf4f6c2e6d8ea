"""Process-level JAX environment setup shared by tests, the node and
the chip entry points.

Three concerns:

* provisioning virtual host devices for CPU test meshes (XLA reads
  --xla_force_host_platform_device_count at CPU-client creation, so
  this must run before jax initialises a backend);
* the persistent compile cache: `JAX_COMPILATION_CACHE_DIR` when it is
  set (then it is the ONLY cache directory), else one fixed directory
  inside the checkout — the path is part of what makes the cache hit,
  so it never carries a temp name, a pid or a time;
* the device guard: a path that claims the TPU fails on any other
  backend unless the process was pinned to the CPU on purpose.

Importing this module does NOT import jax — callers control ordering.
"""

from __future__ import annotations

import os
import re

# <repo>/corda_tpu/utils/jaxenv.py -> <repo>
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

_COUNT_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")


def force_host_device_count(n: int) -> None:
    """Ensure XLA_FLAGS requests >= n virtual host (CPU) devices.

    Raises an existing smaller count rather than silently keeping it;
    must run before the CPU backend initialises.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = _COUNT_RE.search(flags)
    if m is None:
        flags = (flags + f" --xla_force_host_platform_device_count={n}").strip()
    elif int(m.group(1)) < n:
        flags = _COUNT_RE.sub(
            f"--xla_force_host_platform_device_count={n}", flags
        )
    os.environ["XLA_FLAGS"] = flags


def compile_cache_dir() -> str:
    """`JAX_COMPILATION_CACHE_DIR` when set, else `<repo>/.jax_cache`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_compile_cache() -> None:
    """Point jax at the persistent compile cache (idempotent)."""
    import jax

    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")


def cpu_pinned() -> bool:
    """True when the process was explicitly pinned to the CPU backend
    (`JAX_PLATFORMS=cpu` or the `jax_platforms` config, as
    tests/conftest.py does)."""
    import jax

    platforms = jax.config.jax_platforms or os.environ.get(
        "JAX_PLATFORMS", ""
    )
    names = {p.strip() for p in platforms.split(",") if p.strip()}
    return names == {"cpu"}


def require_tpu(what: str, allow_pinned_cpu: bool = False) -> None:
    """Raise unless jax's first device is a TPU. `allow_pinned_cpu`
    lets a process pinned to the CPU on purpose through (tests); an
    unpinned process that merely found no TPU never passes."""
    import jax

    platform = jax.devices()[0].platform
    if platform == "tpu" or (allow_pinned_cpu and cpu_pinned()):
        return
    raise RuntimeError(
        f"{what} needs a TPU but jax's first device is {platform!r}; "
        "set JAX_PLATFORMS=cpu to run on the CPU on purpose"
    )
