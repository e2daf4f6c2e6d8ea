"""Test configuration: pin the CPU backend with 8 virtual devices.

The live jax config is set before any backend initialises, so the
whole suite runs on the CPU on purpose (this pin is what lets a
`verifier_backend="tpu"` node boot here, see utils/jaxenv.require_tpu).
Multi-chip sharding paths run on the virtual CPU mesh; tests never
touch a real TPU (tests/test_tpu_compile.py compiles for a described
one). Chip runs go through `python chip_smoke.py [--chips 4]`.
"""

from corda_tpu.utils import jaxenv

jaxenv.force_host_device_count(8)

import jax

jax.config.update("jax_platforms", "cpu")

# persistent XLA compile cache (JAX_COMPILATION_CACHE_DIR, else
# <repo>/.jax_cache): the EC kernels take 20-200 s to compile per
# (shape, backend) and dominate suite wall time on fresh processes
jaxenv.enable_compile_cache()
