"""Compile the Pallas ladders for a described TPU v5e chip.

Interpret-mode tests (tests/test_pallas_path.py, the slow-marked
tests/test_shard_map_pallas.py) never hand a kernel to Mosaic, so a
block the chip's compiler refuses — a lane block off the 128 tile, too
much VMEM — would first show on the chip. Here every ladder the
verifier can pick compiles for a `v5e:2x2` topology described in this
process, at the production batch (4096) and block (128). `limbs=1`
keeps each compile to seconds: the scan length shrinks, the block
shape and VMEM tiles stay those of production. Full-width compiles
(~90 s each) are the builder's rehearsal, not tier-1.

The topology is described inside a module fixture, never at import:
only one process may load libtpu, and under pytest-xdist every worker
imports this file.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from corda_tpu.crypto import pallas_ec
from corda_tpu.crypto.curves import ED25519, SECP256K1, SECP256R1

BATCH = 4096
BLOCK = 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prior)
        cc.reset_cache()


LADDERS = {
    "p256-windowed": partial(
        pallas_ec.wei_ladder_windowed_pallas, SECP256R1
    ),
    "p256-plain": partial(pallas_ec.wei_ladder_pallas, SECP256R1),
    "k1-plain": partial(pallas_ec.wei_ladder_pallas, SECP256K1),
    "ed25519-plain": partial(pallas_ec.ed_ladder_pallas, ED25519),
}


@pytest.mark.parametrize("name", sorted(LADDERS))
def test_ladder_compiles_for_v5e(name, one_chip, no_persistent_cache):
    limbs = jax.ShapeDtypeStruct((22, BATCH), jnp.int32, sharding=one_chip)
    fn = partial(LADDERS[name], block=BLOCK, limbs=1)
    compiled = jax.jit(fn).lower(limbs, limbs, limbs, limbs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_odd_batch_pads_to_lane_tiles(one_chip, no_persistent_cache):
    """An odd batch (no 128-multiple divisor) pads to whole lane tiles
    instead of a block Mosaic refuses."""
    assert pallas_ec._fit_block(6000, 128) == (6016, 128)
    assert pallas_ec._fit_block(96, 128) == (96, 96)
    assert pallas_ec._fit_block(4096, 200) == (4096, 128)
    odd = jax.ShapeDtypeStruct((22, 200), jnp.int32, sharding=one_chip)
    fn = partial(pallas_ec.wei_ladder_pallas, SECP256R1, block=BLOCK, limbs=1)
    compiled = jax.jit(fn).lower(odd, odd, odd, odd).compile()
    assert "tpu_custom_call" in compiled.as_text()
