"""shard_map + Pallas: multi-chip keeps the fast ladder.

GSPMD cannot partition Mosaic custom calls, so round 1 forced the mesh
branch onto the ~3.6x-slower XLA ladder. shard_map sidesteps GSPMD —
the kernel runs per shard — so each chip keeps the VMEM-resident Pallas
ladder. These tests prove the combination on the CPU mesh:

* the SPI mesh branch (shard_map'd XLA on CPU, shard_map'd Pallas on a
  TPU backend) is covered by tests/test_mesh_verifier.py;
* here, the Pallas kernel itself runs INSIDE shard_map in interpret
  mode with a reduced 1-limb scan and must match the XLA ladder
  bit-for-bit — same formulas, same step order, so projective outputs
  are identical, not just equivalent (full-width interpret runs are
  in tests/test_pallas_path.py).

On real chips the full path is `python chip_smoke.py --chips 4`: a
4-device mesh verify of a 4096x4 chunk, held to construction and the
CPU reference.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from corda_tpu.crypto import ec, limbs as L, modmath as mm, refmath
from corda_tpu.crypto.curves import SECP256K1, SECP256R1
from corda_tpu.crypto.pallas_ec import wei_ladder_pallas
from corda_tpu.parallel import mesh as meshlib


@pytest.mark.slow
@pytest.mark.parametrize("curve", [SECP256R1, SECP256K1], ids=["p256", "k1"])
def test_shard_map_pallas_interpret_matches_xla_ladder(curve):
    rng = random.Random(9)
    B = 8
    u1s = [rng.randrange(1, 1 << 12) for _ in range(B)]
    u2s = [rng.randrange(1, 1 << 12) for _ in range(B)]
    qs = [
        refmath.wei_mul(curve, rng.randrange(1, curve.n), (curve.gx, curve.gy))
        for _ in range(B)
    ]
    u1 = jnp.asarray(L.ints_to_batch(u1s))
    u2 = jnp.asarray(L.ints_to_batch(u2s))
    qx = mm.to_mont(curve.fp, jnp.asarray(L.ints_to_batch([q[0] for q in qs])))
    qy = mm.to_mont(curve.fp, jnp.asarray(L.ints_to_batch([q[1] for q in qs])))

    mesh = meshlib.make_mesh(jax.devices()[:8])
    smapped = jax.shard_map(
        lambda a, b, c, d: wei_ladder_pallas(
            curve, a, b, c, d, block=1, interpret=True, limbs=1
        ),
        mesh=mesh,
        in_specs=(P(None, meshlib.BATCH_AXIS),) * 4,
        out_specs=(P(None, meshlib.BATCH_AXIS),) * 3,
        check_vma=False,
    )
    X, Y, Z = jax.block_until_ready(smapped(u1, u2, qx, qy))

    Q = ec.wei_affine_to_proj(curve.fp, qx, qy)
    Xr, Yr, Zr = ec.wei_double_scalar_mul(curve, u1, u2, Q, nbits=12)
    assert np.array_equal(np.asarray(X), np.asarray(Xr))
    assert np.array_equal(np.asarray(Y), np.asarray(Yr))
    assert np.array_equal(np.asarray(Z), np.asarray(Zr))


def test_mesh_kernel_is_shard_mapped_not_xla_fallback():
    """The mesh branch must build a shard_map'd kernel with the Pallas
    auto policy (use_pallas=None), not force the XLA ladder."""
    from corda_tpu.crypto import schemes
    from corda_tpu.crypto.batch_verifier import TpuBatchVerifier

    mesh = meshlib.make_mesh(jax.devices()[:8])
    v = TpuBatchVerifier(batch_sizes=(16,), mesh=mesh)
    fn = v._kernel(schemes.ECDSA_SECP256R1_SHA256, 16)
    assert fn is v._kernel(schemes.ECDSA_SECP256R1_SHA256, 16)  # cached
    # compiles + runs on the CPU mesh via shard_map (XLA inside shards
    # on this backend; Pallas on a TPU backend)
    from corda_tpu.crypto import encodings

    kp = schemes.generate_keypair(
        schemes.ECDSA_SECP256R1_SHA256, seed=42
    )
    msg = b"mesh"
    items = [(kp.public.data, kp.private.sign(msg), msg)] * 16
    packed, valid = encodings.stage_ecdsa_packed(SECP256R1, items, 16)
    packed = meshlib.shard_operand(mesh, packed, batch_axis=0)
    valid = meshlib.shard_operand(mesh, valid, batch_axis=-1)
    out = np.asarray(fn(packed=packed, valid_in=valid))
    assert out.all()


@pytest.mark.slow
def test_windowed_pallas_interpret_matches_xla():
    """The windowed Pallas kernel (the default TPU verify path) must
    match the windowed XLA function bit-for-bit — 1-limb reduced scan
    in interpret mode, same pattern as the plain-ladder test above."""
    from corda_tpu.crypto.pallas_ec import wei_ladder_windowed_pallas

    curve = SECP256R1
    rng = random.Random(31)
    B = 2
    u1s = [rng.randrange(1, 1 << 12) for _ in range(B)]
    u2s = [rng.randrange(1, 1 << 12) for _ in range(B)]
    qs = [
        refmath.wei_mul(curve, rng.randrange(1, curve.n), (curve.gx, curve.gy))
        for _ in range(B)
    ]
    u1 = jnp.asarray(L.ints_to_batch(u1s))
    u2 = jnp.asarray(L.ints_to_batch(u2s))
    qx = mm.to_mont(curve.fp, jnp.asarray(L.ints_to_batch([q[0] for q in qs])))
    qy = mm.to_mont(curve.fp, jnp.asarray(L.ints_to_batch([q[1] for q in qs])))
    X, Y, Z = jax.block_until_ready(
        wei_ladder_windowed_pallas(
            curve, u1, u2, qx, qy, block=2, interpret=True, limbs=1
        )
    )
    Q = ec.wei_affine_to_proj(curve.fp, qx, qy)
    Xr, Yr, Zr = ec.wei_double_scalar_mul_windowed(curve, u1, u2, Q, nbits=12)
    assert np.array_equal(np.asarray(X), np.asarray(Xr))
    assert np.array_equal(np.asarray(Y), np.asarray(Yr))
    assert np.array_equal(np.asarray(Z), np.asarray(Zr))


@pytest.mark.slow
def test_windowed_ed_pallas_interpret_matches_xla():
    from corda_tpu.crypto.curves import ED25519
    from corda_tpu.crypto.pallas_ec import ed_ladder_windowed_pallas

    curve = ED25519
    rng = random.Random(37)
    B = 2
    ss = [rng.randrange(1, 1 << 12) for _ in range(B)]
    ks = [rng.randrange(1, 1 << 12) for _ in range(B)]
    As = [
        refmath.ed_mul(curve, rng.randrange(1, curve.L), (curve.gx, curve.gy))
        for _ in range(B)
    ]
    s = jnp.asarray(L.ints_to_batch(ss))
    k = jnp.asarray(L.ints_to_batch(ks))
    ax = mm.to_mont(curve.fp, jnp.asarray(L.ints_to_batch([a[0] for a in As])))
    ay = mm.to_mont(curve.fp, jnp.asarray(L.ints_to_batch([a[1] for a in As])))
    X, Y, Z, T = jax.block_until_ready(
        ed_ladder_windowed_pallas(
            curve, s, k, ax, ay, block=2, interpret=True, limbs=1
        )
    )
    A = ec.ed_affine_to_ext(curve.fp, ax, ay)
    Xr, Yr, Zr, Tr = ec.ed_double_scalar_mul_windowed(curve, s, k, A, nbits=12)
    assert np.array_equal(np.asarray(X), np.asarray(Xr))
    assert np.array_equal(np.asarray(Y), np.asarray(Yr))
    assert np.array_equal(np.asarray(Z), np.asarray(Zr))
    assert np.array_equal(np.asarray(T), np.asarray(Tr))
