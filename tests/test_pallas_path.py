"""Pallas-mode field arithmetic: scalar-consts tracing path.

The Pallas ladder kernel (crypto/pallas_ec.py) traces the SAME
modmath/ec code as the XLA path, but under `scalar_consts_mode`, which
swaps constant handling (python-int rebuilds instead of embedded
arrays / the int8 MXU matmul) and scatter-free accumulation (Mosaic has
no scatter-add / value dynamic-slice lowering). These tests pin the two
tracing modes to identical values on CPU, and run the ladders whole in
interpret mode against the pure-python reference; on the chip,
chip_smoke.py checks the kernels bit-exact against the CPU reference.
"""

import random

import jax.numpy as jnp
import pytest

from corda_tpu.crypto import ec, modmath as mm
from corda_tpu.crypto import limbs as L
from corda_tpu.crypto.curves import SECP256K1, SECP256R1

CURVES = {"p256": SECP256R1, "k1": SECP256K1}


def _rand_batch(rng, n, bound):
    return L.ints_to_batch([rng.randrange(1, bound) for _ in range(n)])


@pytest.mark.parametrize("name", sorted(CURVES))
def test_scalar_consts_mode_matches_default(name):
    curve = CURVES[name]
    ctx = curve.fp
    rng = random.Random(42)
    a = jnp.asarray(_rand_batch(rng, 8, curve.p))
    b = jnp.asarray(_rand_batch(rng, 8, curve.p))

    def run():
        am, bm = mm.to_mont(ctx, a), mm.to_mont(ctx, b)
        out = {
            "mul": mm.mont_mul(ctx, am, bm),
            "mulc": mm.mont_mul_const(ctx, am, ctx.r2_limbs),
            "sub": mm.sub_mod(ctx, mm.add_mod(ctx, am, bm), bm),
            "one": mm.mont_one(ctx, 8),
            "const": mm.const_batch(12345678901234567890, 8),
        }
        return {k: mm.canon(ctx, v, 16) for k, v in out.items()}

    plain = run()
    with mm.scalar_consts_mode():
        scalar = run()
    for key in plain:
        assert bool(jnp.all(plain[key] == scalar[key])), key


def test_scalar_consts_mode_point_add_matches():
    curve = SECP256R1
    ctx = curve.fp
    rng = random.Random(7)
    from corda_tpu.crypto import refmath

    d1, d2 = rng.randrange(2, curve.n), rng.randrange(2, curve.n)
    P1 = refmath.wei_mul(curve, d1, (curve.gx, curve.gy))
    P2 = refmath.wei_mul(curve, d2, (curve.gx, curve.gy))
    x1 = mm.to_mont(ctx, jnp.asarray(L.ints_to_batch([P1[0]] * 4)))
    y1 = mm.to_mont(ctx, jnp.asarray(L.ints_to_batch([P1[1]] * 4)))
    x2 = mm.to_mont(ctx, jnp.asarray(L.ints_to_batch([P2[0]] * 4)))
    y2 = mm.to_mont(ctx, jnp.asarray(L.ints_to_batch([P2[1]] * 4)))

    def run():
        A = ec.wei_affine_to_proj(ctx, x1, y1)
        B = ec.wei_affine_to_proj(ctx, x2, y2)
        X, Y, Z = ec.wei_add(curve, A, B)
        return [mm.canon(ctx, v, 16) for v in (X, Y, Z)]

    plain = run()
    with mm.scalar_consts_mode():
        scalar = run()
    for p, s in zip(plain, scalar):
        assert bool(jnp.all(p == s))


def test_pallas_routing_flag(monkeypatch):
    from corda_tpu.crypto.ecdsa import _use_pallas_ladder

    # CPU test mesh: never the pallas path
    assert _use_pallas_ladder() is False
    monkeypatch.setenv("CORDA_TPU_NO_PALLAS", "1")
    assert _use_pallas_ladder() is False


def _affine(fp, p_mod, X, Y, Z):
    """Montgomery projective limb arrays -> affine python ints."""
    xs, ys, zs = (L.batch_to_ints(mm.from_mont(fp, v)) for v in (X, Y, Z))
    return [
        (x * pow(z, -1, p_mod) % p_mod, y * pow(z, -1, p_mod) % p_mod)
        for x, y, z in zip(xs, ys, zs)
    ]


def _ladder_case(name):
    from corda_tpu.crypto import pallas_ec, refmath
    from corda_tpu.crypto.curves import ED25519

    rng = random.Random(53)
    B = 2
    if name == "ed25519-plain":
        curve = ED25519
        pts = [refmath.ed_mul(curve, rng.randrange(1, curve.L),
                              (curve.gx, curve.gy)) for _ in range(B)]
        order, ladder = curve.L, pallas_ec.ed_ladder_pallas

        def want(a, b, P):
            return refmath.ed_add(
                curve, refmath.ed_mul(curve, a, (curve.gx, curve.gy)),
                refmath.ed_mul(curve, b, P),
            )
    else:
        curve = SECP256K1 if name == "k1-plain" else SECP256R1
        pts = [refmath.wei_mul(curve, rng.randrange(1, curve.n),
                               (curve.gx, curve.gy)) for _ in range(B)]
        order = curve.n
        ladder = (pallas_ec.wei_ladder_windowed_pallas
                  if name == "p256-windowed" else pallas_ec.wei_ladder_pallas)

        def want(a, b, P):
            return refmath.wei_add(
                curve, refmath.wei_mul(curve, a, (curve.gx, curve.gy)),
                refmath.wei_mul(curve, b, P),
            )
    a_s = [rng.randrange(1, order) for _ in range(B)]
    b_s = [rng.randrange(1, order) for _ in range(B)]
    args = [jnp.asarray(L.ints_to_batch(v)) for v in (a_s, b_s)] + [
        mm.to_mont(curve.fp, jnp.asarray(L.ints_to_batch([p[i] for p in pts])))
        for i in (0, 1)
    ]
    expect = [want(a, b, P) for a, b, P in zip(a_s, b_s, pts)]
    return curve, ladder, args, expect


@pytest.mark.slow
@pytest.mark.parametrize(
    "name", ["p256-windowed", "p256-plain", "k1-plain", "ed25519-plain"]
)
def test_full_width_ladder_interpret_matches_reference(name):
    """Every ladder the verifier can pick, all 22 limb rows of 256-bit
    scalars, against the pure-python reference (about a minute each in
    interpret mode; tier-1 compiles the ladders in tests/
    test_tpu_compile.py and runs them through chip_smoke's phases)."""
    curve, ladder, args, expect = _ladder_case(name)
    out = ladder(curve, *args, block=2, interpret=True)
    got = _affine(curve.fp, curve.p, *out[:3])
    assert got == expect
