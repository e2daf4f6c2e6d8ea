"""Pallas TPU kernel for the ECDSA double-scalar ladder.

The ladder (R = u1*G + u2*Q, 264 complete doublings + 264 selected
adds) is ~95% of signature-verification compute. Under plain XLA each
point operation materialises its [22, B] limb intermediates to HBM —
at B=32k that is hundreds of GB of HBM traffic per batch and the
program is bandwidth-bound (measured ~17k verifies/s on one v5e). This
kernel runs the ENTIRE ladder for a block of the batch inside VMEM:
the grid splits the batch into blocks of 128 signatures (~0.5 MB of
live state per block; swept 64/128/256/512 on a v5e — 128 wins at 62k
vs 49k verifies/s for 256), and all 6,000+ field multiplies per
signature happen without leaving on-chip memory.

The field/point arithmetic is the same code XLA traces
(modmath/ec.py) — Pallas kernels are jax-traceable functions, so the
Montgomery multiply, carry rounds and the complete RCB15 addition all
reuse the exact implementations the CPU-mesh tests verify bit-exactly.

Bit scan: scalars arrive as canonical [22, B] radix-2^12 digit arrays,
handed to the kernel as [22, 1, B] so a digit row is read by a dynamic
index on the untiled leading axis; the outer `fori_loop` walks limbs
MSB-first and the inner one the 12 bits (or three 4-bit windows) of a
row, so the ladder body traces once. Scanning all 264 limb-bits (vs
256) costs +3% point ops — scalars are < 2^256 so the top bits add the
identity, which the complete formulas absorb.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .curves import EdwardsCurve, WeierstrassCurve
from .limbs import LIMB_BITS, NLIMB, R_BITS
from .modmath import const_batch, mont_one, scalar_consts_mode
from . import ec

DEFAULT_BLOCK = 128


def _block_or_default(block) -> int:
    """Resolve the batch block: explicit arg, else CORDA_TPU_PALLAS_BLOCK,
    else DEFAULT_BLOCK (read per call, not frozen at import — and kept
    out of public signature defaults so the recorded API surface is not
    environment-dependent)."""
    if block is not None:
        return block
    return int(os.environ.get("CORDA_TPU_PALLAS_BLOCK", str(DEFAULT_BLOCK)))


def use_pallas_ladder(use_pallas=None) -> bool:
    """Shared Pallas-vs-XLA dispatch policy for every scheme's ladder:
    Pallas on a real TPU backend, XLA elsewhere; `use_pallas=False`
    forces XLA; CORDA_TPU_NO_PALLAS=1 disables globally. Under meshes
    the SPI wraps the kernel in shard_map (batch_verifier._kernel), so
    the auto policy keeps Pallas per shard — GSPMD alone could not
    partition the Mosaic custom call."""
    if use_pallas is not None:
        return bool(use_pallas)
    if os.environ.get("CORDA_TPU_NO_PALLAS"):
        return False
    return jax.default_backend() == "tpu"


# Default ladder per curve family (round-3 same-link A/B at the
# production shape, 16384/chunk-4096 through the SPI, BASELINE.md):
# p256 windowed 55.2k vs plain 48.9k; secp256k1 windowed 50.6k vs
# plain 54.4k; ed25519 windowed 35.7k vs plain 42.5k. The w=4 tables
# only pay for themselves on p256 — on k1/ed25519 the per-block
# Q-table build and VMEM pressure cost more than the saved doublings.
_WINDOWED_DEFAULT = {"p256": True, "k1": False, "ed25519": False}


def use_windowed_ladder(curve_tag: str = "p256") -> bool:
    """w=4 fixed-window ladder vs the plain bit ladder, chosen per
    curve family (`curve_tag` in {"p256", "k1", "ed25519"}).
    CORDA_TPU_WINDOWED=0/1 forces ALL curves off/on (the selfcheck and
    parity rigs exercise both paths this way); unset uses the measured
    per-curve defaults above."""
    forced = os.environ.get("CORDA_TPU_WINDOWED")
    if forced is not None:
        return forced != "0"
    # unknown tags get the PLAIN ladder: the A/B showed windowed loses
    # on every measured curve but p256, so a mistagged or future curve
    # should land on the safe default, not the p256 special case
    return _WINDOWED_DEFAULT.get(curve_tag, False)


LANE = 128


def _fit_block(batch: int, block: int) -> tuple[int, int]:
    """(padded batch, lane block) for a [22, batch] ladder. A batch no
    wider than the block runs as ONE block spanning it (a block equal
    to the array dimension is always legal). Otherwise the block is
    rounded down to a multiple of the 128-lane tile — Mosaic refuses
    any other lane block — and the batch pads up to whole blocks, so
    an odd batch never falls back to one VMEM-blowing block."""
    if batch <= block:
        return batch, batch
    block = max(LANE, block - block % LANE)
    return -(-batch // block) * block, block


# the ladder's kernel name per curve: a profiler trace names each
# ladder's custom call by it (`ladder_<curve>_<bit|windowed>`)
_CURVE_TAG = {"secp256r1": "p256", "secp256k1": "k1", "ed25519": "ed25519"}


def _ladder_name(curve, windowed: bool) -> str:
    tag = _CURVE_TAG.get(curve.name, curve.name)
    return f"ladder_{tag}_{'windowed' if windowed else 'bit'}"


def _ladder_call(kernel, scalars, points, batch, padded, block, n_out,
                 interpret, name):
    """pallas_call `name` over [22, padded] operands in [22, block]
    tiles; pads the lane axis on the way in and slices it off on the
    way out (padding lanes compute garbage on zero inputs, never read).
    The two scalar digit arrays enter as [22, 1, padded], so the kernel
    reads a digit row by a dynamic index on the untiled leading axis
    (see _scan_limbs)."""
    pad = ((0, 0), (0, padded - batch))
    if padded != batch:
        scalars = [jnp.pad(x, pad) for x in scalars]
        points = [jnp.pad(x, pad) for x in points]
    scalars = [x.reshape(NLIMB, 1, padded) for x in scalars]
    digit_spec = pl.BlockSpec((NLIMB, 1, block), lambda i: (0, 0, i))
    spec = pl.BlockSpec((NLIMB, block), lambda i: (0, i))
    shape = jax.ShapeDtypeStruct((NLIMB, padded), jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid=(padded // block,),
        in_specs=[digit_spec] * len(scalars) + [spec] * len(points),
        out_specs=(spec,) * n_out,
        out_shape=(shape,) * n_out,
        interpret=interpret,
        name=name,
    )(*scalars, *points)
    if padded != batch:
        out = tuple(o[:, :batch] for o in out)
    return out


def _scan_limbs(s1_ref, s2_ref, limbs, acc, limb_step):
    """Walk the digit rows limbs-1 .. 0 (MSB first), folding
    `limb_step(row1, row2, acc)` over them. The digit refs are
    [NLIMB, 1, block]: Mosaic has no dynamic sublane indexing, but a
    dynamic index on the untiled leading axis is a plain address
    offset, so the walk is a fori_loop and the ladder body traces once
    rather than once per limb (22x less to trace and compile)."""

    def body(i, acc):
        limb = limbs - 1 - i
        with scalar_consts_mode():
            return limb_step(s1_ref[limb, 0, :], s2_ref[limb, 0, :], acc)

    return lax.fori_loop(0, limbs, body, acc)


def _g_mont_limbs(curve: WeierstrassCurve, batch: int):
    """Generator affine coords in Montgomery form, as device constants
    (host-computed python ints — no to_mont on device)."""
    R = 1 << R_BITS
    gx = const_batch((curve.gx * R) % curve.p, batch)
    gy = const_batch((curve.gy * R) % curve.p, batch)
    return gx, gy


def wei_ladder_pallas(
    curve: WeierstrassCurve,
    u1,                 # [22, B] canonical standard-domain scalar digits
    u2,                 # [22, B]
    qx_m,               # [22, B] Montgomery-domain affine Q (bounded limbs)
    qy_m,               # [22, B]
    block: int | None = None,
    interpret: bool = False,
    limbs: int = NLIMB,
):
    """R = u1*G + u2*Q, batched; returns Montgomery projective (X, Y, Z).

    `limbs` < NLIMB scans only the low `limbs` digit rows (scalars must
    be < 2^(12*limbs)) — a test-only reduction that makes interpret-mode
    runs of the full kernel tractable on CPU; production always scans
    all NLIMB rows."""
    batch = u1.shape[1]
    padded, block = _fit_block(batch, _block_or_default(block))

    def kernel(u1_ref, u2_ref, qx_ref, qy_ref, x_ref, y_ref, z_ref):
        # scalar-consts mode: Pallas rejects captured array constants,
        # so all field constants rebuild from python ints (modmath)
        with scalar_consts_mode():
            ctx = curve.fp
            Q = ec.wei_affine_to_proj(ctx, qx_ref[:], qy_ref[:])
            gx, gy = _g_mont_limbs(curve, block)
            G = (gx, gy, mont_one(ctx, block))
            GQ = ec.wei_add(curve, G, Q)
            inf = ec.wei_infinity(ctx, block)

            # per limb row, the 12-bit walk is a fori_loop (shift by a
            # traced amount is a plain VPU op)
            def limb_step(row1, row2, acc):
                def step(j, acc):
                    bit = LIMB_BITS - 1 - j
                    with scalar_consts_mode():
                        acc = ec.wei_add(curve, acc, acc)
                        bg = ((row1 >> bit) & 1).astype(jnp.bool_)
                        bq = ((row2 >> bit) & 1).astype(jnp.bool_)
                        lo = ec.wei_select(bg, G, inf)
                        hi = ec.wei_select(bg, GQ, Q)
                        P = ec.wei_select(bq, hi, lo)
                        return ec.wei_add(curve, acc, P)

                return lax.fori_loop(0, LIMB_BITS, step, acc)

            X, Y, Z = _scan_limbs(u1_ref, u2_ref, limbs, inf, limb_step)
            x_ref[:] = X
            y_ref[:] = Y
            z_ref[:] = Z

    return _ladder_call(
        kernel, (u1, u2), (qx_m, qy_m), batch, padded, block, 3, interpret,
        _ladder_name(curve, windowed=False),
    )


def wei_ladder_windowed_pallas(
    curve: WeierstrassCurve,
    u1,                 # [22, B] canonical standard-domain scalar digits
    u2,                 # [22, B]
    qx_m,               # [22, B] Montgomery-domain affine Q
    qy_m,               # [22, B]
    block: int | None = None,
    interpret: bool = False,
    limbs: int = NLIMB,
):
    """Fixed-window (w=4) variant of wei_ladder_pallas: per 4-bit
    window, 4 complete doublings + one add from the constant G-multiple
    table + one add from the per-block Q-multiple table (built once,
    ~14 adds, amortised over 66 windows) — 6 point ops per 4 bits vs
    the plain ladder's 8. A 12-bit limb row yields exactly three
    windows, so the outer unrolled limb walk stays identical; the inner
    fori_loop runs 3 window steps with traced shifts.

    VMEM: the Q table adds 16 x 3 x [22, block] int32 (~1.4 MB at block
    128) on top of the ladder state; G entries are scalar consts."""
    batch = u1.shape[1]
    padded, block = _fit_block(batch, _block_or_default(block))

    def kernel(u1_ref, u2_ref, qx_ref, qy_ref, x_ref, y_ref, z_ref):
        with scalar_consts_mode():
            ctx = curve.fp
            Q = ec.wei_affine_to_proj(ctx, qx_ref[:], qy_ref[:])
            inf = ec.wei_infinity(ctx, block)
            g_tab, q_tab = ec.wei_window_tables(curve, Q, block, w=4)

            def limb_step(row1, row2, acc):
                def win_step(j, acc):
                    shift = LIMB_BITS - 4 - 4 * j      # 8, 4, 0
                    with scalar_consts_mode():
                        for _ in range(4):
                            acc = ec.wei_add(curve, acc, acc)
                        d1 = (row1 >> shift) & 15
                        d2 = (row2 >> shift) & 15
                        acc = ec.wei_add(
                            curve, acc, ec.wei_table_select(d1, g_tab)
                        )
                        return ec.wei_add(
                            curve, acc, ec.wei_table_select(d2, q_tab)
                        )

                return lax.fori_loop(0, LIMB_BITS // 4, win_step, acc)

            X, Y, Z = _scan_limbs(u1_ref, u2_ref, limbs, inf, limb_step)
            x_ref[:] = X
            y_ref[:] = Y
            z_ref[:] = Z

    return _ladder_call(
        kernel, (u1, u2), (qx_m, qy_m), batch, padded, block, 3, interpret,
        _ladder_name(curve, windowed=True),
    )


def ed_ladder_windowed_pallas(
    curve: EdwardsCurve,
    s,                  # [22, B] canonical signature-scalar digits
    k,                  # [22, B] canonical digest-scalar digits
    ax_m,               # [22, B] Montgomery-domain affine point (e.g. -A)
    ay_m,               # [22, B]
    block: int | None = None,
    interpret: bool = False,
    limbs: int = NLIMB,
):
    """w=4 fixed-window variant of ed_ladder_pallas (same structure as
    wei_ladder_windowed_pallas: per window 4 unified doublings + one
    add from the constant base-point table + one from the per-block
    A-multiple table)."""
    batch = s.shape[1]
    padded, block = _fit_block(batch, _block_or_default(block))

    def kernel(s_ref, k_ref, ax_ref, ay_ref, x_ref, y_ref, z_ref, t_ref):
        with scalar_consts_mode():
            ctx = curve.fp
            A = ec.ed_affine_to_ext(ctx, ax_ref[:], ay_ref[:])
            ident = ec.ed_identity(ctx, block)
            b_tab, a_tab = ec.ed_window_tables(curve, A, block, w=4)

            def limb_step(row_s, row_k, acc):
                def win_step(j, acc):
                    shift = LIMB_BITS - 4 - 4 * j      # 8, 4, 0
                    with scalar_consts_mode():
                        for _ in range(4):
                            acc = ec.ed_add(curve, acc, acc)
                        d1 = (row_s >> shift) & 15
                        d2 = (row_k >> shift) & 15
                        acc = ec.ed_add(
                            curve, acc, ec.ed_table_select(d1, b_tab)
                        )
                        return ec.ed_add(
                            curve, acc, ec.ed_table_select(d2, a_tab)
                        )

                return lax.fori_loop(0, LIMB_BITS // 4, win_step, acc)

            X, Y, Z, T = _scan_limbs(s_ref, k_ref, limbs, ident, limb_step)
            x_ref[:] = X
            y_ref[:] = Y
            z_ref[:] = Z
            t_ref[:] = T

    return _ladder_call(
        kernel, (s, k), (ax_m, ay_m), batch, padded, block, 4, interpret,
        _ladder_name(curve, windowed=True),
    )


def ed_ladder_pallas(
    curve: EdwardsCurve,
    s,                  # [22, B] canonical signature-scalar digits
    k,                  # [22, B] canonical digest-scalar digits
    ax_m,               # [22, B] Montgomery-domain affine point (e.g. -A)
    ay_m,               # [22, B]
    block: int | None = None,
    interpret: bool = False,
    limbs: int = NLIMB,
):
    """R = s*B + k*A on the twisted Edwards curve (B = base point),
    VMEM-resident per block like the Weierstrass ladder; returns
    extended coordinates (X, Y, Z, T) in Montgomery domain. `limbs`
    as in wei_ladder_pallas (test-only reduced scan)."""
    batch = s.shape[1]
    padded, block = _fit_block(batch, _block_or_default(block))

    R = 1 << R_BITS

    def kernel(s_ref, k_ref, ax_ref, ay_ref, x_ref, y_ref, z_ref, t_ref):
        with scalar_consts_mode():
            ctx = curve.fp
            A = ec.ed_affine_to_ext(ctx, ax_ref[:], ay_ref[:])
            bx = const_batch((curve.gx * R) % curve.p, block)
            by = const_batch((curve.gy * R) % curve.p, block)
            Bp = ec.ed_affine_to_ext(ctx, bx, by)
            BA = ec.ed_add(curve, Bp, A)
            ident = ec.ed_identity(ctx, block)

            def limb_step(row_s, row_k, acc):
                def step(j, acc):
                    bit = LIMB_BITS - 1 - j
                    with scalar_consts_mode():
                        acc = ec.ed_add(curve, acc, acc)
                        bs = ((row_s >> bit) & 1).astype(jnp.bool_)
                        bk = ((row_k >> bit) & 1).astype(jnp.bool_)
                        lo = ec.ed_select(bs, Bp, ident)
                        hi = ec.ed_select(bs, BA, A)
                        P = ec.ed_select(bk, hi, lo)
                        return ec.ed_add(curve, acc, P)

                return lax.fori_loop(0, LIMB_BITS, step, acc)

            X, Y, Z, T = _scan_limbs(s_ref, k_ref, limbs, ident, limb_step)
            x_ref[:] = X
            y_ref[:] = Y
            z_ref[:] = Z
            t_ref[:] = T

    return _ladder_call(
        kernel, (s, k), (ax_m, ay_m), batch, padded, block, 4, interpret,
        _ladder_name(curve, windowed=False),
    )
