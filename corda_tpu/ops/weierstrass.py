"""Batched ECDSA verification over short-Weierstrass curves on device.

Covers the reference's ECDSA_SECP256K1_SHA256 and ECDSA_SECP256R1_SHA256
schemes (reference Crypto.kt:91,105; verify dispatch Crypto.kt:473-496 via
BouncyCastle). TPU-first design notes:

- Projective (X:Y:Z) coordinates with the *complete* addition law of
  Renes–Costello–Batina (EuroCrypt 2016, "Complete addition formulas for
  prime order elliptic curves", Algorithm 1, arbitrary a, b3 = 3b). Complete
  ⇒ identity/doubling/inverse edge cases all take the same straight-line
  code — no data-dependent branches, exactly what SIMD batching and XLA
  tracing want. Both NIST-style (a=-3) and secp256k1 (a=0) run through the
  same kernel with different curve constants.
- Scalars/bit ladders and field limbs as in ops/field.py; `lax.scan` keeps
  graphs one-iteration-sized.

ECDSA verify (SEC 1 v2 §4.1.4): with e = H(m) as int, w = s⁻¹ mod n,
u1 = e·w, u2 = r·w (host, cheap), accept iff X = [u1]G + [u2]Q ≠ ∞ and
x(X) ≡ r (mod n). x ≡ r (mod n) is checked as x ∈ {r, r + n} (the only
candidates with x < p, r < n < p), with the r+n candidate host-validated;
the affine check X/Z == r_cand is done projectively as X == r_cand·Z.
"""
from __future__ import annotations

import functools
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.crypto.ecmath import (SECP256K1, SECP256K1_BETA, SECP256R1,
                                 WeierstrassCurve, _bits2int, glv_decompose)
from . import field as F

CURVES = {"secp256k1": SECP256K1, "secp256r1": SECP256R1}


def _const(v: int, p: int) -> jnp.ndarray:
    return jnp.asarray(F.to_limbs(v % p))


def identity(shape) -> tuple:
    """Projective identity (0 : 1 : 0)."""
    z = jnp.zeros(shape + (F.NLIMB,), dtype=jnp.uint64)
    return (z, z.at[..., 0].set(1), z)


def _select4(idx, points):
    """4-way batched point select: idx (B,) in [0,4) over 4 projective
    triples → one triple (binary tree of two-way selects per coordinate)."""
    return tuple(
        F.select(idx == 3, c3,
                 F.select(idx == 2, c2, F.select(idx == 1, c1, c0)))
        for c0, c1, c2, c3 in zip(*points))


def select_tree(table, idx):
    """16-way batched point select over a 16-entry table of coordinate
    tuples: fold by index bit (LSB first) — a binary tree of 15 two-way
    selects per coordinate.  (A flat masked-sum over a stacked table is
    HBM-bound and costs more, a dead end of an early round; u32-downcasting
    the tree was measured FLAT on v5e.)  Shared by the k1 hybrid ladder,
    the r1 split ladder, and the ed25519 split ladder."""
    level = table
    for j in range(4):
        b = ((idx >> j) & 1).astype(jnp.bool_)
        level = [tuple(F.select(b, hi_c, lo_c)
                       for lo_c, hi_c in zip(lo, hi))
                 for lo, hi in zip(level[0::2], level[1::2])]
    return level[0]


def _points_to_limbs(col):
    """Affine host points [(x, y)] → projective limb triple with Z = 1.
    Ships u16 (canonical 16-bit limbs); kernels upcast on device — u64 on
    the wire was 4x the transfer bytes for no information."""
    px, py = _points_to_limbs_affine(col)
    pz = jnp.zeros_like(px).at[..., 0].set(1)
    return (px, py, pz)


def _points_to_limbs_affine(col):
    """Affine host points [(x, y)] → (X, Y) u16 limb pair — no Z plane on
    the wire (the hybrid kernel's Q legs are affine; Z = 1 is implied)."""
    px = jnp.asarray(F.to_limbs([pt[0] for pt in col]).astype(np.uint16))
    py = jnp.asarray(F.to_limbs([pt[1] for pt in col]).astype(np.uint16))
    return (px, py)


def _add_k1(Pt, Qt, p: int, b3: int):
    """Fused RCB complete addition for a = 0, small b3 (secp256k1).

    Same mathematics as the a == 0 branch of :func:`add`, but products are
    kept as raw column accumulators (F.mul_cols) and every linear
    combination ±a·b ±c·d normalizes ONCE (F.col_acc + F.norm): ~10
    normalize walks instead of ~22 for the same 12 schoolbook products
    (measured when a walk was ~40% of a field multiply's time, before the
    product moved to 32-bit lanes in PR 32)."""
    X1, Y1, Z1 = Pt
    X2, Y2, Z2 = Qt
    c0 = F.mul_cols(X1, X2)
    c1 = F.mul_cols(Y1, Y2)
    c2 = F.mul_cols(Z1, Z2)
    t1 = F.norm(c1, p)
    t2 = F.norm(c2, p)
    t0x3 = F.norm(F.scale_cols(c0, 3), p)              # 3·t0
    t3 = F.norm(F.col_acc(p, plus=[F.mul_cols(F.rel_add(X1, Y1),
                                              F.rel_add(X2, Y2))],
                          minus=[c0, c1]), p)
    t4b3 = F.norm(F.scale_cols(
        F.col_acc(p, plus=[F.mul_cols(F.rel_add(X1, Z1),
                                      F.rel_add(X2, Z2))],
                  minus=[c0, c2]), b3), p)             # b3·t4
    t5 = F.norm(F.col_acc(p, plus=[F.mul_cols(F.rel_add(Y1, Z1),
                                              F.rel_add(Y2, Z2))],
                          minus=[c1, c2]), p)
    # NOTE: bt2 as scale_rel (skipping this walk) was measured a WASH-to-
    # regression: the relaxed Xm/Zm bounds push an extra pass into each of
    # the three downstream norms — the carry-conservation law again
    bt2 = F.mul_const(t2, b3, p)
    Xm = F.rel_sub(t1, bt2, p)       # t1 - b3·t2, relaxed (no normalize)
    Zm = F.rel_add(t1, bt2)          # t1 + b3·t2, relaxed
    Y3 = F.norm(F.col_acc(p, plus=[F.mul_cols(Xm, Zm),
                                   F.mul_cols(t0x3, t4b3)]), p)
    X3 = F.norm(F.col_acc(p, plus=[F.mul_cols(t3, Xm)],
                          minus=[F.mul_cols(t5, t4b3)]), p)
    Z3 = F.norm(F.col_acc(p, plus=[F.mul_cols(t5, Zm),
                                   F.mul_cols(t3, t0x3)]), p)
    return (X3, Y3, Z3)


def _madd_k1(Pt, Qa, p: int, b3: int):
    """Fused RCB complete MIXED addition (Z2 = 1) for a = 0, small b3
    (secp256k1): the affine addend kills the Z1·Z2 product and t2's walk —
    11 products / 9 walks vs :func:`_add_k1`'s 12 / 10. Complete for every
    projective P1 (identity included); NOT valid for an identity addend —
    the ladder's constant-G table carries a validity flag and the caller
    selects the untouched accumulator for flagged-identity rows instead.

    With Z2 = 1 the RCB cross terms collapse on the host side:
    t2 = Z1, t4 = X1 + Z1·X2, t5 = Y1 + Z1·Y2."""
    X1, Y1, Z1 = Pt
    X2, Y2 = Qa
    c0 = F.mul_cols(X1, X2)
    c1 = F.mul_cols(Y1, Y2)
    t1 = F.norm(c1, p)
    t0x3 = F.norm(F.scale_cols(c0, 3), p)              # 3·t0
    t3 = F.norm(F.col_acc(p, plus=[F.mul_cols(F.rel_add(X1, Y1),
                                              F.rel_add(X2, Y2))],
                          minus=[c0, c1]), p)
    t4b3 = F.norm(F.scale_cols(
        F.col_acc(p, plus=[F.mul_cols(Z1, X2), F.rel(X1)]), b3), p)
    t5 = F.norm(F.col_acc(p, plus=[F.mul_cols(Z1, Y2), F.rel(Y1)]), p)
    bt2 = F.mul_const(Z1, b3, p)     # walked: see _add_k1's bt2 note
    Xm = F.rel_sub(t1, bt2, p)       # t1 - b3·t2, relaxed
    Zm = F.rel_add(t1, bt2)          # t1 + b3·t2, relaxed
    Y3 = F.norm(F.col_acc(p, plus=[F.mul_cols(Xm, Zm),
                                   F.mul_cols(t0x3, t4b3)]), p)
    X3 = F.norm(F.col_acc(p, plus=[F.mul_cols(t3, Xm)],
                          minus=[F.mul_cols(t5, t4b3)]), p)
    Z3 = F.norm(F.col_acc(p, plus=[F.mul_cols(t5, Zm),
                                   F.mul_cols(t3, t0x3)]), p)
    return (X3, Y3, Z3)


def _add_m3(Pt, Qt, p: int, b: int):
    """Fused RCB complete addition for a = -3, general b (secp256r1):
    RCB16 Algorithm 4 with products kept as raw column accumulators so
    every linear combination normalizes ONCE — ~11 normalize walks vs the
    ~25 the generic :func:`add`/:func:`_rcb_finish` path pays for the same
    14 schoolbook products (b is a full-width constant here, unlike k1's
    small b3).  With the P-256 signed Solinas fold (ops/field.py) walks
    are the dominant per-op cost, so this is the r1 sibling of
    :func:`_add_k1` (VERDICT r4 ask #4's second lever)."""
    bc = _const(b, p)
    X1, Y1, Z1 = Pt
    X2, Y2, Z2 = Qt
    m0 = F.mul_cols(X1, X2)
    m1 = F.mul_cols(Y1, Y2)
    m2 = F.mul_cols(Z1, Z2)
    t3 = F.norm(F.col_acc(p, plus=[F.mul_cols(F.rel_add(X1, Y1),
                                              F.rel_add(X2, Y2))],
                          minus=[m0, m1]), p)           # X1Y2 + X2Y1
    t4 = F.norm(F.col_acc(p, plus=[F.mul_cols(F.rel_add(Y1, Z1),
                                              F.rel_add(Y2, Z2))],
                          minus=[m1, m2]), p)           # Y1Z2 + Y2Z1
    xz = F.norm(F.col_acc(p, plus=[F.mul_cols(F.rel_add(X1, Z1),
                                              F.rel_add(X2, Z2))],
                          minus=[m0, m2]), p)           # X1Z2 + X2Z1
    t1n = F.norm(m1, p)
    t2n = F.norm(m2, p)
    return _m3_tail(p, bc, m0, t1n, t2n, t3, t4, xz)


def _m3_tail(p: int, bc, m0, t1n, t2n, t3, t4, xz):
    """Shared tail of the fused a = -3 add/madd: from the six symmetric
    terms to (X3, Y3, Z3) in 5 walks (Algorithm 4's epilogue algebra)."""
    # u = 3(xz - b·t2)
    u = F.norm(F.scale_cols(
        F.col_acc(p, plus=[F.rel(xz)], minus=[F.mul_cols(t2n, bc)]), 3), p)
    # w = 3(b·xz - 3·t2 - t0)
    w = F.norm(F.scale_cols(
        F.col_acc(p, plus=[F.mul_cols(xz, bc)],
                  minus=[F.scale_rel(t2n, 3), m0]), 3), p)
    t0x3 = F.norm(F.scale_cols(m0, 3), p)               # 3·t0
    Xm = F.rel_add(t1n, u)           # t1 + u, relaxed
    Zm = F.rel_sub(t1n, u, p)        # t1 - u, relaxed
    t0f = F.rel_sub(t0x3, F.scale_rel(t2n, 3), p)       # 3t0 - 3t2
    X3 = F.norm(F.col_acc(p, plus=[F.mul_cols(t3, Xm)],
                          minus=[F.mul_cols(t4, w)]), p)
    Y3 = F.norm(F.col_acc(p, plus=[F.mul_cols(Xm, Zm),
                                   F.mul_cols(t0f, w)]), p)
    Z3 = F.norm(F.col_acc(p, plus=[F.mul_cols(t4, Zm),
                                   F.mul_cols(t3, t0f)]), p)
    return (X3, Y3, Z3)


def _dbl_m3(Pt, p: int, b: int):
    """Fused RCB complete doubling for a = -3, general b (secp256r1):
    RCB16 Algorithm 6, column-fused — vs dbl-via-:func:`add`'s generic
    path (~25 walks).  Complete for every input including the identity.

    The three cross products are HALF-COST sum-squares (2XY = (X+Y)² -
    X² - Y²) folded into the consuming walks.  Leaving X²/Z² as RAW
    column accumulators to skip their walks was measured SLOWER on v5e
    (12.2k vs 13.3k end-to-end): the widened DUS products cost more than
    the walks saved — the same normalize-before-multiply law the k1
    formulas follow."""
    bc = _const(b, p)
    X, Y, Z = Pt
    m0n = F.norm(F.sqr_cols(X), p)
    m1n = F.norm(F.sqr_cols(Y), p)
    m2n = F.norm(F.sqr_cols(Z), p)
    # 2XY = (X+Y)² - X² - Y², etc. — triangular squares beat full muls
    xy2 = F.norm(F.col_acc(p, plus=[F.sqr_cols(F.rel_add(X, Y))],
                           minus=[F.rel(m0n), F.rel(m1n)]), p)
    xz2 = F.norm(F.col_acc(p, plus=[F.sqr_cols(F.rel_add(X, Z))],
                           minus=[F.rel(m0n), F.rel(m2n)]), p)
    yz2 = F.norm(F.col_acc(p, plus=[F.sqr_cols(F.rel_add(Y, Z))],
                           minus=[F.rel(m1n), F.rel(m2n)]), p)
    # u = 3(b·Z² - 2XZ)
    u = F.norm(F.scale_cols(
        F.col_acc(p, plus=[F.mul_cols(m2n, bc)], minus=[F.rel(xz2)]), 3), p)
    # w = 3(b·2XZ - 3Z² - X²)
    w = F.norm(F.scale_cols(
        F.col_acc(p, plus=[F.mul_cols(xz2, bc)],
                  minus=[F.scale_rel(m2n, 3), F.rel(m0n)]), 3), p)
    Xm = F.rel_sub(m1n, u, p)        # Y² - u, relaxed
    Ym = F.rel_add(m1n, u)           # Y² + u, relaxed
    t0f = F.rel_sub(F.scale_rel(m0n, 3), F.scale_rel(m2n, 3), p)
    X3 = F.norm(F.col_acc(p, plus=[F.mul_cols(Xm, xy2)],
                          minus=[F.mul_cols(yz2, w)]), p)
    Y3 = F.norm(F.col_acc(p, plus=[F.mul_cols(Xm, Ym),
                                   F.mul_cols(t0f, w)]), p)
    Z3 = F.norm(F.scale_cols(F.mul_cols(yz2, m1n), 4), p)
    return (X3, Y3, Z3)


def add(Pt, Qt, curve: WeierstrassCurve):
    """RCB16 complete projective addition, specialized at trace time.

    Three variants chosen by the curve constants (all complete):
    - ``a == 0`` (secp256k1): the three a·x products are identically zero and
      drop out (RCB16 Algorithm 7 shape); with b3 = 21 small, both b3·x
      products are ``mul_const`` — 12 full field muls per point-add, fused
      column-level in :func:`_add_k1`.
    - ``a = -3`` (secp256r1): Algorithm 4, column-fused in :func:`_add_m3`.
    - general a: Algorithm 1 verbatim.
    """
    doubling = Pt is Qt     # dbl-via-add: every cross product is a square
    Pt = tuple(jnp.asarray(c, jnp.uint64) for c in Pt)
    Qt = Pt if doubling else tuple(jnp.asarray(c, jnp.uint64) for c in Qt)
    p = curve.p
    a = curve.a % p
    b3 = 3 * curve.b % p
    if a == 0 and b3 < F.MUL_CONST_MAX:
        return _add_k1(Pt, Qt, p, b3)
    if a == p - 3:
        return (_dbl_m3(Pt, p, curve.b % p) if doubling
                else _add_m3(Pt, Qt, p, curve.b % p))

    def mul2(x, y):
        return F.sqr(x, p) if doubling else F.mul(x, y, p)

    def mul2_of_sums(a1, a2, b1, b2):
        return (F.sqr_of_sum(a1, a2, p) if doubling
                else F.mul_of_sums(a1, a2, b1, b2, p))

    X1, Y1, Z1 = Pt
    X2, Y2, Z2 = Qt
    t0 = mul2(X1, X2)
    t1 = mul2(Y1, Y2)
    t2 = mul2(Z1, Z2)
    t3 = mul2_of_sums(X1, Y1, X2, Y2)
    t4 = F.add(t0, t1, p)
    t3 = F.sub(t3, t4, p)
    t4 = mul2_of_sums(X1, Z1, X2, Z2)
    t5 = F.add(t0, t2, p)
    t4 = F.sub(t4, t5, p)
    t5 = mul2_of_sums(Y1, Z1, Y2, Z2)
    X3 = F.add(t1, t2, p)
    t5 = F.sub(t5, X3, p)
    return _rcb_finish(t0, t1, t2, t3, t4, t5, curve)


def _rcb_finish(t0, t1, t2, t3, t4, t5, curve: WeierstrassCurve):
    """The curve-constant tail of RCB Algorithm 1 after the six symmetric
    cross products — shared by the full add and the mixed (Z2 = 1) add."""
    p = curve.p
    a = curve.a % p
    b3 = 3 * curve.b % p
    neg_a = p - a
    small = F.MUL_CONST_MAX
    b3_c = None if b3 < small else _const(b3, p)

    def mul_b3(x):
        return F.mul_const(x, b3, p) if b3_c is None else F.mul(x, b3_c, p)

    if neg_a < small:
        # a = -|a|:  Z3 = b3·t2 - |a|·t4 ;  t1' = 3t0 - |a|·t2 ;
        # t4' = b3·t4 + a·(t0 - a·t2) = b3·t4 - |a|·(t0 + |a|·t2)
        Z3 = F.sub(mul_b3(t2), F.mul_const(t4, neg_a, p), p)
        X3 = F.sub(t1, Z3, p)
        Z3 = F.add(t1, Z3, p)
        Y3 = F.mul(X3, Z3, p)
        m = F.add(t0, F.mul_const(t2, neg_a, p), p)   # t0 - a·t2
        t1 = F.sub(F.mul_const(t0, 3, p), F.mul_const(t2, neg_a, p), p)
        t4 = F.sub(mul_b3(t4), F.mul_const(m, neg_a, p), p)
    else:
        a_c = _const(a, p)
        Z3 = F.mul(a_c, t4, p)
        X3 = mul_b3(t2)
        Z3 = F.add(X3, Z3, p)
        X3 = F.sub(t1, Z3, p)
        Z3 = F.add(t1, Z3, p)
        Y3 = F.mul(X3, Z3, p)
        t1 = F.mul_const(t0, 3, p)
        t2 = F.mul(a_c, t2, p)
        t4 = mul_b3(t4)
        t1 = F.add(t1, t2, p)
        t2 = F.sub(t0, t2, p)
        t2 = F.mul(a_c, t2, p)
        t4 = F.add(t4, t2, p)
    t0 = F.mul(t1, t4, p)
    Y3 = F.add(Y3, t0, p)
    t0 = F.mul(t5, t4, p)
    X3 = F.mul(t3, X3, p)
    X3 = F.sub(X3, t0, p)
    t0 = F.mul(t3, t1, p)
    Z3 = F.mul(t5, Z3, p)
    Z3 = F.add(Z3, t0, p)
    return (X3, Y3, Z3)


def _madd_w(Pt, Qa, curve: WeierstrassCurve):
    """Complete MIXED (Z2 = 1) RCB addition for a GENERAL-a curve
    (secp256r1's a = -3 path): with an affine addend the symmetric cross
    products collapse host-side — t2 = Z1, t4 = X1 + Z1·X2,
    t5 = Y1 + Z1·Y2 — saving three of the twelve full products. Complete
    for every projective P1; NOT valid for an identity addend (the
    constant-G tables carry a validity flag).  The a = -3 case
    rides the column-fused tail (:func:`_m3_tail`)."""
    X1, Y1, Z1 = Pt
    X2, Y2 = Qa
    p = curve.p
    if curve.a % p == p - 3:
        bc = _const(curve.b % p, p)
        m0 = F.mul_cols(X1, X2)
        m1 = F.mul_cols(Y1, Y2)
        t3 = F.norm(F.col_acc(p, plus=[F.mul_cols(F.rel_add(X1, Y1),
                                                  F.rel_add(X2, Y2))],
                              minus=[m0, m1]), p)
        t4 = F.norm(F.col_acc(p, plus=[F.mul_cols(Z1, Y2), F.rel(Y1)]), p)
        xz = F.norm(F.col_acc(p, plus=[F.mul_cols(Z1, X2), F.rel(X1)]), p)
        return _m3_tail(p, bc, m0, F.norm(m1, p), Z1, t3, t4, xz)
    t0 = F.mul(X1, X2, p)
    t1 = F.mul(Y1, Y2, p)
    t3 = F.mul_of_sums(X1, Y1, X2, Y2, p)
    t3 = F.sub(t3, F.add(t0, t1, p), p)
    t4 = F.norm(F.col_acc(p, plus=[F.mul_cols(Z1, X2), F.rel(X1)]), p)
    t5 = F.norm(F.col_acc(p, plus=[F.mul_cols(Z1, Y2), F.rel(Y1)]), p)
    return _rcb_finish(t0, t1, Z1, t3, t4, t5, curve)


def dbl(Pt, curve: WeierstrassCurve):
    """Complete projective doubling. For a = 0 with small b3 (secp256k1):
    RCB16 Algorithm 9, column-fused — 7 schoolbook products and 7 normalize
    walks versus the 12-product complete add (doubling chains like 8Y²
    collapse into column scales folded into adjacent normalizes). Complete
    for every input including the identity (0:1:0). Other curves fall back
    to add(P, P), which is complete and already specialized per curve
    constants.

    Derivation from Algorithm 9 (s = Y², z2 = Z², w = b3·z2):
      X3 = 2·(s - 3w)·X·Y
      Y3 = (s - 3w)·(s + w) + 8·w·s
      Z3 = 8·s·Y·Z
    """
    Pt = tuple(jnp.asarray(c, jnp.uint64) for c in Pt)
    p = curve.p
    a = curve.a % p
    b3 = 3 * curve.b % p
    if a != 0 or b3 >= F.MUL_CONST_MAX:
        return add(Pt, Pt, curve)
    X, Y, Z = Pt
    cy = F.sqr_cols(Y)
    s = F.norm(cy, p)                                   # Y²
    w = F.norm(F.scale_cols(F.sqr_cols(Z), b3), p)      # b3·Z²
    xy = F.norm(F.mul_cols(X, Y), p)
    yz = F.norm(F.mul_cols(Y, Z), p)
    sm3w = F.rel_sub(s, F.scale_rel(w, 3), p)           # s - 3w, relaxed
    spw = F.rel_add(s, w)
    Y3 = F.norm(F.col_acc(p, plus=[F.mul_cols(sm3w, spw),
                                   F.scale_cols(F.mul_cols(w, s), 8)]), p)
    X3 = F.norm(F.scale_cols(F.mul_cols(sm3w, xy), 2), p)
    Z3 = F.norm(F.scale_cols(F.mul_cols(yz, s), 8), p)
    return (X3, Y3, Z3)


def shamir_ladder(bits1, bits2, P1, P2, curve: WeierstrassCurve):
    """[k1]P1 + [k2]P2: interleaved double-and-add over complete additions."""
    batch_shape = P1[0].shape[:-1]
    P3 = add(P1, P2, curve)
    Pid = identity(batch_shape)

    def step(acc, bits):
        b1, b2 = bits
        acc = dbl(acc, curve)
        addend = _select4(b1 + 2 * b2, (Pid, P1, P2, P3))
        return add(acc, addend, curve), None

    acc, _ = jax.lax.scan(step, Pid, (bits1.astype(jnp.uint64),
                                      bits2.astype(jnp.uint64)), unroll=2)
    return acc


GLV_BITS = 128  # Babai rounding bounds the halves of secp256k1's lambda
                # decomposition by (|a1|+|a2|)/2 < 2^127.35 and
                # (|b1|+|b2|)/2 < 2^127.12 (ecmath constants), so 128 bits
                # always suffice; scalars_to_bits asserts if a scalar ever
                # exceeded this


def _accept(X, Z, r_cands, p):
    """ECDSA acceptance on the projective result: X/Z ≡ r_cand ⟺ X ≡ r_cand·Z
    (homogeneous coordinates) — two field muls instead of a ~500-mul Fermat
    inversion per batch; Z = 0 (infinity) rejected separately."""
    nonzero = ~F.is_zero(Z, p)
    ok_r = (F.eq(X, F.mul(r_cands[0], Z, p), p)
            | F.eq(X, F.mul(r_cands[1], Z, p), p))
    return nonzero & ok_r


def _accept_rn(X, Z, r, rn_ok, p: int, n: int):
    """Like :func:`_accept`, but the second x-candidate (r + n, valid only
    when it stays below p) is DERIVED on device from r and a 1-bit flag —
    half the candidate wire bytes of shipping both limb arrays. X is
    canonicalised ONCE and compared against both candidates (F.eq would
    re-canonicalise it per comparison; canon's serial sweeps are the
    epilogue's dominant cost)."""
    nonzero = ~F.is_zero(Z, p)
    r1 = F.add(r, jnp.broadcast_to(jnp.asarray(F.to_limbs(n)), r.shape), p)
    cx = F.canon(X, p)
    ok_r = (jnp.all(cx == F.canon(F.mul(r, Z, p), p), axis=-1)
            | (rn_ok & jnp.all(cx == F.canon(F.mul(r1, Z, p), p), axis=-1)))
    return nonzero & ok_r


def _batch_modinv(values, n: int):
    """Montgomery's trick: invert many nonzero values mod prime n with ONE
    modpow + 3(B-1) modmuls. The per-item Fermat inversion was the dominant
    host-prep cost (~50µs each); amortized it is ~1µs."""
    if not values:
        return []
    prefix, acc = [], 1
    for v in values:
        acc = acc * v % n
        prefix.append(acc)
    inv = pow(acc, n - 2, n)
    out = [0] * len(values)
    for i in range(len(values) - 1, 0, -1):
        out[i] = inv * prefix[i - 1] % n
        inv = inv * values[i] % n
    out[0] = inv
    return out


@functools.lru_cache(maxsize=65536)
def _is_on_curve_memo(curve_name: str, pub) -> bool:
    """Memoized on-curve check (same per-signer caching pattern as
    keys.py's decompress LRU): a node verifies the same signers'
    transactions over and over, and the 3-modmul curve test per ITEM was a
    measurable slice of host prep — the service path is host-CPU-bound at
    32k batches."""
    return CURVES[curve_name].is_on_curve(pub)


def _precheck_and_scalars(curve: WeierstrassCurve, items):
    """Shared ECDSA acceptance policy for both kernel preps: structural checks
    (r, s in [1, n-1], on-curve key), e/w/u1/u2 derivation, the
    neutral substitution for invalid items, and the r / r+n x-candidates.
    Returns (precheck, pubs, u1s, u2s, r0, r1). The s-inversions are batched
    (Montgomery's trick) so host prep stays off the service's critical path."""
    precheck = np.ones(len(items), dtype=bool)
    pubs, rs, es, ss = [], [], [], []
    for i, (pub, msg, r, s) in enumerate(items):
        ok = (1 <= r < curve.n and 1 <= s < curve.n
              and pub is not None and _is_on_curve_memo(curve.name, pub))
        if ok:
            es.append(_bits2int(hashlib.sha256(msg).digest(), curve.n)
                      % curve.n)
            ss.append(s)
        else:
            precheck[i] = False
            pub, r = curve.g, 0
            es.append(0)
            ss.append(1)   # placeholder: batch inversion needs nonzero
        pubs.append(pub)
        rs.append(r)
    ws = _batch_modinv(ss, curve.n)
    u1s = [e * w % curve.n for e, w in zip(es, ws)]
    u2s = [r * w % curve.n for r, w in zip(rs, ws)]
    for i in range(len(items)):
        if not precheck[i]:
            u1s[i] = u2s[i] = 0
    r0 = rs
    r1 = [r + curve.n if r + curve.n < curve.p else r for r in rs]
    return precheck, pubs, u1s, u2s, r0, r1


# ---------------------------------------------------------------------------
# Hybrid GLV path (secp256k1): constant-table G legs + selected Q legs
# ---------------------------------------------------------------------------

def _q_window_table(Qc, Qd, curve: WeierstrassCurve):
    """16-entry per-item table T[i + 4j] = [i]Qc + [j]Qd (i, j ∈ [0,4)) from
    AFFINE Qc = (x, y), Qd = (x, y): 2 doublings + 11 complete MIXED adds
    (each affine operand saves a product and a walk vs the projective
    chain), one-time per batch. No exception analysis needed: _madd_k1 is
    complete for every projective P1 given a valid affine P2 ≠ ∞, and the
    host precheck substitutes G for any malformed key."""
    p = curve.p
    b3 = 3 * curve.b % p
    one = F.one_like(Qc[0])
    batch_shape = Qc[0].shape[:-1]
    T = [identity(batch_shape)] * 16
    T[1] = (Qc[0], Qc[1], one)
    T[2] = dbl(T[1], curve)
    T[3] = _madd_k1(T[2], Qc, p, b3)
    T[4] = (Qd[0], Qd[1], one)
    T[8] = dbl(T[4], curve)
    T[12] = _madd_k1(T[8], Qd, p, b3)
    for j in (4, 8, 12):
        T[j + 1] = _madd_k1(T[j], Qc, p, b3)
        T[j + 2] = _madd_k1(T[j + 1], Qc, p, b3)
        T[j + 3] = _madd_k1(T[j + 2], Qc, p, b3)
    return T


#: Default constant-G window width for the hybrid kernel: w = 8 measured 6%
#: over w = 6 on v5e at batch 32k (r4 kernel: affine u16 tables + mixed G
#: adds + GLV 128; the rates of that round are older than the tree, the
#: cell's are in PERF.md). The w=8 table is 2^18
#: affine u16 rows (~17MB baked constants) — 4x less gather footprint than
#: the u64 projective layout that made w=8 a ~100MB non-starter in r3 —
#: and 128 = 16x8 divides exactly: 128 dbls, 64 Q adds, 16 G adds.
HYBRID_G_WINDOW = 8

_G_TABLES_WIDE: dict[tuple, tuple] = {}


def _g_window_table_wide(curve: WeierstrassCurve, w: int):
    """AFFINE constant-G window table: u16 X/Y limb arrays of shape
    (2^(2w+2), NLIMB) plus a u8 validity flag, indexed by
    ``wa + 2^w·wb + 2^(2w)·sa + 2^(2w+1)·sb``: entry = wa·(sa ? -G : G) +
    wb·(sb ? -phi(G) : phi(G)) for w-bit digits wa, wb ∈ [0, 2^w).

    Affine entries let the ladder use the cheaper complete MIXED add
    (:func:`_madd_k1`); identity entries (wa = wb = 0) carry flag 0 and the
    ladder selects the untouched accumulator for them. u16 storage is 4x
    less gather footprint than u64 — at w = 8 the three arrays are ~17MB.

    The build batch-inverts every chord denominator with ONE modpow
    (Montgomery's trick) so even the 2^17 affine adds at w = 8 take ~1s,
    one-time per process. wa·G = ±wb·phi(G) is impossible for nonzero
    digits (it would force wa ≡ ∓wb·lambda (mod n) with tiny wa, wb), so
    every chord add is generic — asserted, not assumed."""
    key = (curve.name, w)
    if key in _G_TABLES_WIDE:
        return _G_TABLES_WIDE[key]
    p, g = curve.p, curve.g
    phi = (SECP256K1_BETA * g[0] % p, g[1])
    span = 1 << w

    def multiples(base):
        out = [None] * span          # None = identity
        acc = None
        for i in range(1, span):
            acc = base if acc is None else curve.add(acc, base)
            out[i] = acc
        return out
    g_mult = multiples(g)
    phi_mult = multiples(phi)

    # One inverse chord slope denominator per (wa, wb) pair, shared by both
    # relative-sign grids (x(-P) = x(P)).
    dens = []
    for wb in range(1, span):
        xb = phi_mult[wb][0]
        for wa in range(1, span):
            d = (xb - g_mult[wa][0]) % p
            assert d != 0, "G/phi(G) multiples can never share an x"
            dens.append(d)
    invs = iter(_batch_modinv(dens, p))

    # grid_pp[wb][wa] = wa·G + wb·phi(G); grid_pm: wa·G - wb·phi(G).
    grid_pp = [[None] * span for _ in range(span)]
    grid_pm = [[None] * span for _ in range(span)]
    grid_pp[0] = list(g_mult)
    grid_pm[0] = list(g_mult)
    for wb in range(1, span):
        xb, yb = phi_mult[wb]
        grid_pp[wb][0] = (xb, yb)
        grid_pm[wb][0] = (xb, (p - yb) % p)
        for wa in range(1, span):
            xa, ya = g_mult[wa]
            inv = next(invs)
            for grid, y2 in ((grid_pp, yb), (grid_pm, p - yb)):
                lam = (y2 - ya) * inv % p
                x3 = (lam * lam - xa - xb) % p
                grid[wb][wa] = (x3, (lam * (xa - x3) - ya) % p)

    xs, ys, flags = [], [], []
    for sb in (False, True):
        for sa in (False, True):
            # (sa, sb) grid: negate-both maps (+,+)↔(-,-) and (+,-)↔(-,+)
            grid, flip = ((grid_pp, sa) if sa == sb else (grid_pm, sa))
            for wb in range(span):
                for wa in range(span):
                    pt = grid[wb][wa]
                    if pt is None:               # wa = wb = 0: identity
                        xs.append(0)
                        ys.append(0)
                        flags.append(0)
                    else:
                        x, y = pt
                        xs.append(x)
                        ys.append((p - y) % p if flip and y else y)
                        flags.append(1)
    tab = (F.to_limbs(xs).astype(np.uint16), F.to_limbs(ys).astype(np.uint16),
           np.asarray(flags, dtype=np.uint8))
    _G_TABLES_WIDE[key] = tab
    return tab


_G_TABLES_1S: dict[tuple, tuple] = {}


def _g_window_table_single(curve: WeierstrassCurve, w: int, shift: int = 0):
    """Single-scalar constant-G window table for curves WITHOUT an
    endomorphism (secp256r1): u16 affine X/Y arrays of shape (2^w, NLIMB)
    plus a u8 validity flag (row 0 = identity). Entry wa = wa·B where the
    base B is [2^shift]G — shift=0 is the plain G table, shift=128 the
    high-half table the half-gcd split ladder pairs with it.

    Built as a JACOBIAN host chain (no inversion per add) landed affine by
    ONE Montgomery batch inversion — 2^16 rows in ~1s."""
    key = (curve.name, w, shift)
    if key in _G_TABLES_1S:
        return _G_TABLES_1S[key]
    p = curve.p
    a = curve.a % p
    gx, gy = curve.mul(1 << shift, curve.g) if shift else curve.g
    span = 1 << w

    def jac_dbl(X1, Y1, Z1):
        """General-a Jacobian doubling (dbl-2007-bl) — for 2·G, where the
        mixed add would be the exceptional equal-points case."""
        A = X1 * X1 % p
        B = Y1 * Y1 % p
        C = B * B % p
        D = 2 * ((X1 + B) * (X1 + B) - A - C) % p
        E = (3 * A + a * pow(Z1, 4, p)) % p
        Fv = E * E % p
        X3 = (Fv - 2 * D) % p
        Y3 = (E * (D - X3) - 8 * C) % p
        Z3 = 2 * Y1 * Z1 % p
        return X3, Y3, Z3

    def jac_madd(X1, Y1, Z1):
        """(X1:Y1:Z1) Jacobian + G affine (madd-2007-bl); the chain from
        3·G on never hits the exceptional cases (wa·G = ±G needs
        tiny-order points)."""
        Z1Z1 = Z1 * Z1 % p
        U2 = gx * Z1Z1 % p
        S2 = gy * Z1 % p * Z1Z1 % p
        H = (U2 - X1) % p
        assert H != 0, "chain hit an exceptional mixed add"
        HH = H * H % p
        I = 4 * HH % p
        J = H * I % p
        r = 2 * (S2 - Y1) % p
        V = X1 * I % p
        X3 = (r * r - J - 2 * V) % p
        Y3 = (r * (V - X3) - 2 * Y1 * J) % p
        Z3 = ((Z1 + H) * (Z1 + H) - Z1Z1 - HH) % p
        return X3, Y3, Z3

    chain = [None, (gx, gy, 1)]
    if span > 2:
        chain.append(jac_dbl(*chain[1]))
    for _ in range(3, span):
        chain.append(jac_madd(*chain[-1]))
    zinvs = iter(_batch_modinv([c[2] for c in chain[1:]], p))
    xs, ys, flags = [0], [0], [0]          # identity row
    for X, Y, Z in chain[1:]:
        zi = next(zinvs)
        zi2 = zi * zi % p
        xs.append(X * zi2 % p)
        ys.append(Y * zi2 % p * zi % p)
        flags.append(1)
    tab = (F.to_limbs(xs).astype(np.uint16), F.to_limbs(ys).astype(np.uint16),
           np.asarray(flags, dtype=np.uint8))
    _G_TABLES_1S[key] = tab
    return tab


def g_window_table_single_device(curve: WeierstrassCurve, w: int,
                                 shift: int = 0):
    return F.device_table_cache(
        ("g_single", curve.name, w, shift),
        lambda: _g_window_table_single(curve, w, shift))


#: Constant-G window width of the r1 split ladder (both tables).
R1_G_WINDOW = 16


#: Per-item Q window width of the r1 split ladder: 4-bit windows over a
#: 16-entry {0..15}·Q per-batch table (14-op build), half the table adds
#: of 2-bit windows (measured on v5e in an early round).
R1_Q_WINDOW = 4


def _q_table_single(Q, curve: WeierstrassCurve):
    """16-entry per-item table T[i] = [i]Q from AFFINE Q: 7 doublings +
    7 complete MIXED adds, one-time per batch (the single-scalar sibling
    of the k1 hybrid's joint Q table)."""
    batch_shape = Q[0].shape[:-1]
    one = F.one_like(Q[0])
    T = [identity(batch_shape)] * 16
    T[1] = (Q[0], Q[1], one)
    for i in range(2, 16):
        T[i] = (dbl(T[i // 2], curve) if i % 2 == 0
                else _madd_w(T[i - 1], Q, curve))
    return T


# ---------------------------------------------------------------------------
# Half-gcd split path (secp256r1): [t_lo]G + [t_hi]G' + [|v1|](±Q) ?= [v2]R
# ---------------------------------------------------------------------------
#
# Antipa et al. (SAC 2005): the extended Euclid run on (n, u2), stopped at
# the first remainder below 2^128, yields v1, v2 < 2^128 with
# u2·v2 ≡ ±v1 (mod n). Multiplying the ECDSA equation X = [u1]G + [u2]Q by
# v2 gives [t]G ± [v1]Q = [v2]X with t = v2·u1 mod n — t is full-width, but
# splitting it at 2^128 against a second constant table G' = [2^128]G keeps
# every DOUBLING run at 128 bits: 124 doublings instead of a full-width
# ladder's 252. The host decompresses R = (r, y) and computes
# x_D = x([v2]R) (one Jacobian ladder + ONE batch inversion per batch);
# the device accepts iff x(W2) == x_D projectively — parity-insensitive,
# and sound because v2 is invertible mod the prime n, so
# W2 = [v2]X = ±[v2]R ⟺ X = ±R ⟺ x(X) = r.
#
# Items where the split can't stand in for the old two-candidate check
# fall back to the HOST oracle, masked per-item (hg_ok=0): r + n < p (the
# second x-candidate exists — ~2^-64 for honest r since p − n ≈ 2^192, but
# craftable), r not a quadratic-residue x-coordinate, or a defensive
# half-gcd bound failure. Precheck failures keep hg_ok=1: their verdict is
# already False and their zeroed windows make W2 = ∞ on device.

def _r1_host_verify_scalars(curve: WeierstrassCurve, pub, e_raw: int,
                            r: int, s: int) -> bool:
    """ecmath.ecdsa_verify from the already-hashed digest int (the words
    path never sees the message). Must stay verdict-identical to the
    oracle — pinned in tests/test_scalarprep.py."""
    n = curve.n
    if not (1 <= r < n and 1 <= s < n):
        return False
    if pub is None or not curve.is_on_curve(pub):
        return False
    e = e_raw % n
    w = pow(s, n - 2, n)
    X = curve.add(curve.mul(e * w % n, curve.g),
                  curve.mul(r * w % n, pub))
    if X is None:
        return False
    return X[0] % n == r


def r1_split_ladder(g_idx, q_digits, Q, gtab_lo, gtab_hi,
                    curve: WeierstrassCurve, w: int):
    """W2 = [t_lo]G + [t_hi]G' + [|v1|](±Q) with every scalar < 2^128: per
    outer step, ``w`` bits — w doublings, w/4 Q adds (4-bit windows over
    the 16-entry {0..15}Q table) and TWO mixed G adds, one gathered from
    the G' = [2^128]G table (high half of t) and one from the plain G
    table (low half). 128/w outer steps; step 0 peeled ⇒ 128 − w
    doublings total (124 at w = 16) vs the full-width ladder's 252.

    ``g_idx``: (128/w, 2, B) — [:, 0] = t_hi windows, [:, 1] = t_lo;
    ``q_digits``: (128/w, w/4, B) 4-bit |v1| digits; ``Q``: affine (x, y)
    limb pair, y already sign-adjusted for neg1 on host."""
    lo_x, lo_y, lo_ok = gtab_lo
    hi_x, hi_y, hi_ok = gtab_hi
    assert (g_idx.shape[0] * w == 128 and g_idx.shape[1] == 2
            and q_digits.shape[1] * 4 == w), (g_idx.shape, q_digits.shape, w)
    assert lo_x.shape[0] == 1 << w and hi_x.shape[0] == 1 << w, \
        (lo_x.shape, hi_x.shape, w)
    q_tab = _q_table_single(Q, curve)

    def q_addend(dig):
        return select_tree(q_tab, dig)

    def g_add(acc, gi, tab_x, tab_y, tab_ok):
        q2 = (tab_x[gi].astype(jnp.uint64), tab_y[gi].astype(jnp.uint64))
        added = _madd_w(acc, q2, curve)
        ok = tab_ok[gi].astype(jnp.bool_)
        return tuple(F.select(ok, new_c, acc_c)
                     for new_c, acc_c in zip(added, acc))

    def q_step(acc, dig):
        acc = dbl(dbl(dbl(dbl(acc, curve), curve), curve), curve)
        return add(acc, q_addend(dig), curve), None

    def step(acc, ins):
        gi, digs = ins
        acc, _ = jax.lax.scan(q_step, acc, digs)
        acc = g_add(acc, gi[0], hi_x, hi_y, hi_ok)
        return g_add(acc, gi[1], lo_x, lo_y, lo_ok), None

    # peel step 0 (accumulator starts as the identity)
    acc = q_addend(q_digits[0][0])
    acc, _ = jax.lax.scan(q_step, acc, q_digits[0][1:])
    acc = g_add(acc, g_idx[0][0], hi_x, hi_y, hi_ok)
    acc = g_add(acc, g_idx[0][1], lo_x, lo_y, lo_ok)
    acc, _ = jax.lax.scan(step, acc, (g_idx[1:], q_digits[1:]))
    return acc


def verify_core_r1_split(g_idx, q_digits, Q, xd_limbs,
                         lo_x, lo_y, lo_ok, hi_x, hi_y, hi_ok,
                         curve_name: str, w: int):
    """Device accept for the split form: W2 ≠ ∞ ∧ x(W2) == x_D checked
    projectively (X == x_D·Z). Single candidate — the r+n twin is a
    host-fallback condition, not a device branch. Zero-window items land
    on W2 = ∞ and reject here; their verdict comes from precheck/forced."""
    g_idx = jnp.asarray(g_idx, jnp.int32)
    q_digits = jnp.asarray(q_digits, jnp.uint64)
    Q = tuple(jnp.asarray(c, jnp.uint64) for c in Q)
    xd = jnp.asarray(xd_limbs, jnp.uint64)
    curve = CURVES[curve_name]
    X, Y, Z = r1_split_ladder(g_idx, q_digits, Q, (lo_x, lo_y, lo_ok),
                              (hi_x, hi_y, hi_ok), curve, w)
    p = curve.p
    nonzero = ~F.is_zero(Z, p)
    ok = jnp.all(F.canon(X, p) == F.canon(F.mul(xd, Z, p), p), axis=-1)
    return nonzero & ok


_verify_kernel_r1_split = jax.jit(
    verify_core_r1_split, static_argnames=("curve_name", "w"))


def prepare_batch_r1_split(curve: WeierstrassCurve, items,
                           w: int = R1_G_WINDOW):
    """Host prep for the half-gcd split kernel. Returns
    ``(*kernel_args, precheck_eff, forced)`` where precheck_eff masks out
    both structural failures AND hg_ok=0 fallbacks, and ``forced`` carries
    the host-oracle verdicts for the fallback items (False elsewhere) —
    callers combine as ``(dev & precheck_eff) | forced``."""
    from . import scalarprep as sp
    if w == 16 and curve.name == "secp256r1" and sp.available():
        return _prepare_r1_split_native_words(*_items_to_words(items), w)
    return _prepare_r1_split_python(curve, items, w)


def _r1_split_pack(curve, g_idx, q_digits, q_pts, xd_limbs, hg_ok,
                   precheck, forced, w: int):
    """Shared tail of both split preps: window reshapes, the fallback
    rows masked out of precheck, and the two G tables (plain G and
    G' = [2^128]G)."""
    B = len(precheck)
    hg = np.asarray(hg_ok, dtype=bool)
    return (jnp.asarray(g_idx.reshape(128 // w, 2, B)),
            jnp.asarray(q_digits.reshape(128 // w, w // 4, B)),
            q_pts, jnp.asarray(xd_limbs),
            *g_window_table_single_device(curve, w),
            *g_window_table_single_device(curve, w, 128),
            precheck & hg, forced)


def _words_row_int(words, i: int) -> int:
    return int.from_bytes(np.ascontiguousarray(words[i]).tobytes(), "little")


def _prepare_r1_split_native_words(e_words, r_words, s_words, pub_words,
                                   w: int):
    """Word-form core of the native half-gcd prep: the whole scalar layer
    (precheck, batch s-inversion, half-gcd, t-split windows, R decompress,
    the [v2]R ladder and its batch inversion) runs in
    native/scalarmath.cpp — bit-identical to _prepare_r1_split_python
    (tests/test_scalarprep.py)."""
    from . import scalarprep as sp
    curve = CURVES["secp256r1"]
    (g_idx, q_digits, q_x, q_y, xd_limbs, hg_ok,
     precheck) = sp.r1_prep_hg(e_words, r_words, s_words, pub_words)
    fb = precheck & ~hg_ok.astype(bool)
    forced = np.zeros(len(precheck), dtype=bool)
    for i in np.nonzero(fb)[0]:
        row = np.ascontiguousarray(pub_words[i]).tobytes()
        pub = (int.from_bytes(row[:32], "little"),
               int.from_bytes(row[32:], "little"))
        forced[i] = _r1_host_verify_scalars(
            curve, pub, _words_row_int(e_words, i),
            _words_row_int(r_words, i), _words_row_int(s_words, i))
    return _r1_split_pack(curve, g_idx, q_digits,
                          (jnp.asarray(q_x), jnp.asarray(q_y)), xd_limbs,
                          hg_ok, precheck, forced, w)


def _prepare_r1_split_python(curve: WeierstrassCurve, items,
                             w: int = R1_G_WINDOW):
    """Pure-Python mirror of sm_r1_prep_hg — bit-identical wire arrays
    (same substitutions, zeroing, window layout and sign handling), so a
    stale/missing native library degrades in speed only."""
    from . import scalarprep as sp
    p, n, b = curve.p, curve.n, curve.b
    precheck, pubs, u1s, u2s, r0, _ = _precheck_and_scalars(curve, items)
    B = len(items)
    g_idx = np.zeros((2 * (128 // w), B), dtype=np.int32)
    q_digits = np.zeros((128 // R1_Q_WINDOW, B), dtype=np.uint8)
    hg_ok = np.ones(B, dtype=np.uint8)
    qys, xds = [], []
    mask16 = (1 << w) - 1
    for i, (pub, u1, u2, r) in enumerate(zip(pubs, u1s, u2s, r0)):
        hg, neg1, v1, v2, tt, y_r = True, False, 0, 0, 0, None
        if precheck[i]:
            dec = sp.r1_halfgcd_py(u2)
            if dec is None:
                hg = False
            else:
                neg1, v1, v2 = dec
                tt = v2 * u1 % n
            if r + n < p:
                hg = False
            if hg:
                z = (r * r % p * r - 3 * r + b) % p
                y_r = pow(z, (p + 1) // 4, p)
                if y_r * y_r % p != z:
                    hg = False
        emit = bool(precheck[i]) and hg
        hg_ok[i] = 1 if hg else 0
        if emit:
            t_hi, t_lo = tt >> 128, tt & ((1 << 128) - 1)
            for j in range(128 // w):
                sh = w * (128 // w - 1 - j)
                g_idx[2 * j, i] = (t_hi >> sh) & mask16
                g_idx[2 * j + 1, i] = (t_lo >> sh) & mask16
            for j in range(128 // R1_Q_WINDOW):
                q_digits[j, i] = (v1 >> (4 * (31 - j))) & 0xF
            D = curve.mul(v2, (r, y_r))
            xds.append(D[0])
        else:
            xds.append(0)
        qys.append((p - pub[1]) % p if (emit and neg1) else pub[1])
    q_pts = (jnp.asarray(F.to_limbs([q[0] for q in pubs]).astype(np.uint16)),
             jnp.asarray(F.to_limbs(qys).astype(np.uint16)))
    xd_limbs = F.to_limbs(xds).astype(np.uint16)
    forced = np.zeros(B, dtype=bool)
    for i in np.nonzero(precheck & ~hg_ok.astype(bool))[0]:
        # precheck already validated the item; the oracle verdict is just
        # X = [u1]G + [u2]Q ≠ ∞ ∧ x(X) ≡ r (mod n)
        X = curve.add(curve.mul(u1s[i], curve.g),
                      curve.mul(u2s[i], pubs[i]))
        forced[i] = X is not None and X[0] % n == r0[i]
    return _r1_split_pack(curve, g_idx, q_digits, q_pts, xd_limbs, hg_ok,
                          precheck, forced, w)


def g_window_table_device(curve: WeierstrassCurve, w: int):
    """The affine constant-G table as COMMITTED DEVICE ARRAYS. The table is
    passed to the kernel as arguments, NOT baked in as constants: at w = 8
    the baked-constant form put ~35MB of literals in the HLO, blowing
    compile time to minutes per process (fatal for CPU test runs). As
    committed jax Arrays the upload happens once per process and repeat
    calls pass the same buffers — same zero-transfer steady state."""
    return F.device_table_cache(
        ("g_hybrid", curve.name, w),
        lambda: _g_window_table_wide(curve, w))


def hybrid_ladder_wide(g_idx, q_bits, Qc, Qd, gtab, curve: WeierstrassCurve,
                       g_w: int):
    """The hybrid ladder with a WIDER constant-G window: per outer step,
    ``g_w`` bits are consumed — g_w doublings, g_w/2 Q adds (2-bit per-item
    windows, unchanged), and ONE mixed G add gathered from the affine
    2^(2·g_w+2)-entry table ``gtab`` (see g_window_table_device). Fewer G
    adds per bit is nearly free compute: only the ladder shrinks.

    ``g_idx``: (W_g, B) table indices; ``q_bits``: (W_g, g_w//2, B) packed
    joint Q digits (wc | wd<<2); ``gtab``: (tab_x, tab_y, tab_ok) arrays.
    """
    # (running the 15-deep select tree on u32-downcast table entries was
    # measured FLAT, within the noise band, in an early round — so the
    # tree stays on the u64 limbs the formulas take and return)
    table = _q_window_table(Qc, Qd, curve)
    tab_x, tab_y, tab_ok = gtab
    p = curve.p
    b3 = 3 * curve.b % p

    def q_addend(qb):
        """qb: (B,) packed joint digit wc | wd<<2 — 4 table-index bits in
        one u8 on the wire (the unpacked (B, 4) bit planes were 4x the
        transfer bytes)."""
        return select_tree(table, qb)

    def g_add(acc, gi):
        """Gather the affine G addend and mixed-add it; identity rows
        (flag 0) select the untouched accumulator instead."""
        q2 = (tab_x[gi].astype(jnp.uint64), tab_y[gi].astype(jnp.uint64))
        added = _madd_k1(acc, q2, p, b3)
        ok = tab_ok[gi].astype(jnp.bool_)
        return tuple(F.select(ok, new_c, acc_c)
                     for new_c, acc_c in zip(added, acc))

    def q_step(acc, qb_t):
        acc = dbl(dbl(acc, curve), curve)
        return add(acc, q_addend(qb_t), curve), None

    def step(acc, ins):
        gi, qb = ins                      # qb: (g_w//2, B)
        # inner scan instead of unrolling g_w//2 pairs: the unrolled body
        # made XLA compile time blow up superlinearly with batch size
        # (157s for a CPU bucket-32 at g_w=8; the nested scan also shrinks
        # the cache key's HLO)
        acc, _ = jax.lax.scan(q_step, acc, qb)
        return g_add(acc, gi), None

    # Peel the first outer step: acc is the identity there, so the leading
    # dbl-dbl-add collapses to selecting the first Q addend directly
    # (saves 2 complete dbls + 1 add vs running step 0 through the scan).
    qb0 = q_bits[0]
    acc = q_addend(qb0[0])
    acc, _ = jax.lax.scan(q_step, acc, qb0[1:])
    acc = g_add(acc, g_idx[0])
    # unroll=2 measured 3% SLOWER here on v5e (an early round): the wide
    # step body is already 6 dbl + 4 adds — unrolling doubles an already
    # register-heavy body for nothing
    acc, _ = jax.lax.scan(step, acc, (g_idx[1:], q_bits[1:]))
    return acc


def verify_core_hybrid_wide(g_idx, q_bits, pts, r_limbs,
                            tab_x, tab_y, tab_ok, g_w: int):
    """CONSOLIDATED wire form — 4 per-batch arrays instead of 8 (each
    host→device transfer pays a per-array latency; its size on an
    attached chip has not been measured): ``g_idx`` (W_g, B) i32 with the
    rn_ok flag packed at BIT 18 of row 0 (indices use 2·g_w+2 = 18
    bits); ``pts`` (B, 4, 16) u16 = (Qc_x, Qc_y, Qd_x, Qd_y) limb rows;
    ``q_bits``/``r_limbs`` as before."""
    g_idx = jnp.asarray(g_idx, jnp.int32)
    q_bits = jnp.asarray(q_bits, jnp.uint64)
    pts = jnp.asarray(pts, jnp.uint64)
    r_limbs = jnp.asarray(r_limbs, jnp.uint64)
    rn_ok = ((g_idx[0] >> 18) & 1).astype(jnp.bool_)
    g_idx = g_idx & ((1 << (2 * g_w + 2)) - 1)
    Qc = (pts[:, 0], pts[:, 1])
    Qd = (pts[:, 2], pts[:, 3])
    curve = CURVES["secp256k1"]
    X, Y, Z = hybrid_ladder_wide(g_idx, q_bits, Qc, Qd,
                                 (tab_x, tab_y, tab_ok), curve, g_w)
    return _accept_rn(X, Z, r_limbs, rn_ok, curve.p, curve.n)


_verify_kernel_hybrid_wide = jax.jit(verify_core_hybrid_wide,
                                     static_argnames=("g_w",))


def _bits_to_windows(bits: np.ndarray) -> np.ndarray:
    """(nbits, B) MSB-first bit array → (nbits/2, B) 2-bit digits, MSB-first
    (a leading zero bit is prepended when nbits is odd) — the Q legs'
    per-item window digits."""
    if bits.shape[0] % 2:
        bits = np.concatenate(
            [np.zeros((1,) + bits.shape[1:], bits.dtype), bits])
    return bits[0::2] * 2 + bits[1::2]


def _bits_to_w_windows(bits: np.ndarray, w: int) -> np.ndarray:
    """(nbits, B) MSB-first bits → (nbits//w, B) w-bit digits, MSB-first."""
    n_w = bits.shape[0] // w
    grouped = bits[: n_w * w].reshape(n_w, w, *bits.shape[1:])
    weights = (1 << np.arange(w - 1, -1, -1, dtype=np.uint32))
    return np.tensordot(weights, grouped.astype(np.uint32), axes=([0], [1]))


def _items_to_words(items):
    """(pub, msg, r, s) items → (e, r, s, pub) LE u64 word arrays for the
    native prep (one C-level to_bytes/hash per item — no bigint loops).
    Out-of-range values (negative, ≥ 2^256 — e.g. a hostile DER integer or
    an off-range point) are clamped to encodings the C precheck REJECTS, so
    a malformed item yields a per-item False verdict exactly like the
    Python path, never a batch-level exception."""
    from . import scalarprep as sp
    digests = [hashlib.sha256(msg).digest() for _, msg, _, _ in items]
    e_words = sp.digests_to_words(digests, 4)
    in_range = lambda v: 0 <= v < (1 << 256)
    r_words = sp.ints_to_words([r if in_range(r) else 0
                                for _, _, r, _ in items])
    s_words = sp.ints_to_words([s if in_range(s) else 0
                                for _, _, _, s in items])
    pub_buf = b"".join(
        (pt[0].to_bytes(32, "little") + pt[1].to_bytes(32, "little"))
        if (pt is not None and in_range(pt[0]) and in_range(pt[1]))
        else bytes(64)
        for pt, _, _, _ in items)
    pub_words = np.frombuffer(pub_buf, dtype="<u8").reshape(len(items), 8)
    return e_words, r_words, s_words, pub_words


def _prepare_hybrid_native(items, g_w: int):
    """Native (C) fast path of prepare_batch_hybrid_wide for g_w = 8: the
    whole scalar layer (precheck, batch s-inversion, GLV split, window
    extraction, limb packing) runs in native/scalarmath.cpp — bit-identical
    outputs to the Python path (tests/test_scalarprep.py)."""
    return _prepare_hybrid_native_words(*_items_to_words(items), g_w)


def _prepare_hybrid_native_words(e_words, r_words, s_words, pub_words,
                                 g_w: int):
    """Word-form core of the native hybrid prep: callers that already hold
    the (B, ·) LE u64 rows (the batcher's cached ECDSA prep, the sharded
    mesh entry) feed them straight to sm_k1_prep with no item tuples."""
    from . import scalarprep as sp
    curve = CURVES["secp256k1"]
    n = len(e_words)
    (g_idx, q_packed, qc_x, qc_y, qd_x, qd_y, r_limbs,
     rn_ok, precheck) = sp.k1_prep(e_words, r_words, s_words, pub_words)
    n_g = 128 // g_w
    q_bits = q_packed.reshape(n_g, g_w // 2, n)
    g_idx[0] |= rn_ok.astype(np.int32) << 18      # consolidated wire form
    pts = np.stack([qc_x, qc_y, qd_x, qd_y], axis=1)     # (B, 4, 16)
    return (jnp.asarray(g_idx), jnp.asarray(q_bits), jnp.asarray(pts),
            jnp.asarray(r_limbs),
            *g_window_table_device(curve, g_w), precheck)


def prepare_batch_hybrid_wide(items, g_w: int):
    """Host prep for the wide-G hybrid kernel: GLV-decompose u1 (G legs:
    g_w-bit digits + signs into the gather index — one gather per g_w bits)
    and u2 (Q legs: 2-bit per-item windows, signs folded into the points),
    with the Q window planes grouped per outer step.

    Dispatches to the native (C) scalar layer when libscalarmath is
    available — bit-identical outputs (tests/test_scalarprep.py)."""
    if g_w % 2 or g_w < 2:
        raise ValueError(f"g_w must be even and >= 2, got {g_w}")
    if 2 * g_w + 2 > 18:
        # the consolidated wire form packs rn_ok at g_idx bit 18, above
        # the widest supported index (2·g_w+2 bits); a wider window would
        # silently corrupt a digit bit
        raise ValueError(f"g_w {g_w} exceeds the packed-index budget")
    from . import scalarprep as sp
    if g_w == 8 and sp.available():
        return _prepare_hybrid_native(items, g_w)
    return _prepare_hybrid_python(items, g_w)


def _prepare_hybrid_python(items, g_w: int):
    curve = CURVES["secp256k1"]
    p = curve.p
    precheck, pubs, u1s, u2s, r0, r1 = _precheck_and_scalars(curve, items)
    nbits = -(-GLV_BITS // g_w) * g_w          # pad to a g_w multiple
    sa, sb, abs_a, abs_b = [], [], [], []
    cs, ds, qc_pts, qd_pts = [], [], [], []
    for pub, u1, u2 in zip(pubs, u1s, u2s):
        a, b = glv_decompose(u1)
        c, d = glv_decompose(u2)
        sa.append(a < 0)
        sb.append(b < 0)
        abs_a.append(abs(a))
        abs_b.append(abs(b))
        phi_q = (SECP256K1_BETA * pub[0] % p, pub[1])
        for k, pt, ks, kpts in ((c, pub, cs, qc_pts), (d, phi_q, ds, qd_pts)):
            if k < 0:
                k, pt = -k, (pt[0], (p - pt[1]) % p)
            ks.append(k)
            kpts.append(pt)
    wa = _bits_to_w_windows(F.scalars_to_bits(abs_a, nbits), g_w)
    wb = _bits_to_w_windows(F.scalars_to_bits(abs_b, nbits), g_w)
    g_idx = (wa + (wb << g_w)
             + (np.asarray(sa, dtype=np.uint32)[None, :] << (2 * g_w))
             + (np.asarray(sb, dtype=np.uint32)[None, :] << (2 * g_w + 1))
             ).astype(np.int32 if g_w > 6 else np.uint16)
    wc = _bits_to_windows(F.scalars_to_bits(cs, nbits))
    wd = _bits_to_windows(F.scalars_to_bits(ds, nbits))
    q_packed = (wc | (wd << 2)).astype(np.uint8)           # (nbits/2, B)
    n_g = nbits // g_w
    q_bits = q_packed.reshape(n_g, g_w // 2, *q_packed.shape[1:])
    r_limbs = jnp.asarray(F.to_limbs(r0).astype(np.uint16))
    rn_ok = np.asarray([r + curve.n < curve.p for r in r0], dtype=np.int32)
    g_idx = g_idx.astype(np.int32)
    g_idx[0] |= rn_ok << 18                       # consolidated wire form
    pts = np.stack([F.to_limbs(xs_).astype(np.uint16)
                    for col in (qc_pts, qd_pts)
                    for xs_ in ([p_[0] for p_ in col],
                                [p_[1] for p_ in col])], axis=1)
    return (jnp.asarray(g_idx), jnp.asarray(q_bits), jnp.asarray(pts),
            r_limbs, *g_window_table_device(curve, g_w), precheck)


def verify_core(u1_bits, u2_bits, q_pts, r_cands, curve_name: str):
    """Device core: X = [u1]G + [u2]Q; ok = Z≠0 ∧ x(X) ∈ {r, r+n} candidates.

    r_cands: (2, B, 16) — limb encodings of r and (r+n if r+n<p else r).
    Unjitted and shape-polymorphic so multi-chip callers can wrap it in
    ``shard_map`` over a batch-sharded mesh (corda_tpu.parallel).
    """
    q_pts = tuple(jnp.asarray(c, jnp.uint64) for c in q_pts)
    r_cands = jnp.asarray(r_cands, jnp.uint64)
    curve = CURVES[curve_name]
    p = curve.p
    batch_shape = q_pts[0].shape[:-1]
    base = tuple(jnp.broadcast_to(_const(v, p), batch_shape + (F.NLIMB,))
                 for v in (curve.gx, curve.gy, 1))
    X, Y, Z = shamir_ladder(u1_bits, u2_bits, base, q_pts, curve)
    return _accept(X, Z, r_cands, p)


_verify_kernel = jax.jit(verify_core, static_argnames=("curve_name",))


def prepare_batch(curve: WeierstrassCurve,
                  items: list[tuple[tuple[int, int] | None, bytes, int, int]]):
    """Host prep: (pub_point, message, r, s) → kernel inputs + precheck mask.

    Structural checks mirror the host oracle ecmath.ecdsa_verify (r, s in
    [1, n-1]). Message hashing (SHA-256) stays host-side here; bulk Merkle
    hashing is the device path in ops/sha256.py.
    """
    precheck, q_pts, u1s, u2s, r0, r1 = _precheck_and_scalars(curve, items)
    qx, qy, qz = _points_to_limbs(q_pts)
    r_cands = jnp.asarray(np.stack(
        [F.to_limbs(r0), F.to_limbs(r1)]).astype(np.uint16))
    u1_bits = jnp.asarray(F.scalars_to_bits(u1s))
    u2_bits = jnp.asarray(F.scalars_to_bits(u2s))
    return u1_bits, u2_bits, (qx, qy, qz), r_cands, precheck



def verify_batch_plain(curve: WeierstrassCurve,
                       items: list[tuple[tuple[int, int] | None, bytes, int, int]]
                       ) -> np.ndarray:
    """The plain reference: [(pub_affine, msg, r, s)] → bool verdicts (B,)
    through the 256-bit two-scalar Shamir ladder (:func:`verify_core`), on
    either curve. What the differential tests hold the production ladders
    to on the device side; nothing ships it."""
    n = len(items)
    if n == 0:
        return np.zeros(0, dtype=bool)
    padded = items + [items[-1]] * (F.bucket_size(n) - n)
    u1_bits, u2_bits, q_pts, r_cands, precheck = prepare_batch(curve, padded)
    ok = np.asarray(_verify_kernel(u1_bits, u2_bits, q_pts, r_cands,
                                   curve.name))
    return (ok & precheck)[:n]


def verify_batch(curve: WeierstrassCurve,
                 items: list[tuple[tuple[int, int] | None, bytes, int, int]]
                 ) -> np.ndarray:
    """Batched ECDSA verify: [(pub_affine, msg, r, s)] → bool verdicts (B,)
    through the curve's production ladder (the hybrid GLV ladder for
    secp256k1, the half-gcd split for secp256r1). Pads to a power-of-two
    bucket (replicating the last item) so the device kernel compiles once
    per bucket size."""
    return finish_batch(verify_batch_async(curve, items))


def verify_batch_async(curve: WeierstrassCurve,
                       items: list[tuple[tuple, bytes, int, int]]):
    """Dispatch a verify batch WITHOUT forcing the result: returns an opaque
    pending handle for :func:`finish_batch`. The device computes while the
    caller preps the next batch (the service batcher's one-deep pipeline —
    host prep was ~2/3 of the unpipelined service-path cost). The item
    form: what the batcher takes on a host without libscalarmath.so."""
    from ..observability.profiling import get_profiler
    prof = get_profiler()
    n = len(items)
    if n == 0:
        return (None, np.zeros(0, dtype=bool), 0)
    padded = items + [items[-1]] * (F.bucket_size(n) - n)
    if curve.name == "secp256k1":
        *args, precheck = prepare_batch_hybrid_wide(padded, HYBRID_G_WINDOW)
        return (prof.call("weierstrass.hybrid_k1", _verify_kernel_hybrid_wide,
                          *args, g_w=HYBRID_G_WINDOW, live=n,
                          capacity=len(padded), scheme=curve.name),
                precheck, n)
    *args, precheck, forced = prepare_batch_r1_split(curve, padded)
    return (prof.call("weierstrass.r1_split", _verify_kernel_r1_split,
                      *args, curve_name=curve.name, w=R1_G_WINDOW,
                      live=n, capacity=len(padded), scheme=curve.name),
            precheck, n, forced)


def words_prep_available(curve: WeierstrassCurve) -> bool:
    """True when the word-form fast path (:func:`verify_batch_async_words`)
    covers ``curve``: one of the two curves scalarmath.cpp prepares (at the
    production widths, k1 g_w = 8 and r1 w = 16: the only ones it
    implements) and the library present."""
    from . import scalarprep as sp
    return curve.name in CURVES and sp.available()


def pad_word_rows(arrays, m: int, staging=None, tags=None):
    """Pad each (B, ·) word-row array to m rows by replicating the last row
    (the word-form analog of verify_batch_async's last-item padding — a
    repeated valid row verifies identically and is sliced off by
    finish_batch). With a staging lease, the padded rows land in reused
    pool buffers (one per tag) instead of fresh concatenations — the
    zero-copy-churn seam for the service path's steady-state shapes."""
    n = len(arrays[0])
    if staging is None:
        if m <= n:
            return arrays
        return tuple(np.concatenate([a, np.repeat(a[-1:], m - n, axis=0)])
                     for a in arrays)
    out = []
    for a, tag in zip(arrays, tags):
        buf = staging.take(tag, (m,) + a.shape[1:], a.dtype)
        buf[:n] = a
        if m > n:
            buf[n:] = a[-1]
        out.append(buf)
    return tuple(out)


def verify_batch_async_words(curve: WeierstrassCurve, e_words, r_words,
                             s_words, pub_words, trace_parent=None,
                             capacity: int | None = None):
    """Word-form async dispatch — the batcher's cached/vectorized ECDSA
    prep path: items arrive as the native preps' LE u64 rows (per-signer
    pub rows from keys.sec1_pub_row_cached, r/s from the batched DER
    parse, e from digests_to_words), skipping the per-item decompress +
    DER + to_bytes loop entirely. Same pending/finish contract as
    :func:`verify_batch_async`; callers gate on words_prep_available.
    Padding goes through reused staging buffers, so steady-state flushes
    allocate no fresh host rows; the kernel call is the module's one jit
    handle of the curve's ladder. The native scalar
    prep (range check, s^-1, the split, window digits) is the span
    ``ecdsa.prep.scalars`` under ``trace_parent``, the caller's span; the
    padding before it is ``ecdsa.prep.pad`` and the jitted call alone,
    until it returns, ``batcher.launch``. All three carry ``cpu_s``."""
    from ..observability import get_tracer
    from ..observability.profiling import get_profiler
    from .staging import get_staging_pool
    prof = get_profiler()
    n = len(e_words)
    if n == 0:
        return (None, np.zeros(0, dtype=bool), 0)
    # the compiled shape: the next power of two unless the caller names a
    # larger one (the batcher: a rung of its ladder)
    capacity = max(capacity or 0, F.bucket_size(n))
    pool = get_staging_pool()
    # On any exception below the lease is simply dropped (never released):
    # a partial dispatch may still alias the buffers, so they must not
    # re-enter the free pool.
    lease = pool.lease()
    tracer = get_tracer()
    span_tags = {"bucket": curve.name, "rows": n}
    with tracer.span("ecdsa.prep.pad", parent=trace_parent, cpu=True,
                     **span_tags):
        tags = tuple(f"{curve.name}.{t}" for t in ("e", "r", "s", "pub"))
        e_words, r_words, s_words, pub_words = pad_word_rows(
            (e_words, r_words, s_words, pub_words), capacity,
            staging=lease, tags=tags)
    k1 = curve.name == "secp256k1"
    with tracer.span("ecdsa.prep.scalars", parent=trace_parent, cpu=True,
                     **span_tags):
        if k1:
            *args, precheck = _prepare_hybrid_native_words(
                e_words, r_words, s_words, pub_words, HYBRID_G_WINDOW)
            forced = ()
        else:
            *args, precheck, oracle = _prepare_r1_split_native_words(
                e_words, r_words, s_words, pub_words, R1_G_WINDOW)
            forced = (oracle,)
    with tracer.span("batcher.launch", parent=trace_parent, cpu=True,
                     capacity=capacity, **span_tags) as launch_span:
        if k1:
            dev = prof.call("weierstrass.hybrid_k1",
                            _verify_kernel_hybrid_wide,
                            *args, g_w=HYBRID_G_WINDOW, live=n,
                            capacity=capacity, scheme=curve.name,
                            trace_span=launch_span)
        else:
            dev = prof.call("weierstrass.r1_split",
                            _verify_kernel_r1_split,
                            *args, curve_name=curve.name, w=R1_G_WINDOW,
                            live=n, capacity=capacity, scheme=curve.name,
                            trace_span=launch_span)
    pending = (dev, precheck, n, *forced)
    pool.attach(pending, lease)
    return pending


def finish_batch(pending) -> np.ndarray:
    """Force a verify_batch_async dispatch into host verdicts. Pendings
    are (dev, precheck, n) or, for the half-gcd split path,
    (dev, precheck_eff, n, forced) — forced carries the host-oracle
    verdicts of the per-item fallbacks masked out of precheck_eff.
    The force wall time lands in the flight recorder as device wait,
    attributed to the dispatching kernel via the pending handle. After the
    force the batch's staging lease (if any) returns to the pool — the
    earliest point the host rows provably no longer alias device work."""
    from ..observability.profiling import get_profiler
    from .staging import get_staging_pool
    dev, precheck, n, *rest = pending
    if n == 0:
        return np.zeros(0, dtype=bool)
    prof = get_profiler()
    name = prof.pending_name(dev, "weierstrass")
    t0 = time.perf_counter()
    forced_dev = np.asarray(dev)
    prof.device_wait(name, time.perf_counter() - t0)
    get_staging_pool().release_for(pending)
    ok = forced_dev & precheck
    if rest:
        ok = ok | rest[0]
    return ok[:n]
