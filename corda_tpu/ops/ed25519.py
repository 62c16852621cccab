"""Batched Ed25519 signature verification on device (JAX/XLA, limb arithmetic).

The TPU hot path for the reference's default signature scheme
(EDDSA_ED25519_SHA512, reference Crypto.kt:119,170; per-signature verify at
Crypto.kt:473-496 via the i2p EdDSA JCA provider). Design per SURVEY.md §7
phase 1: batched double-scalar multiplication over 2^255-19 with
limb-decomposed lanes; no data-dependent control flow; `lax.scan` ladder so
the graph stays one-iteration-sized.

Host/device split (host = cheap per-item prep, device = the EC heavy lifting):
- host: point decompression (one sqrt per unique key, kept in the signer
  table of core/crypto/keys.py that the host route reads too), SHA-512
  challenge k = H(R ‖ A ‖ M) mod L (hashlib), range checks, limb packing.
- device: [s]B + [k](-A) via a Shamir/Straus interleaved ladder with unified
  (complete) extended-coordinate addition, projective comparison against R.

Verification equation: accept iff [s]B == R + [k]A  ⟺  [s]B + [k](-A) == R
(point equality; both sides in the full group — unified hwcd-3 addition with
a = -1 square, d non-square is complete on all curve points, so mixed-batch
edge cases like A = identity or doublings need no branches).
"""
from __future__ import annotations

import functools
import hashlib
import time as _time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.crypto import ecmath
from ..core.crypto.keys import signer_point
from . import field as F

P = F.P25519
_D2 = ecmath.ED_D2


def _const(v: int) -> jnp.ndarray:
    return jnp.asarray(F.to_limbs(v))


# Extended coordinates (X, Y, Z, T): a point batch is a tuple of 4 (..., 16)
# u64 limb arrays.

def identity(shape) -> tuple:
    z = jnp.zeros(shape + (F.NLIMB,), dtype=jnp.uint64)
    one = z.at[..., 0].set(1)
    return (z, one, one, z)


def add(Pt, Qt):
    """Unified extended addition (add-2008-hwcd-3, a=-1); complete for
    ed25519's square a / non-square d. Mirrors host ecmath.ed_point_add."""
    x1, y1, z1, t1 = (jnp.asarray(c, jnp.uint64) for c in Pt)
    x2, y2, z2, t2 = (jnp.asarray(c, jnp.uint64) for c in Qt)
    a = F.mul(F.sub(y1, x1, P), F.sub(y2, x2, P), P)
    b = F.mul_of_sums(y1, x1, y2, x2, P)
    c = F.mul(F.mul(t1, _const(_D2), P), t2, P)
    d = F.mul_const(F.mul(z1, z2, P), 2, P)
    e = F.sub(b, a, P)
    f = F.sub(d, c, P)
    g = F.add(d, c, P)
    h = F.add(b, a, P)
    return (F.mul(e, f, P), F.mul(g, h, P), F.mul(f, g, P), F.mul(e, h, P))


def to_cached(Pt):
    """Extended (X, Y, Z, T) → cached (Y+X, Y−X, 2Z, 2d·T): what the unified
    addition computes from its SECOND operand, computed once for an operand
    that is added many times (the split ladder's joint table)."""
    x, y, z, t = Pt
    return (F.add(y, x, P), F.sub(y, x, P), F.mul_const(z, 2, P),
            F.mul(t, _const(_D2), P))


def cached_identity(shape) -> tuple:
    """The identity (0, 1, 1, 0) in cached form: (1, 1, 2, 0)."""
    z = jnp.zeros(shape + (F.NLIMB,), dtype=jnp.uint64)
    one = z.at[..., 0].set(1)
    return (one, one, z.at[..., 0].set(2), z)


def add_cached(Pt, Ct):
    """The unified addition of :func:`add` with its second operand in cached
    form (:func:`to_cached`): 8 products against 9 (no constant product, no
    Z product doubled, no sums on the cached side). As complete as
    :func:`add`: the same formula, the same values."""
    x1, y1, z1, t1 = Pt
    ypx, ymx, z2, t2d = Ct
    a = F.mul(F.sub(y1, x1, P), ymx, P)
    b = F.norm(F.mul_cols(F.rel_add(y1, x1), ypx), P)
    c = F.mul(t1, t2d, P)
    d = F.mul(z1, z2, P)
    e = F.sub(b, a, P)
    f = F.sub(d, c, P)
    g = F.add(d, c, P)
    h = F.add(b, a, P)
    return (F.mul(e, f, P), F.mul(g, h, P), F.mul(f, g, P), F.mul(e, h, P))


def double(Pt):
    """dbl-2008-hwcd (valid for all inputs; mirrors ecmath.ed_point_double).
    Reads X, Y, Z of its input and never T."""
    x1, y1, z1, _ = (jnp.asarray(c, jnp.uint64) for c in Pt)
    a = F.sqr(x1, P)
    b = F.sqr(y1, P)
    c = F.mul_const(F.sqr(z1, P), 2, P)
    h = F.add(a, b, P)
    e = F.sub(h, F.sqr_of_sum(x1, y1, P), P)
    g = F.sub(a, b, P)
    f = F.add(c, g, P)
    return (F.mul(e, f, P), F.mul(g, h, P), F.mul(f, g, P), F.mul(e, h, P))


def negate(Pt):
    x, y, z, t = Pt
    return (F.neg(x, P), y, z, F.neg(t, P))


def _select4(idx, P0, P1, P2, P3):
    """Branchless 4-way point select by idx (...,) in {0,1,2,3}."""
    def pick(c0, c1, c2, c3):
        return F.select(idx == 3, c3,
                        F.select(idx == 2, c2,
                                 F.select(idx == 1, c1, c0)))
    return tuple(pick(*cs) for cs in zip(P0, P1, P2, P3))


def shamir_ladder(bits1, bits2, P1, P2):
    """[k1]P1 + [k2]P2 by interleaved double-and-add.

    ``bits1``/``bits2``: (256, ...) MSB-first bit arrays; ``P1``/``P2``:
    extended point batches. One double + one (possibly-identity) complete
    add per bit; `lax.scan` keeps the graph one-iteration-sized.
    """
    batch_shape = P1[0].shape[:-1]
    P3 = add(P1, P2)
    Pid = identity(batch_shape)

    def step(acc, bits):
        b1, b2 = bits
        acc = double(acc)
        idx = b1 + 2 * b2
        addend = _select4(idx, Pid, P1, P2, P3)
        return add(acc, addend), None

    acc, _ = jax.lax.scan(step, Pid, (bits1.astype(jnp.uint64),
                                      bits2.astype(jnp.uint64)), unroll=2)
    return acc


# ---------------------------------------------------------------------------
# Constant-B Niels tables (the split ladder's two bases, B and [2^128]B)
# ---------------------------------------------------------------------------

#: Constant-base window width for the split-k ladder (128 = 8x16 divides
#: exactly: 8 outer steps of 16 doubles + 8 joint A adds + 1 B + 1 B' add).
SPLIT_B_WINDOW = 16

_B_TABLES: dict[tuple, tuple] = {}


def _shift_base(k: int):
    """[2^k]B as an affine point (host chain, one-time per process)."""
    ext = ecmath.ed_to_extended(ecmath.ED_B)
    for _ in range(k):
        ext = ecmath.ed_point_double(ext)
    zi = pow(ext[2], P - 2, P)
    return (ext[0] * zi % P, ext[1] * zi % P)


def _b_window_table(w: int, shift: int = 0):
    """(2^w, NLIMB) u16 arrays (y+x, y−x, 2d·x·y) of wa·[2^shift]B — the
    Niels/Duif precomputed form the mixed add consumes. Row 0 (the
    identity) is naturally (1, 1, 0): valid input to the mixed add, NO
    flag machinery (unlike the Weierstrass table's Z=0 rows). Built
    host-side with one Montgomery batch inversion for all the affine-add
    denominators. ``shift=128`` builds the split-k ladder's second
    constant-base table ([2^128]B — see split_ladder)."""
    key = (w, shift)
    if key in _B_TABLES:
        return _B_TABLES[key]
    span = 1 << w
    # chain wa·base in EXTENDED coordinates (no inversion per add), then one
    # Montgomery batch inversion of every Z to land affine
    from .weierstrass import _batch_modinv
    base = ecmath.ED_B if shift == 0 else _shift_base(shift)
    ext = [None] * span
    ext[1] = ecmath.ed_to_extended(base)
    for wa in range(2, span):
        ext[wa] = ecmath.ed_point_add(ext[wa - 1], ext[1])
    zinvs = iter(_batch_modinv([e[2] for e in ext[1:]], P))
    ps, ms, tds = [1], [1], [0]   # identity row: (1, 1, 0)
    for e in ext[1:]:
        zi = next(zinvs)
        x = e[0] * zi % P
        y = e[1] * zi % P
        ps.append((y + x) % P)
        ms.append((y - x) % P)
        tds.append(ecmath.ED_D2 * x % P * y % P)
    tab = tuple(F.to_limbs(v).astype(np.uint16) for v in (ps, ms, tds))
    _B_TABLES[key] = tab
    return tab


def b_table_device(w: int = SPLIT_B_WINDOW, shift: int = 0):
    """The Niels base table as committed device arrays (kernel ARGUMENTS,
    not baked constants — see weierstrass.g_window_table_device)."""
    return F.device_table_cache(("niels_b", w, shift),
                                lambda: _b_window_table(w, shift))


def madd_niels(Pt, tab_p, tab_m, tab_td):
    """Mixed add of a precomputed Niels point (y+x, y−x, 2dxy), Z2 = 1 —
    7 full muls vs the unified add's 9 (add-2008-hwcd-3 with the Z2
    product and both input rotations folded into the table entries).
    Complete for every accumulator, identity rows (1, 1, 0) included."""
    x1, y1, z1, t1 = Pt
    a = F.mul(F.sub(y1, x1, P), tab_m, P)
    b = F.mul(F.add(y1, x1, P), tab_p, P)
    c = F.mul(t1, tab_td, P)
    d = F.mul_const(z1, 2, P)
    e = F.sub(b, a, P)
    f = F.sub(d, c, P)
    g = F.add(d, c, P)
    h = F.add(b, a, P)
    return (F.mul(e, f, P), F.mul(g, h, P), F.mul(f, g, P), F.mul(e, h, P))


# ---------------------------------------------------------------------------
# Split-k windowed ladder: both scalars split at bit 128, HALVING the
# doublings (the dominant ladder cost) — the ed25519 analog of secp256k1's
# GLV shape (edwards25519 has no endomorphism, but [k]A = [k_lo]A +
# [k_hi]([2^128]A) needs only a per-SIGNER precomputation of [2^128]A,
# cached host-side like the decompression):
#   [s]B + [k](−A) = [s_lo]B + [s_hi]B' + [k_lo](−A) + [k_hi](−A')
# with B' = [2^128]B (a CONSTANT → second Niels table) and A' = [2^128]A.
#
# Field operations a signature at w = 16 (M a product, S a squaring; counted
# from the code below, pinned by split_field_products and its test):
#   joint table    2 doubles + 11 unified adds + 15 cached forms   8 S + 122 M
#   126 doublings  4 S + 4 M (the compiler drops the T product of the
#                  first of each pair: a doubling reads no T)      504 S + 441 M
#   63 joint adds  cached form: 8 M                                      504 M
#   16 Niels adds  7 M                                                   112 M
#   tail           one inversion per INV_BATCH_STOP rows + 3 M a row,
#                  2 M to land affine, 3 canonical forms                  ~5 M
# against the plain Shamir ladder's 256 doublings + 256 unified adds.
# T IS computed by every joint add though only the last of a window is read
# (by the Niels add after it): leaving those 55 products out, by flags and a
# window peeled or reordered, was measured 5% SLOWER on v5e (PERF.md, PR 30).
# ---------------------------------------------------------------------------

def _stack(*pts) -> tuple:
    """Point batches → one point batch with a new leading axis."""
    return tuple(jnp.stack(cs) for cs in zip(*pts))


def _unstack(pt, n: int) -> list:
    return [tuple(c[i] for c in pt) for i in range(n)]


def _joint_a_table(neg_a, neg_a2):
    """16-entry per-item table T[i + 4j] = [i](−A) + [j](−A') (i, j ∈ [0,4))
    in CACHED form (see to_cached), from AFFINE (x, y, t) triples (z = 1
    implied): 2 doubles + 11 unified adds + 15 cached forms, one-time per
    batch — the Edwards sibling of the k1 Q window table
    (weierstrass._q_window_table). Operations of one kind on independent
    entries are traced ONCE over a stacked axis (3 formula graphs, not 13:
    the field work is the same, the program a third of the size)."""
    ax, ay, at = neg_a
    a2x, a2y, a2t = neg_a2
    one = F.one_like(ax)
    t1, t4 = (ax, ay, one, at), (a2x, a2y, one, a2t)
    base = _stack(t1, t4)
    t2, t8 = _unstack(double(base), 2)
    t3, t12 = _unstack(add(_stack(t2, t8), base), 2)
    lo, hi = (t1, t2, t3), (t4, t8, t12)
    mixed = _unstack(add(_stack(*(h for h in hi for _ in lo)),
                         _stack(*(l for _ in hi for l in lo))), 9)
    entries = [t1, t2, t3, t4, *mixed[0:3], t8, *mixed[3:6], t12, *mixed[6:9]]
    return [cached_identity(ax.shape[:-1]),
            *_unstack(to_cached(_stack(*entries)), 15)]


def split_ladder(b_idx, b2_idx, a_packed, neg_a, neg_a2, btab, b2tab,
                 w: int):  # noqa: D401 — see verify_core_split for wire form
    """[s_lo]B + [s_hi]B' + [k_lo](−A) + [k_hi](−A') over 128 bits.

    ``b_idx``/``b2_idx``: (128/w, B) Niels-table indices for the two
    constant bases; ``a_packed``: (128/w, w/2, B) packed 2-bit joint digits
    (k_lo | k_hi<<2); ``neg_a``/``neg_a2``: affine (x, y, t) limb triples;
    ``btab``/``b2tab``: the (2^w, NLIMB) Niels arrays for B and [2^128]B."""
    table = _joint_a_table(neg_a, neg_a2)
    tab_p, tab_m, tab_td = btab
    tab2_p, tab2_m, tab2_td = b2tab

    def joint_addend(qb):
        """qb: (B,) packed joint digit klo | khi<<2 — the shared 16-way
        select tree (weierstrass.select_tree)."""
        from .weierstrass import select_tree
        return select_tree(table, qb)

    def b_adds(acc, bi, b2i):
        acc = madd_niels(acc, tab_p[bi].astype(jnp.uint64),
                         tab_m[bi].astype(jnp.uint64),
                         tab_td[bi].astype(jnp.uint64))
        return madd_niels(acc, tab2_p[b2i].astype(jnp.uint64),
                          tab2_m[b2i].astype(jnp.uint64),
                          tab2_td[b2i].astype(jnp.uint64))

    def a_step(acc, qb):
        acc = double(double(acc))
        return add_cached(acc, joint_addend(qb)), None

    def step(acc, ins):
        bi, b2i, qbs = ins
        acc, _ = jax.lax.scan(a_step, acc, qbs)
        return b_adds(acc, bi, b2i), None

    # peel step 0: the accumulator is the identity, so the leading
    # double-double-add collapses to selecting the first joint addend; a
    # doubling comes next, which reads (2X, 2Y, 2Z): the same point
    ypx, ymx, z2, _ = joint_addend(a_packed[0][0])
    acc = (F.sub(ypx, ymx, P), F.add(ypx, ymx, P), z2, jnp.zeros_like(z2))
    acc, _ = jax.lax.scan(a_step, acc, a_packed[0][1:])
    acc = b_adds(acc, b_idx[0], b2_idx[0])
    acc, _ = jax.lax.scan(step, acc, (b_idx[1:], b2_idx[1:], a_packed[1:]))
    return acc


def reencode_verdict(acc, r_y, r_sign):
    """RFC 8032 re-encoding acceptance of a projective result: the affine
    y, canonical, equals the wire y and the affine x's parity the wire sign
    bit. ONE field inversion serves the batch (F.inv_batch), and no row's
    verdict reads another row's Z: a row whose Z is ≡ 0 (the complete
    formulas give none for points on the curve) is refused by itself."""
    x, y, z, _ = acc
    zi, nonzero = F.inv_batch(z, P)
    x_aff = F.canon(F.mul(x, zi, P), P)
    y_aff = F.canon(F.mul(y, zi, P), P)
    ok_y = jnp.all(y_aff == r_y, axis=-1)
    ok_sign = (x_aff[..., 0] & 1) == r_sign
    return nonzero & ok_y & ok_sign


def verify_core_split(bb_idx, a_packed, rows, r_packed,
                      tab_p, tab_m, tab_td, tab2_p, tab2_m, tab2_td,
                      w: int):
    """Split-k verify: RFC 8032 re-encoding acceptance (see
    reencode_verdict: the wire y and sign bit against the DEVICE-computed
    affine point, so the host never pays the per-item modular sqrt of
    decompressing R) over the half-length ladder.

    CONSOLIDATED wire form — 4 per-batch arrays instead of 12: every
    host→device transfer pays a per-array latency on top of bandwidth
    (its size on an attached chip has not been measured).
    ``bb_idx``: (16, B) i32 = b_idx ‖ b2_idx; ``a_packed``: (8, w/2, B)
    u8 joint digits; ``rows``: (B, 6, 16) u16 = (−A x, y, t, −A' x, y,
    t) limb rows; ``r_packed``: (B, 16) u16 wire y with the SIGN bit in
    limb 15 bit 15 (the y value itself is < 2^255)."""
    bb_idx = jnp.asarray(bb_idx, jnp.int32)
    a_packed = jnp.asarray(a_packed, jnp.uint64)
    rows = jnp.asarray(rows, jnp.uint64)
    r_packed = jnp.asarray(r_packed, jnp.uint64)
    b_idx, b2_idx = bb_idx[:8], bb_idx[8:]
    neg_a = tuple(rows[:, j] for j in range(3))
    neg_a2 = tuple(rows[:, 3 + j] for j in range(3))
    r_sign = r_packed[..., 15] >> 15
    r_y = r_packed.at[..., 15].set(r_packed[..., 15] & 0x7FFF)
    acc = split_ladder(b_idx, b2_idx, a_packed, neg_a, neg_a2,
                       (tab_p, tab_m, tab_td), (tab2_p, tab2_m, tab2_td), w)
    return reencode_verdict(acc, r_y, r_sign)


_verify_kernel_split = jax.jit(verify_core_split, static_argnames=("w",))


def _limb_multiplies(jaxpr) -> int:
    """Elements of every integer ``mul`` in a jaxpr, whatever its lanes
    (the product's int32 digits, a fold's or a scale's constant, the tails'
    uint64), a scan's body counted once per iteration."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "mul":
            out = eqn.outvars[0].aval
            if jnp.issubdtype(out.dtype, jnp.integer):
                total += out.size
        times = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += times * _limb_multiplies(sub)
    return total


@functools.lru_cache(maxsize=None)
def split_field_products(rows: int, w: int = SPLIT_B_WINDOW) -> int:
    """Limb multiplications one signature costs in ``verify_core_split``
    at a ``rows``-wide batch, counted from the program itself (its jaxpr,
    scan bodies times their lengths; a full field product is 16 x 16
    balanced digits, two rows for the operands' top carries, and its
    folds: 307; a squaring 171). The flight recorder's ``ed25519.split`` record carries it,
    so a trace says which kernel ran; the trace behind it is the one the
    first dispatch of the bucket makes anyway."""
    S = jax.ShapeDtypeStruct
    table = S((1 << w, F.NLIMB), jnp.uint16)
    traced = _verify_kernel_split.trace(
        S((256 // w, rows), jnp.int32), S((128 // w, w // 2, rows), jnp.uint8),
        S((rows, 6, F.NLIMB), jnp.uint16), S((rows, F.NLIMB), jnp.uint16),
        *(table,) * 6, w=w)
    return round(_limb_multiplies(traced.jaxpr) / rows)


def verify_core(s_bits, k_bits, neg_a, r_affine):
    """Device core: ok[i] = ([s]B + [k](-A) == R) per batch item.

    neg_a: extended -A batch; r_affine: (Rx, Ry) limb batch.
    Unjitted and shape-polymorphic so multi-chip callers can wrap it in
    ``shard_map`` over a batch-sharded mesh (corda_tpu.parallel).
    """
    # upcast the compact wire dtypes (u16 limbs / u8 bit planes) on device
    neg_a = tuple(jnp.asarray(c, jnp.uint64) for c in neg_a)
    r_affine = tuple(jnp.asarray(c, jnp.uint64) for c in r_affine)
    batch_shape = neg_a[0].shape[:-1]
    bx, by = ecmath.ED_B
    base = tuple(jnp.broadcast_to(_const(v), batch_shape + (F.NLIMB,))
                 for v in (bx, by, 1, bx * by % P))
    acc = shamir_ladder(s_bits, k_bits, base, neg_a)
    x, y, z, _ = acc
    rx, ry = r_affine
    # Projective equality vs affine R: X == Rx·Z and Y == Ry·Z.
    ok_x = F.eq(x, F.mul(rx, z, P), P)
    ok_y = F.eq(y, F.mul(ry, z, P), P)
    return ok_x & ok_y


_verify_kernel = jax.jit(verify_core)


def _pack_point_ext(pts) -> tuple:
    """List of affine (x, y) → extended-coordinate limb batch. Ships u16
    (canonical 16-bit limbs); the kernel upcasts on device — u64 on the
    wire was 4x the transfer bytes for no information."""
    xs = F.to_limbs([p[0] for p in pts]).astype(np.uint16)
    ys = F.to_limbs([p[1] for p in pts]).astype(np.uint16)
    zs = np.zeros_like(xs)
    zs[..., 0] = 1
    ts = F.to_limbs([p[0] * p[1] % P for p in pts]).astype(np.uint16)
    return tuple(jnp.asarray(v) for v in (xs, ys, zs, ts))


def _decompress_a(pub: bytes):
    """The signer's key as an affine point, from the ONE per-signer table
    (core/crypto/keys.py ``SignerTable``) that ``Crypto.is_valid`` reads
    too: the sqrt inside ed_point_decompress is ~2 modpows of host bigint
    work per call, and a node verifies the same signers' keys over and over
    (the service path is host-CPU-bound), on whichever route."""
    return signer_point("ed25519", pub)


def _row_from_affine(A) -> np.ndarray:
    """Affine A → the split kernel's packed per-signer row: (−A, −A') as
    two affine (x, y, t) limb triples in one (6, 16) u16 array, where
    A' = [2^128]A (128 host doublings + one inversion — per NEW signer
    only; see _signer_row)."""
    x, y = A
    ext = ecmath.ed_to_extended(A)
    for _ in range(128):
        ext = ecmath.ed_point_double(ext)
    zi = pow(ext[2], P - 2, P)
    x2, y2 = ext[0] * zi % P, ext[1] * zi % P
    nx, nx2 = (P - x) % P, (P - x2) % P
    vals = [nx, y, nx * y % P, nx2, y2, nx2 * y2 % P]
    return F.to_limbs(vals).astype(np.uint16)


@functools.lru_cache(maxsize=65536)
def _signer_row(pub: bytes):
    """Per-signer cache of the split kernel's (−A, −A') limb row (None for
    an invalid key). The [2^128]A precomputation rides the same
    signers-repeat locality as _decompress_a; a cold signer costs ~0.5ms of
    host bigints ONCE, then every batch containing it is a numpy row copy."""
    A = _decompress_a(pub)
    return None if A is None else _row_from_affine(A)


@functools.lru_cache(maxsize=1)
def _substitute_row() -> np.ndarray:
    """Row substituted for structurally-invalid items (base point, matching
    the plain path's A := ED_B substitution; verdict masked by precheck)."""
    return _row_from_affine(ecmath.ED_B)


def _precheck_items(items):
    """The plain reference's host-side structural checks and scalars, one
    loop: lengths, the key and R as points (R pays a modular sqrt here;
    the split kernel verifies by RE-ENCODING the computed point and its
    prep only range-checks the raw y), s < L, k = SHA-512(R ‖ A ‖ M) mod L.
    Returns (precheck, A points, R points, s scalars, k scalars)."""
    n = len(items)
    precheck = np.ones(n, dtype=bool)
    a_pts, r_pts, ss, ks = [], [], [], []
    for i, (pub, sig, msg) in enumerate(items):
        ok = len(sig) == 64
        R = None
        if ok:
            s = int.from_bytes(sig[32:], "little")
            A = _decompress_a(bytes(pub))
            # a non-canonical y (>= p) fails the decompression, as the
            # oracle's ed_point_decompress has it
            R = ecmath.ed_point_decompress(sig[:32])
            ok = A is not None and R is not None and s < ecmath.ED_L
        if not ok:
            precheck[i] = False
            A, R, s, k = ecmath.ED_B, ecmath.ED_B, 0, 0
        else:
            h = hashlib.sha512(sig[:32] + pub + msg).digest()
            k = int.from_bytes(h, "little") % ecmath.ED_L
        a_pts.append(A)
        r_pts.append(R)
        ss.append(s)
        ks.append(k)
    return precheck, a_pts, r_pts, ss, ks


def prepare_batch(items: list[tuple[bytes, bytes, bytes]]):
    """Host prep: (public_key32, signature64, message) triples → kernel inputs.

    Returns (s_bits, k_bits, neg_a, r_affine, precheck) where precheck[i] is
    False for items that already failed host-side structural checks (bad point
    encoding, s out of range — reference doVerify raises on malformed input,
    we map to verdict False and let the caller decide). Failed items are
    substituted with the base point so shapes stay static.
    """
    precheck, a_pts, r_pts, ss, ks = _precheck_items(items)
    neg_a = _pack_point_ext([(P - x, y) for x, y in a_pts])
    rx = jnp.asarray(F.to_limbs([p[0] for p in r_pts]).astype(np.uint16))
    ry = jnp.asarray(F.to_limbs([p[1] for p in r_pts]).astype(np.uint16))
    s_bits = jnp.asarray(F.scalars_to_bits(ss))
    k_bits = jnp.asarray(F.scalars_to_bits(ks))
    return s_bits, k_bits, neg_a, (rx, ry), precheck


def _columns(items):
    """(pub32, sig64, msg) triples → the word prep's three lists."""
    return ([bytes(pub) for pub, _, _ in items],
            [sig for _, sig, _ in items], [msg for _, _, msg in items])


def _signer_slots(keys):
    """The batch's DISTINCT signers as the word prep's slot table, one
    ``_signer_row`` lookup each: (which (n,) i32 — each row's slot —,
    slot_keys (S, 32) u8, slot_rows (S, 6, 16) u16, slot_ok (S,) u8; a key
    that is no point keeps zeros and ok = 0)."""
    slot = {k: j for j, k in enumerate(dict.fromkeys(keys))}
    slot_keys = np.zeros((len(slot), 32), dtype=np.uint8)
    slot_rows = np.zeros((len(slot), 6, F.NLIMB), dtype=np.uint16)
    slot_ok = np.zeros(len(slot), dtype=np.uint8)
    for k, j in slot.items():
        row = _signer_row(k)
        if row is not None:
            slot_keys[j] = np.frombuffer(k, dtype=np.uint8)
            slot_rows[j], slot_ok[j] = row, 1
    which = np.fromiter(map(slot.__getitem__, keys), dtype=np.int32,
                        count=len(keys))
    return which, slot_keys, slot_rows, slot_ok


def prepare_words_split(keys, sigs, msgs, capacity: int | None = None,
                        w: int = SPLIT_B_WINDOW, device_tables: bool = True,
                        staging=None, trace_parent=None):
    """Host prep for the split-k kernel in WORD form: the rows' keys,
    signatures and messages as three lists, taken in bulk. Python builds
    only the inputs of ONE native call (scalarprep.ed_prep_words: parse,
    SHA-512 challenges, scalars, windows, the signers' rows gathered, the
    padding up to ``capacity``), one ``_signer_row`` lookup a DISTINCT
    signer, and hands the four wire arrays over in one ``device_put``.
    Without libscalarmath.so the same inputs go through
    :func:`_prep_words_python`, bit-identical and slow.

    Returns (bb_idx, a_packed, rows, r_packed, [tables...], precheck) —
    the consolidated 4-array wire form of verify_core_split.

    Under ``trace_parent`` (the batcher's ``batcher.dispatch`` span) the
    five phases are its children ``ed25519.prep.sig`` (the signatures'
    join and lengths) / ``.keys`` (the distinct signers' slot table and
    the rows' index into it) / ``.digest`` (the messages' join and
    lengths: the hash's input) / ``.scalars`` (the one native call) /
    ``.handover`` (the transfer), each tagged ``bucket`` and ``rows`` and
    carrying ``cpu_s``; without one (the mesh route, the tools) no span
    is opened."""
    from ..observability.tracing import NOOP_TRACER, get_tracer
    from . import scalarprep as sp
    assert w == 16, "split prep emits 16-bit constant-base windows"
    if capacity is None:
        capacity = len(keys)
    tracer = get_tracer() if trace_parent is not None else NOOP_TRACER
    tags = {"bucket": "ed25519", "rows": capacity}
    with tracer.span("ed25519.prep.sig", parent=trace_parent, cpu=True,
                     **tags):
        sig_buf, sig_len = sp.join_rows(sigs)
    with tracer.span("ed25519.prep.keys", parent=trace_parent, cpu=True,
                     **tags):
        which, slot_keys, slot_rows, slot_ok = _signer_slots(keys)
    with tracer.span("ed25519.prep.digest", parent=trace_parent, cpu=True,
                     **tags):
        msg_buf, msg_len = sp.join_rows(msgs)
    with tracer.span("ed25519.prep.scalars", parent=trace_parent, cpu=True,
                     **tags):
        # ``staging`` (ops.staging.StagingLease) reuses the largest
        # per-batch host buffer across flushes of the same bucket size —
        # every row is overwritten, so carried-over data never leaks into
        # a verdict
        rows_out = (staging.take("ed.rows", (capacity, 6, F.NLIMB),
                                 np.uint16)
                    if staging is not None else None)
        prep = sp.ed_prep_words if sp.available() else _prep_words_python
        bb_idx, a_packed, rows, r_packed, precheck = prep(
            sig_buf, sig_len, msg_buf, msg_len, which, slot_keys, slot_rows,
            slot_ok, _substitute_row(), capacity, rows_out)
    with tracer.span("ed25519.prep.handover", parent=trace_parent, cpu=True,
                     **tags):
        head = jax.device_put(
            (bb_idx, a_packed.reshape(128 // w, w // 2, capacity), rows,
             r_packed))
        if device_tables:
            return (*head, *b_table_device(w, 0), *b_table_device(w, 128),
                    precheck)
        return (*head, precheck)


def prepare_batch_split(items: list[tuple[bytes, bytes, bytes]],
                        w: int = SPLIT_B_WINDOW, device_tables: bool = True,
                        staging=None, trace_parent=None):
    """:func:`prepare_words_split` for (public_key32, signature64, message)
    triples (the mesh route, the tools, the tests): ONE prep behind both
    forms."""
    return prepare_words_split(*_columns(items), None, w, device_tables,
                               staging, trace_parent)


def _prep_words_python(sig_buf, sig_len, msg_buf, msg_len, which, slot_keys,
                       slot_rows, slot_ok, sub_row, capacity, rows_out=None):
    """scalarprep.ed_prep_words as numpy and Python loops: what runs where
    libscalarmath.so is absent or stale, and the oracle the native call is
    held to row for row (tests/test_scalarprep.py). Same arguments, same
    five arrays, bit for bit."""
    n = len(which)
    sig_at = np.concatenate(([0], np.cumsum(sig_len)))
    msg_at = np.concatenate(([0], np.cumsum(msg_len)))
    sig_ok = sig_len == 64
    if sig_ok.all():
        sig_mat = sig_buf.reshape(n, 64)
    else:
        sig_mat = np.zeros((n, 64), dtype=np.uint8)
        for i in np.flatnonzero(sig_ok):
            sig_mat[i] = sig_buf[sig_at[i]:sig_at[i] + 64]
    r_packed = sig_mat[:, :32].copy().view("<u2")       # (n, 16) wire y
    # the wire sign bit stays IN limb 15 bit 15 (the kernel unpacks it);
    # range checks use the masked view
    y15 = r_packed[:, 15] & 0x7FFF
    # non-canonical y (>= p = 2^255-19) rejects like a failed decompression
    ge_p = ((r_packed[:, 0] >= 0xFFED) & (y15 == 0x7FFF)
            & (r_packed[:, 1:15] == 0xFFFF).all(axis=1))
    s_words = sig_mat[:, 32:].copy().view("<u8")        # (n, 4)
    keyed = sig_ok & slot_ok[which].astype(bool)   # length and key passed
    # k := 0 where the key or the length was refused: such a row does not
    # hash (the verdict is masked anyway)
    zero = bytes(64)
    digests = [hashlib.sha512(sig_mat[i, :32].tobytes()
                              + slot_keys[which[i]].tobytes()
                              + msg_buf[msg_at[i]:msg_at[i + 1]].tobytes()
                              ).digest() if keyed[i] else zero
               for i in range(n)]
    b_idx, b2_idx, a_packed, s_ok = _split_windows_python(digests, s_words)
    precheck = keyed & ~ge_p & s_ok
    # rows n .. capacity-1 repeat row n-1 (the kernels' padding)
    fill = np.minimum(np.arange(capacity), n - 1)
    rows = (np.empty((capacity, 6, F.NLIMB), dtype=np.uint16)
            if rows_out is None else rows_out)
    rows[:] = np.where(keyed[:, None, None], slot_rows[which], sub_row)[fill]
    return (np.concatenate([b_idx, b2_idx])[:, fill], a_packed[:, fill],
            rows, r_packed[fill], precheck[fill])


def _split_windows_python(digests: list[bytes], s_words: np.ndarray):
    """The split ladder's windows of a batch, from the rows' SHA-512
    digests and s words: the scalar half of :func:`_prep_words_python`."""
    from .weierstrass import _bits_to_w_windows, _bits_to_windows
    n = len(digests)
    mask128 = (1 << 128) - 1
    s_ints = [int.from_bytes(s_words[i].tobytes(), "little")
              for i in range(n)]
    s_ok = np.array([s < ecmath.ED_L for s in s_ints], dtype=bool)
    ss = [s if ok else 0 for s, ok in zip(s_ints, s_ok)]
    ks = [int.from_bytes(d, "little") % ecmath.ED_L if ok else 0
          for d, ok in zip(digests, s_ok)]
    b_idx = _bits_to_w_windows(
        F.scalars_to_bits([s & mask128 for s in ss], 128), 16).astype(
            np.int32)
    b2_idx = _bits_to_w_windows(
        F.scalars_to_bits([s >> 128 for s in ss], 128), 16).astype(np.int32)
    klo = _bits_to_windows(F.scalars_to_bits([k & mask128 for k in ks], 128))
    khi = _bits_to_windows(F.scalars_to_bits([k >> 128 for k in ks], 128))
    a_packed = (klo | (khi << 2)).astype(np.uint8)
    return b_idx, b2_idx, a_packed, s_ok


def verify_batch(items: list[tuple[bytes, bytes, bytes]]) -> np.ndarray:
    """Batched Ed25519 verify: [(pub32, sig64, msg)] → bool verdicts (B,).

    Pads the batch to a power-of-two bucket (replicating the last item) so the
    device kernel compiles once per bucket size — the batching-service analog
    of the reference's fixed verifier thread pool
    (InMemoryTransactionVerifierService.kt:10-16)."""
    pending = verify_batch_async(items)
    return finish_batch(pending)


def verify_batch_async(items: list[tuple[bytes, bytes, bytes]],
                       trace_parent=None):
    """:func:`verify_batch_async_words` for (pub32, sig64, msg) triples."""
    return verify_batch_async_words(*_columns(items), trace_parent)


def verify_batch_async_words(keys, sigs, msgs, trace_parent=None,
                             capacity: int | None = None):
    """Dispatch without forcing (see weierstrass.verify_batch_async): the
    device computes while the caller preps the next batch. Rides the
    split-k half-length ladder — the fastest measured path (PERF.md
    section 5) — through the module's one jit handle, with leased host
    staging arrays (ops.staging). The rows arrive as
    three lists (:func:`prepare_words_split`, which also pads them to the
    bucket). Dispatches go through the kernel flight recorder
    (observability.profiling): compile-cache accounting + batch occupancy.
    ``trace_parent`` is the batcher's ``batcher.dispatch`` span: the prep's
    phases and ``batcher.launch``, the jitted call alone until it returns,
    are its children. ``capacity`` is the row count the batch is padded
    to (the compiled shape): the next power of two unless the caller names
    a larger one (the batcher: a rung of its ladder)."""
    from ..observability.profiling import get_profiler
    from ..observability.tracing import get_tracer
    from .staging import get_staging_pool
    n = len(keys)
    if n == 0:
        return (None, np.zeros(0, dtype=bool), 0)
    capacity = max(capacity or 0, F.bucket_size(n))
    pool = get_staging_pool()
    lease = pool.lease()
    *args, precheck = prepare_words_split(
        keys, sigs, msgs, capacity, SPLIT_B_WINDOW, staging=lease,
        trace_parent=trace_parent)
    with get_tracer().span("batcher.launch", parent=trace_parent, cpu=True,
                           bucket="ed25519", rows=n,
                           capacity=capacity) as lspan:
        dev = get_profiler().call(
            "ed25519.split", _verify_kernel_split, *args,
            w=SPLIT_B_WINDOW, live=n, capacity=capacity,
            scheme="ed25519",
            field_products_per_row=functools.partial(
                split_field_products, capacity, SPLIT_B_WINDOW),
            trace_span=lspan)
    pending = (dev, precheck, n)
    # the lease rides the pending handle: finish_batch releases it after
    # the force, the earliest point the device provably no longer reads
    # the staged host memory (CPU device_put zero-copies; TPU H2D is
    # async)
    pool.attach(pending, lease)
    return pending


def finish_batch(pending) -> np.ndarray:
    from ..observability.profiling import get_profiler
    from .staging import get_staging_pool
    dev, precheck, n = pending
    if n == 0:
        return np.zeros(0, dtype=bool)
    prof = get_profiler()
    name = prof.pending_name(dev, "ed25519.split")
    t0 = _time.perf_counter()
    ok = np.asarray(dev)
    prof.device_wait(name, _time.perf_counter() - t0)
    # forced above → the staged host buffers are free for the next batch
    # (on a failed force the lease stays attached and is evicted, never
    # reused — a crash cannot corrupt a later batch)
    get_staging_pool().release_for(pending)
    return (ok & precheck)[:n]
