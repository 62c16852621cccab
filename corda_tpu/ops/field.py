"""Batched 256-bit prime-field arithmetic on 16-bit limbs, computed at the
vector unit's own width.

The bigint engine under both curve kernels (ed25519.py, weierstrass.py).
Design (SURVEY.md §7 phase 1 "limb-decomposed lanes"):

- A field element is ``u64[..., 16]`` AT THE SEAMS (kernel arguments, scan
  carries, tables, what every formula takes and returns), little-endian
  16-bit limbs (limb i holds value·2^16i). **Contract (lazy / relaxed
  limbs)**: limbs 0..14 are < LMAX = 1.5·2^16; limb 15 is < 2^18. The value
  is NOT kept < p between operations (any residue), and may exceed 2^256 —
  the top limb's headroom absorbs the overflow that pure 2^256→fold_c
  folding can never eliminate from a relaxed representation.
  Canonicalisation (compare/subtract chains) happens only in
  ``canon``/``eq``/``is_zero`` at kernel tails.
- INSIDE an operation every value lives in ``int32`` (the TPU's vector unit
  is 32 bits wide; a 64-bit multiply lowers as four 32-bit ones with their
  carries): a limb product is ONE native multiply of two balanced 16-bit
  digits (see "The limb product"), a product's columns stay near 2^21, and
  the whole walk after it runs in 32-bit lanes. 64-bit lanes remain only
  for bounds that do not fit (a ``mul_const`` by a wide constant) and for
  the canonical tails.
- Carry handling is *vectorized*: one carry pass computes
  ``(v & 0xffff) + shift(v >> 16)`` across the whole limb axis at once,
  versus a 16-32-step *sequential* sweep per op which serializes the VPU and
  made XLA graphs ~10x bigger (70 s compiles for one curve kernel).
- **Exact per-limb bound tracking**: every internal step carries a Python
  list of inclusive per-limb bounds (signed intervals between operations);
  pass counts, fold counts, lanes, slice widths and the final contract
  check are *derived* from exact integer arithmetic at trace time, not
  hand-proven per op. A limb whose bound is 0 is sliced; an op finishes
  when the bounds meet the contract; a bound no lane holds is refused at
  trace time. Host-side only — the compiled graph contains zero
  data-dependent control flow.
- Every shifted add is ``acc + pad(row)`` (``_place``), never a slice
  update: the TPU compiler fuses a sum of pads into one program step, and
  turns each ``.at[].add`` into four. On the chip the steps, not the
  multiplies, set a field operation's time (PERF.md, PR 32).
- Reduction exploits 16-limb alignment of 2^256 ≡ fold_c (mod p):
  p25519 → fold_c = 38; psecp → fold_c = 2^32+977; psecr1 → 224-bit Solinas
  constant (more fold rounds, still exact). The terminal width-17 state with
  a tiny limb-16 bound is folded *back* into limb 15's headroom.
- Subtraction is plain and SIGNED; the one place a value is made
  non-negative again is the start of its walk, by adding a redundant-limb
  encoding of a multiple of p whose every limb dominates the lowest value
  its limb can take (``_lift``).
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

NLIMB = 16
LIMB_BITS = 16
MASK = (1 << LIMB_BITS) - 1
TWO256 = 1 << 256
LMAX = 3 * (1 << 15)        # exclusive bound, limbs 0..14
LIMB15_MAX = 1 << 18        # exclusive bound, limb 15

P25519 = 2**255 - 19
PSECP = 2**256 - 2**32 - 977
PSECR1 = 0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF

_FOLD = {p: TWO256 % p for p in (P25519, PSECP, PSECR1)}

# Inclusive per-limb bounds of a contract-satisfying element.
_CONTRACT = [(0, LMAX - 1)] * 15 + [(0, LIMB15_MAX - 1)]
# Largest value a contract element can take (drives fold bound walks).
VMAX = sum(b << (LIMB_BITS * i) for i, (_, b) in enumerate(_CONTRACT))


def _c_limbs_of(p: int) -> list[int]:
    c = _FOLD[p]
    n = max(1, -(-c.bit_length() // LIMB_BITS))
    return [(c >> (LIMB_BITS * i)) & MASK for i in range(n)]


# ---------------------------------------------------------------------------
# Host <-> limb conversion
# ---------------------------------------------------------------------------

def to_limbs(x, n: int = NLIMB) -> np.ndarray:
    """Python int(s) → u64 limb array ((n,) or (B, n)), canonical limbs.

    The batch path packs each value to little-endian bytes and views them as
    u16 limbs in one numpy pass — one Python-level call per value instead of
    ``n`` bigint shift/mask pairs (this was the dominant cost of the service
    path's host prep at 32k batches)."""
    if isinstance(x, (int, np.integer)):
        return np.array([(int(x) >> (LIMB_BITS * i)) & MASK for i in range(n)],
                        dtype=np.uint64)
    if LIMB_BITS == 16:
        nbytes = n * 2
        buf = b"".join(int(v).to_bytes(nbytes, "little") for v in x)
        return np.frombuffer(buf, dtype="<u2").reshape(
            len(x), n).astype(np.uint64)
    return np.stack([to_limbs(int(v), n) for v in x])


def from_limbs(a):
    """u64 limb array (possibly relaxed) → Python int(s)."""
    arr = np.asarray(a, dtype=np.uint64)
    if arr.ndim == 1:
        return sum(int(v) << (LIMB_BITS * i) for i, v in enumerate(arr))
    return [from_limbs(row) for row in arr]


# ---------------------------------------------------------------------------
# Bound-tracked carry/fold machinery (host-derived, trace-time static)
#
# Two kinds of exact bounds, both plain Python, both derived at trace time:
# - a RELAXED pair ``(value, bounds)`` between operations carries one
#   inclusive ``(lo, hi)`` interval per limb: a product's columns and a
#   borrow-free-by-sign subtraction are SIGNED;
# - inside the walk (``_normalize`` after its dominator step) every limb is
#   non-negative and ``bounds`` is the list of inclusive upper bounds.
# A value is computed in the vector unit's own 32-bit lanes wherever its
# bounds fit them and in 64-bit lanes otherwise (``_lanes``).
# ---------------------------------------------------------------------------

_S32 = 1 << 31
_S64 = 1 << 63


def _span(bounds):
    """(lowest, highest) value any limb can take, from intervals or from a
    list of non-negative upper bounds."""
    if isinstance(bounds[0], tuple):
        return min(lo for lo, _ in bounds), max(hi for _, hi in bounds)
    return 0, max(bounds)


def _lanes(bounds):
    """The dtype a value with these exact bounds is computed in: int32
    where every limb fits it, int64 otherwise. A bound no lane holds is
    refused here, at trace time, and never wraps."""
    lo, hi = _span(bounds)
    assert -_S64 <= lo and hi < _S64, "limb bound overflows the 64-bit lanes"
    return jnp.int32 if -_S32 <= lo and hi < _S32 else jnp.int64


def _place(v, off: int, n: int):
    """``v`` at limbs [off, off + width) of an ``n``-limb zero value. Every
    shifted add in this module is ``acc + _place(row, off, n)``: the
    compiler fuses a sum of pads into ONE step, where a slice update
    (``.at[].add``, a scatter-add) costs four steps a row."""
    w = v.shape[-1]
    if off == 0 and w == n:
        return v
    return jax.lax.pad(v, v.dtype.type(0),
                       [(0, 0, 0)] * (v.ndim - 1) + [(off, n - off - w, 0)])


def _pad_to(v, n: int):
    return _place(v, 0, n)


def _trim(v, bounds):
    """Drop trailing limbs whose exact bound is 0 (provably zero lanes)."""
    while len(bounds) > NLIMB and bounds[-1] == 0:
        bounds = bounds[:-1]
    return v[..., :len(bounds)], bounds


def _pass(v, bounds):
    """One vectorized carry pass over non-negative limbs. Exact new bounds:
    limb'_i = (limb_i & mask) + (limb_{i-1} >> 16)."""
    v = v.astype(_lanes(bounds))
    lo = v & v.dtype.type(MASK)
    hi = v >> v.dtype.type(LIMB_BITS)
    n = len(bounds) + 1
    v = _place(lo, 0, n) + _place(hi, 1, n)
    nb = [min(b, MASK) for b in bounds] + [0]
    for i, b in enumerate(bounds):
        nb[i + 1] += b >> LIMB_BITS
    return _trim(v, nb)


def _fold_bounds(bounds, c_limbs):
    """Exact post-fold bounds, or None when a fold would overflow 64 bits."""
    lob, hib = bounds[:NLIMB], bounds[NLIMB:]
    acc_w = max(NLIMB, len(hib) + len(c_limbs))
    nb = list(lob) + [0] * (acc_w - NLIMB)
    for j, c in enumerate(c_limbs):
        if c:
            for i, hb in enumerate(hib):
                nb[j + i] += hb * c
    return nb if max(nb) < _S64 else None


def _fold_once(v, bounds, c_limbs):
    """lo + hi·c for a width>16 value (split at bit 256). Exact bounds; the
    ``hi * c`` products are native 32-bit multiplies where the folded
    bounds fit the 32-bit lanes."""
    nb = _fold_bounds(bounds, c_limbs)
    assert nb is not None, "64-bit column overflow"
    v = v.astype(_lanes(nb))
    lo = v[..., :NLIMB]
    hi = v[..., NLIMB:]
    nh = len(bounds) - NLIMB
    acc = _pad_to(lo, len(nb))
    for j, c in enumerate(c_limbs):
        if c:
            acc = acc + _place(hi * v.dtype.type(c), j, len(nb))
    return _trim(acc, nb)


def _fold_bounds_r1(bounds):
    """Exact post-fold bounds of the SIGNED Solinas fold for P-256 (see
    _fold_once_r1), or None when a column would overflow 64 bits."""
    lob, hib = bounds[:NLIMB], bounds[NLIMB:]
    nh = len(hib)
    neg = [0] * (12 + nh)
    for i, b in enumerate(hib):
        neg[6 + i] += b
        neg[12 + i] += b
    if max(neg) >= _S64:
        return None
    off, ob = _dominator_offset(tuple(neg), PSECR1)
    width = max(NLIMB, 14 + nh, len(ob))
    nb = [0] * width
    for i, b in enumerate(lob):
        nb[i] += b
    for i, b in enumerate(hib):
        nb[i] += b
        nb[14 + i] += b
    for i, b in enumerate(ob):
        nb[i] += b
    return nb if max(nb) < _S64 else None


def _fold_once_r1(v, bounds):
    """Signed Solinas fold for p = 2^256 - 2^224 + 2^192 + 2^96 - 1:
    hi·2^256 ≡ hi·2^224 - hi·2^192 - hi·2^96 + hi, i.e. pure LIMB-SHIFTED
    adds/subs (224/192/96 are multiples of 16) kept non-negative by a
    dominator multiple of p — 4 shifted adds instead of the generic
    multiply-fold's ~14 per-limb multiply-adds (c = 2^256 mod p has 14
    nonzero limbs, which also made the generic fold's bounds blow up so it
    was rarely even ELIGIBLE, forcing extra carry passes first; this fold's
    bounds grow additively, so it runs far earlier)."""
    hib = bounds[NLIMB:]
    nh = len(hib)
    nb = _fold_bounds_r1(bounds)
    assert nb is not None, "64-bit column overflow in r1 Solinas fold"
    v = v.astype(_lanes(nb))
    lo = v[..., :NLIMB]
    hi = v[..., NLIMB:]
    neg = [0] * (12 + nh)
    for i, b in enumerate(hib):
        neg[6 + i] += b
        neg[12 + i] += b
    off, _ = _dominator_offset(tuple(neg), PSECR1)
    n = len(nb)
    acc = (_pad_to(lo, n) + _pad_to(hi, n) + _place(hi, 14, n)
           + _pad_to(jnp.asarray(off, v.dtype), n)
           - _place(hi, 6, n) - _place(hi, 12, n))
    return _trim(acc, nb)


def _lift(v, bounds, p: int):
    """Signed intervals → non-negative upper bounds: where any limb can be
    negative, add a multiple of p whose redundant limb encoding dominates
    every limb's lowest value (same residue, no borrow anywhere)."""
    lo, _ = _span(bounds)
    if lo >= 0:
        return v, [hi for _, hi in bounds]
    off, ob = _dominator_offset(tuple(max(0, -l) for l, _ in bounds), p)
    nb = [hi for _, hi in bounds] + [0] * (len(ob) - len(bounds))
    for i, x in enumerate(ob):
        nb[i] += x
    v = _pad_to(v.astype(_lanes(nb)), len(nb))
    return v + jnp.asarray(off, v.dtype), nb


def _normalize(v, bounds, p: int):
    """Carry/fold until the element meets the 16-limb contract. All control
    flow is host-side over exact bounds; terminates because folds strictly
    shrink the value bound and the terminal width-17/limb16≤tiny state folds
    back into limb 15's headroom.

    ``bounds`` are a relaxed pair's intervals: a signed value is first made
    non-negative (:func:`_lift`). Folds run EAGERLY — as soon as the exact
    post-fold bounds fit the lanes the value is in — instead of after
    carrying every limb below LMAX first: an early fold shrinks the array
    from up-to-34 limbs to ~16, so the remaining carry passes run at half
    the width. A value in the 32-bit lanes is never folded out of them: a
    32-bit pass first costs less than a fold and a walk in 64-bit lanes.
    P-256 routes through the signed Solinas fold (_fold_once_r1) instead
    of the generic multiply-fold."""
    v, bounds = _lift(v, list(bounds), p)
    c_limbs = _c_limbs_of(p)
    solinas = p == PSECR1
    for _ in range(64):
        if len(bounds) > NLIMB:
            if (len(bounds) == NLIMB + 1
                    and bounds[15] + (bounds[16] << LIMB_BITS) < LIMB15_MAX):
                # fold limb 16 back into limb 15's headroom: value-preserving
                v = v[..., :NLIMB] + _place(v[..., NLIMB:] << LIMB_BITS, 15, NLIMB)
                bounds = bounds[:15] + [bounds[15] + (bounds[16] << LIMB_BITS)]
                continue
            nb = (_fold_bounds_r1(bounds) if solinas
                  else _fold_bounds(bounds, c_limbs))
            if nb is not None and (max(nb) < _S32 or max(bounds) >= _S32):
                v, bounds = (_fold_once_r1(v, bounds) if solinas
                             else _fold_once(v, bounds, c_limbs))
            else:
                v, bounds = _pass(v, bounds)
            continue
        if all(b <= t for b, (_, t) in zip(bounds, _CONTRACT)):
            # contract outputs are uniformly u64: scan carries and DUS
            # accumulators require exact dtype agreement, so the narrow
            # lanes stay internal to the walk
            return v.astype(jnp.uint64), bounds
        v, bounds = _pass(v, bounds)
    raise AssertionError("field normalization failed to converge")


def exact_sweep(a):
    """Sequential exact carry sweep → canonical limbs < 2^16 plus residual
    carry. Only ``canon`` pays for this serial chain."""
    n = a.shape[-1]
    out = []
    carry = jnp.zeros(a.shape[:-1], dtype=jnp.uint64)
    for i in range(n):
        v = a[..., i] + carry
        out.append(v & MASK)
        carry = v >> LIMB_BITS
    return jnp.stack(out, axis=-1), carry


def cond_sub_p(a, p: int):
    """Branchless ``a - p if a >= p else a`` for *canonical* 16-limb ``a``."""
    p_limbs = jnp.asarray(to_limbs(p))
    ge = jnp.ones(a.shape[:-1], dtype=jnp.bool_)
    decided = jnp.zeros(a.shape[:-1], dtype=jnp.bool_)
    for i in range(NLIMB - 1, -1, -1):
        ai = a[..., i]
        pi = p_limbs[i]
        gt, lt = ai > pi, ai < pi
        ge = jnp.where(decided, ge, jnp.where(gt, True, jnp.where(lt, False, ge)))
        decided = decided | gt | lt
    borrow = jnp.zeros(a.shape[:-1], dtype=jnp.uint64)
    outs = []
    for i in range(NLIMB):
        v = a[..., i] - p_limbs[i] - borrow
        borrow = (v >> 63) & 1  # u64 wraparound ⇒ borrow
        outs.append(v & MASK)
    sub16 = jnp.stack(outs, axis=-1)
    return jnp.where(ge[..., None], sub16, a)


def canon(a, p: int):
    """Fully canonicalise a contract element: canonical limbs, value < p.

    Exact sweep (residual carry <= VMAX>>256 = 4) → fold carry·fold_c back →
    second sweep (carry <= 1, and then the folded value is < 2^256 by the
    ε-argument: a wrapped value's low part is < 4·fold_c) → one more
    fold+sweep → conditional subtractions (2^256 < 2p + fold_c for p25519,
    tighter for the 2^256-aligned primes ⇒ 3 cond-subs always suffice)."""
    c_limbs = _c_limbs_of(p)
    c_arr = jnp.asarray(np.array(c_limbs, dtype=np.uint64))
    nc = len(c_limbs)
    swept, carry = exact_sweep(a)
    folded = swept.at[..., :nc].add(carry[..., None] * c_arr)
    swept2, carry2 = exact_sweep(folded)
    folded2 = swept2.at[..., :nc].add(carry2[..., None] * c_arr)
    swept3, _ = exact_sweep(folded2)
    out = swept3
    for _ in range(3):
        out = cond_sub_p(out, p)
    return out


# ---------------------------------------------------------------------------
# The limb product: exact, on 32-bit lanes, one native multiply a digit pair
#
# An operand's limbs (any relaxed bounds that fit int32, signed or not) are
# re-cut into BALANCED 16-bit digits in [-2^15, 2^15 + carry]: the product
# of two digits then fits int32, so ``scalar · row`` is ONE native multiply
# where 16-bit limbs in 64-bit lanes took four with their carries. Each
# product is cut in its two 16-bit halves, added one column apart, so a
# column of 16 of them stays near 2^21 and the walk after it runs in the
# 32-bit lanes from its first pass. The columns are SIGNED and exact as an
# integer: sum(col_k << 16k) == a · b. The carry out of the top limb (0..4
# for a contract element) is kept beside the 16 digits, not as a 17th: its
# rows are small enough to be added whole, and every array stays 16 wide
# (two sublane tiles, where 17 take three: measured 27% slower, PR 32).
#
# Measured on a TPU v5e at (8192, 16) (PERF.md section 6, PR 32): F.mul
# 64.2 -> 11.1 us. Most of that is HOW A ROW IS PLACED (pad + add: 12
# program steps a multiply; the same digits with `.at[].add`: 54 steps,
# 52 us), the rest the 32-bit lanes. Laws fitted to the uint64 product and
# OPEN AGAIN (not re-measured): one level of limb Karatsuba was 18% slower
# at batch 32k; `_add_k1`'s walked `bt2` and `_dbl_m3`'s normalize-before-
# multiply (ops/weierstrass.py); `INV_BATCH_STOP` 128.
# ---------------------------------------------------------------------------

_HALF = 1 << (LIMB_BITS - 1)
# a product (or a row's every product) under this is added to its column
# whole: cutting it in halves saves no bits a column of them lacks
_WHOLE = 1 << 20
_REFUSED = "operand too wide for the 32-bit limb product"


def _iv_mul(x, y):
    c = (x[0] * y[0], x[0] * y[1], x[1] * y[0], x[1] * y[1])
    return min(c), max(c)


@functools.lru_cache(maxsize=None)
def _digit_bounds(bounds: tuple):
    """Exact intervals of :func:`_digits`' output: (main, top)."""
    lo, hi = _span(bounds)
    assert -_S32 <= lo and hi + _HALF < _S32, _REFUSED
    div, civ = [], []
    for l, h in bounds:
        cl, ch = (l + _HALF) >> LIMB_BITS, (h + _HALF) >> LIMB_BITS
        civ.append((cl, ch))
        div.append((((l + _HALF) & MASK) - _HALF, ((h + _HALF) & MASK) - _HALF)
                   if cl == ch else (-_HALF, _HALF - 1))
    main = [div[0]] + [(dl + cl, dh + ch) for (dl, dh), (cl, ch)
                       in zip(div[1:], civ[:-1])]
    return tuple(main), civ[-1]


def _digits(a, bounds):
    """Limbs with exact bounds → ``(main, main_iv, top, top_iv)``: balanced
    int32 digits of the same value, ``main`` as wide as ``a`` and ``top``
    (..., 1) the carry out of its last limb (None where provably 0)."""
    main_iv, top_iv = _digit_bounds(tuple(bounds))
    t = a.astype(jnp.int32) + jnp.int32(_HALF)
    d = (t & jnp.int32(MASK)) - jnp.int32(_HALF)
    c = t >> jnp.int32(LIMB_BITS)
    main = d + _place(c[..., :-1], 1, len(main_iv))
    return main, main_iv, (None if top_iv == (0, 0) else c[..., -1:]), top_iv


@functools.lru_cache(maxsize=None)
def _row_bounds(siv: tuple, segiv: tuple, weights):
    """Exact intervals of one row ``s · seg · w``: ``(whole, None)`` where
    every product is small, else the two halves' ``(low, high)``."""
    pivs = [_iv_mul(siv, x) for x in segiv]
    assert all(-_S32 <= l and h < _S32 for l, h in pivs), _REFUSED
    w = weights or (1,) * len(segiv)
    if all(-_WHOLE < l and h < _WHOLE for l, h in pivs):
        return tuple((l * k, h * k) for (l, h), k in zip(pivs, w)), None
    low, high = [], []
    for (l, h), k in zip(pivs, w):
        cl, ch = l >> LIMB_BITS, h >> LIMB_BITS
        ll, lh = (l & MASK, h & MASK) if cl == ch else (0, MASK)
        low.append((ll * k, lh * k))
        high.append((cl * k, ch * k))
    return tuple(low), tuple(high)


class _Columns:
    """A product's signed column accumulator with its exact intervals."""

    def __init__(self, width: int):
        self.v = None
        self.lo = [0] * width
        self.hi = [0] * width

    def add(self, row, riv, off: int):
        """cols[off + j] += row_j, a row with exact intervals ``riv``."""
        grow = off + len(riv) - len(self.lo)
        if grow > 0:
            self.lo += [0] * grow
            self.hi += [0] * grow
            if self.v is not None:
                self.v = _pad_to(self.v, len(self.lo))
        row = _place(row, off, len(self.lo))
        self.v = row if self.v is None else self.v + row
        for j, (l, h) in enumerate(riv):
            self.lo[off + j] += l
            self.hi[off + j] += h

    def add_row(self, s, siv, seg, segiv, off: int, weights=None):
        """cols[off + j] += s · seg_j · w_j: one multiply for the row; each
        product cut in halves placed one column apart unless the whole row
        is small. ``weights`` (the square's doubled cross terms) apply
        AFTER the cut: twice a digit product can pass 2^31, twice its
        halves cannot."""
        if siv == (0, 0):
            return
        low, high = _row_bounds(siv, tuple(segiv), weights)
        p = s * seg

        def scale(x):       # weights are 1 or 2: a shift, not a multiply
            if weights is None:
                return x
            by = jnp.asarray([k >> 1 for k in weights], jnp.int32)
            return jax.lax.shift_left(x, jnp.broadcast_to(by, x.shape))

        if high is None:
            self.add(scale(p), low, off)
            return
        lo = scale(p & jnp.int32(MASK))
        hi = scale(p >> jnp.int32(LIMB_BITS))
        self.add(lo, low, off)
        self.add(hi, high, off + 1)

    def done(self):
        bounds = list(zip(self.lo, self.hi))
        lo, hi = _span(bounds)
        assert -_S32 <= lo and hi < _S32, _REFUSED
        return self.v, bounds


def _top_top(cols, t, tiv, at: int):
    """The product of two top carries belongs one column past the last:
    where it is small it goes into the last column times 2^16 (the same
    value) and the accumulator stays a whole number of sublane tiles."""
    if max(abs(tiv[0]), abs(tiv[1])) < (1 << 8):
        k = 1 << LIMB_BITS
        cols.add(t * jnp.int32(k), [(tiv[0] * k, tiv[1] * k)], at - 1)
    else:
        cols.add(t, [tiv], at)


def _product(a, ab, b, bb):
    """Signed exact columns of a · b (see the section note)."""
    A, aiv, at, ativ = _digits(a, ab)
    B, biv, bt, btiv = _digits(b, bb)
    na, nb = len(aiv), len(biv)
    cols = _Columns(na + nb)
    for i in range(na):
        cols.add_row(A[..., i:i + 1], aiv[i], B, biv, i)
    if at is not None:
        cols.add_row(at, ativ, B, biv, na)
    if bt is not None:
        cols.add_row(bt, btiv, A, aiv, nb)
    if at is not None and bt is not None:
        _top_top(cols, at * bt, _iv_mul(ativ, btiv), na + nb)
    return cols.done()


def _square(a, ab):
    """Signed exact columns of a², triangular: col_k = 2·Σ_{i<j, i+j=k}
    a_i·a_j + [k even]·a_{k/2}² — ~n(n+1)/2 digit products instead of n²
    (`dbl`'s Y² / Z² and Fermat's square chain are the beneficiaries).
    Row i covers columns [2i, i+n]: the diagonal a_i² then the doubled
    cross terms (j > i) — CONTIGUOUS placements (a strided cols[0::2]
    diagonal forces a relayout on TPU)."""
    A, aiv, at, ativ = _digits(a, ab)
    n = len(aiv)
    cols = _Columns(2 * n)
    for i in range(n):
        cols.add_row(A[..., i:i + 1], aiv[i], A[..., i:], aiv[i:], 2 * i,
                     None if i == n - 1 else (1,) + (2,) * (n - 1 - i))
    if at is not None:
        cols.add_row(at, ativ, A, aiv, n, (2,) * n)
        _top_top(cols, at * at, _iv_mul(ativ, ativ), 2 * n)
    return cols.done()


# ---------------------------------------------------------------------------
# Core modular ops (shape-polymorphic over leading batch dims)
# All take and return contract elements (see module docstring).
# ---------------------------------------------------------------------------

def raw_mul_bounded(a, b, a_bounds=None, b_bounds=None):
    """Full product with exact column bounds: bounded × bounded → wide.
    Input bounds default to the contract; callers passing *relaxed* operands
    (e.g. un-normalized sums) supply their exact intervals instead. An
    operand whose digits' product does not fit int32 is refused at trace
    time."""
    return _product(a, _CONTRACT if a_bounds is None else a_bounds,
                    b, _CONTRACT if b_bounds is None else b_bounds)


def mul(a, b, p: int):
    """Lazy modular multiply: contract × contract → contract."""
    cols, nb = raw_mul_bounded(a, b)
    return _normalize(cols, nb, p)[0]


# ---------------------------------------------------------------------------
# Column-level fusion primitives (one normalize per *group* of products)
#
# The complete-addition formulas are full of `mul, mul, add/sub` triples that
# each pay a full normalize walk. These primitives keep products as raw
# column accumulators (value, exact intervals) so a whole linear combination
# ± a·b ± c·d ± e normalizes ONCE. Columns and relaxed pairs are signed: a
# negative term is plainly subtracted, and the one place a value is made
# non-negative again is the start of its walk (`_lift`).
# ---------------------------------------------------------------------------

def rel(a, bounds=None):
    """Wrap plain contract limbs as a (value, bounds) relaxed pair."""
    return (a, _CONTRACT if bounds is None else bounds)


def _combine(terms):
    """Σ sign·value over relaxed pairs of any widths → one relaxed pair,
    computed in the lanes the exact result bounds ask for."""
    n = max(len(nb) for _, nb, _ in terms)
    lo, hi = [0] * n, [0] * n
    for _, nb, sign in terms:
        for i, (l, h) in enumerate(nb):
            lo[i] += l if sign > 0 else -h
            hi[i] += h if sign > 0 else -l
    bounds = list(zip(lo, hi))
    dt = _lanes(bounds)
    out = None
    for v, nb, sign in terms:
        v = _pad_to(v.astype(dt), n)
        out = (v if sign > 0 else -v) if out is None else (
            out + v if sign > 0 else out - v)
    return (out, bounds)


def _pair(ar):
    return ar if isinstance(ar, tuple) else rel(ar)


def rel_add(ar, br):
    """Relaxed add: no normalize; bounds sum. Inputs: (v, bounds) pairs or
    plain arrays (contract bounds assumed)."""
    return _combine([(*_pair(ar), 1), (*_pair(br), 1)])


def rel_sub(ar, br, p: int):
    """Relaxed subtract: a - b, signed, NO normalize. Feed it to `mul_cols`
    (whose balanced digits take signed limbs as they are) or normalize
    explicitly via `norm`. ``p`` is the formulas' calling convention: the
    multiple of p that makes a value non-negative is added by its walk."""
    return _combine([(*_pair(ar), 1), (*_pair(br), -1)])


def norm(vr, p: int):
    """Normalize a relaxed (value, bounds) pair to a contract element."""
    v, nb = vr
    return _normalize(v, nb, p)[0]


def mul_cols(ar, br):
    """Schoolbook product of relaxed pairs → raw (cols, bounds), NO
    normalize. Accepts plain arrays (contract bounds) or (v, bounds)."""
    return _product(*_pair(ar), *_pair(br))


def _scaled(v, nb, k: int):
    assert k >= 0
    out = [(l * k, h * k) for l, h in nb]
    dt = _lanes(out)
    return (v.astype(dt) * dt(k), out)


def scale_rel(a, k: int, bounds=None):
    """Small-constant scale of a narrow element WITHOUT normalizing: returns
    a relaxed (value, bounds) pair for feeding rel_add/rel_sub/mul_cols."""
    return _scaled(a, _CONTRACT if bounds is None else bounds, k)


def scale_cols(cr, k: int):
    """Scale a raw (value, bounds) pair by a small host constant — folds a
    mul_const into an adjacent normalize for free."""
    return _scaled(*cr, k)


_DOM_OFFSETS: dict = {}


def _dominator_offset(need: tuple, p: int):
    """A redundant wide-limb encoding of M·p whose limb i dominates
    ``need[i]`` — adding it makes subtracting any value bounded by ``need``
    borrow-free while preserving the residue mod p. Cached per (p, need)
    (bounds are trace-time static)."""
    key = (p, tuple(need))
    if key in _DOM_OFFSETS:
        return _DOM_OFFSETS[key]
    S = sum(int(b) << (LIMB_BITS * i) for i, b in enumerate(need))
    # M = S//p + 1 keeps R = M·p - S in (0, p] — a 16-limb offset. (+2 made
    # R up to 2p ~ 2^257, whose 17th limb livelocked the r1 Solinas fold:
    # a 17-limb value folded to ... a 17-limb value, forever.)
    M = (S // p) + 1
    R = M * p - S
    width = max(len(need), -(-R.bit_length() // LIMB_BITS))
    digits = [int(b) for b in list(need) + [0] * (width - len(need))]
    for i in range(width):
        digits[i] += (R >> (LIMB_BITS * i)) & MASK
    extra = R >> (LIMB_BITS * width)
    if extra:
        digits.append(int(extra))
    assert sum(d << (LIMB_BITS * i) for i, d in enumerate(digits)) == M * p
    assert all(d >= n for d, n in zip(digits, need))
    out = (np.array(digits, dtype=np.int64), digits)
    _DOM_OFFSETS[key] = out
    return out


def col_acc(p: int, plus=(), minus=()):
    """Accumulate raw column products: sum(plus) - sum(minus), returning a
    relaxed (value, bounds) pair (normalize with `norm`). Each entry is a
    (cols, bounds) pair from `mul_cols` (or a relaxed pair from
    rel/rel_add — any (value, exact bounds))."""
    return _combine([(v, nb, 1) for v, nb in plus]
                    + [(v, nb, -1) for v, nb in minus])


def raw_sqr_bounded(a, bounds):
    """Triangular square with exact column bounds (see :func:`_square`).
    Bounds are at least as tight as the general product's."""
    return _square(a, bounds)


def sqr_cols(ar):
    """Triangular square of a relaxed pair → raw (cols, bounds), NO
    normalize — the squared sibling of :func:`mul_cols`."""
    return _square(*_pair(ar))


def sqr(a, p: int):
    cols, nb = raw_sqr_bounded(a, _CONTRACT)
    return _normalize(cols, nb, p)[0]


def mul_of_sums(a1, a2, b1, b2, p: int):
    """(a1+a2)·(b1+b2) mod p without normalizing the sums: the adds' carry
    passes are absorbed into the product's own normalize. Shaves two
    normalize walks off the (X1+Y1)(X2+Y2)-style cross terms that dominate
    complete-addition formulas."""
    return norm(mul_cols(rel_add(a1, a2), rel_add(b1, b2)), p)


def sqr_of_sum(a1, a2, p: int):
    """(a1+a2)² mod p without normalizing the sum."""
    return norm(sqr_cols(rel_add(a1, a2)), p)


def add(a, b, p: int):
    return norm(rel_add(a, b), p)


def sub(a, b, p: int):
    """a - b mod p: the signed difference, lifted by a dominating multiple
    of p at the start of its walk."""
    return norm(rel_sub(a, b, p), p)


def neg(a, p: int):
    return sub(jnp.zeros_like(a), a, p)


# Bound on mul_const's scalar: limb bound (< 2^18) x constant must stay under
# the 64-bit column capacity with headroom for the normalize walk.
MUL_CONST_MAX = 1 << 45


def mul_const(a, c: int, p: int):
    """Multiply by a small host constant (c < MUL_CONST_MAX)."""
    assert 0 <= c < MUL_CONST_MAX
    if c == 0:
        return jnp.zeros_like(a)
    return norm(scale_rel(a, c), p)


# ---------------------------------------------------------------------------
# Predicates / selection (canonicalising)
# ---------------------------------------------------------------------------

def eq(a, b, p: int):
    """Equality mod p of contract elements → bool (...,)."""
    return jnp.all(canon(a, p) == canon(b, p), axis=-1)


def is_zero(a, p: int):
    return jnp.all(canon(a, p) == 0, axis=-1)


def select(cond, a, b):
    """cond (...,) bool → where(cond, a, b) over limb arrays."""
    return jnp.where(cond[..., None], a, b)


def one_like(a):
    """Canonical 1 broadcast to a's batch shape."""
    return jnp.zeros_like(a).at[..., 0].set(1)


def pow_const(a, e: int, p: int):
    """a^e for a host-known exponent.

    Square-and-multiply driven by a ``lax.scan`` over the exponent's bits
    (MSB-first) so the compiled graph is one square + one multiply regardless
    of exponent size — a fully unrolled 256-bit ladder otherwise produces
    megabyte HLO graphs and minutes of XLA compile time.
    """
    if e == 0:
        return one_like(a)
    bits = jnp.asarray([int(b) for b in bin(e)[2:]], dtype=jnp.uint64)

    def step(result, bit):
        result = sqr(result, p)
        with_mul = mul(result, a, p)
        return select(bit.astype(jnp.bool_), with_mul, result), None

    # First bit is always 1: start from a (skips one square+select).
    result, _ = jax.lax.scan(step, a, bits[1:])
    return result


def inv(a, p: int):
    """Modular inverse via Fermat (a^(p-2)); a must be non-zero (inv(0)=0)."""
    if p == P25519:
        return inv25519(a)
    return pow_const(a, p - 2, p)


def _sqr_n(a, n: int, p: int):
    """n successive squarings as a lax.scan (graph stays one-step-sized)."""
    if n == 1:
        return sqr(a, p)
    out, _ = jax.lax.scan(lambda c, _x: (sqr(c, p), None), a, None, length=n)
    return out


def inv25519(a):
    """a^(p-2) mod 2^255-19 via the standard curve25519 addition chain:
    254 squarings + 11 multiplies, versus ~250 multiplies for the generic
    square-and-multiply over the dense exponent (p-2 = 2^255-21 is almost
    all ones). The ed25519 re-encoding epilogue pays one of these per
    batch."""
    p = P25519
    z2 = sqr(a, p)                       # 2
    z8 = _sqr_n(z2, 2, p)                # 8
    z9 = mul(z8, a, p)                   # 9
    z11 = mul(z9, z2, p)                 # 11
    z22 = sqr(z11, p)                    # 22
    z_5_0 = mul(z22, z9, p)              # 2^5 - 1
    z_10_0 = mul(_sqr_n(z_5_0, 5, p), z_5_0, p)      # 2^10 - 1
    z_20_0 = mul(_sqr_n(z_10_0, 10, p), z_10_0, p)   # 2^20 - 1
    z_40_0 = mul(_sqr_n(z_20_0, 20, p), z_20_0, p)   # 2^40 - 1
    z_50_0 = mul(_sqr_n(z_40_0, 10, p), z_10_0, p)   # 2^50 - 1
    z_100_0 = mul(_sqr_n(z_50_0, 50, p), z_50_0, p)  # 2^100 - 1
    z_200_0 = mul(_sqr_n(z_100_0, 100, p), z_100_0, p)  # 2^200 - 1
    z_250_0 = mul(_sqr_n(z_200_0, 50, p), z_50_0, p)    # 2^250 - 1
    return mul(_sqr_n(z_250_0, 5, p), z11, p)        # 2^255 - 21


#: Batch width at which :func:`inv_batch` stops halving and pays the
#: per-row chain, chosen on a TPU v5e (PR 30, the Ed25519 tail at 8192
#: rows, ms by stop width): 8: 5.755, 16: 5.727, 32: 5.698, 64: 5.667,
#: 128: 5.636, 256: 6.058, 512: 6.879, 1024: 8.496, 2048: 8.700, the
#: chain on every row: 13.127. Below 128 rows the chain of ~265 dependent
#: operations costs its latency whatever the width, and each halving adds
#: two product graphs to the program.
INV_BATCH_STOP = 128


def inv_batch(z, p: int):
    """``(inverse, nonzero)`` of a (B, NLIMB) batch from ONE inversion chain
    per ``INV_BATCH_STOP`` rows: Montgomery's trick as a tree over the batch
    axis. Going down, each level multiplies the first half of its rows by
    the second half (contiguous halves: a strided pairing forces a relayout
    on TPU) until ``INV_BATCH_STOP`` rows are left; those are inverted with
    :func:`inv`; coming up, a row's inverse is its pair's inverse times its
    partner. About 3 products a row in place of a ~255-squaring chain.

    One row never decides another's result: a row that is ≡ 0 would zero
    the product of its whole subtree, so it enters the tree as 1, reads
    ``nonzero`` False and inverse 0 (what :func:`inv` gives it).

    The tree needs a power-of-two batch at or over the stop width (every
    bucket :func:`bucket_size` makes, and each shard of one under
    ``parallel/sharded.py``); any other shape keeps the per-row chain."""
    zero = is_zero(z, p)
    n = z.shape[0] if z.ndim >= 2 else 0
    if n < INV_BATCH_STOP or n & (n - 1):
        return inv(z, p), ~zero
    v = select(zero, one_like(z), z)
    levels = []
    while v.shape[0] > INV_BATCH_STOP:
        levels.append(v)
        h = v.shape[0] // 2
        v = mul(v[:h], v[h:], p)
    v = inv(v, p)
    for lvl in reversed(levels):
        # inverse(a) = inverse(a·b)·b and inverse(b) = inverse(a·b)·a: one
        # product over the whole level against its halves swapped
        h = lvl.shape[0] // 2
        v = mul(jnp.concatenate([v, v]),
                jnp.concatenate([lvl[h:], lvl[:h]]), p)
    return select(zero, jnp.zeros_like(v), v), ~zero


# ---------------------------------------------------------------------------
# Scalar bit decomposition (for curve scalar-mul ladders)
# ---------------------------------------------------------------------------

_DEVICE_TABLE_CACHE: dict = {}
_DEVICE_TABLE_LOCK = threading.Lock()


def device_table_cache(key, build):
    """Generic committed-device-array cache for baked lookup tables (the
    constant-G / Niels tables): ``build()`` runs once per key, its arrays
    are device_put once per process, and repeat calls hand back the same
    committed buffers (zero per-call transfer). Tables are ARGUMENTS to
    kernels, never HLO constants — multi-MB literals explode compile time.

    Builds are serialized under a lock: the batcher's per-scheme prep pool
    can race two first-use preps of the same scheme, and the multi-MB
    table builds are exactly the work worth doing once."""
    tabs = _DEVICE_TABLE_CACHE.get(key)
    if tabs is None:
        with _DEVICE_TABLE_LOCK:
            tabs = _DEVICE_TABLE_CACHE.get(key)
            if tabs is None:
                tabs = _DEVICE_TABLE_CACHE[key] = tuple(
                    jax.device_put(t) for t in build())
    return tabs


def bucket_size(n: int, floor: int = 8) -> int:
    """Next power of two >= n (>= floor). Batch kernels pad to bucket sizes so
    XLA compiles once per bucket, not once per batch length (shared by the
    ed25519/weierstrass verify_batch entry points and the verifier service)."""
    b = floor
    while b < n:
        b *= 2
    return b


def scalars_to_bits(xs, nbits: int = 256) -> np.ndarray:
    """Python ints → (nbits, B) u32 bit array, MSB first (scan-ready layout:
    ladder kernels scan over the leading bit axis). Vectorized via unpackbits —
    this runs on the host per batch, so no Python-level 256×B loop.
    ``nbits`` need not be byte-aligned: values are packed into the enclosing
    byte count and the excess high-order rows sliced off (every scalar must
    fit nbits — to_bytes raises otherwise)."""
    nbytes = (nbits + 7) // 8
    packed = np.frombuffer(
        b"".join(int(x).to_bytes(nbytes, "big") for x in xs),
        dtype=np.uint8).reshape(len(xs), nbytes)
    bits = np.unpackbits(packed, axis=1, bitorder="big")  # (B, 8*nbytes) MSB
    if nbits % 8:
        # to_bytes only bounds by the byte count: reject (loudly, not by
        # silent truncation) any scalar using the sliced-off high bits
        assert not bits[:, : 8 * nbytes - nbits].any(), \
            f"scalar exceeds {nbits} bits"
    # u8 on the wire: bit planes are 0/1 and the kernels upcast on device —
    # shipping u32/u64 through the host↔device link was 4-8x the bytes for
    # no information (the service path is transfer-bound at 32k batches)
    return np.ascontiguousarray(bits[:, -nbits:].T).astype(np.uint8)
