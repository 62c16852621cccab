"""Batched 256-bit prime-field arithmetic on 16-bit limbs in uint64 lanes.

The bigint engine under both curve kernels (ed25519.py, weierstrass.py).
Design (SURVEY.md §7 phase 1 "limb-decomposed lanes"):

- A field element is ``u64[..., 16]``, little-endian 16-bit limbs (limb i
  holds value·2^16i). **Contract (lazy / relaxed limbs)**: limbs 0..14 are
  < LMAX = 1.5·2^16; limb 15 is < 2^18. The value is NOT kept < p between
  operations (any residue), and may exceed 2^256 — the top limb's headroom
  absorbs the overflow that pure 2^256→fold_c folding can never eliminate
  from a relaxed representation. Canonicalisation (compare/subtract chains)
  happens only in ``canon``/``eq``/``is_zero`` at kernel tails.
- Carry handling is *vectorized*: one carry pass computes
  ``(v & 0xffff) + shift(v >> 16)`` across the whole limb axis at once,
  versus a 16-32-step *sequential* sweep per op which serializes the VPU and
  made XLA graphs ~10x bigger (70 s compiles for one curve kernel).
- **Exact per-limb bound tracking**: every internal step carries a Python
  list of inclusive per-limb bounds; pass counts, fold counts, slice widths
  and the final contract check are *derived* from exact integer arithmetic
  at trace time, not hand-proven per op. A limb whose bound is 0 is sliced;
  an op finishes when the bounds meet the contract. Host-side only — the
  compiled graph contains zero data-dependent control flow.
- Reduction exploits 16-limb alignment of 2^256 ≡ fold_c (mod p):
  p25519 → fold_c = 38; psecp → fold_c = 2^32+977; psecr1 → 224-bit Solinas
  constant (more fold rounds, still exact). The terminal width-17 state with
  a tiny limb-16 bound is folded *back* into limb 15's headroom.
- Subtraction avoids borrows by adding a redundant-limb encoding of 32p
  whose every limb dominates the contract bound of the subtrahend.
"""
from __future__ import annotations

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
_CONTRACT = [LMAX - 1] * 15 + [LIMB15_MAX - 1]
# Largest value a contract element can take (drives fold bound walks).
VMAX = sum(b << (LIMB_BITS * i) for i, b in enumerate(_CONTRACT))


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
# ---------------------------------------------------------------------------

def _trim(v, bounds):
    """Drop trailing limbs whose exact bound is 0 (provably zero lanes)."""
    while len(bounds) > NLIMB and bounds[-1] == 0:
        bounds = bounds[:-1]
    return v[..., :len(bounds)], bounds


def _pass(v, bounds):
    """One vectorized carry pass. Exact new bounds:
    limb'_i = (limb_i & mask) + (limb_{i-1} >> 16).

    When every incoming bound fits u32 the pass runs in uint32 — the TPU
    VPU is natively 32-bit, so u64 mask/shift/add lower as emulated pairs;
    the downcast is lossless by the exact bounds and jnp's promotion rules
    carry the narrow dtype through downstream adds harmlessly."""
    if max(bounds) < (1 << 32) and v.dtype == jnp.uint64:
        v = v.astype(jnp.uint32)
    lo = v & v.dtype.type(MASK)
    hi = v >> v.dtype.type(LIMB_BITS)
    pad_cfg = [(0, 0)] * (v.ndim - 1)
    v = jnp.pad(lo, pad_cfg + [(0, 1)]) + jnp.pad(hi, pad_cfg + [(1, 0)])
    nb = [min(b, MASK) for b in bounds] + [0]
    for i, b in enumerate(bounds):
        nb[i + 1] += b >> LIMB_BITS
    return _trim(v, nb)


def _fold_bounds(bounds, c_limbs):
    """Exact post-fold bounds, or None when a fold would overflow u64."""
    lob, hib = bounds[:NLIMB], bounds[NLIMB:]
    acc_w = max(NLIMB, len(hib) + len(c_limbs))
    nb = list(lob) + [0] * (acc_w - NLIMB)
    for j, c in enumerate(c_limbs):
        if c:
            for i, hb in enumerate(hib):
                nb[j + i] += hb * c
    return nb if max(nb) < (1 << 63) else None


def _fold_once(v, bounds, c_limbs):
    """lo + hi·c for a width>16 value (split at bit 256). Exact bounds."""
    if v.dtype != jnp.uint64:       # a u32 carry pass may have narrowed v
        v = v.astype(jnp.uint64)
    lo = v[..., :NLIMB]
    hi, hib = v[..., NLIMB:], bounds[NLIMB:]
    nh = len(hib)
    nb = _fold_bounds(bounds, c_limbs)
    assert nb is not None, "u64 column overflow"
    hi = _mul_operand(hi, hib)
    acc_w = max(NLIMB, nh + len(c_limbs))
    acc = jnp.zeros(v.shape[:-1] + (acc_w,), dtype=jnp.uint64)
    acc = acc.at[..., :NLIMB].add(lo)
    for j, c in enumerate(c_limbs):
        if c:
            acc = acc.at[..., j:j + nh].add(hi * jnp.uint64(c))
    return _trim(acc, nb)


def _fold_bounds_r1(bounds):
    """Exact post-fold bounds of the SIGNED Solinas fold for P-256 (see
    _fold_once_r1), or None when a column would overflow u64."""
    lob, hib = bounds[:NLIMB], bounds[NLIMB:]
    nh = len(hib)
    neg = [0] * (12 + nh)
    for i, b in enumerate(hib):
        neg[6 + i] += b
        neg[12 + i] += b
    if max(neg) >= (1 << 63):
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
    return nb if max(nb) < (1 << 63) else None


def _fold_once_r1(v, bounds):
    """Signed Solinas fold for p = 2^256 - 2^224 + 2^192 + 2^96 - 1:
    hi·2^256 ≡ hi·2^224 - hi·2^192 - hi·2^96 + hi, i.e. pure LIMB-SHIFTED
    adds/subs (224/192/96 are multiples of 16) made borrow-free by a
    dominator multiple of p — 4 shifted DUS ops instead of the generic
    multiply-fold's ~14 per-limb multiply-adds (c = 2^256 mod p has 14
    nonzero limbs, which also made the generic fold's bounds blow up so it
    was rarely even ELIGIBLE, forcing extra carry passes first; this fold's
    bounds grow additively, so it runs far earlier).  The r5 lever named in
    BASELINE.md's round-4 r1 section."""
    if v.dtype != jnp.uint64:
        v = v.astype(jnp.uint64)
    lo = v[..., :NLIMB]
    hi, hib = v[..., NLIMB:], bounds[NLIMB:]
    nh = len(hib)
    nb = _fold_bounds_r1(bounds)
    assert nb is not None, "u64 column overflow in r1 Solinas fold"
    neg = [0] * (12 + nh)
    for i, b in enumerate(hib):
        neg[6 + i] += b
        neg[12 + i] += b
    off, _ = _dominator_offset(tuple(neg), PSECR1)
    acc = jnp.zeros(v.shape[:-1] + (len(nb),), dtype=jnp.uint64)
    acc = acc.at[..., :NLIMB].add(lo)
    acc = acc.at[..., :nh].add(hi)
    acc = acc.at[..., 14:14 + nh].add(hi)
    acc = acc.at[..., :len(off)].add(jnp.asarray(off))
    acc = acc.at[..., 6:6 + nh].add(-hi)
    acc = acc.at[..., 12:12 + nh].add(-hi)
    return _trim(acc, nb)


def _normalize(v, bounds, p: int):
    """Carry/fold until the element meets the 16-limb contract. All control
    flow is host-side over exact bounds; terminates because folds strictly
    shrink the value bound and the terminal width-17/limb16≤tiny state folds
    back into limb 15's headroom.

    Folds run EAGERLY — as soon as the exact post-fold bounds fit u64 —
    instead of after carrying every limb below LMAX first: an early fold
    shrinks the array from up-to-31 limbs to ~16, so the remaining carry
    passes run at half the width (measured 4 passes + 2 folds per norm
    before; the wide passes dominated the walk cost).  P-256 routes through
    the signed Solinas fold (_fold_once_r1) instead of the generic
    multiply-fold."""
    c_limbs = _c_limbs_of(p)
    solinas = p == PSECR1
    for _ in range(64):
        if len(bounds) > NLIMB:
            if (len(bounds) == NLIMB + 1
                    and bounds[15] + (bounds[16] << LIMB_BITS) < LIMB15_MAX):
                # fold limb 16 back into limb 15's headroom: value-preserving
                merged = v[..., 15] + (v[..., 16] << LIMB_BITS)
                v = v[..., :NLIMB].at[..., 15].set(merged)
                bounds = bounds[:15] + [bounds[15] + (bounds[16] << LIMB_BITS)]
                continue
            nb = (_fold_bounds_r1(bounds) if solinas
                  else _fold_bounds(bounds, c_limbs))
            if nb is not None:
                v, bounds = (_fold_once_r1(v, bounds) if solinas
                             else _fold_once(v, bounds, c_limbs))
            else:
                v, bounds = _pass(v, bounds)
            continue
        if all(b <= t for b, t in zip(bounds, _CONTRACT)):
            # contract outputs are uniformly u64: scan carries and DUS
            # accumulators require exact dtype agreement, so the u32 pass
            # narrowing stays internal to the walk
            if v.dtype != jnp.uint64:
                v = v.astype(jnp.uint64)
            return v, bounds
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
# Core modular ops (shape-polymorphic over leading batch dims)
# All take and return contract elements (see module docstring).
# ---------------------------------------------------------------------------

def _mul_operand(a, bounds):
    """Route a multiplicand whose exact bounds fit u32 through a
    u32→u64 convert: the value is unchanged (bounds prove the truncation
    is lossless) but the convert ANNOTATES the range, letting the TPU
    backend lower the u64 products to half-width multiplies."""
    if max(bounds) < (1 << 32):
        return a.astype(jnp.uint32).astype(jnp.uint64)
    return a


def raw_mul_bounded(a, b, a_bounds=None, b_bounds=None):
    """Full product with exact column bounds: bounded × bounded → wide.
    Input bounds default to the contract; callers passing *relaxed* operands
    (e.g. un-normalized sums) supply their exact bounds instead.

    Plain 16-DUS schoolbook. One level of limb Karatsuba (3 width-8
    schoolbooks, 192 column MACs vs 256; borrow-free middle term) was
    MEASURED 18% SLOWER on v5e at batch 32k — width-8 rows waste VPU lanes
    and the extra combine ops outweigh the saved MACs. Don't re-try without
    new hardware."""
    a_bounds = _CONTRACT if a_bounds is None else a_bounds
    b_bounds = _CONTRACT if b_bounds is None else b_bounds
    a = _mul_operand(a, a_bounds)
    b = _mul_operand(b, b_bounds)
    cols = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
                     + (2 * NLIMB - 1,), dtype=jnp.uint64)
    for i in range(NLIMB):
        cols = cols.at[..., i:i + NLIMB].add(a[..., i:i + 1] * b)
    nb = [0] * (2 * NLIMB - 1)
    for i, ab in enumerate(a_bounds):
        for j, bb in enumerate(b_bounds):
            nb[i + j] += ab * bb
    assert max(nb) < (1 << 63), "u64 column overflow in schoolbook multiply"
    return cols, nb


def mul(a, b, p: int):
    """Lazy modular multiply: contract × contract → contract."""
    cols, nb = raw_mul_bounded(a, b)
    return _normalize(cols, nb, p)[0]


# ---------------------------------------------------------------------------
# Column-level fusion primitives (one normalize per *group* of products)
#
# The complete-addition formulas are full of `mul, mul, add/sub` triples that
# each pay a full normalize walk. These primitives keep products as raw
# column accumulators (value, exact bounds) so a whole linear combination
# ± a·b ± c·d ± e normalizes ONCE. Negative terms are made borrow-free by
# adding a multiple of p whose redundant limb encoding dominates their
# column bounds (the wide generalization of the 32p trick in `sub`).
# ---------------------------------------------------------------------------

def rel(a, bounds=None):
    """Wrap plain contract limbs as a (value, bounds) relaxed pair."""
    return (a, _CONTRACT if bounds is None else bounds)


def rel_add(ar, br):
    """Relaxed add: no normalize; bounds sum. Inputs: (v, bounds) pairs or
    plain arrays (contract bounds assumed)."""
    a, ab = ar if isinstance(ar, tuple) else rel(ar)
    b, bb = br if isinstance(br, tuple) else rel(br)
    n = max(len(ab), len(bb))
    ab = list(ab) + [0] * (n - len(ab))
    bb = list(bb) + [0] * (n - len(bb))
    if a.shape[-1] < n:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, n - a.shape[-1])])
    if b.shape[-1] < n:
        b = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, n - b.shape[-1])])
    return (a + b, [x + y for x, y in zip(ab, bb)])


def rel_sub(ar, br, p: int):
    """Relaxed borrow-free subtract: a + OFFSET(p, dominating b) - b, NO
    normalize. The result is wider/looser; feed it to `mul_cols` (which takes
    exact bounds) or normalize explicitly via `norm`."""
    a, ab = ar if isinstance(ar, tuple) else rel(ar)
    b, bb = br if isinstance(br, tuple) else rel(br)
    off, ob = _dominator_offset(tuple(bb), p)
    n = max(len(ab), len(ob))
    v = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]) + (n,),
                  dtype=jnp.uint64)
    v = v.at[..., :len(ab)].add(a)
    v = v.at[..., :len(ob)].add(jnp.asarray(off))
    v = v.at[..., :len(bb)].add(-b)   # u64 wrap-free: off dominates b
    nb = [0] * n
    for i, x in enumerate(ab):
        nb[i] += x
    for i, x in enumerate(ob):
        nb[i] += x
    return (v, nb)


def norm(vr, p: int):
    """Normalize a relaxed (value, bounds) pair to a contract element."""
    v, nb = vr
    return _normalize(v, list(nb), p)[0]


def mul_cols(ar, br):
    """Schoolbook product of relaxed pairs → raw (cols, bounds), NO
    normalize. Accepts plain arrays (contract bounds) or (v, bounds)."""
    a, ab = ar if isinstance(ar, tuple) else rel(ar)
    b, bb = br if isinstance(br, tuple) else rel(br)
    a = _mul_operand(a, ab)
    b = _mul_operand(b, bb)
    na, nbw = len(ab), len(bb)
    cols = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
                     + (na + nbw - 1,), dtype=jnp.uint64)
    for i in range(na):
        cols = cols.at[..., i:i + nbw].add(a[..., i:i + 1] * b)
    out = [0] * (na + nbw - 1)
    for i, x in enumerate(ab):
        for j, y in enumerate(bb):
            out[i + j] += x * y
    assert max(out) < (1 << 63), "u64 column overflow in fused schoolbook"
    return (cols, out)


def scale_rel(a, k: int, bounds=None):
    """Small-constant scale of a narrow element WITHOUT normalizing: returns
    a relaxed (value, bounds) pair for feeding rel_add/rel_sub/mul_cols."""
    b = _CONTRACT if bounds is None else bounds
    out = [x * k for x in b]
    assert max(out) < (1 << 63)
    return (_mul_operand(a, b) * jnp.uint64(k), out)


def scale_cols(cr, k: int):
    """Scale a raw (value, bounds) pair by a small host constant — folds a
    mul_const into an adjacent normalize for free."""
    v, nb = cr
    out = [b * k for b in nb]
    assert max(out) < (1 << 63), "u64 column overflow in scale_cols"
    return (_mul_operand(v, nb) * jnp.uint64(k), out)


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
    out = (np.array(digits, dtype=np.uint64), digits)
    _DOM_OFFSETS[key] = out
    return out


def col_acc(p: int, plus=(), minus=()):
    """Accumulate raw column products: sum(plus) - sum(minus) + dominator,
    returning a relaxed (value, bounds) pair (normalize with `norm`).
    Each entry is a (cols, bounds) pair from `mul_cols` (or a relaxed pair
    from rel/rel_add — any (value, exact bounds))."""
    neg_nb: list = []
    for _, nb in minus:
        if len(nb) > len(neg_nb):
            neg_nb += [0] * (len(nb) - len(neg_nb))
        for i, x in enumerate(nb):
            neg_nb[i] += x
    if minus:
        off, ob = _dominator_offset(tuple(neg_nb), p)
    else:
        off, ob = None, []
    width = max([len(nb) for _, nb in plus] + [len(ob)]
                + [len(nb) for _, nb in minus])
    shapes = [v.shape[:-1] for v, _ in list(plus) + list(minus)]
    out = jnp.zeros(jnp.broadcast_shapes(*shapes) + (width,),
                    dtype=jnp.uint64)
    nb_out = [0] * width
    for v, nb in plus:
        out = out.at[..., :v.shape[-1]].add(v)
        for i, x in enumerate(nb):
            nb_out[i] += x
    if off is not None:
        out = out.at[..., :len(ob)].add(jnp.asarray(off))
        for i, x in enumerate(ob):
            nb_out[i] += x
        for v, _ in minus:
            out = out.at[..., :v.shape[-1]].add(-v)
    assert max(nb_out) < (1 << 63), "u64 column overflow in col_acc"
    return (out, nb_out)


def raw_sqr_bounded(a, bounds):
    """Triangular schoolbook square: col_k = 2·Σ_{i<j, i+j=k} a_i·a_j +
    [k even]·a_{k/2}² — ~n(n+1)/2 column MACs instead of n² (the u64 lane
    multiply dominates product cost, so squares run ~40% cheaper than
    general products; `dbl`'s Y² / Z² and Fermat's square chain are the
    beneficiaries). Bounds are identical to the general product's."""
    n = len(bounds)
    a = _mul_operand(a, bounds)
    a2 = _mul_operand(a * jnp.uint64(2), [b * 2 for b in bounds])
    cols = jnp.zeros(a.shape[:-1] + (2 * n - 1,), dtype=jnp.uint64)
    # row i covers columns [2i, i+n): the diagonal a_i² then doubled cross
    # terms a_i·2a_j (j > i) — CONTIGUOUS slice updates (a strided
    # cols[0::2] diagonal scatter forces a relayout on TPU)
    for i in range(n):
        seg = jnp.concatenate([a[..., i:i + 1], a2[..., i + 1:]], axis=-1)
        cols = cols.at[..., 2 * i: i + n].add(a[..., i:i + 1] * seg)
    nb = [0] * (2 * n - 1)
    for i, ab in enumerate(bounds):
        for j, bb in enumerate(bounds):
            nb[i + j] += ab * bb
    assert max(nb) < (1 << 63), "u64 column overflow in squared schoolbook"
    return cols, nb


def sqr_cols(ar):
    """Triangular square of a relaxed pair → raw (cols, bounds), NO
    normalize — the squared sibling of :func:`mul_cols`."""
    a, ab = ar if isinstance(ar, tuple) else rel(ar)
    return raw_sqr_bounded(a, ab)


def sqr(a, p: int):
    cols, nb = raw_sqr_bounded(a, _CONTRACT)
    return _normalize(cols, nb, p)[0]


_CONTRACT2 = [2 * c for c in _CONTRACT]


def mul_of_sums(a1, a2, b1, b2, p: int):
    """(a1+a2)·(b1+b2) mod p without normalizing the sums: the adds' carry
    passes are absorbed into the product's own normalize (2×-contract input
    bounds keep every u64 column far under 2^63 — asserted exactly). Shaves
    two normalize walks off the (X1+Y1)(X2+Y2)-style cross terms that
    dominate complete-addition formulas."""
    cols, nb = raw_mul_bounded(a1 + a2, b1 + b2, _CONTRACT2, _CONTRACT2)
    return _normalize(cols, nb, p)[0]


def sqr_of_sum(a1, a2, p: int):
    """(a1+a2)² mod p without normalizing the sum."""
    cols, nb = raw_sqr_bounded(a1 + a2, _CONTRACT2)
    return _normalize(cols, nb, p)[0]


def add(a, b, p: int):
    nb = [x + y for x, y in zip(_CONTRACT, _CONTRACT)]
    return _normalize(a + b, nb, p)[0]


# 32p in a redundant limb encoding where limbs 0..15 each dominate the
# contract bound, for borrow-free subtraction. 17 limbs total.
def _offset_32p(p: int) -> np.ndarray:
    base = to_limbs(32 * p, 17).astype(np.int64)
    D = 1 << 17
    base[0] += D
    for i in range(1, 15):
        base[i] += D - 2        # add dominator, repay 2 borrowed by limb i-1
    base[15] += (1 << 18) - 2   # limb 15 dominates its 2^18 headroom
    base[16] -= 4               # repay limb 15's dominator
    out = base.astype(np.uint64)
    assert all(int(out[i]) >= _CONTRACT[i] for i in range(NLIMB))
    assert int(out[16]) >= 0
    assert sum(int(v) << (LIMB_BITS * i) for i, v in enumerate(out)) == 32 * p
    return out


_OFFSETS = {p: _offset_32p(p) for p in _FOLD}


def sub(a, b, p: int):
    """a - b mod p via the borrow-free 32p offset (dominates contract limbs)."""
    off = _OFFSETS[p]
    t = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]) + (NLIMB + 1,),
                  dtype=jnp.uint64)
    t = t.at[..., :NLIMB].add(a + jnp.asarray(off[:NLIMB]) - b)
    t = t.at[..., NLIMB].add(jnp.uint64(off[NLIMB]))
    nb = [cb + int(off[i]) for i, cb in enumerate(_CONTRACT)] + [int(off[16])]
    return _normalize(t, nb, p)[0]


def neg(a, p: int):
    return sub(jnp.zeros_like(a), a, p)


# Bound on mul_const's scalar: limb bound (< 2^18) x constant must stay under
# the u64 column capacity with headroom for the normalize walk.
MUL_CONST_MAX = 1 << 45


def mul_const(a, c: int, p: int):
    """Multiply by a small host constant (c < MUL_CONST_MAX)."""
    assert 0 <= c < MUL_CONST_MAX
    if c == 0:
        return jnp.zeros_like(a)
    nb = [b * c for b in _CONTRACT]
    return _normalize(_mul_operand(a, _CONTRACT) * jnp.uint64(c), nb, p)[0]


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


_DONATING_JIT_CACHE: dict = {}
_DONATING_JIT_LOCK = threading.Lock()


def donation_supported() -> bool:
    """True when the active backend implements input-buffer donation.
    CPU does not: jax warns and silently keeps the copy, so donation is
    gated off there rather than paying a warning per dispatch."""
    return jax.default_backend() != "cpu"


def donating_jit(key, fn, donate_argnums, **jit_kwargs):
    """Process-cached ``jax.jit(fn, donate_argnums=...)`` for the async
    service path: per-batch input buffers are donated to the kernel so
    XLA reuses their device memory for outputs/temporaries instead of
    allocating fresh HBM per flush (guide: persistent per-request buffers
    + donate, all_trn_tricks).

    Two rules every caller must honor:

    - donate ONLY per-batch arrays. The committed lookup tables from
      :func:`device_table_cache` are reused across every dispatch —
      donating one would invalidate the cache and crash the next batch.
    - donated variants are SEPARATE jit handles from the plain kernels:
      synchronous callers that re-invoke with the same prepared args
      would find them deleted by donation.

    Resolved lazily at first call (never at import) so pulling in an ops
    module does not force backend initialization; on CPU this degrades
    to a plain ``jax.jit``."""
    cached = _DONATING_JIT_CACHE.get(key)
    if cached is None:
        with _DONATING_JIT_LOCK:
            cached = _DONATING_JIT_CACHE.get(key)
            if cached is None:
                kw = dict(jit_kwargs)
                if donation_supported():
                    kw["donate_argnums"] = donate_argnums
                cached = _DONATING_JIT_CACHE[key] = jax.jit(fn, **kw)
    return cached


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
