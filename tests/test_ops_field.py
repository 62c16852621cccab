"""Differential tests of the device limb field arithmetic vs Python ints.

The field ops are lazily reduced (relaxed limbs < 1.5*2^16, any residue
mod p) — tests canonicalise with F.canon before comparing against Python
modular arithmetic, and separately check the relaxed-limb invariant.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from corda_tpu.ops import field as F

RNG = np.random.default_rng(42)
PRIMES = [F.P25519, F.PSECP, F.PSECR1]


def rand_elems(p, n=64):
    vals = [int.from_bytes(RNG.bytes(32), "little") % p for _ in range(n)]
    # include edge cases
    vals[:6] = [0, 1, p - 1, p - 2, (1 << 255) % p, (p - 1) // 2]
    return vals


def canon_int(a, p):
    """Device array → canonical Python ints, asserting the lazy invariant:
    limbs 0..14 < LMAX, limb 15 < 2^18 (field.py module contract)."""
    arr = np.asarray(a, dtype=np.uint64)
    assert (arr[..., :15] < F.LMAX).all(), "INV violated: limb >= 1.5*2^16"
    assert (arr[..., 15] < F.LIMB15_MAX).all(), "INV violated: limb15 >= 2^18"
    return F.from_limbs(F.canon(a, p))


@pytest.mark.parametrize("p", PRIMES)
def test_limb_roundtrip(p):
    vals = rand_elems(p)
    assert F.from_limbs(F.to_limbs(vals)) == vals


@pytest.mark.parametrize("p", PRIMES)
def test_canon(p):
    # canon must reduce any 16-limb value (up to 2^256-1) below p.
    vals = [0, 1, p - 1, p, p + 1, 2 * p - 1, (1 << 256) - 1, (1 << 256) - 2]
    vals = [v for v in vals if v < (1 << 256)]
    out = F.from_limbs(F.canon(jnp_arr(vals), p))
    assert out == [v % p for v in vals]


def jnp_arr(vals):
    import jax.numpy as jnp
    return jnp.asarray(F.to_limbs(vals))


@pytest.mark.parametrize("p", PRIMES)
def test_mul(p):
    a, b = rand_elems(p), rand_elems(p)
    out = canon_int(F.mul(F.to_limbs(a), F.to_limbs(b), p), p)
    assert out == [(x * y) % p for x, y in zip(a, b)]


@pytest.mark.parametrize("p", PRIMES)
def test_mul_lazy_inputs(p):
    # inputs anywhere in [0, 2^256) must still multiply correctly mod p
    a = [(1 << 256) - 1 - i for i in range(8)] + rand_elems(p, 8)
    b = rand_elems(p, 8) + [(1 << 256) - 17 - i for i in range(8)]
    out = canon_int(F.mul(F.to_limbs(a), F.to_limbs(b), p), p)
    assert out == [(x * y) % p for x, y in zip(a, b)]


def _int(limbs) -> int:
    """The integer a row of (signed) limbs or columns stands for."""
    return sum(int(v) << (F.LIMB_BITS * i) for i, v in enumerate(limbs))


def _rows_within(bounds, n_random=12):
    """Rows of limbs inside exact per-limb intervals: every limb at its
    lowest, at its highest, the two alternating, and random ones."""
    lo = np.array([l for l, _ in bounds], dtype=np.int64)
    hi = np.array([h for _, h in bounds], dtype=np.int64)
    even = np.arange(len(bounds)) % 2 == 0
    rows = [lo, hi, np.where(even, lo, hi), np.where(even, hi, lo)]
    rows += [RNG.integers(lo, hi + 1) for _ in range(n_random)]
    return np.stack(rows)


def _contract_rows(p):
    """Contract elements: limbs at the contract's edge, all-0xffff,
    all-0x8000, all-0x7fff, zero, and canonical elements of the field."""
    edge = [[v] * 16 for v in (0xffff, 0x8000, 0x7fff, 0)]
    return np.concatenate([
        _rows_within(F._CONTRACT, 4), np.array(edge, dtype=np.int64),
        F.to_limbs(rand_elems(p, 12)).astype(np.int64)])


def _assert_exact(cols, bounds, want):
    """Columns are the EXACT integer (not only its residue), each inside
    the interval derived for it at trace time."""
    cols = np.asarray(cols)
    assert cols.dtype == np.int32
    for row, w in zip(cols, want):
        assert _int(row) == w
        assert all(l <= int(c) <= h for c, (l, h) in zip(row, bounds))


@pytest.mark.parametrize("p", PRIMES)
def test_the_product_is_the_exact_integer(p):
    a = _contract_rows(p)
    b = a[::-1].copy()
    ua, ub = jnp.asarray(a, jnp.uint64), jnp.asarray(b, jnp.uint64)
    want = [_int(x) * _int(y) for x, y in zip(a, b)]
    _assert_exact(*F.raw_mul_bounded(ua, ub), want)
    _assert_exact(*F.mul_cols(ua, ub), want)
    out = canon_int(F.mul(ua, ub, p), p)
    assert out == [w % p for w in want]


@pytest.mark.parametrize("p", PRIMES)
def test_a_square_is_the_product_of_a_value_with_itself(p):
    a = _contract_rows(p)
    ua = jnp.asarray(a, jnp.uint64)
    want = [_int(x) ** 2 for x in a]
    _assert_exact(*F.raw_sqr_bounded(ua, F._CONTRACT), want)
    _assert_exact(*F.raw_mul_bounded(ua, ua), want)
    assert canon_int(F.sqr(ua, p), p) == [w % p for w in want]
    assert canon_int(F.sqr_of_sum(ua, ua, p), p) == [4 * w % p for w in want]
    doubled = F.rel_add(ua, ua)                # (a + a)²: a relaxed operand
    _assert_exact(*F.sqr_cols(doubled), [4 * w for w in want])
    _assert_exact(*F.mul_cols(doubled, doubled), [4 * w for w in want])


@functools.lru_cache(maxsize=None)
def _operand_classes():
    """Every distinct exact bounds an operand reaches the product with
    while the two production kernels (and the a = -3 formulas, which no
    kernel on the chip runs) are traced: collected, not listed by hand."""
    from corda_tpu.core.crypto.ecmath import SECP256R1
    from corda_tpu.ops import ed25519 as ed_ops
    from corda_tpu.ops import weierstrass as wc_ops
    seen, digits = set(), F._digits

    def spy(a, bounds):
        seen.add(tuple(bounds))
        return digits(a, bounds)

    S = jax.ShapeDtypeStruct
    rows, w, g = 8, ed_ops.SPLIT_B_WINDOW, wc_ops.HYBRID_G_WINDOW
    table = S((1 << w, F.NLIMB), jnp.uint16)
    gtab = S((1 << (2 * g + 2), F.NLIMB), jnp.uint16)
    el = S((rows, F.NLIMB), jnp.uint64)
    F._digits = spy
    try:
        jax.eval_shape(
            functools.partial(ed_ops.verify_core_split, w=w),
            S((256 // w, rows), jnp.int32),
            S((128 // w, w // 2, rows), jnp.uint8),
            S((rows, 6, F.NLIMB), jnp.uint16), S((rows, F.NLIMB), jnp.uint16),
            *(table,) * 6)
        jax.eval_shape(
            functools.partial(wc_ops.verify_core_hybrid_wide, g_w=g),
            S((128 // g, rows), jnp.int32), S((128 // g, g // 2, rows), jnp.uint8),
            S((rows, 4, F.NLIMB), jnp.uint16), S((rows, F.NLIMB), jnp.uint16),
            gtab, gtab, S((1 << (2 * g + 2),), jnp.uint8))
        for fn in (lambda P, Q: wc_ops.add(P, Q, SECP256R1),
                   lambda P, Q: wc_ops.dbl(P, SECP256R1),
                   lambda P, Q: wc_ops._madd_w(P, Q[:2], SECP256R1)):
            jax.eval_shape(fn, (el,) * 3, (el,) * 3)
    finally:
        F._digits = digits
    return sorted(seen)


def test_the_formulas_pass_signed_and_doubled_operands():
    """The collection is not vacuous: the kernels hand the product plain
    contract elements, un-normalized sums and SIGNED differences."""
    classes = _operand_classes()
    assert tuple(F._CONTRACT) in classes
    assert any(min(l for l, _ in c) < 0 for c in classes)
    assert any(max(h for _, h in c) >= 2 * (F.LIMB15_MAX - 1) for c in classes)
    assert 4 <= len(classes) <= 24


@pytest.mark.parametrize("p", PRIMES)
def test_the_product_takes_the_widest_operands_any_formula_passes(p):
    """Each collected class against itself, against its mirror and
    against a contract element: exact, inside its bounds, and the walk
    lands on the contract with the right residue."""
    contract = _rows_within(F._CONTRACT)
    for bounds in _operand_classes():
        a = _rows_within(bounds)
        for b, bb in ((a[::-1].copy(), bounds), (contract, F._CONTRACT)):
            want = [_int(x) * _int(y) for x, y in zip(a, b)]
            ar, br = (jnp.asarray(a), list(bounds)), (jnp.asarray(b), list(bb))
            cols = F.mul_cols(ar, br)
            _assert_exact(*cols, want)
            assert canon_int(F.norm(cols, p), p) == [w % p for w in want]
        sq = F.sqr_cols((jnp.asarray(a), list(bounds)))
        _assert_exact(*sq, [_int(x) ** 2 for x in a])


def test_an_operand_too_wide_for_the_product_is_refused_at_trace_time():
    a = jnp.asarray(_rows_within(F._CONTRACT), jnp.uint64)
    wide = F.scale_rel(a, 1 << 14)      # limbs up to 2^32: no int32 digit
    for refused in (lambda: F.mul_cols(wide, a), lambda: F.sqr_cols(wide),
                    lambda: F.mul_cols(a, F.scale_rel(a, 1 << 40))):
        with pytest.raises(AssertionError, match="too wide|overflows"):
            jax.eval_shape(refused)
    # the widest that still fits is taken, and exact
    ok = F.scale_rel(a, 1 << 12)
    _assert_exact(*F.mul_cols(ok, a),
                  [(_int(x) << 12) * _int(x) for x in np.asarray(a)])


@pytest.mark.parametrize("p", PRIMES)
def test_add_sub_neg(p):
    a, b = rand_elems(p), rand_elems(p)
    la, lb = F.to_limbs(a), F.to_limbs(b)
    assert canon_int(F.add(la, lb, p), p) == [(x + y) % p for x, y in zip(a, b)]
    assert canon_int(F.sub(la, lb, p), p) == [(x - y) % p for x, y in zip(a, b)]
    assert canon_int(F.neg(la, p), p) == [(-x) % p for x in a]


@pytest.mark.parametrize("p", PRIMES)
def test_add_sub_lazy_inputs(p):
    top = (1 << 256) - 1
    a = [top, top, 0, top - 5]
    b = [top, 0, top, 17]
    la, lb = F.to_limbs(a), F.to_limbs(b)
    assert canon_int(F.add(la, lb, p), p) == [(x + y) % p for x, y in zip(a, b)]
    assert canon_int(F.sub(la, lb, p), p) == [(x - y) % p for x, y in zip(a, b)]


@pytest.mark.parametrize("p", PRIMES)
def test_mul_const(p):
    a = rand_elems(p)
    for c in [0, 1, 2, 8, 38, 977, 121666]:
        out = canon_int(F.mul_const(F.to_limbs(a), c, p), p)
        assert out == [(x * c) % p for x in a]


@pytest.mark.parametrize("p", PRIMES)
def test_predicates(p):
    a = rand_elems(p, 8)
    la = F.to_limbs(a)
    assert list(np.asarray(F.eq(la, la, p))) == [True] * 8
    assert list(np.asarray(F.is_zero(la, p))) == [v == 0 for v in a]
    lb = F.to_limbs(a[::-1])
    assert list(np.asarray(F.eq(la, lb, p))) == [x == y for x, y in zip(a, a[::-1])]
    # lazy congruence: v and v+p are equal mod p though limb-distinct
    small = [3, 9]
    shifted = [v + p for v in small]
    assert list(np.asarray(F.eq(F.to_limbs(small), F.to_limbs(shifted), p))) == [True, True]


@pytest.mark.parametrize("p", PRIMES)
def test_pow_small(p):
    a = rand_elems(p, 8)
    la = F.to_limbs(a)
    out = canon_int(F.pow_const(la, 65537, p), p)
    assert out == [pow(x, 65537, p) for x in a]


@pytest.mark.parametrize("p", PRIMES[:2])
def test_inv(p):
    a = [v or 1 for v in rand_elems(p, 8)]
    la = F.to_limbs(a)
    out = canon_int(F.inv(la, p), p)
    assert out == [pow(x, p - 2, p) for x in a]


# ---------------------------------------------------------------------------
# inv_batch: one inversion chain a batch (Montgomery's trick as a tree)
# ---------------------------------------------------------------------------

#: 1, 8, 24 and 64 keep the per-row chain (no power of two, or under the stop
#: width); 256 and 1024 go through the tree, one level and three
INV_WIDTHS = (1, 8, 24, 64, 256, 1024)


@functools.cache
def _inv_batch_results(p):
    """Every width through ONE compiled program per prime (the chain is
    ~255 squarings: compiled, not dispatched operation by operation)."""
    rng = np.random.default_rng(p % 1000)
    vals = {}
    for n in INV_WIDTHS:
        row = [int.from_bytes(rng.bytes(32), "little") % p or 1
               for _ in range(n)]
        row[0] = p - 1
        if n >= 8:
            # rows that are zero mod p, one of them not canonical (p itself):
            # inside the first and the second half of every level
            row[n // 3], row[n - 2] = 0, p
        vals[n] = row
    out = jax.jit(lambda zs: {n: F.inv_batch(z, p) for n, z in zs.items()})(
        {n: jnp.asarray(F.to_limbs(v)) for n, v in vals.items()})
    return vals, out


@pytest.mark.parametrize("n", INV_WIDTHS)
@pytest.mark.parametrize("p", [F.P25519, F.PSECP], ids=["p25519", "psecp"])
def test_inv_batch_equals_inv_row_for_row(p, n):
    """What F.inv gives a row (a^(p-2), 0 for 0: test_inv) is what
    inv_batch gives it, whatever stands in the other rows."""
    assert INV_WIDTHS[-3] < F.INV_BATCH_STOP < INV_WIDTHS[-2], \
        "the widths above no longer stand on both sides of the stop width"
    vals, out = _inv_batch_results(p)
    inverse, nonzero = out[n]
    assert canon_int(inverse, p) == [pow(x, p - 2, p) for x in vals[n]]
    assert list(np.asarray(nonzero)) == [x % p != 0 for x in vals[n]]
