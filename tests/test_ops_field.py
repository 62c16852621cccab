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
