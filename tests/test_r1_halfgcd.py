"""Half-gcd split path (secp256r1): decomposition contract, native vs
Python differentials, and fallback parity against the host oracle.

The Antipa split rewrites u2 into v1/v2 (both < 2^128); the device then
checks [t_lo]G + [t_hi]G' + [|v1|](±Q) == [v2]R with a 124-doubling
ladder.  These tests pin:

- the decomposition contract (u2·v2 ≡ ±v1 (mod n), STRICT 2^128 bounds —
  a leg exactly 2^128 is impossible: |t_i| ≤ n/r_{i-1} with r_{i-1} ≥
  2^128 at the stopping step);
- bit-identical native (sm_r1_halfgcd / sm_r1_prep_hg) vs pure-Python
  outputs, 10k random scalars + adversarial edges;
- verdict parity with ecmath.ecdsa_verify on mixed valid/invalid/
  malformed/fallback batches, BOTH with and without the native library
  (the acceptance criterion's with/without matrix).
"""
import hashlib
import random

import numpy as np
import pytest

from corda_tpu.core.crypto import ecmath
from corda_tpu.ops import scalarprep as sp
from corda_tpu.ops import weierstrass as wc

CURVE = ecmath.SECP256R1
N = CURVE.n

needs_native = pytest.mark.skipif(not sp.available(),
                                  reason="libscalarmath.so not built")


def _check_contract(u2: int, dec) -> None:
    assert dec is not None, u2
    neg1, v1, v2 = dec
    assert 0 <= v1 < (1 << 128), (u2, v1)       # strict: never == 2^128
    assert 0 < v2 < (1 << 128), (u2, v2)
    want = (N - v1) % N if neg1 else v1
    assert u2 * v2 % N == want, u2


def _edge_scalars():
    return [1, 2, 3, N - 1, N - 2, (1 << 128) - 1, 1 << 128,
            (1 << 128) + 1, N >> 128, 3 << 127, N - (1 << 128), N // 3]


def test_halfgcd_python_contract():
    rng = random.Random(501)
    for u2 in _edge_scalars() + [rng.randrange(1, N) for _ in range(2000)]:
        _check_contract(u2, sp.r1_halfgcd_py(u2))
    # u2 < 2^128 short-circuits to (False, u2, 1)
    assert sp.r1_halfgcd_py(12345) == (False, 12345, 1)
    # degenerate inputs are refused, not mangled
    for bad in (0, N, N + 5):
        assert sp.r1_halfgcd_py(bad) is None


@needs_native
def test_halfgcd_native_matches_python_10k():
    rng = random.Random(502)
    cases = _edge_scalars() + [rng.randrange(1, N) for _ in range(10_000)]
    for u2 in cases:
        native = sp.r1_halfgcd(u2)
        python = sp.r1_halfgcd_py(u2)
        assert native == python, u2
    for bad in (0, N, N + 5):
        assert sp.r1_halfgcd(bad) is None
        assert sp.r1_halfgcd_py(bad) is None


@needs_native
def test_r1p_mulfast_matches_python():
    rng = random.Random(503)
    p = CURVE.p
    ops = [(0, 0), (1, p - 1), (p - 1, p - 1), (1 << 128, 1 << 128)]
    ops += [(rng.randrange(p), rng.randrange(p)) for _ in range(2000)]
    for a, b in ops:
        assert sp.r1p_mulfast(a, b) == a * b % p, (a, b)


def _mixed_items():
    """Valid + tampered + malformed + split-degenerate items.  13 items →
    one 16-bucket, so every e2e test below shares one kernel compile."""
    rng = np.random.default_rng(504)
    items = []
    for _ in range(6):
        priv = int.from_bytes(rng.bytes(32), "little") % (N - 1) + 1
        pub = CURVE.mul(priv, CURVE.g)
        msg = rng.bytes(36)
        r, s = ecmath.ecdsa_sign(CURVE, priv, msg)
        items.append((pub, msg, r, s))
    pub0, msg0, r0, s0 = items[0]
    items += [
        (pub0, msg0 + b"!", r0, s0),                    # tampered message
        (pub0, msg0, (r0 + 1) % N or 1, s0),            # tampered r
        (pub0, msg0, 0, s0),                            # r = 0 (DER clamp)
        (pub0, msg0, N + 5, s0),                        # r >= n
        (pub0, msg0, r0, N - s0),                       # high-s twin: valid
        ((pub0[0], (pub0[1] + 1) % CURVE.p), msg0, r0, s0),  # off-curve
        (None, msg0, r0, s0),                           # missing key
    ]
    return items


def _fallback_items():
    """Items that PASS the structural precheck but degenerate the split
    (r + n < p ⇒ the r+n x-candidate exists ⇒ hg_ok = 0): tiny r values —
    unreachable by honest signing (~2^-64), craftable by an adversary."""
    rng = np.random.default_rng(505)
    priv = int.from_bytes(rng.bytes(32), "little") % (N - 1) + 1
    pub = CURVE.mul(priv, CURVE.g)
    msg = rng.bytes(30)
    _, s = ecmath.ecdsa_sign(CURVE, priv, msg)
    return [(pub, msg, r, s) for r in (1, 2, 5, 1000, 1 << 64)]


def _oracle(items):
    return np.asarray([ecmath.ecdsa_verify(CURVE, pub, msg, r, s)
                       for pub, msg, r, s in items])


@needs_native
def test_r1_prep_hg_native_matches_python():
    items = _mixed_items() + _fallback_items()[:3]
    native = wc._prepare_r1_split_native_words(*wc._items_to_words(items), 16)
    python = wc._prepare_r1_split_python(CURVE, items, 16)
    names = ["g_idx", "q_digits", "Q", "xd_limbs", "lo_x", "lo_y", "lo_ok",
             "hi_x", "hi_y", "hi_ok", "precheck", "forced"]
    assert len(native) == len(python) == len(names)
    for name, a, b in zip(names, native, python):
        if isinstance(a, tuple):
            for i, (ac, bc) in enumerate(zip(a, b)):
                np.testing.assert_array_equal(
                    np.asarray(ac), np.asarray(bc), err_msg=f"{name}[{i}]")
        else:
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=name)


def test_fallback_items_marked_and_forced():
    """hg_ok=0 items must be masked OUT of precheck_eff and carry the host
    oracle's verdict in `forced` — through whichever prep is loaded."""
    items = _fallback_items() + _mixed_items()[:3]
    *_, precheck_eff, forced = wc.prepare_batch_r1_split(CURVE, items, 16)
    n_fb = len(_fallback_items())
    assert not precheck_eff[:n_fb].any()      # every tiny-r item fell back
    np.testing.assert_array_equal(forced[:n_fb], _oracle(items)[:n_fb])
    assert not forced[n_fb:].any()            # non-fallback rows untouched


# The e2e tests below share ONE 16-bucket kernel compile (cold ~minutes on
# CPU, then persistent-cached in .jax_cache — same deal as the r1 kernels
# already in the default tier, see tests/test_ops_curves.py).

@needs_native
def test_split_verdicts_match_oracle_native():
    items = _mixed_items()
    got = wc.verify_batch(CURVE, items)
    np.testing.assert_array_equal(got, _oracle(items))


def test_split_verdicts_match_oracle_python(monkeypatch):
    monkeypatch.setattr(sp, "_LIB", None)
    assert not sp.available()
    items = _mixed_items()
    got = wc.verify_batch(CURVE, items)
    np.testing.assert_array_equal(got, _oracle(items))
    # Crypto.doVerify's rule: the six signed rows and the n - s twin
    assert list(np.nonzero(got)[0]) == [0, 1, 2, 3, 4, 5, 10]


def test_fallback_parity_end_to_end():
    """rn_ok=False (hg_ok=0) items return verdicts identical to the host
    oracle through the FULL verify path — fallbacks mixed into a batch of
    valid and invalid members, plus the async words seam."""
    items = _mixed_items()[:6] + _fallback_items()[:3]
    want = _oracle(items)
    got = wc.verify_batch(CURVE, items)
    np.testing.assert_array_equal(got, want)
    if sp.available():
        pend = wc.verify_batch_async_words(CURVE, *wc._items_to_words(items))
        assert len(pend) == 4                 # (dev, precheck, n, forced)
        np.testing.assert_array_equal(wc.finish_batch(pend), want)


def test_host_verify_scalars_matches_oracle():
    for pub, msg, r, s in _mixed_items():
        e_raw = int.from_bytes(hashlib.sha256(msg).digest(), "big")
        assert (wc._r1_host_verify_scalars(CURVE, pub, e_raw, r, s)
                == ecmath.ecdsa_verify(CURVE, pub, msg, r, s)), (r, s)


def test_split_python_prep_handles_empty_and_all_invalid():
    (g_idx, q_digits, Q, xd, *_tabs, precheck,
     forced) = wc._prepare_r1_split_python(
        CURVE, [(None, b"m", 5, 7), (None, b"n", 0, 0)], 16)
    assert not precheck.any() and not forced.any()
    assert not np.asarray(g_idx).any() and not np.asarray(q_digits).any()
