"""Differential tests: device curve kernels vs the pure-Python host oracle.

Mirrors the reference's crypto unit tests (core/src/test/.../crypto/
CryptoUtilsTest: sign/verify roundtrip + malformed-input rejection per
scheme) as the bit-exactness oracle for the TPU kernels (SURVEY.md §4.1).
"""
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_crypto_host_policy import ROWS as CORPUS_ROWS, _corpus

from corda_tpu.core.crypto import ecmath
from corda_tpu.observability.profiling import get_profiler
from corda_tpu.ops import ed25519 as ed_ops
from corda_tpu.ops import field as F
from corda_tpu.ops import scalarprep
from corda_tpu.ops import weierstrass as wc_ops

RNG = np.random.default_rng(7)


def rand_scalar(n):
    return int.from_bytes(RNG.bytes(32), "little") % n


# ---------------------------------------------------------------------------
# Ed25519
# ---------------------------------------------------------------------------

def ed_rand_points(k):
    pts = []
    for _ in range(k):
        s = rand_scalar(ecmath.ED_L)
        pts.append(ecmath.ed_to_affine(
            ecmath.ed_scalar_mul(s, ecmath.ed_to_extended(ecmath.ED_B))))
    return pts


def test_ed_add_double_matches_host():
    pts = ed_rand_points(4)
    qts = ed_rand_points(4)
    Pb = ed_ops._pack_point_ext(pts)
    Qb = ed_ops._pack_point_ext(qts)
    got_add = ed_ops.add(Pb, Qb)
    got_dbl = ed_ops.double(Pb)
    for i, (pa, qa) in enumerate(zip(pts, qts)):
        want = ecmath.ed_to_affine(ecmath.ed_point_add(
            ecmath.ed_to_extended(pa), ecmath.ed_to_extended(qa)))
        assert _affine(got_add, i) == want
        want_d = ecmath.ed_to_affine(ecmath.ed_point_double(ecmath.ed_to_extended(pa)))
        assert _affine(got_dbl, i) == want_d


def test_ed25519_verify_batch():
    items, want = [], []
    for i in range(8):
        seed = RNG.bytes(32)
        pub = ecmath.ed25519_public_key(seed)
        msg = RNG.bytes(40 + i)
        sig = ecmath.ed25519_sign(seed, msg)
        if i % 4 == 1:  # corrupt signature
            sig = sig[:10] + bytes([sig[10] ^ 1]) + sig[11:]
        if i % 4 == 2:  # corrupt message
            msg = msg[:-1] + bytes([msg[-1] ^ 0xFF])
        if i % 4 == 3:  # wrong key
            pub = ecmath.ed25519_public_key(RNG.bytes(32))
        items.append((pub, sig, msg))
        want.append(ecmath.ed25519_verify(pub, msg, sig))
    got = ed_ops.verify_batch(items)
    assert list(got) == want
    assert want[0] and not all(want)  # sanity: mix of verdicts


def test_ed25519_malformed_inputs():
    seed = RNG.bytes(32)
    pub = ecmath.ed25519_public_key(seed)
    msg = b"hello"
    sig = ecmath.ed25519_sign(seed, msg)
    bad_s = sig[:32] + (ecmath.ED_L + 1).to_bytes(32, "little")  # s >= L
    items = [
        (b"\xff" * 32, sig, msg),        # non-decompressible key
        (pub, b"\x00" * 63, msg),        # short signature
        (pub, bad_s, msg),
        (pub, sig, msg),                 # control: valid
    ]
    got = ed_ops.verify_batch(items)
    assert list(got) == [False, False, False, True]


def test_ed25519_r_encoding_edge_cases():
    """The re-encoding acceptance's R-specific rejections, each checked
    against the host oracle: a flipped x-sign bit (same y, DIFFERENT
    point), a non-canonical y (>= p, must reject like a failed
    decompression), and an off-curve y."""
    seed = RNG.bytes(32)
    pub = ecmath.ed25519_public_key(seed)
    msg = b"sign-bit coverage"
    sig = ecmath.ed25519_sign(seed, msg)
    flipped_sign = sig[:31] + bytes([sig[31] ^ 0x80]) + sig[32:]
    non_canonical = (2**255 - 10).to_bytes(32, "little") + sig[32:]
    # y = 2 is not on the curve (no x satisfies the equation)
    off_curve = (2).to_bytes(32, "little") + sig[32:]
    items = [(pub, s, msg)
             for s in (sig, flipped_sign, non_canonical, off_curve)]
    want = [ecmath.ed25519_verify(pub, msg, s)
            for _, s, _ in items]
    assert want == [True, False, False, False]  # oracle sanity
    assert list(ed_ops.verify_batch(items)) == want


def _affine(pt, i):
    """Row i of a device point batch → host affine (x, y), read from X, Y, Z."""
    x, y, z = (F.from_limbs(c[i]) for c in pt[:3])
    zi = pow(z, ecmath.ED_P - 2, ecmath.ED_P)
    return (x * zi % ecmath.ED_P, y * zi % ecmath.ED_P)


#: name → (device formula over (P batch, Q batch), host formula)
_ED_CACHED = {
    "add_cached": (lambda P, Q: ed_ops.add_cached(P, ed_ops.to_cached(Q)),
                   ecmath.ed_point_add),
    "add_cached_identity": (lambda P, Q: ed_ops.add_cached(
        P, ed_ops.cached_identity((4,))), lambda p, q: p),
}


@pytest.mark.parametrize("name", sorted(_ED_CACHED))
def test_ed_add_cached_matches_host(name):
    """add_cached gives ecmath's X, Y, Z (as the affine point they stand
    for), and a T that is X·Y/Z."""
    device, host = _ED_CACHED[name]
    pts, qts = ed_rand_points(4), ed_rand_points(4)
    pts[3] = qts[3]                      # a doubling through the addition
    # u64 lanes, as inside a kernel (_pack_point_ext ships u16)
    Pb, Qb = (tuple(jnp.asarray(c, jnp.uint64)
                    for c in ed_ops._pack_point_ext(v)) for v in (pts, qts))
    got = device(Pb, Qb)
    for i, (pa, qa) in enumerate(zip(pts, qts)):
        want = ecmath.ed_to_affine(host(ecmath.ed_to_extended(pa),
                                        ecmath.ed_to_extended(qa)))
        assert _affine(got, i) == want
        x, y, z, t = (F.from_limbs(c[i]) for c in got)
        assert (t * z - x * y) % ecmath.ED_P == 0


#: the tail at a width that takes F.inv_batch's tree and at one that keeps
#: the per-row chain
TAIL_WIDTHS = (256, 24)


@functools.cache
def _tail_verdicts():
    """Crafted accumulators through reencode_verdict, both widths in one
    compiled program. Rows: a projective form (X, Y, Z) = (x·z, y·z, z) of a
    point, judged against its own encoding (accept), against another point's
    y (refuse) and against a flipped sign bit (refuse); and rows with Z ≡ 0
    (written 0 and p), which a product tree would spread over their whole
    subtree. Their wire y is 0 with sign 0: what X·0 and Y·0 re-encode to."""
    rng = np.random.default_rng(11)
    pts = ed_rand_points(8)
    p = ecmath.ED_P
    cases, want = {}, {}
    for n in TAIL_WIDTHS:
        xs, ys, zs, r_y, r_sign, verdicts = [], [], [], [], [], []
        for i in range(n):
            (x, y), z = pts[i % 8], int.from_bytes(rng.bytes(32), "little") % p
            kind = ("accept", "wrong_y", "wrong_sign", "accept")[i % 4]
            if i in (1, n // 2 + 3, n - 1):
                kind, z = "zero_z", (0, p, 0)[i % 3]
            xs.append(x * z % p)
            ys.append(y * z % p)
            zs.append(z)
            r_y.append({"wrong_y": pts[(i + 1) % 8][1], "zero_z": 0}.get(kind, y))
            r_sign.append({"wrong_sign": 1 - (x & 1), "zero_z": 0}.get(kind, x & 1))
            verdicts.append(kind == "accept")
        cases[n] = ((jnp.asarray(F.to_limbs(xs)), jnp.asarray(F.to_limbs(ys)),
                     jnp.asarray(F.to_limbs(zs)), None),
                    jnp.asarray(F.to_limbs(r_y)),
                    jnp.asarray(np.asarray(r_sign, dtype=np.uint64)))
        want[n] = verdicts
    got = jax.jit(lambda cs: {n: ed_ops.reencode_verdict(*c)
                              for n, c in cs.items()})(cases)
    return want, got


@pytest.mark.parametrize("n", TAIL_WIDTHS)
def test_a_zero_z_row_is_refused_and_decides_no_other_rows_verdict(n):
    assert (n >= 2 * F.INV_BATCH_STOP) == (n == TAIL_WIDTHS[0])
    want, got = _tail_verdicts()
    assert list(np.asarray(got[n])) == want[n]
    assert want[n].count(True) >= n // 3 and want[n].count(False) >= n // 3


#: the corpus through the device kernel at a bucket that takes the tree (five
#: rotations of it in one call, padded to 256) and at one that does not (8
#: rows a call)
@pytest.mark.parametrize("width", [256, 8])
def test_split_kernel_agrees_with_the_oracle_on_the_edge_corpus(width):
    """Non-canonical y, small-order points, s at and over L, flipped R / A /
    message: tests/test_crypto_host_policy.py's corpus, row for row against
    the pure oracle, through verify_core_split."""
    assert (width >= 2 * F.INV_BATCH_STOP) == (width == 256)
    corpus = _corpus()
    rows = [corpus[n] for n in CORPUS_ROWS]
    assert [ecmath.ed25519_verify(k, m, s) for k, s, m, _ in rows] \
        == [meant for *_, meant in rows]
    if width == 256:      # every row meets other neighbours in every half
        rows = [rows[(i + 7 * turn) % len(rows)]
                for turn in range(5) for i in range(len(rows))]
    got = []
    for at in range(0, len(rows), width):
        chunk = rows[at:at + width]
        assert F.bucket_size(len(chunk)) == width
        got.extend(ed_ops.verify_batch([(k, s, m) for k, s, m, _ in chunk]))
    assert got == [meant for *_, meant in rows]


#: Limb multiplications a row in verify_core_split as it stood at ed7d89b
#: (PR 29: an inversion a row, the joint table in extended form), BOTH SIDES
#: COUNTED THE SAME WAY: that commit's ops/ed25519.py traced over today's
#: ops/field.py with today's counter (every integer ``mul``, whatever its
#: lanes). PR 30 pinned 490,535 here, u64 multiplies of a 16 x 16 schoolbook;
#: since PR 32 a product is 16 x 16 balanced int32 digits plus two rows for
#: the operands' top carries and the folds' constants (307 a product, 171
#: a squaring), which moves the constant, not the ratio's meaning (today's
#: kernel reads 475,015: 0.887 of it; the same at 64 and at 8192 rows: that
#: kernel inverts per row).
PARENT_FIELD_PRODUCTS = 535_769


def test_the_split_kernel_spends_an_eighth_fewer_field_products():
    """At the bucket the service and the benchmark dispatch. (Under
    F.INV_BATCH_STOP rows the tail pays its chain per row, as the parent.)
    The count is the program's as traced: it includes the T products of the
    first doublings, which no compiler keeps, on both sides."""
    got = ed_ops.split_field_products(8192, 16)
    assert 0.5 * PARENT_FIELD_PRODUCTS < got <= 0.89 * PARENT_FIELD_PRODUCTS


def test_the_flight_recorder_says_which_split_kernel_ran():
    seed = RNG.bytes(32)
    pub = ecmath.ed25519_public_key(seed)
    assert list(ed_ops.verify_batch(
        [(pub, ecmath.ed25519_sign(seed, b"recorded", pub), b"recorded")]))
    record = get_profiler().snapshot()["kernels"]["ed25519.split"]
    assert record["field_products_per_row"] == ed_ops.split_field_products(
        8, ed_ops.SPLIT_B_WINDOW)


# ---------------------------------------------------------------------------
# ECDSA secp256k1 / secp256r1
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("curve", [ecmath.SECP256K1, ecmath.SECP256R1],
                         ids=lambda c: c.name)
def test_wc_add_matches_host(curve):
    pts = [curve.mul(rand_scalar(curve.n), curve.g) for _ in range(4)]
    qts = [curve.mul(rand_scalar(curve.n), curve.g) for _ in range(4)]
    qts[1] = pts[1]  # doubling case through the complete formula
    Pb = (F.to_limbs([p[0] for p in pts]), F.to_limbs([p[1] for p in pts]),
          F.to_limbs([1] * 4))
    Qb = (F.to_limbs([q[0] for q in qts]), F.to_limbs([q[1] for q in qts]),
          F.to_limbs([1] * 4))
    X, Y, Z = wc_ops.add(Pb, Qb, curve)
    for i, (pa, qa) in enumerate(zip(pts, qts)):
        want = curve.add(pa, qa)
        x, y, z = F.from_limbs(X[i]), F.from_limbs(Y[i]), F.from_limbs(Z[i])
        zi = pow(z, curve.p - 2, curve.p)
        assert (x * zi % curve.p, y * zi % curve.p) == want
    # dedicated doubling formula (incl. the identity edge case)
    Ib = tuple(np.asarray(c) for c in wc_ops.identity((1,)))
    Db = tuple(np.concatenate([np.asarray(c), i_c])
               for c, i_c in zip(Pb, Ib))
    X, Y, Z = wc_ops.dbl(Db, curve)
    for i, pa in enumerate(pts):
        want = curve.add(pa, pa)
        x, y, z = F.from_limbs(X[i]), F.from_limbs(Y[i]), F.from_limbs(Z[i])
        zi = pow(z, curve.p - 2, curve.p)
        assert (x * zi % curve.p, y * zi % curve.p) == want
    assert F.from_limbs(Z[len(pts)]) % curve.p == 0  # 2·identity = identity


@pytest.mark.parametrize(
    "curve,ladder",
    [(ecmath.SECP256K1, "plain"),
     (ecmath.SECP256K1, "hybrid"),   # endomorphism + constant-G gather table
     # r1 runs in the DEFAULT tier (VERDICT r3 #5): its 224-bit Solinas fold
     # constant makes the cold compile ~4min on CPU, but the persistent
     # .jax_cache (shared by CI/driver runs on this workspace) makes warm
     # runs seconds — an untested-by-default kernel is an unshipped kernel.
     (ecmath.SECP256R1, "plain"),
     # the r1 PRODUCTION path: the half-gcd split ladder, at the 16-row
     # bucket tests/test_r1_halfgcd.py compiles
     (ecmath.SECP256R1, "halfgcd")],
    ids=lambda v: v if isinstance(v, str) else v.name)
def test_ecdsa_verify_batch(curve, ladder):
    """``plain`` is the reference (``verify_batch_plain``); any other label
    names the curve's production ladder, which ``verify_batch`` takes."""
    items, want = [], []
    for i in range(12 if ladder == "halfgcd" else 8):
        priv = rand_scalar(curve.n - 1) + 1
        pub = curve.mul(priv, curve.g)
        msg = RNG.bytes(30 + i)
        r, s = ecmath.ecdsa_sign(curve, priv, msg)
        if i % 4 == 1:
            r = (r + 1) % curve.n or 1
        if i % 4 == 2:
            msg = msg + b"!"
        if i % 4 == 3:
            pub = curve.mul(rand_scalar(curve.n - 1) + 1, curve.g)
        items.append((pub, msg, r, s))
        want.append(ecmath.ecdsa_verify(curve, pub, msg, r, s))
    verify = (wc_ops.verify_batch_plain if ladder == "plain"
              else wc_ops.verify_batch)
    assert list(verify(curve, items)) == want
    assert want[0] and not all(want)


def test_hybrid_wide_window_widths_agree():
    """The wide-G ladder must verify identically at every (even) window
    width ON THE SAME INPUTS — g_w only changes how many bits one
    constant-table gather consumes, never the result (regression lock on
    the digit packing)."""
    curve = ecmath.SECP256K1
    rng = np.random.default_rng(77)
    items, want = [], []
    for i in range(8):
        priv = int.from_bytes(rng.bytes(32), "little") % (curve.n - 1) + 1
        pub = curve.mul(priv, curve.g)
        msg = rng.bytes(24 + i)
        r, s = ecmath.ecdsa_sign(curve, priv, msg)
        if i % 3 == 1:
            msg = msg + b"?"
        items.append((pub, msg, r, s))
        want.append(ecmath.ecdsa_verify(curve, pub, msg, r, s))
    for g_w in (2, 4):
        *args, precheck = wc_ops.prepare_batch_hybrid_wide(items, g_w)
        ok = np.asarray(wc_ops._verify_kernel_hybrid_wide(*args, g_w=g_w))
        assert list(ok & precheck) == want, f"g_w={g_w}"
    with pytest.raises(ValueError, match="even"):
        wc_ops.prepare_batch_hybrid_wide(items, 3)


def test_ecdsa_accepts_the_high_s_twin_and_rejects_off_curve():
    """Crypto.doVerify's rule (BouncyCastle): any s in [1, n-1], so the
    n - s twin of a valid signature is valid too."""
    curve = ecmath.SECP256K1
    priv = rand_scalar(curve.n - 1) + 1
    pub = curve.mul(priv, curve.g)
    msg = b"m"
    r, s = ecmath.ecdsa_sign(curve, priv, msg)
    assert s <= curve.n // 2                    # the signer still normalises
    items = [
        (pub, msg, r, curve.n - s),            # the high-s twin
        ((pub[0], (pub[1] + 1) % curve.p), msg, r, s),  # off-curve key
        (None, msg, r, s),                      # missing key
        (pub, msg, r, s),                       # control
    ]
    got = wc_ops.verify_batch(curve, items)
    assert list(got) == [True, False, False, True]


# ---------------------------------------------------------------------------
# One jit handle a production ladder
# ---------------------------------------------------------------------------

def _ed_rows(n):
    rows = []
    for i in range(n):
        seed = RNG.bytes(32)
        pub = ecmath.ed25519_public_key(seed)
        msg = RNG.bytes(20 + i)
        rows.append((pub, ecmath.ed25519_sign(seed, msg, pub), msg))
    return rows


def _ecdsa_rows(curve, n):
    rows = []
    for i in range(n):
        priv = rand_scalar(curve.n - 1) + 1
        msg = RNG.bytes(20 + i)
        rows.append((curve.mul(priv, curve.g), msg,
                     *ecmath.ecdsa_sign(curve, priv, msg)))
    return rows


def _ed_entries():
    rows = _ed_rows(3)
    return (ed_ops._verify_kernel_split, "ed25519.split",
            lambda: ed_ops.verify_batch(rows),
            lambda: ed_ops.finish_batch(
                ed_ops.verify_batch_async_words(*ed_ops._columns(rows))))


def _ecdsa_entries(curve, handle, record):
    rows = _ecdsa_rows(curve, 3)
    return (handle, record,
            lambda: wc_ops.verify_batch(curve, rows),
            lambda: wc_ops.finish_batch(wc_ops.verify_batch_async_words(
                curve, *wc_ops._items_to_words(rows))))


@pytest.mark.parametrize("entries", [
    pytest.param(_ed_entries, id="ed25519"),
    pytest.param(functools.partial(
        _ecdsa_entries, ecmath.SECP256K1, wc_ops._verify_kernel_hybrid_wide,
        "weierstrass.hybrid_k1"), id="secp256k1"),
    pytest.param(functools.partial(
        _ecdsa_entries, ecmath.SECP256R1, wc_ops._verify_kernel_r1_split,
        "weierstrass.r1_split"), id="secp256r1")])
def test_one_handle_a_kernel(entries, monkeypatch):
    """The synchronous ``verify_batch`` and the service entry
    ``verify_batch_async_words`` dispatch the SAME object, the module's one
    ``jax.jit`` handle of the scheme's production ladder, under the same
    record name. A spy stands where the flight recorder's ``call`` stood and
    runs nothing: no compile."""
    if not scalarprep.available():
        pytest.skip("the ECDSA word form needs libscalarmath.so")
    handle, record, synchronous, service = entries()
    seen = []

    def spy(self, name, fn, *args, capacity=None, **kwargs):
        seen.append((name, fn))
        return np.zeros(capacity, dtype=bool)

    monkeypatch.setattr(type(get_profiler()), "call", spy)
    assert not synchronous().any() and not service().any()
    assert len(seen) == 2
    for name, fn in seen:
        assert name == record
        assert fn is handle
