"""The host route's Ed25519 / ECDSA policy and its signer table.

``Crypto.is_valid`` decodes a signer's key once (``keys.SignerTable``, the
table the device preps read too) and does not decode R at all: the equation
check compares R's bytes with the encoding of the point it computes. The
corpus below proves, row for row against the untouched pure oracle
``ecmath.ed25519_verify``, that this refuses exactly what a decode of R
refused: cold table and warm, with OpenSSL and without.
"""
import functools
import hashlib
import sys
import threading

import pytest

from corda_tpu.core.crypto import (
    Crypto, PublicKey, generate_keypair, EDDSA_ED25519_SHA512,
    ECDSA_SECP256K1_SHA256, ECDSA_SECP256R1_SHA256)
from corda_tpu.core.crypto import ecmath, keys, signatures
from corda_tpu.verifier.batcher import SignatureBatcher

P, L = ecmath.ED_P, ecmath.ED_L
SEEDS = [bytes([i + 1] * 32) for i in range(8)]
ORDER8 = bytes.fromhex(
    "26e8958fc2b227b045c3f489f2ef98f0d5dfac05d3c63339b13802886d53fc05")


def _enc(y: int, sign: int = 0) -> bytes:
    return (y | (sign << 255)).to_bytes(32, "little")


def _mul_base(s: int) -> bytes:
    return ecmath.ed_point_compress(ecmath.ed_to_affine(
        ecmath.ed_scalar_mul(s, ecmath.ed_to_extended(ecmath.ED_B))))


def _challenge(r_bytes: bytes, a_bytes: bytes, msg: bytes) -> int:
    return int.from_bytes(
        hashlib.sha512(r_bytes + a_bytes + msg).digest(), "little") % L


def _msg_where(r_bytes: bytes, a_bytes: bytes, tag: bytes, want) -> bytes:
    """A message whose challenge k satisfies ``want(k)``."""
    for i in range(4096):
        msg = tag + b"-%d" % i
        if want(_challenge(r_bytes, a_bytes, msg)):
            return msg
    raise AssertionError("no message found")


def _off_curve_y() -> int:
    y = 2
    while ecmath.ed_point_decompress(_enc(y)) is not None:
        y += 1
    return y


@functools.cache
def _corpus() -> dict:
    """name -> (key bytes, signature, message, the verdict RFC 8032's strict
    cofactorless check gives). The crafted rows carry signatures that a LAX
    decoder (y taken mod p, x = 0 allowed either sign) would accept, so a
    row is refused by the decode rule it names and by nothing else."""
    rows = {}
    signers = []
    for seed in SEEDS:
        pub = ecmath.ed25519_public_key(seed)
        signers.append((seed, pub, ecmath.ed25519_secret_expand(seed)[0]))
    for i, (seed, pub, _) in enumerate(signers):
        msg = b"valid-%d" % i
        rows[f"valid_{i}"] = (pub, ecmath.ed25519_sign(seed, msg, pub),
                              msg, True)
    seed, pub, a = signers[0]
    msg = b"the quick brown fox"
    sig = ecmath.ed25519_sign(seed, msg, pub)
    r_bytes, s0 = sig[:32], int.from_bytes(sig[32:], "little")

    def with_s(s: int) -> bytes:
        return r_bytes + s.to_bytes(32, "little")

    def flip(data: bytes, at: int) -> bytes:
        return data[:at] + bytes([data[at] ^ 1]) + data[at + 1:]

    rows["flip_message"] = (pub, sig, msg + b"!", False)
    rows["flip_key_other_signer"] = (signers[1][1], sig, msg, False)
    rows["flip_key_bit"] = (flip(pub, 3), sig, msg, False)
    rows["flip_s"] = (pub, flip(sig, 40), msg, False)
    rows["flip_r"] = (pub, flip(sig, 5), msg, False)
    rows["s_eq_L"] = (pub, with_s(L), msg, False)
    rows["s_eq_L_plus_1"] = (pub, with_s(L + 1), msg, False)
    rows["s_plus_L"] = (pub, with_s(s0 + L), msg, False)     # malleated twin
    rows["s_plus_2_253"] = (pub, with_s(s0 + (1 << 253)), msg, False)
    rows["s_all_ones"] = (pub, with_s((1 << 256) - 1), msg, False)
    rows["len_63"] = (pub, sig[:63], msg, False)
    rows["len_65"] = (pub, sig + b"\x00", msg, False)
    rows["len_0"] = (pub, b"", msg, False)
    rows["key_len_31"] = (pub[:31], sig, msg, False)
    rows["r_other_point"] = (pub, _mul_base(12345) + sig[32:], msg, False)

    def by_identity_key(key: bytes, tag: bytes, want=lambda k: True):
        """[k]A is the identity (or is made so by the choice of message):
        R = [r]B, s = r satisfies the equation for a decoder that takes A."""
        rb = _mul_base(777)
        m = _msg_where(rb, key, tag, want)
        return key, rb + (777).to_bytes(32, "little"), m

    def by_given_r(rb: bytes, key: bytes, secret: int, tag: bytes,
                   want=lambda k: True):
        """R is given (r = 0 as far as B goes): s = k * a."""
        m = _msg_where(rb, key, tag, want)
        s = _challenge(rb, key, m) * secret % L
        return key, rb + s.to_bytes(32, "little"), m

    # A and R non-canonical: y = p (= 0), p + 1 (= 1, the identity), 2^255 - 1
    rows["a_y_eq_p"] = (*by_identity_key(_enc(P), b"a-y-p",
                                         lambda k: k % 4 == 0), False)
    rows["a_y_eq_p_plus_1"] = (*by_identity_key(_enc(P + 1), b"a-y-p1"),
                               False)
    rows["a_y_eq_p_plus_1_signed"] = (
        *by_identity_key(_enc(P + 1, 1), b"a-y-p1s"), False)
    rows["a_y_all_ones"] = (_enc((1 << 255) - 1), sig, msg, False)
    rows["r_y_eq_p"] = (pub, _enc(P) + sig[32:], msg, False)
    rows["r_y_eq_p_plus_1"] = (*by_given_r(_enc(P + 1), pub, a, b"r-y-p1"),
                               False)
    rows["r_y_all_ones"] = (pub, _enc((1 << 255) - 1) + sig[32:], msg, False)
    rows["r_y_all_ones_signed"] = (pub, b"\xff" * 32 + sig[32:], msg, False)
    # x = 0 with the sign bit set: y = 1 (the identity) and y = p - 1 (order 2)
    rows["a_x0_signed_y_1"] = (*by_identity_key(_enc(1, 1), b"a-x0-1"), False)
    rows["a_x0_signed_y_p_minus_1"] = (
        *by_identity_key(_enc(P - 1, 1), b"a-x0-m1", lambda k: k % 2 == 0),
        False)
    rows["r_x0_signed_y_1"] = (*by_given_r(_enc(1, 1), pub, a, b"r-x0-1"),
                               False)
    # a key with a torsion part, A' = A + T2: [k]A' = [k]A + T2 for odd k, so
    # R = T2 satisfies the equation with s = k * a
    t2 = ecmath.ed_to_extended((0, P - 1))
    a_t2 = ecmath.ed_point_compress(ecmath.ed_to_affine(ecmath.ed_point_add(
        ecmath.ed_to_extended(ecmath.ed_point_decompress(pub)), t2)))
    odd, even = (lambda k: k % 2 == 1), (lambda k: k % 2 == 0)
    rows["r_x0_signed_y_p_minus_1"] = (
        *by_given_r(_enc(P - 1, 1), a_t2, a, b"r-x0-m1", odd), False)
    rows["r_order_2_accepted"] = (
        *by_given_r(_enc(P - 1), a_t2, a, b"r-t2-odd", odd), True)
    rows["r_order_2_refused"] = (
        *by_given_r(_enc(P - 1), a_t2, a, b"r-t2-even", even), False)
    # y off the curve
    off = _enc(_off_curve_y())
    rows["a_off_curve"] = (off, sig, msg, False)
    rows["r_off_curve"] = (pub, off + sig[32:], msg, False)
    rows["r_off_curve_signed"] = (pub, _enc(_off_curve_y(), 1) + sig[32:],
                                  msg, False)
    # the identity and small-order points, canonically encoded: accepted
    # wherever the cofactorless equation holds (no small-order rule here)
    rows["a_identity"] = (*by_identity_key(_enc(1), b"a-id"), True)
    rows["r_identity"] = (*by_given_r(_enc(1), pub, a, b"r-id"), True)
    rows["a_order_4_accepted"] = (
        *by_identity_key(_enc(0), b"a-t4", lambda k: k % 4 == 0), True)
    rows["a_order_4_refused"] = (
        *by_identity_key(_enc(0), b"a-t4", lambda k: k % 4 != 0), False)
    rows["a_order_8_accepted"] = (
        *by_identity_key(ORDER8, b"a-t8", lambda k: k % 8 == 0), True)
    rows["a_order_8_refused"] = (
        *by_identity_key(ORDER8, b"a-t8", lambda k: k % 8 != 0), False)
    return rows


ROWS = [
    *(f"valid_{i}" for i in range(8)),
    "flip_message", "flip_key_other_signer", "flip_key_bit", "flip_s",
    "flip_r", "s_eq_L", "s_eq_L_plus_1", "s_plus_L", "s_plus_2_253",
    "s_all_ones", "len_63", "len_65", "len_0", "key_len_31", "r_other_point",
    "a_y_eq_p", "a_y_eq_p_plus_1", "a_y_eq_p_plus_1_signed", "a_y_all_ones",
    "r_y_eq_p", "r_y_eq_p_plus_1", "r_y_all_ones", "r_y_all_ones_signed",
    "a_x0_signed_y_1", "a_x0_signed_y_p_minus_1", "r_x0_signed_y_1",
    "r_x0_signed_y_p_minus_1", "r_order_2_accepted", "r_order_2_refused",
    "a_off_curve", "r_off_curve", "r_off_curve_signed", "a_identity",
    "r_identity", "a_order_4_accepted", "a_order_4_refused",
    "a_order_8_accepted", "a_order_8_refused",
]


def _ed_key(encoded: bytes) -> PublicKey:
    return PublicKey(EDDSA_ED25519_SHA512, encoded)


@pytest.fixture
def table(monkeypatch):
    """A fresh signer table in place of the process-wide one."""
    fresh = keys.SignerTable()
    monkeypatch.setattr(keys, "_SIGNERS", fresh)
    return fresh


@pytest.fixture
def decodes(monkeypatch):
    """Counts the square roots: every call of the two point decoders."""
    calls = []
    for module, name in ((ecmath, "ed_point_decompress"),
                         (keys, "sec1_decompress")):
        real = getattr(module, name)

        def counting(*args, _real=real, _name=name):
            calls.append(_name)
            return _real(*args)

        monkeypatch.setattr(module, name, counting)
    return calls


def test_the_corpus_is_the_one_listed_and_the_oracle_reads_it_as_meant():
    corpus = _corpus()
    assert sorted(corpus) == sorted(ROWS)
    for name, (key, sig, msg, meant) in corpus.items():
        assert ecmath.ed25519_verify(key, msg, sig) is meant, name


@pytest.mark.parametrize("cache", ["cold", "warm"])
@pytest.mark.parametrize("equation", ["openssl", "pure"])
@pytest.mark.parametrize("name", ROWS)
def test_is_valid_agrees_with_the_pure_oracle(name, equation, cache, table,
                                              monkeypatch):
    key, sig, msg, _ = _corpus()[name]
    if equation == "pure":
        monkeypatch.setattr(signatures, "_openssl_ed25519_verify",
                            lambda *a: None)
    else:
        assert signatures._openssl_ed_key(_corpus()["valid_0"][0]) is not None
    if cache == "warm":
        keys.signer_point("ed25519", key)
        assert ("ed25519", key) in table
    else:
        assert len(table) == 0
    assert Crypto.is_valid(_ed_key(key), sig, msg) \
        is ecmath.ed25519_verify(key, msg, sig)


def test_no_square_root_for_a_signature_of_a_known_signer(table, decodes):
    """What the change is for: the key is decoded once, R never (OpenSSL
    compares encodings), whatever the row's verdict."""
    corpus = _corpus()
    for name in ("valid_0", "flip_message", "flip_r", "r_other_point",
                 "r_off_curve", "r_x0_signed_y_1", "s_eq_L"):
        key, sig, msg, meant = corpus[name]
        assert Crypto.is_valid(_ed_key(key), sig, msg) is meant
    assert decodes == ["ed_point_decompress"]      # valid_0's key: one signer


def test_an_undecodable_key_is_refused_again_from_the_table(table, decodes):
    key, sig, msg, _ = _corpus()["a_off_curve"]
    assert Crypto.is_valid(_ed_key(key), sig, msg) is False
    assert keys.signer_decoded(_ed_key(key)) is True
    assert Crypto.is_valid(_ed_key(key), sig, msg) is False
    assert decodes == ["ed_point_decompress"]
    assert len(table) == 1


def test_the_table_is_bounded_and_eviction_changes_no_verdict(monkeypatch,
                                                              decodes):
    small = keys.SignerTable(maxsize=4)
    monkeypatch.setattr(keys, "_SIGNERS", small)
    corpus = _corpus()
    names = [f"valid_{i}" for i in range(8)] + ["a_off_curve", "a_y_eq_p"]
    for _ in range(3):
        for name in names:
            key, sig, msg, meant = corpus[name]
            assert Crypto.is_valid(_ed_key(key), sig, msg) is meant, name
            assert len(small) <= 4
    # ten keys through four places in turn: every look was a miss
    assert len(decodes) == 3 * len(names)
    # and the most recent four are the ones kept
    assert [k for _, k in small._points] == [
        corpus[n][0] for n in names[-4:]]


def test_eight_threads_on_one_key_agree(table):
    corpus = _corpus()
    key = corpus["valid_0"][0]
    rows = [corpus[n] for n in ("valid_0", "flip_message", "flip_s", "s_eq_L")]
    assert all(r[0] == key for r in rows)
    start = threading.Barrier(8)
    verdicts, errors = [], []

    def hammer():
        try:
            start.wait(timeout=30)
            verdicts.append([Crypto.is_valid(_ed_key(k), s, m)
                             for _ in range(25) for k, s, m, _ in rows])
        except Exception as e:        # surfaced below, on the test's thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    assert verdicts == [[r[3] for r in rows] * 25] * 8
    assert len(table) == 1


@pytest.mark.parametrize("first", ["host_route", "device_prep"])
def test_the_device_prep_and_the_host_route_fill_one_table(first, table,
                                                           decodes):
    from corda_tpu.ops import ed25519 as ed_ops
    key, sig, msg, _ = _corpus()["valid_3"]
    looks = [lambda: Crypto.is_valid(_ed_key(key), sig, msg),
             lambda: ed_ops._decompress_a(key)]
    if first == "device_prep":
        looks.reverse()
    assert keys.signer_decoded(_ed_key(key)) is False
    assert looks[0]() is not None
    assert keys.signer_decoded(_ed_key(key)) is True       # a hit for the other
    assert looks[1]()
    assert decodes == ["ed_point_decompress"] and len(table) == 1
    assert ed_ops._decompress_a(key) == ecmath.ed_point_decompress(key)


@pytest.mark.parametrize("first", ["host_route", "device_prep"])
@pytest.mark.parametrize("scheme", [ECDSA_SECP256K1_SHA256,
                                    ECDSA_SECP256R1_SHA256],
                         ids=["secp256k1", "secp256r1"])
def test_a_compressed_ecdsa_key_is_decoded_once_for_both_routes(
        scheme, first, table, decodes):
    kp = generate_keypair(scheme, entropy=bytes([9] * 32))
    assert len(kp.public.encoded) == 33
    curve = keys.curve_for_scheme(scheme)
    msg = b"one table"
    sig = Crypto.do_sign(kp.private, msg)
    looks = [lambda: Crypto.is_valid(kp.public, sig, msg),
             lambda: keys.sec1_decompress_cached(curve, kp.public.encoded)]
    if first == "device_prep":
        looks.reverse()
    assert keys.signer_decoded(kp.public) is False
    assert looks[0]()
    assert keys.signer_decoded(kp.public) is True
    assert looks[1]()
    assert Crypto.is_valid(kp.public, sig, msg + b"!") is False
    assert decodes == ["sec1_decompress"] and len(table) == 1
    # a key that is no point is refused from the table too
    bad = PublicKey(scheme, b"\x02" + b"\xff" * 32)
    assert Crypto.is_valid(bad, sig, msg) is False
    assert Crypto.is_valid(bad, sig, msg) is False
    assert decodes == ["sec1_decompress"] * 2 and len(table) == 2


def _count(batcher, name):
    return batcher.metrics.snapshot().get(name, {}).get("count", 0)


@pytest.mark.parametrize("route", ["queued_flush", "inline_collect"])
def test_the_batcher_counts_signer_lookups_and_hits_once_a_flush(route, table):
    """SigBatcher.SignerDecodeLookup: the rows a host flush verified whose
    scheme decodes a key; SignerDecodeHit: those whose signer the table held
    when the flush began. Counted in ``_flush_host``, which both routes run."""
    corpus = _corpus()
    checks = [(_ed_key(k), s, m) for k, s, m, _ in
              (corpus[n] for n in ("valid_0", "valid_1", "flip_message",
                                   "a_off_curve"))]
    kp = generate_keypair(ECDSA_SECP256K1_SHA256, entropy=bytes([5] * 32))
    checks.append((kp.public, Crypto.do_sign(kp.private, b"k1"), b"k1"))
    b = SignatureBatcher()
    try:
        def verify():
            if route == "inline_collect":
                return b.collect_group(b.hold_group(checks))
            return b.submit_group(checks, latency_class="interactive") \
                .result(timeout=30)

        assert verify() == [True, True, False, False, True]
        assert _count(b, "SigBatcher.SignerDecodeLookup") == 5
        assert _count(b, "SigBatcher.SignerDecodeHit") == 0
        assert verify() == [True, True, False, False, True]
        assert _count(b, "SigBatcher.SignerDecodeLookup") == 10
        assert _count(b, "SigBatcher.SignerDecodeHit") == 5
        assert _count(b, "SigBatcher.HostInline") == (
            10 if route == "inline_collect" else 0)
        assert _count(b, "SigBatcher.DeviceChecked") == 0
    finally:
        b.close()
