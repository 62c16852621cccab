"""The ECDSA acceptance rule, one for secp256k1 and secp256r1, on every route.

``Crypto.doVerify`` for the two ECDSA schemes is BouncyCastle's
``SHA256withECDSA``: it takes any ``r`` and ``s`` in ``[1, n-1]``, and its
signer does not normalise ``s``. The program takes the same set, with strict
DER as the one departure (BouncyCastle 1.57 also took BER), which is exactly
what the ``cryptography`` package (OpenSSL) accepts. The corpus below holds
every route to that oracle row for row: ``Crypto.is_valid`` with OpenSSL under
the equation and without (``ecmath.ecdsa_verify``), and the device path as the
batcher drives it (``SignatureBatcher._start_ecdsa``), by the pure-Python item
prep and by the native word prep. Each curve's kernel is compiled once, at
the bucket other tier-1 files already use (secp256k1 8 rows, secp256r1 16);
nothing waits on a wall clock.
"""
import functools

import numpy as np
import pytest
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import \
    decode_dss_signature

from corda_tpu.core.crypto import (
    Crypto, PublicKey, ECDSA_SECP256K1_SHA256, ECDSA_SECP256R1_SHA256)
from corda_tpu.core.crypto import ecmath, keys, signatures
from corda_tpu.ops import scalarprep as sp
from corda_tpu.utils.metrics import MetricRegistry
from corda_tpu.verifier.batcher import SignatureBatcher, _Pending

#: curve -> (scheme, the program's curve, the oracle's curve, device bucket)
CURVES = {
    "secp256k1": (ECDSA_SECP256K1_SHA256, ecmath.SECP256K1, ec.SECP256K1(), 8),
    "secp256r1": (ECDSA_SECP256R1_SHA256, ecmath.SECP256R1, ec.SECP256R1(),
                  16),
}
SHA256 = ec.ECDSA(hashes.SHA256())
needs_native = pytest.mark.skipif(not sp.available(),
                                  reason="libscalarmath.so not built")


def oracle(curve_name: str, key: bytes, sig: bytes, msg: bytes) -> bool:
    """The ``cryptography`` package's verify, called directly."""
    try:
        pub = ec.EllipticCurvePublicKey.from_encoded_point(
            CURVES[curve_name][2], key)
    except ValueError:
        return False
    try:
        pub.verify(sig, msg, SHA256)
        return True
    except InvalidSignature:
        return False


def _int_body(v: int, pad: int = 0) -> bytes:
    raw = v.to_bytes((v.bit_length() + 7) // 8 or 1, "big")
    if raw[0] & 0x80:
        raw = b"\x00" + raw
    return b"\x00" * pad + raw


def _der(r_body: bytes, s_body: bytes, tag=0x30, int_tag=0x02) -> bytes:
    body = (bytes([int_tag, len(r_body)]) + r_body
            + bytes([0x02, len(s_body)]) + s_body)
    return bytes([tag, len(body)]) + body


def _sig(r: int, s: int) -> bytes:
    return _der(_int_body(r), _int_body(s))


def _sec1(point, compressed=True) -> bytes:
    x, y = point
    if compressed:
        return bytes([2 + (y & 1)]) + x.to_bytes(32, "big")
    return b"\x04" + x.to_bytes(32, "big") + y.to_bytes(32, "big")


@functools.cache
def _corpus(curve_name: str) -> dict:
    """name -> (key bytes, DER signature, message, verdict meant, why a
    refused row is refused: ``equation``, ``range``, ``encoding``, ``key``)."""
    _scheme, curve, oracle_curve, _bucket = CURVES[curve_name]
    n, p = curve.n, curve.p
    rows = {}
    signers = []
    for i in range(4):
        priv = int.from_bytes(bytes([i + 1] * 32), "big") % (n - 1) + 1
        signers.append((priv, curve.mul(priv, curve.g)))
    for i, (priv, pub) in enumerate(signers):
        msg = b"valid-%d" % i
        r, s = ecmath.ecdsa_sign(curve, priv, msg)
        assert s <= n // 2
        rows[f"valid_low_s_{i}"] = (_sec1(pub), _sig(r, s), msg, True, None)
        rows[f"valid_high_s_twin_{i}"] = (_sec1(pub), _sig(r, n - s), msg,
                                          True, None)
    # the reference's own kind of signer: OpenSSL's, s as it comes
    theirs = ec.derive_private_key(signers[0][0], oracle_curve)
    alg = ec.ECDSA(hashes.SHA256(), deterministic_signing=True)
    for want_high in (True, False):
        for i in range(64):
            msg = b"theirs-%d" % i
            der = theirs.sign(msg, alg)
            if (decode_dss_signature(der)[1] > n // 2) == want_high:
                break
        name = "openssl_signed_high_s" if want_high else "openssl_signed_low_s"
        rows[name] = (_sec1(signers[0][1]), der, msg, True, None)

    priv, pub = signers[0]
    key = _sec1(pub)
    msg = b"the quick brown fox"
    r, s = ecmath.ecdsa_sign(curve, priv, msg)
    sig = _sig(r, s)
    rows["key_uncompressed"] = (_sec1(pub, compressed=False), sig, msg, True,
                                None)
    # the equation
    rows["altered_message"] = (key, sig, msg + b"!", False, "equation")
    rows["another_signers_key"] = (_sec1(signers[1][1]), sig, msg, False,
                                   "equation")
    rows["flipped_last_byte"] = (key, sig[:-1] + bytes([sig[-1] ^ 1]), msg,
                                 False, "equation")
    rows["r_plus_1"] = (key, _sig(r + 1, s), msg, False, "equation")
    rows["s_is_n_minus_1"] = (key, _sig(r, n - 1), msg, False, "equation")
    # the ranges: 0, n, n + 1, 2^256 - 1 for r and for s, and the twins mod n
    for which in ("r", "s"):
        for label, v in (("0", 0), ("n", n), ("n_plus_1", n + 1),
                         ("all_ones", (1 << 256) - 1)):
            pair = (v, s) if which == "r" else (r, v)
            rows[f"{which}_eq_{label}"] = (key, _sig(*pair), msg, False,
                                           "range")
    rows["s_plus_n"] = (key, _sig(r, s + n), msg, False, "range")
    rows["r_plus_n"] = (key, _sig(r + n, s), msg, False, "range")
    # the encoding: the same (r, s) or a neighbour, not strict DER
    rb, sb = _int_body(r), _int_body(s)
    rows["der_padded_r"] = (key, _der(_int_body(r, pad=1), sb), msg, False,
                            "encoding")
    rows["der_padded_s"] = (key, _der(rb, _int_body(s, pad=1)), msg, False,
                            "encoding")
    high = next(v for v in (r, n - s, s) if v >> 255)        # top bit set
    rows["der_negative"] = (
        key, _der(high.to_bytes(32, "big"), sb) if high == r
        else _der(rb, high.to_bytes(32, "big")), msg, False, "encoding")
    rows["der_trailing_byte"] = (key, sig + b"\x00", msg, False, "encoding")
    rows["der_trailing_byte_counted"] = (
        key, bytes([0x30, sig[1] + 1]) + sig[2:] + b"\x00", msg, False,
        "encoding")
    rows["der_long_form_length"] = (key, b"\x30\x81" + sig[1:], msg, False,
                                    "encoding")
    rows["der_wrong_outer_tag"] = (key, _der(rb, sb, tag=0x31), msg, False,
                                   "encoding")
    rows["der_wrong_integer_tag"] = (key, _der(rb, sb, int_tag=0x03), msg,
                                     False, "encoding")
    rows["der_truncated"] = (key, sig[:-1], msg, False, "encoding")
    rows["der_empty_integer"] = (key, _der(b"", sb), msg, False, "encoding")
    rows["der_empty"] = (key, b"", msg, False, "encoding")
    rows["der_raw_r_s"] = (key, r.to_bytes(32, "big") + s.to_bytes(32, "big"),
                           msg, False, "encoding")
    # the key
    x, y = pub
    off = b"\x04" + x.to_bytes(32, "big") + ((y + 1) % p).to_bytes(32, "big")
    no_root = next(v for v in range(2, 64) if keys.sec1_decompress(
        curve, b"\x02" + v.to_bytes(32, "big")) is None)
    rows["key_off_curve"] = (off, sig, msg, False, "key")
    rows["key_x_without_root"] = (b"\x02" + no_root.to_bytes(32, "big"), sig,
                                  msg, False, "key")
    rows["key_x_eq_p"] = (b"\x02" + p.to_bytes(32, "big"), sig, msg, False,
                          "key")
    rows["key_wrong_prefix"] = (b"\x05" + key[1:], sig, msg, False, "key")
    rows["key_hybrid_prefix"] = (bytes([6 + (y & 1)]) + off[1:33]
                                 + y.to_bytes(32, "big"), sig, msg, False,
                                 "key")
    rows["key_short"] = (key[:32], sig, msg, False, "key")
    rows["key_infinity"] = (b"\x00", sig, msg, False, "key")
    rows["key_wrong_parity"] = (bytes([key[0] ^ 1]) + key[1:], sig, msg,
                                False, "equation")
    return rows


ROWS = [
    *(f"valid_low_s_{i}" for i in range(4)),
    *(f"valid_high_s_twin_{i}" for i in range(4)),
    "openssl_signed_high_s", "openssl_signed_low_s", "key_uncompressed",
    "altered_message", "another_signers_key", "flipped_last_byte", "r_plus_1",
    "s_is_n_minus_1", "key_wrong_parity",
    "r_eq_0", "r_eq_n", "r_eq_n_plus_1", "r_eq_all_ones",
    "s_eq_0", "s_eq_n", "s_eq_n_plus_1", "s_eq_all_ones", "s_plus_n",
    "r_plus_n",
    "der_padded_r", "der_padded_s", "der_negative", "der_trailing_byte",
    "der_trailing_byte_counted", "der_long_form_length",
    "der_wrong_outer_tag", "der_wrong_integer_tag", "der_truncated",
    "der_empty_integer", "der_empty", "der_raw_r_s",
    "key_off_curve", "key_x_without_root", "key_x_eq_p", "key_wrong_prefix",
    "key_hybrid_prefix", "key_short", "key_infinity",
]
both_curves = pytest.mark.parametrize("curve_name", list(CURVES))


@both_curves
def test_the_corpus_is_the_one_listed_and_the_oracle_reads_it_as_meant(
        curve_name):
    corpus = _corpus(curve_name)
    assert sorted(corpus) == sorted(ROWS)
    for name, (key, sig, msg, meant, _why) in corpus.items():
        assert oracle(curve_name, key, sig, msg) is meant, name
    accepted = [name for name, row in corpus.items() if row[3]]
    assert len(accepted) == 11
    n = CURVES[curve_name][1].n
    high = [name for name in accepted
            if decode_dss_signature(corpus[name][1])[1] > n // 2]
    assert sorted(high) == ["openssl_signed_high_s",
                            *(f"valid_high_s_twin_{i}" for i in range(4))]


@both_curves
@pytest.mark.parametrize("equation", ["openssl", "pure"])
@pytest.mark.parametrize("name", ROWS)
def test_is_valid_agrees_with_cryptography(name, equation, curve_name,
                                           monkeypatch):
    key, sig, msg, _meant, _why = _corpus(curve_name)[name]
    if equation == "pure":
        monkeypatch.setattr(signatures, "_openssl_ecdsa_verify",
                            lambda *a: None)
    public = PublicKey(CURVES[curve_name][0], key)
    assert Crypto.is_valid(public, sig, msg) \
        is oracle(curve_name, key, sig, msg)


@both_curves
def test_do_verify_throws_where_is_valid_refuses(curve_name):
    from corda_tpu.core.crypto.signatures import SignatureException
    corpus = _corpus(curve_name)
    scheme = CURVES[curve_name][0]
    key, sig, msg, _m, _w = corpus["valid_high_s_twin_0"]
    assert Crypto.do_verify(PublicKey(scheme, key), sig, msg) is True
    key, sig, msg, _m, _w = corpus["s_eq_n"]
    with pytest.raises(SignatureException):
        Crypto.do_verify(PublicKey(scheme, key), sig, msg)


@both_curves
@pytest.mark.parametrize("name", [
    n for n in ROWS if not n.startswith(("der_", "key_"))] + [
    "key_uncompressed", "key_wrong_parity"])
def test_the_pure_oracle_agrees_with_cryptography(name, curve_name):
    """``ecmath.ecdsa_verify`` on its own, for every row that reaches it: a
    key that decodes and a signature that parses."""
    curve = CURVES[curve_name][1]
    key, sig, msg, _meant, _why = _corpus(curve_name)[name]
    point = keys.sec1_decompress(curve, key)
    r, s = ecmath.ecdsa_sig_from_der(sig)
    assert ecmath.ecdsa_verify(curve, point, msg, r, s) \
        is oracle(curve_name, key, sig, msg)


@both_curves
def test_the_strict_parse_refuses_what_openssl_refuses(curve_name):
    """The two DER parsers, the item prep's and the word prep's, refuse the
    same encodings, and each of them is one OpenSSL refuses."""
    corpus = _corpus(curve_name)
    names = [n for n in ROWS if n.startswith("der_")]
    sigs = [corpus[n][1] for n in names]
    _r, _s, ok = sp.ecdsa_sigs_to_words(sigs)
    assert not ok.any()
    for name, sig in zip(names, sigs):
        with pytest.raises((ValueError, IndexError)):
            ecmath.ecdsa_sig_from_der(sig)
        key, _sig, msg, _m, _w = corpus[name]
        assert oracle(curve_name, key, sig, msg) is False, name


@both_curves
def test_the_plain_reference_agrees_with_cryptography(curve_name):
    """``verify_batch_plain``, the 256-bit Shamir ladder every differential
    test of a production ladder trusts, held to the corpus itself: every row
    that reaches it (a key that decodes, a signature that parses), verdict
    for verdict. In chunks of 8 rows, the bucket
    ``tests/test_ops_curves.py::test_ecdsa_verify_batch[*-plain]`` compiles."""
    from corda_tpu.ops import weierstrass as wc_ops
    curve = CURVES[curve_name][1]
    names, items = [], []
    for name in ROWS:
        key, sig, msg, _meant, _why = _corpus(curve_name)[name]
        point = keys.sec1_decompress(curve, key)
        try:
            r, s = ecmath.ecdsa_sig_from_der(sig)
        except (ValueError, IndexError):
            continue
        if point is not None:
            names.append(name)
            items.append((point, msg, r, s))
    assert {"valid_high_s_twin_0", "r_eq_0", "s_eq_n", "altered_message",
            "another_signers_key", "r_plus_n"} <= set(names)
    verdicts = []
    for at in range(0, len(items), 8):
        verdicts.extend(wc_ops.verify_batch_plain(curve, items[at:at + 8]))
    for name, verdict in zip(names, verdicts, strict=True):
        key, sig, msg, _meant, _why = _corpus(curve_name)[name]
        assert bool(verdict) is oracle(curve_name, key, sig, msg), name


# -- the device path, as the batcher drives it ---------------------------------------

def _device_verdicts(curve_name: str, prep: str):
    """Every row of the corpus through ``SignatureBatcher._start_ecdsa`` in
    dispatches of exactly the curve's bucket (filled up with valid rows), and
    the batcher's meters afterwards."""
    scheme, _curve, _oc, bucket = CURVES[curve_name]
    corpus = _corpus(curve_name)
    filler = corpus["valid_low_s_1"]
    rows = [corpus[name] for name in ROWS]
    rows += [filler] * (-len(rows) % bucket)
    registry = MetricRegistry()
    batcher = SignatureBatcher(metrics=registry, host_crossover=0)
    verdicts = []
    with pytest.MonkeyPatch.context() as mp:
        if prep == "items":
            mp.setattr(sp, "_LIB", None)
        try:
            for at in range(0, len(rows), bucket):
                items = [_Pending(PublicKey(scheme, key), sig, msg)
                         for key, sig, msg, _m, _w in rows[at:at + bucket]]
                pending, finish = batcher._start_ecdsa(curve_name, items)
                verdicts.extend(bool(v) for v in finish(pending))
        finally:
            batcher.close()
    meters = {name: registry.meter(f"SigBatcher.{name}").count
              for name in ("EcdsaWordsPrep", "EcdsaItemsPrep",
                           "EcdsaRefusedEncoding", "EcdsaRefusedRange")}
    return dict(zip(ROWS, verdicts)), meters, len(rows)


@functools.cache
def _device(curve_name: str, prep: str):
    return _device_verdicts(curve_name, prep)


PREPS = [pytest.param("words", marks=needs_native), "items"]


@both_curves
@pytest.mark.parametrize("prep", PREPS)
@pytest.mark.parametrize("name", ROWS)
def test_the_device_path_agrees_with_cryptography(name, prep, curve_name):
    verdicts, _meters, _n = _device(curve_name, prep)
    key, sig, msg, _meant, _why = _corpus(curve_name)[name]
    assert verdicts[name] is oracle(curve_name, key, sig, msg)


@both_curves
@pytest.mark.parametrize("prep", PREPS)
def test_the_meters_say_which_prep_ran_and_what_it_refused(prep, curve_name):
    _verdicts, meters, n_rows = _device(curve_name, prep)
    took, other = (("EcdsaWordsPrep", "EcdsaItemsPrep") if prep == "words"
                   else ("EcdsaItemsPrep", "EcdsaWordsPrep"))
    assert meters[took] == n_rows and meters[other] == 0
    why = [row[4] for row in _corpus(curve_name).values()]
    # refused before the kernel: every encoding, key and range row, and no
    # other; a range row whose integer needs 33 bytes is an encoding to the
    # word prep (it has no words for it) and a range to the item prep
    refused = meters["EcdsaRefusedEncoding"] + meters["EcdsaRefusedRange"]
    structural = sum(w in ("encoding", "key", "range") for w in why)
    if curve_name == "secp256r1":
        # the split hands a row whose r is no x-coordinate to the host
        # oracle: refused there, before the kernel, and counted
        assert structural <= refused <= structural + why.count("equation")
    else:
        assert refused == structural
    assert meters["EcdsaRefusedEncoding"] >= sum(
        w in ("encoding", "key") for w in why)
    if prep == "items" and curve_name == "secp256k1":
        assert meters["EcdsaRefusedRange"] == why.count("range")


def _ecdsa_words_a_row(curve, items):
    """``SignatureBatcher._ecdsa_words`` as it was before it took a batch in
    bulk: the Python DER loop, and one cached key row assigned a ROW."""
    import hashlib
    r_words, s_words, ok = sp.ecdsa_sigs_to_words_py(
        [p.signature for p in items])
    pub_words = np.zeros((len(items), 8), dtype=np.uint64)
    for i, p in enumerate(items):
        row = keys.sec1_pub_row_cached(curve, p.key.encoded)
        if row is None:
            ok[i] = False
        else:
            pub_words[i] = row
    r_words[~ok] = 0
    e_words = sp.digests_to_words(
        [hashlib.sha256(p.content).digest() for p in items], 4)
    return (e_words, r_words, s_words, pub_words), int((~ok).sum())


@both_curves
@pytest.mark.parametrize("library", [
    pytest.param("native", marks=needs_native), "absent"])
def test_the_bulk_word_prep_hands_over_what_the_per_row_form_did(
        curve_name, library, monkeypatch):
    """Several signers in no order, a key that does not decode (twice), a key
    of the wrong length, a refused DER under a good key and under a bad one:
    the same four word arrays, rows in the same order, the same refused
    count. An empty batch is a batch."""
    if library == "absent":
        monkeypatch.setattr(sp, "_LIB", None)
    scheme, curve, _oc, _bucket = CURVES[curve_name]
    corpus = _corpus(curve_name)
    good = [corpus[f"valid_low_s_{i}"] for i in range(4)] \
        + [corpus[f"valid_high_s_twin_{i}"] for i in range(4)]
    key, sig, msg = good[0][:3]
    no_point = b"\x02" + b"\xff" * 32
    padded = corpus["der_padded_r"][1]
    triples = [row[:3] for row in good[4:] + good[:4]] + [
        (no_point, sig, msg), (key[:32], sig, msg), (key, padded, msg),
        good[2][:3], (no_point, padded, msg), (no_point, sig, b"another"),
        corpus["key_uncompressed"][:3], good[7][:3]]
    items = [_Pending(PublicKey(scheme, k), sg, m) for k, sg, m in triples]
    for batch in (items, items[:1], items[8:9], []):
        got, got_refused = SignatureBatcher._ecdsa_words(curve, batch)
        want, want_refused = _ecdsa_words_a_row(curve, batch)
        assert got_refused == want_refused
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)
    (_e, r_words, _s, pub_words), refused = SignatureBatcher._ecdsa_words(
        curve, items)
    assert refused == 5
    assert [i for i in range(len(items)) if not r_words[i].any()] \
        == [8, 9, 10, 12, 13]
    assert [i for i in range(len(items)) if not pub_words[i].any()] \
        == [8, 9, 12, 13]
    np.testing.assert_array_equal(pub_words[14], pub_words[4])  # 04 | x | y


@needs_native
def test_an_ecdsa_batch_names_its_prep_parts_and_launch_under_the_dispatch():
    """With tracing on, one ECDSA batch through the batcher's own front door
    leaves ``ecdsa.prep.der``, ``.keys``, ``.digest``, ``.pad`` and
    ``.scalars`` as children of its ``batcher.dispatch`` span, tagged with
    the bucket and the rows, then ``batcher.launch`` (the jitted call alone);
    each says how long its thread ran (``cpu_s``), and the dispatch too."""
    from corda_tpu.observability import disable_tracing, enable_tracing
    corpus = _corpus("secp256k1")
    names = [f"valid_low_s_{i}" for i in range(4)] \
        + [f"valid_high_s_twin_{i}" for i in range(4)]
    checks = [(PublicKey(ECDSA_SECP256K1_SHA256, corpus[n][0]),
               corpus[n][1], corpus[n][2]) for n in names]
    tracer = enable_tracing(4096)
    batcher = SignatureBatcher(metrics=MetricRegistry(), host_crossover=0,
                               max_batch=8)
    try:
        assert batcher.submit_group(checks).result(timeout=600) == [True] * 8
    finally:
        batcher.close()
        disable_tracing()
    spans = tracer.spans()
    (dispatch,) = [s for s in spans if s["name"] == "batcher.dispatch"]
    assert dispatch["tags"]["bucket"] == "secp256k1"
    parts = [s for s in spans if s["name"].startswith("ecdsa.prep.")]
    assert [s["name"] for s in parts] == [
        "ecdsa.prep.der", "ecdsa.prep.keys", "ecdsa.prep.digest",
        "ecdsa.prep.pad", "ecdsa.prep.scalars"]
    (launch,) = [s for s in spans if s["name"] == "batcher.launch"]
    assert launch["tags"] == {"bucket": "secp256k1", "rows": 8,
                              "capacity": 8,
                              "compiled": launch["tags"]["compiled"]}
    assert launch["tags"]["compiled"] in (True, False)
    assert dispatch["cpu_s"] is not None
    for part in parts + [launch]:
        assert part["parent_id"] == dispatch["span_id"]
        assert part["trace_id"] == dispatch["trace_id"]
        assert part["start_s"] >= dispatch["start_s"]
        assert part["duration_s"] <= dispatch["duration_s"]
        assert part["cpu_s"] is not None
    for part in parts:
        assert part["tags"] == {"bucket": "secp256k1", "rows": 8}
    assert launch["start_s"] >= parts[-1]["start_s"] + parts[-1]["duration_s"] \
        - 1e-4


@needs_native
@both_curves
def test_the_two_preps_hand_the_kernel_the_same_precheck(curve_name):
    """One acceptance set for the python and the native prep: the range
    rows, as ``(pub, msg, r, s)`` items, through both."""
    from corda_tpu.ops import weierstrass as wc
    curve = CURVES[curve_name][1]
    corpus = _corpus(curve_name)
    items = []
    for name in ROWS:
        key, sig, msg, _m, why = corpus[name]
        if why in ("range", "equation", None) and name != "key_uncompressed":
            items.append((keys.sec1_decompress(curve, key), msg,
                          *ecmath.ecdsa_sig_from_der(sig)))
    if curve_name == "secp256k1":
        native = wc._prepare_hybrid_native(items, 8)[-1]
        python = wc._prepare_hybrid_python(items, 8)[-1]
    else:
        native = wc._prepare_r1_split_native_words(
            *wc._items_to_words(items), 16)
        python = wc._prepare_r1_split_python(curve, items, 16)
        native, python = native[-2] | native[-1], python[-2] | python[-1]
    want = [1 <= r < curve.n and 1 <= s < curve.n for _p, _m, r, s in items]
    np.testing.assert_array_equal(np.asarray(native), np.asarray(python))
    if curve_name == "secp256k1":
        assert list(np.asarray(native)) == want


def test_no_verify_route_compares_s_with_half_the_order():
    """The signer's normalisation and the GLV rounding keep their n / 2;
    no verifier does."""
    import inspect
    import pathlib
    from corda_tpu.ops import weierstrass as wc
    for fn in (ecmath.ecdsa_verify, Crypto.is_valid,
               wc._precheck_and_scalars, wc._r1_host_verify_scalars):
        src = inspect.getsource(fn)
        assert "// 2" not in src and ">> 1" not in src, fn.__name__
    assert "n // 2" in inspect.getsource(ecmath.ecdsa_sign)
    native = (pathlib.Path(__file__).resolve().parents[1] / "native"
              / "scalarmath.cpp").read_text()
    assert "mp_cmp(s4, N->half" not in native
    # one range check a native ECDSA prep: sm_k1_prep, sm_r1_prep_hg
    assert native.count("mp_cmp(s4, N->m, 4) < 0") == 2
    assert "N->half" in native                      # the GLV split's bias
    assert sp.SM_VERSION >= 5   # 4->5: the strict-DER parse is an export
