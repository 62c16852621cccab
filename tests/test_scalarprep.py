"""Differential tests: native (C) scalar prep vs the Python bigint path.

The native layer (native/scalarmath.cpp via ops/scalarprep.py) must be
BIT-IDENTICAL to the Python prep it replaces — these tests lock that for
the low-level arithmetic seams (Barrett mulmod/mod512, GLV split) and the
full batch preps (secp256k1 hybrid, the Ed25519 word form), over valid,
tampered, and structurally-malformed inputs.  Mirrors the reference's
approach of differential-testing Crypto.doVerify against test vectors
(core/src/test/kotlin/net/corda/core/crypto/CryptoUtilsTest.kt).
"""
import os
import random

import numpy as np
import pytest

from corda_tpu.core.crypto import ecmath
from corda_tpu.ops import scalarprep as sp
from corda_tpu.ops import weierstrass as wc

pytestmark = pytest.mark.skipif(not sp.available(),
                                reason="libscalarmath.so not built")


def test_mulmod_matches_python():
    rng = random.Random(11)
    mods = [ecmath.SECP256K1.n, ecmath.SECP256K1.p, ecmath.SECP256R1.n,
            ecmath.SECP256R1.p, ecmath.ED_L, ecmath.ED_P]
    for mid, m in enumerate(mods):
        for _ in range(50):
            a, b = rng.getrandbits(256) % m, rng.getrandbits(256) % m
            assert sp.mulmod(mid, a, b) == a * b % m
        for _ in range(50):
            x = rng.getrandbits(512)
            assert sp.mod512(mid, x) == x % m
        # boundary values
        for a in (0, 1, m - 1):
            assert sp.mulmod(mid, a, m - 1) == a * (m - 1) % m
        assert sp.mod512(mid, (1 << 512) - 1) == ((1 << 512) - 1) % m


def test_glv_matches_python():
    rng = random.Random(12)
    n = ecmath.SECP256K1.n
    cases = [0, 1, n - 1, n // 2, n // 2 + 1]
    cases += [rng.getrandbits(256) % n for _ in range(300)]
    for k in cases:
        assert sp.glv(k) == ecmath.glv_decompose(k), k


def _k1_items(n_valid: int):
    rng = np.random.default_rng(42)
    curve = ecmath.SECP256K1
    items = []
    for _ in range(n_valid):
        priv = int.from_bytes(rng.bytes(32), "little") % (curve.n - 1) + 1
        pub = curve.mul(priv, curve.g)
        msg = rng.bytes(48)
        r, s = ecmath.ecdsa_sign(curve, priv, msg)
        items.append((pub, msg, r, s))
    # malformed rows: None point, r = 0, s = 0, s = n - 1 (in range since
    # sm_version 4: both preps hand it to the kernel, which refuses it), s = n,
    # r >= n, off-curve, oversized r (DER can carry > 2^256 ints)
    pub0 = items[0][0]
    items += [
        (None, b"x", 5, 7),
        (pub0, b"m", 0, 7),
        (pub0, b"m", 5, 0),
        (pub0, b"m", 5, curve.n - 1),           # the largest s in range
        (pub0, b"m", 5, curve.n),               # the smallest out of range
        (pub0, b"m", curve.n, 7),
        ((pub0[0], (pub0[1] + 1) % curve.p), b"m", 5, 7),
        (pub0, b"m", 1 << 300, 7),
    ]
    return items


def test_k1_prep_native_matches_python():
    items = _k1_items(24)
    native = wc._prepare_hybrid_native(items, 8)
    python = wc._prepare_hybrid_python(items, 8)
    assert len(native) == len(python)
    names = ["g_idx", "q_bits", "Qc", "Qd", "r_limbs", "rn_ok",
             "tab_x", "tab_y", "tab_ok", "precheck"]
    for name, a, b in zip(names, native, python):
        if isinstance(a, tuple):
            for i, (ac, bc) in enumerate(zip(a, b)):
                np.testing.assert_array_equal(
                    np.asarray(ac), np.asarray(bc), err_msg=f"{name}[{i}]")
        else:
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=name)


@pytest.mark.parametrize(
    "msg_len", [0, 1, 47, 48, 111, 112, 127, 128, 239, 240, 1000])
def test_native_sha512_matches_hashlib(msg_len):
    """The library's own SHA-512 (sm_ed_prep_words hashes R || A || M with
    it) against hashlib, at message lengths that cross the padding's edges:
    64 bytes of R || A, then a message that ends 1 or 17 bytes short of a
    block, on it, and past it."""
    import hashlib
    data = np.random.default_rng(msg_len).bytes(64 + msg_len)
    assert sp.sha512(data) == hashlib.sha512(data).digest()
    assert sp.sha512(data[64:]) == hashlib.sha512(data[64:]).digest()


@pytest.fixture(scope="module")
def ed_mixed_rows():
    """(keys, sigs, msgs) of 257 rows by 66 signers and a key that is no
    point (the first 66 rows: 65 distinct keys): messages of 32 bytes, an empty and a 5 kB one; a short, a long
    and an empty signature; R's y >= p; s >= L; s = L - 1; y >= p under the
    key that is no point."""
    from corda_tpu.ops import ed25519 as ed
    rng = np.random.default_rng(40)
    seeds = [rng.bytes(32) for _ in range(66)]
    pubs = [ecmath.ed25519_public_key(sd) for sd in seeds]
    keys, sigs, msgs = [], [], []
    for i in range(257):
        k = i % 66
        msg = {6: b"", 9: rng.bytes(5000)}.get(i, rng.bytes(32))
        # one real signature a signer (pure-Python signing is ms a call);
        # the prep does not verify, so any 64 bytes do for the rest
        sig = (ecmath.ed25519_sign(seeds[k], msg, pubs[k]) if i < 66
               else rng.bytes(32) + rng.bytes(31) + b"\x01")
        keys.append(pubs[k]); sigs.append(sig); msgs.append(msg)
    bad_key = next(bytes([b]) + bytes(31) for b in range(2, 255)
                   if ecmath.ed_point_decompress(bytes([b]) + bytes(31))
                   is None)
    y_ge_p = b"\xee" + b"\xff" * 30 + b"\x7f"
    keys[3] = bad_key
    sigs[5] = sigs[5][:63]
    sigs[7] = y_ge_p + sigs[7][32:]
    sigs[8] = sigs[8] + b"\x00"
    sigs[10] = b""
    sigs[11] = sigs[11][:32] + b"\xff" * 32
    sigs[12] = sigs[12][:32] + (ecmath.ED_L - 1).to_bytes(32, "little")
    keys[13], sigs[13] = bad_key, y_ge_p + sigs[13][32:]
    sigs[14] = sigs[14][:32] + ecmath.ED_L.to_bytes(32, "little")
    assert ed._signer_row(bad_key) is None
    return keys, sigs, msgs


def _ed_words_both_ways(keys, sigs, msgs, capacity, hold_rows, monkeypatch):
    from corda_tpu.ops import ed25519 as ed
    monkeypatch.setattr(sp, "ED_WORDS_HOLD_LOCK_ROWS", hold_rows)
    args = (*sp.join_rows(sigs), *sp.join_rows(msgs),
            *ed._signer_slots(keys), ed._substitute_row(), capacity)
    return sp.ed_prep_words(*args), ed._prep_words_python(*args)


@pytest.mark.parametrize("handle", ["lock_let_go", "lock_held"])
@pytest.mark.parametrize("live", [1, 255, 256, 257])
def test_ed_prep_words_native_matches_python_row_for_row(
        live, handle, ed_mixed_rows, monkeypatch):
    """sm_ed_prep_words against the pure-Python form on a mixed batch, the
    live rows padded to their bucket, through both handles of the export
    (CDLL lets the interpreter lock go, PyDLL holds it)."""
    from corda_tpu.ops import field as F
    keys, sigs, msgs = (col[:live] for col in ed_mixed_rows)
    capacity = F.bucket_size(live)
    native, python = _ed_words_both_ways(
        keys, sigs, msgs, capacity,
        0 if handle == "lock_let_go" else 1 << 30, monkeypatch)
    names = ["bb_idx", "a_packed", "rows", "r_packed", "precheck"]
    for name, a, b in zip(names, native, python):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    precheck = native[4]
    assert precheck.shape == (capacity,)
    refused = {3, 5, 7, 8, 10, 11, 13, 14} & set(range(live))
    assert set(np.flatnonzero(~precheck[:live]).tolist()) == refused
    # the padding repeats the last live row in every array
    for a, axis in zip(native, (1, 1, 0, 0, 0)):
        a = np.moveaxis(a, axis, 0)
        assert (a[live:] == a[live - 1]).all()


@pytest.mark.parametrize("signers", [1, 65])
def test_ed_prep_words_one_signer_and_sixty_five(signers, ed_mixed_rows,
                                                 monkeypatch):
    """The slot table at its smallest and over 64 (one slot a DISTINCT
    signer, whatever their number), and k held to the definition: k =
    SHA-512(R || A || M) mod L, read back from the packed joint digits."""
    import hashlib
    keys, sigs, msgs = (col[:66] for col in ed_mixed_rows)
    if signers == 1:
        keys = keys[:1] * 66
    native, python = _ed_words_both_ways(keys, sigs, msgs, 128,
                                         sp.ED_WORDS_HOLD_LOCK_ROWS,
                                         monkeypatch)
    for a, b in zip(native, python):
        np.testing.assert_array_equal(a, b)
    from corda_tpu.ops import ed25519 as ed
    assert len(ed._signer_slots(keys)[3]) == signers
    a_packed = native[1]
    for i in (0, 6, 9, 65):        # 32 bytes, empty, 5 kB, the last row
        klo = khi = 0
        for d in a_packed[:, i]:   # MSB-first 2-bit digits of each half
            klo, khi = klo << 2 | int(d) & 3, khi << 2 | int(d) >> 2
        want = int.from_bytes(hashlib.sha512(
            sigs[i][:32] + keys[i] + msgs[i]).digest(), "little") % ecmath.ED_L
        assert klo | khi << 128 == want


def test_ed_prep_words_refuses_inconsistent_input(ed_mixed_rows):
    """Sizes are checked before a pointer is passed: lengths that overrun
    the joined buffer and a slot outside the table are refused, not read."""
    from corda_tpu.ops import ed25519 as ed
    keys, sigs, msgs = (col[:8] for col in ed_mixed_rows)
    sig_buf, sig_len = sp.join_rows(sigs)
    msg_buf, msg_len = sp.join_rows(msgs)
    which, *slots = ed._signer_slots(keys)
    sub = ed._substitute_row()
    with pytest.raises(RuntimeError, match="-3"):
        sp.ed_prep_words(sig_buf[:-1], sig_len, msg_buf, msg_len, which,
                         *slots, sub, 8)
    with pytest.raises(RuntimeError, match="-2"):
        sp.ed_prep_words(sig_buf, sig_len, msg_buf, msg_len, which + 8,
                         *slots, sub, 8)
    with pytest.raises(ValueError):
        sp.ed_prep_words(sig_buf, sig_len, msg_buf, msg_len, which,
                         *slots, sub, 7)


def test_ed_split_kernel_matches_plain_reference():
    """The split-k kernel and the plain reference (``verify_core``, the
    256-bit Shamir ladder fed by ``prepare_batch``) must agree verdict for
    verdict over valid + tampered + edge-encoded signatures."""
    from corda_tpu.ops import ed25519 as ed
    rng = np.random.default_rng(45)
    items = []
    for i in range(6):
        seed = rng.bytes(32)
        pub = ecmath.ed25519_public_key(seed)
        msg = rng.bytes(24)
        sig = ecmath.ed25519_sign(seed, msg)
        items.append((pub, sig, msg))
    pub0, sig0, msg0 = items[0]
    items += [
        (pub0, sig0, b"tampered"),
        (pub0, sig0[:31] + bytes([sig0[31] ^ 0x80]) + sig0[32:], msg0),
        (pub0, sig0[:32] + (ecmath.ED_L + 5).to_bytes(32, "little"), msg0),
        (pub0, b"short", msg0),
    ]
    split = ed.verify_batch(items)   # routes through the split kernel
    *args, pre = ed.prepare_batch(items + [items[-1]] * (16 - len(items)))
    plain = (np.asarray(ed._verify_kernel(*args)) & pre)[:len(items)]
    np.testing.assert_array_equal(split, plain)
    want = [ecmath.ed25519_verify(pub, msg, sig) for pub, sig, msg in items]
    np.testing.assert_array_equal(split, np.asarray(want))


def _der_corpus():
    """Valid DER signatures plus every malformed shape ecdsa_sig_from_der
    rejects: truncated, trailing bytes, wrong tags, zero-length ints,
    negative ints, non-minimal encodings, oversized ints."""
    rng = random.Random(47)
    curve = ecmath.SECP256K1
    sigs = []
    for _ in range(24):
        priv = rng.randrange(1, curve.n)
        r, s = ecmath.ecdsa_sign(curve, priv, rng.randbytes(40))
        sigs.append(ecmath.ecdsa_sig_to_der(r, s))
    good = sigs[0]
    sigs += [
        b"",                                     # empty
        b"\x30",                                 # sequence tag alone
        good[:-1],                               # truncated
        good + b"\x00",                          # trailing byte
        b"\x31" + good[1:],                      # wrong outer tag
        good[:2] + b"\x03" + good[3:],           # wrong INTEGER tag
        b"\x30\x04\x02\x00\x02\x00",             # zero-length ints
        b"\x30\x06\x02\x01\x81\x02\x01\x01",     # negative r (high bit)
        b"\x30\x07\x02\x02\x00\x01\x02\x01\x01",  # non-minimal r
        b"\x30\x26\x02\x21\x01" + b"\x00" * 32 + b"\x02\x01\x01",  # r > 2^256
        bytes([good[0], good[1] + 1]) + good[2:] + b"\x00",  # length lies
    ]
    return sigs


def test_ecdsa_sigs_to_words_matches_der_parser():
    """The batched DER parse vs the strict per-item parser
    (ecmath.ecdsa_sig_from_der + ints_to_words): identical accepted set and
    word rows for every signature whose ints fit 256 bits. Oversized ints
    (which the strict parser accepts and leaves to the range precheck) and
    outright malformations both get ok=False + zeroed rows — r = 0 forces
    the native range precheck to reject, so the VERDICT is identical."""
    sigs = _der_corpus()
    r_words, s_words, ok = sp.ecdsa_sigs_to_words(sigs)
    assert r_words.shape == (len(sigs), 4) and s_words.shape == (len(sigs), 4)
    for i, der in enumerate(sigs):
        try:
            r, s = ecmath.ecdsa_sig_from_der(der)
            accept = max(r, s) < 1 << 256
        except Exception:
            accept = False
        if not accept:
            assert not ok[i], f"sig {i}: batched parse accepted"
            assert not r_words[i].any() and not s_words[i].any()
            continue
        assert ok[i], f"sig {i}: batched parse rejected, strict accepted"
        np.testing.assert_array_equal(r_words[i], sp.ints_to_words([r])[0])
        np.testing.assert_array_equal(s_words[i], sp.ints_to_words([s])[0])
    assert ok[:24].all() and not ok[24:].any()


def _der_int(rng) -> bytes:
    """One INTEGER's body: 1-33 random bytes, the first with its high bit
    set or clear as drawn, the sign byte in front of a set one. A first byte
    that comes out 0 is a non-minimal body; 33 bytes after the sign byte are
    an integer the words cannot hold."""
    raw = bytearray(rng.randbytes(rng.randrange(1, 34)))
    raw[0] = raw[0] | 0x80 if rng.random() < 0.5 else raw[0] & 0x7F
    return (b"\x00" if raw[0] & 0x80 else b"") + bytes(raw)


def _der_seq(r_body: bytes, s_body: bytes) -> bytes:
    body = (bytes([0x02, len(r_body)]) + r_body
            + bytes([0x02, len(s_body)]) + s_body)
    return bytes([0x30, len(body)]) + body


def _der_random(count: int = 10_000):
    """``count`` seeded random signatures, each followed by its mutations,
    and the strings no signature is; shuffled, so that what lies beside a
    row in the joined buffer is no help to it."""
    rng = random.Random(4805)
    sigs = []
    for _ in range(count):
        r_body, s_body = _der_int(rng), _der_int(rng)
        der = _der_seq(r_body, s_body)
        s_tag = 2 + 2 + len(r_body)          # where the second INTEGER starts
        at = rng.choice((0, 2, s_tag))       # a tag ...
        ln = rng.choice((1, 3, s_tag + 1))   # ... and a length byte
        cut = rng.randrange(1, 4)
        sigs += [
            der,
            der[:at] + bytes([der[at] ^ (1 << rng.randrange(8))])
            + der[at + 1:],                              # a flipped tag
            der[:ln] + bytes([der[ln] + 1]) + der[ln + 1:],  # a length + 1
            der[:ln] + bytes([der[ln] - 1]) + der[ln + 1:],  # a length - 1
            der[:-cut],                                  # a truncated tail
            der + rng.randbytes(cut),                    # an extended tail
            _der_seq(b"\x00" + r_body, s_body),          # a leading zero more
            _der_seq(r_body, b"\x00" + s_body),
        ]
    sigs += [b""] + [rng.randbytes(k) for k in range(1, 8)]
    sigs += [b"\x30" + bytes([k - 2]) + rng.randbytes(k - 2)
             for k in range(2, 8)]
    # 300 bytes: a length that no one byte holds, also where the byte holds
    # the length's low eight bits
    sigs += [rng.randbytes(300), b"\x30" + bytes([298 & 0xFF])
             + _der_seq(_der_int(rng), _der_int(rng)).ljust(298, b"\x00")]
    rng.shuffle(sigs)
    return sigs


_DER_FAMILIES = {
    "corpus": lambda: [_der_corpus()],
    "random_and_mutated": lambda: [_der_random()],
    "empty_batch": lambda: [[]],
    "batches_of_one": lambda: [[der] for der in _der_corpus()],
}


@pytest.mark.parametrize(
    "family", [*_DER_FAMILIES, "fallback_without_the_library"])
def test_native_der_parse_is_the_python_parse_row_for_row(family, monkeypatch):
    """sm_ecdsa_der_words against ``ecdsa_sigs_to_words_py``, the oracle:
    the same ``ok``, ``r_words`` and ``s_words`` as arrays. Without the
    library ``ecdsa_sigs_to_words`` hands back what the native parse did."""
    fallback = family == "fallback_without_the_library"
    batches = _DER_FAMILIES["corpus" if fallback else family]()
    if fallback:
        wants = [sp.ecdsa_sigs_to_words(sigs) for sigs in batches]
        monkeypatch.setattr(sp, "_LIB", None)
    else:
        wants = [sp.ecdsa_sigs_to_words_py(sigs) for sigs in batches]
    accepted = refused = 0
    for sigs, want in zip(batches, wants):
        got = sp.ecdsa_sigs_to_words(sigs)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)
        ok = got[2]
        assert not got[0][~ok].any() and not got[1][~ok].any()
        accepted += int(ok.sum())
        refused += int((~ok).sum())
    if family == "random_and_mutated":
        assert accepted > 5_000 and refused > 50_000
    elif family != "empty_batch":
        assert (accepted, refused) == (24, 11)


def test_pub_row_cache_matches_decompress():
    """keys.sec1_pub_row_cached vs the bigint decompress: same affine point
    as LE u64 words, None for undecodable encodings, and cache hits return
    the identical row."""
    from corda_tpu.core.crypto.keys import sec1_compress, sec1_pub_row_cached
    rng = random.Random(48)
    for curve in (ecmath.SECP256K1, ecmath.SECP256R1):
        for _ in range(8):
            pt = curve.mul(rng.randrange(1, curve.n), curve.g)
            enc = sec1_compress(curve, pt)
            row = sec1_pub_row_cached(curve, enc)
            want = np.frombuffer(pt[0].to_bytes(32, "little")
                                 + pt[1].to_bytes(32, "little"), dtype="<u8")
            np.testing.assert_array_equal(row, want)
            assert sec1_pub_row_cached(curve, enc) is row   # LRU hit
        assert sec1_pub_row_cached(curve, b"\x02" + b"\xff" * 32) is None
        assert sec1_pub_row_cached(curve, b"\x09" * 33) is None


def test_stale_so_falls_back_loudly(caplog):
    """ABI gate (sm_version): a stale .so must be REFUSED with a warning —
    the Python fallback is bit-identical (differential tests above), so a
    silent downgrade would masquerade as a performance regression."""
    import logging
    real = next(p for p in sp._CANDIDATES if os.path.exists(p))
    with caplog.at_level(logging.WARNING, logger="corda_tpu.ops.scalarprep"):
        assert sp._load(candidates=[real],
                        expected=sp.SM_VERSION + 1) is None
    assert any("stale libscalarmath.so" in rec.message
               and "make -C native libscalarmath.so" in rec.message
               for rec in caplog.records)
    # the matching version loads fine (the gate, not the loader, refused)
    assert sp._load(candidates=[real]) is not None
    # and a refused library means available() gates every native seam
    assert sp.SM_VERSION == 7  # 6→7: the exports of deleted ladders left


def test_k1_verify_through_native_prep():
    """End-to-end: verify_batch (which routes through the native prep when
    available) accepts valid signatures and rejects tampered ones."""
    items = _k1_items(6)
    kitems = [(pub, msg, r, s) for pub, msg, r, s in items]
    ok = wc.verify_batch(ecmath.SECP256K1, kitems)
    assert ok[:6].all()
    assert not ok[6:].any()
    # tamper: flip a message byte
    pub, msg, r, s = kitems[0]
    bad = bytes([msg[0] ^ 1]) + msg[1:]
    ok2 = wc.verify_batch(ecmath.SECP256K1, [(pub, bad, r, s)])
    assert not ok2.any()
