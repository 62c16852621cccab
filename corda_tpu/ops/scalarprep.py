"""ctypes binding for native/libscalarmath.so — batch host-side scalar prep.

The C library (native/scalarmath.cpp) performs the per-item scalar layer of
signature verification (Barrett mulmod, Montgomery batch inversion, GLV
decomposition, window/digit extraction, u16 limb packing) in one pass per
batch; the Python bigint loops it replaces were the service path's ceiling
(~0.9s per 32k secp256k1 batch, ~1.9s Ed25519, measured in an early
round).  Callers (ops/weierstrass.py, ops/ed25519.py) fall back to the
original Python prep when the library is absent — behavior is identical
(locked by tests/test_scalarprep.py differential tests).

Word convention: multiword integers are little-endian u64 arrays; a
256-bit value is a (4,) row, reinterpretable as 16 little-endian u16 limbs
(the kernels' wire format) — the C side writes limbs by memcpy.
"""
from __future__ import annotations

import ctypes
import logging
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_CANDIDATES = [
    os.path.join(_HERE, "..", "..", "native", "libscalarmath.so"),
    os.path.join(_HERE, "libscalarmath.so"),
]

#: ABI gate: the .so and this module move together (docs/PERFORMANCE.md
#: "sharp edges").  Version 3 added sm_r1_halfgcd / sm_r1_prep_hg /
#: sm_r1p_mulfast (the secp256r1 half-gcd split ladder).  Version 4 changed
#: the ECDSA preps' range check to Crypto.doVerify's (s in [1, n-1], no
#: low-s bound): a version-3 library would refuse every high-s signature.
#: Version 5 added sm_ecdsa_der_words (the strict-DER parse of a batch).
#: Version 6 added sm_ed_prep_words (the whole Ed25519 split prep of a batch:
#: parse, SHA-512 challenges, scalars, windows, the signers' rows, padding)
#: and sm_sha512, the seam its hash is held to hashlib through.
#: Version 7 took out three exports, the preps of ladders that are gone
#: (the Ed25519 scalar-only split and plain windowed preps, the secp256r1
#: single-scalar windowed prep): a version-6 library still exports them and
#: is refused, so what loads is what scalarmath.cpp says.
SM_VERSION = 7

#: Live rows up to which sm_ed_prep_words is called with the interpreter lock
#: HELD (PyDLL), and over which it is let go (CDLL): one algorithm, the one
#: parameter read off the input. A thread that gives the lock up waits a
#: switch interval (5 ms) to get it back while any other thread computes, so
#: a short call is cheaper held; a long one holds every other thread up.
#: Measured on a TPU v5e's host (PERF.md section 6, PR 40), both handles:
#: at 8,192 rows (genledger-ed25519.wave8k; the call runs 7.5-8.3 ms) let go
#: 316.8-330.5k sigs/s, held 243.0-249.9k; at 256 rows (genledger-oop.stream's
#: flushes; 0.23 ms) ``ed25519.prep.scalars`` p50 0.30-0.34 ms held, 1.93 let
#: go (mean 3.09). The rungs between were not measured: the constant sits
#: where the call is ~1 ms, a fifth of a switch interval.
ED_WORDS_HOLD_LOCK_ROWS = 1024

_log = logging.getLogger(__name__)

_U64P = np.ctypeslib.ndpointer(dtype=np.uint64, flags="C_CONTIGUOUS")
_U16P = np.ctypeslib.ndpointer(dtype=np.uint16, flags="C_CONTIGUOUS")
_U8P = np.ctypeslib.ndpointer(dtype=np.uint8, flags="C_CONTIGUOUS")
_I32P = np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS")
_I64P = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")


def _bind(lib) -> None:
    """Attach argtypes for every export of the expected ABI version."""
    lib.sm_mulmod.restype = ctypes.c_int
    lib.sm_mulmod.argtypes = [ctypes.c_int, _U64P, _U64P, _U64P]
    lib.sm_mod512.restype = ctypes.c_int
    lib.sm_mod512.argtypes = [ctypes.c_int, _U64P, _U64P]
    lib.sm_glv.restype = ctypes.c_int
    lib.sm_glv.argtypes = [_U64P, _U8P, _U64P, _U64P]
    lib.sm_r1_halfgcd.restype = ctypes.c_int
    lib.sm_r1_halfgcd.argtypes = [_U64P, _U8P, _U64P, _U64P]
    lib.sm_r1p_mulfast.restype = ctypes.c_int
    lib.sm_r1p_mulfast.argtypes = [_U64P, _U64P, _U64P]
    # The one export called WITHOUT letting go of the interpreter lock
    # (PyDLL over the same handle): the parse takes ~0.2 ms a batch of 8,192,
    # and a thread that gave the lock up waits a switch interval (5 ms) to get
    # it back while any other thread computes (measured: PERF.md, PR 38).
    der_words = ctypes.PyDLL(lib._name, handle=lib._handle).sm_ecdsa_der_words
    der_words.restype = ctypes.c_int
    der_words.argtypes = [ctypes.c_int64, _U8P, _I64P, _U64P, _U64P, _U8P]
    lib.sm_ecdsa_der_words = der_words
    lib.sm_k1_prep.restype = ctypes.c_int
    lib.sm_k1_prep.argtypes = [
        ctypes.c_int64, _U64P, _U64P, _U64P, _U64P,
        _I32P, _U8P, _U16P, _U16P, _U16P, _U16P, _U16P,
        _U8P, _U8P, _U64P]
    lib.sm_r1_prep_hg.restype = ctypes.c_int
    lib.sm_r1_prep_hg.argtypes = [
        ctypes.c_int64, _U64P, _U64P, _U64P, _U64P,
        _I32P, _U8P, _U16P, _U16P, _U16P,
        _U8P, _U8P, _U64P]
    lib.sm_sha512.restype = ctypes.c_int
    lib.sm_sha512.argtypes = [_U8P, ctypes.c_int64, _U8P]
    # both handles of the one export: ed_prep_words chooses by row count
    for name, dll in (("sm_ed_prep_words", lib),
                      ("sm_ed_prep_words_held",
                       ctypes.PyDLL(lib._name, handle=lib._handle))):
        fn = dll.sm_ed_prep_words
        fn.restype = ctypes.c_int
        fn.argtypes = [
            ctypes.c_int64, ctypes.c_int64,
            _U8P, ctypes.c_int64, _I64P, _U8P, ctypes.c_int64, _I64P,
            _I32P, ctypes.c_int64, _U8P, _U16P, _U8P, _U16P,
            _I32P, _U8P, _U16P, _U16P, _U8P]
        setattr(lib, name, fn)


def _load(candidates=None, expected: int = SM_VERSION):
    """Load the first candidate .so whose sm_version matches ``expected``.

    A version mismatch (stale .so after a repo update — the graceful-degrade
    path pinned by tests/test_scalarprep.py) is LOUD: the pure-Python prep
    is bit-identical but an order of magnitude slower, so silence here
    would read as a performance regression, not a build drift."""
    for path in (candidates if candidates is not None else _CANDIDATES):
        if not os.path.exists(path):
            continue
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            continue
        lib.sm_version.restype = ctypes.c_int
        got = lib.sm_version()
        if got != expected:
            _log.warning(
                "stale libscalarmath.so at %s (sm_version %d, need %d): "
                "falling back to the pure-Python scalar prep — rebuild with "
                "`make -C native libscalarmath.so`", path, got, expected)
            continue
        _bind(lib)
        return lib
    return None


_LIB = _load()

#: Modulus ids for the test seams (must match scalarmath.cpp).
MOD_K1_N, MOD_K1_P, MOD_R1_N, MOD_R1_P, MOD_ED_L, MOD_ED_P = range(6)


def available() -> bool:
    return _LIB is not None


# ---------------------------------------------------------------------------
# Host int <-> word-array conversion
# ---------------------------------------------------------------------------

def ints_to_words(xs, nwords: int = 4) -> np.ndarray:
    """Python ints → (B, nwords) LE u64 array (one C-level to_bytes each)."""
    nbytes = nwords * 8
    buf = b"".join(int(x).to_bytes(nbytes, "little") for x in xs)
    return np.frombuffer(buf, dtype="<u8").reshape(len(xs), nwords).copy()


def digests_to_words(digests: list[bytes], nwords: int) -> np.ndarray:
    """Big-endian digests (e.g. SHA-256 outputs) → (B, nwords) LE u64 words
    of the digest interpreted as a big-endian integer."""
    buf = b"".join(digests)
    be = np.frombuffer(buf, dtype=">u8").reshape(len(digests), nwords)
    return be[:, ::-1].astype("<u8")


def le_digests_to_words(digests: list[bytes], nwords: int) -> np.ndarray:
    """Little-endian-integer digests (RFC 8032 SHA-512) → LE u64 words."""
    buf = b"".join(digests)
    return np.frombuffer(buf, dtype="<u8").reshape(
        len(digests), nwords).copy()


def join_rows(chunks) -> tuple[np.ndarray, np.ndarray]:
    """Byte strings of any lengths → (their join as a u8 array, their
    lengths as int64): the form a native batch export takes rows in. Two
    calls that hold the interpreter lock and no numpy pass over the rows."""
    lengths = np.fromiter(map(len, chunks), dtype=np.int64, count=len(chunks))
    return np.frombuffer(b"".join(chunks), dtype=np.uint8), lengths


def ecdsa_sigs_to_words(sigs) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Strict-DER ECDSA signatures → (r_words (B,4), s_words (B,4),
    ok (B,) bool), the preps' LE u64 wire format — a batched
    ``ecmath.ecdsa_sig_from_der`` that skips the Python-bigint round trip
    (parse to int, then ints_to_words immediately re-serializes; at 32k
    items that double conversion was a measurable slice of ECDSA prep).

    Acceptance set is exactly ecdsa_sig_from_der's (tag/length/minimality/
    sign/trailing checks) plus the >= 2^256 clamp of the item-loop prep.
    Rejected encodings get ok=False and an all-zero row — r = 0 fails the
    preps' range precheck, so the member's verdict is False either way
    (locked by the test_scalarprep differential).

    One native call (sm_ecdsa_der_words) over the joined signatures; without
    the library, :func:`ecdsa_sigs_to_words_py`, the same parse a row."""
    if _LIB is None:
        return ecdsa_sigs_to_words_py(sigs)
    n = len(sigs)
    buf, lengths = join_rows(sigs)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    r_words = np.empty((n, 4), dtype=np.uint64)
    s_words = np.empty((n, 4), dtype=np.uint64)
    ok = np.empty(n, dtype=np.uint8)
    rc = _LIB.sm_ecdsa_der_words(n, buf, offsets, r_words, s_words, ok)
    if rc != 0:
        raise RuntimeError(f"sm_ecdsa_der_words failed: {rc}")
    return r_words, s_words, ok.view(bool)


def ecdsa_sigs_to_words_py(sigs) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The strict-DER parse as a Python loop: the oracle that
    sm_ecdsa_der_words is held to row for row (tests/test_scalarprep.py),
    and :func:`ecdsa_sigs_to_words` itself where the library is absent."""
    n = len(sigs)
    r_rows = np.zeros((n, 32), dtype=np.uint8)
    s_rows = np.zeros((n, 32), dtype=np.uint8)
    ok = np.ones(n, dtype=bool)
    for i, der in enumerate(sigs):
        if len(der) < 8 or der[0] != 0x30 or der[1] != len(der) - 2:
            ok[i] = False
            continue
        idx, bad = 2, False
        for rows in (r_rows, s_rows):
            if idx + 2 > len(der) or der[idx] != 0x02:
                bad = True
                break
            ln = der[idx + 1]
            body = der[idx + 2:idx + 2 + ln]
            if (ln == 0 or len(body) != ln or body[0] & 0x80
                    or (ln > 1 and body[0] == 0 and not (body[1] & 0x80))):
                bad = True
                break
            if body[0] == 0:
                body = body[1:]     # minimal leading zero (sign byte)
            if len(body) > 32:      # >= 2^256: clamp-to-reject
                bad = True
                break
            rows[i, :len(body)] = np.frombuffer(body, dtype=np.uint8)[::-1]
            idx += 2 + ln
        if bad or idx != len(der):
            ok[i] = False
            r_rows[i] = 0
            s_rows[i] = 0
    return r_rows.view("<u8"), s_rows.view("<u8"), ok


# ---------------------------------------------------------------------------
# Test seams
# ---------------------------------------------------------------------------

def mulmod(mod_id: int, a: int, b: int) -> int:
    aw = ints_to_words([a])
    bw = ints_to_words([b])
    r = np.zeros((1, 4), dtype=np.uint64)
    rc = _LIB.sm_mulmod(mod_id, aw, bw, r)
    assert rc == 0, rc
    return int.from_bytes(r.tobytes(), "little")


def mod512(mod_id: int, x: int) -> int:
    xw = ints_to_words([x], nwords=8)
    r = np.zeros((1, 4), dtype=np.uint64)
    rc = _LIB.sm_mod512(mod_id, xw, r)
    assert rc == 0, rc
    return int.from_bytes(r.tobytes(), "little")


def glv(k: int) -> tuple[int, int]:
    kw = ints_to_words([k])
    negs = np.zeros(2, dtype=np.uint8)
    a1 = np.zeros(2, dtype=np.uint64)
    a2 = np.zeros(2, dtype=np.uint64)
    rc = _LIB.sm_glv(kw, negs, a1, a2)
    assert rc == 0, rc
    k1 = int.from_bytes(a1.tobytes(), "little")
    k2 = int.from_bytes(a2.tobytes(), "little")
    return (-k1 if negs[0] else k1), (-k2 if negs[1] else k2)


def sha512(data: bytes) -> bytes:
    """Native seam: the library's own SHA-512 (sm_ed_prep_words hashes with
    it), held to hashlib.sha512 by tests/test_scalarprep.py."""
    out = np.empty(64, dtype=np.uint8)
    rc = _LIB.sm_sha512(np.frombuffer(data, dtype=np.uint8), len(data), out)
    assert rc == 0, rc
    return out.tobytes()


def r1p_mulfast(a: int, b: int) -> int:
    """Native seam: a*b mod p256 via the Solinas fast reduction (the
    [v2]R ladder's field mul — differential-tested vs Barrett/bigint)."""
    aw = ints_to_words([a])
    bw = ints_to_words([b])
    r = np.zeros((1, 4), dtype=np.uint64)
    rc = _LIB.sm_r1p_mulfast(aw, bw, r)
    assert rc == 0, rc
    return int.from_bytes(r.tobytes(), "little")


#: secp256r1 group order (duplicated from ecmath.SECP256R1 to keep this
#: module import-light — the value is pinned by test_scalarprep).
R1_N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551


def r1_halfgcd_py(k: int) -> tuple[bool, int, int] | None:
    """Pure-Python reference for the half-gcd split (Antipa et al., SAC
    2005): extended Euclid on (n, k) stopped at the first remainder below
    2^128.  Returns (neg1, v1, v2) with k*v2 ≡ (-v1 if neg1 else v1)
    (mod n), 0 <= v1 < 2^128, 0 < v2 < 2^128 — bit-identical to the
    native sm_r1_halfgcd — or None when the split degenerates (k = 0 or
    k >= n; the in-range legs can never reach 2^128: |t_i| <= n/r_{i-1}
    with r_{i-1} >= 2^128).  Signs in the EEA t-sequence strictly
    alternate, so only magnitudes are tracked with one parity bit."""
    if k <= 0 or k >= R1_N:
        return None
    r0, r1 = R1_N, k
    m0, m1 = 0, 1
    s_pos = True                     # sign of the t attached to r1
    while r1 >> 128:
        q, rem = divmod(r0, r1)
        r0, r1 = r1, rem
        m0, m1 = m1, m0 + q * m1
        s_pos = not s_pos
    if r1 == 0 or m1 == 0 or (m1 >> 128):
        return None
    return (not s_pos), r1, m1


def r1_halfgcd(k: int) -> tuple[bool, int, int] | None:
    """Native seam for the half-gcd split; same contract as
    :func:`r1_halfgcd_py`."""
    kw = ints_to_words([k])
    neg1 = np.zeros(1, dtype=np.uint8)
    v1 = np.zeros(2, dtype=np.uint64)
    v2 = np.zeros(2, dtype=np.uint64)
    rc = _LIB.sm_r1_halfgcd(kw, neg1, v1, v2)
    if rc != 0:
        return None
    return (bool(neg1[0]), int.from_bytes(v1.tobytes(), "little"),
            int.from_bytes(v2.tobytes(), "little"))


# ---------------------------------------------------------------------------
# Batch preps
# ---------------------------------------------------------------------------

def k1_prep(e_words, r_words, s_words, pub_words):
    """secp256k1 hybrid-GLV prep (w = 8).  All inputs (B, ·) u64 arrays.
    Returns (g_idx(16,B) i32, q_packed(64,B) u8, qc_x, qc_y, qd_x, qd_y
    (B,16) u16, r_limbs(B,16) u16, rn_ok(B) u8, precheck(B) bool)."""
    n = len(e_words)
    g_idx = np.empty((16, n), dtype=np.int32)
    q_packed = np.empty((64, n), dtype=np.uint8)
    qc_x = np.empty((n, 16), dtype=np.uint16)
    qc_y = np.empty((n, 16), dtype=np.uint16)
    qd_x = np.empty((n, 16), dtype=np.uint16)
    qd_y = np.empty((n, 16), dtype=np.uint16)
    r_limbs = np.empty((n, 16), dtype=np.uint16)
    rn_ok = np.empty(n, dtype=np.uint8)
    precheck = np.empty(n, dtype=np.uint8)
    work = np.empty((3 * n, 4), dtype=np.uint64)
    rc = _LIB.sm_k1_prep(
        n, np.ascontiguousarray(e_words), np.ascontiguousarray(r_words),
        np.ascontiguousarray(s_words), np.ascontiguousarray(pub_words),
        g_idx, q_packed, qc_x, qc_y, qd_x, qd_y, r_limbs,
        rn_ok, precheck, work)
    if rc != 0:
        raise RuntimeError(f"sm_k1_prep failed: {rc}")
    return (g_idx, q_packed, qc_x, qc_y, qd_x, qd_y, r_limbs,
            rn_ok, precheck.astype(bool))


def r1_prep_hg(e_words, r_words, s_words, pub_words):
    """secp256r1 half-gcd split prep (the PR-3 fast path; wire layout in
    scalarmath.cpp sm_r1_prep_hg).  Returns (g_idx(16,B) i32 — row 2j =
    t_hi window j, row 2j+1 = t_lo window j; q_digits(32,B) u8 4-bit |v1|
    digits; q_x, q_y (B,16) u16 sign-adjusted Q; xd_limbs (B,16) u16
    x([v2]R); hg_ok (B) u8; precheck (B) bool)."""
    n = len(e_words)
    g_idx = np.empty((16, n), dtype=np.int32)
    q_digits = np.empty((32, n), dtype=np.uint8)
    q_x = np.empty((n, 16), dtype=np.uint16)
    q_y = np.empty((n, 16), dtype=np.uint16)
    xd_limbs = np.empty((n, 16), dtype=np.uint16)
    hg_ok = np.empty(n, dtype=np.uint8)
    precheck = np.empty(n, dtype=np.uint8)
    work = np.empty((5 * n, 4), dtype=np.uint64)
    rc = _LIB.sm_r1_prep_hg(
        n, np.ascontiguousarray(e_words), np.ascontiguousarray(r_words),
        np.ascontiguousarray(s_words), np.ascontiguousarray(pub_words),
        g_idx, q_digits, q_x, q_y, xd_limbs, hg_ok, precheck, work)
    if rc != 0:
        raise RuntimeError(f"sm_r1_prep_hg failed: {rc}")
    return (g_idx, q_digits, q_x, q_y, xd_limbs, hg_ok,
            precheck.astype(bool))


def ed_prep_words(sig_buf, sig_len, msg_buf, msg_len, which, slot_keys,
                  slot_rows, slot_ok, sub_row, capacity: int, rows_out=None):
    """The whole Ed25519 split-k prep of a batch in ONE native call
    (sm_ed_prep_words; its wire form is described there): the rows' joined
    signatures and messages with their lengths (:func:`join_rows`), each
    row's slot ``which`` (n,) i32 in the table of the batch's distinct
    signers (``slot_keys`` (S, 32) u8, ``slot_rows`` (S, 6, 16) u16,
    ``slot_ok`` (S,) u8), the substitute row, and the padded ``capacity``.
    Returns (bb_idx (16, cap) i32, a_packed (64, cap) u8, rows (cap, 6, 16)
    u16 — ``rows_out`` where given, a staging lease's buffer —, r_packed
    (cap, 16) u16, precheck (cap,) bool).

    Made with the interpreter lock held up to ED_WORDS_HOLD_LOCK_ROWS live
    rows and let go over that."""
    n = len(which)
    if not (len(sig_len) == len(msg_len) == n and 0 <= n <= capacity
            and len(slot_keys) == len(slot_rows) == len(slot_ok)
            and slot_keys.shape[1:] == (32,)
            and slot_rows.shape[1:] == sub_row.shape == (6, 16)):
        raise ValueError("ed_prep_words: inconsistent input shapes")
    rows = (np.empty((capacity, 6, 16), dtype=np.uint16)
            if rows_out is None else rows_out)
    if rows.shape != (capacity, 6, 16):
        raise ValueError("ed_prep_words: rows_out is not (capacity, 6, 16)")
    bb_idx = np.empty((16, capacity), dtype=np.int32)
    a_packed = np.empty((64, capacity), dtype=np.uint8)
    r_packed = np.empty((capacity, 16), dtype=np.uint16)
    precheck = np.empty(capacity, dtype=np.uint8)
    call = (_LIB.sm_ed_prep_words_held if n <= ED_WORDS_HOLD_LOCK_ROWS
            else _LIB.sm_ed_prep_words)
    rc = call(n, capacity, sig_buf, len(sig_buf), sig_len,
              msg_buf, len(msg_buf), msg_len, which, len(slot_ok),
              slot_keys, slot_rows, slot_ok, sub_row,
              bb_idx, a_packed, rows, r_packed, precheck)
    if rc != 0:
        raise RuntimeError(f"sm_ed_prep_words failed: {rc}")
    return bb_idx, a_packed, rows, r_packed, precheck.view(bool)
