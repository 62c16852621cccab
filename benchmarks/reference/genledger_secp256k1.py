"""Plain reference for the ``genledger-secp256k1`` deployment: every row's
verdict from the ``cryptography`` package's ECDSA verify (OpenSSL), which
shares nothing with the program. That is ``Crypto.doVerify``'s rule: strict
DER, ``r`` and ``s`` in ``[1, n-1]`` (a high ``s`` is valid), the key on the
curve, the equation. Also home of the control that stands in for the program
with one guarantee broken."""
from __future__ import annotations

import functools

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec

CURVE = ec.SECP256K1()
SHA256 = ec.ECDSA(hashes.SHA256())


@functools.lru_cache(maxsize=4096)
def _key(pub: bytes):
    try:
        return ec.EllipticCurvePublicKey.from_encoded_point(CURVE, pub)
    except ValueError:
        return None


def ecdsa_valid(pub: bytes, sig: bytes, msg: bytes) -> bool:
    key = _key(pub)
    if key is None:
        return False
    try:
        key.verify(sig, msg, SHA256)
        return True
    except InvalidSignature:
        return False


def verdicts(rows) -> list[bool]:
    """``rows``: (SEC1 public key, DER signature, message) triples."""
    return [ecdsa_valid(pub, sig, msg) for pub, sig, msg in rows]


def control_verdicts(rows) -> list[bool]:
    """CONTROL, never the reference: checks every other row and waves the
    rest through, which breaks "every verdict equals the reference"."""
    return [True if i % 2 else ecdsa_valid(pub, sig, msg)
            for i, (pub, sig, msg) in enumerate(rows)]
