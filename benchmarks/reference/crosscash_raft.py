"""Plain reference for the ``crosscash-raft`` deployment.

Imports nothing of the program and takes nothing it has computed except the
answers under test: signatures are checked with the ``cryptography`` package
(OpenSSL), Merkle roots with hashlib, the notary's consumed set with a dict.
The contract rules (cash conservation inside one transaction, the commercial
paper's clauses) have no independent copy here: see PERF.md, Open questions.
"""
from __future__ import annotations

import functools
import hashlib


def merkle_root(leaves: list[bytes]) -> bytes:
    """Zero-pad to a power of two, single-SHA-256 combine (MerkleTree.kt)."""
    n = 1
    while n < len(leaves):
        n <<= 1
    level = list(leaves) + [bytes(32)] * (n - len(leaves))
    while len(level) > 1:
        level = [hashlib.sha256(level[i] + level[i + 1]).digest()
                 for i in range(0, len(level), 2)]
    return level[0]


@functools.lru_cache(maxsize=4096)
def _public_key(pub: bytes):
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PublicKey
    try:
        return Ed25519PublicKey.from_public_bytes(pub)
    except ValueError:
        return None


def ed25519_valid(pub: bytes, sig: bytes, msg: bytes) -> bool:
    from cryptography.exceptions import InvalidSignature
    key = _public_key(bytes(pub))
    if key is None:
        return False
    try:
        key.verify(bytes(sig), msg)
        return True
    except (InvalidSignature, ValueError):
        return False


def count_bad_signatures(sigs: list[tuple[bytes, bytes]], content: bytes) -> int:
    """How many (public key, signature) pairs do not verify over ``content``
    (every key of this deployment is Ed25519, 32 raw bytes)."""
    return sum(not ed25519_valid(pub, sig, content) for pub, sig in sigs)


def consumed_set(committed) -> dict:
    """Put-if-absent of every input ref, in commit order: ref -> the
    transaction that consumed it first."""
    consumed: dict = {}
    for tx_id, refs in committed:
        for ref in refs:
            consumed.setdefault(ref, tx_id)
    return consumed


class LossyUniqueness:
    """CONTROL, never the reference: the map above with its put-if-absent
    check removed, in the notary's place. It records the last spender and
    refuses nothing, which breaks exactly-once."""

    def __init__(self):
        self.consumed: dict = {}

    def commit(self, states, tx_id, caller, **_kw) -> None:
        for ref in states:
            self.consumed[ref] = tx_id
