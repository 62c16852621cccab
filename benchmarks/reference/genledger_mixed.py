"""Plain reference for the ``genledger-mixed`` deployment: what a verifier
has to answer for one whole transaction of a mixed-scheme ledger, from bytes
alone (``SignedTransaction.kt:71-100`` ``verifySignatures``: every signature
valid, then every required key ``isFulfilledBy`` the signers' set). It
imports nothing of the program:

- the transaction's id is the Merkle root (hashlib, as
  ``crosscash_raft.merkle_root``) of the SHA-256 of each serialised component;
- every (scheme, raw key, signature) is checked over that id by the
  ``cryptography`` package: Ed25519, or ECDSA over secp256k1 by
  ``Crypto.doVerify``'s rule (``genledger_secp256k1.ecdsa_valid``: strict DER,
  ``r`` and ``s`` in ``[1, n-1]``, a high ``s`` VALID);
- coverage by this file's OWN decoder of the composite encoding (``0xC0``,
  threshold, weighted children, nested) and its own weighted-threshold
  recursion over the signers' set (``CompositeKey.kt:35``);
- the contract's rule written out (``GeneratedLedger.kt``'s dummy accepts
  everything).

A transaction is judged in the order ``SignedTransaction.verify`` judges it:
a signature that does not verify (``signature``), then a required key not
fulfilled (``missing``), then the contract (``contract``, which this
deployment never produces); what is left is ``valid``."""
from __future__ import annotations

import hashlib
import struct

from reference.crosscash_raft import ed25519_valid, merkle_root
from reference.genledger_secp256k1 import ecdsa_valid

VALID, BAD_SIGNATURE, MISSING, CONTRACT = \
    "valid", "signature", "missing", "contract"
#: ``Crypto.kt``'s scheme numbers as they stand on the wire
SECP256K1, ED25519, COMPOSITE = 2, 4, 6
CHECKS = {ED25519: ed25519_valid, SECP256K1: ecdsa_valid}
HEAD, CHILD = ">BIH", ">IBI"


def transaction_id(component_blobs: list[bytes]) -> bytes:
    return merkle_root([hashlib.sha256(b).digest() for b in component_blobs])


def dummy_contract_accepts(component_blobs: list[bytes]) -> bool:
    """``DummyContract.verify``: no clause, every transaction passes."""
    return True


def signature_valid(scheme: int, pub: bytes, sig: bytes, msg: bytes) -> bool:
    check = CHECKS.get(scheme)
    return check is not None and check(pub, sig, msg)


def decode_composite(encoded: bytes):
    """``(threshold, [(weight, scheme, child)])`` of one composite
    encoding: tag ``0xC0``, u32 threshold, u16 count, then per child u32
    weight, u8 scheme, u32 length and the child's own encoding (a nested
    composite's is decoded in turn, a leaf's kept as bytes). Every byte has
    to be consumed."""
    tag, threshold, count = struct.unpack_from(HEAD, encoded, 0)
    if tag != 0xC0:
        raise ValueError("not a composite key")
    at = struct.calcsize(HEAD)
    children = []
    for _ in range(count):
        weight, scheme, length = struct.unpack_from(CHILD, encoded, at)
        at += struct.calcsize(CHILD)
        body = encoded[at:at + length]
        if len(body) != length:
            raise ValueError("a child runs past the end")
        at += length
        children.append((weight, scheme, decode_composite(body)
                         if scheme == COMPOSITE else body))
    if at != len(encoded):
        raise ValueError("bytes after the last child")
    return threshold, children


def fulfilled(scheme: int, key, signers: set) -> bool:
    """Whether ``signers`` (a set of (scheme, raw key)) fulfil one required
    key: a leaf by being among them, a composite (decoded) where the weights
    of its fulfilled children reach its threshold, recursively."""
    if scheme != COMPOSITE:
        return (scheme, key) in signers
    threshold, children = key
    return sum(weight for weight, child_scheme, child in children
               if fulfilled(child_scheme, child, signers)) >= threshold


def required_fulfilled(scheme: int, encoded: bytes, signers: set) -> bool:
    return fulfilled(scheme, decode_composite(encoded)
                     if scheme == COMPOSITE else encoded, signers)


def verdict(fact) -> str:
    """``fact``: (component blobs, [(scheme, raw key, signature)],
    [(scheme, key encoding)] required) of one transaction."""
    blobs, sigs, required = fact
    tx_id = transaction_id(blobs)
    if not all(signature_valid(scheme, pub, sig, tx_id)
               for scheme, pub, sig in sigs):
        return BAD_SIGNATURE
    signers = {(scheme, pub) for scheme, pub, _sig in sigs}
    if not all(required_fulfilled(scheme, encoded, signers)
               for scheme, encoded in required):
        return MISSING
    return VALID if dummy_contract_accepts(blobs) else CONTRACT


def verdicts(facts) -> list[str]:
    return [verdict(f) for f in facts]
