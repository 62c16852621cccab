"""Plain reference for the ``genledger-oop`` deployment: what a verifier has
to answer for one whole transaction, from bytes alone. It imports nothing of
the program: the transaction's id is the Merkle root (hashlib, as
``crosscash_raft.merkle_root``) of the SHA-256 of each serialised component,
every signature is checked over that id with the ``cryptography`` package's
Ed25519, the required-signers rule is a set comparison, and the contract's
rule is written out below (the deployment's contract is
``GeneratedLedger.kt``'s dummy: it accepts everything).

A transaction is judged in the order the deployment's two halves judge it:
the requestor refuses one whose required signers have not all signed
(``missing_signer``) before anything is sent; the worker refuses one with a
signature that does not verify over its id (``signature``); what is left has
to satisfy the contract (``contract``, which this deployment never
produces) and is ``valid``."""
from __future__ import annotations

import hashlib

from reference.crosscash_raft import ed25519_valid, merkle_root

VALID, BAD_SIGNATURE, MISSING_SIGNER, CONTRACT = \
    "valid", "signature", "missing_signer", "contract"


def transaction_id(component_blobs: list[bytes]) -> bytes:
    return merkle_root([hashlib.sha256(b).digest() for b in component_blobs])


def dummy_contract_accepts(component_blobs: list[bytes]) -> bool:
    """``DummyContract.verify``: no clause, every transaction passes."""
    return True


def verdict(fact) -> str:
    """``fact``: (component blobs, [(raw key, signature)], [required raw
    keys]) of one transaction."""
    blobs, sigs, required = fact
    if not set(required) <= {pub for pub, _sig in sigs}:
        return MISSING_SIGNER
    tx_id = transaction_id(blobs)
    if not all(ed25519_valid(pub, sig, tx_id) for pub, sig in sigs):
        return BAD_SIGNATURE
    return VALID if dummy_contract_accepts(blobs) else CONTRACT


def verdicts(facts) -> list[str]:
    return [verdict(f) for f in facts]
