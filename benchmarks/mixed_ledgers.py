"""The seeded ledgers of the ``genledger-mixed`` deployment: whole
transactions by the program's port of ``GeneratedLedger.kt``
(``corda_tpu.testing.generated_ledger.make_generated_ledger`` with
``scheme_mix``, composite owners and a cluster notary), some altered
afterwards, and beside each what the plain reference needs to judge it
without the program: the serialised components its id is the Merkle root of,
its (scheme, raw key, signature) triples and its required keys as (scheme,
encoding) pairs, a CompositeKey in its own wire encoding.

THE SIGNER is the ``cryptography`` package for both schemes, handed to the
generator as its ``signer``: Ed25519 by RFC 8032 (the bytes the program's own
signer gives) and ECDSA with RFC 6979 nonces and NO normalisation of ``s`` (as
BouncyCastle's ``SHA256withECDSA``: about half the signatures carry ``s > n /
2``; the program's own signer normalises to low ``s`` and takes 88 ms a
signature in pure Python). A seed gives a byte-identical ledger on any number
of cores. ``make_ledger`` is the job ``ecdsa_pool.parallel_map`` hands to
fresh interpreters, which import the program's core and testing packages and
nothing of JAX.

Altered kinds (``KINDS``), each in rotation through the whole pool. The first
eight are INVALID, 1 transaction in ``invalid_every``:

0. the last byte of the first signature flipped;
1. the first signature replaced by ANOTHER party's signature (same scheme)
   over the same id, under the original signer's key;
2. the first signature replaced by the same signer's signature over ANOTHER
   transaction's id;
3. a required plain signer's signature removed;
4. a flat 2-of-3 owner with ONE leaf signature;
5. a flat 2-of-3 owner with two leaf signatures of which the secp256k1 one
   carries ``s + n`` (no strict DER of an ``s`` in range);
6. a nested owner (threshold 3: leaf A weight 2, inner 1-of-2 weight 1)
   signed by A alone: the weights fall one short;
7. the notary's signature by a key that is no replica's (the signature
   itself verifies under that key).

The last two are VALID shapes a wrong rule would refuse, 1 transaction in
``4 * invalid_every``:

8. a flat 2-of-3 owner over-fulfilled with all three leaves;
9. a flat 2-of-3 owner whose secp256k1 leaf signature has a HIGH ``s`` (the
   twin ``n - s`` is taken where the signer emitted the low one).

``VERDICTS`` names the class each kind has to come back as.
"""
from __future__ import annotations

import pathlib
import random
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import ecdsa_pool  # noqa: E402  (benchmarks/ecdsa_pool.py)

KINDS = ("flipped signature byte", "signature by another party's key",
         "signature over another transaction's id",
         "a required plain signer's signature removed",
         "2-of-3 owner with one leaf signature",
         "2-of-3 owner whose secp256k1 leaf signature is s + n",
         "nested owner whose signed weights fall one short",
         "notary signature by a key that is no replica's",
         "VALID: 2-of-3 owner over-fulfilled with all three leaves",
         "VALID: a high-s secp256k1 leaf")
N_INVALID = 8
VALID, BAD_SIGNATURE, MISSING = "valid", "signature", "missing"
VERDICTS = (BAD_SIGNATURE, BAD_SIGNATURE, BAD_SIGNATURE, MISSING, MISSING,
            BAD_SIGNATURE, MISSING, MISSING, VALID, VALID)
K1_ORDER = ecdsa_pool.ORDERS["secp256k1"]


def ledger_seeds(seed: int, n_ledgers: int) -> list[int]:
    """One seed per ledger, derived from ``--seed`` (any whole number up to
    a little over 2**31)."""
    rng = random.Random(f"genledger-mixed:{int(seed)}")
    return [rng.getrandbits(48) for _ in range(n_ledgers)]


def make_signer():
    """``signer(key_pair, content)`` for ``make_generated_ledger``: the
    ``cryptography`` package's Ed25519 and deterministic, un-normalised
    ECDSA over secp256k1, a private key object a key pair."""
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PrivateKey
    from corda_tpu.core.crypto.schemes import EDDSA_ED25519_SHA512
    from corda_tpu.core.crypto.signatures import DigitalSignatureWithKey
    alg = ecdsa_pool._algorithm()
    ed = EDDSA_ED25519_SHA512.scheme_number_id
    keys: dict = {}

    def sign(kp, content: bytes):
        sk = keys.get(kp.public)
        if sk is None:
            raw = kp.private.encoded
            sk = keys[kp.public] = (
                Ed25519PrivateKey.from_private_bytes(raw)
                if kp.public.scheme.scheme_number_id == ed
                else ec.derive_private_key(int.from_bytes(raw, "big"),
                                           ec.SECP256K1()))
        if kp.public.scheme.scheme_number_id == ed:
            return DigitalSignatureWithKey(sk.sign(content), kp.public)
        return DigitalSignatureWithKey(sk.sign(content, alg), kp.public)

    return sign


def with_s(sig: bytes, change) -> bytes:
    """A DER ECDSA signature with ``s`` replaced by ``change(s)``."""
    from cryptography.hazmat.primitives.asymmetric.utils import \
        decode_dss_signature
    r, s = decode_dss_signature(sig)
    return ecdsa_pool.der_sig(ecdsa_pool.der_int(r),
                              ecdsa_pool.der_int(change(s)))


def eligible(kind: int, about: dict) -> bool:
    """Whether a transaction (``about``: ``n_sigs``, ``owner`` = plain |
    flat | nested, ``notarised``, ``first_plain``) can carry ``kind``."""
    if kind in (0, 2):
        return True
    if kind == 1:
        return about["first_plain"]
    if kind == 3:
        return about["first_plain"] and about["n_sigs"] >= 2
    if kind in (4, 5, 8, 9):
        return about["owner"] == "flat"
    if kind == 6:
        return about["owner"] == "nested"
    return about["notarised"]


def pick_altered(seed: int, abouts: list, invalid_every: int,
                 first_kind: int) -> dict[int, int]:
    """index -> kind: ``len(abouts) // invalid_every`` transactions of one
    ledger made invalid, the eight kinds in rotation from ``first_kind``,
    and a quarter as many given a valid shape, the two in rotation. A kind
    takes the next transaction of a seeded order that can carry it (none
    where the ledger holds none: the caller sees which kinds were drawn)."""
    rng = random.Random(f"altered:{seed}")
    order = list(range(len(abouts)))
    rng.shuffle(order)
    want = len(abouts) // invalid_every if invalid_every else 0
    turns = [(first_kind + k) % N_INVALID for k in range(want)] \
        + [N_INVALID + (first_kind + k) % 2 for k in range(want // 4)]
    picked: dict[int, int] = {}
    for kind in turns:
        at = next((j for j, i in enumerate(order)
                   if eligible(kind, abouts[i])), None)
        if at is not None:
            picked[order.pop(at)] = kind
    return picked


def make_ledger(job) -> dict:
    """``(ledger seed, transactions, parties, composite parties, nested
    composites, notary replicas, invalid_every, first kind)`` -> the ledger
    as the driver and the reference take it:

    ``stx``    the serialised SignedTransactions, in ledger order, the
               altered ones as they are handed over;
    ``facts``  per transaction ``(component blobs, [(scheme id, raw key,
               signature)], [(scheme id, key encoding)] required)``;
    ``kinds``  index -> kind of the altered ones."""
    from corda_tpu.core.crypto.composite import CompositeKey
    from corda_tpu.core.crypto.keys import generate_keypair
    from corda_tpu.core.crypto.schemes import ECDSA_SECP256K1_SHA256
    from corda_tpu.core.crypto.signatures import DigitalSignatureWithKey
    from corda_tpu.core.serialization import serialize
    from corda_tpu.core.transactions.signed import SignedTransaction
    from corda_tpu.testing.generated_ledger import (CompositeSigner,
                                                    make_generated_ledger)

    (seed, n_tx, n_parties, n_composite, n_nested, replicas, invalid_every,
     first_kind) = job
    sign = make_signer()
    ledger = make_generated_ledger(
        n_tx, seed=seed, n_parties=n_parties, scheme_mix=True,
        composite_parties=n_composite, nested_composites=n_nested,
        notary_replicas=replicas, signer=sign)
    txs = list(ledger.transactions)
    k1 = ECDSA_SECP256K1_SHA256.scheme_number_id
    material = {kp.public: kp for _party, kp in ledger.parties}
    plain = {}          # every key pair that can sign, by its public key
    for kp in list(material.values()) + [ledger.notary_kp]:
        for leaf in (kp.leaves if isinstance(kp, CompositeSigner) else (kp,)):
            plain[leaf.public] = leaf
    stranger = generate_keypair(
        entropy=random.Random(f"stranger:{seed}").randbytes(32))

    def owner_of(stx):
        """The key material of the transaction's first required key (the
        issuer's, or the consumed state's owner's)."""
        return material[stx.tx.must_sign[0]]

    abouts = []
    for stx in txs:
        owner = owner_of(stx)
        shape = "plain" if not isinstance(owner, CompositeSigner) else (
            "flat" if len(owner.public.children) == 3 else "nested")
        abouts.append({"n_sigs": len(stx.sigs), "owner": shape,
                       "notarised": len(stx.tx.must_sign) > 1,
                       "first_plain": shape == "plain"})
    kinds = pick_altered(seed, abouts, invalid_every, first_kind)

    def resigned(stx, signers):
        return tuple(sign(kp, stx.id.bytes) for kp in signers)

    for i, kind in kinds.items():
        stx = txs[i]
        first = stx.sigs[0]
        owner = owner_of(stx)
        n_owner = len(owner.signing) \
            if isinstance(owner, CompositeSigner) else 1
        rest = stx.sigs[n_owner:]       # the notary's, where notarised
        if kind == 0:
            sigs = (DigitalSignatureWithKey(
                first.bytes[:-1] + bytes([first.bytes[-1] ^ 1]), first.by),
            ) + stx.sigs[1:]
        elif kind == 1:
            other = next(kp for pub, kp in plain.items()
                         if pub != first.by and pub.scheme == first.by.scheme)
            sigs = (DigitalSignatureWithKey(
                sign(other, stx.id.bytes).bytes, first.by),) + stx.sigs[1:]
        elif kind == 2:
            elsewhere = txs[(i + 1) % len(txs)].id.bytes
            sigs = (DigitalSignatureWithKey(
                sign(plain[first.by], elsewhere).bytes, first.by),
            ) + stx.sigs[1:]
        elif kind == 3:
            sigs = stx.sigs[1:]
        elif kind == 4:
            sigs = resigned(stx, owner.signing[:1]) + rest
        elif kind in (5, 9):
            ec_leaf = next(kp for kp in owner.leaves
                           if kp.public.scheme.scheme_number_id == k1)
            other = next(kp for kp in owner.leaves if kp is not ec_leaf)
            good = sign(ec_leaf, stx.id.bytes)
            change = (lambda s: s + K1_ORDER) if kind == 5 else (
                lambda s: s if s > K1_ORDER // 2 else K1_ORDER - s)
            sigs = (DigitalSignatureWithKey(with_s(good.bytes, change),
                                            good.by),) \
                + resigned(stx, (other,)) + rest
        elif kind == 6:
            sigs = resigned(stx, owner.leaves[:1]) + rest
        elif kind == 7:
            sigs = stx.sigs[:n_owner] + resigned(stx, (stranger,))
        else:   # 8
            sigs = resigned(stx, owner.leaves) + rest
        txs[i] = SignedTransaction.of(stx.tx, sigs)
    facts = []
    for stx in txs:
        wtx = stx.tx
        facts.append(([serialize(c) for c in wtx.available_components],
                      [(s.by.scheme.scheme_number_id, s.by.encoded, s.bytes)
                       for s in stx.sigs],
                      [(k.scheme.scheme_number_id, k.encoded)
                       for k in wtx.must_sign]))
    n_composite_required = sum(isinstance(k, CompositeKey)
                               for stx in txs for k in stx.tx.must_sign)
    return {"stx": [serialize(stx) for stx in txs], "facts": facts,
            "kinds": kinds, "composite_required": n_composite_required}
