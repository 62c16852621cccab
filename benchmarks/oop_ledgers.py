"""The seeded ledgers of the ``genledger-oop`` deployment: whole transactions
by the program's own port of ``GeneratedLedger.kt``
(``corda_tpu.testing.generated_ledger.make_generated_ledger``, unedited),
some made invalid afterwards, and beside each what the plain reference needs
to judge it without the program: the serialised components its id is the
Merkle root of, its (raw key, signature) pairs and its required signers.

The port signs in pure Python (about 5 ms a transaction on one core), so the
ledgers are made side by side: ``make_ledger`` is the job
``ecdsa_pool.parallel_map`` hands to fresh interpreters, which import the
program's core and testing packages and nothing of JAX.

Invalid kinds (``KINDS``), in rotation through the whole pool:

0. the last byte of the first signature flipped;
1. the first signature replaced by ANOTHER party's signature over the same
   id, under the original signer's key;
2. the first signature replaced by the same signer's signature over ANOTHER
   transaction's id;
3. one required signer's signature removed (only a transaction with two
   signatures is picked for it: one without any cannot be built).

The first three are refused by the verifier worker (a signature does not
verify), the fourth by the requestor before dispatch (``verify_signed``:
signatures missing). ``VERDICTS`` names what a valid transaction and each
kind has to come back as.
"""
from __future__ import annotations

import pathlib
import random
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

KINDS = ("flipped signature byte", "signature by another party's key",
         "signature over another transaction's id",
         "a required signer's signature removed")
VALID, BAD_SIGNATURE, MISSING_SIGNER = "valid", "signature", "missing_signer"
VERDICTS = (BAD_SIGNATURE, BAD_SIGNATURE, BAD_SIGNATURE, MISSING_SIGNER)


def ledger_seeds(seed: int, n_ledgers: int) -> list[int]:
    """One seed per ledger, derived from ``--seed`` (any whole number up to
    a little over 2**31)."""
    rng = random.Random(f"genledger-oop:{int(seed)}")
    return [rng.getrandbits(48) for _ in range(n_ledgers)]


def pick_invalid(seed: int, n_sigs: list[int], invalid_every: int,
                 first_kind: int) -> dict[int, int]:
    """index -> kind for ``len(n_sigs) // invalid_every`` transactions of
    one ledger, the kinds in rotation from ``first_kind``. Kind 3 needs a
    transaction with two signatures: the draw moves on to the next index
    that has them."""
    rng = random.Random(f"invalid:{seed}")
    order = list(range(len(n_sigs)))
    rng.shuffle(order)
    want = len(n_sigs) // invalid_every if invalid_every else 0
    picked: dict[int, int] = {}
    for k in range(want):
        kind = (first_kind + k) % len(KINDS)
        at = next(j for j, i in enumerate(order)
                  if kind != 3 or n_sigs[i] >= 2)
        picked[order.pop(at)] = kind
    return picked


def make_ledger(job) -> dict:
    """``(ledger seed, transactions, parties, invalid_every, first kind)``
    -> the ledger as the requestor and the reference take it:

    ``stx``    the serialised SignedTransactions, in ledger order, the
               invalid ones as they are sent;
    ``facts``  per transaction ``(component blobs, [(raw key, signature)],
               [required raw keys])``, all bytes;
    ``kinds``  index -> kind of the invalid ones."""
    from corda_tpu.core.crypto.signatures import (Crypto,
                                                  DigitalSignatureWithKey)
    from corda_tpu.core.serialization import serialize
    from corda_tpu.core.transactions.signed import SignedTransaction
    from corda_tpu.testing.generated_ledger import make_generated_ledger

    seed, n_tx, n_parties, invalid_every, first_kind = job
    ledger = make_generated_ledger(n_tx, seed=seed, n_parties=n_parties,
                                   scheme_mix=False)
    txs = list(ledger.transactions)
    kinds = pick_invalid(seed, [len(s.sigs) for s in txs], invalid_every,
                         first_kind)
    keypairs = {kp.public: kp for _party, kp in ledger.parties}
    keypairs[ledger.notary_kp.public] = ledger.notary_kp
    others = [kp for _party, kp in ledger.parties]
    for i, kind in kinds.items():
        stx = txs[i]
        first = stx.sigs[0]
        if kind == 0:
            sigs = (DigitalSignatureWithKey(
                first.bytes[:-1] + bytes([first.bytes[-1] ^ 1]), first.by),
            ) + stx.sigs[1:]
        elif kind == 1:
            other = next(kp for kp in others if kp.public != first.by)
            forged = Crypto.sign_with_key(other, stx.id.bytes)
            sigs = (DigitalSignatureWithKey(forged.bytes, first.by),) \
                + stx.sigs[1:]
        elif kind == 2:
            elsewhere = txs[(i + 1) % len(txs)].id.bytes
            moved = Crypto.sign_with_key(keypairs[first.by], elsewhere)
            sigs = (DigitalSignatureWithKey(moved.bytes, first.by),) \
                + stx.sigs[1:]
        else:
            sigs = stx.sigs[1:]
        txs[i] = SignedTransaction.of(stx.tx, sigs)
    facts = []
    for stx in txs:
        wtx = stx.tx
        facts.append(([serialize(c) for c in wtx.available_components],
                      [(s.by.encoded, s.bytes) for s in stx.sigs],
                      [k.encoded for k in wtx.must_sign]))
    return {"stx": [serialize(stx) for stx in txs], "facts": facts,
            "kinds": kinds}
