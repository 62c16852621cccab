"""GeneratedLedger — property-based generation of always-valid ledgers.

Reference parity: verifier/src/integration-test/.../GeneratedLedger.kt:25-190
— the key fixture for bulk verification benchmarking and the device-kernel
parity harness: arbitrarily long chains of issuance/move/exit transitions
over a pool of identities, every transaction correctly signed and
platform-rule-valid, with a notary attached so the chains notarise.
"""
from __future__ import annotations

import random
from dataclasses import dataclass, field

from ..core.contracts.structures import (Command, StateAndRef, StateRef,
                                         TransactionState)
from ..core.crypto.composite import CompositeKey
from ..core.crypto.keys import KeyPair, generate_keypair
from ..core.crypto.schemes import (ECDSA_SECP256K1_SHA256,
                                   EDDSA_ED25519_SHA512)
from ..core.identity import Party
from ..core.transactions.signed import SignedTransaction
from ..core.transactions.wire import WireTransaction
from ..testing.dummy import DummyContract, DummyState
from .generator import Generator


@dataclass(frozen=True)
class CompositeSigner:
    """A CompositeKey identity's signing material: every leaf key pair, and
    the leaves that sign for it (``signing``: they reach the threshold and
    no leaf more). Stands where a ``KeyPair`` stands in ``LedgerState``;
    ``public`` is the CompositeKey."""

    public: CompositeKey
    leaves: tuple[KeyPair, ...]
    signing: tuple[KeyPair, ...]


@dataclass
class LedgerState:
    """Generation-time model of the unspent set. A party's (or the
    notary's) key material is a ``KeyPair``, or a ``CompositeSigner`` where
    its identity is a CompositeKey."""

    parties: list[tuple[Party, KeyPair]]
    notary: Party
    notary_kp: KeyPair
    unspent: list[StateAndRef] = field(default_factory=list)
    transactions: list[SignedTransaction] = field(default_factory=list)
    owners: dict = field(default_factory=dict)   # StateRef -> KeyPair


def composite_party_indices(n_parties: int, composite_parties: int) -> list:
    """Which of ``n_parties`` parties are CompositeKeys: one in each run of
    ``n_parties // composite_parties``, the run's last and last but one in
    turn, so that under ``scheme_mix`` (schemes alternate by index) the
    plain parties stay half and half and so do the trees' first leaves."""
    if not composite_parties:
        return []
    step = n_parties // composite_parties
    return [k * step + (step - 1 if k % 2 else max(0, step - 2))
            for k in range(composite_parties)]


def make_generated_ledger(n_transactions: int, seed: int = 0,
                          n_parties: int = 4,
                          scheme_mix: bool = True,
                          composite_parties: int = 0,
                          nested_composites: int = 0,
                          notary_replicas: int = 0,
                          signer=None) -> LedgerState:
    """Generate `n_transactions` valid signed transactions: ~30% issuances,
    ~55% moves, ~15% exits (shifting to issuance when the unspent set runs
    dry).

    `scheme_mix` alternates the party keys between Ed25519 (even parties)
    and secp256k1 (odd parties): the mixed-scheme batch of BASELINE.json
    configs[1]; without it every party is Ed25519. The notary's key is
    Ed25519 either way. The defaults of the other arguments draw exactly
    that ledger (tests/test_genledger_mixed.py pins a digest):

    `composite_parties` of the parties (``composite_party_indices``) own
    under a CompositeKey over THREE fresh leaf keys whose schemes alternate
    from the party's own (so every tree holds both schemes where
    `scheme_mix`): a flat 2-of-3 with weights 1, but for the first
    `nested_composites` of them, which are nested and weighted: threshold
    3 over leaf A (weight 2) and an inner 1-of-2 over leaves B and C
    (weight 1), `CompositeKeyTests.kt`'s shapes. A composite party's
    transactions carry the leaf signatures that reach its threshold and no
    more (two leaves of a flat tree, drawn per party; A and one of B, C of
    a nested one). `notary_replicas` (0: one plain key) makes the notary a
    cluster identity, a 1-of-n CompositeKey over that many Ed25519 replica
    keys, and every notarised transaction is signed by ONE replica, drawn
    per transaction. `signer(key_pair, content) -> DigitalSignatureWithKey`
    replaces the program's own pure-Python signer (milliseconds a
    signature; low-s ECDSA)."""
    from ..core.crypto.signatures import Crypto
    rng = random.Random(seed)
    sign_one = signer if signer is not None else Crypto.sign_with_key
    schemes = ([EDDSA_ED25519_SHA512, ECDSA_SECP256K1_SHA256] if scheme_mix
               else [EDDSA_ED25519_SHA512])
    composite_at = composite_party_indices(n_parties, composite_parties)
    nested_at = set(composite_at[:nested_composites])
    parties = []
    for i in range(n_parties):
        if i in composite_at:
            leaves = tuple(
                generate_keypair(schemes[(i + j) % len(schemes)],
                                 entropy=rng.randbytes(32))
                for j in range(3))
            a, b, c = (kp.public for kp in leaves)
            if i in nested_at:
                inner = CompositeKey.Builder().add_keys(b, c).build(1)
                key = CompositeKey.Builder().add_key(a, 2) \
                    .add_key(inner, 1).build(3)
                signing = (leaves[0], leaves[1 + rng.randrange(2)])
            else:
                key = CompositeKey.Builder().add_keys(a, b, c).build(2)
                signing = tuple(rng.sample(leaves, 2))
            kp = CompositeSigner(key, leaves, signing)
        else:
            kp = generate_keypair(schemes[i % len(schemes)],
                                  entropy=rng.randbytes(32))
        parties.append((Party(f"O=Gen Party {i}, L=City, C=GB", kp.public), kp))
    if notary_replicas:
        replicas = tuple(generate_keypair(entropy=rng.randbytes(32))
                         for _ in range(notary_replicas))
        notary_kp = CompositeSigner(
            CompositeKey.Builder().add_keys(
                *(kp.public for kp in replicas)).build(1),
            replicas, replicas[:1])
    else:
        notary_kp = generate_keypair(entropy=rng.randbytes(32))
    notary = Party("O=Gen Notary, L=Zurich, C=CH", notary_kp.public)
    ledger = LedgerState(parties, notary, notary_kp)

    party_gen = Generator.choice(range(n_parties))
    magic_gen = Generator.int_range(1, 1 << 30)
    kind_gen = Generator.frequency(
        (0.30, Generator.pure("issue")),
        (0.55, Generator.pure("move")),
        (0.15, Generator.pure("exit")))

    def sign(wtx: WireTransaction, *kps) -> SignedTransaction:
        leaves = [leaf for kp in kps
                  for leaf in (kp.signing if isinstance(kp, CompositeSigner)
                               else (kp,))]
        sigs = [sign_one(kp, wtx.id.bytes) for kp in leaves]
        return SignedTransaction.of(wtx, sigs)

    def notary_signer():
        if not notary_replicas:
            return notary_kp
        return notary_kp.leaves[rng.randrange(notary_replicas)]

    def record(stx: SignedTransaction, owner_kps) -> None:
        ledger.transactions.append(stx)
        for i, out in enumerate(stx.tx.outputs):
            ref = StateRef(stx.id, i)
            ledger.unspent.append(StateAndRef(out, ref))
            ledger.owners[ref] = owner_kps[i]

    for _ in range(n_transactions):
        kind = kind_gen.generate(rng)
        if kind != "issue" and not ledger.unspent:
            kind = "issue"
        if kind == "issue":
            who = party_gen.generate(rng)
            party, kp = parties[who]
            n_out = max(1, Generator.poisson_size(1.5, 4).generate(rng))
            outputs = tuple(
                TransactionState(DummyState(magic_gen.generate(rng),
                                            (party.owning_key,)), notary)
                for _ in range(n_out))
            wtx = WireTransaction(
                outputs=outputs,
                commands=(Command(DummyContract.Create(), (party.owning_key,)),),
                notary=notary, must_sign=(party.owning_key,))
            record(sign(wtx, kp), [kp] * n_out)
        else:
            idx = rng.randrange(len(ledger.unspent))
            sar = ledger.unspent.pop(idx)
            owner_kp = ledger.owners[sar.ref]
            if kind == "move":
                who = party_gen.generate(rng)
                new_party, new_kp = parties[who]
                outputs = (TransactionState(
                    DummyState(sar.state.data.magic_number,
                               (new_party.owning_key,)), notary),)
                owner_kps = [new_kp]
            else:  # exit: consume with no outputs
                outputs = ()
                owner_kps = []
            wtx = WireTransaction(
                inputs=(sar.ref,), outputs=outputs,
                commands=(Command(DummyContract.Move(),
                                  (owner_kp.public,)),),
                notary=notary,
                must_sign=(owner_kp.public, notary.owning_key))
            record(sign(wtx, owner_kp, notary_signer()), owner_kps)
    return ledger


def signature_triples(ledger: LedgerState):
    """Flatten the ledger into (key, signature, content) checks — the raw feed
    for the device signature batcher (the bulk-verification benchmark input)."""
    triples = []
    for stx in ledger.transactions:
        for sig in stx.sigs:
            triples.append((sig.by, sig.bytes, stx.id.bytes))
    return triples
