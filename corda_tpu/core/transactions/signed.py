"""SignedTransaction — serialized wire bytes + signatures over the id.

Reference parity: SignedTransaction.kt — checkSignaturesAreValid (:96-100) verifies
each signature cryptographically against the id; verifySignatures (:71-85) then
checks the *coverage* of required keys (CompositeKey thresholds included), with an
``allowed_to_be_missing`` escape for counterparties collecting signatures.

The TPU path batches the per-signature EC verifications of MANY transactions into
one device call (the north-star seam); coverage checking stays host-side.
"""
from __future__ import annotations

from functools import cached_property

from ..crypto.composite import CompositeKey
from ..crypto.keys import PublicKey
from ..crypto.secure_hash import SecureHash
from ..crypto.signatures import DigitalSignatureWithKey, SignatureException
from ..serialization import deserialize, register_type
from .wire import WireTransaction


class SignaturesMissingException(SignatureException):
    def __init__(self, missing: set[PublicKey], descriptions: list[str], id: SecureHash):
        super().__init__(f"Missing signatures for {descriptions} on transaction "
                         f"{id.prefix_chars()}")
        self.missing = missing
        self.id = id


class SignedTransaction:
    def __init__(self, tx_bits: bytes, sigs: tuple[DigitalSignatureWithKey, ...]):
        if not sigs:
            raise ValueError("Tried to instantiate a SignedTransaction without signatures")
        self.tx_bits = bytes(tx_bits)
        self.sigs = tuple(sigs)

    @staticmethod
    def of(wtx: WireTransaction, sigs) -> "SignedTransaction":
        stx = SignedTransaction(wtx.serialized, tuple(sigs))
        stx.__dict__["tx"] = wtx  # prime the cache; avoids a deserialize round-trip
        return stx

    @cached_property
    def tx(self) -> WireTransaction:
        wtx = deserialize(self.tx_bits)
        if not isinstance(wtx, WireTransaction):
            raise ValueError("tx_bits do not contain a WireTransaction")
        return wtx

    @property
    def id(self) -> SecureHash:
        return self.tx.id

    @property
    def inputs(self):
        return self.tx.inputs

    @property
    def notary(self):
        return self.tx.notary

    # -- signature checking -------------------------------------------------
    def check_signatures_are_valid(self) -> None:
        """Cryptographically verify every attached signature against the id.
        Does NOT check coverage (SignedTransaction.kt:96-100)."""
        for sig in self.sigs:
            sig.verify(self.id.bytes)

    def verify_signatures(self, *allowed_to_be_missing: PublicKey) -> set[PublicKey]:
        """Full check: all sigs valid AND every required key fulfilled, except those
        explicitly allowed to be missing. Returns the missing set."""
        self.check_signatures_are_valid()
        missing = self.get_missing_signatures()
        if missing:
            allowed = set(allowed_to_be_missing)
            needed = missing - allowed
            if needed:
                raise SignaturesMissingException(
                    needed, [k.to_string_short() for k in needed], self.id)
        return missing

    def get_missing_signatures(self, tally: list | None = None
                               ) -> set[PublicKey]:
        """The required keys the signers' set does not fulfil (a
        CompositeKey: by its weighted thresholds). ``tally``, where given,
        is ``[required keys, of them CompositeKeys, leaf keys looked up in
        the composite walks]`` and is added to (the verifier service's
        coverage pass meters it)."""
        sig_keys = {s.by for s in self.sigs}
        if tally is None:
            return {k for k in self.tx.must_sign
                    if not k.is_fulfilled_by(sig_keys)}
        missing = set()
        for k in self.tx.must_sign:
            tally[0] += 1
            if isinstance(k, CompositeKey):
                tally[1] += 1
                fulfilled = k.is_fulfilled_by(sig_keys, tally)
            else:
                fulfilled = k.is_fulfilled_by(sig_keys)
            if not fulfilled:
                missing.add(k)
        return missing

    # -- combination --------------------------------------------------------
    def plus(self, *sigs: DigitalSignatureWithKey) -> "SignedTransaction":
        combined = self.sigs + tuple(s for s in sigs if s not in self.sigs)
        stx = SignedTransaction(self.tx_bits, combined)
        if "tx" in self.__dict__:
            stx.__dict__["tx"] = self.__dict__["tx"]
        return stx

    def with_additional_signature(self, sig: DigitalSignatureWithKey) -> "SignedTransaction":
        return self.plus(sig)

    # -- resolution / full verify -------------------------------------------
    def to_ledger_transaction(self, services):
        return self.tx.to_ledger_transaction(services)

    def verify(self, services, check_sufficient_signatures: bool = True) -> None:
        """Synchronous host verify (SignedTransaction.kt:174-178): signatures, then
        resolution, then contract/platform rules."""
        if check_sufficient_signatures:
            self.verify_signatures()
        else:
            self.check_signatures_are_valid()
        self.to_ledger_transaction(services).verify()

    def __eq__(self, other):
        return (isinstance(other, SignedTransaction)
                and self.id == other.id and self.sigs == other.sigs)

    def __hash__(self):
        return hash((self.id, self.sigs))

    def __repr__(self):
        return f"SignedTransaction(id={self.id.prefix_chars()}, {len(self.sigs)} sigs)"


register_type("SignedTransaction", SignedTransaction,
              to_fields=lambda s: [s.tx_bits, list(s.sigs)],
              from_fields=lambda f: SignedTransaction(f[0], tuple(f[1])))
