"""Plain reference for the ``genledger-ed25519`` deployment: every row's
verdict from the ``cryptography`` package's Ed25519 (OpenSSL), which shares
nothing with the program. Also home of the control that stands in for the
program with one guarantee broken."""
from __future__ import annotations

from reference.crosscash_raft import ed25519_valid


def verdicts(rows) -> list[bool]:
    """``rows``: (raw public key, signature, message) triples."""
    return [ed25519_valid(pub, sig, msg) for pub, sig, msg in rows]


def control_verdicts(rows) -> list[bool]:
    """CONTROL, never the reference: checks every other row and waves the
    rest through, which breaks "every verdict equals the reference"."""
    return [True if i % 2 else ed25519_valid(pub, sig, msg)
            for i, (pub, sig, msg) in enumerate(rows)]
