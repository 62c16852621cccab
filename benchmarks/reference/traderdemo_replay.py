"""Plain reference for the ``traderdemo-replay`` deployment: what a verifier
has to answer for one BOOK of the trader-demo ledger (cash issues, a
commercial paper's issue, its DvP trade against cash, its redemption), judged
in order, from bytes and plain tuples alone. It imports nothing of the program
and nothing else of the benchmark (``tests/trader_reference.py`` is this file
byte for byte: the repo's own copy, for its tests).

A member is judged as ``SignedTransaction.verify`` judges it
(``SignedTransaction.kt:71-100``, ``:174-178``):

- its id is the Merkle root (hashlib) of the SHA-256 of each serialised
  component;
- every (scheme, raw key, signature) is checked over that id by the
  ``cryptography`` package's ECDSA verify over secp256k1, which is
  ``Crypto.doVerify``'s rule: strict DER, ``r`` and ``s`` in ``[1, n-1]``, a
  high ``s`` VALID (``signature``);
- every required key is among the signers (``missing``; every key of this
  deployment is a plain one);
- its inputs resolve from the outputs of the book's EARLIER members and from
  nothing else (``resolution``);
- the platform's rules (``TransactionTypes.kt``: no input twice, every
  command's signers and the inputs' notary among the required keys, a
  time-window only under a notary) and the two contracts' rules, written out
  straight below (``contract``): ``Cash.kt`` and ``CommercialPaper.kt`` as
  their clauses read, state group by state group.

``judge(book)`` returns ``(verified, class)``: the members that passed before
the first that did not, and why that one did not (``valid`` and all of them
where none failed).

A member (``fact``) is a dict of plain values, keys as ``(scheme number, key
encoding)`` pairs::

    blobs     the serialised components, in the id's order
    sigs      [(scheme number, raw key, signature)]
    required  [key]
    inputs    [(transaction id, output index)]
    notary    key | None
    outputs   [state]
    commands  [(name, [key], payload)]      "Cash.Move", "CommercialPaper.Redeem"
    window    (from, until) in epoch microseconds, either None | None

    state     ("cash", (issuer key, issuer reference, currency), quantity,
               owner key)
              ("paper", (issuer key, reference), owner key, face quantity,
               face token as a cash state's, maturity in epoch microseconds)

What the reference takes on trust: that the plain view is the view of those
bytes (it has no decoder of the program's codec; ``blobs`` alone enter the
id)."""
from __future__ import annotations

import functools
import hashlib

VALID, BAD_SIGNATURE, MISSING, CONTRACT, RESOLUTION = \
    "valid", "signature", "missing", "contract", "resolution"
#: ``Crypto.kt``'s scheme number of ECDSA over secp256k1 with SHA-256
SECP256K1 = 2
CASH, PAPER = "cash", "paper"


# -- ids and signatures --------------------------------------------------------

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


def transaction_id(blobs: list[bytes]) -> bytes:
    return merkle_root([hashlib.sha256(b).digest() for b in blobs])


@functools.lru_cache(maxsize=4096)
def _key(pub: bytes):
    from cryptography.hazmat.primitives.asymmetric import ec
    try:
        return ec.EllipticCurvePublicKey.from_encoded_point(ec.SECP256K1(),
                                                            pub)
    except ValueError:
        return None


def signature_valid(scheme: int, pub: bytes, sig: bytes, msg: bytes) -> bool:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    key = _key(pub) if scheme == SECP256K1 else None
    if key is None:
        return False
    try:
        key.verify(sig, msg, ec.ECDSA(hashes.SHA256()))
        return True
    except InvalidSignature:
        return False


# -- the contracts' rules ------------------------------------------------------

def instant(window):
    """The instant a time-sensitive rule reads: the window's midpoint, or its
    one bound."""
    if window is None:
        return None
    lo, hi = window
    if lo is not None and hi is not None:
        return (lo + hi) // 2
    return lo if lo is not None else hi


def grouped(kind: str, inputs, outputs, key) -> list:
    """``[(group key, inputs, outputs)]`` of the states of ``kind``."""
    groups: dict = {}
    for side, states in enumerate((inputs, outputs)):
        for state in states:
            if state[0] == kind:
                groups.setdefault(key(state), ([], []))[side].append(state)
    return [(k, ins, outs) for k, (ins, outs) in groups.items()]


def signers_of(commands, name: str) -> list:
    """The signer sets of the commands called ``name``."""
    return [set(keys) for cmd, keys, _payload in commands if cmd == name]


def cash_accepts(inputs, outputs, commands) -> bool:
    """``Cash.kt``, per (issuer, currency): an issue puts out more than it
    takes in and is signed by the issuer; a move conserves the amount
    (nothing exits in this deployment: a ``Cash.Exit`` is refused) and is
    signed by every input's owner. Cash without a cash command is refused."""
    issues = signers_of(commands, "Cash.Issue")
    moves = signers_of(commands, "Cash.Move")
    if signers_of(commands, "Cash.Exit") or not (issues or moves):
        return False
    for token, ins, outs in grouped(CASH, inputs, outputs, lambda s: s[1]):
        cents_in = sum(s[2] for s in ins)
        cents_out = sum(s[2] for s in outs)
        if issues and not (outs and cents_out > cents_in
                           and all(token[0] in by for by in issues)):
            return False
        if moves and not (cents_in == cents_out and
                          {s[3] for s in ins} <= set().union(*moves)):
            return False
    return True


def paper_accepts(inputs, outputs, commands, window) -> bool:
    """``CommercialPaper.kt``, per (issuance, face value's token, maturity):
    issued by its issuer, out of nothing, for a positive face value, with
    maturity after the window; moved by its owner with nothing else changed;
    redeemed only once matured, signed by its holder, for at least its face
    value paid to the holder in the face value's own cash, and consumed."""
    issues = signers_of(commands, "CommercialPaper.Issue")
    moves = signers_of(commands, "CommercialPaper.Move")
    redeems = signers_of(commands, "CommercialPaper.Redeem")
    if not (issues or moves or redeems):
        return False
    at = instant(window)
    for (issuance, _token, maturity), ins, outs in grouped(
            PAPER, inputs, outputs, lambda s: (s[1], s[4], s[5])):
        if issues and not (not ins and len(outs) == 1 and outs[0][3] > 0
                           and at is not None and maturity > at
                           and issuance[0] in set().union(*issues)):
            return False
        if moves and not (len(ins) == 1 and len(outs) == 1
                          and ins[0][3] == outs[0][3]
                          and ins[0][2] in set().union(*moves)):
            return False
        if redeems:
            if len(ins) != 1 or outs or at is None or at < maturity:
                return False
            _kind, _issuance, holder, face, token, _maturity = ins[0]
            paid = sum(s[2] for s in outputs
                       if s[0] == CASH and s[3] == holder and s[1] == token)
            if paid < face or holder not in set().union(*redeems):
                return False
    return True


def rules_accept(fact, resolved) -> bool:
    """The platform's rules, then each contract a state of the transaction
    belongs to. ``resolved``: ``[(state, its notary)]`` of the inputs."""
    if fact["window"] is not None and fact["notary"] is None:
        return False
    if len(set(fact["inputs"])) != len(fact["inputs"]):
        return False
    needed = {key for _cmd, keys, _payload in fact["commands"]
              for key in keys} | {notary for _state, notary in resolved}
    if len({notary for _state, notary in resolved}) > 1 \
            or not needed <= set(fact["required"]):
        return False
    inputs = [state for state, _notary in resolved]
    kinds = {state[0] for state in inputs + list(fact["outputs"])}
    if CASH in kinds and not cash_accepts(inputs, fact["outputs"],
                                          fact["commands"]):
        return False
    return PAPER not in kinds or paper_accepts(
        inputs, fact["outputs"], fact["commands"], fact["window"])


# -- a book, in order ----------------------------------------------------------

def verdict(fact, made: dict) -> str:
    """One member's class; ``made`` holds the outputs of the members before
    it (id -> (outputs, notary)) and gains this member's."""
    tx_id = transaction_id(fact["blobs"])
    resolved = []
    for from_id, index in fact["inputs"]:
        outputs, notary = made.get(from_id, ((), None))
        resolved.append((outputs[index], notary)
                        if 0 <= index < len(outputs) else None)
    made[tx_id] = (fact["outputs"], fact["notary"])
    if not all(signature_valid(scheme, pub, sig, tx_id)
               for scheme, pub, sig in fact["sigs"]):
        return BAD_SIGNATURE
    signers = {(scheme, pub) for scheme, pub, _sig in fact["sigs"]}
    if not set(fact["required"]) <= signers:
        return MISSING
    if None in resolved:
        return RESOLUTION
    return VALID if rules_accept(fact, resolved) else CONTRACT


def judge(book) -> tuple:
    """``(verified, class)`` of one book (its members' facts, in order)."""
    made: dict = {}
    for verified, fact in enumerate(book):
        found = verdict(fact, made)
        if found != VALID:
            return verified, found
    return len(book), VALID


def judge_all(books) -> list:
    return [judge(book) for book in books]
