"""The seeded books of the ``traderdemo-replay`` deployment: whole
transactions by the program's own generator of the trader-demo ledger
(``corda_tpu.testing.trader_ledger.make_trader_book``: cash issues, a
commercial paper's issue, its DvP trade, its redemption, three topological
levels), ONE member of some books altered afterwards, and beside each member
what the plain reference needs to judge it without the program
(``reference/traderdemo_replay.py``'s ``fact``: the serialised components its
id is the Merkle root of, its signatures, its required keys, and its inputs,
states, commands and time-window as plain tuples).

THE SIGNER is ``mixed_ledgers.make_signer``'s: the ``cryptography`` package's
ECDSA over secp256k1 with RFC 6979 nonces and NO normalisation of ``s`` (as
BouncyCastle's ``SHA256withECDSA``: about half the signatures carry ``s > n /
2``; the program's own signer normalises to low ``s`` and takes 62-88 ms a
signature in pure Python), and the same package derives the parties' public
keys (the program's own takes 62 ms a key; the bytes are the same). A seed
gives a byte-identical book on any number of cores. ``make_book`` is the job
``ecdsa_pool.parallel_map`` hands to fresh interpreters, which import the
program's core, finance and testing packages and nothing of JAX.

Altered kinds (``KINDS``): a book carries at most one, in ONE member of a
seeded trade; ``CLASSES`` names the class that member has to come back as and
``LEVELS`` the level it stands in. The cell carries the first four (its
configuration's ``altered_kinds``); the rest are the tests':

0. the first signature of a trade with its last byte flipped;
1. a trade whose cash outputs exceed its cash inputs by one cent (the
   seller is paid a cent more), signed again by everyone;
2. a redemption whose time-window lies before maturity, signed again;
3. a trade whose ``CommercialPaper.Move`` names the buyer and not the
   paper's owner as its signer; every NAMED signer's signature is there;
4. a redemption that pays the holder a cent under the face value (the cent
   goes to the issuer's change: cash is conserved);
5. a paper whose ``Issue`` names and carries another party's signature, not
   its issuer's;
6. a trade with a required signature (its second) removed;
7. a redemption whose paper input names an output index its trade does not
   have.
"""
from __future__ import annotations

import functools
import pathlib
import random
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import ecdsa_pool  # noqa: E402  (benchmarks/ecdsa_pool.py)
import mixed_ledgers  # noqa: E402  (the signer)

KINDS = ("a trade's first signature with its last byte flipped",
         "a trade whose cash outputs exceed its cash inputs by one cent",
         "a redemption whose time-window lies before maturity",
         "a trade whose CommercialPaper.Move names the buyer, not the owner",
         "a redemption that pays a cent under the face value",
         "a paper issued under another party's signature",
         "a trade with a required signature removed",
         "a redemption whose paper input index its trade does not have")
VALID, BAD_SIGNATURE, MISSING, CONTRACT, RESOLUTION = \
    "valid", "signature", "missing", "contract", "resolution"
CLASSES = (BAD_SIGNATURE, CONTRACT, CONTRACT, CONTRACT, CONTRACT, CONTRACT,
           MISSING, RESOLUTION)
LEVELS = (1, 1, 2, 1, 2, 0, 1, 2)


def book_seeds(seed: int, n_books: int) -> list[int]:
    """One seed per book, derived from ``--seed`` (any whole number up to a
    little over 2**31)."""
    rng = random.Random(f"traderdemo-replay:{int(seed)}")
    return [rng.getrandbits(48) for _ in range(n_books)]


def make_keygen():
    """``keygen(entropy) -> KeyPair`` for ``make_trader_book``: the key pair
    ``generate_keypair(ECDSA_SECP256K1_SHA256, entropy)`` gives, its public
    point computed by the ``cryptography`` package."""
    from corda_tpu.core.crypto.keys import KeyPair, PrivateKey, PublicKey
    from corda_tpu.core.crypto.schemes import ECDSA_SECP256K1_SHA256 as K1
    order = ecdsa_pool.ORDERS["secp256k1"]

    def keygen(entropy: bytes):
        d = int.from_bytes(entropy, "big") % (order - 1) + 1
        (pub,) = ecdsa_pool.public_keys("secp256k1", [d])
        return KeyPair(PublicKey(K1, pub),
                       PrivateKey(K1, d.to_bytes(32, "big")))

    return keygen


def position(n_trades: int, level: int, trade: int) -> int:
    """Where, in a book's order, ``trade``'s member of ``level`` stands
    (its paper, at level 0)."""
    return (3 * trade + 2, 3 * n_trades + trade, 4 * n_trades + trade)[level]


def rewritten(book, stx, **changes):
    """``stx``'s transaction with some components replaced, signed by every
    key it then requires."""
    from corda_tpu.core.transactions.signed import SignedTransaction
    from corda_tpu.core.transactions.wire import WireTransaction
    wtx = stx.tx
    parts = {name: getattr(wtx, name)
             for name in ("inputs", "attachments", "outputs", "commands",
                          "notary", "must_sign", "type", "time_window")}
    parts.update(changes)
    new = WireTransaction(**parts)
    return SignedTransaction.of(
        new, [book.sign(book.key_pairs[key], new.id.bytes)
              for key in new.must_sign])


def altered(book, kind: int, trade: int):
    """``(level, the altered member)``: ``trade``'s member of
    ``LEVELS[kind]``, made invalid in the way ``KINDS[kind]`` says."""
    from dataclasses import replace
    from corda_tpu.core.contracts.structures import (Command, StateRef,
                                                     TimeWindow)
    from corda_tpu.core.crypto.signatures import DigitalSignatureWithKey
    from corda_tpu.core.transactions.signed import SignedTransaction
    from corda_tpu.finance.commercial_paper import CommercialPaperState
    from corda_tpu.testing.trader_ledger import TOLERANCE
    deal, legs = book.trades[trade], book.of_trade(trade)
    stx = legs[("paper", "trade", "redeem")[LEVELS[kind]]]
    wtx = stx.tx

    def paid(outputs, owner, cents):
        """``outputs`` with ``cents`` more in ``owner``'s cash state."""
        outputs = list(outputs)
        at = next(i for i, o in enumerate(outputs)
                  if not isinstance(o.data, CommercialPaperState)
                  and o.data.owner == owner)
        amount = outputs[at].data.amount
        outputs[at] = replace(outputs[at], data=replace(
            outputs[at].data,
            amount=replace(amount, quantity=amount.quantity + cents)))
        return tuple(outputs)

    def named(commands, command_type, *keys):
        """``commands`` with the one of ``command_type`` signed by ``keys``
        alone, and what the transaction then requires."""
        commands = tuple(Command(c.value, keys)
                         if isinstance(c.value, command_type) else c
                         for c in commands)
        required = {k for c in commands for k in c.signers} \
            | {book.notary.owning_key}
        return {"commands": commands, "must_sign": tuple(sorted(required))}

    seller, buyer = deal.seller[0].owning_key, deal.buyer[0].owning_key
    if kind == 0:
        first = stx.sigs[0]
        new = SignedTransaction.of(wtx, (DigitalSignatureWithKey(
            first.bytes[:-1] + bytes([first.bytes[-1] ^ 1]), first.by),
        ) + stx.sigs[1:])
    elif kind == 1:
        new = rewritten(book, stx, outputs=paid(wtx.outputs, seller, 1))
    elif kind == 2:
        early = deal.maturity - 2 * TOLERANCE
        new = rewritten(book, stx, time_window=TimeWindow(
            early - TOLERANCE, early + TOLERANCE))
    elif kind == 3:
        from corda_tpu.finance.commercial_paper import Move
        new = rewritten(book, stx, **named(wtx.commands, Move, buyer))
    elif kind == 4:
        new = rewritten(book, stx, outputs=paid(
            paid(wtx.outputs, buyer, -1), seller, 1))
    elif kind == 5:
        from corda_tpu.finance.commercial_paper import Issue
        new = rewritten(book, stx, **named(wtx.commands, Issue, buyer))
    elif kind == 6:
        new = SignedTransaction.of(wtx, stx.sigs[:1] + stx.sigs[2:])
    else:
        paper_from = legs["trade"].id
        new = rewritten(book, stx, inputs=tuple(
            StateRef(ref.txhash, 7)
            if ref.txhash == paper_from and ref.index == 0 else ref
            for ref in wtx.inputs))
    return LEVELS[kind], new


def plain_key(key) -> tuple:
    return (key.scheme.scheme_number_id, key.encoded)


def plain_state(state) -> tuple:
    from corda_tpu.finance.cash import CashState

    def token(issued):
        return (plain_key(issued.issuer.party.owning_key),
                issued.issuer.reference, issued.product.code)

    if isinstance(state, CashState):
        return ("cash", token(state.amount.token), state.amount.quantity,
                plain_key(state.owner))
    return ("paper", (plain_key(state.issuance.party.owning_key),
                      state.issuance.reference), plain_key(state.owner),
            state.face_value.quantity, token(state.face_value.token),
            state.maturity_micros)


@functools.cache
def command_names() -> dict:
    """A command's class -> the name the plain reference knows it by."""
    from corda_tpu.finance import cash, commercial_paper as paper
    return {cash.Issue: "Cash.Issue", cash.Move: "Cash.Move",
            cash.Exit: "Cash.Exit", paper.Issue: "CommercialPaper.Issue",
            paper.Move: "CommercialPaper.Move",
            paper.Redeem: "CommercialPaper.Redeem"}


def fact_of(stx) -> dict:
    """One member as the plain reference takes it."""
    from corda_tpu.core.serialization import serialize
    names = command_names()
    wtx = stx.tx
    window = wtx.time_window
    return {
        "blobs": [serialize(c) for c in wtx.available_components],
        "sigs": [(*plain_key(s.by), s.bytes) for s in stx.sigs],
        "required": [plain_key(k) for k in wtx.must_sign],
        "inputs": [(ref.txhash.bytes, ref.index) for ref in wtx.inputs],
        "notary": None if wtx.notary is None
        else plain_key(wtx.notary.owning_key),
        "outputs": [plain_state(o.data) for o in wtx.outputs],
        "commands": [(names[type(c.value)],
                      [plain_key(k) for k in c.signers], None)
                     for c in wtx.commands],
        "window": None if window is None
        else (window.from_time, window.until_time)}


def make_book(job) -> dict:
    """``(book seed, trades, banks, altered kind | None)`` -> the book as
    the driver and the reference take it:

    ``levels``   the serialised SignedTransactions, level by level, the
                 altered one as it is handed over;
    ``facts``    one ``fact`` a member, in the levels' order;
    ``kind``     the altered kind, or None;
    ``expect``   ``(verified, class)``: the members before the altered one
                 and its class, or ``(all of them, "valid")``."""
    from corda_tpu.core.serialization import serialize
    from corda_tpu.testing.trader_ledger import make_trader_book
    seed, n_trades, n_banks, kind = job
    book = make_trader_book(n_trades, seed=seed, n_banks=n_banks,
                            signer=mixed_ledgers.make_signer(),
                            keygen=make_keygen())
    levels = [list(level) for level in book.levels]
    expect = (len(book.transactions), VALID)
    if kind is not None:
        trade = random.Random(f"altered:{seed}").randrange(n_trades)
        level, member = altered(book, kind, trade)
        at = position(n_trades, level, trade)
        levels[level][at - sum(len(lv) for lv in levels[:level])] = member
        expect = (at, CLASSES[kind])
    return {"levels": [[serialize(stx) for stx in level]
                       for level in levels],
            "facts": [fact_of(stx) for level in levels for stx in level],
            "kind": kind, "expect": expect}
