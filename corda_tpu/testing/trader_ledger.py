"""The trader-demo ledger at any size: books of issue, DvP trade and
redemption, built WITHOUT flows by the contracts' own ``generate_*`` helpers.

Reference parity: samples/trader-demo (``TraderDemoClientApi.kt``: a bank
issues cash to the buyer, the seller issues itself commercial paper,
``TwoPartyTradeFlow`` settles paper against cash in one transaction signed by
buyer, seller and notary) and ``CommercialPaperTests.kt``'s lifecycle, which
goes on to REDEEM the paper (both named from memory). The repo's sample
(``samples/trader_demo.py``) trades once through flows on a ``MockNetwork``; a
thousand notarised trades do not fit a test or a benchmark's set-up, so a book
is what those flows would have left on the ledger, transaction for
transaction, and nothing of how it got there.

One TRADE is five transactions:

(a) the bank issues cash to the buyer and, in a transaction of its own, to
    the seller: one signature each, the bank's. The seller's is the FLOAT its
    redemption pays from, since a paper sells under its face value;
(b) the seller issues itself a commercial paper under a time-window (seller,
    notary); its face value is in the bank's cash (``TraderDemoClientApi.kt``
    issues ``1100.DOLLARS `issuedBy` DUMMY_CASH_ISSUER``);
(c) the DvP trade: the paper and the buyer's cash in; cash to the seller,
    change to the buyer, the paper to the buyer (buyer, seller, notary);
(d) the redemption, in a time-window after maturity: the paper and the
    issuer's cash (its proceeds of (c) and its float of (a)) in; the face
    value to the holder, change to the issuer (issuer, holder, notary).

A BOOK is ``n_trades`` of them and its topological LEVELS: level 0 every (a)
and (b) (three transactions a trade, trade by trade), level 1 every (c),
level 2 every (d): what ``ResolveTransactionsFlow`` would hand the verifier
as one ordered ``VerifyMany``.

Departures from the demo, each the port's or this file's: one cash state a
buyer where the demo's bank issues several of random size; a bank reference a
trade (identical issues would be ONE transaction: there is no privacy salt in
v0.14's wire format); no attachment on the paper; no time-window on the trade
(``finance/trade.py`` sets none); the notary's key named among an issue's
required signers by hand, where ``TransactionBuilder.kt``'s ``addTimeWindow``
adds it (the port's ``set_time_window`` does not).
"""
from __future__ import annotations

import random
from dataclasses import dataclass

from ..core.contracts.amount import USD, Amount
from ..core.contracts.structures import (Issued, PartyAndReference,
                                         StateAndRef, StateRef, TimeWindow)
from ..core.crypto.keys import KeyPair, generate_keypair
from ..core.crypto.schemes import ECDSA_SECP256K1_SHA256
from ..core.identity import Party
from ..core.transactions.builder import TransactionBuilder
from ..core.transactions.signed import SignedTransaction
from ..finance.cash import Cash
from ..finance.commercial_paper import CommercialPaper

#: 2017-06-01T00:00:00Z, the month v0.14 was cut: a book's clock starts here
EPOCH_MICROS = 1_496_275_200_000_000
SECOND, DAY = 1_000_000, 86_400_000_000
#: half the width of a time-window (``TwoPartyTradeFlow.kt``: 30 seconds)
TOLERANCE = 30 * SECOND
MATURITY = 30 * DAY
#: transactions a trade, by level
PER_TRADE = (3, 1, 1)


@dataclass(frozen=True)
class Trade:
    """One trade's parties (``(Party, KeyPair)`` each), its amounts in cents
    and its clock in epoch microseconds."""

    index: int
    bank: tuple
    buyer: tuple
    seller: tuple
    price: int
    face: int
    buyer_cash: int
    seller_float: int
    issued_at: int

    @property
    def maturity(self) -> int:
        return self.issued_at + MATURITY

    @property
    def redeemed_at(self) -> int:
        return self.maturity + DAY


@dataclass(frozen=True)
class TraderBook:
    """``transactions`` in the order of the levels; ``levels`` as tuples of
    them; the trades they came from; every key pair by its public key (the
    notary's too), and the signer that signed them."""

    transactions: tuple
    levels: tuple
    trades: tuple
    parties: tuple
    notary: Party
    key_pairs: dict
    sign: object

    def of_trade(self, index: int) -> dict:
        """One trade's five transactions by leg."""
        l0, l1, l2 = self.levels
        cash_buyer, cash_seller, paper = l0[3 * index:3 * index + 3]
        return {"cash_buyer": cash_buyer, "cash_seller": cash_seller,
                "paper": paper, "trade": l1[index], "redeem": l2[index]}


def _out(stx: SignedTransaction, index: int) -> StateAndRef:
    return StateAndRef(stx.tx.outputs[index], StateRef(stx.id, index))


def make_trader_book(n_trades: int, seed: int = 0, signer=None,
                     n_banks: int = 64, keygen=None) -> TraderBook:
    """``n_trades`` trades among ``n_banks`` parties (every trade draws its
    bank, buyer and seller from them, all different) and one notary, every
    identity secp256k1. The same arguments give the same bytes.

    ``signer(key_pair, content) -> DigitalSignatureWithKey`` replaces the
    program's own pure-Python signer (62 ms a signature; low-s ECDSA), as
    ``make_generated_ledger``'s does; ``keygen(entropy) -> KeyPair`` the
    program's key generation (62 ms a key), and has to give the key pair
    ``generate_keypair(ECDSA_SECP256K1_SHA256, entropy)`` gives."""
    from ..core.crypto.signatures import Crypto
    if n_banks < 3:
        raise ValueError("a trade takes a bank, a buyer and a seller")
    rng = random.Random(f"trader-book:{int(seed)}")
    sign_one = signer if signer is not None else Crypto.sign_with_key
    if keygen is None:
        def keygen(entropy):
            return generate_keypair(ECDSA_SECP256K1_SHA256, entropy=entropy)
    parties = []
    for i in range(n_banks):
        kp = keygen(rng.randbytes(32))
        parties.append((Party(f"O=Bank {i}, L=London, C=GB", kp.public), kp))
    notary_kp = keygen(rng.randbytes(32))
    notary = Party("O=Notary Service, L=Zurich, C=CH", notary_kp.public)
    key_pairs = {kp.public: kp for _party, kp in parties}
    key_pairs[notary.owning_key] = notary_kp

    def signed(builder: TransactionBuilder) -> SignedTransaction:
        wtx = builder.to_wire_transaction()
        return SignedTransaction.of(
            wtx, [sign_one(key_pairs[key], wtx.id.bytes)
                  for key in wtx.must_sign])

    def cash_issue(deal: Trade, to: Party, cents: int) -> SignedTransaction:
        bank = deal.bank[0]
        builder = TransactionBuilder(notary=notary)
        Cash.generate_issue(
            builder, Amount(cents, USD),
            PartyAndReference(bank, deal.index.to_bytes(4, "big")),
            to.owning_key, notary)
        return signed(builder)

    trades, levels = [], ([], [], [])
    for index in range(n_trades):
        bank, buyer, seller = rng.sample(parties, 3)
        price = 100_000 + rng.randrange(10_000)         # $1,000 and a bit
        face = price + price // 10                      # the demo's 10%
        deal = Trade(index, bank, buyer, seller, price, face,
                     buyer_cash=price + 1 + rng.randrange(20_000),
                     seller_float=face - price + 1 + rng.randrange(20_000),
                     issued_at=EPOCH_MICROS + index * SECOND)
        trades.append(deal)
        # (a) the bank funds both sides
        cash_buyer = cash_issue(deal, buyer[0], deal.buyer_cash)
        cash_seller = cash_issue(deal, seller[0], deal.seller_float)
        # (b) the seller issues itself the paper, in the bank's cash
        issuance = PartyAndReference(seller[0], b"\x01")
        builder = TransactionBuilder(notary=notary)
        CommercialPaper.generate_issue(
            builder, issuance,
            Amount(face, Issued(PartyAndReference(
                bank[0], index.to_bytes(4, "big")), USD)),
            deal.maturity, notary)
        builder.set_time_window(TimeWindow(deal.issued_at - TOLERANCE,
                                           deal.issued_at + TOLERANCE))
        builder.signers.add(notary.owning_key)  # addTimeWindow's, see above
        paper = signed(builder)
        levels[0].extend((cash_buyer, cash_seller, paper))
        # (c) paper against cash, as finance/trade.py's buyer assembles it
        builder = TransactionBuilder()
        CommercialPaper.generate_move(builder, _out(paper, 0),
                                      buyer[0].owning_key)
        Cash.generate_spend(builder, Amount(price, USD),
                            seller[0].owning_key, [_out(cash_buyer, 0)],
                            change_owner=buyer[0].owning_key)
        trade = signed(builder)
        levels[1].append(trade)
        # (d) the holder redeems; the issuer pays out of proceeds and float.
        # Outputs of (c): the paper, the seller's cash, the buyer's change.
        builder = TransactionBuilder()
        CommercialPaper.generate_redeem(
            builder, _out(trade, 0), [_out(trade, 1), _out(cash_seller, 0)])
        builder.set_time_window(TimeWindow(deal.redeemed_at - TOLERANCE,
                                           deal.redeemed_at + TOLERANCE))
        levels[2].append(signed(builder))
    levels = tuple(tuple(level) for level in levels)
    return TraderBook(tuple(stx for level in levels for stx in level),
                      levels, tuple(trades), tuple(parties), notary,
                      key_pairs, sign_one)
