"""The trader-demo ledger at any size: ``CommercialPaper.generate_redeem``
against ``RedeemClause``, and ``make_trader_book``'s books (the same seed the
same bytes, levels that are topological, every input an output of a level
below). Books are signed by the benchmark's ``cryptography`` signer (the
program's own takes 62 ms a signature); one small book by the program's own
signer and key generation pins that the two give the same bytes but for the
signatures' ``s``."""
import hashlib
import pathlib
import sys
from dataclasses import replace

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
for _p in (str(BENCH), str(BENCH.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import mixed_ledgers  # noqa: E402
import trader_books  # noqa: E402

from corda_tpu.core.contracts.exceptions import (  # noqa: E402
    ContractRejection)
from corda_tpu.core.contracts.structures import (  # noqa: E402
    Command, StateAndRef, StateRef, TimeWindow)
from corda_tpu.core.serialization import serialize  # noqa: E402
from corda_tpu.core.transactions.builder import (  # noqa: E402
    TransactionBuilder)
from corda_tpu.finance.cash import CashState  # noqa: E402
from corda_tpu.finance.commercial_paper import (  # noqa: E402
    CommercialPaper, CommercialPaperState, Redeem)
from corda_tpu.node.services import ResolvedFromWalk  # noqa: E402
from corda_tpu.testing.services import MockServices  # noqa: E402
from corda_tpu.testing.trader_ledger import (  # noqa: E402
    PER_TRADE, TOLERANCE, make_trader_book)


def fast_book(n_trades=8, seed=7, n_banks=6):
    return make_trader_book(n_trades, seed=seed, n_banks=n_banks,
                            signer=mixed_ledgers.make_signer(),
                            keygen=trader_books.make_keygen())


@pytest.fixture(scope="module")
def book():
    return fast_book()


def _out(stx, index):
    return StateAndRef(stx.tx.outputs[index], StateRef(stx.id, index))


def redemption(book, change):
    """Trade 0's redemption, built again by ``generate_redeem`` with
    ``change(builder, deal)`` applied before it is frozen; unsigned: the
    rules read the commands' signers, not the signatures."""
    deal, legs = book.trades[0], book.of_trade(0)
    builder = TransactionBuilder()
    keys = CommercialPaper.generate_redeem(
        builder, _out(legs["trade"], 0),
        [_out(legs["trade"], 1), _out(legs["cash_seller"], 0)])
    assert keys == [deal.seller[0].owning_key]
    builder.set_time_window(TimeWindow(deal.redeemed_at - TOLERANCE,
                                       deal.redeemed_at + TOLERANCE))
    change(builder, deal)
    return builder.to_wire_transaction()


def _before_maturity(builder, deal):
    builder.time_window = TimeWindow(deal.maturity - 3 * TOLERANCE,
                                     deal.maturity - TOLERANCE)


def _under_face_value(builder, deal):
    # a cent from the holder's payment to the issuer's change
    for i, cents in ((0, -1), (1, 1)):
        state = builder.outputs[i]
        amount = state.data.amount
        builder.outputs[i] = replace(state, data=replace(
            state.data, amount=replace(amount,
                                       quantity=amount.quantity + cents)))


def _not_the_holders_signature(builder, deal):
    builder.commands = [
        Command(c.value, (deal.seller[0].owning_key,))
        if isinstance(c.value, Redeem) else c for c in builder.commands]


@pytest.mark.parametrize("change,refused_for", [
    (lambda builder, deal: None, None),
    (_before_maturity, "matured before redemption"),
    (_under_face_value, "pay the face value"),
    (_not_the_holders_signature, "signed by the paper's owner")],
    ids=["valid", "before_maturity", "under_face_value",
         "not_the_holders_signature"])
def test_generate_redeem_against_the_redeem_clause(book, change,
                                                   refused_for):
    wtx = redemption(book, change)
    deal = book.trades[0]
    # the paper and the issuer's cash in; face value to the holder, change
    # to the issuer; Redeem by the holder, Cash.Move by the issuer
    assert len(wtx.inputs) == 3 and len(wtx.outputs) == 2
    paid, rest = (o.data for o in wtx.outputs)
    assert isinstance(paid, CashState) and isinstance(rest, CashState)
    assert not any(isinstance(o.data, CommercialPaperState)
                   for o in wtx.outputs)
    assert paid.owner == deal.buyer[0].owning_key
    assert rest.owner == deal.seller[0].owning_key
    assert paid.amount.quantity + rest.amount.quantity \
        == deal.price + deal.seller_float
    ltx = wtx.to_ledger_transaction(
        ResolvedFromWalk(MockServices(), book.transactions))
    if refused_for is None:
        assert paid.amount.quantity == deal.face
        assert wtx.id == book.of_trade(0)["redeem"].id
        ltx.verify()
        return
    with pytest.raises(ContractRejection, match=refused_for):
        ltx.verify()


def test_generate_redeem_refuses_cash_of_another_token(book):
    legs, other = book.of_trade(0), book.of_trade(1)
    with pytest.raises(ValueError, match="no other cash"):
        CommercialPaper.generate_redeem(
            TransactionBuilder(), _out(legs["trade"], 0),
            [_out(other["cash_seller"], 0)])


def test_the_same_seed_gives_the_same_bytes(book):
    again = fast_book()
    assert [serialize(stx) for stx in again.transactions] \
        == [serialize(stx) for stx in book.transactions]
    other = fast_book(seed=8)
    assert {stx.id for stx in other.transactions}.isdisjoint(
        stx.id for stx in book.transactions)
    digest = hashlib.sha256(b"".join(
        stx.id.bytes for stx in book.transactions)).hexdigest()
    assert len({stx.id for stx in book.transactions}) == 40
    assert digest == hashlib.sha256(b"".join(
        stx.id.bytes for stx in again.transactions)).hexdigest()


def test_the_programs_own_signer_and_keys_give_the_same_transactions():
    """One trade by the program's pure-Python key generation and signer:
    the same ids (so the same keys and components), every signature valid;
    the signatures differ only where the signer normalises ``s``."""
    slow = make_trader_book(1, seed=7, n_banks=3)
    fast = fast_book(1, seed=7, n_banks=3)
    assert [stx.id for stx in slow.transactions] \
        == [stx.id for stx in fast.transactions]
    assert [kp for _party, kp in slow.parties] \
        == [kp for _party, kp in fast.parties]
    services = ResolvedFromWalk(MockServices(), slow.transactions)
    for stx in slow.transactions:
        stx.verify(services)


def test_the_levels_are_topological(book):
    n = len(book.trades)
    assert [len(level) for level in book.levels] == [k * n for k in PER_TRADE]
    assert [sum(len(stx.sigs) for stx in level) for level in book.levels] \
        == [4 * n, 3 * n, 3 * n]
    assert book.transactions == tuple(
        stx for level in book.levels for stx in level)
    below: dict = {}
    for k, level in enumerate(book.levels):
        for stx in level:
            assert (k == 0) == (not stx.tx.inputs)
            for ref in stx.tx.inputs:
                # an output of a level below, never of its own or above
                made_at, n_outputs = below[ref.txhash]
                assert made_at < k and ref.index < n_outputs
        for stx in level:
            below[stx.id] = (k, len(stx.tx.outputs))
    # and no output is spent twice
    spent = [ref for stx in book.transactions for ref in stx.tx.inputs]
    assert len(spent) == len(set(spent)) == 5 * n


def test_every_member_of_a_book_verifies_on_the_host(book):
    services = ResolvedFromWalk(MockServices(), book.transactions)
    for stx in book.transactions:
        stx.verify(services)
    legs = book.of_trade(3)
    deal = book.trades[3]
    assert {len(legs[leg].sigs) for leg in ("cash_buyer", "cash_seller")} \
        == {1}
    assert [len(legs[leg].sigs) for leg in ("paper", "trade", "redeem")] \
        == [2, 3, 3]
    assert book.notary.owning_key in legs["paper"].tx.must_sign
    paper = legs["trade"].tx.outputs[0].data
    assert paper.owner == deal.buyer[0].owning_key
    assert paper.face_value.quantity == deal.face > deal.price


def test_the_references_two_copies_are_one_file():
    here = pathlib.Path(__file__).resolve().parent
    assert (here / "trader_reference.py").read_bytes() \
        == (BENCH / "reference" / "traderdemo_replay.py").read_bytes()
