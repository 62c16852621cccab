"""A late joiner resolves a deep back chain (ResolveTransactionsFlowTest.kt's
cases at depth): the joiner's store and record order against the plain
reference, hostile holders refused, the cap, a joiner killed in mid-walk,
the holder's pages of ancestors (round trips, budgets, what a reply may
hold), and the walk's growth pinned by COUNTS (no wall-clock deadline
anywhere)."""
import hashlib
import pathlib
import sys

import pytest

from corda_tpu.core.contracts.amount import USD, Amount
from corda_tpu.core.crypto.secure_hash import SecureHash
from corda_tpu.core.crypto.signatures import TransactionSignature
from corda_tpu.core.serialization import serialize
from corda_tpu.core.transactions.signed import SignedTransaction
from corda_tpu.finance import CashIssueFlow, CashPaymentFlow
from corda_tpu.flows import FlowException
from corda_tpu.flows import library
from corda_tpu.node.checkpoints import (CheckpointStorage,
                                        FileCheckpointStorage,
                                        KvCheckpointStorage)
from corda_tpu.node.statemachine import (SessionData, SessionInit,
                                         StateMachineManager)
from corda_tpu.testing import MockNetwork
from corda_tpu.utils.metrics import MetricRegistry

BENCH = str(pathlib.Path(__file__).resolve().parents[1] / "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from reference import crosscash_deepchain as ref  # noqa: E402


def dollars(n):
    return Amount(int(n) * 100, USD)


class Ledger:
    """A validating notary, a bank, a wallet that pays from one coin and
    takes change, a counterparty, and joiners with empty stores."""

    def __init__(self, joiner_storage=None):
        self.net = MockNetwork()
        self.notary = self.net.create_notary_node(validating=True)
        self.bank = self.net.create_node("O=Bank, L=London, C=GB")
        self.wallet = self.net.create_node("O=Wallet, L=Oslo, C=NO")
        self.other = self.net.create_node("O=Other, L=Oslo, C=NO")
        self.joiners = [self.net.create_node(
            f"O=Joiner {i}, L=Oslo, C=NO",
            checkpoint_storage=joiner_storage if i == 0 else None)
            for i in range(3)]
        self.net.start_nodes()
        self.registry = MetricRegistry()
        for node in self.net.nodes:
            node.services.monitoring = self.registry
        self.n_issues = 0

    def run(self, node, flow):
        fsm = node.start_flow(flow)
        self.net.run_network()
        return fsm.result_future.result(timeout=1)

    def issue(self, to, n=1_000_000):
        self.n_issues += 1
        return self.run(self.bank, CashIssueFlow(
            dollars(n), self.n_issues.to_bytes(4, "big"), to.party,
            self.notary.party))

    def pay(self, payer, payee, n=10):
        return self.run(payer, CashPaymentFlow(dollars(n), payee.party))

    def chain(self, depth):
        """One coin, ``depth`` moves: the wallet pays the counterparty and
        spends its own change next time."""
        self.issue(self.wallet)
        for _ in range(depth):
            self.pay(self.wallet, self.other)

    def history(self):
        """The raw history as the reference takes it: every transaction any
        honest store holds (the notary validated all but the newest, and its
        copies are genuine; a tampered wallet's come last)."""
        out = {}
        for node in (self.notary, self.bank, self.other, self.wallet):
            for stx in node.services.storage.transactions:
                out.setdefault(stx.id.bytes, raw_of(stx))
        return out


def raw_of(stx):
    return ref.raw(stx.id.bytes,
                   [h.bytes for h in stx.tx.available_component_hashes],
                   [(r.txhash.bytes, r.index) for r in stx.inputs],
                   [(s.by.encoded, s.bytes) for s in stx.sigs],
                   [o.data.amount.quantity for o in stx.tx.outputs])


def recorded(node):
    return [stx.id.bytes for stx in node.services.storage.transactions]


CLEAN = {"missing": 0, "extra": 0, "recorded_twice": 0, "order_violations": 0,
         "bad_ids": 0, "bad_signatures": 0, "unbalanced": 0}


# -- the joiner's store and record order against the plain reference --------------

def count(registry, name):
    return registry.meter(f"Resolve.{name}").count


def alone(node):
    """A registry of this node's own, from here on: its walks and no other's
    (the notary and the counterparty walk one level at every payment)."""
    node.services.monitoring = MetricRegistry()
    return node.services.monitoring


def test_chain_of_64_is_resolved_whole_and_in_order():
    led = Ledger()
    led.chain(64)
    mine = alone(led.joiners[0])
    final = led.pay(led.wallet, led.joiners[0])
    got = recorded(led.joiners[0])
    assert len(got) == 66            # the issue, 64 moves, the payment
    assert ref.judge_join(led.history(), final.id.bytes, got) == CLEAN
    # the joiner's one walk: 65 levels of ancestry, as ever, in the 7 round
    # trips of 1, 3, 7, 15, 31, 63, 65 transactions held
    assert count(mine, "Walks") == 1
    assert count(mine, "Hops") >= 65
    assert count(mine, "RoundTrips") <= 8
    assert count(mine, "Recorded") == count(mine, "Fetched") == 65
    assert count(mine, "Prefetched") \
        == count(mine, "Fetched") - count(mine, "RoundTrips")
    assert count(mine, "PrefetchUnused") == 0       # its store was empty
    assert count(mine, "Refused") == 0
    depth = mine.histogram("resolve_depth")
    assert depth.count == 1 and depth.snapshot_fields()["max"] == 65
    # every other walk of this ledger went down one level, in one request
    walks = count(led.registry, "Walks")
    assert walks >= 64
    assert count(led.registry, "Hops") == walks \
        == count(led.registry, "RoundTrips")
    assert count(led.registry, "Prefetched") == 0
    assert count(led.registry, "Recorded") == count(led.registry, "Fetched")
    assert led.registry.histogram("resolve_depth").count == walks


def test_diamond_is_resolved_whole_and_in_order():
    """The payment spends the wallet's change of tx1 and the coin tx2 paid
    back, and tx2 itself spends from tx1: two paths to one ancestor."""
    led = Ledger()
    led.issue(led.wallet, 100)
    led.pay(led.wallet, led.other, 60)            # tx1: 60 away, 40 change
    led.pay(led.other, led.wallet, 50)            # tx2: 50 back
    final = led.pay(led.wallet, led.joiners[0], 90)   # needs both coins
    assert len(final.inputs) == 2
    got = recorded(led.joiners[0])
    assert len(got) == 4
    assert ref.judge_join(led.history(), final.id.bytes, got) == CLEAN


def test_many_input_merge_is_resolved_whole_and_in_order():
    led = Ledger()
    for _ in range(8):
        led.issue(led.wallet, 10)
    final = led.pay(led.wallet, led.joiners[0], 80)
    assert len(final.inputs) == 8
    got = recorded(led.joiners[0])
    assert len(got) == 9
    assert ref.judge_join(led.history(), final.id.bytes, got) == CLEAN


# -- the verification of a walk: one park, levels whole and in order -----------------

def serve(led, node, backend):
    """Put ``backend`` behind ``node``'s verifier seam; what it hands back
    is to be shut down (None: the no-service fallback)."""
    from corda_tpu.verifier import (InMemoryTransactionVerifierService,
                                    SignatureBatcher,
                                    TpuTransactionVerifierService)
    from corda_tpu.verifier.out_of_process import (
        OutOfProcessTransactionVerifierService, VerifierWorker)
    if backend == "fallback":
        return None
    if backend == "in_memory":
        svc = InMemoryTransactionVerifierService()
    elif backend == "tpu":
        svc = TpuTransactionVerifierService()
    elif backend == "tpu_bulk":     # every level of two rows is a burst
        batcher = SignatureBatcher(host_crossover=2, max_batch=2)

        def device(bucket, items, reason="full", bctx=None):
            """Host verdicts behind the device route: no kernel."""
            batcher._mark_device(items)
            batcher._resolve(bucket, items, batcher._run_host(items), bctx)

        batcher._dispatch_device = device
        svc = TpuTransactionVerifierService(batcher=batcher)
    else:
        svc = OutOfProcessTransactionVerifierService(node.messaging)
        VerifierWorker(led.net.bus.create_node(
            f"worker-of-{node.party.name.organisation}"),
            str(node.info.address))
        led.net.run_network()           # the worker's handshake
    node.services.verifier_service = svc
    return svc


def shut(*services):
    for svc in services:
        if svc is not None:
            svc.shutdown()


@pytest.mark.parametrize("depths", [(64, 128)])
def test_chain_is_verified_in_one_park_whatever_its_depth(depths):
    """A walk spends ONE suspension in verification (it was one a level),
    so what a walk checkpoints grows with its round trips alone; each level
    still reaches the batcher as a level of two rows, inline on the one
    task's thread; the store is whole and in order."""
    led = Ledger()
    led.issue(led.wallet)
    depth, suspensions = 0, {}
    for target, joiner in zip(depths, led.joiners):
        while depth < target:
            led.pay(led.wallet, led.other)
            depth += 1
        svc = serve(led, joiner, "tpu")
        mine = alone(joiner)
        try:
            final = led.pay(led.wallet, joiner)
        finally:
            shut(svc)
        depth += 1
        got = recorded(joiner)
        assert len(got) == depth + 1
        assert ref.judge_join(led.history(), final.id.bytes, got) == CLEAN
        assert count(mine, "Walks") == 1 == count(mine, "VerifyParks")
        assert count(mine, "Hops") == count(mine, "Recorded") == depth
        rows = svc.batcher.metrics.snapshot()
        # the walk's levels (the issue has one signature, a move two) and
        # the payment itself, a lone ``Verify``
        assert rows["SigBatcher.HostInline"]["count"] == 2 * depth + 1
        assert "SigBatcher.DeviceChecked" not in rows
        waves = svc.metrics.snapshot()
        assert waves["Verifier.WaveTx.held"]["count"] == depth
        assert "Verifier.WaveTx.bulk" not in waves
        written = mine.histogram("checkpoint_entries")
        suspensions[target] = written.count - count(mine, "RoundTrips")
        assert written.snapshot_fields()["max"] <= 6
    # suspensions that are not fetch round trips: a constant, not the depth
    assert suspensions[depths[0]] == suspensions[depths[1]] <= 4


SHAPES = {
    # name -> (build the history, dollars of the joiner's payment,
    #          transactions the joiner ends with)
    "chain": (lambda led: led.chain(20), 10, 22),
    "diamond": (lambda led: (led.issue(led.wallet, 100),
                             led.pay(led.wallet, led.other, 60),
                             led.pay(led.other, led.wallet, 50)), 90, 4),
    "merge": (lambda led: [led.issue(led.wallet, 10) for _ in range(8)],
              80, 9)}


@pytest.mark.parametrize("backend", ["in_memory", "tpu", "tpu_bulk",
                                     "out_of_process"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_walk_resolves_whole_in_one_park_behind_every_backend(shape, backend):
    """The chain, the diamond and the many-input merge through the ordered
    form, behind each service of the seam (the no-service fallback is what
    every other test of this file runs): the same store, the same order."""
    build, dollars_, n_held = SHAPES[shape]
    led = Ledger()
    build(led)
    joiner = led.joiners[0]
    svc = serve(led, joiner, backend)
    mine = alone(joiner)
    try:
        final = led.pay(led.wallet, joiner, dollars_)
    finally:
        shut(svc)
    got = recorded(joiner)
    assert len(got) == n_held
    assert ref.judge_join(led.history(), final.id.bytes, got) == CLEAN
    assert count(mine, "VerifyParks") == count(mine, "Walks") == 1
    assert count(mine, "Recorded") == n_held - 1
    assert sum(s.state.data.amount.quantity for s in
               joiner.services.vault.unconsumed_states()) == dollars_ * 100


def test_a_batch_that_consumes_its_own_outputs_is_recorded_in_order():
    """``record_transactions`` of a whole chain in ONE call: storage keeps
    the order it was handed and the vault walks the batch in that order, so
    the change each move makes is there to be consumed by the next. The
    same vault as one call a transaction."""
    from corda_tpu.node.vault import NodeVaultService
    led = Ledger()
    led.chain(8)
    chain = list(led.wallet.services.storage.transactions)
    whole, one_by_one = (NodeVaultService(led.wallet.services)
                         for _ in range(2))
    updates = whole.notify_all(chain)
    for stx in chain:
        one_by_one.notify_all([stx])
    assert len(updates) == 9
    assert [len(u.consumed) for u in updates] == [0] + [1] * 8
    assert set(whole._unconsumed) == set(one_by_one._unconsumed) \
        == {r for r in led.wallet.services.vault._unconsumed}
    assert list(whole._consumed) == list(one_by_one._consumed)
    assert len(whole._unconsumed) == 1 and len(whole._consumed) == 8
    joiner = led.joiners[0]
    joiner.services.record_transactions(*chain)
    assert recorded(joiner) == [stx.id.bytes for stx in chain]


# -- pages of ancestors: what goes over the wire ------------------------------------------

@pytest.fixture
def wire(monkeypatch):
    """Every session message any node posts, as the object it serialises."""
    sent = []
    post = StateMachineManager._post

    def spy(self, party, message, fsm=None):
        sent.append(message)
        return post(self, party, message, fsm)

    monkeypatch.setattr(StateMachineManager, "_post", spy)
    return sent


def fetches(wire, by=None):
    """(request, reply) of every fetch on the wire, in order; ``by``, where
    given, is the one node whose requests are wanted."""
    replies = {m.recipient_session_id: m.payload for m in wire
               if isinstance(m, SessionData) and isinstance(m.payload, list)}
    return [(m.first_payload, replies[m.initiator_session_id])
            for m in wire if isinstance(m, SessionInit)
            and isinstance(m.first_payload, library.FetchTransactionsRequest)
            and (by is None or m.initiator_party == str(by.party.name))]


def test_a_request_for_no_ancestors_is_on_the_wire_what_it_always_was():
    ids = (SecureHash.sha256(b"a"), SecureHash.sha256(b"b"))
    plain = library.FetchTransactionsRequest(ids)
    assert plain.ancestors == 0
    blob = serialize(plain)
    # the bytes the parent of this protocol wrote for the same request
    assert hashlib.sha256(blob).hexdigest() == \
        "a98c5e9dc365e1cb0bdc94117d0f42a0fac14980a722fb1926d1c2e7c5a956dc"
    from corda_tpu.core.serialization import deserialize
    assert deserialize(blob) == plain
    paged = library.FetchTransactionsRequest(ids, 7)
    assert deserialize(serialize(paged)) == paged != plain


def test_one_level_walk_asks_for_no_ancestors_and_gets_what_it_asked(wire):
    led = Ledger()
    led.chain(3)
    del wire[:]
    # the validating notary holds all but this payment's input (the payee
    # was paid by it, and walks nowhere)
    paid = led.pay(led.wallet, led.other)
    (previous,) = {r.txhash for r in paid.inputs}
    ((request, reply),) = fetches(wire)
    assert request.tx_ids == (previous,) and request.ancestors == 0
    assert [stx.id for stx in reply] == [previous]
    assert count(led.registry, "Prefetched") == 0


def test_walk_doubles_its_budget_and_the_holder_fills_it_nearest_first(wire):
    led = Ledger()
    led.chain(20)
    chain = [stx.id for stx in led.wallet.services.storage.transactions]
    assert len(chain) == 21
    del wire[:]
    mine = alone(led.joiners[0])
    led.pay(led.wallet, led.joiners[0])
    first, *seen = fetches(wire, by=led.joiners[0])
    assert first[0] == library.FetchTransactionsRequest((chain[-1],))
    assert [stx.id for stx in first[1]] == [chain[-1]]
    # 1 held, then 3, 7, 15 and the last 6: each request one id, the budget
    # what the walk held, the reply that id and then its ancestors in order
    assert [request.ancestors for request, _reply in seen] == [1, 3, 7, 15]
    down = chain[::-1]
    at = 1
    for request, reply in seen:
        assert request.tx_ids == (down[at],)
        assert [stx.id for stx in reply] == \
            down[at:at + 1 + request.ancestors]
        at += len(reply)
    assert at == 21
    assert count(mine, "RoundTrips") == 5 and count(mine, "Hops") == 21
    assert count(mine, "Prefetched") == 16


@pytest.mark.parametrize("ancestors", [0, 2, 50])
def test_fetch_flow_alone_takes_a_budget_and_defaults_to_none(ancestors):
    led = Ledger()
    led.chain(6)
    chain = [stx.id for stx in led.wallet.services.storage.transactions]
    flow = library.FetchTransactionsFlow(
        led.wallet.party, chain[-2:], **({"ancestors": ancestors}
                                         if ancestors else {}))
    got = [stx.id for stx in led.run(led.joiners[0], flow)]
    # the two asked for, in the order asked, then what they descend from,
    # nearest first, as far as the budget and the holder's store go
    assert got == chain[-2:] + chain[:-2][::-1][:ancestors]
    assert recorded(led.joiners[0]) == []       # a download records nothing


@pytest.mark.parametrize("page", [2, 3, 4])
def test_small_pages_of_a_wide_and_deep_graph_resolve_whole(page, monkeypatch):
    """Several pages a round, budgets on the later ones, ancestors that a
    page brings while an earlier page's reply has them queued."""
    monkeypatch.setattr(library, "FETCH_PAGE", page)
    led = Ledger()
    for _ in range(3):
        led.issue(led.wallet, 100)
    for _ in range(4):
        led.pay(led.wallet, led.other, 30)        # spends a coin, takes change
        led.pay(led.other, led.wallet, 20)        # and a coin comes back
    mine = alone(led.joiners[0])
    final = led.pay(led.wallet, led.joiners[0], 250)
    assert len(final.inputs) >= 4
    got = recorded(led.joiners[0])
    assert len(got) == 3 + 8 + 1
    assert ref.judge_join(led.history(), final.id.bytes, got) == CLEAN
    assert count(mine, "Recorded") == count(mine, "Fetched") == 11
    assert count(mine, "RoundTrips") >= -(-11 // page)


def test_ancestor_that_an_earlier_page_queued_crosses_the_wire_once(
        wire, monkeypatch):
    """Pages of two. The payment spends C (the change of a move out of P's
    change), an issue, and A (what came back out of P's other output): the
    first page's reply queues P, and the second page, the one id A with
    room for one ancestor, brings P."""
    monkeypatch.setattr(library, "FETCH_PAGE", 2)
    led = Ledger()
    issue = led.issue(led.wallet, 100)
    p = led.pay(led.wallet, led.other, 30)
    c = led.pay(led.wallet, led.other, 5)
    b = led.issue(led.wallet, 100)
    a = led.pay(led.other, led.wallet, 20)
    del wire[:]
    joiner = led.joiners[0]
    mine = alone(joiner)
    final = led.pay(led.wallet, joiner, 185)
    assert [r.txhash for r in final.inputs] == [c.id, b.id, a.id]
    got = recorded(joiner)
    assert ref.judge_join(led.history(), final.id.bytes, got) == CLEAN
    seen = fetches(wire, by=joiner)
    assert [(request.tx_ids, request.ancestors) for request, _r in seen] == [
        ((c.id, b.id), 0), ((a.id,), 1), ((issue.id,), 1)]
    assert [[stx.id for stx in reply] for _r, reply in seen] == [
        [c.id, b.id], [a.id, p.id], [issue.id]]
    assert count(mine, "RoundTrips") == 3 and count(mine, "Hops") == 3
    assert count(mine, "Fetched") == 5 and count(mine, "Prefetched") == 1
    assert count(mine, "PrefetchUnused") == 0


def test_requester_that_holds_the_deep_half_records_nothing_twice():
    led = Ledger()
    led.chain(32)
    joiner = led.joiners[0]
    led.pay(led.wallet, joiner)     # the chain goes on through this payment
    assert len(recorded(joiner)) == 34
    for _ in range(32):
        led.pay(led.wallet, led.other)
    mine = alone(joiner)
    final = led.pay(led.wallet, joiner)
    got = recorded(joiner)
    assert len(got) == 34 + 32 + 1
    assert ref.judge_join(led.history(), final.id.bytes, got) == CLEAN
    # 32 to fetch, in rounds of 1, 2, 4, 8, 16 and then one id with a
    # budget of 31: the one move still missing, and 31 the joiner held
    assert count(mine, "Hops") == 32 == count(mine, "Fetched")
    assert count(mine, "RoundTrips") == 6
    assert count(mine, "PrefetchUnused") == 31
    assert count(mine, "Prefetched") == 26 + 31
    assert count(mine, "PrefetchUnused") <= count(mine, "Fetched")
    assert count(mine, "Recorded") == 32


# -- hostile holders ----------------------------------------------------------------

def stranger(led):
    """A genuine transaction that no walk of the wallet's chain descends
    from, put where the wallet's handler finds it."""
    stx = led.issue(led.other, 5)
    led.wallet.services.storage._txs[stx.id] = stx
    return stx


REPLIES = {
    # name -> what the holder appends in place of the ancestors it owes
    "no_ancestor": lambda owed, asked, odd: owed[:-1] + [odd],
    "over_the_budget": lambda owed, asked, odd: owed + [odd],
    "ancestor_twice": lambda owed, asked, odd: owed[:-1] + owed[:1],
    "requested_again": lambda owed, asked, odd: owed[:-1] + asked[:1],
}


@pytest.mark.parametrize("kind", sorted(REPLIES))
def test_reply_with_anything_but_ancestors_within_the_budget_is_refused(
        kind, monkeypatch):
    led = Ledger()
    led.chain(12)
    odd = stranger(led)
    honest = library._held_ancestors
    sent = []

    def hostile(storage, stxs, budget):
        owed = honest(storage, stxs, budget)
        if len(owed) < 3:           # the first rounds stay honest
            return owed
        sent.append(REPLIES[kind](owed, list(stxs), odd))
        return sent[-1]

    monkeypatch.setattr(library, "_held_ancestors", hostile)
    joiner = led.joiners[0]
    mine = alone(joiner)
    with pytest.raises(FlowException, match={
            "no_ancestor": "no ancestor of what was",
            "over_the_budget": "wrong number of transactions",
            "ancestor_twice": "twice", "requested_again": "twice"}[kind]):
        led.pay(led.wallet, joiner)
    assert len(sent) == 1           # the walk stopped at that reply
    assert recorded(joiner) == []
    assert count(mine, "Refused") == 1 and count(mine, "Recorded") == 0
    assert joiner.services.vault.unconsumed_states() == []


def tamper(holder, tx_id, kind, other_key):
    """The holder's stored copy of one back-chain transaction, made bad."""
    store = holder.services.storage
    genuine = store._txs[tx_id]
    if kind == "withheld":
        del store._txs[tx_id]
        return genuine
    sig = genuine.sigs[0]
    bad = TransactionSignature(bytes([sig.bytes[0] ^ 0xFF]) + sig.bytes[1:],
                               sig.by) if kind == "flipped_signature" \
        else TransactionSignature(sig.bytes, other_key)
    store._txs[tx_id] = SignedTransaction(genuine.tx_bits,
                                          [bad, *genuine.sigs[1:]])
    return genuine


@pytest.mark.parametrize("kind", ["flipped_signature", "wrong_signer_key",
                                  "withheld"])
def test_hostile_chain_is_refused_and_nothing_at_or_below_it_recorded(kind):
    led = Ledger()
    led.chain(12)
    chain = [stx for stx in led.wallet.services.storage.transactions]
    bad_tx = chain[6].id
    tamper(led.wallet, bad_tx, kind, led.other.party.owning_key)
    joiner = led.joiners[0]
    with pytest.raises(FlowException, match="FINAL but could not be delivered"):
        led.pay(led.wallet, joiner)
    got = recorded(joiner)
    assert ref.judge_refusal(led.history(), bad_tx.bytes, got) == 0
    if kind == "withheld":
        assert got == []               # the download failed: nothing verified
    else:
        assert len(got) == 6           # what the bad one descends from, no more
        assert ref.judge_join(led.history(), chain[5].id.bytes, got) == CLEAN
    assert led.registry.meter("Resolve.Refused").count == 1
    assert joiner.services.vault.unconsumed_states() == []


@pytest.mark.parametrize("backend", ["in_memory", "tpu", "out_of_process"])
@pytest.mark.parametrize("kind", ["flipped_signature", "wrong_signer_key"])
def test_hostile_chain_is_refused_alike_behind_every_backend(kind, backend):
    """The test above behind each service of the seam: the six the bad
    transaction descends from are recorded, in order, and nothing at or
    below it, though every level went to the verifier in one request."""
    led = Ledger()
    led.chain(12)
    chain = [stx for stx in led.wallet.services.storage.transactions]
    bad_tx = chain[6].id
    tamper(led.wallet, bad_tx, kind, led.other.party.owning_key)
    joiner = led.joiners[0]
    svc = serve(led, joiner, backend)
    mine = alone(joiner)
    try:
        with pytest.raises(FlowException,
                           match="FINAL but could not be delivered"):
            led.pay(led.wallet, joiner)
    finally:
        shut(svc)
    got = recorded(joiner)
    assert ref.judge_refusal(led.history(), bad_tx.bytes, got) == 0
    assert len(got) == 6
    assert ref.judge_join(led.history(), chain[5].id.bytes, got) == CLEAN
    assert count(mine, "Refused") == 1 == count(mine, "VerifyParks")
    assert count(mine, "Recorded") == 6
    assert joiner.services.vault.unconsumed_states() == []


def test_walk_over_the_cap_is_refused_and_nothing_recorded(monkeypatch):
    monkeypatch.setattr(library, "MAX_RESOLVE_TRANSACTIONS", 20)
    led = Ledger()
    led.chain(19)       # the issue + 19 moves = 20 to fetch: at the cap
    led.pay(led.wallet, led.joiners[0])
    assert len(recorded(led.joiners[0])) == 21
    # one more in the chain (that payment) and the next joiner is over it
    with pytest.raises(FlowException, match="exceeds the 20 limit"):
        led.pay(led.wallet, led.joiners[1])
    assert recorded(led.joiners[1]) == []


# -- a joiner killed and restored in mid-walk ---------------------------------------

def storage_of(kind, tmp_path):
    if kind == "memory":
        return CheckpointStorage()
    if kind == "file":
        return FileCheckpointStorage(str(tmp_path / "ckpts"))
    return KvCheckpointStorage(str(tmp_path / "ckpts.kv"), use_native=False)


@pytest.mark.parametrize("kind", ["memory", "file", "kv"])
def test_joiner_killed_in_mid_walk_finishes_with_the_same_store(kind, tmp_path):
    depth = 40
    led = Ledger(joiner_storage=storage_of(kind, tmp_path))
    led.chain(depth)
    joiner = led.joiners[0]
    fsm = led.wallet.start_flow(CashPaymentFlow(dollars(10), joiner.party))
    # pump until the joiner stands between two fetch rounds: the payment and
    # three replies (1 + 2 + 4 transactions) are in its log, the fourth
    # request is out, and 34 of the chain are still to come
    for _ in range(100_000):
        walking = [f for f in joiner.smm.flows.values()
                   if len(f.response_log) >= 4]
        if walking:
            break
        led.net.bus.run_network(rounds=1)
    else:
        raise AssertionError("the joiner never got that far")
    assert recorded(joiner) == []                   # nothing verified yet
    held = joiner.smm.checkpoints.get_all_checkpoints()
    assert len(held) == 1
    pages = [value for kind_, value in held[0].response_log[1:]
             if kind_ == "data"]
    assert [len(page) for page in pages] == [1, 2, 4]
    assert len(held[0].sessions) <= 3               # ended ones left
    if kind != "memory":        # the restart reads the disk, not the object
        if kind == "kv":
            joiner.smm.checkpoints.close()
        joiner.smm.checkpoints = storage_of(kind, tmp_path)
        reread = joiner.smm.checkpoints.get_all_checkpoints()
        assert [(c.run_id, serialize(c.response_log), c.sessions)
                for c in reread] == \
            [(c.run_id, serialize(c.response_log), c.sessions) for c in held]
    restored = joiner.restart()
    restored.services.monitoring = led.registry
    restored.start()
    assert len(restored.smm.flows) == 1
    led.net.run_network()
    final = fsm.result_future.result(timeout=1)
    got = recorded(restored)
    assert len(got) == depth + 2
    assert ref.judge_join(led.history(), final.id.bytes, got) == CLEAN
    assert restored.smm.checkpoints.get_all_checkpoints() == []
    assert len(restored.services.vault.unconsumed_states()) == 1


class NeverAnswers:
    """A verifier that takes every member and answers none."""

    def __init__(self):
        self.asked = []

    def verify_signed(self, stx, services, check_sufficient_signatures=True):
        from concurrent.futures import Future
        self.asked.append(stx.id)
        return Future()


@pytest.mark.parametrize("kind", ["memory", "file", "kv"])
def test_joiner_killed_in_its_one_verification_park_finishes_with_the_same_store(
        kind, tmp_path):
    """The whole chain is down and handed to the verifier, nothing is
    recorded, and the joiner dies: the restored flow replays its fetches
    from the log, hands the same levels over again and ends with the store
    an undisturbed joiner has."""
    from corda_tpu.flows.api import VerifyMany
    depth = 24
    led = Ledger(joiner_storage=storage_of(kind, tmp_path))
    led.chain(depth)
    joiner = led.joiners[0]
    silent = joiner.services.verifier_service = NeverAnswers()
    fsm = led.wallet.start_flow(CashPaymentFlow(dollars(10), joiner.party))
    for _ in range(100_000):
        if any(isinstance(f.parked_on, VerifyMany)
               for f in joiner.smm.flows.values()):
            break
        led.net.bus.run_network(rounds=1)
    else:
        raise AssertionError("the joiner never got that far")
    assert len(silent.asked) == depth + 1 and recorded(joiner) == []
    assert joiner.smm.awaiting_external == 1
    (held,) = joiner.smm.checkpoints.get_all_checkpoints()
    assert sum(len(value) for what, value in held.response_log[1:]
               if what == "data") == depth + 1
    if kind != "memory":        # the restart reads the disk, not the object
        if kind == "kv":
            joiner.smm.checkpoints.close()
        joiner.smm.checkpoints = storage_of(kind, tmp_path)
    restored = joiner.restart()
    restored.services.monitoring = led.registry
    # the no-service fallback from here on: the restored flow replays its
    # log and verifies the levels in the step that takes it up
    restored.start()
    led.net.run_network()
    final = fsm.result_future.result(timeout=1)
    got = recorded(restored)
    assert len(got) == depth + 2
    assert ref.judge_join(led.history(), final.id.bytes, got) == CLEAN
    assert restored.smm.checkpoints.get_all_checkpoints() == []
    assert len(restored.services.vault.unconsumed_states()) == 1


@pytest.mark.parametrize("backend", ["tpu", "tpu_bulk"])
def test_more_walks_at_once_than_the_pool_has_workers_all_finish(backend):
    """Six walks of one service with four workers, every one of them a task
    that holds its worker from its first level to its last: a task waits
    for nothing that needs another worker, so the six finish."""
    import time
    led = Ledger()
    led.chain(16)
    tip = led.wallet.services.storage.transactions[-1]
    walkers = led.joiners + [led.net.create_node(
        f"O=Walker {i}, L=Oslo, C=NO") for i in range(3)]
    for node in walkers[3:]:
        node.start()
    svc = serve(led, walkers[0], backend)
    assert svc._pool._max_workers == 4
    for node in walkers:
        node.services.verifier_service = svc
    try:
        with svc.batcher._lock:     # a task's first level waits for this
            fsms = [node.start_flow(library.ResolveTransactionsFlow(
                led.wallet.party, tx_ids=[tip.id])) for node in walkers]
            deadline = time.monotonic() + 60
            while sum(n.smm.awaiting_external for n in walkers) < 6:
                led.net.bus.run_network(rounds=1)
                assert time.monotonic() < deadline
            assert svc._pool._work_queue.qsize() == 2   # six tasks, four taken
        deadline = time.monotonic() + 60    # the test's own limit
        while not all(f.result_future.done() for f in fsms):
            for node in walkers:
                node.smm.drain_external()
            led.net.bus.run_network(rounds=1)
            assert time.monotonic() < deadline, "the walks stand still"
    finally:
        shut(svc)
    for node, fsm in zip(walkers, fsms):
        assert len(fsm.result_future.result(timeout=1)) == 17
        assert ref.judge_join(led.history(), tip.id.bytes,
                              recorded(node)) == CLEAN


# -- growth, pinned by counts ----------------------------------------------------------

def test_checkpoint_entries_per_hop_are_bounded_by_a_constant():
    """What a suspension writes (sessions + log entries; a page of
    transactions is one entry) does not grow with the number of hops behind
    it: the same small bound at 64, 128, 256. And the suspensions
    themselves grow as the round trips do, with the logarithm of the
    depth."""
    led = Ledger()
    led.issue(led.wallet)
    depth, worst = 0, {}
    for target, joiner in zip((64, 128, 256), led.joiners):
        while depth < target:
            led.pay(led.wallet, led.other)
            depth += 1
        fresh = MetricRegistry()
        joiner.services.monitoring = fresh      # this joiner's writes alone
        led.pay(led.wallet, joiner)
        depth += 1
        written = fresh.histogram("checkpoint_entries")
        trips = count(fresh, "RoundTrips")
        assert count(fresh, "Hops") == depth
        assert trips <= target.bit_length() + 2  # the walk doubles
        assert written.count >= trips   # one suspension a round trip at least
        worst[target] = written.snapshot_fields()["max"]
        assert len(recorded(joiner)) == depth + 1
    assert worst[64] == worst[128] == worst[256] <= 6


class CountingId:
    """A transaction id that counts how often it is hashed or compared."""
    touched = 0

    def __init__(self, n):
        self.n = n

    def __hash__(self):
        CountingId.touched += 1
        return hash(self.n)

    def __eq__(self, other):
        CountingId.touched += 1
        return self.n == other.n


class StubRef:
    def __init__(self, txhash):
        self.txhash = txhash


class StubTx:
    def __init__(self, n, parents):
        self.id = CountingId(n)
        self.inputs = [StubRef(CountingId(p)) for p in parents]


@pytest.mark.parametrize("shape", ["chain", "two_parents"])
def test_topological_waves_touch_ids_linearly_in_depth(shape):
    def touched(depth):
        parents = (lambda n: [n - 1] if n else []) if shape == "chain" \
            else (lambda n: [p for p in (n - 1, n - 2) if p >= 0])
        # newest first, as a walk fetches them
        txs = {}
        for n in reversed(range(depth)):
            stx = StubTx(n, parents(n))
            txs[stx.id] = stx
        CountingId.touched = 0
        waves = library._topological_waves(txs)
        assert [[s.id.n for s in w] for w in waves] == \
            [[n] for n in range(depth)]
        return CountingId.touched
    counts = {d: touched(d) for d in (64, 128, 256)}
    # twice the depth adds twice as many: no term grows faster than depth
    assert counts[256] - counts[128] <= 2 * (counts[128] - counts[64]) + 16
    assert counts[256] <= 32 * 256


# -- the durable stores write a delta, not the flow's history ----------------------------

def _delta(run_id, log_from, entries, n_sessions=1):
    from corda_tpu.node.checkpoints import Checkpoint, SessionSnapshot
    return Checkpoint(run_id, "mod.Flow", {"peer": "p"}, list(entries),
                      [SessionSnapshot("O=P, L=Oslo, C=NO", 7 + i, None,
                                       "open", [], [], i)
                       for i in range(n_sessions)], log_from=log_from)


@pytest.mark.parametrize("kind", ["file", "kv"])
def test_durable_checkpoint_rewrites_a_bounded_head_and_seals_the_rest(
        kind, tmp_path, monkeypatch):
    from corda_tpu.node import checkpoints
    store = storage_of(kind, tmp_path)
    written = []
    put = type(store)._put
    monkeypatch.setattr(type(store), "_put", lambda self, key, blob: (
        written.append((key, len(blob))), put(self, key, blob))[1])
    for i in range(40):
        store.add_checkpoint(_delta("abc", i, [("data", b"x" * 100 + bytes([i]))]))
    heads = [n for key, n in written if key == "abc"]
    segments = [key for key, _n in written if key != "abc"]
    assert len(heads) == 40 and segments == ["abc.0", "abc.1"]   # each once
    assert max(heads) < 16 * 160 + 400       # a head never carries 16 entries
    assert len(store.get_all_checkpoints()[0].response_log) == 40
    # a segment no head counts (a crash before the head) is ignored and goes
    put(store, "abc.2", b"torn")
    if kind == "kv":
        store.close()
    again = storage_of(kind, tmp_path)
    [cp] = again.get_all_checkpoints()
    assert [bytes(e[1])[-1] for e in cp.response_log] == list(range(40))
    assert cp.log_from == 0 and len(cp.sessions) == 1
    assert "abc.2" not in again._load()
    # a delta that does not start where the held log ends is refused
    with pytest.raises(ValueError):
        again.add_checkpoint(_delta("abc", 41, [("value", None)]))
    with pytest.raises(ValueError):
        again.add_checkpoint(_delta("nobody", 3, [("value", None)]))
    again.add_checkpoint(_delta("abc", 40, [("value", None)], n_sessions=2))
    assert len(again.get_all_checkpoints()[0].sessions) == 2
    again.remove_checkpoint("abc")
    assert again._load() == {} and again.get_all_checkpoints() == []
    assert checkpoints.SEAL_ENTRIES == 16


def test_a_head_written_before_segments_existed_still_loads(tmp_path):
    from corda_tpu.node.checkpoints import FileCheckpointStorage
    (tmp_path / "ckpts").mkdir()
    (tmp_path / "ckpts" / "old.ckpt").write_bytes(serialize([
        "old", "mod.Flow", {"a": 1}, [("value", 1), ("value", 2)],
        [["O=P, L=Oslo, C=NO", 7, None, "open", [], [], 0]]]))
    [cp] = FileCheckpointStorage(str(tmp_path / "ckpts")).get_all_checkpoints()
    assert [e[1] for e in cp.response_log] == [1, 2] and cp.run_id == "old"
