"""A late joiner resolves a deep back chain (ResolveTransactionsFlowTest.kt's
cases at depth): the joiner's store and record order against the plain
reference, hostile holders refused, the cap, a joiner killed in mid-walk,
and the walk's growth pinned by COUNTS (no wall-clock deadline anywhere)."""
import pathlib
import sys

import pytest

from corda_tpu.core.contracts.amount import USD, Amount
from corda_tpu.core.crypto.signatures import TransactionSignature
from corda_tpu.core.serialization import serialize
from corda_tpu.core.transactions.signed import SignedTransaction
from corda_tpu.finance import CashIssueFlow, CashPaymentFlow
from corda_tpu.flows import FlowException
from corda_tpu.flows import library
from corda_tpu.node.checkpoints import (CheckpointStorage,
                                        FileCheckpointStorage,
                                        KvCheckpointStorage)
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

def test_chain_of_64_is_resolved_whole_and_in_order():
    led = Ledger()
    led.chain(64)
    final = led.pay(led.wallet, led.joiners[0])
    got = recorded(led.joiners[0])
    assert len(got) == 66            # the issue, 64 moves, the payment
    assert ref.judge_join(led.history(), final.id.bytes, got) == CLEAN
    walks = led.registry.meter("Resolve.Walks").count
    assert led.registry.meter("Resolve.Hops").count >= 65
    assert led.registry.meter("Resolve.Recorded").count \
        == led.registry.meter("Resolve.Fetched").count
    assert led.registry.meter("Resolve.Refused").count == 0
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


# -- hostile holders ----------------------------------------------------------------

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
    # pump until the joiner is half way down the chain
    for _ in range(100_000):
        walking = [f for f in joiner.smm.flows.values()
                   if len(f.response_log) >= depth // 2]
        if walking:
            break
        led.net.bus.run_network(rounds=1)
    else:
        raise AssertionError("the joiner never got half way")
    assert recorded(joiner) == []                   # nothing verified yet
    held = joiner.smm.checkpoints.get_all_checkpoints()
    assert len(held) == 1 and len(held[0].response_log) >= depth // 2
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


# -- growth, pinned by counts ----------------------------------------------------------

def test_checkpoint_entries_per_hop_are_bounded_by_a_constant():
    """What a suspension writes (sessions + log entries) does not grow with
    the number of hops behind it: the same small bound at 64, 128, 256."""
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
        assert written.count >= target          # one suspension a hop at least
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
