"""Driver ``latejoin``: parties with empty stores are paid, one after another,
out of a coin with a long history, and each has to fetch, verify and record
the coin's whole back chain before it acknowledges.

The deployment is ``crosscash-raft``'s cluster (``ledger.Deployment``, used
as it is) plus a hot wallet, a hostile wallet and the joiners. Set-up builds
the history (``build_history``: the issue and the last ``history_flow_moves``
moves through the program's own flows, the moves before them with the
program's own ``TransactionBuilder``, recorded into the holders' stores and
committed through the notary's own commit path), then runs a warm-up of
``warmup_ops`` joins and one hostile join per kind in ``hostile_kinds``, every
one of which has to be refused. The window is an open loop of joins, one
fresh joiner per op, evenly spaced at ``rate_tx_per_s``; an op is
``CashPaymentFlow(pay_dollars, joiner)`` started at the wallet, timed from its
intended send to the initiator's acknowledgement, which ``FinalityFlow``
gives only after the joiner has resolved, verified, recorded and sent its
``ack``. Then the queue is drained and the guarantees are checked, over ALL
joins of the warm-up and the window, by the plain reference
(``reference/crosscash_deepchain.py``).

The loop BLOCKS when nothing is runnable: every node's ``scheduler_poke`` sets
one event and the loop waits on it; it does not spin as ``ledger.Loop.drive``
does. The payment path sends the chip nothing at default routing, so the one
device call of a run is part of ``correct``, after the drain: the ids of one
joiner's whole store recomputed by the program's device Merkle kernel.

Control (``--control unchecked_backchain``, has to come out ``correct: false``):
the joiners' verifier checks the contract rules and waves every signature
through, so the hostile chains with a bad signature are accepted.
"""
from __future__ import annotations

import gc
import importlib.util
import pathlib
import threading
import time

from bench_common import check_device_path, nearest_rank as quantile
from reference import crosscash_deepchain as ref  # benchmarks/reference/

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_ledger", pathlib.Path(__file__).with_name("ledger.py"))
ledger = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ledger)

CONTROL = "unchecked_backchain"


# -- the deployment --------------------------------------------------------------

class _WithoutControl:
    """The run's context as ``ledger.Deployment`` may see it: this driver's
    control is not one of the ledger's."""

    def __init__(self, ctx):
        self._ctx = ctx
        self.control = None

    def __getattr__(self, name):
        return getattr(self._ctx, name)


class Deployment(ledger.Deployment):
    """``crosscash-raft``'s cluster, a hot wallet, a hostile wallet, joiners."""

    def __init__(self, ctx):
        if ctx.control not in (None, CONTROL):
            raise ValueError(f"driver latejoin has no control {ctx.control!r}")
        super().__init__(_WithoutControl(ctx))
        # the loop sleeps on this; whatever makes a flow runnable from
        # another thread (a verdict, a commit, a scheduler's next launch)
        # goes through a node's _post_external, which pokes
        self.wake = threading.Event()
        for node in self.network.nodes:
            node.smm.scheduler_poke = self.wake.set
        self.node_concurrency = int(ctx.param("node_concurrency"))
        one_coin = int(ctx.param("wallet_concurrency"))
        self.wallet = self.add_node("O=Hot Wallet, L=Bergen, C=NO", one_coin)
        self.hostile_wallet = self.add_node(
            "O=Hostile Wallet, L=Bergen, C=NO", one_coin)
        self.unchecked = ref.UncheckedVerifier() \
            if ctx.control == CONTROL else None
        self.joiners: list = []

    def add_node(self, name: str, concurrency: int):
        from corda_tpu.node.statemachine import FlowScheduler
        node = self.network.create_node(name)
        node.start()
        node.services.monitoring = self.registry
        node.services.verifier_service = self.verifier
        node.smm.scheduler_poke = self.wake.set
        self.schedulers[str(node.info.address)] = FlowScheduler(
            node.smm, concurrency)
        return node

    def fresh_joiner(self):
        """A party with an empty store and vault."""
        node = self.add_node(f"O=Joiner {len(self.joiners)}, L=Tromso, C=NO",
                             self.node_concurrency)
        if self.unchecked is not None:
            node.services.verifier_service = self.unchecked
        self.joiners.append(node)
        return node


# -- the loop ------------------------------------------------------------------------

#: ``ledger.Op`` with the NODES themselves as initiator and counterparty
Op = ledger.Op


class Loop(ledger.Loop):
    """The ledger's launch and sweep, over ops that name their nodes, under
    a loop that blocks."""

    def _node_for(self, op):
        return op.initiator

    def _make_flow(self, op, node):
        from corda_tpu.finance import CashIssueFlow, CashPaymentFlow
        if op.kind == "issue":
            return CashIssueFlow(self._dollars(self.p("issue_dollars")),
                                 op.seq.to_bytes(4, "big"),
                                 op.counterparty.party, self.dep.notary.party)
        return CashPaymentFlow(self._dollars(self.p("pay_dollars")),
                               op.counterparty.party)

    def drive(self, ops, offer_s, drain_limit_s) -> dict:
        """``ledger.Loop.drive``'s schedule and bookkeeping; where that loop
        spins, this one waits for a poke (or the next intended send)."""
        dep, ctx = self.dep, self.ctx
        bus, wake = dep.network.bus, dep.wake
        inflight: list = []
        next_i = 0
        t0 = time.monotonic()
        last_offer = ops[-1].intended_s if ops else 0.0
        hard_stop = (offer_s if offer_s is not None else last_offer) \
            + drain_limit_s
        while next_i < len(ops) or inflight:
            tick = time.monotonic()
            cpu0 = time.process_time()
            now = tick - t0
            if now > hard_stop:
                break
            wake.clear()
            with ctx.span("host.launch"):
                while next_i < len(ops) and ops[next_i].intended_s <= now:
                    self._launch(ops[next_i], t0)
                    inflight.append(ops[next_i])
                    next_i += 1
            with ctx.span("host.flows"):
                ran = False
                for n in dep.network.nodes:
                    ran |= n.smm.drain_external()
                pumped = bus.run_network(rounds=256, exclude=dep.excluded)
            with ctx.span("host.sweep"):
                self._sweep(inflight, t0)
            if not pumped and not ran:
                until = ops[next_i].intended_s - (time.monotonic() - t0) \
                    if next_i < len(ops) else IDLE_WAIT_S
                with ctx.span("host.idle_wait"):
                    wake.wait(timeout=max(0.0, min(until, IDLE_WAIT_S)))
            if time.monotonic() - tick > ledger.STALL_S:
                ctx.say("loop_stalled", at_s=now,
                        seconds=time.monotonic() - tick,
                        process_cpu_s=time.process_time() - cpu0)
        end = time.monotonic() - t0
        for op in inflight:
            op.ok, op.done_s = False, end
            op.error = "not committed by the drain limit"
        for op in ops[next_i:]:
            op.ok, op.done_s = False, end
            op.error = "never launched"
        return {"t0": t0, "end_s": end}


#: the longest the idle loop sleeps without a poke (nothing in this
#: deployment becomes runnable without one; this is the belt to that brace)
IDLE_WAIT_S = 0.05


# -- the history -----------------------------------------------------------------------

class History:
    """Every transaction of the run as the plain reference takes it: raw
    values only, by id."""

    def __init__(self):
        self.raw: dict = {}

    def add(self, stx) -> None:
        if stx.id.bytes not in self.raw:
            self.raw[stx.id.bytes] = ref.raw(
                stx.id.bytes,
                [h.bytes for h in stx.tx.available_component_hashes],
                [(r.txhash.bytes, r.index) for r in stx.inputs],
                [(s.by.encoded, s.bytes) for s in stx.sigs],
                [o.data.amount.quantity for o in stx.tx.outputs])


def _signer(node):
    """The node's Ed25519 key in the ``cryptography`` package's hands: the
    scheme is deterministic, so these are the bytes the program's signer gives."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PrivateKey

    from corda_tpu.core.crypto.signatures import DigitalSignatureWithKey
    key = Ed25519PrivateKey.from_private_bytes(node.key_pair.private.encoded)
    public = node.party.owning_key
    return lambda content: DigitalSignatureWithKey(key.sign(content), public)


def build_history(ctx, dep, loop, history, wallet, depth: int, seq: int):
    """One coin issued to ``wallet`` and paid out of ``depth`` times, $pay to
    the parties in turn, change back to the wallet: one chain of ``depth``
    moves and one issue. Returns (the chain, issue first; ops that failed)."""
    from corda_tpu.core.contracts.structures import StateAndRef, StateRef
    from corda_tpu.core.transactions.builder import TransactionBuilder
    from corda_tpu.core.transactions.signed import SignedTransaction
    from corda_tpu.finance.cash import Cash, CashState

    p = ctx.param
    limit = float(p("setup_limit_s", 240.0))
    parties = dep.parties
    n_seen = len(loop.finals)
    issue = Op("issue", seq, 0.0, dep.bank, wallet)
    loop.drive([issue], None, limit)
    failed = int(not issue.ok)
    n_direct = max(0, depth - int(p("history_flow_moves")))
    if n_direct and not failed:
        [coin] = wallet.services.vault.unconsumed_states(CashState)
        sign_w, sign_n = _signer(wallet), _signer(dep.notary)
        me = wallet.party.owning_key
        amount = loop._dollars(p("pay_dollars"))
        built = []
        for i in range(n_direct):
            builder = TransactionBuilder()
            Cash.generate_spend(builder, amount,
                                parties[i % len(parties)].party.owning_key,
                                [coin], change_owner=me)
            wtx = builder.to_wire_transaction()
            stx = SignedTransaction.of(
                wtx, [sign_w(wtx.id.bytes), sign_n(wtx.id.bytes)])
            built.append(stx)
            change = next(k for k, out in enumerate(wtx.outputs)
                          if out.data.owner == me)
            coin = StateAndRef(wtx.outputs[change], StateRef(stx.id, change))
        # the notary's replicated map, through its own commit path
        notary_svc = dep.notary.notary_service
        pending = [notary_svc.commit_async(stx.inputs, stx.id,
                                           str(wallet.party.name))
                   for stx in built]
        for fut in pending:
            fut.result(timeout=limit)
        # the holders' stores: the wallet and the notary hold all of it, a
        # party what it was paid with (the chain up to its last payment)
        issue_stx = loop.finals[n_seen][1]
        wallet.services.record_transactions(*built)
        dep.notary.services.record_transactions(issue_stx, *built)
        for k, party in enumerate(parties):
            paid = range(k, n_direct, len(parties))
            if paid:
                party.services.record_transactions(issue_stx,
                                                   *built[:paid[-1] + 1])
        for stx in built:
            loop.finals.append((wallet, stx, 0.0))
            loop.committed.append((stx.id, tuple(stx.inputs)))
            loop.counts["committed"] += 1
            loop.counts["notarised"] += 1
    moves = [Op("pay", i, 0.0, wallet, parties[i % len(parties)])
             for i in range(n_direct, depth)]
    loop.drive(moves, None, limit)
    failed += sum(not op.ok for op in moves)
    chain = [stx for _n, stx, _t in loop.finals[n_seen:]]
    for stx in chain:
        history.add(stx)
    return chain, failed


# -- hostile joins ------------------------------------------------------------------------

class HostileJoins:
    """One join per kind paid by the hostile wallet, whose stored copy of
    one back-chain transaction is bad (or missing). The notary holds the
    genuine copies and notarises; the joiner has to refuse, and its store
    has to hold nothing at or below the bad transaction."""

    def __init__(self):
        self.injected = self.refused = self.held_below = 0
        self.rows: list = []

    def run(self, ctx, dep, loop, history, chain, limit_s) -> None:
        from corda_tpu.core.crypto.signatures import TransactionSignature
        from corda_tpu.core.transactions.signed import SignedTransaction
        holder = dep.hostile_wallet
        store = holder.services.storage
        target = chain[len(chain) // 2]
        other_key = dep.parties[0].party.owning_key
        for k, kind in enumerate(ctx.param("hostile_kinds")):
            genuine = store._txs[target.id]
            sig = genuine.sigs[0]
            if kind == "withheld":
                del store._txs[target.id]
            elif kind == "flipped_signature":
                bad = TransactionSignature(
                    bytes([sig.bytes[0] ^ 0xFF]) + sig.bytes[1:], sig.by)
                store._txs[target.id] = SignedTransaction(
                    genuine.tx_bits, [bad, *genuine.sigs[1:]])
            elif kind == "wrong_signer_key":
                store._txs[target.id] = SignedTransaction(
                    genuine.tx_bits,
                    [TransactionSignature(sig.bytes, other_key),
                     *genuine.sigs[1:]])
            else:
                raise ValueError(f"no hostile kind {kind!r}")
            joiner = dep.fresh_joiner()
            op = Op("pay", k, 0.0, holder, joiner)
            before = len(store.transactions)
            loop.drive([op], None, limit_s)
            paid = store.transactions[before:]
            store._txs[target.id] = genuine
            self.injected += 1
            held = [stx.id.bytes
                    for stx in joiner.services.storage.transactions]
            # the payment is notarised and FINAL at the payer whatever the
            # joiner does: it belongs to the ledger's history
            for stx in paid:
                history.add(stx)
                if not op.ok:
                    loop.committed.append((stx.id, tuple(stx.inputs)))
            below = ref.judge_refusal(history.raw, target.id.bytes, held)
            refused = not op.ok and "could not be delivered" in str(op.error)
            self.refused += refused and below == 0
            self.held_below += below
            self.rows.append({"kind": kind, "refused": refused,
                              "held_at_or_below": below, "held": len(held),
                              "error": str(op.error)[:120]})


# -- the run ---------------------------------------------------------------------------------

def run(ctx) -> dict:
    from corda_tpu.observability import enable_tracing, get_profiler, get_tracer

    p = ctx.param
    rate = float(p("rate_tx_per_s"))
    if ctx.trace:
        enable_tracing(int(p("trace_capacity", 262144)))
    dep = Deployment(ctx)
    try:
        loop = Loop(ctx, dep)
        history = History()
        limit = float(p("setup_limit_s", 240.0))
        t_setup = time.monotonic()
        # set-up 1: the histories
        chain, failed = build_history(ctx, dep, loop, history, dep.wallet,
                                      int(p("chain_depth")), 1)
        hostile_chain, failed_h = build_history(
            ctx, dep, loop, history, dep.hostile_wallet,
            int(p("hostile_chain_depth")), 2)
        setup_failed = failed + failed_h
        t_history = time.monotonic() - t_setup
        # set-up 2: a warm-up of joins, then the hostile joins
        n_ops = max(1, int(round(rate * ctx.seconds)))
        warm = [Op("pay", i, 0.0, dep.wallet, dep.fresh_joiner())
                for i in range(int(p("warmup_ops")))]
        loop.drive(warm, None, limit)
        setup_failed += sum(not op.ok for op in warm)
        hostile = HostileJoins()
        hostile.run(ctx, dep, loop, history, hostile_chain, limit)
        get_profiler().mark_warm()
        ctx.say("setup", chain_moves=len(chain) - 1,
                hostile_chain_moves=len(hostile_chain) - 1,
                history_s=t_history, warm_joins=len(warm),
                warm_join_s=[op.done_s for op in warm],
                setup_ops_failed=setup_failed)
        reg = dep.registry
        snap0 = reg.snapshot()

        # the window: one fresh joiner per op
        ops = [Op("pay", i, i / rate, dep.wallet, dep.fresh_joiner())
               for i in range(n_ops)]
        ctx.say("schedule", ops=len(ops), rate_tx_per_s=rate,
                joiners_used=len(dep.joiners),
                joiners_configured=int(p("joiners")))
        elections0 = sum(rn.stats()["elections_total"]
                         for rn in dep.raft_nodes)
        n_setup_finals = len(loop.finals)
        # what set-up left on the heap (the history, in some fifty stores)
        # leaves the collector's reach: see the configuration's `assumed`
        gc.collect()
        gc.freeze()
        gc_watch = ledger.GcWatch()
        gc.callbacks.append(gc_watch)
        ctx.window_opens()
        wall0 = time.time()
        clock = loop.drive(ops, ctx.seconds, float(p("drain_limit_s")))
        gc.callbacks.remove(gc_watch)
        elections = sum(rn.stats()["elections_total"]
                        for rn in dep.raft_nodes) - elections0
        snap1 = reg.snapshot()
        ctx.trace_closes()
        spans = []
        if ctx.trace:
            for trace_spans in get_tracer().traces().values():
                spans.extend(trace_spans)
        for _n, stx, _t in loop.finals:       # the joins' own payments too
            history.add(stx)

        done = [op for op in ops if op.ok]
        lat = sorted(op.done_s - op.intended_s for op in done)
        late = sorted(op.launch_s - op.intended_s for op in ops
                      if op.launch_s is not None)
        in_window = [t for _n, _f, t in loop.finals[n_setup_finals:]
                     if t <= ctx.seconds]
        e2e = {"commit_ms_p50": quantile(lat, 0.50) * 1e3,
               "tx_per_s": len(in_window) / ctx.seconds}
        ctx.say("window", ops=len(ops), committed_ops=len(done),
                failed_ops=len(ops) - len(done), latency_samples=len(lat),
                commit_ms_p50=e2e["commit_ms_p50"],
                commit_ms_p95=quantile(lat, 0.95) * 1e3,
                commit_ms_max=lat[-1] * 1e3 if lat else None,
                commit_ms_all=[x * 1e3 for x in lat],
                tx_committed_in_window=len(in_window),
                tx_per_s=e2e["tx_per_s"],
                ops_open_at_window_end=sum(
                    1 for op in ops
                    if op.done_s is None or op.done_s > ctx.seconds),
                drained_s=clock["end_s"],
                raft_elections_in_window=elections,
                gc_longest_pause_s=gc_watch.longest_s,
                gc_full_collections=gc_watch.full,
                generator_late_ms_p50=quantile(late, 0.5) * 1e3,
                generator_late_ms_max=late[-1] * 1e3 if late else None)
        errs = sorted({op.error for op in ops if not op.ok})
        if errs:
            ctx.say("failed_ops", errors=[e[:200] for e in errs[:5]])
        joins = [op for op in warm + ops if op.ok]
        check_guarantees(ctx, dep, loop, history, hostile, ops, joins,
                         setup_failed)
        if joins:      # the run's one device call: the last joiner's store
            last = joins[-1].counterparty
            ledger.check_ids_on_device(ctx, [
                (last, stx, 0.0)
                for stx in last.services.storage.transactions])
        return {"attempted": len(ops), "failed": len(ops) - len(done),
                "end_to_end": e2e,
                "layer_data": {"snap0": snap0, "snap1": snap1, "spans": spans,
                               "window_wall": (wall0, wall0 + ctx.seconds),
                               "samples": {},
                               "gap_prefixes": ("host.",)}}
    finally:
        gc.unfreeze()
        dep.close()
        if ctx.trace:
            from corda_tpu.observability import disable_tracing
            disable_tracing()


def check_guarantees(ctx, dep, loop, history, hostile, ops, joins,
                     setup_failed):
    """Everything the configuration promises, each as a number beside its
    limit (0: these are exact comparisons)."""
    from corda_tpu.finance.cash import CashState

    p = ctx.param
    ctx.check("setup_ops_failed", setup_failed, 0)
    if p("require_all_committed", False):
        ctx.check("window_ops_not_committed",
                  sum(not op.ok for op in ops), 0)
    # exactly-once on every replica, replicas agree (followers may lag)
    deadline = time.monotonic() + 10
    while True:
        views = [{r: d.consuming_tx for r, d in m._map.items()}
                 for m in dep.machines]
        if all(v == views[0] for v in views[1:]) \
                or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    ctx.check("replica_disagreements",
              sum(v != views[0] for v in views[1:]), 0)
    ctx.check("exactly_once_violations", sum(
        1 for tx_id, refs in loop.committed for r in refs for v in views
        if v.get(r) != tx_id), 0)
    # the plain reference fed the same committed order gives the same set
    want = ref.consumed_set(loop.committed)
    ctx.check("reference_consumed_set_diff",
              len(set(want.items()) ^ set(views[0].items())), 0)
    # every acknowledged commit is read back from its initiator
    open_refs = {id(n): {s.ref for s in n.services.vault.unconsumed_states()}
                 for n in dep.network.nodes}
    ctx.check("acknowledged_commits_not_read_back", sum(
        1 for node, stx, _t in loop.finals
        if node.services.storage.get_transaction(stx.id) is None
        or any(r in open_refs[id(node)] for r in stx.inputs)), 0)
    # issued = held, but for what a refused joiner was paid and would not take
    held = sum(s.state.data.amount.quantity
               for n in dep.network.nodes
               for s in n.services.vault.unconsumed_states(CashState))
    issued = 2 * int(p("issue_dollars")) * 100
    refused_cash = int(p("pay_dollars")) * 100 * sum(
        row["refused"] for row in hostile.rows)
    ctx.check("cash_issued_minus_held_minus_refused",
              abs(issued - held - refused_cash), 0)
    c = loop.counts
    ctx.check("committed_minus_notarised_minus_self_issued",
              abs(c["committed"] - c["notarised"] - c["self_issue"]), 0)
    # the hostile joins: all refused, nothing at or below the bad one held
    ctx.check("hostile_joins_accepted", hostile.injected - hostile.refused, 0)
    ctx.check("hostile_joins_held_at_or_below_bad", hostile.held_below, 0)
    ctx.say("hostile", injected=hostile.injected, refused=hostile.refused,
            joins=hostile.rows)
    # ALL joins of the warm-up and the window under the plain reference
    total = {"missing": 0, "extra": 0, "recorded_twice": 0,
             "order_violations": 0, "bad_ids": 0, "bad_signatures": 0,
             "unbalanced": 0}
    n_txs = n_sigs = 0
    for op in joins:
        tip = op.future.result()
        recorded = [stx.id.bytes
                    for stx in op.counterparty.services.storage.transactions]
        for key, n in ref.judge_join(history.raw, tip.id.bytes,
                                     recorded).items():
            total[key] += n
        n_txs += len(recorded)
        n_sigs += sum(len(history.raw[t]["sigs"]) for t in recorded
                      if t in history.raw)
    for key, n in total.items():
        ctx.check(f"joiner_{key}", n, 0)
    # the chain itself, once: every id, signature and balance of the history
    ids = list(history.raw)
    ctx.check("reference_id_mismatches", ref.bad_ids(history.raw, ids), 0)
    ctx.check("reference_signature_failures",
              ref.bad_signatures(history.raw, ids), 0)
    ctx.check("reference_unbalanced_transactions",
              ref.unbalanced(history.raw, ids), 0)
    ctx.say("reference", joins=len(joins), joiner_transactions=n_txs,
            joiner_signatures=n_sigs, history_transactions=len(ids))
    ctx.say("batcher", **check_device_path(ctx, dep.registry,
                                            dep.verifier.batcher))
