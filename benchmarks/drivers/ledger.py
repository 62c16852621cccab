"""Driver ``ledger``: a raft-notarised payment deployment under an open loop.

The benchmark's own copy of the sound parts of
``corda_tpu/observability/ledger_harness.py`` (op schedule from the seed, open
loop timed from the intended send, generator lateness, the invariants), built
on the program's normal entry points: ``MockNetwork``, ``CashIssueFlow`` /
``CashPaymentFlow`` / ``SellerFlow``, ``RaftUniquenessProvider.build`` with a
durable store per replica, one shared ``TpuTransactionVerifierService`` at its
defaults. It does not import ``run_ledger_scenario``.

A run: build the deployment and fund every party (set-up), run a seeded
warm-up of the cell's own mix with a handful of hostile submissions in it
(set-up), ``mark_warm()``; then the window offers ``rate_tx_per_s`` for
``--seconds`` on a fixed schedule, drains what is still queued against
``drain_limit_s``, and checks the guarantees the configuration states. The
payment path sends the chip nothing at default routing (ROADMAP A2), so the
window holds no device call at all; the one device call of a run is part of
``correct``, after the drain: a seeded sample of the window's transaction ids
recomputed by the program's device Merkle kernel.

Controls (``--control``), each of which has to come out ``correct: false``:
``notary_accepts_replays`` puts the plain reference's uniqueness map in the
notary's place with its put-if-absent check removed.
"""
from __future__ import annotations

import gc
import hashlib
import random
import threading
import time

from bench_common import check_device_path, nearest_rank as quantile
from reference import crosscash_raft as ref  # benchmarks/reference/


# -- the op schedule -------------------------------------------------------------

class Op:
    __slots__ = ("kind", "seq", "intended_s", "initiator", "counterparty",
                 "step", "future", "launch_s", "paper_ref", "ok", "error",
                 "done_s")

    def __init__(self, kind, seq, intended_s, initiator, counterparty=None):
        self.kind, self.seq, self.intended_s = kind, seq, intended_s
        self.initiator, self.counterparty = initiator, counterparty
        self.step = 0
        self.future = self.launch_s = self.paper_ref = None
        self.ok = False
        self.error = self.done_s = None


def build_schedule(seed: int, n_ops: int, rate: float, parties: int,
                   settle_share: float, tag: str) -> list[Op]:
    """``n_ops`` pays and settles, evenly spaced at ``rate``. Every seed gets
    the same work in another order: the same NUMBER of settles
    (``round(settle_share * n_ops)``) at shuffled positions, and in every
    block of ``parties`` ops each party initiates once and is paid once (a
    shuffled permutation, counterparties by a random rotation of it), so no
    seed loads one node more than another seed does."""
    rng = random.Random(f"{tag}/{seed}")
    kinds = ["settle"] * int(round(settle_share * n_ops))
    kinds += ["pay"] * (n_ops - len(kinds))
    rng.shuffle(kinds)
    ops = []
    perm, shift = [], 1
    for i, kind in enumerate(kinds):
        k = i % parties
        if k == 0:
            perm = list(range(parties))
            rng.shuffle(perm)
            shift = rng.randrange(1, parties)
        ops.append(Op(kind, i, i / rate, perm[k],
                      perm[(k + shift) % parties]))
    return ops


def schedule_digest(ops) -> str:
    h = hashlib.sha256()
    for op in ops:
        h.update(f"{op.kind},{op.seq},{op.intended_s!r},{op.initiator},"
                 f"{op.counterparty};".encode())
    return h.hexdigest()


# -- the deployment ----------------------------------------------------------------

class Deployment:
    """Notary over raft replicas, a bank, N parties, one shared verifier."""

    def __init__(self, ctx):
        from corda_tpu.consensus.raft import LEADER
        from corda_tpu.consensus.raft_uniqueness import (
            DistributedImmutableMap, RaftUniquenessProvider)
        from corda_tpu.node.notary import ValidatingNotaryService
        from corda_tpu.node.services import ServiceInfo
        from corda_tpu.node.statemachine import FlowScheduler
        from corda_tpu.testing import MockNetwork
        from corda_tpu.utils.metrics import MetricRegistry
        from corda_tpu.verifier.service import TpuTransactionVerifierService

        p = ctx.param
        self.n_parties = int(p("parties"))
        self.registry = MetricRegistry()
        self.network = MockNetwork()
        self.notary = self.network.create_node(
            "O=Raft Notary, L=Zurich, C=CH",
            advertised_services=(ServiceInfo(ValidatingNotaryService.type_id),))
        self.bank = self.network.create_node("O=Bench Bank, L=London, C=GB")
        self.parties = [self.network.create_node(f"O=Party {i}, L=Oslo, C=NO")
                        for i in range(self.n_parties)]
        self.network.start_nodes()
        self.verifier = TpuTransactionVerifierService(metrics=self.registry)
        for node in self.network.nodes:
            node.services.monitoring = self.registry
            node.services.verifier_service = self.verifier

        # raft replicas: extra bus endpoints, every one on a durable store
        n_rep = int(p("raft_replicas"))
        self.raft_names = [f"raft{i}" for i in range(n_rep)]
        self.machines = [DistributedImmutableMap() for _ in self.raft_names]
        store_dir = ctx.state_dir / "raft"
        store_dir.mkdir(parents=True, exist_ok=True)
        self.providers = []
        self.lossy = None
        if ctx.control == "notary_accepts_replays":
            self.lossy = ref.LossyUniqueness()
        elif ctx.control is not None:
            raise ValueError(f"driver ledger has no control {ctx.control!r}")
        for i, name in enumerate(self.raft_names):
            prov = RaftUniquenessProvider.build(
                name, self.raft_names, self.network.bus.create_node(name),
                state_machine=self.machines[i],
                # the election's seed is the deployment's, not the run's:
                # which replica leads changes the round's length (the pump
                # serves them in order), and every seed must do the same work
                seed=int(p("raft_election_seed")) + i,
                native=False, storage_path=str(store_dir / f"{name}.kv"),
                snapshot_entries=int(p("raft_snapshot_entries")))
            prov.timeout_s = float(p("provider_timeout_s", 5.0))
            self.providers.append(prov)
        self.raft_nodes = [pr.raft for pr in self.providers]
        self._pump_poll_s = float(p("raft_pump_poll_s"))
        self._stop = threading.Event()
        self._pump = threading.Thread(target=self._raft_pump, daemon=True,
                                      name="bench-raft-pump")
        self._pump.start()
        deadline = time.monotonic() + 15
        while not any(rn.role == LEADER for rn in self.raft_nodes):
            if time.monotonic() > deadline:
                raise RuntimeError("no raft leader elected")
            time.sleep(0.01)
        leader = next(rn for rn in self.raft_nodes if rn.role == LEADER)
        self.entry = self.providers[self.raft_nodes.index(leader)]
        self.entry.committer_opts = {"label": "s0"}
        self.notary.install_notary(
            ValidatingNotaryService,
            uniqueness=self.lossy if self.lossy is not None else self.entry)
        conc = int(p("node_concurrency"))
        self.schedulers = {str(n.info.address): FlowScheduler(n.smm, conc)
                           for n in self.network.nodes}
        self.excluded = set(self.raft_names)

    def _raft_pump(self) -> None:
        bus = self.network.bus
        while not self._stop.is_set():
            for rn in self.raft_nodes:
                rn.tick()
            for name in self.raft_names:
                while bus.pump_receive(name) is not None:
                    pass
            time.sleep(self._pump_poll_s)

    def close(self) -> None:
        for pr in self.providers:
            try:
                pr.close()
            except Exception:
                pass
        self._stop.set()
        self._pump.join(timeout=5)
        for pr in self.providers:
            store = getattr(pr.raft, "storage", None)
            if store is not None:
                try:
                    store.close()
                except Exception:
                    pass
        try:
            self.verifier.shutdown()
        except Exception:
            pass


# -- the open loop -----------------------------------------------------------------

class Loop:
    """Launches ops at their intended times, pumps the bus, sweeps finished
    flows. One instance runs the funding, the warm-up and the window in turn,
    so the window drives the same objects the warm-up did."""

    def __init__(self, ctx, dep: Deployment):
        self.ctx, self.dep = ctx, dep
        self.p = ctx.param
        self.committed: list = []        # (tx_id, input refs), commit order
        self.finals: list = []           # (initiating node, stx, done_s)
        self.counts = {"committed": 0, "notarised": 0, "self_issue": 0}

    def _dollars(self, n):
        from corda_tpu.core.contracts.amount import USD, Amount
        return Amount(int(n) * 100, USD)

    def _node_for(self, op):
        return self.dep.bank if op.kind == "issue" \
            else self.dep.parties[op.initiator]

    def _make_flow(self, op, node):
        from corda_tpu.finance import CashIssueFlow, CashPaymentFlow
        from corda_tpu.finance.trade import SellerFlow
        from corda_tpu.flows.library import FinalityFlow
        dep, p = self.dep, self.p
        if op.kind == "issue":
            # the issuer ref is unique per op: identical issues would build
            # byte-identical transactions and the vault would keep one coin
            return CashIssueFlow(self._dollars(p("issue_dollars")),
                                 op.seq.to_bytes(4, "big"),
                                 dep.parties[op.initiator].party,
                                 dep.notary.party)
        if op.kind == "pay":
            return CashPaymentFlow(self._dollars(p("pay_dollars")),
                                   dep.parties[op.counterparty].party)
        if op.step == 0:
            return FinalityFlow(ref_paper_issue(
                node, dep.notary.party, self._dollars(p("paper_dollars"))))
        return SellerFlow(dep.parties[op.counterparty].party, op.paper_ref,
                          self._dollars(p("price_dollars")))

    def _launch(self, op, t0):
        node = self._node_for(op)
        sched = self.dep.schedulers[str(node.info.address)]

        def factory(op=op, node=node):
            return self._make_flow(op, node)

        if op.launch_s is None:
            op.launch_s = time.monotonic() - t0
        op.future = sched.submit(factory)

    def _sweep(self, inflight, t0):
        from corda_tpu.core.contracts.structures import StateAndRef, StateRef
        now = time.monotonic() - t0
        for op in list(inflight):
            fut = op.future
            if fut is None or not fut.done():
                continue
            exc = fut.exception()
            if exc is not None:
                inflight.remove(op)
                op.ok, op.error, op.done_s = False, str(exc), now
                continue
            final = fut.result()
            if hasattr(final, "tx"):
                self.counts["committed"] += 1
                if final.inputs or final.tx.time_window is not None:
                    self.counts["notarised"] += 1
                else:
                    self.counts["self_issue"] += 1
                self.finals.append((self._node_for(op), final, now))
                if final.inputs:
                    self.committed.append((final.id, tuple(final.inputs)))
            if op.kind == "settle" and op.step == 0:
                op.paper_ref = StateAndRef(final.tx.outputs[0],
                                           StateRef(final.id, 0))
                op.step = 1
                self._launch(op, t0)
            else:
                inflight.remove(op)
                op.ok, op.done_s = True, now

    def drive(self, ops, offer_s: float | None, drain_limit_s: float,
              hostile=None) -> dict:
        """Offer ``ops`` on their schedule, then drain. Returns the loop's
        clock readings; the ops carry their own outcomes. An open loop that
        stands still reads as a slow system, so a round of the loop longer
        than ``STALL_S`` is reported with the processor time the process
        used in it: about as much as the round lasted or more means busy
        threads, next to none means the process was blocked or not run."""
        dep, ctx = self.dep, self.ctx
        live = dep.network.nodes
        bus = dep.network.bus
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
            with ctx.span("host.launch"):
                while next_i < len(ops) and ops[next_i].intended_s <= now:
                    op = ops[next_i]
                    self._launch(op, t0)
                    inflight.append(op)
                    next_i += 1
                    if hostile is not None:
                        hostile.maybe_inject(next_i, self)
            with ctx.span("host.flows"):
                for n in live:
                    n.smm.drain_external()
                pumped = bus.run_network(rounds=256, exclude=dep.excluded)
            with ctx.span("host.sweep"):
                self._sweep(inflight, t0)
            if not pumped and not inflight:
                with ctx.span("host.idle_wait"):
                    time.sleep(0.001)
            if time.monotonic() - tick > STALL_S:
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


#: a round of the open loop longer than this is reported (see Loop.drive)
STALL_S = 1.0


class GcWatch:
    """Times the interpreter's collections: a full one stops every thread,
    and on a process holding a whole deployment it can take a second."""

    def __init__(self):
        self.longest_s = 0.0
        self.full = 0
        self._t0 = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.longest_s = max(self.longest_s,
                                 time.perf_counter() - self._t0)
            self.full += info.get("generation") == 2


def ref_paper_issue(node, notary_party, face):
    """A commercial-paper self-issue (the first leg of a settle): it carries
    a time window, so it is notarised too."""
    import datetime

    from corda_tpu.core.contracts.amount import Amount
    from corda_tpu.core.contracts.structures import (Issued,
                                                     PartyAndReference,
                                                     TimeWindow)
    from corda_tpu.core.serialization.codec import exact_epoch_micros
    from corda_tpu.core.transactions.builder import TransactionBuilder
    from corda_tpu.finance.commercial_paper import CommercialPaper

    me = node.party
    now = datetime.datetime.now(datetime.timezone.utc)
    maturity = exact_epoch_micros(now + datetime.timedelta(days=30))
    builder = TransactionBuilder(notary=notary_party)
    issued = Amount(face.quantity,
                    Issued(PartyAndReference(me, b"\x01"), face.token))
    CommercialPaper.generate_issue(builder, PartyAndReference(me, b"\x01"),
                                   issued, maturity, notary_party)
    builder.set_time_window(TimeWindow.with_tolerance(
        now, datetime.timedelta(seconds=30)))
    builder.sign_with(node.services.key_management.key_pair(me.owning_key))
    return builder.to_signed_transaction(check_sufficient_signatures=False)


# -- hostile submissions in the warm-up ------------------------------------------------

class Hostile:
    """A seeded handful of replayed consumed refs and mis-signed transactions,
    injected during the warm-up. Every one has to be refused."""

    def __init__(self, ctx, n_ops: int):
        n = int(ctx.param("hostile_ops"))
        self.rng = random.Random(f"hostile/{ctx.seed}")
        self.at = sorted(int(n_ops * (0.5 + 0.45 * k / max(1, n - 1)))
                         for k in range(n))
        self.k = 0
        self.pending: list = []
        self.injected = 0
        self.refused = 0
        self._template = None

    def maybe_inject(self, launched: int, loop: Loop) -> None:
        while self.at and launched >= self.at[0] and loop.committed:
            self.at.pop(0)
            self._inject(loop)

    def _inject(self, loop: Loop) -> None:
        from corda_tpu.core.crypto.secure_hash import SecureHash
        from corda_tpu.core.crypto.signatures import TransactionSignature
        from corda_tpu.core.transactions.signed import SignedTransaction
        dep = loop.dep
        k, self.k = self.k, self.k + 1
        self.injected += 1
        if k % 2 == 0:
            tx_id, refs = loop.committed[
                self.rng.randrange(len(loop.committed))]
            attacker = SecureHash.sha256(b"bench-replay:%d:" % k + tx_id.bytes)
            uniq = dep.notary.notary_service.uniqueness
            if hasattr(uniq, "commit_async"):
                fut = uniq.commit_async(list(refs), attacker, "hostile")
                self.pending.append(("replay", fut, tx_id, refs))
            else:
                try:
                    uniq.commit(list(refs), attacker, "hostile")
                except Exception as e:
                    self._judge_replay(e, tx_id, refs)
            return
        node = dep.parties[k % len(dep.parties)]
        if self._template is None:
            self._template = ref_paper_issue(
                node, dep.notary.party, loop._dollars(loop.p("paper_dollars")))
        stx = self._template
        sig = stx.sigs[0]
        bad = TransactionSignature(bytes([sig.bytes[0] ^ 0xFF]) + sig.bytes[1:],
                                   sig.by)
        hostile = SignedTransaction(stx.tx_bits, [bad, *stx.sigs[1:]])
        fut = dep.verifier.verify_signed(hostile, node.services,
                                         check_sufficient_signatures=False)
        self.pending.append(("missign", fut, None, None))

    def _judge_replay(self, err, tx_id, refs) -> None:
        conflicts = getattr(err, "conflicts", None)
        if conflicts is not None and all(
                conflicts.get(r) is not None
                and conflicts[r].consuming_tx == tx_id for r in refs):
            self.refused += 1

    def resolve(self, timeout_s: float) -> None:
        for kind, fut, tx_id, refs in self.pending:
            try:
                fut.result(timeout=timeout_s)
            except Exception as e:
                if kind == "replay":
                    self._judge_replay(e, tx_id, refs)
                else:
                    self.refused += 1
        self.pending.clear()


# -- the run ---------------------------------------------------------------------------

def run(ctx) -> dict:
    from corda_tpu.observability import enable_tracing, get_profiler, get_tracer

    p = ctx.param
    rate = float(p("rate_tx_per_s"))
    parties = int(p("parties"))
    if ctx.trace:
        enable_tracing(int(p("trace_capacity", 262144)))
    dep = Deployment(ctx)
    try:
        loop = Loop(ctx, dep)
        # set-up 1: fund every party, as fast as the bank can issue
        fund = [Op("issue", i, 0.0, i % parties)
                for i in range(parties * int(p("coins_per_party")))]
        loop.drive(fund, None, float(p("setup_limit_s", 120.0)))
        unfunded = sum(not op.ok for op in fund)
        # set-up 2: the cell's own mix, unmeasured, hostile handful in it
        warm = build_schedule(ctx.seed, int(p("warmup_ops")), rate, parties,
                              float(p("settle_share")), "warmup")
        hostile = Hostile(ctx, len(warm))
        loop.drive(warm, None, float(p("setup_limit_s", 120.0)), hostile)
        while hostile.at and loop.committed:     # none left un-injected
            hostile.at.pop(0)
            hostile._inject(loop)
        hostile.resolve(float(p("provider_timeout_s", 5.0)))
        get_profiler().mark_warm()
        warm_failed = sum(not op.ok for op in warm)
        n_setup_finals = len(loop.finals)
        reg = dep.registry
        committer = dep.entry.group_committer
        rounds_before = len(committer.round_samples()) \
            if committer is not None else 0
        snap0 = reg.snapshot()

        # the window
        n_ops = max(1, int(round(rate * ctx.seconds)))
        ops = build_schedule(ctx.seed, n_ops, rate, parties,
                             float(p("settle_share")), "window")
        ctx.say("schedule", ops=len(ops), rate_tx_per_s=rate,
                settles=sum(op.kind == "settle" for op in ops),
                digest=schedule_digest(ops)[:16])
        elections0 = sum(rn.stats()["elections_total"]
                         for rn in dep.raft_nodes)
        gc_watch = GcWatch()
        gc.callbacks.append(gc_watch)
        ctx.window_opens()
        wall0 = time.time()
        clock = loop.drive(ops, ctx.seconds, float(p("drain_limit_s")))
        gc.callbacks.remove(gc_watch)
        elections = sum(rn.stats()["elections_total"]
                        for rn in dep.raft_nodes) - elections0
        snap1 = reg.snapshot()
        ctx.trace_closes()
        rounds = committer.round_samples()[rounds_before:] \
            if committer is not None else []
        spans = []
        if ctx.trace:
            for trace_spans in get_tracer().traces().values():
                spans.extend(trace_spans)

        # what the window did, on the benchmark's own clock
        done = [op for op in ops if op.ok]
        lat = sorted(op.done_s - op.intended_s for op in done)
        late = sorted(op.launch_s - op.intended_s for op in ops
                      if op.launch_s is not None)
        in_window = [t for _n, _f, t in loop.finals[n_setup_finals:]
                     if t <= ctx.seconds]
        backlog = sum(1 for op in ops
                      if op.done_s is None or op.done_s > ctx.seconds)
        ticks = [0.0] + sorted(in_window) + [ctx.seconds]
        longest_gap = max(b - a for a, b in zip(ticks, ticks[1:]))
        e2e = {"commit_ms_p50": quantile(lat, 0.50) * 1e3,
               "tx_per_s": len(in_window) / ctx.seconds}
        ctx.say("window", ops=len(ops), committed_ops=len(done),
                failed_ops=len(ops) - len(done), latency_samples=len(lat),
                beyond_p95=len(lat) - int(round(0.95 * (len(lat) - 1))) - 1
                if lat else 0,
                commit_ms_p50=e2e["commit_ms_p50"],
                commit_ms_p95=quantile(lat, 0.95) * 1e3,
                commit_ms_max=lat[-1] * 1e3 if lat else None,
                tx_committed_in_window=len(in_window),
                tx_per_s=e2e["tx_per_s"], ops_open_at_window_end=backlog,
                drained_s=clock["end_s"],
                longest_gap_between_commits_s=longest_gap,
                raft_elections_in_window=elections,
                gc_longest_pause_s=gc_watch.longest_s,
                gc_full_collections=gc_watch.full,
                raft_leader=dep.entry.raft.node_id,
                commit_ms_p50_by_quarter=[
                    quantile(sorted(
                        op.done_s - op.intended_s for op in done
                        if k * ctx.seconds / 4 <= op.intended_s
                        < (k + 1) * ctx.seconds / 4), 0.5) * 1e3
                    for k in range(4)],
                generator_late_ms_p50=quantile(late, 0.5) * 1e3,
                generator_late_ms_max=late[-1] * 1e3 if late else None)

        errs = sorted({op.error for op in ops if not op.ok})
        if errs:
            ctx.say("failed_ops", errors=[e[:200] for e in errs[:5]])
        check_guarantees(ctx, dep, loop, hostile, ops,
                         unfunded + warm_failed)
        check_ids_on_device(ctx, loop.finals[n_setup_finals:])
        return {"attempted": len(ops), "failed": len(ops) - len(done),
                "end_to_end": e2e,
                "layer_data": {"snap0": snap0, "snap1": snap1, "spans": spans,
                               "window_wall": (wall0, wall0 + ctx.seconds),
                               "samples": {"raft_round_s": rounds},
                               "gap_prefixes": ("host.",)}}
    finally:
        dep.close()
        if ctx.trace:
            from corda_tpu.observability import disable_tracing
            disable_tracing()


def check_guarantees(ctx, dep, loop, hostile, ops, setup_failed):
    """Everything the configuration promises, each as a number beside its
    limit (0: these are exact comparisons)."""
    from corda_tpu.finance.cash import CashState

    p = ctx.param
    ctx.check("setup_ops_failed", setup_failed, 0)
    if p("require_all_committed", False):
        ctx.check("window_ops_not_committed",
                  sum(not op.ok for op in ops), 0)
    # exactly-once on every replica, replicas agree (followers may lag)
    machines = dep.machines
    if dep.lossy is None:
        deadline = time.monotonic() + 10
        while True:
            views = [{r: d.consuming_tx for r, d in m._map.items()}
                     for m in machines]
            agree = all(v == views[0] for v in views[1:])
            if agree or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        disagreements = sum(v != views[0] for v in views[1:])
        violations = sum(
            1 for tx_id, refs in loop.committed for r in refs for v in views
            if v.get(r) != tx_id)
        consumed = views[0]
    else:
        disagreements = 0
        consumed = dict(dep.lossy.consumed)
        violations = sum(1 for tx_id, refs in loop.committed for r in refs
                         if consumed.get(r) != tx_id)
    ctx.check("replica_disagreements", disagreements, 0)
    ctx.check("exactly_once_violations", violations, 0)
    # the plain reference fed the same committed order gives the same set
    want = ref.consumed_set(loop.committed)
    ctx.check("reference_consumed_set_diff",
              len(set(want.items()) ^ set(consumed.items())), 0)
    # every acknowledged commit is read back from its initiator
    unread = 0
    open_refs = {id(n): {s.ref for s in n.services.vault.unconsumed_states()}
                 for n in dep.network.nodes}
    for node, stx, _t in loop.finals:
        if node.services.storage.get_transaction(stx.id) is None \
                or any(r in open_refs[id(node)] for r in stx.inputs):
            unread += 1
    ctx.check("acknowledged_commits_not_read_back", unread, 0)
    # issued = held
    held = sum(s.state.data.amount.quantity
               for n in dep.network.nodes
               for s in n.services.vault.unconsumed_states(CashState))
    issued = int(p("issue_dollars")) * 100 * dep.n_parties * \
        int(p("coins_per_party"))
    ctx.check("cash_issued_minus_held", abs(issued - held), 0)
    c = loop.counts
    ctx.check("committed_minus_notarised_minus_self_issued",
              abs(c["committed"] - c["notarised"] - c["self_issue"]), 0)
    ctx.check("hostile_submissions_accepted",
              hostile.injected - hostile.refused, 0)
    ctx.say("hostile", injected=hostile.injected, refused=hostile.refused)
    # a seeded sample of committed transactions under the plain reference
    rng = random.Random(f"sample/{ctx.seed}")
    sample = rng.sample(loop.finals,
                        min(int(p("reference_sample")), len(loop.finals)))
    bad_sigs = bad_ids = bad_host = 0
    for node, stx, _t in sample:
        bad_sigs += ref.count_bad_signatures(
            [(s.by.encoded, s.bytes) for s in stx.sigs], stx.id.bytes)
        leaves = [h.bytes for h in stx.tx.available_component_hashes]
        bad_ids += ref.merkle_root(leaves) != stx.id.bytes
        try:    # the program's own host path: a self-check, not the reference
            stx.to_ledger_transaction(node.services).verify()
        except Exception:
            bad_host += 1
    ctx.check("reference_signature_failures", bad_sigs, 0)
    ctx.check("reference_id_mismatches", bad_ids, 0)
    ctx.check("host_contract_reverify_failures", bad_host, 0)
    ctx.say("reference_sample", transactions=len(sample),
            signatures=sum(len(stx.sigs) for _n, stx, _t in sample))
    ctx.say("batcher", **check_device_path(ctx, dep.registry,
                                            dep.verifier.batcher))


def check_ids_on_device(ctx, finals):
    """The run's one device call, after the drain and part of ``correct``
    only: a seeded sample of the window's transactions, all of the commonest
    component count (one shape), has its ids recomputed by the program's
    ``batch_roots`` with the device forced, and each is compared with a
    hashlib Merkle root and with the id the ledger holds. In the traced run
    it is a traced segment of its own, outside the window's."""
    from corda_tpu.core.transactions.batch_merkle import batch_roots
    by_size: dict[int, list] = {}
    for _node, stx, _t in finals:
        leaves = stx.tx.available_component_hashes
        by_size.setdefault(1 << (len(leaves) - 1).bit_length(), []) \
            .append((stx.id, leaves))
    size, rows = max(by_size.items(), key=lambda kv: len(kv[1]),
                     default=(0, []))
    rows = random.Random(f"device-ids/{ctx.seed}").sample(
        rows, min(int(ctx.param("reference_sample")), len(rows)))
    with ctx.traced(), ctx.span("host.id_check"):
        roots = batch_roots([lv for _id, lv in rows], device_crossover=1)
    bad = sum(root.bytes != tx_id.bytes
              or root.bytes != ref.merkle_root([h.bytes for h in lv])
              for (tx_id, lv), root in zip(rows, roots))
    ctx.check("device_id_mismatches", bad, 0)
    ctx.say("device_ids", rows=len(rows), padded_leaves=size)
