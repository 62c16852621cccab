"""Library flows: notarisation, finality, broadcast, resolution, signing.

Reference parity (core/src/main/kotlin/net/corda/core/flows/):
- NotaryFlow.Client/Service (NotaryFlow.kt:31-120)
- FinalityFlow (FinalityFlow.kt:36,86-98): notarise → record → broadcast
- BroadcastTransactionFlow + NotifyTransactionHandler (CoreFlowHandlers.kt)
- FetchTransactionsFlow / FetchDataFlow (hash-addressed download + check)
- ResolveTransactionsFlow (dependency-graph walk, topological order, 5000-tx
  cap — ResolveTransactionsFlow.kt:31,40,98,134)
- CollectSignaturesFlow / SignTransactionFlow (CollectSignaturesFlow.kt:1-258)
"""
from __future__ import annotations

import time as _time
from dataclasses import dataclass
from typing import Any

from ..core.crypto.signatures import DigitalSignatureWithKey
from ..core.serialization import register_type
from ..core.transactions.signed import SignedTransaction
from ..observability import get_tracer
from .api import (AwaitFuture, FlowException, FlowLogic, Receive, Send,
                  SendAndReceive, Verify, VerifyMany, initiating_flow)

MAX_RESOLVE_TRANSACTIONS = 5000  # ResolveTransactionsFlow.kt partial-tx cap


# ---------------------------------------------------------------------------
# Wire payloads
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class NotarisationRequest:
    stx: Any                  # SignedTransaction (validating) or filtered form


@dataclass(frozen=True)
class FetchTransactionsRequest:
    tx_ids: tuple             # SecureHash...
    ancestors: int = 0        # besides those, at most this many of the
    #                           transactions they descend from, as held


@dataclass(frozen=True)
class FetchAttachmentsRequest:
    att_ids: tuple           # SecureHash...


@dataclass(frozen=True)
class NotifyTxRequest:
    stx: Any


@dataclass(frozen=True)
class SignTransactionRequest:
    stx: Any


for _cls in (NotarisationRequest, FetchAttachmentsRequest, NotifyTxRequest,
             SignTransactionRequest):
    register_type(f"flows.{_cls.__name__}", _cls)
# a request for no ancestors is on the wire what it was before there was a
# budget: the field travels only where it says something
register_type(
    "flows.FetchTransactionsRequest", FetchTransactionsRequest,
    to_fields=lambda r: [r.tx_ids, r.ancestors] if r.ancestors
    else [r.tx_ids],
    from_fields=lambda f: FetchTransactionsRequest(tuple(f[0]), *f[1:]))


class NotaryException(FlowException):
    """Conflict or rejection from the notary (NotaryException.Conflict)."""


# ---------------------------------------------------------------------------
# Notarisation
# ---------------------------------------------------------------------------

@initiating_flow
class NotaryFlow(FlowLogic):
    """Client side (NotaryFlow.Client, NotaryFlow.kt:31-44): request a notary
    signature over the transaction; raises NotaryException on conflict."""

    def __init__(self, stx: SignedTransaction):
        self.stx = stx

    def call(self):
        notary = self.stx.notary
        if notary is None:
            raise FlowException("Transaction has no notary set")
        try:
            resp = yield SendAndReceive(notary, NotarisationRequest(self.stx),
                                        DigitalSignatureWithKey)
        except FlowException as e:
            raise NotaryException(str(e)) from e

        def validate(sig):
            if not isinstance(sig, DigitalSignatureWithKey):
                raise FlowException(f"Notary returned {type(sig).__name__}")
            if not notary.owning_key.is_fulfilled_by(sig.by):
                raise FlowException("Notary signature by an unexpected key")
            sig.verify(self.stx.id.bytes)
            return sig

        return [resp.unwrap(validate)]


class NotaryServiceFlow(FlowLogic):
    """Service side (NotaryFlow.Service, NotaryFlow.kt:95-120), instantiated
    per request by the notary node's installed NotaryService. Validating
    services fully verify first (ValidatingNotaryFlow); both check the time
    window and commit input uniqueness before signing."""

    def __init__(self, peer, service):
        self.peer = peer
        self.service = service

    def call(self):
        req = yield Receive(self.peer, NotarisationRequest)
        stx = req.unwrap(lambda r: r.stx if isinstance(r, NotarisationRequest)
                         else _reject("Expected a NotarisationRequest"))
        if self.service.validating:
            # resolve dependencies from the requester, then fully verify
            yield from self.sub_flow(ResolveTransactionsFlow(
                self.peer, stx=stx))
            yield Verify(stx, check_sufficient_signatures=False)
        if not self.service.time_window_checker.is_valid(stx.tx.time_window):
            raise FlowException("Transaction time-window is outside tolerance")
        try:
            if getattr(self.service, "supports_async_commit", False):
                # group-commit path: park the flow on the GroupCommitter's
                # future instead of blocking the notary node thread for a
                # full consensus round — concurrently suspended requests
                # coalesce into one put_all_batch raft append
                trace_ctx = getattr(self.state_machine, "trace_ctx", None)
                yield AwaitFuture(lambda: self.service.commit_async(
                    stx.inputs, stx.id, str(self.peer.name),
                    trace_ctx=trace_ctx), purpose="notary.commit")
            elif getattr(self.service, "supports_trace_ctx", False):
                self.service.commit(
                    stx.inputs, stx.id, str(self.peer.name),
                    trace_ctx=getattr(self.state_machine, "trace_ctx", None))
            else:
                self.service.commit(stx.inputs, stx.id, str(self.peer.name))
        except Exception as e:
            raise FlowException(str(e)) from e
        sig = self.service.sign_tx_id(stx.id)
        yield Send(self.peer, sig)
        return None


def _reject(msg: str):
    raise FlowException(msg)


# ---------------------------------------------------------------------------
# Fetch / resolve
# ---------------------------------------------------------------------------

@initiating_flow
class FetchTransactionsFlow(FlowLogic):
    """Download transactions by id from a peer, verifying each returned blob
    hashes to its requested id (FetchDataFlow's maybeCheckHash).

    ``ancestors`` is a budget: besides the ids, the peer may send up to that
    many of the transactions they descend from and that it holds (nearest
    first; see ``FetchTransactionsHandler``). They come back after the
    requested ones. Each has to be an ancestor: its id, recomputed from its
    bytes, is an input of a transaction of the same reply or, for a caller
    that is walking a graph, of ``descends_from`` (the input ids its walk
    has met). At 0, the default, request and reply are FetchDataFlow's:
    exactly the items asked for."""

    def __init__(self, peer, tx_ids, ancestors: int = 0, descends_from=()):
        self.peer = peer
        self.tx_ids = tuple(tx_ids)
        self.ancestors = ancestors
        self.descends_from = descends_from

    def call(self):
        from_disk, to_fetch = [], []
        for tx_id in self.tx_ids:
            stx = self.service_hub.storage.get_transaction(tx_id)
            (from_disk if stx is not None else to_fetch).append(stx or tx_id)
        if not to_fetch:
            return from_disk
        resp = yield SendAndReceive(
            self.peer,
            FetchTransactionsRequest(tuple(to_fetch), self.ancestors), list)

        def validate(stxs):
            unasked = len(stxs) - len(to_fetch)
            if not 0 <= unasked <= self.ancestors:
                raise FlowException("Peer returned wrong number of transactions")
            for tx_id, stx in zip(to_fetch, stxs):
                if not isinstance(stx, SignedTransaction) or stx.id != tx_id:
                    raise FlowException(
                        f"Peer returned a transaction that hashes to {stx.id} "
                        f"instead of the requested {tx_id}")
            if unasked:
                _check_ancestors(stxs, len(to_fetch), self.descends_from)
            return list(stxs)

        return from_disk + resp.unwrap(validate)


def _check_ancestors(stxs, n_asked: int, descends_from) -> None:
    """The transactions of a reply after its first ``n_asked`` came unasked:
    each is a SignedTransaction whose id (recomputed from its bytes) is an
    input of a transaction of the reply or of the walk, and none comes
    twice. Ids are hashes, so no cycle can vouch for itself: every chain of
    such links ends in something that was asked for."""
    for stx in stxs[n_asked:]:
        if not isinstance(stx, SignedTransaction):
            raise FlowException(
                f"Peer returned {type(stx).__name__} among the ancestors")
    spent = {ref.txhash for stx in stxs for ref in stx.inputs}
    sent = {stx.id for stx in stxs[:n_asked]}
    for stx in stxs[n_asked:]:
        if stx.id in sent:
            raise FlowException(
                f"Peer returned transaction {stx.id} twice")
        if stx.id not in spent and stx.id not in descends_from:
            raise FlowException(
                f"Peer returned transaction {stx.id}, which was not asked "
                "for and is no ancestor of what was")
        sent.add(stx.id)


class FetchTransactionsHandler(FlowLogic):
    """Serves FetchTransactionsFlow requests from local storage — installed on
    every node (installCoreFlows, AbstractNode.kt:285). The requested
    transactions come first, in the order asked, and one that is missing
    fails the request. Where the request has a budget of ``ancestors``, the
    holder then walks its own store breadth first from their inputs and
    appends the ancestors it holds, nearest first and none twice, until the
    budget (a page at most) is spent; one it does not hold is left out."""

    def __init__(self, peer):
        self.peer = peer

    def call(self):
        req = yield Receive(self.peer, FetchTransactionsRequest)
        tx_ids, budget = req.unwrap(lambda r: (r.tx_ids, r.ancestors))
        storage = self.service_hub.storage
        out = []
        for tx_id in tx_ids:
            stx = storage.get_transaction(tx_id)
            if stx is None:
                raise FlowException(f"Transaction {tx_id} not found")
            out.append(stx)
        if isinstance(budget, int) and budget > 0:
            out.extend(_held_ancestors(storage, out, min(budget, FETCH_PAGE)))
        yield Send(self.peer, out)
        return None


def _held_ancestors(storage, stxs, budget: int) -> list:
    """Breadth first from ``stxs``' inputs through ``storage``: the ancestors
    held there, nearest first, none twice and none of ``stxs``, at most
    ``budget`` of them."""
    out = []
    seen = {stx.id for stx in stxs}
    level = stxs
    while level:
        following = []
        for stx in level:
            for ref in stx.inputs:
                if ref.txhash in seen:
                    continue
                seen.add(ref.txhash)
                held = storage.get_transaction(ref.txhash)
                if held is not None:
                    following.append(held)
        out.extend(following[:budget - len(out)])
        if len(out) >= budget:
            break
        level = following
    return out


@initiating_flow
class FetchAttachmentsFlow(FlowLogic):
    """Download attachments by hash from a peer, verifying content hashes
    (FetchAttachmentsFlow: the hash IS the id, so tampering is detectable)."""

    def __init__(self, peer, att_ids):
        self.peer = peer
        self.att_ids = tuple(att_ids)

    def call(self):
        hub = self.service_hub
        to_fetch = [a for a in self.att_ids if not hub.attachments.has_attachment(a)]
        if to_fetch:
            resp = yield SendAndReceive(
                self.peer, FetchAttachmentsRequest(tuple(to_fetch)), list)

            def validate(blobs):
                if len(blobs) != len(to_fetch):
                    raise FlowException("Peer returned wrong attachment count")
                from ..core.crypto.secure_hash import SecureHash
                for att_id, blob in zip(to_fetch, blobs):
                    if SecureHash.sha256(blob) != att_id:
                        raise FlowException(
                            f"Attachment content does not hash to {att_id}")
                return blobs

            for blob in resp.unwrap(validate):
                hub.attachments.import_attachment(blob)
        return [hub.attachments.open_attachment(a) for a in self.att_ids]


class FetchAttachmentsHandler(FlowLogic):
    def __init__(self, peer):
        self.peer = peer

    def call(self):
        req = yield Receive(self.peer, FetchAttachmentsRequest)
        att_ids = req.unwrap(lambda r: r.att_ids)
        hub = self.service_hub
        blobs = []
        for att_id in att_ids:
            att = hub.attachments.open_attachment(att_id)
            if att is None:
                raise FlowException(f"Attachment {att_id} not found")
            blobs.append(att.data)
        yield Send(self.peer, blobs)
        return None


FETCH_PAGE = 500  # tx ids per FetchTransactionsFlow request within a wave


@initiating_flow
class ResolveTransactionsFlow(FlowLogic):
    """Wave-based dependency download + verify+record
    (ResolveTransactionsFlow.kt:31-134, vectorized): each round fetches the
    ENTIRE unseen frontier as one batched request (paged at FETCH_PAGE
    ids), and a request carries a BUDGET of ancestors: besides the ids, the
    holder sends up to that many of the transactions they descend from and
    that it holds (``FetchTransactionsFlow``). The budget is what the walk
    can see of itself, its length so far:
    ``min(len(fetched), FETCH_PAGE - len(page), cap - all that is wanted)``.
    A walk's first request therefore asks for none, and a walk that ends
    after one level (the validating notary's, a payment whose receiver
    lacks only the last move) sends and receives FetchDataFlow's protocol
    to the byte. A walk that goes on holds 1, 3, 7, 15, ... transactions
    after each round: a chain D deep is down in about log2(D) round trips
    where it was D, and a walk never receives more transactions it did not
    ask for than it has already had to fetch, so what is wasted where the
    requester turns out to hold the deeper history is bounded by what was
    of use. Budgets are a function of counts the response log replays: a
    restored flow sends the same requests.

    This departs from v0.14: ``FetchDataFlow.kt``'s reply holds exactly the
    items asked for, and ``ResolveTransactionsFlow.kt`` learns a level's ids
    only from the level above. Here the holder, who has the chain,
    volunteers it. Nothing that arrives is trusted more for it: every id is
    recomputed from the bytes, a transaction nobody asked for has to be an
    ancestor of what was (``_check_ancestors``) or the walk is refused, one
    the requester already holds is dropped, and everything kept, asked for
    or not, takes the same order, verification and recording.

    Verification runs in topological LEVELS, and the walk hands them to
    the verifier WHOLE, in order, in ONE suspension (the ordered
    ``VerifyMany``): every member of a level has its dependencies in the
    levels before it, and resolves them from the walk's own transactions
    (nothing is recorded between levels). What passed is then recorded in
    one ``record_transactions`` call, in topological order; where member k
    fails, the members before k are recorded, nothing at or after it is,
    and k's exception leaves the flow with its type. Hard cap of 5000
    transactions per walk; the budget never asks past it. What a level
    costs depends on the graph's WIDTH, and each level is still routed by
    its own size. On a wide graph a level's signatures reach the batcher
    together, one bulk burst at or over ``host_crossover``. On a CHAIN (one
    coin paid on and on, its change spent by the next payment) every level
    is one transaction: host-routed, inline on the thread of the walk's one
    verification task, and not one row for the device however deep the
    chain (sending a walk's rows as ONE group is a change at
    ``TpuTransactionVerifierService._verify_in_order``, once a first device
    call is affordable there). The verify half of a walk is still linear in
    D; the constant is what fell: a level costs its two signature checks,
    coverage, resolution and the contract rules, where it also cost a
    suspension, a checkpoint, two thread hand-offs and a recording
    (measured: PERF.md, ``crosscash-deepchain.latejoin``).

    A HOP is one level of ancestry (it was also a round trip while a round
    fetched one level): a walk's ``hops`` is the breadth-first distance of
    the deepest transaction it fetched, plus one. Traced, a walk that sent
    a request leaves ``resolve.walk`` (tags ``fetched``, ``hops``,
    ``round_trips``, ``waves``, ``peer``) with the children
    ``resolve.fetch`` (the download loop), ``resolve.order``,
    ``resolve.verify`` (the one park) and ``resolve.record`` (the one
    recording), each tagged ``hops`` too. They join
    the flow's trace without a parent span: the walk spans many of the
    flow's steps and waits, and a critical-path walk that charges every
    millisecond of a ``flow.run`` to one span has to go on charging those.
    Counted always: ``Resolve.Walks`` / ``Hops`` / ``RoundTrips`` (fetch
    requests sent) / ``Fetched`` (transactions kept) / ``Prefetched``
    (transactions that arrived unasked) / ``PrefetchUnused`` (of those, the
    ones the requester already held) / ``Recorded`` / ``Refused`` /
    ``VerifyParks`` (the suspensions the walk spent in verification: one,
    whatever its depth) and the ``resolve_depth`` histogram (hops per
    walk)."""

    def __init__(self, peer, tx_ids=None, stx: SignedTransaction | None = None):
        self.peer = peer
        self.tx_ids = tuple(tx_ids) if tx_ids else ()
        self.stx = stx

    def call(self):
        hub = self.service_hub
        frontier = list(self.tx_ids)
        if self.stx is not None:
            frontier.extend(ref.txhash for ref in self.stx.inputs)
        fetched: dict = {}
        seen = set(frontier)    # the ids asked for and every input id met
        held = hub.storage.get_transaction
        queue = [tx_id for tx_id in frontier if held(tx_id) is None]
        walk = _WalkRecord(self, queue, fetched)
        try:
            while queue:
                if len(fetched) + len(queue) > MAX_RESOLVE_TRANSACTIONS:
                    raise FlowException(
                        "Transaction resolution exceeds the "
                        f"{MAX_RESOLVE_TRANSACTIONS} limit")
                # one wave = the whole current frontier; page only to bound
                # the size of a single wire message
                wave, queue = queue, []
                for i in range(0, len(wave), FETCH_PAGE):
                    page = wave[i:i + FETCH_PAGE]
                    # the budget for ancestors nobody asked for by id: as
                    # many as the walk has had to fetch so far (none on its
                    # first request), within the page and within the cap
                    # less all that is known to be wanted
                    budget = max(0, min(
                        len(fetched), FETCH_PAGE - len(page),
                        MAX_RESOLVE_TRANSACTIONS - len(fetched)
                        - (len(wave) - i) - len(queue)))
                    walk.asking = page
                    walk.round_trips += 1
                    stxs = yield from self.sub_flow(FetchTransactionsFlow(
                        self.peer, page, ancestors=budget,
                        descends_from=seen))
                    walk.asking = ()
                    asked = set(page)
                    kept = []
                    for stx in stxs:
                        if stx.id not in asked:
                            walk.prefetched += 1
                            if stx.id in fetched or held(stx.id) is not None:
                                walk.prefetch_unused += 1
                                continue
                            seen.add(stx.id)
                        fetched[stx.id] = stx
                        kept.append(stx)
                    for stx in kept:
                        for ref in stx.inputs:
                            dep = ref.txhash
                            if dep not in seen:
                                seen.add(dep)
                                if held(dep) is None:
                                    queue.append(dep)
                # a wave's last page (the one with room for a budget) may
                # have brought what an earlier page's reply had queued
                queue = [tx_id for tx_id in queue if tx_id not in fetched]
            walk.fetched = len(fetched)
            # attachments referenced anywhere in the resolved set must be
            # local before verification can open them (FetchAttachmentsFlow
            # leg of ResolveTransactionsFlow.kt)
            att_ids = {a for stx in fetched.values()
                       for a in stx.tx.attachments}
            if self.stx is not None:
                att_ids |= set(self.stx.tx.attachments)
            missing = [a for a in att_ids
                       if not hub.attachments.has_attachment(a)]
            if missing:
                yield from self.sub_flow(
                    FetchAttachmentsFlow(self.peer, missing))
            walk.phase("fetch")
            waves = _topological_waves(fetched)
            walk.waves = len(waves)
            walk.phase("order")
            # verify the levels in topological order in ONE suspension: the
            # verifier takes them whole and resolves a member's inputs from
            # the walk itself (an id was recomputed from the bytes before a
            # transaction was kept, so what a StateRef names is settled;
            # what a verdict is worth is not, past the first failure)
            request = VerifyMany(levels=tuple(map(tuple, waves)),
                                 check_sufficient_signatures=False)
            ordered = list(request.stxs)
            refusal = None
            if ordered:
                walk.verify_parks += 1
                try:
                    yield request
                except Exception as e:
                    refusal = e
                    del ordered[getattr(e, "verified", 0):]
                walk.phase("verify")
                # what passed, in topological order, in one call: a batch
                # that consumes its own outputs is sound because storage
                # and vault walk it in order
                hub.record_transactions(*ordered)
                walk.recorded = len(ordered)
                walk.phase("record")
            if refusal is not None:
                raise refusal
        except Exception:
            walk.close(refused=True)
            raise
        walk.close(refused=False)
        return [stx.id for stx in ordered]


class _WalkRecord:
    """One resolution walk's counts and, traced, its phases' times: the
    meters and spans named in ResolveTransactionsFlow's docstring."""

    def __init__(self, flow, first, fetched):
        self.flow = flow
        self.first = tuple(first)      # what the walk set out to fetch
        self.got = fetched             # the walk's own dict, as it fills
        self.asking = ()               # the page of the request in flight
        self.round_trips = self.prefetched = self.prefetch_unused = 0
        self.fetched = self.waves = self.recorded = self.verify_parks = 0
        self.tracer = get_tracer()
        self.phases: dict = {}         # name -> [first start, summed seconds]
        self.t0 = self.mark = _time.time() if self.tracer.enabled else None

    def phase(self, name: str) -> None:
        """What has passed since the last mark belongs to ``name``."""
        if self.t0 is None:
            return
        now = _time.time()
        first = self.phases.setdefault(name, [self.mark, 0.0])
        first[1] += now - self.mark
        self.mark = now

    def levels(self) -> int:
        """The walk's hops: the levels of ancestry it went down, which is
        the breadth-first distance of the deepest transaction it fetched
        (or was asking for when it failed), plus one. It is the number of
        requests a walk that learned each level from the one above would
        have sent."""
        wanted = set(self.got).union(self.asking)
        level = [tx_id for tx_id in self.first if tx_id in wanted]
        seen = set(level)
        hops = 0
        while level:
            hops += 1
            following = []
            for tx_id in level:
                if tx_id not in self.got:       # asked for, never received
                    continue
                for ref in self.got[tx_id].inputs:
                    if ref.txhash in wanted and ref.txhash not in seen:
                        seen.add(ref.txhash)
                        following.append(ref.txhash)
            level = following
        return hops

    def close(self, refused: bool) -> None:
        if not self.round_trips:        # nothing to fetch: no walk to count
            return
        hops = self.levels()
        monitoring = getattr(self.flow.service_hub, "monitoring", None)
        if monitoring is not None:
            monitoring.meter("Resolve.Walks").mark()
            monitoring.meter("Resolve.Hops").mark(hops)
            monitoring.meter("Resolve.RoundTrips").mark(self.round_trips)
            monitoring.meter("Resolve.VerifyParks").mark(self.verify_parks)
            monitoring.meter("Resolve.Fetched").mark(self.fetched)
            monitoring.meter("Resolve.Prefetched").mark(self.prefetched)
            monitoring.meter("Resolve.PrefetchUnused").mark(
                self.prefetch_unused)
            monitoring.meter("Resolve.Recorded").mark(self.recorded)
            if refused:
                monitoring.meter("Resolve.Refused").mark()
            monitoring.histogram("resolve_depth").update(hops)
        ctx = getattr(self.flow.state_machine, "trace_ctx", None)
        if self.t0 is None or ctx is None:
            return
        trace_id = ctx[0] if isinstance(ctx, tuple) else ctx.trace_id
        walk = self.tracer.record(
            "resolve.walk", parent=(trace_id, None), start_s=self.t0,
            duration_s=_time.time() - self.t0, fetched=self.fetched,
            hops=hops, round_trips=self.round_trips, waves=self.waves,
            recorded=self.recorded, refused=refused,
            peer=str(self.flow.peer.name))
        for name, (start, seconds) in self.phases.items():
            self.tracer.record(f"resolve.{name}", parent=walk,
                               start_s=start, duration_s=seconds, hops=hops)


def _topological_waves(txs: dict) -> list:
    """Kahn's algorithm by levels, in time linear in transactions + edges:
    wave k = every tx whose dependencies all live in waves < k
    (dependency-free members first, in ``txs``' own order). Flattening the
    waves yields a valid topological order. Dependencies outside ``txs``
    (already in storage) count for nothing."""
    waiting_on = {}                 # tx id -> dependencies not yet in a wave
    dependants: dict = {}           # tx id -> the txs that spend from it
    wave = []
    for tx_id, stx in txs.items():
        deps = {ref.txhash for ref in stx.inputs if ref.txhash in txs}
        if not deps:
            wave.append(stx)
            continue
        waiting_on[tx_id] = len(deps)
        for dep in deps:
            dependants.setdefault(dep, []).append(stx)
    waves = []
    while wave:
        waves.append(wave)
        following = []
        for done in wave:
            for stx in dependants.get(done.id, ()):
                waiting_on[stx.id] -= 1
                if not waiting_on[stx.id]:
                    del waiting_on[stx.id]
                    following.append(stx)
        wave = following
    if waiting_on:
        raise FlowException("Transaction dependency cycle detected")
    return waves


def _topological_order(txs: dict) -> list:
    """Dependencies-first flat order (kept for callers/tests that assert on
    the order directly)."""
    return [stx for wave in _topological_waves(txs) for stx in wave]


# ---------------------------------------------------------------------------
# Broadcast / finality
# ---------------------------------------------------------------------------

@initiating_flow
class BroadcastTransactionFlow(FlowLogic):
    """Send a finalised transaction to each participant
    (BroadcastTransactionFlow → NotifyTransactionHandler)."""

    def __init__(self, stx: SignedTransaction, participants):
        self.stx = stx
        self.participants = tuple(participants)

    def call(self):
        me = str(self.service_hub.my_info.legal_identity.name)
        sent = {me}
        undelivered = []
        for party in self.participants:
            if str(party.name) in sent:
                continue
            sent.add(str(party.name))
            # ACKNOWLEDGED delivery: the reference rides durable broker
            # queues, so a recipient that is down still gets the broadcast
            # on recovery; the TCP plane has no such durability, so the
            # sender waits until the recipient has RECORDED the transaction
            # — a finalised payment can no longer vanish with a crashed
            # recipient's in-flight frame. A failed recipient must not
            # starve the REMAINING recipients (the transaction is already
            # final): every delivery is attempted, then the undelivered
            # set surfaces as one error.
            try:
                resp = yield SendAndReceive(party, NotifyTxRequest(self.stx),
                                            bytes)
                resp.unwrap(lambda ack: ack)
            except FlowException as e:
                undelivered.append((party, str(e)))
        if undelivered:
            detail = "; ".join(f"{p.name}: {reason}"
                               for p, reason in undelivered)
            raise FlowException(
                f"transaction {self.stx.id.prefix_chars()} is FINAL but "
                f"could not be delivered to: {detail}")
        return None


class NotifyTransactionHandler(FlowLogic):
    """Receives a broadcast transaction: resolve deps from the sender, verify,
    record, acknowledge (CoreFlowHandlers.kt NotifyTransactionHandler)."""

    def __init__(self, peer):
        self.peer = peer

    def call(self):
        req = yield Receive(self.peer, NotifyTxRequest)
        stx = req.unwrap(lambda r: r.stx)
        yield from self.sub_flow(ResolveTransactionsFlow(self.peer, stx=stx))
        yield Verify(stx, check_sufficient_signatures=False)
        self.service_hub.record_transactions(stx)
        yield Send(self.peer, b"ack")
        return None


@initiating_flow
class FinalityFlow(FlowLogic):
    """Notarise (if needed), record locally, broadcast to participants
    (FinalityFlow.kt:36,86-98)."""

    def __init__(self, stx: SignedTransaction, extra_recipients=()):
        self.stx = stx
        self.extra_recipients = tuple(extra_recipients)

    def call(self):
        hub = self.service_hub
        stx = self.stx
        needs_notary = stx.notary is not None and (
            len(stx.inputs) > 0 or stx.tx.time_window is not None)
        if needs_notary:
            # client-observed notarisation round trip (request → notary
            # uniqueness/raft commit → signature back) — the commit path's
            # dominant wait, so it gets its own node histogram alongside
            # the notary-side notary_uniqueness_seconds stage
            t0 = _time.perf_counter()
            notary_sigs = yield from self.sub_flow(NotaryFlow(stx))
            stx = stx.plus(*notary_sigs)
            monitoring = getattr(hub, "monitoring", None)
            if monitoring is not None:
                sm = getattr(self, "state_machine", None)
                ctx = getattr(sm, "trace_ctx", None)
                monitoring.histogram("notarise_seconds").update(
                    _time.perf_counter() - t0,
                    trace_id=getattr(ctx, "trace_id", None))
        hub.record_transactions(stx)
        participants = self._participant_parties(stx)
        yield from self.sub_flow(
            BroadcastTransactionFlow(stx, participants + list(self.extra_recipients)))
        return stx

    def _participant_parties(self, stx):
        hub = self.service_hub
        parties = []
        seen = set()
        for out in stx.tx.outputs:
            for key in getattr(out.data, "participants", []):
                owning = getattr(key, "owning_key", key)
                party = hub.identity_service.party_from_key(owning) \
                    if hasattr(hub.identity_service, "party_from_key") else None
                if party is None:
                    party = _party_by_key(hub, owning)
                if party is not None and party.owning_key not in seen:
                    seen.add(party.owning_key)
                    parties.append(party)
        return parties


@initiating_flow
class ManualFinalityFlow(FinalityFlow):
    """FinalityFlow that broadcasts ONLY to the explicitly named recipients —
    no participant derivation (core ManualFinalityFlow: used when states'
    participants cannot be resolved to well-known parties, e.g. anonymous
    or externally-held keys)."""

    def __init__(self, stx: SignedTransaction, recipients):
        super().__init__(stx, extra_recipients=recipients)

    def _participant_parties(self, stx):
        return []


def _party_by_key(hub, key):
    for info in hub.network_map_cache.all_nodes():
        if info.legal_identity.owning_key == key:
            return info.legal_identity
    return None


# ---------------------------------------------------------------------------
# Signature collection
# ---------------------------------------------------------------------------

@initiating_flow
class CollectSignaturesFlow(FlowLogic):
    """Collect signatures from every required signer other than ourselves and
    the notary (CollectSignaturesFlow.kt:1-258)."""

    def __init__(self, stx: SignedTransaction):
        self.stx = stx

    def call(self):
        hub = self.service_hub
        our_keys = hub.key_management.keys
        notary_key = stx_notary_key = None
        if self.stx.notary is not None:
            notary_key = self.stx.notary.owning_key
        stx = self.stx
        for key in stx.tx.must_sign:
            if key == notary_key or any(k in our_keys for k in key.keys):
                continue
            # a signature already attached (e.g. an oracle's tear-off
            # signature collected before this flow) is not re-requested
            if key.is_fulfilled_by({s.by for s in stx.sigs}):
                continue
            party = _party_by_key(hub, key)
            if party is None:
                raise FlowException(
                    f"No well-known party found for signer {key.to_string_short()}")
            resp = yield SendAndReceive(party, SignTransactionRequest(stx),
                                        DigitalSignatureWithKey)

            def validate(sig, _key=key):
                sig.verify(stx.id.bytes)
                if not _key.is_fulfilled_by({sig.by}):
                    raise FlowException("Signature from an unexpected key")
                return sig

            stx = stx.plus(resp.unwrap(validate))
        return stx


def install_core_flows(smm) -> None:
    """Register the always-on service handlers every node must serve
    (AbstractNode.installCoreFlows, AbstractNode.kt:285)."""
    from .api import flow_name
    smm.register_flow_factory(flow_name(FetchTransactionsFlow),
                              FetchTransactionsHandler)
    smm.register_flow_factory(flow_name(FetchAttachmentsFlow),
                              FetchAttachmentsHandler)
    smm.register_flow_factory(flow_name(BroadcastTransactionFlow),
                              NotifyTransactionHandler)


class SignTransactionFlow(FlowLogic):
    """Counter-signer side (abstract in the reference; subclass and override
    `check_transaction` to add business validation)."""

    def __init__(self, peer):
        self.peer = peer

    def check_transaction(self, stx: SignedTransaction) -> None:
        """Override for business checks; raise FlowException to refuse."""

    def call(self):
        req = yield Receive(self.peer, SignTransactionRequest)
        stx = req.unwrap(lambda r: r.stx)
        # the initiator must already have signed it
        stx.check_signatures_are_valid()
        self.check_transaction(stx)
        hub = self.service_hub
        our_key = next((k for k in stx.tx.must_sign
                        for leaf in k.keys
                        if leaf in hub.key_management.keys), None)
        if our_key is None:
            raise FlowException("Transaction does not require our signature")
        leaf = next(k for k in our_key.keys if k in hub.key_management.keys)
        sig = hub.sign(stx.id.bytes, leaf)
        yield Send(self.peer, sig)
        return None
