"""Out-of-process verification: request/response queues + worker pool.

Reference parity:
- `VerifierApi.VerificationRequest{verificationId, transaction,
  responseAddress}` / `VerificationResponse{verificationId, exception?}`
  (node-api/.../VerifierApi.kt:17-59)
- the standalone verifier worker loop (verifier/.../Verifier.kt:42-79):
  deserialize the LedgerTransaction, run `.verify()`, reply exception-or-null
- competing consumers + redistribution on worker death
  (VerifierTests.kt:53-71, 73+ "verification redistributes on verifier
  death"), and the node's warning when no verifier is attached
  (NodeMessagingClient.kt:200-210)

The queue semantics live in `VerifierRequestQueue` (the Artemis
`verifier.requests` queue analog): work is dealt to attached workers by a
load-aware router (live queue depth from periodic worker load reports +
scheme affinity, round-robin tie-break), outstanding work is tracked per
worker, and a worker's detachment requeues everything it held. An idle
worker triggers WORK STEALING: the node asks the deepest straggler to hand
back the tail of its stealable backlog (WorkReturned) and re-deals it —
exactly-once future resolution is preserved because a returned request is
re-dealt only while still charged to the victim, and duplicate responses
find their handle already popped. Transport-independent — the deterministic
in-memory bus in tests, the TCP plane in production.
"""
from __future__ import annotations

import itertools
import json
import logging
import sys
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, replace as dc_replace
from typing import Any

from ..core.serialization import deserialize, register_type, serialize
from ..network.messaging import (TOPIC_VERIFIER_REQUESTS,
                                 TOPIC_VERIFIER_RESPONSES, TopicSession)
from ..observability import (FleetMetricsFederation, RequestLog, get_tracer,
                             make_span_dict)
from ..observability.slog import jlog
from ..utils import retry
from ..utils.faults import DROP, fault_point
from ..utils.metrics import MetricRegistry
from .service import (TransactionVerifierService, burst_verdicts,
                      first_unverified)

log = logging.getLogger(__name__)


@dataclass(frozen=True)
class VerificationRequest:
    """One transaction's verification work unit (VerifierApi.kt:33-37).

    TPU-first extension over the reference shape: ``signatures`` carries the
    (public key, signature bytes, signed content) triples of the enclosing
    SignedTransaction so the WORKER runs them through its device batcher —
    N workers × cross-request batching is the scale-out story
    (Verifier.kt:42-79) with the EC math actually on the accelerator.
    Empty signatures = reference semantics (ltx platform/contract rules
    only, host-side)."""

    verification_id: int
    transaction: Any          # LedgerTransaction
    response_address: str
    signatures: tuple = ()    # ((PublicKey, sig_bytes, content_bytes), ...)
    #: Serialized SpanContext ``(trace_id, span_id)`` of the node-side
    #: verifier.oop_submit span — the worker parents its child spans here.
    #: Trailing default keeps old-worker decode working (cross-process
    #: trace stitching; empty when node tracing is off).
    trace: tuple = ()


@dataclass(frozen=True)
class VerificationResponse:
    verification_id: int
    error_message: str | None
    #: Finished worker-side span dicts (backlog wait, device dispatch,
    #: host verify) piggybacked on the reply — the node ``ingest``s them
    #: into its span ring to stitch the end-to-end trace. JSON-encoded
    #: (``_pack_obs``): span timings are floats, which the codec forbids
    #: in typed consensus data; the diagnostic payload rides as a string.
    spans: str = ""


@dataclass(frozen=True)
class WorkerHello:
    """A worker attaching to the queue (the Artemis consumer-creation analog).

    ``device_shard`` carries the jax device ids this worker's batcher is
    pinned to and ``capacity`` its relative weight (≈ devices in the shard)
    — the router normalizes estimated load by capacity, and both surface as
    per-worker ``Fleet.*`` gauges on /metrics. Defaults keep pre-fleet
    hellos deserializing."""

    worker_address: str
    device_shard: tuple = ()    # jax device ids, () = host-only / unpinned
    capacity: int = 1


@dataclass(frozen=True)
class WorkerGoodbye:
    worker_address: str


@dataclass(frozen=True)
class WorkerLoadReport:
    """Periodic worker → node load report (the PR 2 batcher gauges shipped
    back over the worker wire): ``pending`` is the stealable backlog weight
    in signatures, ``in_flight`` the signatures submitted to the batcher but
    unresolved, ``queue_depths`` the per-scheme batcher depths (affinity
    signal). A report is also a liveness signal (_last_activity)."""

    worker_address: str
    pending: int
    in_flight: int
    queue_depths: tuple = ()    # ((scheme, depth), ...)
    capacity: int = 1
    #: Finished spans with no reply to ride (worker.stolen parked-time
    #: spans) — drained from the worker's span outbox onto the next
    #: report. JSON-encoded list (``_pack_obs``).
    spans: str = ""
    #: The worker's metric registry snapshot, JSON-encoded
    #: ``{family: fields}`` — the node federates these into worker-labeled
    #: /metrics families (observability/federation.py).
    metrics: str = ""


@dataclass(frozen=True)
class StealRequest:
    """Node → straggler: hand back up to ``max_items`` requests from the
    tail of your stealable backlog (``thief_address`` is informational —
    the node re-deals through the router, it does not promise the thief)."""

    thief_address: str
    max_items: int
    #: SpanContext of the node's verifier.steal_request span — stolen-work
    #: spans tag it so a steal decision cross-links to the requests it moved.
    trace: tuple = ()


@dataclass(frozen=True)
class WorkReturned:
    """Straggler → node: the stolen requests (possibly empty — an empty
    return still acks the StealRequest and clears the in-flight marker)."""

    worker_address: str
    requests: tuple = ()


for _cls in (VerificationRequest, VerificationResponse, WorkerHello,
             WorkerGoodbye, WorkerLoadReport, StealRequest, WorkReturned):
    register_type(f"verifier.{_cls.__name__}", _cls)


def _pack_obs(obj) -> str:
    """Observability piggyback (span lists / metric snapshots) → JSON
    string. The codec deliberately rejects floats in typed wire data
    (non-deterministic in consensus), but span durations and metric rates
    ARE floats — so the diagnostic payload travels as one opaque string
    and never constrains (or is constrained by) consensus typing. Returns
    "" for empty/unserializable input: observability must never fail a
    verification message."""
    if not obj:
        return ""
    try:
        return json.dumps(obj, default=str)
    except (TypeError, ValueError):
        return ""


def _unpack_obs(blob, default):
    """Inverse of _pack_obs — tolerant: anything malformed (an old worker,
    a truncated report) yields ``default`` rather than raising."""
    if not blob or not isinstance(blob, str):
        return default
    try:
        out = json.loads(blob)
    except ValueError:
        return default
    return out if isinstance(out, type(default)) else default


def _weight(req: VerificationRequest) -> int:
    """Routing weight of one request: its signature count (≥ 1 — an
    ltx-only request still occupies the worker's host path)."""
    return max(1, len(req.signatures))


def _dominant_bucket(signatures) -> str | None:
    """The batcher bucket most of a request's signatures route to — the
    scheme-affinity token the router compares against the worker's last
    dealt bucket (same vocabulary as SigBatcher.<name>.* gauges)."""
    if not signatures:
        return None
    from .batcher import _BUCKETS
    counts: dict[str, int] = {}
    for key, _sig, _content in signatures:
        b = _BUCKETS.get(key.scheme.scheme_number_id, "host")
        counts[b] = counts.get(b, 0) + 1
    return max(counts, key=counts.get)


class VerifierRequestQueue:
    """Node-side queue with competing-consumer semantics. Attach it to the
    node's messaging; workers announce themselves with WorkerHello.

    Guarded by one lock: control messages arrive on the messaging executor,
    submissions on flow/RPC threads, and overdue-redelivery scans on a timer
    thread. ``redelivery_timeout_s`` is the Artemis-redelivery analog for
    REAL transports, where a killed worker process never sends Goodbye: a
    request outstanding longer than the timeout declares its worker dead and
    requeues everything it held."""

    #: Router slack (capacity-normalized signature weight): workers within
    #: this much of the least-loaded worker stay candidates, so light loads
    #: keep the old round-robin fairness and affinity has room to act.
    ROUTE_SLACK = 4.0
    #: Minimum reported stealable backlog (signatures) before the node asks
    #: a straggler to hand work back — below this a steal round-trip costs
    #: more than it saves.
    STEAL_MIN_WEIGHT = 4
    #: Max requests one StealRequest may pull (the worker additionally caps
    #: at half its backlog, so a steal can never starve the victim).
    STEAL_MAX_ITEMS = 64
    #: A StealRequest with no WorkReturned after this long is forgotten —
    #: the victim crashed (detach requeues its work anyway) or the ack got
    #: lost; either way the victim becomes stealable again.
    STEAL_TIMEOUT_S = 2.0
    #: Smoothing for the per-worker service-rate EWMA (signatures/s,
    #: updated on every acknowledge): high enough to track a worker that
    #: slowed down mid-run, low enough that one lucky tiny batch does not
    #: whipsaw the router.
    EWMA_ALPHA = 0.3

    def __init__(self, network_service, redelivery_timeout_s: float | None = None,
                 metrics: MetricRegistry | None = None):
        self.network_service = network_service
        self.redelivery_timeout_s = redelivery_timeout_s
        self.metrics = metrics if metrics is not None else MetricRegistry()
        self._lock = threading.RLock()
        self._workers: list[str] = []
        self._rr = 0
        self._pending: "deque[VerificationRequest]" = deque()  # no worker yet
        # worker -> {vid: request}, in dealing order
        self._outstanding: dict[str, dict[int, VerificationRequest]] = {}
        self._dealt_at: dict[int, tuple[str, float]] = {}  # vid -> (worker, t)
        # worker -> weight dealt to it since its last load report arrived
        # and still outstanding (the router's estimate, kept as it changes:
        # a requestor holds thousands of requests outstanding)
        self._dealt_since: dict[str, int] = {}
        self._last_activity: dict[str, float] = {}         # worker -> t
        # fleet state: per-worker shard/capacity from the hello, latest load
        # report (+ node arrival time), last-dealt scheme bucket (affinity),
        # and in-flight StealRequests (one per victim at a time)
        self._shards: dict[str, tuple] = {}
        self._capacity: dict[str, int] = {}
        self._reports: dict[str, tuple[WorkerLoadReport, float]] = {}
        self._affinity: dict[str, str] = {}
        self._steal_inflight: dict[str, float] = {}
        self._gauged: set[str] = set()
        # predictive routing state: per-worker completed-signature rate
        # EWMA (from acknowledge timing) + the previous acknowledge time
        self._ewma_rate: dict[str, float] = {}
        self._last_ack: dict[str, float] = {}
        # fleet observability plane: per-request lifecycle timelines
        # (/debug/requests + request.* jlog events) and the worker-metrics
        # federation whose families ride every metrics snapshot
        self.request_log = RequestLog()
        self.federation = FleetMetricsFederation()
        self.metrics.add_collector(self.federation.snapshot)
        self.metrics.gauge("Fleet.WorkersAttached",
                           lambda: len(self._workers))
        network_service.add_message_handler(
            TopicSession(TOPIC_VERIFIER_REQUESTS), self._on_control)

    # -- worker membership ---------------------------------------------------
    def _on_control(self, msg) -> None:
        payload = deserialize(msg.data)
        if isinstance(payload, WorkerHello):
            with self._lock:
                if payload.worker_address not in self._workers:
                    self._workers.append(payload.worker_address)
                    self._outstanding.setdefault(payload.worker_address, {})
                self._last_activity[payload.worker_address] = time.monotonic()
                self._shards[payload.worker_address] = \
                    tuple(payload.device_shard)
                self._capacity[payload.worker_address] = \
                    max(1, int(payload.capacity))
                self._register_worker_gauges(payload.worker_address)
            self._drain()
        elif isinstance(payload, WorkerGoodbye):
            self.detach_worker(payload.worker_address)
        elif isinstance(payload, WorkerLoadReport):
            self._on_load_report(payload)
        elif isinstance(payload, WorkReturned):
            self._on_work_returned(payload)

    def _register_worker_gauges(self, worker: str) -> None:
        """Per-worker fleet gauges on /metrics (CALLER HOLDS THE LOCK).
        Registration is idempotent; a detached worker's gauges read 0
        (capacity is popped on detach) rather than disappearing."""
        if worker in self._gauged:
            return
        self._gauged.add(worker)
        self.metrics.gauge(
            f"Fleet.WorkerCapacity.{worker}",
            lambda w=worker: self._capacity.get(w, 0))
        self.metrics.gauge(
            f"Fleet.WorkerQueueDepth.{worker}",
            lambda w=worker: self._queue_depth_of(w))

    def _queue_depth_of(self, worker: str) -> int:
        """Raw (un-normalized) estimated signature depth of one worker."""
        with self._lock:
            if worker not in self._workers:
                return 0
            return int(self._est_load_locked(worker, time.monotonic())
                       * self._capacity.get(worker, 1))

    def detach_worker(self, worker: str) -> None:
        """Worker death: requeue everything it held (broker redelivery)."""
        with self._lock:
            if worker in self._workers:
                self._workers.remove(worker)
            held = list(self._outstanding.pop(worker, {}).values())
            self._dealt_since.pop(worker, None)
            for req in held:
                self._dealt_at.pop(req.verification_id, None)
            if held:
                log.info("requeueing %d verifications from dead worker %s",
                         len(held), worker)
            self._pending.extendleft(reversed(held))
            self._reports.pop(worker, None)
            self._capacity.pop(worker, None)
            self._shards.pop(worker, None)
            self._affinity.pop(worker, None)
            self._steal_inflight.pop(worker, None)
            self._ewma_rate.pop(worker, None)
            self._last_ack.pop(worker, None)
        self.federation.detach(worker)
        for req in held:
            self.request_log.append(req.verification_id, "requeued",
                                    trace=req.trace or None,
                                    reason="worker-detached", worker=worker)
        self._drain()

    # -- load reports + work stealing ----------------------------------------
    def _on_load_report(self, report: WorkerLoadReport) -> None:
        with self._lock:
            worker = report.worker_address
            if worker not in self._workers:
                return   # detached (or never attached): its re-hello re-joins
            now = time.monotonic()
            self._reports[worker] = (report, now)
            self._dealt_since[worker] = 0    # the report accounts for them
            self._last_activity[worker] = now
            if report.capacity:
                self._capacity[worker] = max(1, int(report.capacity))
        # piggybacked observability: orphan spans (stolen parked-time) into
        # the span ring, the metric snapshot into the federation
        spans = _unpack_obs(report.spans, [])
        if spans:
            tracer = get_tracer()
            for s in spans:
                tracer.ingest(s)
        metrics = _unpack_obs(report.metrics, {})
        if metrics:
            self.federation.ingest(worker, metrics)
        # a newly idle worker can take pending work right away — and may
        # justify stealing from a straggler's backlog
        self._drain()
        self._maybe_steal()

    def _on_work_returned(self, ret: WorkReturned) -> None:
        """Stolen work coming back from a straggler. Re-deal ONLY requests
        still charged to the victim in _dealt_at — a request the overdue
        scan already requeued (steal racing a requeue) has a live copy
        elsewhere, and re-dealing the stale return would double-verify it
        (harmless for the future — _on_response pops the handle — but a
        wasted batch slot)."""
        victim = ret.worker_address
        with self._lock:
            self._steal_inflight.pop(victim, None)
            self._last_activity[victim] = time.monotonic()
            requeued = []
            still_held = self._outstanding.get(victim)
            for req in ret.requests:
                owner, _t = self._dealt_at.get(req.verification_id,
                                               (None, 0.0))
                if owner != victim or still_held is None:
                    continue
                self._uncharge_locked(victim, req.verification_id)
                requeued.append(req)
            self._pending.extendleft(reversed(requeued))
        if requeued:
            self.metrics.meter("Fleet.Stolen").mark(len(requeued))
            tracer = get_tracer()
            for req in requeued:
                self.request_log.append(req.verification_id, "stolen",
                                        trace=req.trace or None,
                                        victim=victim)
                if req.trace:
                    # node-side steal-hop marker inside the request's own
                    # trace: the stitched tree shows the re-deal boundary
                    tracer.record("verifier.steal_return",
                                  parent=tuple(req.trace), victim=victim)
        self._drain()

    def _maybe_steal(self) -> None:
        """If some worker sits idle while another holds a deep stealable
        backlog, ask the straggler to hand back its tail. One StealRequest
        in flight per victim; the send itself rides the crash-detach path
        (a dead victim's work requeues via detach, not via the steal)."""
        with self._lock:
            if len(self._workers) < 2:
                return
            now = time.monotonic()
            for v, t in list(self._steal_inflight.items()):
                if now - t > self.STEAL_TIMEOUT_S:
                    del self._steal_inflight[v]
            idle = [w for w in self._workers
                    if self._est_load_locked(w, now) <= 0.0]
            if not idle:
                return
            victim, backlog = None, 0
            for w in self._workers:
                if w in idle or w in self._steal_inflight:
                    continue
                rep = self._reports.get(w)
                stealable = rep[0].pending if rep is not None else 0
                if stealable > backlog:
                    victim, backlog = w, stealable
            if victim is None or backlog < self.STEAL_MIN_WEIGHT:
                return
            self._steal_inflight[victim] = now
            thief = idle[0]
        self.metrics.meter("Fleet.Steals").mark()
        steal_trace: tuple = ()
        tracer = get_tracer()
        if tracer.enabled:
            ctx = tracer.record("verifier.steal_request", thief=thief,
                                victim=victim,
                                max_items=self.STEAL_MAX_ITEMS)
            if ctx is not None:
                steal_trace = ctx.as_tuple()
        try:
            if fault_point("oop.deliver", detail=f"->{victim}") == DROP:
                return   # lost steal: the timeout forgets it
            self.network_service.send(
                TopicSession(TOPIC_VERIFIER_REQUESTS),
                serialize(StealRequest(thief, self.STEAL_MAX_ITEMS,
                                       steal_trace)), victim)
        except Exception:
            log.warning("steal request to verifier %s failed; detaching",
                        victim, exc_info=True)
            self.detach_worker(victim)

    # -- load-aware routing --------------------------------------------------
    def _est_load_locked(self, worker: str, now: float) -> float:
        """Estimated queue depth of one worker, normalized by its capacity:
        the last load report's (pending + in-flight) signatures, plus the
        weight of everything dealt to it SINCE that report arrived (the
        report already accounts for earlier deals). No report yet → the
        full outstanding weight."""
        rep = self._reports.get(worker)
        base = 0 if rep is None else rep[0].pending + rep[0].in_flight
        return (base + self._dealt_since.get(worker, 0)) \
            / max(1, self._capacity.get(worker, 1))

    def _uncharge_locked(self, worker: str, verification_id: int
                         ) -> VerificationRequest | None:
        """Take one request off ``worker``'s books (CALLER HOLDS THE
        LOCK): out of ``_dealt_at`` and ``_outstanding``, and out of the
        weight dealt since the worker's last report if it was dealt after
        it."""
        _w, dealt_t = self._dealt_at.pop(verification_id, (None, 0.0))
        req = self._outstanding.get(worker, {}).pop(verification_id, None)
        rep = self._reports.get(worker)
        if req is not None and (rep is None or dealt_t > rep[1]):
            self._dealt_since[worker] = max(
                0, self._dealt_since.get(worker, 0) - _weight(req))
        return req

    def _service_rate_ref_locked(self) -> float | None:
        """Median of the known per-worker service-rate EWMAs — the
        neutral rate assumed for workers with no completion history yet
        (None while NO worker has one: routing falls back to raw load)."""
        rates = sorted(r for r in self._ewma_rate.values() if r > 0.0)
        if not rates:
            return None
        return rates[len(rates) // 2]

    def _pick_worker_locked(self, req: VerificationRequest,
                            now: float) -> tuple[str, str, dict]:
        """The router: workers within ROUTE_SLACK of the least estimated
        load are candidates; among candidates, prefer the ones whose last
        dealt bucket matches this request's dominant scheme (a warm batcher
        queue coalesces same-scheme groups into fuller device batches);
        round-robin breaks the remaining tie so light load keeps the old
        fair dealing.

        PREDICTIVE refinement: once acknowledge timing has produced
        service-rate EWMAs, each worker's load is scaled by (median rate /
        its rate) — i.e. compared by predicted *drain time*, not snapshot
        depth, so a worker that completes twice as fast legitimately
        carries twice the queue before the router balks. Returns ``(pick,
        reason, est-load vector)`` — the decision record the request's
        lifecycle timeline keeps, so a misrouted request is debuggable
        from the loads the router SAW."""
        if len(self._workers) == 1:
            only = self._workers[0]
            return only, "single-worker", {
                only: round(self._est_load_locked(only, now), 2)}
        loads = {w: self._est_load_locked(w, now) for w in self._workers}
        ref = self._service_rate_ref_locked()
        reason = "least-loaded-rr"
        if ref is not None:
            loads = {w: (v * (ref / self._ewma_rate[w])
                         if self._ewma_rate.get(w, 0.0) > 0.0 else v)
                     for w, v in loads.items()}
            reason = "predictive-ewma"
        best = min(loads.values())
        slack = max(self.ROUTE_SLACK, best * 0.25)
        candidates = [w for w in self._workers if loads[w] <= best + slack]
        bucket = _dominant_bucket(req.signatures)
        if bucket is not None:
            affine = [w for w in candidates
                      if self._affinity.get(w) == bucket]
            if affine:
                candidates = affine
                reason = f"affinity:{bucket}"
        pick = candidates[self._rr % len(candidates)]
        self._rr += 1
        if bucket is not None:
            self._affinity[pick] = bucket
        return pick, reason, {w: round(v, 2) for w, v in loads.items()}

    def requeue_overdue(self) -> None:
        """Declare dead any worker that is BOTH holding a request past the
        redelivery timeout AND silent for that long — a busy worker that is
        still acknowledging results (or re-Hello-ing) must not be flagged
        while it works through a deep backlog (review r3). VerifierTests.kt
        :73+ semantics for transports without liveness signals."""
        if self.redelivery_timeout_s is None:
            return
        cutoff = time.monotonic() - self.redelivery_timeout_s
        with self._lock:
            overdue = {w for w, t in self._dealt_at.values()
                       if t < cutoff
                       and self._last_activity.get(w, 0.0) < cutoff}
        for worker in overdue:
            log.warning("verifier %s overdue past %.1fs with no activity; "
                        "presuming dead", worker, self.redelivery_timeout_s)
            self.detach_worker(worker)

    @property
    def worker_count(self) -> int:
        with self._lock:
            return len(self._workers)

    # -- dispatch ------------------------------------------------------------
    def submit(self, request: VerificationRequest) -> None:
        with self._lock:
            self._pending.append(request)
            no_worker = not self._workers
        self.request_log.append(request.verification_id, "submitted",
                                trace=request.trace or None,
                                n_sigs=len(request.signatures))
        if no_worker:
            self.request_log.append(request.verification_id, "parked",
                                    trace=request.trace or None,
                                    reason="no-worker-attached")
            log.warning("verification request queued but no verifier is "
                        "attached (reference warns every 10s here)")
        self._drain()

    def acknowledge(self, verification_id: int) -> str | None:
        """Retire a completed request from its worker's outstanding list;
        returns the worker it was charged to (None for an unknown or
        already-acknowledged id). Acknowledge timing feeds the worker's
        service-rate EWMA (signatures completed per second between
        consecutive acknowledges) — the predictive-routing signal."""
        with self._lock:
            worker, _ = self._dealt_at.get(verification_id, (None, 0.0))
            if worker is None:
                return None
            now = time.monotonic()
            self._last_activity[worker] = now
            req = self._uncharge_locked(worker, verification_id)
            weight = _weight(req) if req is not None else 1
            prev_t = self._last_ack.get(worker)
            self._last_ack[worker] = now
            if prev_t is not None:
                inst = weight / max(1e-6, now - prev_t)
                prev = self._ewma_rate.get(worker)
                self._ewma_rate[worker] = (
                    inst if prev is None
                    else self.EWMA_ALPHA * inst
                    + (1.0 - self.EWMA_ALPHA) * prev)
        return worker

    def service_rates(self) -> dict:
        """Per-worker service-rate EWMA snapshot (signatures/s) — the
        controller's and fleet_status's view of the predictive signal."""
        with self._lock:
            return {w: round(r, 2) for w, r in self._ewma_rate.items()}

    def _drain(self) -> None:
        while True:
            with self._lock:
                if not self._pending or not self._workers:
                    return
                req = self._pending.popleft()
                worker, reason, loads = self._pick_worker_locked(
                    req, time.monotonic())
                self._outstanding[worker][req.verification_id] = req
                self._dealt_at[req.verification_id] = (worker,
                                                       time.monotonic())
                self._dealt_since[worker] = \
                    self._dealt_since.get(worker, 0) + _weight(req)
            self.request_log.append(req.verification_id, "routed",
                                    trace=req.trace or None, worker=worker,
                                    reason=reason, est_load=loads)
            try:
                # a "drop" rule here models a lost delivery (the worker
                # never sees the request): the redelivery-timeout scan is
                # what recovers it — exactly the path chaos tests pin down
                if fault_point("oop.deliver", detail=f"->{worker}") == DROP:
                    continue
                self.network_service.send(
                    TopicSession(TOPIC_VERIFIER_REQUESTS),
                    serialize(req), worker)
            except Exception:
                # a SEND failure is a live crash signal — detach now and
                # requeue everything the worker held (this request
                # included), instead of waiting out redelivery_timeout_s
                log.warning("delivering to verifier %s failed; detaching",
                            worker, exc_info=True)
                self.detach_worker(worker)
                return   # detach_worker re-drained onto the survivors


class OutOfProcessTransactionVerifierService(TransactionVerifierService):
    """Async verify(ltx) backed by the worker pool
    (OutOfProcessTransactionVerifierService.kt:18-71: nonce → handle map,
    duration/success/failure/in-flight metrics, response consumer)."""

    def __init__(self, network_service, metrics: MetricRegistry | None = None,
                 redelivery_timeout_s: float | None = None,
                 expected_workers: int | None = None,
                 load_report_interval_s: float | None = None,
                 stale_detach_intervals: int | None = None):
        self.metrics = metrics if metrics is not None else MetricRegistry()
        self.network_service = network_service
        # expected fleet size (config): /readyz compares attached against it
        # and reports a partial fleet as degraded (fleet_status)
        self.expected_workers = expected_workers
        # the interval workers were configured to report at: fleet_status
        # flags a worker silent past 3× it as stale/degraded (None = the
        # deployment has no report loop, staleness is not judged)
        self.load_report_interval_s = load_report_interval_s
        # after this many CONSECUTIVE stale windows (each 3× the report
        # interval) of total silence, the worker is presumed wedged and
        # crash-detached — its charged work requeues instead of hanging
        # behind a worker that merely LOOKS attached. None = flag-only
        # (the pre-controller behavior).
        self.stale_detach_intervals = stale_detach_intervals
        # the FleetController driving this service, when one is attached
        # (fleet_status / readyz surface its status block)
        self.controller = None
        self.queue = VerifierRequestQueue(
            network_service, redelivery_timeout_s=redelivery_timeout_s,
            metrics=self.metrics)
        self._ids = itertools.count(1)
        self._handles: dict[int, Future] = {}
        self._timers: dict[int, object] = {}
        # vid -> live verifier.oop_submit span: opened at submit, finished
        # EXACTLY ONCE when the final response lands — a request that gets
        # stolen or crash-requeued keeps its span open across the re-deal
        self._spans: dict[int, object] = {}
        self._scanner = None
        self._stopping = threading.Event()
        network_service.add_message_handler(
            TopicSession(TOPIC_VERIFIER_RESPONSES), self._on_response)
        self.metrics.gauge("Verification.InFlightOOP",
                           lambda: len(self._handles))
        # transport-level crash detection: the TCP plane reports abandoned
        # sends via on_send_failure — chain it into an immediate
        # detach-and-requeue so a crashed worker costs one redelivery, not
        # a redelivery_timeout_s wait. Detaching an address that is not a
        # worker is a no-op, so sharing the hook is safe.
        if hasattr(network_service, "on_send_failure"):
            prev_hook = network_service.on_send_failure

            def _send_failed(recipient, _prev=prev_hook):
                if _prev is not None:
                    _prev(recipient)
                self.queue.detach_worker(recipient)

            network_service.on_send_failure = _send_failed
        periods = []
        if redelivery_timeout_s is not None:
            periods.append(redelivery_timeout_s / 2)
        if (stale_detach_intervals is not None
                and load_report_interval_s is not None):
            periods.append(stale_detach_intervals * 3.0
                           * load_report_interval_s / 2)
        if periods:
            self._scan_period_s = min(periods)
            self._scanner = threading.Thread(
                target=self._scan_overdue, daemon=True,
                name="verifier-redelivery")
            self._scanner.start()

    def _scan_overdue(self) -> None:
        while not self._stopping.wait(self._scan_period_s):
            try:
                self.queue.requeue_overdue()
                self.reap_stale_workers()
            except Exception:
                log.exception("overdue-redelivery scan failed")

    def reap_stale_workers(self, now: float | None = None) -> list[str]:
        """Crash-detach workers whose load reports went silent for
        ``stale_detach_intervals`` consecutive stale windows (each 3× the
        report interval — the same window ``fleet_status`` flags at). The
        detach rides the standard crash path, so everything the wedged
        worker held requeues to the survivors and every future still
        resolves exactly once. No-op (returns []) unless both
        ``load_report_interval_s`` and ``stale_detach_intervals`` are
        configured. Called by the redelivery scanner and every controller
        tick; deterministic tests call it by hand with an explicit
        ``now``."""
        interval = self.load_report_interval_s
        n = self.stale_detach_intervals
        if interval is None or n is None:
            return []
        if now is None:
            now = time.monotonic()
        horizon = n * 3.0 * interval
        q = self.queue
        doomed: list[tuple[str, float]] = []
        with q._lock:
            for w in list(q._workers):
                rep = q._reports.get(w)
                seen = rep[1] if rep is not None \
                    else q._last_activity.get(w, now)
                # a worker whose results are still acknowledging is alive
                # even when its reports lag (GIL stalls under host verify
                # delay the report pump long before work actually stops)
                seen = max(seen, q._last_ack.get(w, 0.0))
                if now - seen > horizon:
                    doomed.append((w, now - seen))
        for w, age in doomed:
            jlog(log, "fleet.stale_detach", level=logging.WARNING,
                 worker=w, silent_s=round(age, 3),
                 stale_windows=n, window_s=round(3.0 * interval, 3))
            self.metrics.meter("Fleet.StaleDetached").mark()
            q.detach_worker(w)
        return [w for w, _ in doomed]

    def shutdown(self) -> None:
        self._stopping.set()

    def fleet_status(self) -> dict:
        """Fleet membership + per-worker load for /readyz: attached vs
        expected, each worker's shard / capacity / estimated depth, and
        report freshness — ``last_report_age_s`` per worker, with workers
        silent past 3× the configured load-report interval flagged
        ``stale`` (the whole fleet reads degraded while any worker is:
        the router is flying blind on its load)."""
        q = self.queue
        interval = self.load_report_interval_s
        now = time.monotonic()
        stale: list[str] = []
        with q._lock:
            workers = {}
            for w in q._workers:
                rep = q._reports.get(w)
                age = (now - rep[1]) if rep is not None else None
                # a just-attached worker has no report yet: judge it from
                # its hello (last_activity), not as instantly stale
                seen = rep[1] if rep is not None \
                    else q._last_activity.get(w, now)
                is_stale = (interval is not None
                            and now - seen > 3.0 * interval)
                if is_stale:
                    stale.append(w)
                rate = q._ewma_rate.get(w)
                workers[w] = {
                    "device_shard": list(q._shards.get(w, ())),
                    "capacity": q._capacity.get(w, 1),
                    "queue_depth": q._queue_depth_of(w),
                    "last_report_age_s": (round(age, 3)
                                          if age is not None else None),
                    "service_rate_ewma": (round(rate, 2)
                                          if rate is not None else None),
                    "stale": is_stale}
        out = {"expected": self.expected_workers, "attached": len(workers),
               "workers": workers, "stale": stale}
        if self.stale_detach_intervals is not None:
            out["stale_detach_intervals"] = self.stale_detach_intervals
        out["degraded"] = bool(stale) or (
            self.expected_workers is not None
            and len(workers) < self.expected_workers)
        if self.controller is not None:
            out["controller"] = self.controller.status()
        return out

    @property
    def request_log(self) -> RequestLog:
        """Per-request lifecycle timelines (the /debug/requests payload)."""
        return self.queue.request_log

    def verify_signatures(self, checks) -> Future:
        """Bulk signature-group verification through the fleet: one future
        resolving when every (key, sig, content) check of the group passed
        (None) or with the first failure's message. The request carries no
        transaction — the worker runs only the EC math through its batcher
        (the fleet bench / bulk-backlog path; verify_signed for full
        SignedTransaction semantics)."""
        sigs = tuple((key, sig, content) for key, sig, content in checks)
        return self._submit(VerificationRequest(
            next(self._ids), None, self.network_service.my_address, sigs))

    def verify(self, ltx) -> Future:
        return self._submit(VerificationRequest(
            next(self._ids), ltx, self.network_service.my_address))

    def verify_signed(self, stx, services,
                      check_sufficient_signatures: bool = True,
                      trace_ctx=None) -> Future:
        """Full SignedTransaction verification with the signature EC math on
        the WORKER's device batcher (SignedTransaction.verify semantics,
        SignedTransaction.kt:174-178, shipped over the VerifierApi seam).
        Coverage (missing-signer) checks are cheap and need the stx, so they
        run node-side before dispatch; resolution happens node-side because
        it needs the ServiceHub. The worker hop is TRACED: the submit span's
        context rides the request and the worker's child spans ship back on
        the reply (cross-process stitching)."""
        if check_sufficient_signatures:
            missing = stx.get_missing_signatures()
            if missing:
                from ..core.transactions.signed import (
                    SignaturesMissingException)
                fut: Future = Future()
                fut.set_exception(SignaturesMissingException(
                    missing, [k.to_string_short() for k in missing], stx.id))
                return fut
        ltx = stx.to_ledger_transaction(services)
        sigs = tuple((sig.by, sig.bytes, stx.id.bytes) for sig in stx.sigs)
        return self._submit(
            VerificationRequest(next(self._ids), ltx,
                                self.network_service.my_address, sigs),
            trace_ctx=trace_ctx, tx_id=stx.id.bytes.hex()[:16])

    def _submit(self, request: VerificationRequest, trace_ctx=None,
                **tags) -> Future:
        # a LIVE span per request, finished exactly once in _on_response:
        # its duration covers the whole fleet round-trip, including any
        # steal hops and crash-requeues in between. With tracing off this
        # is the shared no-op span and the request ships without a context.
        span = get_tracer().span("verifier.oop_submit", parent=trace_ctx,
                                 n_sigs=len(request.signatures), **tags)
        ctx = span.context()
        if ctx is not None:
            request = dc_replace(request, trace=ctx.as_tuple())
            self._spans[request.verification_id] = span
        fut: Future = Future()
        self._handles[request.verification_id] = fut
        timer = self.metrics.timer("Verification.Duration")
        timer.__enter__()
        self._timers[request.verification_id] = timer
        self.queue.submit(request)
        return fut

    def _on_response(self, msg) -> None:
        resp: VerificationResponse = deserialize(msg.data)
        fut = self._handles.pop(resp.verification_id, None)
        timer = self._timers.pop(resp.verification_id, None)
        if timer is not None:
            timer.__exit__(None, None, None)
        if fut is None:
            return   # duplicate reply: the first copy finished the span too
        worker = self.queue.acknowledge(resp.verification_id)
        # stitch: worker-side spans from the reply into the node's ring
        tracer = get_tracer()
        dispatched = None
        for s in _unpack_obs(resp.spans, []):
            tracer.ingest(s)
            if isinstance(s, dict) and s.get("name") == "worker.device_dispatch":
                dispatched = s
        span = self._spans.pop(resp.verification_id, None)
        trace = None
        if span is not None:
            trace = span.context().as_tuple()
            if worker is not None:
                span.set_tag("worker", worker)
            if resp.error_message is not None:
                span.set_tag("error", resp.error_message)
            span.finish()
        rlog = self.queue.request_log
        if dispatched is not None:
            tags = dispatched.get("tags", {})
            rlog.append(resp.verification_id, "dispatched", trace=trace,
                        worker=tags.get("worker"),
                        n_sigs=tags.get("n_sigs"),
                        duration_s=round(dispatched.get("duration_s", 0.0),
                                         6))
        rlog.append(resp.verification_id, "resolved", trace=trace,
                    ok=resp.error_message is None, worker=worker)
        if resp.error_message is None:
            self.metrics.meter("Verification.Success").mark()
            fut.set_result(None)
        else:
            self.metrics.meter("Verification.Failure").mark()
            from ..core.contracts.exceptions import TransactionVerificationException
            fut.set_exception(
                TransactionVerificationException(None, resp.error_message))


class VerifierWorker:
    """The worker half (Verifier.kt:42-79): attach, consume, verify, reply.
    Stateless — run N of them against one queue; kill any mid-run and its
    work redistributes.

    Device path (VERDICT r2 #1): requests carrying ``signatures`` run their
    EC checks through this worker's ``SignatureBatcher`` — the message
    handler parks them on a STEALABLE BACKLOG and a feeder admits them into
    the batcher in BURSTS, each one ``submit_groups`` call and one
    completion task. A StealRequest pops the backlog's tail (LIFO — the
    feeder drains the head) and hands it back to the node for re-dealing.

    What the feeder admits, and when. A request carries 1-2 signatures, a
    device bucket holds ``batcher.max_batch``; handed over one at a time
    the batcher host-routes them as they come (its queue never reaches
    ``host_crossover``) or cuts whatever its linger gathered from the first
    row on. The worker knows what the batcher cannot: whether a request is
    alone (its backlog, its in-flight groups, the frames its transport
    holds, ``MessagingService.inbound_backlog``) and when the last one came.
    So, with the default ``max_inflight_groups=None``:

    - a LONE request (nothing parked before it, no group in flight, no
      frame behind it in the transport) is admitted at once: today's short
      path, no linger;
    - a full bucket of parked signatures is admitted at once, whole;
    - anything else is part of a stream and stays parked, stealable, while
      requests keep coming: it is admitted, all of it, once the stream has
      PAUSED: no request has come for ``PAUSE_SWITCHES`` switch intervals of
      the interpreter (50 ms as Python ships; the batcher's
      ``max_latency_s`` where that is longer), the transport holds none, and
      every request admitted earlier has been answered (a requestor that
      keeps a window outstanding sends its next requests when it has the
      answers: while some are due the stream has not paused, and what is
      parked goes with what they bring, one larger flush and not two small).
      The pause is the worker's own measure and not the batcher's linger: a
      stream of 6,000 signatures a second needs 32 ms to reach
      ``host_crossover`` and 1.4 s to fill a bucket of 8,192, so rows that
      keep coming ARE the company, however long ago the first one came; and
      a peer written in Python sends once a turn of its loop thread, which
      waits up to a switch interval for the interpreter lock, as this
      process's reader does, so gaps of a few intervals are scheduling and
      say nothing of the peer. A thread that lives while something is parked
      watches for the pause; an observation it makes after it was itself
      kept from running (a collection, a long burst) is void, because the
      threads that read and decode were kept from running too.

    Back-pressure: while the worker holds ``HELD_BUCKETS`` buckets' worth of
    signatures (parked and admitted-and-unanswered together) it takes no
    further request from its transport. One bucket being answered and one
    filling is all a worker can use; what stands behind them waits where it
    is cheapest, as frames in the transport (the reference's consumer takes
    one message at a time and leaves the rest in the broker's queue). Without
    the bound a worker that falls behind its requestor for a moment admits a
    second and a third bucket while the first is still being answered; the
    passes that answer them share one interpreter lock, so each takes as
    many times longer as there are of them, everything outstanding stands
    decoded in memory for that long, the collector walks it, and the worker
    stays behind (measured: PERF.md section 6, PR 37).

    A finite ``max_inflight_groups`` (fleet deployments, so that a
    straggler keeps a stealable tail) admits head-first one group at a time
    while the window has room, as before. Requests without signatures keep
    the reference's synchronous host semantics (deterministic for the
    manually-pumped test bus).

    Observability (docs/OBSERVABILITY.md): meters ``Verifier.RequestsIn`` /
    ``BytesIn`` / ``ResponsesOut`` on ``metrics`` (the batcher's registry
    when one is passed), and with the process tracer on the spans
    ``worker.decode`` (a request), ``worker.backlog_wait``,
    ``worker.device_dispatch``, ``worker.host_verify`` and ``worker.reply``
    (a burst each), recorded locally whether or not the requestor traces;
    a request that arrives with a trace context still gets its own span
    dicts shipped back on the reply."""

    #: switch intervals of the interpreter without a request before the
    #: stream counts as paused (the class docstring has why)
    PAUSE_SWITCHES = 10
    #: buckets' worth of signatures held (parked + admitted and unanswered)
    #: at which the worker stops taking requests from its transport
    HELD_BUCKETS = 2

    def __init__(self, network_service, queue_address: str,
                 batcher=None, use_device: bool = True, pool_workers: int = 4,
                 hello_interval_s: float | None = None,
                 device_shard: tuple = (), capacity: int | None = None,
                 load_report_interval_s: float | None = None,
                 max_inflight_groups: int | None = None):
        self.network_service = network_service
        self.queue_address = queue_address
        self.verified_count = 0
        self.processed_sig_count = 0   # signatures through the batcher
        self.last_completion_t = None  # monotonic t of last device group
        self._count_lock = threading.Lock()
        self.use_device = use_device
        self.device_shard = tuple(device_shard)
        self.capacity = (capacity if capacity is not None
                         else max(1, len(self.device_shard)))
        self.max_inflight_groups = max_inflight_groups
        self._backlog: "deque[VerificationRequest]" = deque()
        self._backlog_sigs = 0          # signatures parked on the backlog
        self._backlog_lock = threading.Condition()
        # arrival wall time per parked vid (kept while the process tracer
        # is on, or for a request that arrived carrying a trace context):
        # feeds the backlog-wait spans; the outbox holds finished spans
        # with no reply to ride (worker.stolen), drained onto the next
        # load report
        self._arrival: dict[int, float] = {}
        self._span_outbox: "deque[dict]" = deque(maxlen=512)
        self._last_arrival = 0.0        # monotonic, of the newest parked
        self._linger_thread = None      # alive while something is parked
        self._inflight_groups = 0
        self._inflight_sigs = 0
        self._report_enabled = load_report_interval_s is not None
        self._batcher = batcher            # created lazily if None
        self.metrics = batcher.metrics if batcher is not None \
            else MetricRegistry()
        self._requests_in = self.metrics.meter("Verifier.RequestsIn")
        self._bytes_in = self.metrics.meter("Verifier.BytesIn")
        self._responses_out = self.metrics.meter("Verifier.ResponsesOut")
        # completion tasks (one per admitted burst); threads start on the
        # first submit
        from concurrent.futures import ThreadPoolExecutor
        self._pool = ThreadPoolExecutor(
            max_workers=pool_workers, thread_name_prefix="verifier-worker")
        self._registration = network_service.add_message_handler(
            TopicSession(TOPIC_VERIFIER_REQUESTS), self._on_request)
        self._alive = True
        self._hello()
        if hello_interval_s is not None:
            # periodic re-attach (consumer keep-alive): a worker the queue
            # presumed dead during a long device compile re-joins on the
            # next Hello — attachment is idempotent on the queue side
            def _rehello():
                while self._alive:
                    time.sleep(hello_interval_s)
                    if self._alive:
                        try:
                            self._hello()
                        except Exception:
                            # the keep-alive thread must survive a flaky
                            # queue link — the next interval retries anyway
                            log.warning("re-hello to %s failed",
                                        self.queue_address, exc_info=True)
            threading.Thread(target=_rehello, daemon=True,
                             name="verifier-hello").start()
        if load_report_interval_s is not None:
            def _report_loop():
                while self._alive:
                    time.sleep(load_report_interval_s)
                    if self._alive:
                        try:
                            self.send_load_report()
                        except Exception:
                            log.warning("load report to %s failed",
                                        self.queue_address, exc_info=True)
            threading.Thread(target=_report_loop, daemon=True,
                             name="verifier-load-report").start()

    def _hello(self) -> None:
        retry.retry_call(
            lambda: self.network_service.send(
                TopicSession(TOPIC_VERIFIER_REQUESTS),
                serialize(WorkerHello(self.network_service.my_address,
                                      self.device_shard, self.capacity)),
                self.queue_address),
            site="oop.hello",
            policy=retry.RetryPolicy(base_s=0.05, cap_s=0.5, max_attempts=4),
            retry_on=(OSError, ConnectionError, LookupError))

    def send_load_report(self) -> None:
        """Ship the live load picture to the node's router: stealable
        backlog weight + batcher in-flight signatures + the per-scheme
        queue-depth gauges. Called on the report interval, on going idle,
        and by hand from deterministic tests.

        Federation piggyback: the worker's full metric snapshot rides each
        report (the node re-exports it under a worker label), along with
        any orphan spans waiting in the outbox."""
        with self._backlog_lock:
            pending = sum(_weight(r) for r in self._backlog)
            in_flight = self._inflight_sigs
        depths: tuple = ()
        metrics: str = ""
        if self._batcher is not None:
            try:
                depths = tuple(sorted(self._batcher.queue_depths().items()))
            except Exception:
                depths = ()
            try:
                metrics = _pack_obs(self._batcher.metrics.snapshot())
            except Exception:
                metrics = ""
        spans: list = []
        while len(spans) < 128:
            try:
                spans.append(self._span_outbox.popleft())
            except IndexError:
                break
        try:
            self.network_service.send(
                TopicSession(TOPIC_VERIFIER_REQUESTS),
                serialize(WorkerLoadReport(
                    self.network_service.my_address, pending, in_flight,
                    depths, self.capacity, _pack_obs(spans), metrics)),
                self.queue_address)
        except Exception:
            # a lost report loses its piggybacked spans; put them back so
            # the next report retries (bounded — the deque cap still holds)
            self._span_outbox.extendleft(reversed(spans))
            raise

    @property
    def batcher(self):
        if self._batcher is None:
            from .batcher import SignatureBatcher
            self._batcher = SignatureBatcher(use_device=self.use_device,
                                             metrics=self.metrics)
        return self._batcher

    def _on_request(self, msg) -> None:
        if not self._alive:
            return
        tracer = get_tracer()
        t_wall, t0 = time.time(), time.perf_counter()
        payload = deserialize(msg.data)
        if isinstance(payload, StealRequest):
            self._on_steal(payload)
            return
        req: VerificationRequest = payload
        self._requests_in.mark()
        self._bytes_in.mark(len(msg.data))
        if tracer.enabled:
            tracer.record("worker.decode", parent=tuple(req.trace) or None,
                          start_s=t_wall,
                          duration_s=time.perf_counter() - t0,
                          bytes=len(msg.data), n_sigs=len(req.signatures),
                          **self._span_tags())
        if not req.signatures:
            h_wall, h0 = time.time(), time.perf_counter()
            error = self._verify_host(req)
            took = time.perf_counter() - h0
            spans: tuple = ()
            if req.trace:
                spans = (make_span_dict(
                    "worker.host_verify", tuple(req.trace), h_wall, took,
                    **self._span_tags()),)
            if tracer.enabled:
                tracer.record("worker.host_verify",
                              parent=tuple(req.trace) or None,
                              start_s=h_wall, duration_s=took, n_requests=1,
                              **self._span_tags())
            self._reply_all([(req, error, spans)])
            return
        # device path: park on the stealable backlog; the feeder admits
        # bursts into the batcher (non-blocking)
        with self._backlog_lock:
            self._backlog.append(req)
            self._backlog_sigs += len(req.signatures)
            self._last_arrival = time.monotonic()
            if req.trace or tracer.enabled:
                self._arrival[req.verification_id] = t_wall
        self._feed()
        if self.max_inflight_groups is None:
            # back-pressure: the transport's thread stays here, and the
            # frames behind this one in the transport, while HELD_BUCKETS
            # are held. A full parked bucket was admitted just above, so
            # what is held is mostly in flight and is answered without this
            # thread; the timeout only re-reads ``_alive``
            with self._backlog_lock:
                while self._alive and (
                        self._backlog_sigs + self._inflight_sigs
                        >= self.HELD_BUCKETS * self.batcher.max_batch):
                    self._backlog_lock.wait(timeout=1.0)

    def _span_tags(self) -> dict:
        """Identity tags every worker-side span carries."""
        tags = {"worker": self.network_service.my_address}
        if self.device_shard:
            tags["device_shard"] = list(self.device_shard)
        return tags

    def _next_burst_locked(self, stalled: bool = False) -> list:
        """The requests to admit now, taken off the backlog's head, or []
        (CALLER HOLDS THE BACKLOG LOCK). The class docstring has the rule;
        ``stalled`` is the linger thread's call."""
        if not self._backlog:
            return []
        bucket = None
        if self.max_inflight_groups is not None:
            # finite window: one group at a time while it has room
            if self._inflight_groups >= self.max_inflight_groups:
                return []
            bucket = 1
        elif self._backlog_sigs >= self.batcher.max_batch:
            bucket = self.batcher.max_batch
        elif not stalled and (len(self._backlog) > 1
                              or self._inflight_groups
                              or self.network_service.inbound_backlog()):
            if self._linger_thread is None:
                self._linger_thread = threading.Thread(
                    target=self._linger, daemon=True,
                    name="verifier-linger")
                self._linger_thread.start()
            return []
        burst, n_sigs = [], 0
        while self._backlog and (bucket is None or n_sigs < bucket):
            req = self._backlog.popleft()
            burst.append(req)
            n_sigs += len(req.signatures)
        self._backlog_sigs -= n_sigs
        self._inflight_groups += len(burst)
        self._inflight_sigs += n_sigs
        return burst

    def _pause_s(self) -> float:
        """How long no request must have come for the stream to count as
        paused (the class docstring has the rule)."""
        return max(self.batcher.max_latency_s,
                   self.PAUSE_SWITCHES * sys.getswitchinterval())

    def _linger(self) -> None:
        """Admit what is parked once the stream has paused; ends when
        nothing is parked."""
        due = None      # when this thread meant to look next
        while self._alive:
            now = time.monotonic()
            with self._backlog_lock:
                if not self._backlog:
                    self._linger_thread = None
                    return
                pause = self._pause_s()
                wait = self._last_arrival + pause - now
                answering = self._inflight_groups
            if wait <= 0 and (
                    answering
                    or (due is not None and now - due > pause / 2)
                    or self.network_service.inbound_backlog()):
                # not a pause of the stream: requests admitted earlier are
                # unanswered yet, and a requestor that keeps a window
                # outstanding sends its next ones when it has the answers
                # (what is parked meanwhile joins them: a partial bucket
                # behind a partial bucket is two small flushes where one
                # larger would do); or this thread woke late, so the
                # threads that read and decode were kept from running as
                # well (a collection, another thread's burst); or requests
                # ARE coming, the transport holds them. Look again in a
                # moment
                wait = pause / 5
            if wait > 0:
                due = now + wait
                time.sleep(wait)
            else:
                due = None
                self._feed(stalled=True)

    def _feed(self, stalled: bool = False) -> None:
        """Admit bursts off the backlog's head while the rule allows one.
        Everything still on the backlog is stealable."""
        while True:
            with self._backlog_lock:
                burst = self._next_burst_locked(stalled)
                arrivals = [self._arrival.pop(r.verification_id, None)
                            for r in burst] if self._arrival else None
            if not burst:
                return
            self._admit(burst, arrivals)

    def _admit(self, burst: list, arrivals) -> None:
        """One burst into the batcher: one ``submit_groups`` call, one
        completion task. A traced request grows its span accumulator here
        (the backlog-wait span closes, a device-dispatch span opens whose
        context the batcher's spans nest under); with the process tracer
        on, the burst's own ``worker.backlog_wait`` is recorded from its
        OLDEST request's arrival."""
        tracer = get_tracer()
        now_wall, t0 = time.time(), time.perf_counter()
        n_sigs = sum(len(r.signatures) for r in burst)
        rts: list = [None] * len(burst)
        ctxs = None
        if any(r.trace for r in burst):
            ctxs = [None] * len(burst)
            for i, req in enumerate(burst):
                if not req.trace:
                    continue
                rt = rts[i] = {"spans": [], "t0": t0}
                arrived = arrivals[i] if arrivals is not None else None
                if arrived is not None:
                    rt["spans"].append(make_span_dict(
                        "worker.backlog_wait", tuple(req.trace), arrived,
                        now_wall - arrived, **self._span_tags()))
                rt["dispatch"] = make_span_dict(
                    "worker.device_dispatch", tuple(req.trace), now_wall,
                    0.0, n_sigs=len(req.signatures), **self._span_tags())
                ctxs[i] = (rt["dispatch"]["trace_id"],
                           rt["dispatch"]["span_id"])
        try:
            futures = self.batcher.submit_groups(
                [r.signatures for r in burst], ctxs)
        except Exception as e:
            with self._backlog_lock:
                self._inflight_groups -= len(burst)
                self._inflight_sigs -= n_sigs
                self._backlog_lock.notify_all()
            self._reply_all([(req, str(e), ()) for req in burst])
            return
        local = None
        if tracer.enabled:
            local = dict(self._span_tags(), n_requests=len(burst),
                         n_sigs=n_sigs)
            first = min((a for a in arrivals or () if a is not None),
                        default=now_wall)
            tracer.record("worker.backlog_wait", start_s=first,
                          duration_s=now_wall - first, **local)
        self._pool.submit(self._complete_burst, burst, futures, rts,
                          (now_wall, t0, n_sigs, local))

    def _on_steal(self, steal: StealRequest) -> None:
        """Hand the backlog's TAIL back to the node (the feeder eats the
        head — LIFO stealing keeps the oldest work local where its scheme
        affinity already warmed the batcher). At most half the backlog goes;
        an empty return still acks the steal."""
        taken: list[VerificationRequest] = []
        now_wall = time.time()
        with self._backlog_lock:
            limit = min(steal.max_items, (len(self._backlog) + 1) // 2)
            for _ in range(limit):
                taken.append(self._backlog.pop())
            self._backlog_sigs -= sum(len(r.signatures) for r in taken)
            arrivals = {r.verification_id:
                        self._arrival.pop(r.verification_id, now_wall)
                        for r in taken if r.trace}
        taken.reverse()
        try:
            self.network_service.send(
                TopicSession(TOPIC_VERIFIER_REQUESTS),
                serialize(WorkReturned(self.network_service.my_address,
                                       tuple(taken))),
                self.queue_address)
        except Exception:
            # the node link died mid-steal: keep the work — our requests are
            # still charged to us, so the node's detach path re-deals them
            with self._backlog_lock:
                self._backlog.extendleft(reversed(taken))
                self._backlog_sigs += sum(len(r.signatures) for r in taken)
                for vid, t in arrivals.items():
                    self._arrival[vid] = t
            log.warning("returning stolen work to %s failed",
                        self.queue_address, exc_info=True)
            return
        # the stolen requests never get a reply from US — their parked-time
        # spans ride the next load report instead, tagged with the steal's
        # own trace id as a cross-link
        for r in taken:
            if not r.trace:
                continue
            t_arr = arrivals.get(r.verification_id, now_wall)
            self._span_outbox.append(make_span_dict(
                "worker.stolen", tuple(r.trace), t_arr, now_wall - t_arr,
                thief=steal.thief_address,
                steal_trace=steal.trace[0] if steal.trace else None,
                **self._span_tags()))

    def _verify_host(self, req: VerificationRequest) -> str | None:
        if req.transaction is None:
            return None   # pure signature group (verify_signatures)
        try:
            req.transaction.verify()
            return None
        except Exception as e:
            return str(e)

    def _complete_burst(self, burst: list, futures: list, rts: list,
                        admitted: tuple) -> None:
        """One admitted burst, on a pool thread, in three passes: wait for
        every group's verdicts, run the host rules of the requests whose
        signatures all verified, reply to each. A pass is one contiguous
        interval, so each is one span of the process tracer."""
        tracer = get_tracer()
        now_wall, t0, n_sigs, local = admitted
        verdicts = burst_verdicts(futures)
        t_back = time.perf_counter()
        if local is not None:
            tracer.record("worker.device_dispatch", start_s=now_wall,
                          duration_s=t_back - t0, **local)
        h_wall = time.time()
        replies = []
        for req, got, rt in zip(burst, verdicts, rts):
            error = None
            if isinstance(got, Exception):
                error = str(got)
            else:
                bad = first_unverified((c[0] for c in req.signatures), got)
                if bad is not None:
                    error = (f"Signature by {bad.to_string_short()} "
                             f"did not verify")
            if rt is not None:
                self._finish_dispatch_span(
                    rt, t_back, error if isinstance(got, Exception) else None)
            if error is None:
                if rt is not None:
                    r_wall, r0 = time.time(), time.perf_counter()
                    error = self._verify_host(req)
                    rt["spans"].append(make_span_dict(
                        "worker.host_verify", tuple(req.trace), r_wall,
                        time.perf_counter() - r0, **self._span_tags()))
                else:
                    error = self._verify_host(req)
            replies.append((req, error,
                            tuple(rt["spans"]) if rt is not None else ()))
        if local is not None:
            tracer.record("worker.host_verify", start_s=h_wall,
                          duration_s=time.perf_counter() - t_back, **local)
        self._reply_all(replies)
        with self._backlog_lock:
            self._inflight_groups -= len(burst)
            self._inflight_sigs -= n_sigs
            self.processed_sig_count += n_sigs
            # busy-time marker: the fleet bench's scaling-efficiency metric
            # is mean(last_completion - t0) / makespan across workers
            self.last_completion_t = time.monotonic()
            self._backlog_lock.notify_all()
        self._feed()
        with self._backlog_lock:
            idle = not self._backlog and self._inflight_groups == 0
        if idle and self._report_enabled and self._alive:
            # immediate idle ping: the router learns this worker drained
            # without waiting out the report interval — the steal trigger
            try:
                self.send_load_report()
            except Exception:
                log.warning("idle load report failed", exc_info=True)

    def _finish_dispatch_span(self, rt: dict, t_back: float,
                              error: str | None = None) -> None:
        """Close a traced request's device-dispatch span (duration =
        submit→verdicts back) and tag it with any breaker that was open
        when the group resolved — the breaker-reroute marker for
        host-fallback diagnosis."""
        disp = rt.pop("dispatch", None)
        if disp is None:
            return
        disp["duration_s"] = t_back - rt["t0"]
        if error is not None:
            disp["tags"]["error"] = error
        try:
            status = getattr(self._batcher, "breaker_status", None)
            if status is not None:
                rerouted = sorted(n for n, st in status().items()
                                  if st.get("state") != "closed")
                if rerouted:
                    disp["tags"]["breaker_rerouted"] = rerouted
        except Exception:
            pass
        rt["spans"].append(disp)

    def _reply_all(self, replies: list) -> None:
        """Send ``(request, error, spans)`` replies, one frame each; one
        failed send does not keep the rest from going."""
        tracer = get_tracer()
        r_wall, r0 = time.time(), time.perf_counter()
        sent = 0
        for req, error, spans in replies:
            try:
                sent += self._reply(req, error, spans)
            except Exception:
                log.warning("reply to %s failed", req.response_address,
                            exc_info=True)
        self._responses_out.mark(sent)
        if tracer.enabled and sent:
            tracer.record("worker.reply", start_s=r_wall,
                          duration_s=time.perf_counter() - r0,
                          n_requests=sent, **self._span_tags())

    def _reply(self, req: VerificationRequest, error: str | None,
               spans: tuple = ()) -> int:
        """One reply; returns how many frames went out (0 for a worker
        that was killed, or a reply a fault rule dropped)."""
        if not self._alive:
            return 0   # killed mid-verify: the node requeues our outstanding work
        # a "drop" rule here models a worker crashing BETWEEN finishing the
        # verify and sending the response — the node must redeliver
        if fault_point(
                "oop.reply",
                detail=f"{self.network_service.my_address}"
                       f"->{req.response_address}") == DROP:
            return 0
        with self._count_lock:   # replies run on the completion pool's threads
            self.verified_count += 1
        self.network_service.send(
            TopicSession(TOPIC_VERIFIER_RESPONSES),
            serialize(VerificationResponse(req.verification_id, error,
                                           _pack_obs(list(spans)))),
            req.response_address)
        return 1

    def stop(self, announce: bool = True) -> None:
        """Graceful stop announces Goodbye; a crash (announce=False) relies on
        the node detaching the worker when it notices (detach_worker)."""
        self._alive = False
        with self._backlog_lock:
            self._backlog_lock.notify_all()
        self.network_service.remove_message_handler(self._registration)
        if announce:
            self.network_service.send(
                TopicSession(TOPIC_VERIFIER_REQUESTS),
                serialize(WorkerGoodbye(self.network_service.my_address)),
                self.queue_address)
        self._pool.shutdown(wait=False)
        if self._batcher is not None:
            self._batcher.close()
