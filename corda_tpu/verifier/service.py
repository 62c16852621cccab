"""Async transaction-verification services behind one pluggable seam.

Reference parity:
- `TransactionVerifierService.verify(ltx) → ListenableFuture` (Services.kt:544-550)
- `InMemoryTransactionVerifierService` — fixed 4-worker pool running
  `transaction.verify()` (InMemoryTransactionVerifierService.kt:10-18)
- `OutOfProcessTransactionVerifierService` metrics names
  (OutOfProcessTransactionVerifierService.kt:33-45)

TPU-first redesign: `TpuTransactionVerifierService` splits a transaction's
verification into (a) per-signature EC checks → `SignatureBatcher` device
kernels, batched ACROSS transactions; (b) signature-coverage / platform-rule /
contract-code checks → host thread pool. The `VerifierType`-style selection
seam (NodeConfiguration.kt:91-94) is `make_verifier_service`.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

from ..core.crypto.signatures import SignatureException
from ..observability import get_tracer
from ..utils.metrics import MetricRegistry
from .batcher import SignatureBatcher


def burst_verdicts(futures) -> list:
    """Pass one of a burst's completion (``submit_groups``' futures, in
    submission order): each group's verdict list, or the exception its
    future failed with. The in-process service's wave and the out-of-process
    worker's burst both wait here."""
    got = []
    for fut in futures:
        try:
            got.append(fut.result())
        except Exception as e:
            got.append(e)
    return got


def first_unverified(signers, verdicts):
    """The signer of the first signature, in order, that did not verify;
    None where every one did. ``signers`` yields one key per verdict."""
    if all(verdicts):
        return None
    return next(key for key, ok in zip(signers, verdicts) if not ok)


def _after_the_last(futures, then) -> None:
    """Call ``then()`` when the last of ``futures`` resolves (at once where
    there is none, or all are done), on the thread that resolves it."""
    left = [len(futures)]
    lock = threading.Lock()

    def one_done(_f):
        with lock:
            left[0] -= 1
            last = left[0] == 0
        if last:
            then()

    if not futures:
        then()
    for fut in futures:
        fut.add_done_callback(one_done)


def _on_this_thread(fn, *args) -> Future:
    """``ThreadPoolExecutor.submit``'s contract without the pool: ``fn`` runs
    here and now, and the future handed back is already resolved. What a
    level's admission takes in the pool's place when the thread it is
    admitted on is the one that waits for it (``verify_levels``)."""
    done: Future = Future()
    try:
        done.set_result(fn(*args))
    except Exception as exc:
        done.set_exception(exc)
    return done


def _passed_in_order(members) -> tuple:
    """The prefix rule over resolved futures in order: ``(k, error)`` where
    member ``k`` is the first that failed and ``error`` what it failed with,
    or ``(len(members), None)``."""
    for k, fut in enumerate(members):
        err = fut.exception()
        if err is not None:
            return k, err
    return len(members), None


class TransactionVerifierService:
    """SPI: async verification of a resolved LedgerTransaction. Subclasses
    share the metrics-instrumented submission path (the named metrics of
    OutOfProcessTransactionVerifierService.kt:33-45)."""

    metrics: MetricRegistry
    _pool: ThreadPoolExecutor

    #: capability flag callers probe before passing trace_ctx — a custom
    #: service with the pre-observability signature keeps working
    supports_trace_ctx = True

    def verify(self, ltx, trace_ctx=None) -> Future:
        return self._submit_instrumented(ltx.verify, trace_ctx=trace_ctx)

    def verify_signed(self, stx, services,
                      check_sufficient_signatures: bool = True,
                      trace_ctx=None) -> Future:
        """Async full verify of a SignedTransaction on the service's pool —
        the future every backend offers the SMM's Verify suspension point
        (flows park on it instead of blocking the node thread). Subclasses
        accelerate it (Tpu: device-batched signatures; OutOfProcess: worker
        fan-out); this base version runs `stx.verify` host-side."""
        return self._submit_instrumented(
            lambda: stx.verify(
                services,
                check_sufficient_signatures=check_sufficient_signatures),
            trace_ctx=trace_ctx)

    def _submit_instrumented(self, work_fn, trace_ctx=None,
                             run=None) -> Future:
        """``work_fn`` under the service's metrics and its ``verifier.run``
        span, handed to ``run`` (the pool's ``submit`` unless told
        otherwise)."""
        self.metrics.counter("Verification.InFlight").inc()
        hist = self.metrics.histogram("tx_verify_seconds")
        tracer = get_tracer()

        def work():
            t0 = time.perf_counter()
            with self.metrics.timer("Verification.Duration"), \
                    tracer.span("verifier.run", parent=trace_ctx):
                try:
                    result = work_fn()
                    self.metrics.meter("Verification.Success").mark()
                    return result
                except Exception:
                    self.metrics.meter("Verification.Failure").mark()
                    raise
                finally:
                    self.metrics.counter("Verification.InFlight").dec()
                    hist.update(time.perf_counter() - t0,
                                trace_id=getattr(trace_ctx, "trace_id", None))

        return (run or self._pool.submit)(work)

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False)


class InMemoryTransactionVerifierService(TransactionVerifierService):
    """Host thread-pool backend (InMemoryTransactionVerifierService.kt:10-18)."""

    def __init__(self, workers: int = 4, metrics: MetricRegistry | None = None):
        self.metrics = metrics if metrics is not None else MetricRegistry()
        self._pool = ThreadPoolExecutor(max_workers=workers,
                                        thread_name_prefix="verifier")


class TpuTransactionVerifierService(TransactionVerifierService):
    """Device-batched backend: signatures on TPU, contract rules on host.

    `verify(ltx)` keeps the reference SPI (contract/platform rules only — the
    reference's callers have already checked signatures by the time an ltx
    exists). `verify_signed(stx, services)` is the full TPU-accelerated path:
    device-batched `check_signatures_are_valid` + coverage + resolution +
    `ltx.verify()`, semantics of SignedTransaction.verify
    (SignedTransaction.kt:174-178).
    """

    #: safe to block a flow on: a verify resolves on this service's pool
    #: (and, over the crossover, on the batcher's threads), never via the
    #: node's serial executor (hub.verify_transaction). The node's thread
    #: only submits and parks.
    resolves_off_node_thread = True

    def __init__(self, workers: int = 4, batcher: SignatureBatcher | None = None,
                 metrics: MetricRegistry | None = None, mesh=None):
        self.metrics = metrics if metrics is not None else MetricRegistry()
        # mesh: shard every device batch over the local chips (the node's
        # whole slice verifies as one SPMD program; corda_tpu.parallel)
        self.batcher = batcher if batcher is not None else SignatureBatcher(
            metrics=self.metrics, mesh=mesh)
        self._pool = ThreadPoolExecutor(max_workers=workers,
                                        thread_name_prefix="tpu-verifier")

    # -- full TPU path (verify(ltx) is inherited) ----------------------------
    def verify_signed(self, stx, services,
                      check_sufficient_signatures: bool = True,
                      trace_ctx=None) -> Future:
        """Async full verify of ONE SignedTransaction (a wave of them goes
        to ``verify_wave``); the per-signature EC math
        rides the shared device batcher (cross-transaction batching). With
        tracing enabled the whole pipeline — submit, batch flush, device
        dispatch, resolve — lands in one trace rooted here (or in the
        caller's, when ``trace_ctx`` carries the flow's context).

        Which threads it crosses: the caller (the node's thread) puts the
        rows on the batcher's queue without waking its planner
        (``SignatureBatcher.hold_group``) and hands ``work`` to the pool. The
        ``tpu-verifier`` worker that takes it collects the verdicts
        (``collect_group``): a group the planner would host-route at once,
        which under ``host_crossover`` is every transaction that arrives
        alone, is verified on that worker, one thread hand-off in all.
        Otherwise the rows stay queued for the planner (prep pool, device)
        and the worker blocks on them; the groups of flows suspended
        together share the queue, so they still coalesce into one device
        batch."""
        return self._verify_held(stx, services, check_sufficient_signatures,
                                 trace_ctx, None, self._pool.submit)

    def _verify_held(self, stx, services, check_sufficient_signatures,
                     trace_ctx, wave_rows: int | None, run) -> Future:
        """``verify_signed``'s body. ``wave_rows`` is the signature count of
        the LEVEL ``stx`` belongs to (``_admit_level`` under the crossover):
        the batcher judges a member by it. ``run`` takes the member's work:
        the pool's ``submit``, or ``_on_this_thread`` where the thread that
        holds the rows is the one that collects them."""
        tracer = get_tracer()
        root = tracer.span("tx.verify", parent=trace_ctx,
                           tx_id=stx.id.bytes.hex()[:16],
                           n_sigs=len(stx.sigs))
        ctx = root.context()
        tracer.record("verifier.submit", parent=ctx, n_sigs=len(stx.sigs))
        try:
            # ONE group future for the whole signature set: per-signature
            # Future allocation measured ~25µs each — real money on
            # many-signature transactions (the batcher resolves the group
            # with one lock acquire per flush). Interactive class: a single
            # tx's few signatures are latency-bound — they flush on the
            # short deadline instead of lingering behind a bulk megabatch.
            held = self.batcher.hold_group(
                [(sig.by, sig.bytes, stx.id.bytes) for sig in stx.sigs],
                ctx=ctx, wave_rows=wave_rows)

            def work():
                try:
                    for sig, ok in zip(stx.sigs,
                                       self.batcher.collect_group(held)):
                        if not ok:
                            raise SignatureException(
                                f"Signature by {sig.by.to_string_short()} "
                                f"did not verify on transaction "
                                f"{stx.id.prefix_chars()}")
                    if check_sufficient_signatures:
                        missing = stx.get_missing_signatures()
                        if missing:
                            from ..core.transactions.signed import (
                                SignaturesMissingException)
                            raise SignaturesMissingException(
                                missing,
                                [k.to_string_short() for k in missing],
                                stx.id)
                    with tracer.span("verifier.resolve", parent=ctx):
                        stx.to_ledger_transaction(services).verify()
                finally:
                    root.finish()

            return self._submit_instrumented(work, trace_ctx=ctx, run=run)
        except Exception as exc:
            # submission failed (e.g. closed batcher / shut-down pool): the
            # root span must still close and the caller must get a FAILED
            # FUTURE, not an exception — verify_signed's contract is async
            root.finish()
            failed: Future = Future()
            failed.set_exception(exc)
            return failed

    # -- a wave of transactions, and a walk's levels in order -------------------
    def verify_wave(self, stxs, services,
                    check_sufficient_signatures: bool = True,
                    trace_ctx=None) -> list[Future]:
        """Async full verify of a WAVE of SignedTransactions (one level: a
        dependency-resolution frontier, a back-fill's ledger): one future a
        member, in order, each resolved with that member's own outcome
        (None, ``SignatureException``, ``SignaturesMissingException``, a
        resolution or contract failure); no member fails because another
        did. The wave is admitted on the caller's thread and completed on
        the pool (``_admit_level``). A closed batcher or a shut-down pool
        yields FAILED FUTURES, never an exception."""
        return self._admit_level(list(stxs), services,
                                 check_sufficient_signatures, trace_ctx,
                                 self._pool.submit)

    def verify_levels(self, levels, services,
                      check_sufficient_signatures: bool = True,
                      trace_ctx=None) -> Future:
        """The entry point the scheduler's ``VerifyMany`` calls: a dependency
        walk's topological LEVELS, first level first, verified in order. ONE
        future for the request, resolved (never failed) with
        ``(verified, error)``: the number of members, counted through the
        levels in order, that passed before the first that did not, and
        what that one failed with (``(all of them, None)`` where none did).

        Whatever the number of levels, a LEVEL is what the batcher judges,
        by the rule and the constant it has (``_admit_level``). A request of
        one level is a wave: admitted here, on the caller's thread, its
        members completed on the pool side by side, as ``verify_wave``
        does. A request of more runs as ONE task on the pool
        (``_verify_in_order``), where each level is admitted and completed
        on the task's own thread before the next begins, and the task stops
        at the first level with a failure: a 400-deep chain is one hand-off
        to the pool and one back, where it was 400 of each. ``services``
        has to resolve a member's inputs from the earlier levels (the
        scheduler hands ``ResolvedFromWalk``): nothing is recorded between
        levels.

        Tracing: a request of several levels leaves ``verifier.levels``
        (tags ``levels``, ``n_tx``, ``verified``) over its task, and under
        it a ``verifier.wave`` a level and a ``tx.verify`` a member, as a
        wave leaves them."""
        levels = list(levels)
        outcome: Future = Future()
        if len(levels) == 1:
            members = self._admit_level(
                levels[0], services, check_sufficient_signatures, trace_ctx,
                self._pool.submit)
            _after_the_last(members, lambda: outcome.set_result(
                _passed_in_order(members)))
            return outcome
        try:
            self._pool.submit(self._verify_in_order, levels, services,
                              check_sufficient_signatures, trace_ctx, outcome)
        except Exception as exc:        # a shut-down pool
            outcome.set_result((0, exc))
        return outcome

    def _verify_in_order(self, levels, services, check_sufficient_signatures,
                         trace_ctx, outcome: Future) -> None:
        """``verify_levels``' task. It waits for nothing that needs another
        thread of this pool (more walks than workers would stand still): a
        level under the crossover is verified here, row by row; a level at
        or over it waits for the batcher's verdicts alone."""
        verified, error = 0, None
        span = get_tracer().span(
            "verifier.levels", parent=trace_ctx, levels=len(levels),
            n_tx=sum(len(level) for level in levels))
        try:
            ctx = span.context() or trace_ctx
            for k, level in enumerate(levels):
                passed, error = _passed_in_order(self._admit_level(
                    level, services, check_sufficient_signatures, ctx,
                    _on_this_thread, level=k))
                verified += passed
                if error is not None:
                    break
        except BaseException as exc:    # never a request left unresolved
            error = exc
            raise
        finally:
            span.set_tag("verified", verified)
            span.finish()
            outcome.set_result((verified, error))

    def _admit_level(self, stxs, services, check_sufficient_signatures,
                     trace_ctx, run, level: int | None = None
                     ) -> list[Future]:
        """One level, admitted on the calling thread; ``run`` takes what
        completes it (the pool's ``submit``: members complete side by side
        on its workers; ``_on_this_thread``: here, one after another, and
        every future handed back is resolved). One future a member, in
        order.

        How the level is admitted follows from its size, as its route does
        (``SignatureBatcher.wave_is_the_planners``, the batcher's one
        routing rule). At or over ``host_crossover`` the level is a BULK
        burst: ONE ``submit_groups`` call and ONE completion
        (``_complete_wave``). Under it every member takes
        ``verify_signed``'s path, judged by the level's size: held on the
        queue, collected and verified by the thread that completes it.

        Tracing: span ``verifier.wave`` (entry -> last member resolved;
        tags ``n_tx``, ``n_sigs``, ``admitted`` = ``bulk`` | ``held`` and,
        under a ``verifier.levels``, ``level``: its place in the walk, 0
        first); a bulk level's children are ``verifier.wave.submit`` /
        ``.verdicts`` / ``.coverage`` / ``.rules``. Meters
        ``Verifier.WaveTx.bulk`` / ``.held`` count members by how their
        level was admitted."""
        n_sigs = sum(len(stx.sigs) for stx in stxs)
        tracer = get_tracer()
        bulk = self.batcher.wave_is_the_planners(
            (sig.by for stx in stxs for sig in stx.sigs), n_sigs)
        wave = tracer.span("verifier.wave", parent=trace_ctx,
                           n_tx=len(stxs), n_sigs=n_sigs,
                           admitted="bulk" if bulk else "held")
        if level is not None:
            wave.set_tag("level", level)
        self.metrics.meter("Verifier.WaveTx.bulk" if bulk
                           else "Verifier.WaveTx.held").mark(len(stxs))
        if not bulk:
            futures = [self._verify_held(stx, services,
                                         check_sufficient_signatures,
                                         trace_ctx, n_sigs, run)
                       for stx in stxs]
            if tracer.enabled:
                _after_the_last(futures, wave.finish)
            return futures
        ctx = wave.context()
        members = [Future() for _ in stxs]
        t0 = time.perf_counter()
        try:
            with tracer.span("verifier.wave.submit", parent=ctx, cpu=True):
                groups = self.batcher.submit_groups(
                    [[(sig.by, sig.bytes, stx.id.bytes) for sig in stx.sigs]
                     for stx in stxs],
                    None if ctx is None else [ctx] * len(stxs))
            self.metrics.counter("Verification.InFlight").inc(len(stxs))
            run(self._complete_wave, stxs, services,
                check_sufficient_signatures, groups, members, wave, t0)
        except Exception as exc:
            wave.set_tag("error", f"{type(exc).__name__}: {exc}")
            wave.finish()
            for fut in members:
                if not fut.done():
                    fut.set_exception(exc)
        return members

    def _complete_wave(self, stxs, services, check_sufficient_signatures,
                       groups, members, wave, t0) -> None:
        """One bulk wave, on a pool thread, in three passes, each one
        contiguous interval and one span: the groups' verdicts (the wait);
        coverage of the members whose signatures all verified (every
        required key, CompositeKey thresholds included); resolution and the
        contract rules of those. Then every member's future, in order. The
        out-of-process worker's ``_complete_burst`` runs the same passes
        over its requests (``burst_verdicts`` / ``first_unverified`` are
        shared).

        The time INSIDE the contracts is tallied apart from resolution (two
        clock reads a contract a member, no span) and marked once a wave,
        as coverage's tally is: ``Verifier.ContractRuns.<Contract>`` and
        ``Verifier.ContractMicros.<Contract>``, by the contract's class
        name."""
        from ..core.transactions.signed import SignaturesMissingException
        tracer = get_tracer()
        ctx = wave.context()
        outcomes: list = [None] * len(stxs)
        try:
            with tracer.span("verifier.wave.verdicts", parent=ctx):
                verdicts = burst_verdicts(groups)
            alive = []
            for i, (stx, got) in enumerate(zip(stxs, verdicts)):
                if isinstance(got, Exception):
                    outcomes[i] = got
                    continue
                bad = first_unverified((sig.by for sig in stx.sigs), got)
                if bad is None:
                    alive.append(i)
                else:
                    outcomes[i] = SignatureException(
                        f"Signature by {bad.to_string_short()} did not "
                        f"verify on transaction {stx.id.prefix_chars()}")
            if check_sufficient_signatures:
                tally = [0, 0, 0]   # required, composite required, visits
                with tracer.span("verifier.wave.coverage", parent=ctx,
                                 cpu=True, n_tx=len(alive)):
                    covered = []
                    for i in alive:
                        missing = stxs[i].get_missing_signatures(tally)
                        if missing:
                            outcomes[i] = SignaturesMissingException(
                                missing,
                                [k.to_string_short() for k in missing],
                                stxs[i].id)
                        else:
                            covered.append(i)
                    alive = covered
                self.metrics.meter("Verifier.RequiredKeys").mark(tally[0])
                self.metrics.meter("Verifier.CompositeRequired").mark(
                    tally[1])
                self.metrics.meter("Verifier.CompositeLeafVisits").mark(
                    tally[2])
            contracts: dict = {}    # class name -> [runs, nanoseconds]
            with tracer.span("verifier.wave.rules", parent=ctx, cpu=True,
                             n_tx=len(alive)):
                for i in alive:
                    try:
                        stxs[i].to_ledger_transaction(services).verify(
                            contracts)
                    except Exception as e:
                        outcomes[i] = e
            for name, (runs, nanos) in contracts.items():
                self.metrics.meter(f"Verifier.ContractRuns.{name}").mark(runs)
                self.metrics.meter(f"Verifier.ContractMicros.{name}").mark(
                    nanos // 1000)
        except BaseException as exc:    # never a member left unresolved
            outcomes = [exc if o is None else o for o in outcomes]
            raise
        finally:
            self._resolve_wave(members, outcomes, wave, t0)

    def _resolve_wave(self, members, outcomes, wave, t0) -> None:
        failed = sum(o is not None for o in outcomes)
        self.metrics.meter("Verification.Success").mark(
            len(outcomes) - failed)
        self.metrics.meter("Verification.Failure").mark(failed)
        self.metrics.counter("Verification.InFlight").dec(len(outcomes))
        took = time.perf_counter() - t0
        hist = self.metrics.histogram("tx_verify_seconds")
        for fut, outcome in zip(members, outcomes):
            hist.update(took)
            if fut.done():
                continue
            if outcome is None:
                fut.set_result(None)
            else:
                fut.set_exception(outcome)
        wave.set_tag("failed", failed)
        wave.finish()

    def shutdown(self) -> None:
        super().shutdown()
        self.batcher.close()


def make_verifier_service(verifier_type: str = "InMemory", **kwargs
                          ) -> TransactionVerifierService:
    """The VerifierType config seam (NodeConfiguration.kt:91-94):
    "InMemory" | "Tpu" | "OutOfProcess".

    NOTE on the Tpu backend: ``verify_signed(stx, ...)``,
    ``verify_wave(stxs, ...)`` and ``verify_levels(levels, ...)`` are the
    calls that pay off on device — the reference-shaped ``verify(ltx)`` SPI
    verifies contract and platform rules only (an ltx's signatures are
    already checked by the time it exists), so callers holding
    SignedTransactions should use them. The node's flow path does: the SMM's
    Verify suspension point routes through ``verify_signed`` and its
    VerifyMany through ``verify_levels``, which takes a walk's levels whole
    and in order and admits each LEVEL by its own size: at or over the
    batcher's crossover ONE bulk burst, under it member by member (locked
    by tests/test_verify_suspension.py's device-batch assertions). A
    service without ``verify_levels`` (InMemory, OutOfProcess, a custom
    one) is handed a VerifyMany's members through ``verify_signed`` one by
    one, with the same view over the walk for resolution, and the SMM
    applies the prefix rule to the outcomes.

    "OutOfProcess" needs ``network_service=`` (the node's messaging — the
    queue the worker fleet attaches to); ``expected_workers=`` sizes the
    fleet for /readyz degradation reporting."""
    if verifier_type == "InMemory":
        return InMemoryTransactionVerifierService(**kwargs)
    if verifier_type == "Tpu":
        return TpuTransactionVerifierService(**kwargs)
    if verifier_type == "OutOfProcess":
        from .out_of_process import OutOfProcessTransactionVerifierService
        return OutOfProcessTransactionVerifierService(**kwargs)
    raise ValueError(f"Unknown verifier type: {verifier_type}")
