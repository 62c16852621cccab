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

import time
from concurrent.futures import Future, ThreadPoolExecutor

from ..core.crypto.signatures import SignatureException
from ..observability import get_tracer
from ..utils.metrics import MetricRegistry
from .batcher import SignatureBatcher


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

    def _submit_instrumented(self, work_fn, trace_ctx=None) -> Future:
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

        return self._pool.submit(work)

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

    #: verify_signed takes ``wave_rows`` (the SMM's VerifyMany passes the
    #: wave's signature count to a service that says so)
    supports_wave_rows = True

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
                      trace_ctx=None, wave_rows: int | None = None) -> Future:
        """Async full verify of a SignedTransaction; the per-signature EC math
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
        batch. ``wave_rows`` is the signature count of the ``VerifyMany``
        wave ``stx`` belongs to: the batcher judges a member by it."""
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

            return self._submit_instrumented(work, trace_ctx=ctx)
        except Exception as exc:
            # submission failed (e.g. closed batcher / shut-down pool): the
            # root span must still close and the caller must get a FAILED
            # FUTURE, not an exception — verify_signed's contract is async
            root.finish()
            failed: Future = Future()
            failed.set_exception(exc)
            return failed

    def shutdown(self) -> None:
        super().shutdown()
        self.batcher.close()


def make_verifier_service(verifier_type: str = "InMemory", **kwargs
                          ) -> TransactionVerifierService:
    """The VerifierType config seam (NodeConfiguration.kt:91-94):
    "InMemory" | "Tpu" | "OutOfProcess".

    NOTE on the Tpu backend: only ``verify_signed(stx, ...)`` pays off on
    device — the reference-shaped ``verify(ltx)`` SPI verifies contract and
    platform rules only (an ltx's signatures are already checked by the time
    it exists), so callers holding a SignedTransaction should use
    ``verify_signed``. The node's flow path does (the SMM's Verify
    suspension point routes through verify_signed; locked by
    tests/test_verify_suspension.py's device-batch assertion).

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
