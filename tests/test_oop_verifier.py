"""Out-of-process verifier tests — VerifierTests.kt parity:
"verification works with N out-of-process verifiers", work redistribution on
verifier death, failure propagation, no-worker warning path.
"""
import pytest

from corda_tpu.core.contracts import Command, TransactionState
from corda_tpu.core.contracts.exceptions import TransactionVerificationException
from corda_tpu.core.crypto import generate_keypair
from corda_tpu.core.identity import Party
from corda_tpu.core.transactions import WireTransaction
from corda_tpu.network.inmemory import InMemoryMessagingNetwork
from corda_tpu.testing import DummyContract, DummyState, DUMMY_NOTARY_NAME
from corda_tpu.verifier.out_of_process import (
    OutOfProcessTransactionVerifierService, VerifierWorker)

NOTARY = Party(DUMMY_NOTARY_NAME, generate_keypair(entropy=b"\x51" * 32).public)
ALICE_KP = generate_keypair(entropy=b"\x52" * 32)


def make_ltx(i, valid=True):
    from corda_tpu.core.contracts.structures import AuthenticatedObject
    from corda_tpu.core.transactions.ledger import LedgerTransaction
    wtx = WireTransaction(
        outputs=(TransactionState(DummyState(i, (ALICE_KP.public,)), NOTARY),),
        commands=(Command(DummyContract.Create(), (ALICE_KP.public,)),),
        notary=NOTARY, must_sign=(ALICE_KP.public,) if valid else ())
    return LedgerTransaction(
        inputs=(), outputs=wtx.outputs,
        commands=tuple(AuthenticatedObject(c.signers, (), c.value)
                       for c in wtx.commands),
        attachments=(), id=wtx.id, notary=wtx.notary, must_sign=wtx.must_sign,
        type=wtx.type, time_window=None)


@pytest.fixture
def bus():
    return InMemoryMessagingNetwork()


def test_single_worker_verifies(bus):
    node = bus.create_node("node")
    svc = OutOfProcessTransactionVerifierService(node)
    worker = VerifierWorker(bus.create_node("w1"), "node")
    bus.run_network()
    futures = [svc.verify(make_ltx(i)) for i in range(20)]
    bus.run_network()
    for f in futures:
        assert f.result(timeout=1) is None
    assert worker.verified_count == 20
    snap = svc.metrics.snapshot()
    assert snap["Verification.Success"]["count"] == 20


def test_work_is_shared_across_workers(bus):
    node = bus.create_node("node")
    svc = OutOfProcessTransactionVerifierService(node)
    workers = [VerifierWorker(bus.create_node(f"w{i}"), "node")
               for i in range(4)]
    bus.run_network()
    futures = [svc.verify(make_ltx(i)) for i in range(40)]
    bus.run_network()
    for f in futures:
        assert f.result(timeout=1) is None
    counts = [w.verified_count for w in workers]
    assert all(c == 10 for c in counts), counts  # round-robin deal


def test_redistribution_on_worker_death(bus):
    node = bus.create_node("node")
    svc = OutOfProcessTransactionVerifierService(node)
    w1 = VerifierWorker(bus.create_node("w1"), "node")
    w2 = VerifierWorker(bus.create_node("w2"), "node")
    bus.run_network()
    futures = [svc.verify(make_ltx(i)) for i in range(30)]
    # w1 dies BEFORE pumping: its dealt share is still in flight
    w1.stop(announce=False)
    svc.queue.detach_worker("w1")
    bus.run_network()
    for f in futures:
        assert f.result(timeout=1) is None
    assert w1.verified_count == 0
    assert w2.verified_count == 30


def test_failure_propagates(bus):
    node = bus.create_node("node")
    svc = OutOfProcessTransactionVerifierService(node)
    VerifierWorker(bus.create_node("w1"), "node")
    bus.run_network()
    fut = svc.verify(make_ltx(1, valid=False))  # required signer missing
    bus.run_network()
    with pytest.raises(TransactionVerificationException):
        fut.result(timeout=1)
    assert svc.metrics.snapshot()["Verification.Failure"]["count"] == 1


def _pump_until(bus, futures, timeout=300.0):
    """Pump the manual bus until every future resolves (the device path
    replies from worker threads, so replies land between pumps). The limit
    only ends a hang: three EC kernels' trace + lower take 60 s here alone
    and over 90 s beside five other test workers."""
    import time
    deadline = time.monotonic() + timeout
    while not all(f.done() for f in futures):
        bus.run_network()
        time.sleep(0.005)
        assert time.monotonic() < deadline, "verifications did not complete"


def test_device_path_through_worker(bus):
    """VERDICT r2 #1a: requests carrying signatures run their EC math through
    the worker's device batcher — the out-of-process scale-out story with
    the TPU actually in the worker."""
    from corda_tpu.testing.generated_ledger import make_generated_ledger
    from corda_tpu.testing.services import MockServices
    from corda_tpu.verifier.batcher import SignatureBatcher

    ledger = make_generated_ledger(12, seed=7)
    services = MockServices()
    for stx in ledger.transactions:
        services.record_transactions(stx)
    node = bus.create_node("node")
    svc = OutOfProcessTransactionVerifierService(node)
    batcher = SignatureBatcher(use_device=True, host_crossover=0,
                               max_latency_s=0.01)
    worker = VerifierWorker(bus.create_node("w1"), "node", batcher=batcher)
    bus.run_network()
    futures = [svc.verify_signed(stx, services)
               for stx in ledger.transactions]
    _pump_until(bus, futures)
    for f in futures:
        assert f.result(timeout=1) is None
    snap = batcher.metrics.snapshot()
    assert snap["SigBatcher.DeviceBatches"]["count"] > 0
    assert snap["SigBatcher.DeviceChecked"]["count"] >= len(futures)
    worker.stop()


def test_device_path_rejects_bad_signature(bus):
    """A transaction whose signature does not match its id must fail through
    the worker device path with a signature error."""
    from corda_tpu.core.crypto.signatures import Crypto
    from corda_tpu.core.transactions.signed import SignedTransaction
    from corda_tpu.core.transactions.wire import WireTransaction
    from corda_tpu.testing.services import MockServices
    from corda_tpu.verifier.batcher import SignatureBatcher

    wtx = WireTransaction(
        outputs=(TransactionState(DummyState(1, (ALICE_KP.public,)), NOTARY),),
        commands=(Command(DummyContract.Create(), (ALICE_KP.public,)),),
        notary=NOTARY, must_sign=(ALICE_KP.public,))
    bad_sig = Crypto.sign_with_key(ALICE_KP, b"some other content")
    stx = SignedTransaction.of(wtx, [bad_sig])

    node = bus.create_node("node")
    svc = OutOfProcessTransactionVerifierService(node)
    batcher = SignatureBatcher(use_device=True, host_crossover=0,
                               max_latency_s=0.01)
    worker = VerifierWorker(bus.create_node("w1"), "node", batcher=batcher)
    bus.run_network()
    fut = svc.verify_signed(stx, MockServices())
    _pump_until(bus, [fut])
    with pytest.raises(TransactionVerificationException,
                       match="did not verify"):
        fut.result(timeout=1)
    worker.stop()


def test_requests_queue_until_worker_attaches(bus):
    node = bus.create_node("node")
    svc = OutOfProcessTransactionVerifierService(node)
    futures = [svc.verify(make_ltx(i)) for i in range(5)]
    bus.run_network()
    assert not any(f.done() for f in futures)
    VerifierWorker(bus.create_node("late"), "node")
    bus.run_network()
    for f in futures:
        assert f.result(timeout=1) is None


def test_fleet_status_and_worker_gauges(bus):
    """Hello carries device shard + capacity; the node exposes them via
    fleet_status() (the /readyz payload) and per-worker Fleet.* gauges on
    the metrics registry (the /metrics payload)."""
    node = bus.create_node("node")
    svc = OutOfProcessTransactionVerifierService(node, expected_workers=2)
    w1 = VerifierWorker(bus.create_node("w1"), "node",
                        device_shard=(0, 1), capacity=2)
    bus.run_network()

    status = svc.fleet_status()
    assert status["expected"] == 2
    assert status["attached"] == 1
    assert status["degraded"] is True          # 1 of 2 → degraded
    assert status["workers"]["w1"]["device_shard"] == [0, 1]
    assert status["workers"]["w1"]["capacity"] == 2

    snap = svc.metrics.snapshot()
    assert snap["Fleet.WorkersAttached"]["value"] == 1
    assert snap["Fleet.WorkerCapacity.w1"]["value"] == 2
    assert snap["Fleet.WorkerQueueDepth.w1"]["value"] == 0

    w2 = VerifierWorker(bus.create_node("w2"), "node")
    bus.run_network()
    status = svc.fleet_status()
    assert status["attached"] == 2 and status["degraded"] is False

    w2.stop()   # graceful goodbye detaches; gauges read 0, not KeyError
    bus.run_network()
    snap = svc.metrics.snapshot()
    assert svc.fleet_status()["degraded"] is True
    assert snap["Fleet.WorkerCapacity.w2"]["value"] == 0
    w1.stop()


def test_load_aware_routing_prefers_idle_worker(bus):
    """A worker reporting a deep backlog must stop receiving new deals
    while an idle worker is in the slack band."""
    from corda_tpu.verifier.out_of_process import WorkerLoadReport
    node = bus.create_node("node")
    svc = OutOfProcessTransactionVerifierService(node)
    busy = VerifierWorker(bus.create_node("busy"), "node")
    idle = VerifierWorker(bus.create_node("idle"), "node")
    bus.run_network()

    # hand-deliver the reports (deterministic: no worker threads involved)
    svc.queue._on_load_report(WorkerLoadReport("busy", pending=64,
                                               in_flight=12))
    svc.queue._on_load_report(WorkerLoadReport("idle", pending=0,
                                               in_flight=0))
    futures = [svc.verify(make_ltx(i)) for i in range(8)]
    bus.run_network()
    for f in futures:
        assert f.result(timeout=1) is None
    assert idle.verified_count == 8
    assert busy.verified_count == 0
    busy.stop()
    idle.stop()


def test_submit_spans_finish_exactly_once_across_crash_requeue(bus):
    """Regression: the node-side verifier.oop_submit span must finish
    EXACTLY once per request even when the dealt worker crashes and the
    share is requeued to a survivor — no leaked live spans in svc._spans,
    no duplicate finished spans in the ring."""
    from corda_tpu.observability import disable_tracing, enable_tracing
    tracer = enable_tracing()
    try:
        node = bus.create_node("node")
        svc = OutOfProcessTransactionVerifierService(node)
        w1 = VerifierWorker(bus.create_node("w1"), "node")
        w2 = VerifierWorker(bus.create_node("w2"), "node")
        bus.run_network()
        futures = [svc.verify(make_ltx(i)) for i in range(10)]
        # w1 dies BEFORE pumping: its dealt share is requeued to w2
        w1.stop(announce=False)
        svc.queue.detach_worker("w1")
        bus.run_network()
        for f in futures:
            assert f.result(timeout=1) is None
        # every submit span finished exactly once, none leaked live
        assert svc._spans == {}
        submits = [s for s in tracer.ring.snapshot()
                   if s["name"] == "verifier.oop_submit"]
        assert len(submits) == len(futures)
        assert all(s["duration_s"] > 0 for s in submits)
        # the requeue left a lifecycle breadcrumb for the moved requests
        moved = [vid for vid, tl in
                 ((int(k), v) for k, v in svc.request_log.snapshot().items())
                 if any(e["event"] == "requeued" for e in tl)]
        assert moved, "no request recorded the worker-detached requeue"
        for vid in moved:
            assert svc.request_log.terminal_count(vid) == 1
        w2.stop()
    finally:
        disable_tracing()


def test_stale_worker_flagged_degraded(bus):
    """A worker whose last load report is older than 3× the report
    interval is flagged stale in fleet_status() — attached but possibly
    wedged — and the fleet reads degraded (the /readyz surface)."""
    import time
    node = bus.create_node("node")
    svc = OutOfProcessTransactionVerifierService(
        node, expected_workers=1, load_report_interval_s=0.02)
    w1 = VerifierWorker(bus.create_node("w1"), "node")
    bus.run_network()
    w1.send_load_report()
    bus.run_network()

    status = svc.fleet_status()
    assert status["workers"]["w1"]["stale"] is False
    assert status["workers"]["w1"]["last_report_age_s"] is not None
    assert status["stale"] == [] and status["degraded"] is False

    time.sleep(0.08)   # > 3× the 0.02s interval with no further report
    status = svc.fleet_status()
    assert status["workers"]["w1"]["stale"] is True
    assert status["stale"] == ["w1"]
    assert status["degraded"] is True

    w1.send_load_report()   # a fresh report clears the flag
    bus.run_network()
    status = svc.fleet_status()
    assert status["workers"]["w1"]["stale"] is False
    assert status["degraded"] is False
    w1.stop()
