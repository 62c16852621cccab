"""Fleet chaos tests: routing, stealing, and membership churn under faults.

The invariant inherited from test_chaos_oop and extended to the fleet
machinery: EVERY submitted verification future resolves EXACTLY ONCE —
a worker killed mid-batch, a worker joining into a bulk backlog, or a
steal racing the overdue-redelivery scan must never lose a future or
double-resolve one (Verification.Success marks only when the response
finds a live handle, so the success count IS the exactly-once witness).
"""
import time

import pytest

from corda_tpu.network.inmemory import InMemoryMessagingNetwork
from corda_tpu.testing.faults import FaultRule, inject
from corda_tpu.verifier.fleet import make_sig_checks
from corda_tpu.verifier.out_of_process import (
    OutOfProcessTransactionVerifierService, VerifierWorker)

pytestmark = pytest.mark.chaos

SEEDS = [7, 101, 9001]

GROUPS = 12
GROUP_SIZE = 4


@pytest.fixture
def bus():
    return InMemoryMessagingNetwork()


#: What a host check cost when these tests were written (two point decodes a
#: signature, in Python bigints). The signer table took them out (0.1 ms a
#: row now), and a backlog that has to stay parked while a steal is lost,
#: retried or raced by the overdue scan must not lean on that cost: every
#: worker here verifies at the old pace.
ROW_DELAY_S = 0.0007


def _host_worker(bus, name, max_inflight_groups=1):
    """A fleet worker on the host route (no kernels — chaos tests exercise
    protocol, not device math) with a finite in-flight window so a deep
    backlog stays parked and stealable."""
    from corda_tpu.verifier.batcher import SignatureBatcher
    batcher = SignatureBatcher(use_device=False, max_latency_s=0.002)
    run_host = batcher._run_host

    def paced(items):
        time.sleep(ROW_DELAY_S * len(items))
        return run_host(items)

    batcher._run_host = paced
    return VerifierWorker(
        bus.create_node(name), "node", batcher=batcher,
        use_device=False, capacity=1,
        max_inflight_groups=max_inflight_groups)


def _pump_until(bus, futures, workers=(), timeout=60.0):
    """Pump the bus (and the workers' load reports, so routing and steal
    decisions keep flowing) until every future resolves."""
    deadline = time.monotonic() + timeout
    last_report = 0.0
    while not all(f.done() for f in futures):
        bus.run_network()
        now = time.monotonic()
        if now - last_report > 0.01:
            last_report = now
            for w in workers:
                if w._alive:
                    w.send_load_report()
        time.sleep(0.002)
        assert time.monotonic() < deadline, \
            "fleet verifications did not complete"


def _assert_exactly_once(svc, futures):
    for f in futures:
        assert f.result(timeout=1) is None
    snap = svc.metrics.snapshot()
    assert snap["Verification.Success"]["count"] == len(futures)
    assert snap.get("Verification.Failure", {}).get("count", 0) == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_worker_killed_mid_batch_fleet(bus, seed):
    """A worker dies mid-batch with signature groups split between its
    batcher window and its stealable backlog: every reply it would have
    sent is dropped, it is killed, and the redelivery scan must move its
    WHOLE dealt share — admitted and parked alike — to the survivor."""
    svc = OutOfProcessTransactionVerifierService(bus.create_node("node"))
    svc.queue.redelivery_timeout_s = 0.1
    w1 = w2 = None
    try:
        w1 = _host_worker(bus, "w1")
        w2 = _host_worker(bus, "w2")
        bus.run_network()
        assert svc.queue.worker_count == 2

        checks = make_sig_checks(GROUP_SIZE, seed=seed)
        with inject(FaultRule("oop.reply", "drop", detail="w1->*"),
                    seed=seed):
            futures = [svc.verify_signatures(checks) for _ in range(GROUPS)]
            bus.run_network()
            w1.stop(announce=False)   # crash: no Goodbye, replies black-holed

            # keep pumping while the timeout elapses: the SURVIVOR's
            # trickling replies refresh its activity (the dual-condition
            # scan must flag only the silent dead worker, never a busy one)
            end = time.monotonic() + 0.25
            while time.monotonic() < end:
                bus.run_network()
                time.sleep(0.01)
            svc.queue.requeue_overdue()
            _pump_until(bus, futures, workers=[w2])

        _assert_exactly_once(svc, futures)
        assert svc.queue.worker_count == 1
        assert w2.processed_sig_count >= GROUPS * GROUP_SIZE // 2
    finally:
        for w in (w1, w2):
            if w is not None and w._alive:
                w.stop(announce=False)
        svc.shutdown()


@pytest.mark.parametrize("seed", SEEDS)
def test_worker_join_steals_from_bulk_backlog(bus, seed):
    """A worker joining while the only other worker holds a deep bulk
    backlog must receive work via a steal — including when the first
    StealRequest is LOST (the in-flight steal marker expires and the next
    idle report retries). Every future still resolves exactly once."""
    svc = OutOfProcessTransactionVerifierService(bus.create_node("node"))
    try:
        w1 = _host_worker(bus, "w1")
        bus.run_network()

        checks = make_sig_checks(GROUP_SIZE, seed=seed)
        futures = [svc.verify_signatures(checks) for _ in range(GROUPS)]
        bus.run_network()          # all dealt to the only worker
        w1.send_load_report()
        bus.run_network()          # node sees the deep backlog

        w2 = _host_worker(bus, "w2")
        bus.run_network()
        svc.queue.STEAL_TIMEOUT_S = 0.01   # lost-steal retry, test-speed
        with inject(FaultRule("oop.deliver", "drop", detail="->w1",
                              count=1), seed=seed) as inj:
            w2.send_load_report()  # idle report → steal → injected drop
            bus.run_network()
            assert inj.fired("oop.deliver") == 1
        time.sleep(0.02)           # expire the lost steal's marker
        _pump_until(bus, futures, workers=[w1, w2])

        _assert_exactly_once(svc, futures)
        assert svc.metrics.meter("Fleet.Steals").count >= 1
        # the joiner got work one way or the other: stolen-and-redealt, or
        # routed to it once the router saw the load imbalance
        assert w2.processed_sig_count > 0
        w1.stop(announce=False)
        w2.stop(announce=False)
    finally:
        svc.shutdown()


@pytest.mark.parametrize("seed", SEEDS)
def test_steal_racing_requeue_resolves_exactly_once(bus, seed):
    """The nastiest interleaving: a WorkReturned is in flight when the
    overdue scan declares the victim dead and requeues its whole share.
    The returned requests are no longer charged to the victim, so the
    node must IGNORE the stale return (no double-deal), and duplicated
    victim replies must not double-resolve any future."""
    svc = OutOfProcessTransactionVerifierService(bus.create_node("node"))
    try:
        w1 = _host_worker(bus, "w1")
        bus.run_network()
        checks = make_sig_checks(GROUP_SIZE, seed=seed)
        futures = [svc.verify_signatures(checks) for _ in range(GROUPS)]
        bus.run_network()
        w1.send_load_report()
        bus.run_network()

        w2 = _host_worker(bus, "w2")
        bus.run_network()
        # drain any queued w1 replies so the next node pump is the report
        bus.run_network()
        with inject(FaultRule("net.send", "duplicate", detail="w1->node"),
                    seed=seed):
            w2.send_load_report()
            # deliver ONLY the report to the node: the StealRequest goes
            # out to w1 but its WorkReturned must NOT be pumped yet
            while True:
                t = bus.pump_receive("node")
                assert t is not None, "load report never reached the node"
                if t.sender == "w2":
                    break
            assert bus.pump_receive("w1") is not None   # w1 sends the return
            # ... and NOW the victim goes overdue before the return lands
            svc.queue.redelivery_timeout_s = 0.05
            time.sleep(0.12)
            svc.queue.requeue_overdue()
            assert svc.queue.worker_count == 1   # w1 presumed dead
            _pump_until(bus, futures, workers=[w2])

        _assert_exactly_once(svc, futures)
        # the stale WorkReturned was ignored: nothing it carried was
        # re-dealt through the steal path after the requeue took them
        assert svc.metrics.meter("Fleet.Stolen").count == 0
        w1.stop(announce=False)
        w2.stop(announce=False)
    finally:
        svc.shutdown()


@pytest.mark.parametrize("seed", SEEDS)
def test_stolen_request_yields_one_stitched_trace(bus, seed):
    """Observability under churn: a request stolen mid-flight must still
    yield ONE stitched trace (node submit span + worker-side spans under
    the same trace id) and its lifecycle timeline must carry exactly one
    terminal resolution event — the steal hop adds events and spans, never
    duplicates or orphans them."""
    from corda_tpu.observability import Tracer, get_tracer, set_tracer
    prev_tracer = get_tracer()
    tracer = Tracer()
    set_tracer(tracer)
    svc = OutOfProcessTransactionVerifierService(bus.create_node("node"))
    try:
        w1 = _host_worker(bus, "w1")
        bus.run_network()
        checks = make_sig_checks(GROUP_SIZE, seed=seed)
        futures = [svc.verify_signatures(checks) for _ in range(GROUPS)]
        bus.run_network()          # all dealt to the only worker
        w1.send_load_report()
        bus.run_network()          # node sees the deep backlog

        w2 = _host_worker(bus, "w2")
        bus.run_network()
        w2.send_load_report()      # idle report → steal from w1's backlog
        bus.run_network()
        _pump_until(bus, futures, workers=[w1, w2])
        for f in futures:
            assert f.result(timeout=1) is None
        assert svc.metrics.meter("Fleet.Stolen").count >= 1
        # flush the victim's worker.stolen span outbox onto a load report
        w1.send_load_report()
        bus.run_network()

        timelines = svc.request_log.snapshot()
        assert len(timelines) == len(futures)
        stolen_vids = [int(vid) for vid, tl in timelines.items()
                       if any(e["event"] == "stolen" for e in tl)]
        assert stolen_vids, "no request recorded a steal hop"
        for vid in (int(v) for v in timelines):
            assert svc.request_log.terminal_count(vid) == 1, vid
        # no leaked live submit spans either
        assert svc._spans == {}
        for vid in stolen_vids:
            tl = timelines[str(vid)]
            stolen_ev = next(e for e in tl if e["event"] == "stolen")
            assert stolen_ev["victim"] == "w1"
            trace_id = next(e["trace_id"] for e in tl if "trace_id" in e)
            spans = tracer.trace(trace_id)
            names = [s["name"] for s in spans]
            assert names.count("verifier.oop_submit") == 1, names
            assert any(n.startswith("worker.") for n in names), names
            assert "worker.stolen" in names, names
            # every span of the stolen request is stitched into ONE trace
            assert {s["trace_id"] for s in spans} == {trace_id}
    finally:
        try:
            w1.stop(announce=False)
            w2.stop(announce=False)
        except Exception:
            pass
        svc.shutdown()
        set_tracer(prev_tracer)
