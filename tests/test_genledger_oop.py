"""The ``genledger-oop`` deployment on the system's normal path: a requestor
hands whole generated transactions over the TCP plane to a ``VerifierWorker``
and every answer equals the plain reference's; the worker gathers one-request
groups into ONE device flush, lets a lone request through at once, and keeps
its spans and meters; the pieces that carry it (``submit_groups``, the
transports' ``inbound_backlog``, a send that does not wait for the loop, the
queue's running load estimate)."""
import asyncio
import pathlib
import sys
import threading
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
for _p in (str(BENCH), str(BENCH.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import oop_ledgers  # noqa: E402
from reference import genledger_oop as ref  # noqa: E402

import corda_tpu.core.transactions  # noqa: E402,F401
from corda_tpu.core.crypto import generate_keypair  # noqa: E402
from corda_tpu.core.crypto.signatures import Crypto  # noqa: E402
from corda_tpu.core.serialization import deserialize  # noqa: E402
from corda_tpu.network.inmemory import InMemoryMessagingNetwork  # noqa: E402
from corda_tpu.network.messaging import TopicSession  # noqa: E402
from corda_tpu.network.tcp import TcpMessagingService  # noqa: E402
from corda_tpu.observability import (disable_tracing,  # noqa: E402
                                     enable_tracing)
from corda_tpu.testing.services import MockServices  # noqa: E402
from corda_tpu.utils.faults import FaultRule, inject  # noqa: E402
from corda_tpu.verifier.batcher import SignatureBatcher  # noqa: E402
from corda_tpu.verifier.out_of_process import (  # noqa: E402
    OutOfProcessTransactionVerifierService, VerifierWorker)

KP = generate_keypair(entropy=b"\x61" * 32)
CONTENT = b"\x07" * 32
SIG = Crypto.sign_with_key(KP, CONTENT).bytes
ROW = (KP.public, SIG, CONTENT)


def _count(registry, name):
    return registry.snapshot().get(name, {}).get("count", 0)


def _stub_device(batcher):
    """Host verdicts in the kernels' place; returns the flushes' rows."""
    flushes = []

    def device(bucket, items, reason="full", bctx=None):
        flushes.append((len(items), reason))
        batcher._mark_device(items)
        batcher._resolve(bucket, items, batcher._run_host(items), bctx)

    batcher._dispatch_device = device
    return flushes


def _literal(name):
    host, _, port = name.rpartition(":")
    return host, int(port)


def _endpoint(name):
    m = TcpMessagingService(name, "127.0.0.1", 0, _literal)
    m._name = f"127.0.0.1:{m.port}"
    return m


def _verdict(fut) -> str:
    exc = fut.exception(timeout=120)
    if exc is None:
        return ref.VALID
    if type(exc).__name__ == "SignaturesMissingException":
        return ref.MISSING_SIGNER
    assert "did not verify" in str(exc), exc
    return ref.BAD_SIGNATURE


@pytest.fixture(scope="module")
def ledger():
    """256 transactions, 1 in 16 invalid: the four kinds four times."""
    return oop_ledgers.make_ledger((7, 256, 8, 16, 0))


def test_every_answer_over_tcp_equals_the_plain_reference(ledger):
    want = ref.verdicts(ledger["facts"])
    assert sorted(ledger["kinds"].values()) == sorted([0, 1, 2, 3] * 4)
    assert all(want[i] == oop_ledgers.VERDICTS[k]
               for i, k in ledger["kinds"].items())
    assert want.count(ref.VALID) == 256 - 16
    node, plane = _endpoint("node"), _endpoint("worker")
    svc = OutOfProcessTransactionVerifierService(node)
    frames = []
    node.add_message_handler(
        TopicSession("verifier.responses"),
        lambda m: frames.append(deserialize(m.data).verification_id))
    batcher = SignatureBatcher(use_device=False, max_latency_s=0.05)
    worker = VerifierWorker(plane, node.my_address, batcher=batcher)
    try:
        services = MockServices()
        txs = [deserialize(b) for b in ledger["stx"]]
        services.record_transactions(*txs)
        deadline = time.monotonic() + 10
        while svc.queue.worker_count < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        futures = [svc.verify_signed(stx, services) for stx in txs]
        got = [_verdict(f) for f in futures]
        assert got == want
        # the fourth kind never left the node; the other three were refused
        # by the worker, which answered every request it got exactly once
        node_side = sum(k == 3 for k in ledger["kinds"].values())
        time.sleep(0.2)
        assert sorted(frames) == list(range(1, 256 - node_side + 1))
        assert worker.verified_count == 256 - node_side
        assert _count(worker.metrics, "Verifier.RequestsIn") \
            == _count(worker.metrics, "Verifier.ResponsesOut") \
            == 256 - node_side
        assert _count(worker.metrics, "Verifier.BytesIn") > 256 * 500
        assert svc.metrics.snapshot()["Verification.Failure"]["count"] == 12
    finally:
        worker.stop(announce=False)
        svc.shutdown()
        plane.stop()
        node.stop()


def test_the_reference_recomputes_the_id_the_signatures_are_over(ledger):
    txs = [deserialize(b) for b in ledger["stx"]]
    for stx, (blobs, sigs, required) in zip(txs[:32], ledger["facts"]):
        assert ref.transaction_id(blobs) == stx.id.bytes
        assert [pub for pub, _s in sigs] == [s.by.encoded for s in stx.sigs]
        assert required == [k.encoded for k in stx.tx.must_sign]
    source = (BENCH / "reference" / "genledger_oop.py").read_text()
    assert "import corda_tpu" not in source and "from corda_tpu" not in source


def test_a_ledger_is_the_same_for_a_seed_and_the_kinds_rotate():
    a = oop_ledgers.make_ledger((11, 64, 4, 8, 2))
    b = oop_ledgers.make_ledger((11, 64, 4, 8, 2))
    c = oop_ledgers.make_ledger((12, 64, 4, 8, 2))
    assert a["stx"] == b["stx"] and a["kinds"] == b["kinds"]
    assert a["stx"] != c["stx"]
    assert sorted(a["kinds"].values()) == sorted((2 + k) % 4
                                                 for k in range(8))
    assert oop_ledgers.ledger_seeds(2**31 + 5, 8) \
        == oop_ledgers.ledger_seeds(2**31 + 5, 8)
    assert len(set(oop_ledgers.ledger_seeds(2**31 + 5, 8))) == 8


def _bus_pair(**batcher_args):
    bus = InMemoryMessagingNetwork()
    svc = OutOfProcessTransactionVerifierService(bus.create_node("node"))
    batcher = SignatureBatcher(**batcher_args)
    flushes = _stub_device(batcher)
    worker = VerifierWorker(bus.create_node("w1"), "node", batcher=batcher)
    bus.run_network()
    return bus, svc, batcher, worker, flushes


def _pump(bus, futures, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not all(f.done() for f in futures):
        bus.run_network()
        time.sleep(0.002)
        assert time.monotonic() < deadline, "verifications did not complete"


def test_512_one_request_groups_within_the_linger_are_one_device_flush():
    bus, svc, batcher, worker, flushes = _bus_pair(
        max_batch=512, host_crossover=4, max_latency_s=0.05)
    try:
        futures = [svc.verify_signatures([ROW]) for _ in range(512)]
        _pump(bus, futures)
        assert all(f.result() is None for f in futures)
        assert flushes == [(512, "max_batch")]
        assert _count(batcher.metrics, "SigBatcher.DeviceChecked") == 512
        assert _count(batcher.metrics, "SigBatcher.HostRouted") == 0
    finally:
        worker.stop()


def test_a_partial_bucket_goes_once_the_stream_stalls():
    bus, svc, batcher, worker, flushes = _bus_pair(
        max_batch=512, host_crossover=4, max_latency_s=0.05)
    try:
        futures = [svc.verify_signatures([ROW, ROW]) for _ in range(20)]
        t0 = time.monotonic()
        _pump(bus, futures)
        assert all(f.result() is None for f in futures)
        assert sum(rows for rows, _r in flushes) == 40 and len(flushes) == 1
        assert time.monotonic() - t0 >= 0.05       # it lingered
        assert worker._linger_thread is None or \
            not worker._linger_thread.is_alive() or not worker._backlog
    finally:
        worker.stop()


def test_the_pause_is_the_workers_own_measure_and_not_the_batchers_linger():
    """Ten switch intervals of the interpreter, whatever the batcher's
    linger is (5 ms by default), and that linger where it is longer."""
    bus, svc, batcher, worker, flushes = _bus_pair(
        max_batch=512, host_crossover=4)
    try:
        assert batcher.max_latency_s == 0.005
        assert worker._pause_s() == 10 * sys.getswitchinterval()
        batcher.max_latency_s = 7.0
        assert worker._pause_s() == 7.0
        batcher.max_latency_s = 0.005
        # at the batcher's default linger a stream whose requests come in
        # pairs 10 ms apart (two of its lingers) still leaves as ONE flush
        futures = []
        for _ in range(12):
            futures += [svc.verify_signatures([ROW]) for _ in range(2)]
            bus.run_network()
            time.sleep(0.01)
        _pump(bus, futures)
        assert [rows for rows, _reason in flushes] == [24]
    finally:
        worker.stop()


def test_no_partial_bucket_goes_while_answers_are_due():
    """A requestor that keeps a window outstanding sends its next requests
    when it has the answers: while a burst is unanswered, a pause admits
    nothing, and what is parked leaves together once the answers are out."""
    bus, svc, batcher, worker, flushes = _bus_pair(
        max_batch=64, host_crossover=4)
    held = threading.Event()
    device = batcher._dispatch_device

    def slow_device(bucket, items, reason="full", bctx=None):
        held.wait(timeout=30)
        device(bucket, items, reason, bctx)

    batcher._dispatch_device = slow_device
    try:
        first = [svc.verify_signatures([ROW, ROW]) for _ in range(32)]
        bus.run_network()                   # a full bucket: admitted, held
        later = []
        for _ in range(3):                  # three trickles, a pause apart
            later += [svc.verify_signatures([ROW]) for _ in range(5)]
            bus.run_network()
            time.sleep(3 * worker._pause_s())
        with worker._backlog_lock:
            assert (len(worker._backlog), worker._inflight_groups) == (15, 32)
        assert flushes == []
        held.set()
        _pump(bus, first + later)
        assert flushes == [(64, "max_batch"), (15, flushes[1][1])]
    finally:
        held.set()
        worker.stop()


def test_the_worker_leaves_in_its_transport_what_it_cannot_use():
    """Two buckets' worth held (one being answered, one filling or admitted):
    the transport's thread waits in the handler and the frames behind stay
    frames, until an answer frees room."""
    bus, svc, batcher, worker, flushes = _bus_pair(
        max_batch=8, host_crossover=4)
    held = threading.Event()
    device = batcher._dispatch_device

    def slow_device(bucket, items, reason="full", bctx=None):
        held.wait(timeout=30)
        device(bucket, items, reason, bctx)

    batcher._dispatch_device = slow_device
    try:
        futures = [svc.verify_signatures([ROW]) for _ in range(24)]
        pump = threading.Thread(target=_pump, args=(bus, futures),
                                daemon=True)
        pump.start()
        deadline = time.monotonic() + 10
        while _count(worker.metrics, "Verifier.RequestsIn") < 16:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        time.sleep(0.2)
        assert _count(worker.metrics, "Verifier.RequestsIn") == 16
        assert worker.network_service.inbound_backlog() == 8
        with worker._backlog_lock:
            assert worker._backlog_sigs + worker._inflight_sigs == 16
        held.set()
        pump.join(timeout=30)
        assert all(f.result(timeout=30) is None for f in futures)
        assert sum(rows for rows, _reason in flushes) == 24
    finally:
        held.set()
        worker.stop()


def test_a_lone_request_does_not_linger():
    """Nothing parked, nothing in flight, nothing behind it: admitted at
    once, however long the linger (today's short path)."""
    bus, svc, batcher, worker, flushes = _bus_pair(
        max_batch=512, host_crossover=4, max_latency_s=30.0)
    try:
        t0 = time.monotonic()
        fut = svc.verify_signatures([ROW])
        _pump(bus, [fut])
        assert fut.result() is None
        assert time.monotonic() - t0 < 5.0
        assert worker._linger_thread is None
        assert _count(batcher.metrics, "SigBatcher.HostRouted") == 1
        assert flushes == []
    finally:
        worker.stop()


def test_a_finite_window_admits_one_group_at_a_time_as_before():
    bus = InMemoryMessagingNetwork()
    svc = OutOfProcessTransactionVerifierService(bus.create_node("node"))
    batcher = SignatureBatcher(use_device=False)
    worker = VerifierWorker(bus.create_node("w1"), "node", batcher=batcher,
                            max_inflight_groups=1)
    bus.run_network()
    try:
        futures = [svc.verify_signatures([ROW]) for _ in range(6)]
        _pump(bus, futures)
        assert all(f.result() is None for f in futures)
        assert worker._linger_thread is None
        assert worker.processed_sig_count == 6
    finally:
        worker.stop()


def test_the_worker_keeps_its_own_spans_and_meters():
    tracer = enable_tracing(4096)
    bus, svc, batcher, worker, flushes = _bus_pair(
        max_batch=64, host_crossover=4, max_latency_s=0.02)
    try:
        # the requestor's tracer is this process's too, so requests carry a
        # context; the worker's local spans are recorded either way
        futures = [svc.verify_signatures([ROW]) for _ in range(8)]
        _pump(bus, futures)
        spans = [s for ss in tracer.traces().values() for s in ss]
        by_name: dict = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        assert len(by_name["worker.decode"]) == 8
        for name in ("worker.backlog_wait", "worker.device_dispatch",
                     "worker.host_verify", "worker.reply"):
            local = [s for s in by_name[name]
                     if s["tags"].get("n_requests")]
            assert sum(s["tags"]["n_requests"] for s in local) == 8, name
            assert all(s["duration_s"] >= 0 for s in local)
        assert all(s["tags"]["bytes"] > 100 for s in by_name["worker.decode"])
        assert _count(worker.metrics, "Verifier.RequestsIn") == 8
        assert _count(worker.metrics, "Verifier.ResponsesOut") == 8
        assert worker.metrics is batcher.metrics
    finally:
        worker.stop()
        disable_tracing()


def test_submit_groups_is_one_enqueue_and_a_verdict_list_a_group():
    b = SignatureBatcher(max_batch=8, host_crossover=4, max_latency_s=30.0)
    flushes = _stub_device(b)
    bad = (KP.public, SIG[:-1] + bytes([SIG[-1] ^ 1]), CONTENT)
    try:
        futures = b.submit_groups([[ROW], [ROW, bad], [], [ROW] * 5])
        assert [f.result(timeout=30) for f in futures] \
            == [[True], [True, False], [], [True] * 5]
        assert flushes == [(8, "max_batch")]       # no linger: the cap
    finally:
        b.close()


def test_a_device_flush_is_metered_by_rows_reason_and_padded_rows():
    b = SignatureBatcher(max_batch=16, host_crossover=0, max_latency_s=0.01)
    try:
        assert b.submit_group([ROW] * 16).result(timeout=600) == [True] * 16
        snap = b.metrics.snapshot()
        assert snap["SigBatcher.DeviceFlush.max_batch"]["count"] == 1
        assert snap["SigBatcher.DevicePadded.16"]["count"] == 1
        assert snap["verifier_device_batch_rows"]["sum"] == 16
    finally:
        b.close()


def test_tcp_frames_arrive_in_order_and_the_backlog_is_what_waits():
    a, b = _endpoint("a"), _endpoint("b")
    got, gate = [], threading.Event()
    seen_backlog = []

    def handler(msg):
        if not got:
            gate.wait(timeout=10)           # hold the executor: frames queue
            seen_backlog.append(b.inbound_backlog())
        got.append(int.from_bytes(msg.data, "big"))

    b.add_message_handler(TopicSession("t"), handler)
    try:
        assert a.inbound_backlog() == 0
        for i in range(2000):
            a.send(TopicSession("t"), i.to_bytes(4, "big"), b.my_address)
        deadline = time.monotonic() + 10
        while b.inbound_backlog() < 1999:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        gate.set()
        while len(got) < 2000:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert got == list(range(2000))
        assert seen_backlog == [1999] and b.inbound_backlog() == 0
        assert not a._out_pending        # every frame off the books
    finally:
        a.stop()
        b.stop()


def test_frames_that_gathered_leave_in_one_write_and_a_fault_takes_one_frame(
        monkeypatch):
    """A socket write lets go of the interpreter lock, and beside a thread
    that computes the loop thread waits a switch interval to get it back: a
    write a frame would be 200 frames a second. What gathered while the
    loop looked away goes out in ONE write; an injected drop, a duplicate
    and a raise (retried) still each take one frame."""
    a, b = _endpoint("a"), _endpoint("b")
    got = []
    b.add_message_handler(
        TopicSession("t"),
        lambda m: got.append(int.from_bytes(m.data, "big")))

    def wait_for(n):
        deadline = time.monotonic() + 10
        while len(got) < n:
            assert time.monotonic() < deadline, len(got)
            time.sleep(0.01)

    try:
        a.send(TopicSession("t"), (0).to_bytes(4, "big"), b.my_address)
        wait_for(1)                             # the connection is up
        writes, real = [], asyncio.StreamWriter.write

        def counted(self, data):
            if self.get_extra_info("peername")[1] == b.port:
                writes.append(len(data))
            return real(self, data)

        monkeypatch.setattr(asyncio.StreamWriter, "write", counted)
        away = threading.Event()
        a._loop.call_soon_threadsafe(away.wait, 10)     # the loop looks away
        rules = (FaultRule("tcp.send", "drop", after=10, count=3),
                 FaultRule("tcp.send", "duplicate", after=100, count=1),
                 FaultRule("tcp.send", "raise", after=200, count=1))
        with inject(*rules, seed=1) as inj:
            for i in range(1, 501):
                a.send(TopicSession("t"), i.to_bytes(4, "big"), b.my_address)
            assert sum(a._out_pending.values()) == 500
            away.set()
            wait_for(1 + 500 - 3 + 1)
            assert inj.fired("tcp.send") == 5
        # a rule counts the frames the rules before it let pass
        want = [i for i in range(501) if i not in (11, 12, 13)]
        want.insert(want.index(104), 104)
        assert got == want
        # the raise cost a fresh connection, not a second write
        assert len(writes) == 1 and writes[0] > 500 * 20
        assert not a._out_pending
    finally:
        a.stop()
        b.stop()


def test_a_buffered_burst_of_frames_gives_the_loops_other_tasks_their_turn():
    """A burst that is already in the reader's buffer is read without one
    suspension, so what the endpoint has to SEND waited until the whole burst
    was taken: to a worker whose requestor had just got a bucket's answers in
    one write, the stream of requests paused. The connection yields every
    ``READ_BATCH_FRAMES`` frames."""
    from corda_tpu.core.serialization import serialize
    from corda_tpu.network import tcp
    a = _endpoint("a")
    n = 50 * tcp.READ_BATCH_FRAMES

    class Writer:
        def get_extra_info(self, _name):
            return None

        def close(self):
            pass

    async def burst():
        reader = asyncio.StreamReader(limit=2 ** 24)
        for i in range(n):
            body = serialize(["t", 0, "peer", i.to_bytes(4, "big")])
            reader.feed_data(len(body).to_bytes(4, "big") + body)
        reader.feed_eof()
        turns = 0

        async def other_task():
            nonlocal turns
            while True:
                turns += 1
                await asyncio.sleep(0)

        other = asyncio.ensure_future(other_task())
        await a._handle_connection(reader, Writer())
        other.cancel()
        return turns

    try:
        before = a._frames_queued
        turns = asyncio.run(burst())
        assert a._frames_queued - before == n
        assert turns >= n // tcp.READ_BATCH_FRAMES
    finally:
        a.stop()


def test_the_bus_says_what_stands_behind_a_message():
    bus = InMemoryMessagingNetwork()
    a, b = bus.create_node("a"), bus.create_node("b")
    behind = []
    b.add_message_handler(TopicSession("t"),
                          lambda m: behind.append(b.inbound_backlog()))
    for _ in range(3):
        a.send(TopicSession("t"), b"x", "b")
    bus.run_network()
    assert behind == [2, 1, 0]


def test_the_queue_keeps_its_load_estimate_as_it_changes():
    bus = InMemoryMessagingNetwork()
    svc = OutOfProcessTransactionVerifierService(bus.create_node("node"))
    q = svc.queue
    worker = VerifierWorker(bus.create_node("w1"), "node",
                            batcher=SignatureBatcher(use_device=False))
    bus.run_network()
    try:
        futures = [svc.verify_signatures([ROW, ROW]) for _ in range(5)]
        with q._lock:
            assert q._est_load_locked("w1", time.monotonic()) == 10
            assert list(q._outstanding["w1"]) == [1, 2, 3, 4, 5]
        _pump(bus, futures)
        with q._lock:
            assert q._est_load_locked("w1", time.monotonic()) == 0
            assert not q._outstanding["w1"] and not q._dealt_at
        # a load report accounts for what was dealt before it
        pending = svc.verify_signatures([ROW])
        worker.send_load_report()
        bus.run_network(rounds=1, exclude=("w1",))
        with q._lock:
            assert q._dealt_since["w1"] == 0
        _pump(bus, [pending])
    finally:
        worker.stop()
