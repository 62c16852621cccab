"""The wait for the node's thread, named (ISSUE 25): ``flow.step`` per
scheduler step, ``wait.runnable`` from readiness to take-up, the ``session.*``
spans' real extent, ``thread`` on every span.

Deterministic: the tracer, the state machine and the bus read ONE fake clock
that moves only when the test moves it, or when a call the test knows to be
costly (serialize, deserialize, sign, checkpoint, verify, record) charges
its fixed price. No sleeps, no wall-clock thresholds: a millisecond that no
span names is a costly call outside every span."""
import threading
from concurrent.futures import Future

import numpy as np
import pytest

import corda_tpu.finance  # noqa: F401
from corda_tpu.core.contracts.amount import USD, Amount
from corda_tpu.finance import CashIssueFlow, CashPaymentFlow
from corda_tpu.flows.api import (AwaitFuture, FlowException, FlowLogic,
                                 Receive, Send, Sleep, VerifyMany,
                                 flow_name, initiating_flow)
from corda_tpu.network import inmemory
from corda_tpu.node import checkpoints, services, statemachine
from corda_tpu.observability import (disable_tracing, enable_tracing,
                                     tracing)
from corda_tpu.observability.critpath import component_of
from corda_tpu.observability.profiling import KernelProfiler
from corda_tpu.testing import MockNetwork

PRICE = {"serialize": 1e-3, "deserialize": 1e-3, "sign": 2e-3,
         "checkpoint": 0.5e-3, "verify": 3e-3, "record": 1e-3}


class FakeClock:
    """time / perf_counter / monotonic of the modules under test."""

    def __init__(self, start: float = 5_000.0):
        self.now = start

    def time(self) -> float:
        return self.now

    perf_counter = monotonic = time

    def advance(self, seconds: float) -> None:
        self.now += seconds


@pytest.fixture
def clock(monkeypatch):
    fake = FakeClock()
    monkeypatch.setattr(tracing, "time", fake)
    monkeypatch.setattr(statemachine, "_time", fake)
    monkeypatch.setattr(inmemory, "time", fake)
    monkeypatch.setattr(services, "_time", fake)

    def priced(fn, what):
        def wrapper(*a, **kw):
            fake.advance(PRICE[what])
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(statemachine, "serialize",
                        priced(statemachine.serialize, "serialize"))
    monkeypatch.setattr(statemachine, "deserialize",
                        priced(statemachine.deserialize, "deserialize"))
    monkeypatch.setattr(services.KeyManagementService, "sign", priced(
        services.KeyManagementService.sign, "sign"))
    monkeypatch.setattr(checkpoints.CheckpointStorage, "add_checkpoint",
                        priced(checkpoints.CheckpointStorage.add_checkpoint,
                               "checkpoint"))
    monkeypatch.setattr(services.ServiceHub, "record_transactions", priced(
        services.ServiceHub.record_transactions, "record"))
    return fake


@pytest.fixture
def tracer():
    tr = enable_tracing(65536)
    yield tr
    disable_tracing()


class ManualVerifier:
    """An async verifier whose futures the TEST completes: ``finish_one``
    verifies on the calling thread and resolves the oldest future, which
    posts the completion to the node's external queue."""

    supports_trace_ctx = True

    def __init__(self, clock):
        self.clock = clock
        self.pending: list = []

    def verify_signed(self, stx, hub, check_sufficient_signatures=True,
                      trace_ctx=None):
        fut: Future = Future()
        self.pending.append((fut, stx, hub, check_sufficient_signatures))
        return fut

    def finish_one(self) -> None:
        fut, stx, hub, check = self.pending.pop(0)
        self.clock.advance(PRICE["verify"])
        try:
            stx.verify(hub, check_sufficient_signatures=check)
        except Exception as e:      # thrown into the flow at its yield site
            fut.set_exception(e)
        else:
            fut.set_result(None)


def make_network(verifier=None):
    net = MockNetwork()
    notary = net.create_notary_node(validating=True)
    alice = net.create_node("O=Alice, L=London, C=GB")
    bob = net.create_node("O=Bob, L=Oslo, C=NO")
    net.start_nodes()
    for n in net.nodes:
        n.services.verifier_service = verifier
    return net, notary, alice, bob


def settle(net, clock, verifier=None, post_to_drain_s=2e-3):
    """Pump to quiescence; with a manual verifier, complete one future at a
    time, letting ``post_to_drain_s`` pass before the node's thread drains."""
    while True:
        net.bus.run_network()
        if verifier is not None and verifier.pending:
            verifier.finish_one()
            clock.advance(post_to_drain_s)
        drained = False
        for n in net.nodes:
            drained |= n.smm.drain_external()
        if not drained and not net.bus.pending_count() \
                and not (verifier is not None and verifier.pending):
            return


def pay(clock, verifier=None):
    """Issue to Alice, then Alice pays Bob: the spans of the payment only."""
    net, notary, alice, bob = make_network(verifier)
    fsm = alice.start_flow(CashIssueFlow(Amount(1000_00, USD), b"\x01",
                                         alice.party, notary.party))
    settle(net, clock, verifier)
    fsm.result_future.result(timeout=0)
    tracing.get_tracer().ring.clear()
    fsm = alice.start_flow(CashPaymentFlow(Amount(100_00, USD), bob.party))
    settle(net, clock, verifier)
    fsm.result_future.result(timeout=0)
    return tracing.get_tracer().spans()


def end(span) -> float:
    return span["start_s"] + span["duration_s"]


def covered(intervals) -> float:
    total, hi_seen = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        lo = max(lo, hi_seen)
        if hi > lo:
            total += hi - lo
            hi_seen = hi
    return total


# -- the tiling ----------------------------------------------------------------

@pytest.mark.parametrize("mode", ["sync_verify", "async_verify"])
def test_every_flow_run_is_tiled_by_its_named_children(clock, tracer, mode):
    verifier = ManualVerifier(clock) if mode == "async_verify" else None
    spans = pay(clock, verifier)
    runs = [s for s in spans if s["name"] == "flow.run"]
    assert len(runs) >= 4       # payer, notary service, fetch handler, payee
    kids: dict = {}
    for s in spans:
        kids.setdefault(s["parent_id"], []).append(s)
    for run in runs:
        assert run["duration_s"] > 0
        named = [c for c in kids.get(run["span_id"], ())
                 if c["name"] in ("flow.step", "flow.run")
                 or c["name"].startswith(("wait.", "session."))]
        got = covered((max(c["start_s"], run["start_s"]),
                       min(end(c), end(run))) for c in named)
        unnamed = run["duration_s"] - got
        assert unnamed < 0.05 * run["duration_s"], (
            run["tags"]["flow_type"], unnamed, run["duration_s"])


def test_every_span_carries_the_thread_that_recorded_it(clock, tracer):
    spans = pay(clock)
    assert spans and all(isinstance(s["thread"], str) and s["thread"]
                         for s in spans)
    me = threading.current_thread().name
    assert {s["thread"] for s in spans
            if s["name"].startswith(("flow.", "session.", "wait."))} == {me}
    seen = []
    worker = threading.Thread(
        target=lambda: seen.append(tracer.record("elsewhere")),
        name="not-the-node-thread")
    worker.start()
    worker.join()
    assert tracer.spans()[-1]["thread"] == "not-the-node-thread"
    with tracer.span("live") as sp:
        assert sp.thread == me
    # a finished span from an older worker has none: kept, not refused
    tracer.ingest({"trace_id": "t", "span_id": "s", "name": "worker.old"})
    assert tracer.spans()[-1]["thread"] is None


def test_one_step_span_per_advance_under_its_flow_run(clock, tracer,
                                                      monkeypatch):
    advances = []
    inner = statemachine.StateMachineManager._advance_inner

    def counted(self, fsm, *a, **kw):
        if fsm.generator is not None and not fsm.done:
            advances.append(fsm.run_id)
        return inner(self, fsm, *a, **kw)

    monkeypatch.setattr(statemachine.StateMachineManager, "_advance_inner",
                        counted)
    net, notary, alice, bob = make_network()
    alice.start_flow(CashIssueFlow(Amount(500_00, USD), b"\x02",
                                   alice.party, notary.party))
    settle(net, clock)
    spans = tracer.spans()
    runs = {s["span_id"]: s for s in spans if s["name"] == "flow.run"}
    steps = [s for s in spans if s["name"] == "flow.step"]
    assert len(steps) == len(advances) > 0
    requests = {"Send", "Receive", "SendAndReceive", "Verify", "VerifyMany",
                "AwaitFuture", "Sleep", "WaitForLedgerCommit"}
    last = {}
    for st in steps:
        run = runs[st["parent_id"]]
        assert st["tags"]["flow_type"] == run["tags"]["flow_type"]
        assert st["tags"]["exit"] in requests | {"done", "failed"}
        assert run["start_s"] <= st["start_s"]
        last[st["parent_id"]] = st
    assert {st["tags"]["exit"] for st in last.values()} == {"done"}


def test_a_step_that_signs_or_parks_carries_the_cost_as_tags(clock, tracer):
    spans = pay(clock)
    steps = [s for s in spans if s["name"] == "flow.step"]
    signed = [s for s in steps if "n_sign" in s["tags"]]
    # the payer signs its spend, the notary signs the id: one each, and the
    # cost rides on the step (no span of its own in the ring)
    kinds = sorted(s["tags"]["flow_type"].rsplit(".", 1)[-1] for s in signed)
    assert kinds == ["CashPaymentFlow", "NotaryServiceFlow"]
    for s in signed:
        assert s["tags"]["n_sign"] == 1
        assert s["tags"]["sign_s"] == pytest.approx(PRICE["sign"])
        assert s["tags"]["sign_s"] < s["duration_s"]
    parked = [s for s in steps if s["tags"]["exit"] not in ("done", "failed")]
    assert parked
    for s in parked:
        assert s["tags"]["checkpoint_s"] == pytest.approx(PRICE["checkpoint"])
    assert not any("checkpoint_s" in s["tags"] for s in steps
                   if s["tags"]["exit"] == "done")
    assert not any(s["name"] in ("sign", "checkpoint", "flow.sign",
                                 "flow.checkpoint") for s in spans)


def test_a_failing_flow_ends_its_last_step_failed(clock, tracer):
    class Doomed(FlowLogic):
        def call(self):
            yield Sleep(0.0)
            raise FlowException("doomed")

    net, _notary, alice, _bob = make_network()
    fsm = alice.start_flow(Doomed())
    net.advance_clock(1.0)
    assert isinstance(fsm.result_future.exception(timeout=0), FlowException)
    exits = [s["tags"]["exit"] for s in tracer.spans()
             if s["name"] == "flow.step"]
    assert exits == ["Sleep", "failed"]


# -- readiness: where a park ends and the wait for the thread begins -------------

class Parked(FlowLogic):
    """One park on ``request`` (built at call time), then done."""

    def __init__(self, make_request):
        self.make_request = make_request

    def call(self):
        yield self.make_request()
        return "resumed"


def _await_request(box):
    def producer():
        box["fut"] = Future()
        return box["fut"]
    return lambda: AwaitFuture(producer, purpose="notary.commit")


@pytest.mark.parametrize("kind,wait_name,wait_kind", [
    ("verify", "wait.verify_park", "verify.park"),
    ("verify_many", "wait.verify_gather", "verify.gather"),
    ("await_future", "wait.await_future", "notary.commit"),
])
def test_a_park_ends_when_the_completion_is_posted_and_runnable_begins_there(
        clock, tracer, kind, wait_name, wait_kind):
    verifier = ManualVerifier(clock)
    net, notary, alice, _bob = make_network(verifier)
    # something to verify: an issue, completed the slow way
    issue = alice.start_flow(CashIssueFlow(Amount(10_00, USD), b"\x03",
                                           alice.party, notary.party))
    settle(net, clock, verifier)
    stx = issue.result_future.result(timeout=0)
    tracer.ring.clear()
    box: dict = {}
    from corda_tpu.flows.api import Verify
    make = {"verify": lambda: Verify(stx),
            "verify_many": lambda: VerifyMany([stx, stx]),
            "await_future": _await_request(box)}[kind]
    fsm = alice.start_flow(Parked(make))
    t_park = clock.now
    clock.advance(0.010)                  # parked: the awaited thing runs
    if kind == "await_future":
        box["fut"].set_result(None)
    else:
        while verifier.pending:
            verifier.finish_one()
    t_ready = clock.now                   # the completion is posted here
    clock.advance(0.007)                  # the node's thread is elsewhere
    t_taken = clock.now
    assert alice.smm.drain_external()
    assert fsm.result_future.result(timeout=0) == "resumed"
    spans = tracer.spans()
    (wait,) = [s for s in spans if s["name"] == wait_name]
    assert wait["tags"]["wait_kind"] == wait_kind
    # from the park inside the step, before the step's checkpoint
    assert wait["start_s"] == pytest.approx(t_park - PRICE["checkpoint"])
    assert end(wait) == pytest.approx(t_ready)
    (runnable,) = [s for s in spans if s["name"] == "wait.runnable"]
    assert runnable["tags"] == {"wait_kind": "scheduler.runnable",
                                "source": "external"}
    assert runnable["start_s"] == pytest.approx(t_ready)
    assert end(runnable) == pytest.approx(t_taken)
    assert runnable["parent_id"] == wait["parent_id"]
    resumed = [s for s in spans if s["name"] == "flow.step"][-1]
    assert resumed["start_s"] == pytest.approx(t_taken)
    assert component_of(runnable) == "scheduler.wait"


class Ask(FlowLogic):
    def __init__(self, peer):
        self.peer = peer

    def call(self):
        yield Send(self.peer, "ping")
        answer = yield Receive(self.peer, str)
        return answer.unwrap(lambda x: x)


Ask = initiating_flow(Ask)


def _answering(peer):
    class Answer(FlowLogic):
        def call(self):
            got = yield Receive(peer, str)
            yield Send(peer, got.unwrap(lambda x: x) + "-pong")
    return Answer()


def test_runnable_source_message_and_the_three_spans_end_to_end(clock,
                                                                tracer):
    net, _notary, alice, bob = make_network()
    bob.smm.register_flow_factory(flow_name(Ask), _answering)
    fsm = alice.start_flow(Ask(bob.party))
    sent_at = clock.now
    while net.bus.pending_count():
        clock.advance(0.003)          # every message waits 3 ms in its queue
        net.bus.run_network(rounds=1)
    assert fsm.result_future.result(timeout=0) == "ping-pong"
    spans = tracer.spans()
    waits = [s for s in spans if s["name"] == "wait.runnable"]
    receives = [s for s in spans if s["name"] == "session.receive"]
    assert len(waits) == len(receives) > 0
    assert {s["tags"]["source"] for s in waits} == {"message"}
    first = min(waits, key=lambda s: s["start_s"])
    assert first["start_s"] == pytest.approx(sent_at, abs=PRICE["serialize"])
    runs = {s["span_id"] for s in spans if s["name"] == "flow.run"}
    steps = [s for s in spans if s["name"] == "flow.step"]
    for w, r in zip(sorted(waits, key=end),
                    sorted(receives, key=lambda s: s["start_s"])):
        assert w["duration_s"] >= 0.003 - 1e-9
        assert end(w) == pytest.approx(r["start_s"])     # taken up here
        assert w["parent_id"] == r["parent_id"] and r["parent_id"] in runs
        assert r["duration_s"] == pytest.approx(PRICE["deserialize"])
        assert r["tags"]["bytes"] > 0
        # closed before the flow it wakes is stepped
        assert not any(st["start_s"] < end(r) and end(st) > r["start_s"]
                       for st in steps)
    # the responder is not born when its first message waits: that wait and
    # that receive hang under the sender's flow.run, beside the responder's
    init = min(receives, key=lambda s: s["start_s"])
    assert init["tags"]["kind"] == "SessionInit"
    responder = next(s for s in spans if s["name"] == "flow.run"
                     and s["tags"]["flow_type"].endswith("Answer"))
    assert init["parent_id"] == responder["parent_id"]
    assert responder["start_s"] >= end(init)


def test_session_send_is_a_real_span_under_the_running_step(clock, tracer):
    net, _notary, alice, bob = make_network()
    bob.smm.register_flow_factory(flow_name(Ask), _answering)
    alice.start_flow(Ask(bob.party))
    settle(net, clock)
    spans = tracer.spans()
    steps = {s["span_id"]: s for s in spans if s["name"] == "flow.step"}
    runs = {s["span_id"]: s for s in spans if s["name"] == "flow.run"}
    sends = [s for s in spans if s["name"] == "session.send"]
    assert {s["tags"]["kind"] for s in sends} >= {
        "SessionInit", "SessionConfirm", "SessionData", "NormalSessionEnd"}
    for s in sends:
        assert s["duration_s"] == pytest.approx(PRICE["serialize"])
        assert s["tags"]["bytes"] > 0
        if s["tags"]["kind"] == "SessionConfirm":
            # sent as the responder is born, before its first step
            assert s["parent_id"] in runs
        else:
            step = steps[s["parent_id"]]
            assert step["start_s"] <= s["start_s"]
            assert end(s) <= end(step) + 1e-9


def test_runnable_source_timer_starts_at_the_deadline(clock, tracer):
    class Nap(FlowLogic):
        def call(self):
            yield Sleep(10.0)
            return "woke"

    net, _notary, alice, _bob = make_network()
    fsm = alice.start_flow(Nap())
    assert net.advance_clock(12.5) == 1       # 2.5 s after the deadline
    assert fsm.result_future.result(timeout=0) == "woke"
    (runnable,) = [s for s in tracer.spans() if s["name"] == "wait.runnable"]
    assert runnable["tags"]["source"] == "timer"
    assert runnable["duration_s"] == pytest.approx(2.5)
    assert end(runnable) == pytest.approx(clock.now)


# -- tracing off costs what it cost ----------------------------------------------

def test_with_the_noop_tracer_a_payment_builds_no_span(clock, monkeypatch):
    disable_tracing()
    built = []
    real_init = tracing.Span.__init__

    def spy(self, *a, **kw):
        built.append(a)
        real_init(self, *a, **kw)

    monkeypatch.setattr(tracing.Span, "__init__", spy)
    posted = []
    real_put = statemachine.queue.Queue.put

    def put(self, item, *a, **kw):
        posted.append(item)
        return real_put(self, item, *a, **kw)

    monkeypatch.setattr(statemachine.queue.Queue, "put", put)
    verifier = ManualVerifier(clock)
    net, notary, alice, bob = make_network(verifier)
    fsm = alice.start_flow(CashIssueFlow(Amount(10_00, USD), b"\x04",
                                         alice.party, notary.party))
    assert fsm.step_span is None
    settle(net, clock, verifier)
    fsm.result_future.result(timeout=0)
    fsm = alice.start_flow(CashPaymentFlow(Amount(5_00, USD), bob.party))
    settle(net, clock, verifier)
    fsm.result_future.result(timeout=0)
    assert built == []
    # completions are queued without a ready stamp, messages without one
    stamps = [item[1] for item in posted if isinstance(item, tuple)]
    assert stamps and set(stamps) == {None}
    assert all(t.message.ready_s is None and t.message.trace is None
               for t in net.bus.delivered_log)


# -- the small repairs ------------------------------------------------------------

def test_kernel_compile_span_starts_before_it_ends(tracer, monkeypatch):
    fake = FakeClock()
    from corda_tpu.observability import profiling
    monkeypatch.setattr(profiling, "time", fake)

    def kernel(a):
        fake.advance(4.0)             # the "compile"
        return a

    t0 = fake.now
    KernelProfiler().call("slow_to_build", kernel, np.zeros(3), capacity=8)
    (span,) = [s for s in tracer.spans() if s["name"] == "kernel.compile"]
    assert span["start_s"] == pytest.approx(t0)
    assert span["duration_s"] == pytest.approx(4.0)
    assert end(span) == pytest.approx(fake.now)


def test_flush_and_dispatch_say_which_route_they_took(tracer):
    from corda_tpu.core.crypto import Crypto, generate_keypair
    from corda_tpu.verifier.batcher import SignatureBatcher
    kp = generate_keypair(entropy=b"\x31" * 32)
    msg = b"\x07" * 32
    sig = Crypto.sign_with_key(kp, msg)
    batcher = SignatureBatcher()      # one row: far below the host crossover
    try:
        parent = tracer.record("test.root")
        assert batcher.submit(kp.public, sig.bytes, msg,
                              ctx=parent).result(timeout=60) is True
    finally:
        batcher.close()
    flush = next(s for s in tracer.spans() if s["name"] == "batcher.flush")
    dispatch = next(s for s in tracer.spans()
                    if s["name"] == "batcher.dispatch")
    assert flush["tags"]["route"] == dispatch["tags"]["route"] == "host"
    assert flush["thread"] == dispatch["thread"] != \
        threading.current_thread().name


def test_span_ids_stay_unique_and_sixteen_hex_across_threads(tracer):
    def burst():
        for _ in range(500):
            tracer.record("burst")

    threads = [threading.Thread(target=burst) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tracer.spans()
    ids = [s["span_id"] for s in spans] + [s["trace_id"] for s in spans]
    assert len(set(ids)) == len(ids) == 4000
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)
    # one process, one prefix: ids differ in the counter alone
    assert len({i[:8] for i in ids}) == 1
